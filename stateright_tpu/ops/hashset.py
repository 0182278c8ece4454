"""Device-resident visited set: open addressing over uint32 limb pairs.

The TPU counterpart of the reference BFS's sharded concurrent
``DashMap`` visited set (bfs.rs:28-29): a fixed-capacity power-of-two
table of 64-bit fingerprints stored as two ``uint32`` arrays (0,0 =
empty — fingerprints are never zero), with batched insert-if-absent.

Batched insertion resolves conflicts — including DUPLICATE keys within
one batch — without atomics: each still-active key reads its slot; on
empty it *claims* via ``scatter-max`` of its row index into a claim
array, then re-reads to learn the winner; a claim loser re-reads
before moving (the winner may hold its own key), and occupied-by-other
keys advance their private triangular probe sequence. This is the
classic GPU model-checker table insert (cf. GPUexplore), expressed as
XLA scatter/gather. (:func:`sort_unique` remains available for callers
that want an explicit pre-dedup pass.)

NOTE: on TPU hardware, XLA lowers these scatters poorly (~50x slower
than sorts at equal row counts — see checkers/tpu_sortmerge.py, which
is the TPU-preferred dedup built on sorts instead).

Everything is functional: ``insert`` returns the new table arrays.
The probe loop is a static Python loop (PROBE_ROUNDS is small) so XLA
unrolls and fuses it.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

PROBE_ROUNDS = 24


class DeviceHashSet(NamedTuple):
    """Table state (a pytree — pass through jit freely)."""

    lo: Any  # uint32[capacity]
    hi: Any  # uint32[capacity]

    @staticmethod
    def empty(capacity: int, xp) -> "DeviceHashSet":
        if capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two: {capacity}")
        return DeviceHashSet(
            xp.zeros(capacity, dtype=xp.uint32),
            xp.zeros(capacity, dtype=xp.uint32),
        )

    @property
    def capacity(self) -> int:
        return self.lo.shape[0]


def _slot_hash(key_lo, key_hi, mask, xp):
    # Cheap avalanche of the already-mixed fingerprint into a slot.
    x = key_lo ^ (key_hi * xp.uint32(0x9E3779B9))
    x = x ^ (x >> xp.uint32(16))
    return x & mask


def sort_unique(key_lo, key_hi, xp):
    """Sort keys by (hi, lo) and mark the first occurrence of each.

    Returns ``((sorted_lo, sorted_hi, order), unique_mask)``: the keys
    in sorted order, the permutation ``order`` that produced them
    (gather other per-key arrays with it), and ``unique_mask[i]`` True
    iff sorted position i is the first of its key. Invalid entries
    should be pre-set to the all-ones key so they sort last (and
    collapse into one dup run).
    """
    n = key_lo.shape[0]
    idx = xp.arange(n, dtype=xp.uint32)
    if xp.__name__.startswith("jax"):
        import jax

        sorted_hi, sorted_lo, order = jax.lax.sort(
            (key_hi, key_lo, idx), num_keys=2
        )
    else:
        perm = xp.lexsort((key_lo, key_hi))
        sorted_hi, sorted_lo, order = key_hi[perm], key_lo[perm], idx[perm]
    prev_same = xp.zeros(n, dtype=bool)
    if n > 1:
        same = (sorted_hi[1:] == sorted_hi[:-1]) & (
            sorted_lo[1:] == sorted_lo[:-1]
        )
        prev_same = xp.concatenate([xp.zeros(1, dtype=bool), same])
    return (sorted_lo, sorted_hi, order), ~prev_same


def insert(
    table: DeviceHashSet,
    key_lo: Any,
    key_hi: Any,
    active: Any,
    xp,
    rounds: int = PROBE_ROUNDS,
) -> Tuple[DeviceHashSet, Any, Any, Any]:
    """Insert keys where ``active``; return
    ``(new_table, is_new, overflow, slot)``.

    ``is_new[i]`` — key i was inserted (absent before); ``overflow[i]``
    — probing exhausted without a slot (caller must grow + retry);
    ``slot[i]`` — the table index key i landed at (inserted or already
    present; undefined for inactive/overflowed rows). Slots let callers
    keep side tables indexed by table position — the engine stores the
    parent fingerprint of each visited state this way, so the whole
    parent forest stays device-resident (bfs.rs:28-29 equivalent).

    The batch may contain DUPLICATE keys: every row keeps its own
    probe position along the deterministic triangular sequence for its
    key, and a row that loses a claim race re-reads its slot before
    moving on — so of N rows with one key, exactly one reports
    ``is_new`` (if absent) and the rest find the winner's entry. This
    is what lets the engines skip a whole sort-unique pass per wave.
    """
    if xp.__name__.startswith("jax"):
        return _insert_jax(table, key_lo, key_hi, active, rounds)
    n = key_lo.shape[0]
    mask = xp.uint32(table.capacity - 1)
    row_ids = xp.arange(n, dtype=xp.uint32)
    idx = _slot_hash(key_lo, key_hi, mask, xp)
    probe = xp.zeros(n, dtype=xp.uint32)
    lo, hi = table.lo, table.hi
    lo, hi = lo.copy(), hi.copy()  # keep numpy path functional too
    inserted = xp.zeros(n, dtype=bool)
    overflow = xp.zeros(n, dtype=bool)
    slot = xp.zeros(n, dtype=xp.uint32)
    pending = active
    # Each round with pending rows makes progress, so n + rounds bounds
    # the loop; a key overflows when its probe DEPTH exhausts `rounds`
    # (claim-loser re-read rounds don't eat the probe budget).
    for _ in range(rounds + n):
        if not pending.any():
            break
        slot_lo = lo[idx]
        slot_hi = hi[idx]
        is_empty = (slot_lo == 0) & (slot_hi == 0)
        is_match = (slot_lo == key_lo) & (slot_hi == key_hi)
        slot = xp.where(pending & is_match, idx, slot)
        pending = pending & ~is_match
        # Claim empty slots: scatter-max row ids, winners re-read.
        want = pending & is_empty
        claims = xp.zeros(table.capacity, dtype=xp.uint32)
        import numpy as np

        np.maximum.at(
            claims, idx, xp.where(want, row_ids + 1, xp.uint32(0))
        )
        won = want & (claims[idx] == row_ids + 1)
        lo[idx[won]] = key_lo[won]
        hi[idx[won]] = key_hi[won]
        inserted = inserted | won
        slot = xp.where(won, idx, slot)
        pending = pending & ~won
        # Advance only rows that saw a different key; claim losers
        # re-read (the winner may hold their own key). Each row steps
        # its own triangular sequence so a key's probe path never
        # depends on batch contention.
        advance = pending & ~is_empty & ~is_match
        probe = xp.where(advance, probe + 1, probe)
        idx = xp.where(advance, (idx + probe) & mask, idx)
        exhausted = pending & (probe >= rounds)
        overflow = overflow | exhausted
        pending = pending & ~exhausted
    return DeviceHashSet(lo, hi), inserted, overflow | pending, slot


def _insert_jax(
    table: DeviceHashSet, key_lo: Any, key_hi: Any, active: Any, rounds: int
) -> Tuple[DeviceHashSet, Any, Any, Any]:
    """Device insert: the probe rounds run in a ``lax.while_loop`` that
    exits as soon as no key is pending. At sane load factors (<50%)
    nearly every batch resolves within 2-4 rounds, so this costs a
    fraction of a fixed ``rounds``-times-unrolled loop; ``rounds`` is
    the safety bound whose exhaustion reports overflow."""
    import jax.numpy as jnp
    from jax import lax

    n = key_lo.shape[0]
    cap = table.capacity
    mask = jnp.uint32(cap - 1)
    row_ids = jnp.arange(n, dtype=jnp.uint32)

    def cond(c):
        # Every round with pending rows makes progress (an insertion,
        # a match, or a probe advance), so n + rounds bounds the loop;
        # per-key overflow is governed by probe DEPTH below, not by
        # the iteration count — claim-loser re-read rounds don't eat
        # a key's probe budget.
        return (c["r"] < rounds + n) & jnp.any(c["pending"])

    def body(c):
        lo, hi, idx, pending = c["lo"], c["hi"], c["idx"], c["pending"]
        slot_lo = lo[idx]
        slot_hi = hi[idx]
        is_empty = (slot_lo == 0) & (slot_hi == 0)
        is_match = (slot_lo == key_lo) & (slot_hi == key_hi)
        newly_found = pending & is_match
        slot = jnp.where(newly_found, idx, c["slot"])
        pending = pending & ~is_match
        # Claim empty slots: scatter-max row ids, winners re-read.
        want = pending & is_empty
        claims = jnp.zeros(cap, dtype=jnp.uint32).at[idx].max(
            jnp.where(want, row_ids + 1, jnp.uint32(0))
        )
        won = want & (claims[idx] == row_ids + 1)
        # Only winners write; losers scatter out of range (dropped).
        # A plain at[idx].set with stale values for losers would race
        # the winner's write at duplicate indices.
        write_idx = jnp.where(won, idx, jnp.uint32(cap))
        lo = lo.at[write_idx].set(key_lo, mode="drop")
        hi = hi.at[write_idx].set(key_hi, mode="drop")
        # Advance only rows that saw a DIFFERENT key; claim losers
        # re-read their slot next round (the winner may hold their own
        # key — that's how duplicate keys within a batch resolve).
        # Per-row probe counters keep each key's triangular sequence
        # deterministic regardless of contention, so later inserts and
        # contains() retrace the same path.
        pending = pending & ~won
        advance = pending & ~is_empty & ~is_match
        probe = jnp.where(advance, c["probe"] + 1, c["probe"])
        # A key whose probe depth exhausts `rounds` overflows and
        # leaves the pending set (reported to the caller).
        exhausted = pending & (probe >= rounds)
        return dict(
            lo=lo,
            hi=hi,
            idx=jnp.where(advance, (idx + probe) & mask, idx),
            probe=probe,
            pending=pending & ~exhausted,
            overflow=c["overflow"] | exhausted,
            inserted=c["inserted"] | won,
            slot=jnp.where(won, idx, slot),
            r=c["r"] + 1,
        )

    init = dict(
        lo=table.lo,
        hi=table.hi,
        idx=_slot_hash(key_lo, key_hi, mask, jnp),
        probe=jnp.zeros(n, dtype=jnp.uint32),
        pending=active,
        overflow=jnp.zeros(n, dtype=bool),
        inserted=jnp.zeros(n, dtype=bool),
        slot=jnp.zeros(n, dtype=jnp.uint32),
        r=jnp.int32(0),
    )
    out = lax.while_loop(cond, body, init)
    return (
        DeviceHashSet(out["lo"], out["hi"]),
        out["inserted"],
        out["overflow"] | out["pending"],
        out["slot"],
    )


def contains(
    table: DeviceHashSet, key_lo: Any, key_hi: Any, xp,
    rounds: int = PROBE_ROUNDS,
) -> Any:
    """Membership probe (no mutation)."""
    mask = xp.uint32(table.capacity - 1)
    idx = _slot_hash(key_lo, key_hi, mask, xp)
    found = xp.zeros(key_lo.shape, dtype=bool)
    missing = xp.zeros(key_lo.shape, dtype=bool)
    done = xp.zeros(key_lo.shape, dtype=bool)
    for r in range(rounds):
        slot_lo = table.lo[idx]
        slot_hi = table.hi[idx]
        is_empty = (slot_lo == 0) & (slot_hi == 0)
        is_match = (slot_lo == key_lo) & (slot_hi == key_hi)
        found = found | (~done & is_match)
        missing = missing | (~done & is_empty)
        done = done | is_match | is_empty
        idx = (idx + xp.uint32(r + 1)) & mask
    return found
