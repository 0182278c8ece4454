"""The adaptive sort-merge wave engine: dedup without scatters, sized
to the running wave.

TPU microbenchmarks (v5e, round 2; re-runnable at current shapes via
``tools/profile_stages.py --micro``) show the hash
table engine's cost profile is inverted on TPU hardware: arbitrary-
index scatter/gather — the heart of GPU-style open-addressing
(ops/hashset.py) — runs ~10ns/row, and a 21-step binary search over a
sorted 2M-row array costs 2,085ms for 4M queries (sequential gathers),
while ``lax.sort`` moves 4M 3-lane rows in 6.5ms. XLA:TPU lowers
scatters to serialized updates; sorts are native and fast. So this
engine architects the wave around sorts, the classic vector-machine
model-checking layout:

* The visited set is a **sorted fingerprint array** (two uint32 limb
  lanes, all-ones sentinel padding), not a hash table — and since
  round 10 it is kept **incrementally sorted**: every wave merges its
  winners into the sorted prefix with a streaming linear merge
  (``ops/merge.py`` — a Pallas kernel on chip, a sort-free XLA
  implementation on the CPU), so no per-wave pass ever sorts O(C) rows.
* Per wave: vmap-expand the frontier → fingerprint candidates →
  compact the valid candidates (tiled top-B sorts) → ONE B-row
  candidate order sort + a streaming membership pass against the
  sorted visited prefix (visited wins ties; intra-wave duplicates
  resolve on the adjacent-equal check, first-of-buffer-order wins) →
  linear-merge the ≤F winner keys into the visited prefix → compact
  the new states into the next frontier.
* The parent forest is an **append-only device log** of
  (child, parent) fingerprint pairs written with
  ``dynamic_update_slice`` — contiguous writes, no scatter — drained
  lazily on the host only when a counterexample path is reconstructed.

**Adaptive wave sizing (round 3).** The round-2 engine compiled ONE
wave program at worst-case shapes, so every wave paid peak cost: the
2pc rm=8 profile showed a flat ~365ms/wave whether the wave produced
2 or 244,342 new states (the round-2 wave profile; per-wave walls now
come from ``--trace=deep`` + tools/latency_report.py), dominated by a
22M-row sort over the full F×K candidate tensor and a 4M-row payload
gather. This engine instead compiles a LADDER of wave-body variants
and dispatches per wave with ``lax.switch`` — still inside the
device-resident ``lax.while_loop``, so the host still syncs only once
per chunk:

* **frontier class** — the frontier is always a compacted prefix, so
  a wave with n live rows runs the smallest variant with F_c ≥ n:
  expansion, fingerprinting, and candidate compaction all scale with
  the running wave, not the worst one.
* **visited class** — the visited array is sorted with sentinel
  padding, so only the prefix holding the current unique count needs
  to participate in the merge; the merge stage is a nested switch
  over visited-prefix sizes.
* **tiling** — within a class, candidate compaction runs as NT
  per-tile top-B sorts (lax.sort is superlinear: 22M rows cost 109ms
  where 16×1.4M cost ~40ms).
* **full-flat mode** — when the class's F×K×W successor tensor fits
  the memory budget it is kept alive through the merge, and only the
  ≤F winning rows are gathered at the end of the wave (the round-2
  engine gathered all B candidate payloads every wave: ~10ns/row ≈
  40ms/wave at rm=8). Classes too big for the budget fall back to
  per-tile payload gathers.

Everything else — the device-resident multi-wave ``lax.while_loop``,
packed-stats chunk sync, properties/EventuallyBits/discovery logic —
is shared with :mod:`stateright_tpu.checkers.tpu`.

**Transposed resident layout + collapsed ladder carries (round 9,
PERF.md §layout).** Resident state lives COLUMN-major:

* the frontier is ``uint32[W, F]`` (minor dim = rows, so the
  T(8,128) tile tax on every elementwise/fold pass — and on every
  carry copy the class-ladder switches materialize — vanishes; the
  fingerprint fold measured 1.65x col-major on chip),
* the visited keys are one SoA block ``vkeys: uint32[2, C_pad]``
  (lane 0 = lo limb, lane 1 = hi limb), rows ``[0, new)`` a dense
  SORTED prefix of real keys (round 10),
* the parent log is ``plog: uint32[4, L]`` — parent limbs in lanes
  0-1, child limbs in lanes 2-3. (Round 9 derived the children from
  ``vkeys`` at drain — the visited append then WAS the insertion
  order; the round-10 sorted merge re-orders the visited rows every
  wave, so the log carries the child keys again. The two extra lanes
  exist only when ``track_paths`` allocates the log at all; the
  headline perf lanes run paths-off with ``L = 0``.)

**Incrementally sorted visited + streaming merge (round 10, PERF.md
§merge-kernel).** Rounds 5-9 kept the visited array append-only and
unsorted, paying a from-scratch ``(V_v + B)``-row stable 3-lane
``lax.sort`` every wave to dedup — the irreducible b·V floor the
wave-wall work kept exposing. The sorted invariant replaces that
rebuild with B-scale sorts plus two O(V + B) streaming passes:

* ONE ``B_eff``-row stable sort orders the wave's candidates (the
  only place candidate keys are sorted; the tiled compaction's
  per-tile sorts remain, but nothing re-sorts visited rows),
* membership rides a streaming pass over the sorted visited prefix
  (``ops.merge.member_sorted`` — Pallas linear merge on chip, 2-limb
  binary search on the XLA implementation), and intra-wave duplicates
  resolve on the adjacent-equal check of the sorted candidates
  (stable sort ⇒ lowest buffer position wins, matching the old
  stable-concat winner exactly),
* the visited append is a linear merge of the ≤F sorted winner keys
  into the prefix (``ops.merge.merge_sorted``), written back as one
  class-local ``dynamic_update_slice`` block under the v-class
  switch.

The ``merge_impl`` knob (None = auto: Pallas on TPU, XLA on the CPU;
``"pallas_interpret"`` runs the same kernel under the
Pallas interpreter so tier-1 pins it on CPU) selects the
implementation; it is cache-keyed and recorded in lane configs and
bench provenance.

Boundary transposes happen only at host upload/download and at the
table-gather seams where row-major genuinely wins (PERF.md §gathers:
payload gathers measured equal either way, so gather staging keeps
``[N, W]`` rows; the per-wave ``frontier_t.T`` feeding the pair-step
row gathers is the one sanctioned seam copy).

The (f, v) class ladder no longer copies full carry tuples between
branches: the v-class switch runs the B-scale membership pass (its
only branch output is a ``bool[B_eff]`` mask), ONE fetch-class switch
per wave updates frontier/ebits/plog with class-local
``dynamic_update_slice`` blocks, a second v-class switch merges the
winner keys into ``vkeys`` (its only branch output is the updated
``vkeys`` buffer), and the next carry is assembled outside any
switch. The ``carry-copy-bytes`` lint rule (GATED,
analysis/tables.py budgets) pins the collapse.
"""

from __future__ import annotations

import numpy as np

from ..encoding import (
    SparseEncodedModel,
    has_trivial_boundary,
    pair_step_seam,
    within_boundary_cols,
)
from ..model import Expectation
from ..ops.bitmask import mask_words
from ..ops.fingerprint import fingerprint_u32v, fingerprint_u32v_t
from ..ops.merge import (
    compact_winners,
    member_sorted,
    merge_sorted,
    resolve_impl,
)
from ..ops.u64 import U64, u64_add
from .tpu import (
    TpuBfsChecker,
    _monitor_snapshot,
    discovery_update,
    expand_frontier,
    frontier_props_t,
)

_SENT = 0xFFFFFFFF

#: compiled TIERED chunk programs (stateright_tpu/tier.py), keyed by
#: the untiered program identity + the tier marker — a separate cache
#: from tpu._CHUNK_CACHE so tiered builds never touch the untiered
#: entries (or their _build_info/_carry_pspecs riders).
_TIER_CACHE: dict = {}


def payload_width(W: int, track_paths: bool) -> int:
    """Lanes of the packed candidate payload (see payload_pack)."""
    return W + 3 + (2 if track_paths else 0)


def payload_pack(jnp, state, key_lo, key_hi, ebits, par_lo=None,
                 par_hi=None):
    """THE single-chip packed-payload lane layout:
    ``[state 0:W | key_lo W | key_hi W+1 | ebits W+2 | par_lo W+3 |
    par_hi W+4]`` — every SINGLE-CHIP pack site and fetch unpack goes
    through this pair so those call sites can't drift (round-5 review
    finding). The sharded engine's routed destination tiles use a
    DIFFERENT lane order and their own named helper
    (parallel/engine_sortmerge.py ``dest_tile_pack``) — the two
    layouts never meet: this one is unpacked by :func:`payload_unpack`
    at the merge fetch, that one by the post-shuffle merge."""
    parts = [state, key_lo[:, None], key_hi[:, None], ebits[:, None]]
    if par_lo is not None:
        parts += [par_lo[:, None], par_hi[:, None]]
    return jnp.concatenate(parts, axis=1)


def payload_unpack(p, W: int, track_paths: bool):
    """Inverse of payload_pack, in the merge-fetch return order:
    ``(state, par_lo, par_hi, ebits, key_lo, key_hi)``."""
    return (
        p[:, :W],
        p[:, W + 3] if track_paths else None,
        p[:, W + 4] if track_paths else None,
        p[:, W + 2],
        p[:, W],
        p[:, W + 1],
    )


def _ladder(lo: int, hi: int, step: int) -> list[int]:
    """Geometric size ladder [min(lo,hi), ..., hi] with ratio `step`."""
    vals = []
    v = min(lo, hi)
    while v < hi:
        vals.append(v)
        v *= step
    vals.append(hi)
    return vals


def _divisor_at_least(n: int, want: int) -> int:
    """Smallest divisor of n that is ≥ want (≤ n)."""
    d = max(min(want, n), 1)
    while n % d:
        d += 1
    return d


def frontier_enabled_bits(enc, frontier_t, fval_f, expand, *,
                          mask_budget_cells, n_rows=None, pv=None,
                          ample_words=None):
    """The enabled-bitmap pass of :func:`sparse_pair_candidates` —
    per-row packed ``uint32[F_f, L]`` words plus per-row enabled
    counts over the transposed ``[W, F]`` block, tiled through a
    ``fori_loop`` when ``F_f * K`` exceeds the mask-cell budget (so
    the dense ``[F, K]`` bool never materializes at large F).

    ONE home shared with tools/profile_stages.py's mask stage, the
    same way ``encoding.pair_step_seam`` is the one home of the pair
    gather seam: a mask-path change that lands here is the pipeline
    the profiler times, by construction — no hand-synced mirror to
    drift. ``pv`` marks loop-carry seeds shard-varying under
    ``shard_map`` (identity otherwise).

    ``ample_words`` (off by default) is a host-constant packed
    ``uint32[L]`` ample-set mask (ops/canonical.py companion: the
    partial-order-reduction filter the encoding precomputes,
    ``ample_mask_host``): the filter is ONE word-AND folded into this
    pass — slots outside the ample set never reach the peel, the
    compaction, or the candidate counts. The encoding owns the
    soundness argument for its mask; engines only apply it."""
    import jax.numpy as jnp
    from jax import lax

    from ..encoding import enabled_bits_cols, enabled_mask_cols
    from ..ops.bitmask import mask_to_words, popcount_words

    if pv is None:
        pv = lambda x: x  # noqa: E731 — identity outside shard_map
    W = frontier_t.shape[0]
    F_f = int(n_rows) if n_rows is not None else frontier_t.shape[1]
    K = enc.max_actions
    L = mask_words(K)
    bits_fn = getattr(enc, "enabled_bits_vec", None)
    aw = (None if ample_words is None
          else jnp.asarray(np.asarray(ample_words, np.uint32)))

    def mask_bits(tf_t, tfv):
        if bits_fn is not None:
            tb = enabled_bits_cols(enc, tf_t)
            tb = jnp.where(expand, tb, jnp.uint32(0))
            tb = jnp.where(tfv[:, None], tb, jnp.uint32(0))
            if aw is not None:
                tb = tb & aw[None, :]
            return tb, popcount_words(jnp, tb)
        m = enabled_mask_cols(enc, tf_t)
        m = m & tfv[:, None] & expand
        w = mask_to_words(jnp, m)
        if aw is not None:
            # counts must see the filtered bitmap too
            w = w & aw[None, :]
            return w, popcount_words(jnp, w)
        tc = jnp.sum(m, axis=1, dtype=jnp.uint32)
        return w, tc

    if F_f * K > mask_budget_cells:
        NTm = _divisor_at_least(F_f, -(-F_f * K // mask_budget_cells))
        Tm = F_f // NTm

        def mtile(ti, acc):
            bits_a, cnt_a = acc
            off = ti * Tm
            tf_t = lax.dynamic_slice(frontier_t, (0, off), (W, Tm))
            tfv = lax.dynamic_slice(fval_f, (off,), (Tm,))
            tb, tc = mask_bits(tf_t, tfv)
            bits_a = lax.dynamic_update_slice(bits_a, tb, (off, 0))
            cnt_a = lax.dynamic_update_slice(cnt_a, tc, (off,))
            return bits_a, cnt_a

        return lax.fori_loop(
            0,
            NTm,
            mtile,
            (
                pv(jnp.zeros((F_f, L), jnp.uint32)),
                pv(jnp.zeros(F_f, jnp.uint32)),
            ),
        )
    # untiled: the class view fuses into the elementwise mask pass
    # (no loop operand, so no materialized copy)
    return mask_bits(frontier_t[:, :F_f], fval_f)


def sparse_pair_candidates(enc, frontier_t, fval_f, expand, *, EV, B_p,
                           NT, T, mask_budget_cells, Ba,
                           axis_name=None, n_rows=None,
                           ample_words=None, compact=False):
    """The sparse-dispatch pair pipeline, shared by the single-chip and
    sharded sort-merge engines (PERF.md §sparse): per-slot enabled
    mask → per-row bitmaps (tiled so the [F, K] bool mask never
    materializes at large F) → lowest-set-bit peel into ≤EV slots per
    row → tiled 1-lane packed-append compaction into a [Ba] buffer of
    pair indices.

    ``frontier_t`` is the TRANSPOSED resident block ``uint32[W, F_f]``
    (PERF.md §layout): the enabled predicate batches over axis 1
    (``enabled_bits_cols`` — per-state lane reads become contiguous
    row slices of the [W, N] block) and everything downstream of the
    bitmap is row-count-indexed exactly as before.

    ``n_rows`` lets an engine pass its FULL resident ``[W, F]``
    buffer with the class width F_f given explicitly: a column-prefix
    slice of the transposed layout is STRIDED, and a strided slice
    that becomes a ``fori_loop`` operand (the tiled mask loop below)
    forces XLA to materialize a per-wave copy of the whole class
    prefix — the full carry buffer aliases for free. Tile slices are
    taken from the full buffer at class-bounded offsets; only the
    untiled elementwise mask pass (which fuses) sees a sliced view.

    Encodings that build the packed words directly
    (``enabled_bits_vec`` — the compiled actor codegen) skip the dense
    ``bool[K]`` mask entirely: the engine consumes ``uint32[L]`` rows
    and counts by popcount, so no [tile, K] bool tensor exists even
    per tile (PERF.md §ordered: the compiled mask tax).

    Returns ``(pidx[Ba], live[Ba], pslot[Ba], cnt[F_f], n_pairs,
    pair_ovf, tile_max)`` — ``pair_ovf`` is True when a row enabled
    more than EV slots or the wave enabled more than B_p pairs.

    ``compact`` forces the packed-append compaction where the class's
    ``F_f * EV`` pair grid already fits the budget (the mesh engine
    sizes its pair step by the live pairs, so it wants them as a dense
    prefix in every class); the live pairs keep their pair-index
    order either way.

    Codegen contract (pinned by ``pytest -m lint`` /
    tools/lint_kernels.py for every registered encoding, in BOTH
    invocation styles — this direct call and the sharded engine's
    ``axis_name="shard"`` call under ``shard_map``): no dense
    ``[F, K]`` bool anywhere, no gather anywhere; the bitmap
    predicate, peel, and packed-append compaction are elementwise +
    sort only (stateright_tpu/analysis/).
    """
    import jax.numpy as jnp
    from jax import lax

    F_f = int(n_rows) if n_rows is not None else frontier_t.shape[1]
    K = enc.max_actions
    L = mask_words(K)
    NPg = F_f * EV
    compaction = compact or NPg > B_p

    def pv(x):
        """Inside shard_map, fori_loop carries seeded from constants
        are 'unvarying' while the body outputs vary per shard — mark
        the seeds as shard-varying to keep carry types equal."""
        if axis_name is None:
            return x
        return lax.pcast(x, axis_name, to="varying")

    bits, cnt = frontier_enabled_bits(
        enc, frontier_t, fval_f, expand,
        mask_budget_cells=mask_budget_cells, n_rows=n_rows, pv=pv,
        ample_words=ample_words,
    )
    n_pairs = jnp.sum(cnt, dtype=jnp.uint32)
    pair_ovf = jnp.any(cnt > jnp.uint32(EV)) | (
        n_pairs > jnp.uint32(B_p)
    )

    # Peel the lowest set bit per row, EV times — pure elementwise
    # [F, L] passes plus a min-reduce along L (argmax/take_along_axis
    # formulations lower to slow gathers on TPU: measured
    # ~6ms/iteration vs <0.5ms for this form at F=2^18, L=9).
    lane_base = (
        jnp.arange(L, dtype=jnp.uint32) * jnp.uint32(32)
    )[None, :]
    lanes = bits
    slot_cols, val_cols = [], []
    for _ in range(EV):
        low = lanes & (jnp.uint32(0) - lanes)
        pos = lax.population_count(low - jnp.uint32(1))
        cand = jnp.where(
            lanes != 0, lane_base + pos, jnp.uint32(_SENT)
        )
        slot = jnp.min(cand, axis=1)
        any_ = slot != jnp.uint32(_SENT)
        slot_cols.append(jnp.where(any_, slot, jnp.uint32(0)))
        val_cols.append(any_)
        lanes = jnp.where(
            cand == slot[:, None],
            lanes & (lanes - jnp.uint32(1)),
            lanes,
        )
    slots_flat = jnp.stack(slot_cols, axis=1).reshape(NPg)
    valid_g = jnp.stack(val_cols, axis=1)

    pair_idx = (
        jnp.arange(F_f, dtype=jnp.uint32)[:, None] * jnp.uint32(EV)
        + jnp.arange(EV, dtype=jnp.uint32)[None, :]
    )
    keys = jnp.where(valid_g, pair_idx, jnp.uint32(_SENT)).reshape(NPg)

    if compaction:
        # Tiled packed-append compaction (the sparse analog of the
        # dense tiled key compaction; sort is superlinear so NT small
        # sorts beat one big one). The slot rides the sort as a VALUE
        # lane so no post-compaction ``slots_flat[pidx]`` gather is
        # needed (PERF.md §gathers: one Ba-row gather ≈ a whole extra
        # sort).
        def tile_body(ti, acc):
            pk, ps, app_off, tmax = acc
            off = ti * (T * EV)
            tk = lax.dynamic_slice(keys, (off,), (T * EV,))
            ts = lax.dynamic_slice(slots_flat, (off,), (T * EV,))
            tc = jnp.sum(tk != jnp.uint32(_SENT), dtype=jnp.uint32)
            tmax = jnp.maximum(tmax, tc)
            sk, ss = lax.sort((tk, ts), num_keys=1)
            pk = lax.dynamic_update_slice(pk, sk, (app_off,))
            ps = lax.dynamic_update_slice(ps, ss, (app_off,))
            return pk, ps, app_off + tc, tmax

        pk, psl, _, tile_max = lax.fori_loop(
            0,
            NT,
            tile_body,
            (
                pv(jnp.full(Ba, _SENT, jnp.uint32)),
                pv(jnp.zeros(Ba, jnp.uint32)),
                pv(jnp.uint32(0)),
                pv(jnp.uint32(0)),
            ),
        )
    else:
        pk = keys
        psl = slots_flat
        tile_max = n_pairs

    live = pk != jnp.uint32(_SENT)
    pidx = jnp.where(live, pk, jnp.uint32(0))
    pslot = jnp.where(live, psl, jnp.uint32(0))
    return pidx, live, pslot, cnt, n_pairs, pair_ovf, tile_max


def budget_store_path() -> str:
    """The auto-budget store (``cand_capacity="auto"``), beside the
    compile cache in ``memplan.cache_root()``."""
    import os

    from ..memplan import cache_root

    return os.path.join(cache_root(), "stateright_tpu_budgets.json")


class SortMergeTpuBfsChecker(TpuBfsChecker):
    """``CheckerBuilder.spawn_tpu_sortmerge()``.

    ``capacity`` is the visited-array length — unlike the hash table
    there is no load-factor pressure: it can sit at exactly the
    expected unique-state count (overflow is detected, not silent).

    ``cand_capacity`` is the per-wave candidate budget for the LARGEST
    frontier class; smaller classes use min(F_c*K, cand_capacity).
    Overflow is never silent: the full-flat path checks the whole-wave
    candidate count against the budget (packed tile append has no
    per-tile budget); the per-tile-payload fallback checks per tile.

    ``tiles`` forces at least that many expansion tiles on the largest
    frontier class (smaller classes tile automatically so no single
    compaction sort exceeds ``tile_rows`` rows).

    ``f_min``/``v_min``/``ladder_step`` shape the adaptive ladders; a
    small model (F ≤ f_min, capacity ≤ v_min) degenerates to a single
    fixed-shape wave program, which is also the fallback the test
    suite exercises at toy scale.
    """

    #: Device symmetry capability (checkers/common.symmetry_refusal):
    #: the sort-merge engine canonicalizes candidate blocks before the
    #: fingerprint fold (ops/canonical.py) when the encoding declares
    #: a DeviceRewriteSpec — the base TpuBfsChecker refuses instead.
    _supports_device_symmetry = True
    _engine_name = "spawn_tpu_sortmerge"

    def __init__(
        self,
        builder,
        tiles: int = 1,
        tile_rows: int = 1 << 21,
        f_min: int = 1 << 15,
        v_min: int = 1 << 19,
        ladder_step: int = 2,
        # Round 10 re-derivation: the v-ladder now prices LINEARLY —
        # the streaming passes cost a·V_v + b·B, so a class step of s
        # wastes at most (s-1)x of the V-term on the worst wave
        # (round 6's superlinear-sort argument priced the same waste
        # at (s-1)·log extra compare passes). At step 2 the bound is
        # 2x on a term that is now ~5x cheaper per row (PERF.md
        # §merge-kernel CPU A/B); step 4 would re-expose up to 4x of
        # it for ~half the compiled merge variants — the compile time
        # the persistent XLA cache already absorbs. 2 stays optimal.
        v_ladder_step: int = 2,
        flat_budget_bytes: int = 1 << 30,
        sparse: bool | None = None,
        pair_width: int | None = None,
        mask_budget_cells: int = 1 << 23,
        merge_impl: str | None = None,
        tier_hot_rows=None,
        tier_budget_bytes: int | None = None,
        tier_max_runs: int = 8,
        ample_set: bool = False,
        **kwargs,
    ):
        #: ``cand_capacity="auto"`` (VERDICT r4 item 7): size the
        #: candidate budget (and, in sparse mode, pair_width) from
        #: MEASURED wave peaks instead of a hand-tuned table. The
        #: first run starts from a persisted budget (or a growth
        #: heuristic), and a loud overflow triggers an automatic
        #: resize-and-rerun from the exact observed peak — the same
        #: metric a human re-tuner would read — then persists it for
        #: later processes (``memplan.cache_root()``).
        self.auto_budget = kwargs.get("cand_capacity") == "auto"
        if self.auto_budget:
            kwargs["cand_capacity"] = None
        super().__init__(builder, **kwargs)
        self.tiles = tiles
        self.tile_rows = tile_rows
        self.f_min = f_min
        self.v_min = v_min
        self.ladder_step = ladder_step
        self.v_ladder_step = v_ladder_step
        self.flat_budget_bytes = flat_budget_bytes
        #: sparse action dispatch (None = auto: on iff the encoding
        #: implements SparseEncodedModel). pair_width bounds the
        #: enabled slots extracted per frontier row per wave (overflow
        #: detected, never silent); None defers to the encoding's
        #: ``pair_width_hint`` and finally to max_actions, which can
        #: never overflow per-row.
        self.sparse = sparse
        self.pair_width = pair_width
        self.mask_budget_cells = mask_budget_cells
        #: visited-dedup implementation (ops/merge.py): None = auto
        #: (Pallas kernel on TPU, sort-free XLA implementation on the
        #: CPU); "pallas_interpret" runs the kernel under the Pallas
        #: interpreter — the tier-1 CPU gate for the kernel itself.
        self.merge_impl = resolve_impl(merge_impl)
        #: Tiered visited set (stateright_tpu/tier.py, ROADMAP 1b):
        #: None = off (the all-resident engine, byte-identical to
        #: round 15); an int = the hot-tier ladder ceiling in visited
        #: rows (tests force it tiny to spill repeatedly); "auto" =
        #: decided by the memplan capacity projection against
        #: ``tier_budget_bytes`` (memplan.decide_hot_rows — the
        #: projection is exactly the split signal). Host-side only:
        #: the untiered chunk programs compile byte-identically; the
        #: tiered program is a second, separately-keyed program built
        #: lazily at the first spill.
        self.tier_hot_rows = tier_hot_rows
        self.tier_budget_bytes = tier_budget_bytes
        self.tier_max_runs = tier_max_runs
        #: Partial-order-reduction ample-set filter (off by default):
        #: AND the encoding's host-precomputed ample_mask_host() words
        #: into the packed enabled bitmap, dropping redundant
        #: interleavings before pair compaction. Sparse path only —
        #: the mask is a bitmap-domain object. The ENCODING owns the
        #: soundness argument for its mask (see
        #: two_phase_commit_tpu.ample_mask_host); the engine only
        #: validates shape and applies the AND.
        self.ample_set = bool(ample_set)
        #: tiered-mode frontier-headroom pre-check policy
        #: (memplan.tier_frontier_headroom, checked BEFORE device
        #: work): "warn" — surface the PR 12 known bound up front
        #: (default; the old behavior surfaced it only as a mid-run
        #: f_overflow message), "bump" — raise frontier_capacity to
        #: the provable bound before programs build, "refuse" —
        #: raise instead of risking a mid-run overflow.
        self.tier_headroom_policy = "warn"
        self._tier_headroom_checked = False
        #: the live ColdStore while a tiered run is in flight, and
        #: the resume-staged tier state (checkpoint.resume_from)
        self._tier_state = None
        self._tier_resume_state = None
        self._tier_hot_ceiling = None
        self._tier_spill_wall = 0.0
        self._tier_plog_rows = None
        if tiles > 1 and self.frontier_capacity % tiles:
            raise ValueError(
                f"frontier_capacity {self.frontier_capacity} not divisible "
                f"by tiles {tiles}"
            )
        if self.auto_budget:
            saved = self._load_budget()
            if saved is not None:
                self.cand_capacity = saved["cand_capacity"]
                # A persisted pair_width only fills the default: an
                # EXPLICIT constructor pair_width wins over the store
                # (cand_capacity="auto" silently widening a passed
                # pair_width was ADVICE r5).
                if (self._use_sparse() and saved.get("pair_width")
                        and self.pair_width is None):
                    self.pair_width = saved["pair_width"]
            else:
                # Growth heuristic: a wave rarely multiplies the
                # frontier by more than a few; overflow (loud) corrects
                # upward from the measured peak.
                F = self.frontier_capacity
                K = self.encoded.max_actions
                self.cand_capacity = min(
                    4 * F, F * (self._pair_width()
                                if self._use_sparse() else K)
                )

    @property
    def codegen_opt(self):
        """Codegen-optimizer summary of the encoding this engine is
        executing (actor/compile.py ``CompiledActorEncoding.codegen_opt``
        — fused switch / elided gathers / table widths), or ``None``
        for hand encodings and ``optimize=False`` compiles. One seam
        for bench detail + provenance on both sort-merge engines (the
        sharded engine inherits it)."""
        return getattr(self.encoded, "codegen_opt", None)

    # -- auto budget (VERDICT r4 item 7) -----------------------------------

    def _budget_store(self):
        return budget_store_path()

    def _budget_key(self) -> str:
        enc = self.encoded
        key_fn = getattr(enc, "cache_key", None)
        ident = repr(key_fn()) if key_fn is not None else ""
        return (
            f"{type(enc).__name__}/{ident}/W{enc.width}/"
            f"K{enc.max_actions}/F{self.frontier_capacity}/"
            f"C{self.capacity}"
        )

    def _load_budget(self):
        import json
        import os

        path = self._budget_store()
        if not os.path.exists(path):
            return None
        try:
            with open(path) as fh:
                data = json.load(fh)
        except ValueError as exc:
            # Torn-write hardening: a truncated/corrupt store (a crash
            # mid-write from a pre-atomic version, disk-level
            # truncation) must not raise at engine START — fall back
            # to the growth heuristic with one line saying why (the
            # next clean run's _save_budget rewrites the store
            # atomically). Parse-guard IS the checksum here: the
            # store is JSON, and torn JSON does not parse.
            import warnings

            warnings.warn(
                f"auto-budget store {path} is corrupt ({exc}); "
                "falling back to default budgets (the store rewrites "
                "on the next clean run)",
                RuntimeWarning,
                stacklevel=3,
            )
            return None
        except OSError:
            return None
        try:
            return data.get(self._budget_key())
        except AttributeError:
            return None

    def _save_budget(self) -> None:
        import json
        import os

        path = self._budget_store()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # Concurrent checkers (pytest workers, multi-model drivers)
        # write different keys into one store: an unlocked
        # read-modify-write dropped the loser's entry (ADVICE r5).
        # Serialize the whole cycle on a lock file so the re-read
        # immediately before the atomic replace sees every earlier
        # writer's keys.
        with open(path + ".lock", "w") as lock_fh:
            try:
                import fcntl

                fcntl.flock(lock_fh, fcntl.LOCK_EX)
            except (ImportError, OSError):
                # Non-POSIX, or a filesystem without flock support
                # (NFS/overlay): fall back to the unlocked-but-atomic
                # replace rather than failing a finished check run.
                pass
            data = {}
            try:
                with open(path) as fh:
                    data = json.load(fh)
            except (OSError, ValueError):
                pass
            data[self._budget_key()] = {
                "cand_capacity": self._shrunk_cand_capacity(),
                "pair_width": (
                    self._pair_width() if self._use_sparse() else None
                ),
                "observed_peak": self.metrics.get("max_wave_candidates"),
            }
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(data, fh, indent=1, sort_keys=True)
            os.replace(tmp, path)

    #: shrink target: persisted budget heads toward observed_peak *
    #: this margin on clean runs; shrink fires only past 2x headroom
    #: so a near-peak budget isn't thrashed by wave-to-wave noise.
    _SHRINK_MARGIN = 1.25

    def _shrunk_cand_capacity(self):
        """The cand_capacity to PERSIST (VERDICT/ROADMAP carried item):
        the budget store only ever grew, so a lane whose growth
        heuristic overshot kept its headroom forever — paxos-4
        converged at 2,097,152 against an observed peak of 660,492,
        3.2x slack that silently pushed the padded-residency gate into
        CHUNKED memory-lean mode and paid recompute fetch every wave.
        On a CLEAN run (no overflow retry this process — a just-grown
        budget is geometric, not measured, and must survive to the
        next run) with more than 2x headroom over the measured peak,
        persist ``observed_peak * margin`` instead; the running
        checker keeps its budget (programs are compiled), the next
        process picks up the shrunk one. Emits ``auto_budget_shrink``
        so TRACE artifacts show the resize."""
        cap = self.cand_capacity
        peak = self.metrics.get("max_wave_candidates")
        if (
            not cap
            or not peak
            or getattr(self, "_budget_grew", False)
        ):
            return cap
        want = max(int(peak * self._SHRINK_MARGIN), 1024)
        if cap <= 2 * want:
            return cap
        from .. import telemetry

        telemetry.emit(
            "auto_budget_shrink", kind="cand_capacity", old=cap,
            new=want, observed_peak=int(peak),
        )
        return want

    def _run(self, reporter=None) -> None:
        if not self.auto_budget:
            return super()._run(reporter)
        self._budget_grew = False
        last_exc = None
        for _attempt in range(6):
            if last_exc is not None:
                # Reset at the TOP of the retry so a final failed
                # attempt keeps its recorded discoveries (the
                # discoveries-survive-overflow contract in tpu.py).
                self._reset_for_retry()
            try:
                super()._run(reporter)
                self._save_budget()
                return
            except RuntimeError as exc:
                msg = str(exc)
                if ("pair-buffer overflow" not in msg
                        and "candidate-buffer overflow" not in msg):
                    raise
                last_exc = exc
                rowen = self.metrics.get("max_row_enabled", 0)
                if (self._use_sparse()
                        and rowen > self._pair_width()):
                    # The mask counts are exact even on the overflow
                    # run, so one resize suffices for pair_width —
                    # and a pure pair_width overflow must NOT also
                    # inflate (and persist) the candidate budget.
                    self._note_budget_growth(
                        "pair_width", self._pair_width(), int(rowen),
                        _attempt,
                    )
                    self.pair_width = int(rowen)
                    continue
                # The observed peak only covers waves BEFORE the
                # overflow, so grow geometrically past it — the
                # converged budget still ends within ~4x of the true
                # peak and one clean re-run records the exact value.
                peak = self.metrics.get("max_wave_candidates", 0)
                grown = max(
                    int(peak * 1.15) + 1024,
                    4 * (self.cand_capacity or 1),
                )
                self._note_budget_growth(
                    "cand_capacity", self.cand_capacity, grown,
                    _attempt,
                )
                self.cand_capacity = grown
        raise RuntimeError(
            "auto budget did not converge in 6 attempts; last overflow: "
            f"{last_exc}"
        ) from last_exc

    def _note_budget_growth(self, kind: str, old, new,
                            attempt: int) -> None:
        """The geometric capacity ladder used to retry SILENTLY: a
        run that overflowed and re-ran 3x read as 'slow', not
        'mis-budgeted'. Every resize now lands as a one-line warning
        naming the old/new capacity plus a telemetry event (when a
        tracer is active) so the retry shows up in TRACE artifacts."""
        import warnings

        from .. import telemetry

        # An overflow-grown budget is a geometric guess, not a
        # measurement: the clean-run shrink must not fire on it
        # (_shrunk_cand_capacity).
        self._budget_grew = True

        warnings.warn(
            f"auto-budget: {kind} {old} -> {new} after a buffer "
            f"overflow (retry {attempt + 1}); the resized wave "
            "programs recompile at the new shapes",
            RuntimeWarning,
            stacklevel=3,
        )
        telemetry.emit(
            "auto_budget_retry", kind=kind, old=old, new=new,
            attempt=attempt + 1,
        )

    def _reset_for_retry(self) -> None:
        """Discard one failed attempt's partial results so the resized
        re-run starts clean (programs rebuild at the new shapes).
        The memory ledger re-derives with them — a resized budget is
        a different class ladder."""
        self._programs = None
        self.memory_plan = None
        self._discovered_fps.clear()
        self._discoveries.clear()
        self._total_states = 0
        self._unique_states = 0
        self._max_depth = 0
        self.metrics = {}
        self.generated = None
        # a resized re-run re-explores (and re-spills) from scratch
        self._tier_state = None
        self._tier_plog_rows = None
        self._tier_mem = None

    def _checkpoint_family(self) -> str:
        # Both sort-merge engines carry the same sorted-prefix visited
        # structure, so their snapshots interconvert under the
        # (owner, fp) re-route (checkpoint.reshard_sortmerge).
        return "sortmerge"

    def _degrade_memory_lean(self) -> bool:
        """Supervisor OOM hook (checkpoint.supervised_run): quarter
        the flat budget so the padded-residency gates flip the big
        classes into CHUNKED memory-lean mode on the next attempt
        (the successor tensor is never materialized; winners
        recompute at fetch). Programs rebuild — flat_budget_bytes is
        cache-keyed, so the degraded shapes are a new entry."""
        new_budget = max(self.flat_budget_bytes // 4, 1 << 22)
        if new_budget >= self.flat_budget_bytes:
            return False
        import warnings

        from .. import telemetry

        warnings.warn(
            f"repeated OOM under supervision: flat_budget_bytes "
            f"{self.flat_budget_bytes} -> {new_budget} (CHUNKED "
            "memory-lean classes engage where the gate trips; "
            "programs recompile)",
            RuntimeWarning,
            stacklevel=3,
        )
        telemetry.emit(
            "oom_degrade", engine=type(self).__name__,
            flat_budget_bytes_old=int(self.flat_budget_bytes),
            flat_budget_bytes=int(new_budget),
        )
        self.flat_budget_bytes = new_budget
        self._programs = None
        self.memory_plan = None
        return True

    # -- tiered visited set (stateright_tpu/tier.py, ROADMAP 1b) -----------

    def _overflow_message(self, s):
        msg = super()._overflow_message(s)
        if (msg is not None and bool(s[1])
                and self.tier_hot_rows is not None):
            # the takeover only runs at chunk syncs: one UNTIERED
            # transition chunk can carry the resident count from the
            # ceiling past the capacity before the first spill fires
            msg += (
                "  (tiering is configured but the ceiling was "
                "crossed and overrun within one untiered chunk — "
                "lower waves_per_sync so a sync lands between the "
                "ceiling and the capacity, or lower tier_hot_rows)"
            )
        return msg

    def _pre_run_check(self) -> None:
        """The tiered frontier-headroom bound, pre-checked BEFORE any
        program build or device work (memplan.tier_frontier_headroom
        — the PR 12 known bound, which used to surface only as a
        mid-run f_overflow message): refuse, auto-bump the frontier
        to the provable bound, or warn up front, per
        ``tier_headroom_policy``."""
        if self.tier_hot_rows is None or self._tier_headroom_checked:
            return
        self._tier_headroom_checked = True
        from ..memplan import tier_frontier_headroom

        cand = self.cand_capacity
        if cand is None:
            # no compaction: the true static bound on a wave's
            # candidates (and therefore provisional winners) is F x K
            cand = self.frontier_capacity * self.encoded.max_actions
        chk = tier_frontier_headroom(
            self.capacity, self.frontier_capacity, cand
        )
        if chk["holds"] is not False:
            # True = provable; None = still-unresolved auto budget
            # (nothing provable or refutable before it lands)
            return
        policy = getattr(self, "tier_headroom_policy", "warn")
        if policy == "refuse":
            raise ValueError(
                "tiered frontier-headroom pre-check refused "
                "(tier_headroom_policy='refuse'): " + chk["message"]
            )
        import warnings

        if policy == "bump" and chk["required_frontier"]:
            old = self.frontier_capacity
            bumped = int(chk["required_frontier"])
            if self.tiles > 1 and bumped % self.tiles:
                bumped = (
                    (bumped + self.tiles - 1) // self.tiles
                ) * self.tiles
            self.frontier_capacity = bumped
            self._programs = None
            self.memory_plan = None
            warnings.warn(
                "tiered frontier-headroom pre-check: "
                f"frontier_capacity {old} -> {bumped} "
                "(tier_headroom_policy='bump' — provisional winners "
                "are bounded by cand_capacity="
                f"{self.cand_capacity}, so the bumped frontier makes "
                "the bound provable before device work)",
                RuntimeWarning,
                stacklevel=3,
            )
            return
        warnings.warn(chk["message"], RuntimeWarning, stacklevel=3)

    def _tier_ceiling(self):
        """The hot-tier ladder ceiling in visited rows (None = tier
        off). ``"auto"`` resolves through the memplan capacity
        projection's own pricing (memplan.decide_hot_rows) against
        ``tier_budget_bytes``."""
        if self.tier_hot_rows is None:
            return None
        if self._tier_hot_ceiling is None:
            if self.tier_hot_rows == "auto":
                from ..memplan import decide_hot_rows

                budget = self.tier_budget_bytes or (1 << 31)
                self._tier_hot_ceiling = decide_hot_rows(
                    self.capacity, self.v_min, self.v_ladder_step,
                    self.frontier_capacity, budget,
                )
            else:
                hr = int(self.tier_hot_rows)
                if hr < 1:
                    raise ValueError(
                        f"tier_hot_rows must be >= 1: {hr}"
                    )
                self._tier_hot_ceiling = min(hr, self.capacity)
        return self._tier_hot_ceiling

    def _tier_headroom(self):
        cold = self._tier_state
        if cold is None:
            return None
        out = cold.summary()
        out["hot_ceiling_rows"] = self._tier_hot_ceiling
        out["spill_wall_sec"] = round(self._tier_spill_wall, 6)
        return out

    def _reset_for_resume(self) -> None:
        super()._reset_for_resume()
        self._tier_state = None
        self._tier_plog_rows = None
        self._tier_mem = None

    def _tier_takeover(self, carry, n0, chunk_no, reporter):
        staged = self._tier_resume_state
        ceiling = self._tier_ceiling()
        if ceiling is None and staged is None:
            return None
        if staged is None:
            h_np = self._tier_resident_counts(carry)
            limit = min(
                ceiling, max(self.capacity - self.frontier_capacity, 1)
            )
            if int(h_np.max()) <= limit:
                return None
        return self._tier_run(carry, n0, chunk_no, reporter)

    def _lookup_tier_programs(self, n0: int):
        """Build-or-fetch the TIERED chunk program — a separate
        program (and cache slot) from the untiered pair, keyed by the
        same program identity plus the tier marker. Never touches the
        untiered cache entry, ``_wave_body``, or ``_build_info``."""
        key = self._program_cache_key(n0)
        if key is None:
            fn = self._build_programs(n0, tiered=True)
            return fn
        tkey = (key, "tiered")
        if tkey not in _TIER_CACHE:
            fn = self._build_programs(n0, tiered=True)
            _TIER_CACHE[tkey] = (
                fn, getattr(self, "_tier_pspecs", None)
            )
        fn, self._tier_pspecs = _TIER_CACHE[tkey]
        return fn

    # engine-shape hooks the shared loop uses (the sharded engine
    # overrides placement and the hot/pend lane layouts)

    def _tier_resident_counts(self, carry) -> np.ndarray:
        return np.array([int(np.asarray(carry["new"]))], np.int64)

    def _tier_hot_lane(self) -> str:
        return "n_hot"

    def _tier_zero_hot(self):
        return np.uint32(0)

    def _tier_hot_value(self, h_np):
        return np.uint32(int(h_np[0]))

    def _tier_zero_pl(self):
        return np.uint32(0)

    def _tier_place(self, name, arr):
        # jax-owned COPY: the tiered chunk donates its carry, and a
        # zero-copy upload aliasing numpy memory under donate_argnums
        # is the exact round-15 bug class (checkpoint.py)
        import jax.numpy as jnp

        return jnp.copy(jnp.asarray(arr))

    def _tier_mask_dev(self, mask_np: np.ndarray):
        import jax.numpy as jnp

        return jnp.asarray(np.ascontiguousarray(mask_np.reshape(-1)))

    def _tier_shard_rows(self, shard_log):
        return None

    def _tier_pend_zero(self):
        return np.uint32(0)

    def _tier_extend_carry(self, carry, h_np):
        """The handoff: untiered carry + the tiered staging lanes
        (empty pend, hot count, tier-shaped trace logs)."""
        S = getattr(self, "n_shards", 1)
        F = self.frontier_capacity
        ext = dict(carry)
        ext["pend_keys"] = self._tier_place(
            "pend_keys", np.full((2, S * F), _SENT, np.uint32)
        )
        if self.track_paths:
            ext["pend_par"] = self._tier_place(
                "pend_par", np.zeros((2, S * F), np.uint32)
            )
        ext["pend_n"] = self._tier_place(
            "pend_n", self._tier_pend_zero()
        )
        ext["pend_valid"] = self._tier_place(
            "pend_valid", np.bool_(False)
        )
        ext[self._tier_hot_lane()] = self._tier_place(
            self._tier_hot_lane(), self._tier_hot_value(h_np)
        )
        if self._wave_log_enabled():
            from ..telemetry import WAVE_LOG_LANES as WL

            ext["wlog"] = self._tier_place(
                "wlog", np.zeros((1, WL), np.uint32)
            )
            ext["pstash"] = self._tier_place(
                "pstash", np.zeros(8, np.uint32)
            )
            self._tier_extend_trace(ext)
        return ext

    def _tier_extend_trace(self, ext) -> None:
        """Hook: extra tier-shaped trace lanes (the sharded engine's
        per-shard mesh log)."""

    def _tier_pend_read(self, carry):
        S = getattr(self, "n_shards", 1)
        F = self.frontier_capacity
        pk = np.asarray(carry["pend_keys"]).reshape(2, S, F)
        pn = np.atleast_1d(
            np.asarray(carry["pend_n"])
        ).astype(np.int64).reshape(-1)
        return pn, pk[0], pk[1]

    def _tier_spill(self, carry, cold, h_np):
        """Spill the whole hot prefix to the cold store at the sync:
        the prefix download piggybacks the readback that just blocked
        (the checkpoint seam), ingest runs on the worker thread
        overlapped with the next dispatch, and the device hot tier
        resets to empty. Emits the schema-validated ``tier_spill``
        event."""
        import time as _time

        from .. import telemetry

        t0 = _time.monotonic()
        S = getattr(self, "n_shards", 1)
        C_pad = self.capacity + self.frontier_capacity
        vk = np.asarray(carry["vkeys"]).reshape(2, S, C_pad)
        per_shard = []
        for s_i in range(S):
            n = int(h_np[s_i])
            per_shard.append((
                vk[0, s_i, :n].copy(), vk[1, s_i, :n].copy()
            ))
        prev_rows = cold.rows()
        prev_runs = cold.run_count()
        cold.ingest(per_shard)
        carry = dict(carry)
        carry["vkeys"] = self._tier_place(
            "vkeys", np.full((2, S * C_pad), _SENT, np.uint32)
        )
        carry[self._tier_hot_lane()] = self._tier_place(
            self._tier_hot_lane(), self._tier_zero_hot()
        )
        wall = _time.monotonic() - t0
        self._tier_spill_wall += wall
        rows = int(h_np.sum())
        from ..tier import COLD_BYTES_PER_ROW

        telemetry.emit(
            "tier_spill",
            engine=type(self).__name__,
            rows=rows,
            bytes=rows * COLD_BYTES_PER_ROW,
            rows_per_shard=[int(x) for x in h_np],
            hot_rows_before=rows,
            hot_ceiling_rows=self._tier_hot_ceiling,
            spill_index=int(cold.spills),
            # pre-compaction run count (ingest is async; compaction
            # may fold runs before the next sync)
            runs=int(prev_runs + sum(1 for lo, _ in per_shard
                                     if lo.size)),
            cold_rows_total=int(prev_rows + rows),
            cold_bytes_total=int(
                (prev_rows + rows) * COLD_BYTES_PER_ROW
            ),
            wall_sec=round(wall, 6),
            ingest_sec=round(cold.ingest_sec, 6),
        )
        return carry

    def _tier_plog_reset(self, carry):
        """Take the device parent log's rows host-side and rewind the
        cursor — tiered runs outgrow the device log (it is sized for
        one capacity's worth of uniques, the cumulative count is
        unbounded), so the host accumulates the drained rows and
        ``_build_generated`` reads them instead."""
        S = getattr(self, "n_shards", 1)
        L = self.capacity + self.frontier_capacity
        pl = np.atleast_1d(
            np.asarray(carry["pl_n"])
        ).astype(np.int64).reshape(-1)
        plog = np.asarray(carry["plog"]).reshape(4, S, L)
        rows = []
        for s_i in range(S):
            n = int(pl[s_i])
            if n:
                rows.append(plog[:, s_i, :n].copy())
        carry = dict(carry)
        carry["pl_n"] = self._tier_place("pl_n", self._tier_zero_pl())
        return carry, rows

    def _tier_plog_drain(self, carry, pl_cursor, confs):
        """Per-dispatch drain of the rows the commit just appended
        (the host knows the count — it built the keep mask), with a
        cursor rewind when the device log nears its end. Slices
        DEVICE-side before materializing, so only the freshly
        appended ≤F rows transfer — not the whole [4, S*L] log
        (which would be a multi-MB D2H per wave on real HBM)."""
        S = getattr(self, "n_shards", 1)
        F = self.frontier_capacity
        L = self.capacity + F
        if int(confs.sum()):
            plog = carry["plog"]
            for s_i in range(S):
                cnf = int(confs[s_i])
                if cnf:
                    st = int(pl_cursor[s_i])
                    off = s_i * L + st
                    self._tier_plog_rows.append(
                        np.asarray(plog[:, off:off + cnf])
                    )
                    pl_cursor[s_i] = st + cnf
        if int(pl_cursor.max()) + F > L:
            carry = dict(carry)
            carry["pl_n"] = self._tier_place(
                "pl_n", self._tier_zero_pl()
            )
            pl_cursor[:] = 0
        return carry

    def _tier_generated_map(self):
        rows = getattr(self, "_tier_plog_rows", None)
        if rows is None:
            return None
        generated: dict = {}
        for blk in rows:
            child = (
                blk[3].astype(np.uint64) << np.uint64(32)
            ) | blk[2].astype(np.uint64)
            parent = (
                blk[1].astype(np.uint64) << np.uint64(32)
            ) | blk[0].astype(np.uint64)
            for ch, pa in zip(child.tolist(), parent.tolist()):
                generated[int(ch)] = int(pa) if pa else None
        return generated

    def _tier_run(self, carry, n0, chunk_no, reporter):
        """The tiered chunk loop (the host side of the deferred-commit
        protocol): per dispatch — commit the previous wave's survivors
        under the mask computed here, run one wave, read the new
        provisional winners at the one sync, run the batched
        sort-merge membership against the cold runs, spill the hot
        prefix when it crosses the ceiling. Returns the final
        ``(carry, stats)`` to the shared completion path in tpu.py."""
        import time as _time

        from .. import faultinject, telemetry
        from ..report import ReportData
        from ..telemetry import WAVE_LOG_LANES as WL
        from ..tier import ColdStore

        tracer = self._tracer
        S = getattr(self, "n_shards", 1)
        F = self.frontier_capacity
        C = self.capacity
        props = list(self.model.properties())
        n_props = len(props)
        trace_log = self._wave_log_enabled()
        ceiling = self._tier_ceiling()
        self._tier_hot_ceiling = ceiling
        limit = min(ceiling if ceiling else C, max(C - F, 1))

        staged = self._tier_resume_state
        self._tier_resume_state = None
        if staged is not None:
            cold = staged["cold"]
            cold.max_runs = self.tier_max_runs
            h_np = np.asarray(staged["hot"], np.int64).reshape(-1)
        else:
            cold = ColdStore(n_shards=S, max_runs=self.tier_max_runs)
            h_np = self._tier_resident_counts(carry)
        self._tier_state = cold

        tier_fn = self._lookup_tier_programs(n0)
        carry = self._tier_extend_carry(carry, h_np)
        if self.track_paths:
            carry, rows0 = self._tier_plog_reset(carry)
            # a resumed run's host-drained rows (snapshot tier_plog)
            # lead; then whatever the restored device log carried
            pre = (staged or {}).get("plog_rows") or []
            self._tier_plog_rows = list(pre) + rows0
        pl_cursor = np.zeros(S, np.int64)

        # first spill: activation means the ceiling is crossed (or
        # resumed cold runs exist with hot above it)
        if int(h_np.max()) > limit:
            carry = self._tier_spill(carry, cold, h_np)
            h_np = np.zeros(S, np.int64)

        verdicts_seen: set = set()
        d0 = np.asarray(carry["disc_found"])
        for i, prop in enumerate(props):
            if i < d0.size and d0[i]:
                verdicts_seen.add(prop.name)

        lat = self._lat
        mem_peak = None
        mem_src = None
        mem_polls = 0
        prev_waves = int(np.asarray(carry["waves"]))
        chunk_idx = lat["chunks"]
        mask_np = np.zeros((S, F), bool)
        pending_confs = np.zeros(S, np.int64)
        s = None
        while True:
            if (self.cancel_event is not None
                    and self.cancel_event.is_set()):
                self.cancelled = True
                return carry, s
            t0 = _time.monotonic()
            keep_dev = self._tier_mask_dev(mask_np)
            wd_snap = (_monitor_snapshot()
                       if getattr(self, "watchdog_factor", None)
                       else None)

            def exec_chunk(carry=carry, keep_dev=keep_dev,
                           chunk_no=chunk_no):
                if getattr(self, "mesh", None) is not None:
                    faultinject.fire("collective_seam", chunk_no,
                                     shards=self._fault_shards())
                out = tier_fn(carry, keep_dev)
                c_out, stats = out[0], out[1]
                slog = out[2] if len(out) > 2 else None
                faultinject.fire("mid_chunk", chunk_no,
                                 shards=self._fault_shards())
                td = _time.monotonic()
                return c_out, np.asarray(stats), slog, td

            # the tiered dispatch+sync runs under the same
            # hung-dispatch watchdog as the untiered chunk loop
            carry, s, shard_log, t_disp = self._guarded_dispatch(
                exec_chunk, chunk_no
            )
            t1 = _time.monotonic()
            self._note_watchdog_wall(t1 - t0, wd_snap)
            lat["chunks"] += 1
            lat["dispatch_sec"] += t_disp - t0
            fetch = t1 - t_disp
            lat["fetch_sec"] += fetch
            if lat["fetch_min"] is None or fetch < lat["fetch_min"]:
                lat["fetch_min"] = fetch
            if lat["t_first_sync"] is None:
                lat["t_first_sync"] = t1

            if tracer is not None:
                from ..memplan import device_bytes_in_use

                mem_now, src = device_bytes_in_use()
                if mem_now is not None:
                    mem_src = src
                    mem_polls += 1
                    mem_peak = (mem_now if mem_peak is None
                                else max(mem_peak, mem_now))
                waves_now = int(s[4])
                n_waves = waves_now - prev_waves
                rows = None
                if trace_log:
                    off = 11 + 3 * n_props + 3
                    rows = np.asarray(
                        s[off:off + WL]
                    ).reshape(1, WL)
                srows = self._tier_shard_rows(shard_log)
                # health layer: straggler detection (no-op unless
                # sharded + straggler_factor configured)
                self._note_shard_health(
                    None if srows is None else srows[:, :n_waves],
                    prev_waves,
                )
                tracer.record_chunk(
                    chunk=chunk_idx,
                    wave0=prev_waves,
                    t0=t0,
                    t1=t1,
                    dispatch_sec=t_disp - t0,
                    device_sec=None,
                    fetch_sec=fetch,
                    n_waves=n_waves,
                    wave_rows=(None if rows is None
                               else rows[:n_waves]),
                    pairs_valid=self._wave_log_pairs_valid(),
                    shard_rows=(None if srows is None
                                else srows[:, :n_waves]),
                    mem_bytes=mem_now,
                )
                prev_waves = waves_now
                chunk_idx += 1
                if n_props:
                    disc = s[11:11 + n_props]
                    for i, prop in enumerate(props):
                        if disc[i] and prop.name not in verdicts_seen:
                            verdicts_seen.add(prop.name)
                            tracer.event(
                                "verdict",
                                property=prop.name,
                                expectation=(
                                    prop.expectation.name.lower()
                                ),
                                kind="discovery",
                                wave=int(s[4]),
                                depth=int(s[3]),
                                chunk=chunk_idx - 1,
                            )

            done = bool(s[0])
            self._total_states = int(s[6]) | (int(s[7]) << 32)
            self._unique_states = int(s[8])
            self._max_depth = max(self._max_depth, int(s[3]))
            self.metrics = dict(
                frontier_size=int(s[5]),
                occupancy=(
                    self._unique_states / self.total_capacity
                ),
                dedup_ratio=(
                    1.0 - self._unique_states / self._total_states
                    if self._total_states else 0.0
                ),
                waves=int(s[4]),
            )
            if mem_peak is not None:
                self.metrics["device_peak_bytes"] = mem_peak

            h_np = h_np + pending_confs
            if self.track_paths:
                carry = self._tier_plog_drain(
                    carry, pl_cursor, pending_confs
                )

            overflow_msg = self._overflow_message(s)
            if overflow_msg is not None:
                if bool(s[2]):
                    overflow_msg += (
                        "  (tiered mode: the bound applies to the "
                        "wave's PROVISIONAL winners — hot-tier-new "
                        "rows before the cold membership pass — so a "
                        "frontier that fits the all-resident run may "
                        "need headroom once the hot tier spills)"
                    )
                cold.sync()
                self._consume_extra_stats(s[11 + 3 * n_props:])
                self._record_discoveries(s, props)
                if self._discovered_fps:
                    overflow_msg += (
                        "  Discoveries recorded before truncation "
                        f"(valid counterexamples): "
                        f"{sorted(self._discovered_fps)} — read them "
                        "via discovered_property_names() / "
                        "discovery_fingerprints() after catching "
                        "this error."
                    )
                self._tier_mem = (mem_peak, mem_src, mem_polls)
                if tracer is not None:
                    self._emit_memory_watermark(tracer, None, None, 0)
                raise RuntimeError(overflow_msg)

            if done:
                break

            pn, p_lo, p_hi = self._tier_pend_read(carry)
            cold.sync()
            mask_np = np.zeros((S, F), bool)
            for s_i in range(S):
                n_p = int(pn[s_i]) if s_i < pn.size else 0
                if n_p == 0:
                    continue
                member = cold.member(
                    s_i, p_lo[s_i, :n_p], p_hi[s_i, :n_p]
                )
                mask_np[s_i, :n_p] = ~member
            pending_confs = mask_np.sum(axis=1).astype(np.int64)

            if int(h_np.max()) > limit:
                carry = self._tier_spill(carry, cold, h_np)
                h_np = np.zeros(S, np.int64)

            if (self.checkpoint_every
                    and (chunk_no + 1) % self._ckpt_cadence() == 0):
                from .. import checkpoint as _ckpt

                t_ck = _time.monotonic()
                _ckpt.write_snapshot(
                    self, carry, self.checkpoint_path,
                    chunk=chunk_no, wave=int(s[4]),
                    depth=int(s[3]), unique=int(s[8]),
                    tier=cold, tier_plog=self._tier_plog_rows,
                )
                self._note_snapshot_wall(
                    _time.monotonic() - t_ck, t1 - t0
                )
            faultinject.fire("chunk_boundary", chunk_no,
                             shards=self._fault_shards())
            chunk_no += 1
            if reporter is not None:
                reporter.report_checking(
                    ReportData(
                        total_states=self._total_states,
                        unique_states=self._unique_states,
                        max_depth=self._max_depth,
                        duration_sec=self.duration_sec(),
                        done=False,
                    )
                )

        cold.sync()
        self._tier_mem = (mem_peak, mem_src, mem_polls)
        self.metrics.update(
            tier_spills=int(cold.spills),
            cold_rows=cold.rows(),
            cold_bytes=cold.bytes(),
            hot_rows=int(h_np.sum()),
        )
        return carry, s

    def _use_sparse(self) -> bool:
        if self.sparse is not None:
            return self.sparse
        return isinstance(self.encoded, SparseEncodedModel)

    def _pair_width(self) -> int:
        K = self.encoded.max_actions
        if self.pair_width is not None:
            return min(self.pair_width, K)
        hint = getattr(self.encoded, "pair_width_hint", None)
        return min(hint, K) if hint else K

    def _resolve_ample_words(self):
        """The validated host-constant ample mask (``uint32[L]``), or
        None when the filter is off. ONE home for the single-chip and
        sharded program builders — the refusals must not drift."""
        if not self.ample_set:
            return None
        from ..encoding import ample_mask_host

        enc = self.encoded
        if not self._use_sparse():
            raise ValueError(
                "ample_set requires the sparse dispatch path (the "
                "filter is an AND over the packed enabled bitmap); "
                "this run resolved to the dense wave"
            )
        aw = ample_mask_host(enc)
        if aw is None:
            raise ValueError(
                f"ample_set: encoding {type(enc).__name__} declares "
                "no ample_mask_host() — the engine cannot invent a "
                "sound reduction; declare the mask on the encoding "
                "(it owns the soundness argument) or drop the flag"
            )
        if aw.shape[0] != mask_words(enc.max_actions):
            raise ValueError(
                f"ample_mask_host() returned {aw.shape[0]} words; "
                f"max_actions={enc.max_actions} needs "
                f"{mask_words(enc.max_actions)}"
            )
        # the certificate gate (analysis/soundness.py): the declared
        # mask only filters once enabledness-preservation and
        # non-suppression are discharged — an uncertifiable mask
        # refuses with the failed obligation unless --unsound-ok.
        from ..analysis.soundness import gate_ample

        gate_ample(enc, self._engine_name, self.unsound_ok)
        return np.asarray(aw, np.uint32)

    def _cache_extras(self) -> tuple:
        return (
            "sortmerge",
            self.tiles,
            self.tile_rows,
            self.f_min,
            self.v_min,
            self.ladder_step,
            self.v_ladder_step,
            self.flat_budget_bytes,
            self._use_sparse(),
            self._pair_width(),
            self.mask_budget_cells,
            # the visited-dedup implementation changes the compiled
            # wave program (Pallas kernel vs XLA implementation).
            self.merge_impl,
            # traced runs carry the wave log: a different program.
            self._wave_log_enabled(),
            # device symmetry / ample-set change the compiled wave
            # programs (canonicalization pass, enabled-bits AND) for
            # the SAME encoding, so they key the program cache too.
            self.sym_spec is not None,
            self.ample_set,
        )

    # -- telemetry (stateright_tpu/telemetry.py) ---------------------------
    #
    # _wave_log_enabled is inherited from TpuBfsChecker (one home for
    # the tracer→program gate; the mesh engine keys its per-shard
    # mesh log on the same flag).

    def _wave_log_rows(self, s: np.ndarray, n_props: int):
        if not self._wave_log_enabled():
            return None
        from ..telemetry import WAVE_LOG_LANES as WL

        off = 11 + 3 * n_props + 3  # scalars + discovery + peak lanes
        return s[off:off + self.waves_per_sync * WL].reshape(
            self.waves_per_sync, WL
        )

    def _lane_config(self) -> dict:
        lane = super()._lane_config()
        lane.update(
            sparse=self._use_sparse(),
            pair_width=(self._pair_width() if self._use_sparse()
                        else None),
            auto_budget=self.auto_budget,
            tiles=self.tiles,
            tile_rows=self.tile_rows,
            f_min=self.f_min,
            v_min=self.v_min,
            ladder_step=self.ladder_step,
            v_ladder_step=self.v_ladder_step,
            flat_budget_bytes=self.flat_budget_bytes,
            mask_budget_cells=self.mask_budget_cells,
            merge_impl=self.merge_impl,
            tier_hot_rows=self.tier_hot_rows,
            symmetry=self.sym_spec is not None,
            ample_set=self.ample_set,
        )
        if self.sym_spec is not None or self.ample_set:
            # certificate provenance rides the lane config (and hence
            # every trace's run_begin record): True/False when the
            # analyzer ran, absent when no reduction is on.
            from ..analysis.soundness import soundness_status

            lane.update(
                soundness_certified=soundness_status(self.encoded)
            )
        return lane

    def _maybe_warn_occupancy(self, occupancy: float) -> None:
        """No probe pressure: the sorted array works at 100% occupancy
        and overflow is detected exactly — nothing to warn about
        per-chunk. (Per-SHARD occupancy headroom on the mesh engines
        IS watched, by the trace-side metric: telemetry.shard_balance
        reuses the shared formatter in stateright_tpu/occupancy.py
        with the exact-capacity HEADROOM_THRESHOLD.)"""

    # -- memory observability (stateright_tpu/memplan.py) ------------------

    def _visited_bytes_per_row(self) -> int:
        # vkeys: two uint32 key limbs; plog appends 4 uint32 lanes
        # (parent + child limbs) per unique state when paths are on.
        return 8 + (16 if self.track_paths else 0)

    def _budget_headroom(self):
        """The observed-peak-vs-budget join the watermark carries:
        the same persisted store the auto-budget sizes from, so the
        headroom the event reports is the headroom the next process's
        budget decision reads."""
        if not self.auto_budget:
            return None
        peak = self.metrics.get("max_wave_candidates")
        if not peak:
            saved = self._load_budget() or {}
            peak = saved.get("observed_peak")
        cap = self.cand_capacity
        return dict(
            cand_capacity=cap,
            observed_peak=(int(peak) if peak else None),
            headroom_ratio=(round(cap / peak, 4)
                            if cap and peak else None),
        )

    def _memory_projection(self) -> dict:
        """Predicted resident bytes at the NEXT visited ladder class
        — the number that decides when V stops fitting VMEM (ROADMAP
        direction 2b) and when the visited set must tier to host DRAM
        (direction 1b). Past the ladder top the projection prices the
        next capacity step (``capacity * v_ladder_step``) instead: the
        cost of the next-size workload."""
        v_ladder = _ladder(self.v_min, self.capacity,
                           self.v_ladder_step)
        shards = getattr(self, "n_shards", 1)
        u_shard = -(-max(self._unique_states, 1) // shards)
        # the class the engine dispatched at the end of the run (the
        # same u > V_i counting the wave body's ladder switch uses),
        # and the step PAST it — past the ladder top, the next
        # capacity a bigger workload would need
        idx = sum(1 for V_i in v_ladder[:-1] if u_shard > V_i)
        cur = v_ladder[idx]
        nxt = (v_ladder[idx + 1] if idx + 1 < len(v_ladder)
               else self.capacity * self.v_ladder_step)
        F = self.frontier_capacity
        return dict(
            kind="next_v_class",
            current_rows=int(cur),
            next_rows=int(nxt),
            # vkeys [2, V + F]: the resident block the class keeps
            next_vkeys_bytes=int((nxt + F) * 8),
            # the streaming merge reads [0, V) and writes the merged
            # [0, V + NF) block back: the class-local scratch
            next_merge_scratch_bytes=int((nxt + F) * 8),
        )

    def _cand_overflow_message(self) -> str:
        if self._use_sparse():
            return (
                "pair-buffer overflow: a wave enabled more (row, slot) "
                f"pairs than cand_capacity={self.cand_capacity}, or one "
                f"row enabled more than pair_width={self._pair_width()} "
                "slots; raise the exceeded knob — the "
                "max_wave_candidates metric reports the observed peak"
            )
        return (
            "candidate-buffer overflow: a wave generated more valid "
            f"successors than cand_capacity={self.cand_capacity} (or, on "
            "the per-tile-payload fallback path, one tile exceeded its "
            "slice of that budget); re-run with a larger cand_capacity — "
            "the max_wave_candidates metric reports the observed peak"
        )

    # -- device programs ---------------------------------------------------

    def _build_programs(self, n0: int, tiered: bool = False):
        """``tiered=False`` (the default) builds the untiered
        seed/chunk pair — byte-identical to every round since 10.
        ``tiered=True`` builds the TIERED chunk program
        (stateright_tpu/tier.py): one wave per dispatch, whose carry
        additionally stages the wave's provisional winners
        (``pend_keys``/``pend_par``/``pend_n``) and whose entry phase
        COMMITS the previous wave's survivors under the host's
        cold-membership ``keep`` mask — count, frontier, parent log,
        and the hot-tier merge see exactly the truly-new rows, in the
        same key-sorted order the untiered engine commits."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        tier_mode = bool(tiered)
        enc = self.encoded
        # Device symmetry reduction (ops/canonical.py): when the
        # builder asked for symmetry, __init__ resolved the encoding's
        # DeviceRewriteSpec (or refused loudly). Every fingerprint
        # site below folds the CANONICAL block; the frontier keeps the
        # concrete states — the visited-through-representatives /
        # search-through-originals split of the host DFS.
        sym = self.sym_spec
        if sym is not None:
            from ..ops.canonical import canonicalize_rows, canonicalize_t
        ample_words = self._resolve_ample_words()
        props = list(self.model.properties())
        n_props = len(props)
        evt_idx = [
            i for i, p in enumerate(props)
            if p.expectation == Expectation.EVENTUALLY
        ]
        if evt_idx and max(evt_idx) >= 32:
            raise ValueError(
                "the TPU engine supports eventually properties only at "
                "property indices < 32; reorder properties() so eventually "
                f"properties come first (got index {max(evt_idx)})"
            )
        # the pair-state gather seam is backend-adaptive
        # (encoding.pair_step_seam)
        cpu_backend = jax.default_backend() == "cpu"
        K, W, F = enc.max_actions, enc.width, self.frontier_capacity
        C = self.capacity
        B_user = min(self.cand_capacity or F * K, F * K)
        target_states = self.builder._target_state_count
        target_depth = self.builder._target_max_depth
        waves_per_sync = self.waves_per_sync
        ebits_init = self._eventually_bits_init()
        track_paths = self.track_paths
        # Per-wave trace log (telemetry.py): when a tracer is active
        # the carry gains a small uint32[waves_per_sync, WL] log the
        # wave body appends one row to, downloaded WITH the packed
        # stats — one readback per chunk, async dispatch preserved.
        # Gated (and cache-keyed, _cache_extras) so untraced runs
        # compile the exact programs they always did.
        from ..telemetry import WAVE_LOG_LANES as WL

        trace_log = self._wave_log_enabled()
        # Parent log rows: every unique state (≤ C) gets one entry;
        # the F-row block write at a dynamic offset needs headroom.
        L = C + F if track_paths else 0

        # Ladder bottoms are deliberately coarse (waves below f_min
        # frontier rows are dispatch/sync-dominated, merges below v_min
        # cost single-digit ms) and the visited ladder is coarser than
        # the frontier ladder: XLA compile time grows superlinearly in
        # the number of (f, v) branch combinations, and each visited
        # step only changes merge-sort row counts.
        f_ladder = _ladder(self.f_min, F, self.ladder_step)
        v_ladder = _ladder(self.v_min, C, self.v_ladder_step)

        def clamp_keys(lo, hi):
            # All-ones is the visited-array padding sentinel; nudge
            # real fingerprints off it (mirrors the NonZero convention
            # at the other end of the range, ops/fingerprint.py).
            both = (lo == jnp.uint32(_SENT)) & (hi == jnp.uint32(_SENT))
            return lo, jnp.where(both, jnp.uint32(_SENT - 1), hi)

        # The visited array is INCREMENTALLY SORTED (round 10): rows
        # [0, u) are a dense sorted run of real keys, [u, C_pad) all-
        # ones sentinels. Each wave's merge_stage linear-merges the
        # ≤F sorted winner keys into the prefix (ops/merge.py) — the
        # invariant every streaming pass (membership, append) depends
        # on, and the one the module docstring's "sorted fingerprint
        # array" line has described since round 2. The F rows of
        # headroom let the class-local [0, V_v + NF) merged-block
        # write land inside the buffer even at V_v == C.
        C_pad = C + F

        def seed(init_rows):
            # Host upload boundary: init states arrive row-major and
            # transpose ONCE into the [W, F] resident layout (PERF.md
            # §layout — boundary transposes live here and at the
            # gather seams only).
            # Canonical visited keys from wave zero: the init rows
            # fingerprint through their orbit representatives, same
            # as every candidate wave below (the frontier still
            # stores the concrete init states).
            fp_rows = (canonicalize_rows(sym, init_rows, jnp)
                       if sym is not None else init_rows)
            lo0, hi0 = fingerprint_u32v(fp_rows, jnp)
            lo0, hi0 = clamp_keys(lo0, hi0)
            # Seed the SORTED invariant: the init keys are the first
            # visited prefix, so they go in (hi, lo)-ordered (an
            # n0-row sort, once, at upload).
            hi0, lo0 = lax.sort((hi0, lo0), num_keys=2)
            vkeys = (
                jnp.full((2, C_pad), _SENT, jnp.uint32)
                .at[0, :n0].set(lo0)
                .at[1, :n0].set(hi0)
            )
            frontier = jnp.zeros((W, F), dtype=jnp.uint32).at[
                :, :n0
            ].set(init_rows.T)
            fval = jnp.arange(F) < n0
            ebits = jnp.where(fval, jnp.uint32(ebits_init), jnp.uint32(0))
            extra = (
                dict(
                    wlog=jnp.zeros((waves_per_sync, WL), jnp.uint32),
                    wv_pairs=jnp.uint32(0),
                    wv_canon=jnp.uint32(0),
                )
                if trace_log
                else {}
            )
            return dict(
                vkeys=vkeys,
                **extra,
                plog=jnp.zeros((4, L), jnp.uint32),
                pl_n=jnp.uint32(0),
                frontier=frontier,
                fval=fval,
                ebits=ebits,
                n_frontier=jnp.uint32(n0),
                depth=jnp.int32(1),
                wchunk=jnp.int32(0),
                waves=jnp.uint32(0),
                gen_lo=jnp.uint32(n0),
                gen_hi=jnp.uint32(0),
                new=jnp.uint32(n0),
                disc_found=jnp.zeros(n_props, dtype=bool),
                disc_lo=jnp.zeros(n_props, dtype=jnp.uint32),
                disc_hi=jnp.zeros(n_props, dtype=jnp.uint32),
                overflow=jnp.bool_(n0 > C),
                f_overflow=jnp.bool_(False),
                c_overflow=jnp.bool_(False),
                e_overflow=jnp.bool_(False),
                max_cand=jnp.uint32(0),
                max_tile_cand=jnp.uint32(0),
                max_rowen=jnp.uint32(0),
                done=jnp.bool_(n0 == 0),
            )

        def class_params(fc: int):
            """Static per-frontier-class shapes."""
            F_f = f_ladder[fc]
            FK = F_f * K
            B_class = min(FK, B_user)
            compaction = FK > B_class
            want_tiles = -(-FK // self.tile_rows)
            if F_f == F:
                want_tiles = max(want_tiles, self.tiles)
            NT = _divisor_at_least(F_f, want_tiles)
            T = F_f // NT
            # Non-full-flat (per-tile payload) path: per-tile budget
            # gets slack over the even split (25% plus a floor) —
            # candidates skew across tiles. Capped at the lossless T*K.
            Bt = -(-B_class // NT)
            if NT > 1:
                Bt += max(8192, Bt // 4)
            Bt = min(Bt, T * K)
            B_eff = Bt * NT
            # Full-flat path: packed tile append needs one tile of
            # headroom past the whole-wave budget and has NO per-tile
            # overflow mode.
            Ba = (B_class + T * K) if compaction else FK
            full_flat = FK * W * 4 <= self.flat_budget_bytes
            return (F_f, FK, NT, T, Bt, B_eff, Ba, B_class, compaction,
                    full_flat)

        def merge_stage(c, v_class, B_eff, ck_lo, ck_hi, fetch, n_cand,
                        disc_found, disc_lo, disc_hi, c_overflow,
                        e_overflow, max_tile_cand, max_rowen=None,
                        wv_pairs=None, wv_canon=None):
            """The streaming-merge dedup (round 10, PERF.md
            §merge-kernel), class-collapsed per round 9: no switch
            branch ever returns more than one resident buffer.

            * ONE stable 3-lane ``B_eff``-row sort orders the wave's
              candidates by key with the buffer position as the value
              lane — the only per-wave sort whose row count exceeds
              the winner block, and it is B-scale: the ``(V_v +
              B)``-row concat sort this stage ran through round 9 is
              gone (the b·V term). Stability keeps equal keys in
              buffer order, so the adjacent-equal check makes the
              lowest-position candidate the intra-wave winner —
              exactly the old stable-concat-sort winner;
            * the v-ladder switch runs the MEMBERSHIP pass against
              the sorted visited prefix (``ops.merge.member_sorted``:
              the Pallas streaming kernel or the binary-search XLA
              implementation, per ``merge_impl``); its only branch output
              is the ``bool[B_eff]`` mask;
            * winners — in KEY order, which IS their order in the
              sorted candidate array — come out of one order-
              preserving 4-lane compaction sort (B-scale), yielding
              ``nf_pos`` (buffer positions, for the fetch gather) and
              the sorted winner keys the visited merge consumes;
            * ONE fetch-class switch per wave (the third ladder axis,
              sized to this wave's new_count) gathers the winners and
              updates frontier, ebits, and ``plog`` with class-local
              ``dynamic_update_slice`` blocks; rows past the block
              keep stale values, which ``fval`` masks everywhere;
            * a second v-class switch linear-merges the winner keys
              into ``vkeys`` (``ops.merge.merge_sorted`` + one
              class-local block write — no O(C)-row sort); its only
              branch output is the updated ``vkeys``;
            * the next carry is assembled OUTSIDE any switch.

            ``fetch(nf_row)`` returns ``(state_cols[W, n], par_lo,
            par_hi, row_ebits, key_lo, key_hi)`` — winner states come
            back COLUMN-major, matching the ``[W, F]`` resident
            frontier's block write (the recompute fetch produces this
            natively; gather-seam fetches transpose their row-major
            winner block once, the sanctioned seam copy). The keys
            still ride the SAME packed gather as the payload (PERF.md
            §gathers: one multi-lane gather, never N scalar
            gathers); with the fetch order now key-sorted they land
            in ``plog``'s child lanes ascending, same values the
            visited merge gets from the compaction sort."""
            NF = min(F, B_eff)

            cpos = jnp.arange(1, B_eff + 1, dtype=jnp.uint32)
            s_hi, s_lo, s_pos = lax.sort(
                (ck_hi, ck_lo, cpos), num_keys=2
            )
            real = ~(
                (s_hi == jnp.uint32(_SENT))
                & (s_lo == jnp.uint32(_SENT))
            )
            prev_same = jnp.concatenate(
                [
                    jnp.zeros(1, bool),
                    (s_hi[1:] == s_hi[:-1])
                    & (s_lo[1:] == s_lo[:-1]),
                ]
            )
            fresh = real & ~prev_same

            def member_core(vc):
                V_v = v_ladder[vc]

                def br(_):
                    return member_sorted(
                        c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                        s_lo, s_hi, impl=self.merge_impl,
                    )

                return br

            in_visited = lax.switch(
                v_class,
                [member_core(vc) for vc in range(len(v_ladder))],
                0,
            )
            is_new = fresh & ~in_visited
            new_count = jnp.sum(is_new)
            # Order-preserving winner compaction (ops/merge.py,
            # impl-adaptive: O(B) rank scatter on the XLA path,
            # one 4-lane B-scale sort on the Pallas/TPU path):
            # winners lead in key order, the order every consumer now
            # shares (fetch block, plog append, visited merge).
            nf_pos, w_lo, w_hi = compact_winners(
                is_new, s_pos, s_lo, s_hi, NF, impl=self.merge_impl
            )

            if tier_mode:
                # the commit phase (next dispatch) owns the visited-
                # capacity check against the HOT count; the cumulative
                # unique count may legitimately exceed device capacity
                overflow = c["overflow"]
            else:
                overflow = c["overflow"] | (
                    c["new"] + new_count.astype(jnp.uint32)
                    > jnp.uint32(C)
                )
            f_overflow = c["f_overflow"] | (new_count > F)

            # Fetch width: the payload gather is the merge's costliest
            # op at big shapes (paxos-5: a static min(F, B_eff)=1.57M-
            # row gather cost ~62ms/wave while typical waves produced
            # ~120k new states), so the fetch runs under its own class
            # switch sized to THIS wave's new_count.
            nf_ladder = [n for n in f_ladder if n < NF] + [NF]
            nf_class = jnp.int32(0)
            for n in nf_ladder[:-1]:
                nf_class = nf_class + (new_count > n).astype(jnp.int32)

            def make_fetch(NF_c):
                def br(_):
                    pos = nf_pos[:NF_c]
                    valid = jnp.arange(NF_c) < new_count
                    nf_row = jnp.where(valid, pos - 1, jnp.uint32(0))
                    (state_cols, par_lo, par_hi, row_ebits,
                     key_lo, key_hi) = fetch(nf_row)
                    z = jnp.uint32(0)
                    frontier2 = lax.dynamic_update_slice(
                        c["frontier"],
                        jnp.where(valid[None, :], state_cols,
                                  jnp.uint32(0)),
                        (z, z),
                    )
                    ebits2 = lax.dynamic_update_slice(
                        c["ebits"],
                        jnp.where(valid, row_ebits, 0),
                        (z,),
                    )
                    # Parent-log append: parent AND child limbs —
                    # the sorted visited merge re-orders vkeys rows
                    # every wave, so the round-9 derive-children-
                    # from-vkeys drain no longer has an insertion
                    # order to read; the log carries the child keys
                    # again (lanes 2-3), in the same key-sorted
                    # fetch order as the parents (_build_generated).
                    if not track_paths:
                        plog2 = c["plog"]
                    elif tier_mode:
                        # stage the parent limbs beside the staged
                        # states — the commit appends the SURVIVORS
                        # to the parent log, so no false-new row ever
                        # reaches the drain
                        plog2 = lax.dynamic_update_slice(
                            c["pend_par"],
                            jnp.stack([
                                jnp.where(valid, par_lo, 0),
                                jnp.where(valid, par_hi, 0),
                            ]),
                            (z, z),
                        )
                    else:
                        plog2 = lax.dynamic_update_slice(
                            c["plog"],
                            jnp.stack([
                                jnp.where(valid, par_lo, 0),
                                jnp.where(valid, par_hi, 0),
                                jnp.where(valid, key_lo, 0),
                                jnp.where(valid, key_hi, 0),
                            ]),
                            (z, c["pl_n"]),
                        )
                    return frontier2, ebits2, plog2

                return br

            next_frontier, next_ebits, plog_new = lax.switch(
                nf_class,
                [make_fetch(n) for n in nf_ladder],
                0,
            )

            # Visited append: linear-merge the sorted winner block
            # into the sorted prefix and write the merged run back as
            # ONE class-local block at offset 0 (rows past V_v + NF
            # are untouched sentinels by the C_pad headroom). The
            # branch output is vkeys alone — the same single-resident-
            # buffer switch discipline as the fetch switch above.
            def append_core(vc):
                V_v = v_ladder[vc]

                def br(_):
                    m_lo, m_hi = merge_sorted(
                        c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                        w_lo, w_hi, impl=self.merge_impl,
                    )
                    return lax.dynamic_update_slice(
                        c["vkeys"],
                        jnp.stack([m_lo, m_hi]),
                        (jnp.uint32(0), jnp.uint32(0)),
                    )

                return br

            if tier_mode:
                vkeys_new = c["vkeys"]  # the commit phase merges
            else:
                vkeys_new = lax.switch(
                    v_class,
                    [append_core(vc) for vc in range(len(v_ladder))],
                    0,
                )

            nf_valid_f = jnp.arange(F) < new_count
            if track_paths and not tier_mode:
                # Clamp to the NF rows the largest block write can
                # hold: on an f_overflow wave new_count can exceed
                # it, and _run raises before reconstruction — but
                # the live-count invariant should hold regardless.
                pl_n = c["pl_n"] + jnp.minimum(
                    new_count.astype(jnp.uint32), jnp.uint32(NF)
                )
            else:
                pl_n = c["pl_n"]

            g = u64_add(
                U64(c["gen_lo"], c["gen_hi"]),
                U64(n_cand.astype(jnp.uint32), jnp.uint32(0)),
            )
            if tier_mode:
                # DEFERRED COMMIT (stateright_tpu/tier.py): stage the
                # provisional winners — sorted keys here, states/ebits
                # already written into the frontier staging by the
                # fetch switch, parent limbs in pend_par — and leave
                # vkeys, the parent log, and every committed counter
                # untouched. compact_winners sentinel-pads past
                # new_count, so the staged key block is (hi, lo)-
                # sorted with a sentinel tail, exactly what the
                # commit's merge consumes.
                nc32 = new_count.astype(jnp.uint32)
                pk_lo = lax.dynamic_update_slice(
                    jnp.full(F, _SENT, jnp.uint32), w_lo[:NF], (0,)
                )
                pk_hi = lax.dynamic_update_slice(
                    jnp.full(F, _SENT, jnp.uint32), w_hi[:NF], (0,)
                )
                trace_extra = {}
                if trace_log:
                    trace_extra = dict(
                        wlog=c["wlog"],
                        pstash=c["pstash"],
                        wv_pairs=(n_cand if wv_pairs is None
                                  else wv_pairs).astype(jnp.uint32),
                        wv_canon=(jnp.uint32(0) if wv_canon is None
                                  else wv_canon.astype(jnp.uint32)),
                    )
                return dict(
                    **trace_extra,
                    **(dict(pend_par=plog_new) if track_paths
                       else {}),
                    vkeys=c["vkeys"],
                    plog=c["plog"],
                    pl_n=c["pl_n"],
                    frontier=next_frontier,
                    fval=nf_valid_f,
                    ebits=next_ebits,
                    n_frontier=nc32,
                    n_hot=c["n_hot"],
                    pend_keys=jnp.stack([pk_lo, pk_hi]),
                    pend_n=nc32,
                    pend_valid=jnp.bool_(True),
                    depth=c["depth"],
                    wchunk=c["wchunk"] + 1,
                    waves=c["waves"],
                    gen_lo=g.lo,
                    gen_hi=g.hi,
                    new=c["new"],
                    disc_found=disc_found,
                    disc_lo=disc_lo,
                    disc_hi=disc_hi,
                    overflow=overflow,
                    f_overflow=f_overflow,
                    c_overflow=c_overflow,
                    e_overflow=e_overflow,
                    max_cand=jnp.maximum(c["max_cand"], n_cand),
                    max_tile_cand=max_tile_cand,
                    max_rowen=(c["max_rowen"] if max_rowen is None
                               else max_rowen),
                    done=c["done"],
                )
            new = c["new"] + new_count.astype(jnp.uint32)
            all_disc = (
                jnp.all(disc_found) if n_props else jnp.bool_(False)
            )
            if target_states is None:
                target_hit = jnp.bool_(False)
            else:
                target_hit = new >= jnp.uint32(target_states)
            cont = (
                (new_count > 0)
                & ~all_disc
                & ~target_hit
                & ~overflow
                & ~f_overflow
                & ~c_overflow
                & ~e_overflow
            )
            trace_extra = {}
            if trace_log:
                # The wave log never crosses a switch boundary now —
                # it rides only the assembled carry; the body wrapper
                # writes this wave's row after the f-switch returns.
                trace_extra = dict(
                    wlog=c["wlog"],
                    wv_pairs=(n_cand if wv_pairs is None
                              else wv_pairs).astype(jnp.uint32),
                    wv_canon=(jnp.uint32(0) if wv_canon is None
                              else wv_canon.astype(jnp.uint32)),
                )
            return dict(
                **trace_extra,
                vkeys=vkeys_new,
                plog=plog_new,
                pl_n=pl_n,
                frontier=next_frontier,
                fval=nf_valid_f & cont,
                ebits=next_ebits,
                # The true row count even when the run stops (the
                # wave loop gates on done/fval, so this is safe) —
                # frontier rows past the class-local block are
                # STALE now, so tooling that reruns stages on a
                # captured carry (tools/profile_stages.py) reads
                # the live-row count here instead of scanning for
                # zero rows.
                n_frontier=new_count.astype(jnp.uint32),
                depth=jnp.where(cont, c["depth"] + 1, c["depth"]),
                wchunk=c["wchunk"] + 1,
                waves=c["waves"] + 1,
                gen_lo=g.lo,
                gen_hi=g.hi,
                new=new,
                disc_found=disc_found,
                disc_lo=disc_lo,
                disc_hi=disc_hi,
                overflow=overflow,
                f_overflow=f_overflow,
                c_overflow=c_overflow,
                e_overflow=e_overflow,
                max_cand=jnp.maximum(c["max_cand"], n_cand),
                max_tile_cand=max_tile_cand,
                max_rowen=(c["max_rowen"] if max_rowen is None
                           else max_rowen),
                done=~cont,
            )

        def make_wave(fc: int, v_class):
            (
                F_f, FK, NT, T, Bt, B_eff, Ba, B_class, compaction,
                full_flat,
            ) = class_params(fc)

            def wave(c):
                if target_depth is None:
                    expand = jnp.bool_(True)
                else:
                    expand = c["depth"] < target_depth

                # Dense expansion runs step_vec on state ROWS; the
                # resident frontier is [W, F], so the dense path pays
                # one seam transpose of its class prefix per wave
                # (the sparse path — the default for every registered
                # encoding — stays transpose-free up to the pair-step
                # gather seam).
                frontier_rows = c["frontier"][:, :F_f].T
                fval_f = c["fval"][:F_f]
                ebits_f = c["ebits"][:F_f]

                if full_flat:
                    # Expand the whole class prefix at once; the F_f*K
                    # successor tensor stays alive through the merge so
                    # only the ≤F winning rows are ever gathered.
                    ex = expand_frontier(
                        enc, props, evt_idx, frontier_rows, fval_f,
                        ebits_f, expand, with_repeats=False,
                        sym_spec=sym,
                    )
                    e_overflow = c["e_overflow"] | jnp.any(ex["trunc"])
                    disc_found, disc_lo, disc_hi = discovery_update(
                        props, ex, fval_f,
                        c["disc_found"], c["disc_lo"], c["disc_hi"],
                    )
                    flat, valid = ex["flat"], ex["v"]
                    wv_canon = None
                    if sym is not None:
                        cflat = canonicalize_rows(sym, flat, jnp)
                        k_lo, k_hi = fingerprint_u32v(cflat, jnp)
                        if trace_log:
                            wv_canon = jnp.sum(
                                valid & (cflat != flat).any(axis=1),
                                dtype=jnp.uint32,
                            )
                    else:
                        k_lo, k_hi = fingerprint_u32v(flat, jnp)
                    k_lo, k_hi = clamp_keys(k_lo, k_hi)
                    k_lo = jnp.where(valid, k_lo, jnp.uint32(_SENT))
                    k_hi = jnp.where(valid, k_hi, jnp.uint32(_SENT))
                    n_cand = jnp.sum(valid).astype(jnp.uint32)
                    if compaction:
                        # Tiled key compaction via PACKED APPEND: each
                        # tile sorts its keys (sort is superlinear: NT
                        # small sorts beat one big one; sentinel keys
                        # sort last, so valid rows lead) and writes its
                        # FULL sorted block at the running valid-count
                        # offset. Successive contiguous writes overlap
                        # the previous tile's sentinel tail, so valid
                        # candidates pack densely and no per-tile
                        # budget exists to overflow — only the
                        # whole-wave cand_capacity contract remains.
                        def tile_body(t, acc):
                            ck_lo, ck_hi, crow, app_off, tmax = acc
                            off = t * (T * K)
                            t_lo = lax.dynamic_slice(k_lo, (off,), (T * K,))
                            t_hi = lax.dynamic_slice(k_hi, (off,), (T * K,))
                            t_vd = lax.dynamic_slice(
                                valid, (off,), (T * K,)
                            )
                            rows = off.astype(jnp.uint32) + jnp.arange(
                                T * K, dtype=jnp.uint32
                            )
                            tc = jnp.sum(t_vd).astype(jnp.uint32)
                            tmax = jnp.maximum(tmax, tc)
                            s_hi, s_lo, s_row = lax.sort(
                                (t_hi, t_lo, rows), num_keys=2
                            )
                            o = (app_off,)
                            ck_lo = lax.dynamic_update_slice(ck_lo, s_lo, o)
                            ck_hi = lax.dynamic_update_slice(ck_hi, s_hi, o)
                            crow = lax.dynamic_update_slice(crow, s_row, o)
                            return ck_lo, ck_hi, crow, app_off + tc, tmax

                        ck_lo, ck_hi, crow, _app_off, tile_max = (
                            lax.fori_loop(
                                0,
                                NT,
                                tile_body,
                                (
                                    jnp.full(Ba, _SENT, jnp.uint32),
                                    jnp.full(Ba, _SENT, jnp.uint32),
                                    jnp.zeros(Ba, jnp.uint32),
                                    jnp.uint32(0),
                                    jnp.uint32(0),
                                ),
                            )
                        )
                        c_overflow = c["c_overflow"] | (
                            n_cand > jnp.uint32(B_class)
                        )
                    else:
                        ck_lo, ck_hi = k_lo, k_hi
                        crow = jnp.arange(FK, dtype=jnp.uint32)
                        c_overflow = c["c_overflow"]
                        tile_max = n_cand

                    # Packed fetch (PERF.md §gathers): candidate meta
                    # (key limbs + source row) rides ONE 3-lane gather;
                    # frontier-side meta (ebits + parent fp) another.
                    meta3 = jnp.stack([ck_lo, ck_hi, crow], axis=1)
                    fr_meta = jnp.stack(
                        [ex["ebits"]]
                        + ([ex["f_lo"], ex["f_hi"]] if track_paths
                           else []),
                        axis=1,
                    )

                    def fetch(nf_row):
                        m = meta3[nf_row]
                        srow = m[:, 2]
                        q = fr_meta[srow // jnp.uint32(K)]
                        return (
                            # gather seam: winners come off the row-
                            # major flat tensor; one small [n, W]
                            # transpose feeds the [W, F] block write.
                            flat[srow].T,
                            q[:, 1] if track_paths else None,
                            q[:, 2] if track_paths else None,
                            q[:, 0],
                            m[:, 0],
                            m[:, 1],
                        )

                    return merge_stage(
                        c, v_class, Ba, ck_lo, ck_hi, fetch,
                        n_cand, disc_found, disc_lo, disc_hi,
                        c_overflow, e_overflow,
                        jnp.maximum(c["max_tile_cand"], tile_max),
                        wv_canon=wv_canon,
                    )

                # Per-tile payload path (successor tensor too big to
                # keep): expansion, fingerprinting, compaction, and a
                # Bt-row payload gather all happen inside each tile.
                # Payload lanes are PACKED into one [B_eff, EP] buffer
                # (payload_pack layout) so the merge fetch is a single
                # multi-lane gather (PERF.md §gathers); the key limbs
                # are kept as separate 1-D arrays too — the merge sort
                # concatenates those.
                EP = payload_width(W, track_paths)

                def tile_body(t, acc):
                    (
                        ck_lo, ck_hi, cpay,
                        dfound, dlo, dhi, n_cand, c_ovf, e_ovf, tmax,
                    ) = acc
                    off = t * T
                    tf = lax.dynamic_slice(
                        frontier_rows, (off, 0), (T, W)
                    )
                    tfv = lax.dynamic_slice(c["fval"], (off,), (T,))
                    teb = lax.dynamic_slice(c["ebits"], (off,), (T,))
                    ex = expand_frontier(
                        enc, props, evt_idx, tf, tfv, teb, expand,
                        with_repeats=False, sym_spec=sym,
                    )
                    e_ovf = e_ovf | jnp.any(ex["trunc"])
                    dfound, dlo, dhi = discovery_update(
                        props, ex, tfv, dfound, dlo, dhi
                    )
                    flat, valid = ex["flat"], ex["v"]
                    # Canonical keys; the payload keeps the CONCRETE
                    # successor rows (the hits lane rides the sparse
                    # and full-flat paths only — this fallback path
                    # reports wv_canon=0).
                    fp_flat = (canonicalize_rows(sym, flat, jnp)
                               if sym is not None else flat)
                    k_lo, k_hi = fingerprint_u32v(fp_flat, jnp)
                    k_lo, k_hi = clamp_keys(k_lo, k_hi)
                    k_lo = jnp.where(valid, k_lo, jnp.uint32(_SENT))
                    k_hi = jnp.where(valid, k_hi, jnp.uint32(_SENT))
                    t_cand = jnp.sum(valid)
                    tmax = jnp.maximum(tmax, t_cand.astype(jnp.uint32))
                    c_ovf = c_ovf | (t_cand > Bt)
                    rows = jnp.arange(T * K, dtype=jnp.uint32)
                    s_hi, s_lo, s_row = lax.sort(
                        (k_hi, k_lo, rows), num_keys=2
                    )
                    s_hi, s_lo, s_row = s_hi[:Bt], s_lo[:Bt], s_row[:Bt]
                    prow = s_row // jnp.uint32(K)
                    blk = payload_pack(
                        jnp, flat[s_row], s_lo, s_hi,
                        ex["ebits"][prow],
                        ex["f_lo"][prow] if track_paths else None,
                        ex["f_hi"][prow] if track_paths else None,
                    )
                    o = t * Bt
                    ck_lo = lax.dynamic_update_slice(ck_lo, s_lo, (o,))
                    ck_hi = lax.dynamic_update_slice(ck_hi, s_hi, (o,))
                    cpay = lax.dynamic_update_slice(cpay, blk, (o, 0))
                    return (
                        ck_lo, ck_hi, cpay,
                        dfound, dlo, dhi,
                        n_cand + t_cand.astype(jnp.uint32), c_ovf, e_ovf,
                        tmax,
                    )

                (
                    ck_lo, ck_hi, b_pay,
                    disc_found, disc_lo, disc_hi, n_cand, c_overflow,
                    e_overflow, tile_max,
                ) = lax.fori_loop(
                    0,
                    NT,
                    tile_body,
                    (
                        jnp.full(B_eff, _SENT, jnp.uint32),
                        jnp.full(B_eff, _SENT, jnp.uint32),
                        jnp.zeros((B_eff, EP), jnp.uint32),
                        c["disc_found"],
                        c["disc_lo"],
                        c["disc_hi"],
                        jnp.uint32(0),
                        c["c_overflow"],
                        c["e_overflow"],
                        jnp.uint32(0),
                    ),
                )

                def fetch(nf_row):
                    st, p_lo, p_hi, eb_w, k_lo_w, k_hi_w = (
                        payload_unpack(b_pay[nf_row], W, track_paths)
                    )
                    # gather seam: one [n, W] winner-block transpose.
                    return st.T, p_lo, p_hi, eb_w, k_lo_w, k_hi_w

                return merge_stage(
                    c, v_class, B_eff, ck_lo, ck_hi, fetch,
                    n_cand, disc_found, disc_lo, disc_hi,
                    c_overflow, e_overflow,
                    jnp.maximum(c["max_tile_cand"], tile_max),
                )

            return wave

        # -- sparse action dispatch (PERF.md §paxos) ---------------------
        #
        # The dense wave pays O(F·K) successor construction,
        # fingerprints and compaction sorts even when only a sliver of
        # the K slots is enabled (paxos check 3: ~200x padding). The
        # sparse wave instead:
        #   1. evaluates the encoding's CHEAP per-slot enabled
        #      predicate over [F, K] (field extracts, no successors),
        #   2. packs it to per-row bitmaps and peels up to pair_width
        #      enabled slots per row with a lowest-set-bit loop —
        #      elementwise passes over [F, K/32] lanes, no sort,
        #   3. compacts the (row, slot) pairs with tiled 1-lane
        #      packed-append sorts over the F×pair_width grid (a
        #      K/pair_width-times smaller sort than the dense path's),
        #   4. runs the table-driven per-pair transition, fingerprints,
        #      and the shared merge on ≤B real candidates only.
        # Every O(F·K) stage that remains is a pure elementwise pass.
        sparse_boundary = not has_trivial_boundary(enc)

        import jax as _jax

        use_sparse = self._use_sparse()
        if use_sparse:
            _res_shape = _jax.eval_shape(
                enc.step_slot_vec,
                _jax.ShapeDtypeStruct((W,), jnp.uint32),
                _jax.ShapeDtypeStruct((), jnp.uint32),
            )
            sparse_has_trunc = isinstance(_res_shape, tuple)
            # The transposed pair step: COLUMN-major successor block
            # out — the shape fingerprint_u32v_t folds coalesced and
            # the [W, F] frontier's class-local DUS consumes without
            # a transpose. The INPUT seam is backend-adaptive and
            # lives in ONE place (encoding.pair_step_seam, PERF.md
            # §layout): TPU row-gathers off a per-wave seam
            # transpose; XLA:CPU column-gathers the resident buffer
            # directly (measured at paxos-4 peak-wave shapes: seam-T
            # + row gather 1.13s vs direct column gather 0.86s vs
            # the old row-major 1.35s step+fp).
            step_cols, make_pair_states = pair_step_seam(
                enc, cpu_backend
            )
        else:
            sparse_has_trunc = False

        def sparse_class_params(fc: int) -> dict:
            """Static per-frontier-class shapes of the sparse wave —
            ONE home shared by ``make_sparse_wave`` and the memory
            ledger's per-class staging rows (``_build_info``), so the
            plan the ``memory_plan`` event declares cannot drift from
            the classes the wave programs compile."""
            F_f = f_ladder[fc]
            EV = self._pair_width()
            NPg = F_f * EV
            B_p = min(B_user, NPg)
            compaction = NPg > B_p
            want_tiles = -(-NPg // self.tile_rows)
            if F_f == F:
                want_tiles = max(want_tiles, self.tiles)
            if compaction:
                # Packed append needs ONE TILE of headroom past the
                # pair budget; with few tiles that headroom is
                # NPg/NT ≈ half the grid (ABD ordered 2c/3s: Ba blew
                # to 2.8x the budget and the 128x-padded [Ba, 1] step
                # temps OOMed the chip). Keep the headroom ≤ B_p/4.
                want_tiles = max(
                    want_tiles, -(-(4 * NPg) // max(B_p, 1))
                )
            NT = _divisor_at_least(F_f, want_tiles) if compaction else 1
            T = F_f // NT
            Ba = (B_p + T * EV) if compaction else NPg
            # Memory-lean mode: when the [Ba, W] successor tensor would
            # blow the flat budget (paxos check 4: 28M pairs × 19 lanes
            # ≈ 2GB at merge-time peak), fingerprint pairs in chunks
            # without materializing successors, and RECOMPUTE the ≤F
            # winning rows' successors at fetch time. Extra cost: one
            # step_slot pass over the winners; saving: the whole [Ba,W]
            # tensor is never alive.
            # Chunk-mode gate and chunk count use the PADDED row cost
            # (~512 B/row on TPU for any [N, W<=32] buffer — PERF.md):
            # gating on unpadded W*4 bytes let an ABD-ordered probe
            # build ~86GB of padded step temps at Ba=8.4M (round 5).
            row_pad = -(-W // 128) * 512
            chunked = compaction and (
                Ba * row_pad > self.flat_budget_bytes
            )
            NC = Bc = 0
            if chunked:
                NC = -(-(Ba * row_pad) // self.flat_budget_bytes)
                Bc = -(-Ba // NC)
                Ba = NC * Bc  # pad so chunks tile it exactly
            # Fetch mode (PERF.md §gathers): keep the [Ba, W+3] packed
            # candidate payload (successor lanes + key limbs + parent
            # row) alive through the merge when its PADDED residency —
            # 512 B per 128-lane group on TPU, so ceil(EP/128)*512
            # B/row (a hardcoded 512 undercounted packed payloads
            # wider than 128 lanes by the full multiple, ADVICE r5) —
            # fits the flat budget, so the winners' fetch is ONE
            # multi-lane gather + one frontier-meta gather. Otherwise
            # fetch recomputes winners' successors from a packed
            # 4-lane (key_lo, key_hi, pair, slot) meta gather (the
            # chunked path never materializes [Ba, W] at all).
            pay_row_pad = -(-payload_width(W, track_paths) // 128) * 512
            pay_fetch = (not chunked) and (
                Ba * pay_row_pad <= self.flat_budget_bytes
            )
            return dict(
                F_f=F_f, EV=EV, NPg=NPg, B_p=B_p,
                compaction=compaction, NT=NT, T=T, Ba=Ba,
                row_pad=row_pad, chunked=chunked, NC=NC, Bc=Bc,
                pay_fetch=pay_fetch,
            )

        def make_sparse_wave(fc: int, v_class):
            p = sparse_class_params(fc)
            F_f, EV, B_p = p["F_f"], p["EV"], p["B_p"]
            NT, T, Ba = p["NT"], p["T"], p["Ba"]
            chunked, pay_fetch = p["chunked"], p["pay_fetch"]
            NC, Bc = p["NC"], p["Bc"]

            def wave(c):
                if target_depth is None:
                    expand = jnp.bool_(True)
                else:
                    expand = c["depth"] < target_depth
                frontier_t = c["frontier"][:, :F_f]
                fval_f = c["fval"][:F_f]
                ebits_f = c["ebits"][:F_f]
                cond, eb, f_lo, f_hi = frontier_props_t(
                    enc, props, evt_idx, frontier_t, fval_f, ebits_f,
                    sym_spec=sym,
                )

                pidx, live, pslot, cnt, n_pairs, pair_ovf, tile_max = (
                    sparse_pair_candidates(
                        # the FULL resident buffer + explicit class
                        # width: a strided column-prefix slice as a
                        # loop operand would materialize a per-wave
                        # copy (see the n_rows note on the pipeline)
                        enc, c["frontier"], fval_f, expand,
                        EV=EV, B_p=B_p, NT=NT, T=T,
                        mask_budget_cells=self.mask_budget_cells,
                        Ba=Ba, n_rows=F_f, ample_words=ample_words,
                    )
                )
                # Pair-state gather seam: the shared backend policy
                # (encoding.pair_step_seam) — pair rows are < F_f by
                # construction, so the CPU column gather can read the
                # full carry buffer (aliases for free).
                pair_states = make_pair_states(c["frontier"],
                                               frontier_t)
                c_overflow = c["c_overflow"] | pair_ovf
                e_overflow = c["e_overflow"]
                needs_scan = sparse_boundary or sparse_has_trunc

                def eval_pairs(pidx_b, live_b, slot_b):
                    """fingerprint keys + transposed successors +
                    validity (+ scan stats) for a block of compacted
                    pairs. ``step_cols`` returns ``(succ_t[W, n],
                    trunc|None, hard|None)``: trunc marks pairs pruned
                    by an internal encoding bound (compiled envelope
                    counts) — excluded from candidates and, when
                    in-boundary, raised as e_overflow (the dense
                    truncation contract); hard marks unrepresentable
                    successors (un-harvested history transitions) —
                    excluded and raised REGARDLESS of boundary, since
                    the garbage successor can't faithfully evaluate
                    it. The fingerprint fold runs lane-major over the
                    [W, n] block (fingerprint_u32v_t, the 1.65x
                    coalesced fold)."""
                    prow_b = pidx_b // jnp.uint32(EV)
                    succ_t, ptr_b, hard_b = step_cols(
                        pair_states(prow_b), slot_b
                    )
                    eov = jnp.bool_(False)
                    if hard_b is not None:
                        eov = jnp.any(live_b & hard_b)
                        live_b = live_b & ~hard_b
                    if sparse_boundary:
                        inb = within_boundary_cols(enc, succ_t)
                        ok = live_b & inb
                    else:
                        ok = live_b
                    if ptr_b is not None:
                        eov = eov | jnp.any(ok & ptr_b)
                        ok = ok & ~ptr_b
                    hits = None
                    if sym is not None:
                        # Canonical fingerprint, concrete successor
                        # block: succ_t flows untouched to the fetch /
                        # frontier write — the canonical block exists
                        # only to feed the fold (and the hits lane).
                        canon_t = canonicalize_t(sym, succ_t, jnp)
                        lo, hi = fingerprint_u32v_t(canon_t, jnp)
                        if trace_log:
                            hits = jnp.sum(
                                ok & (canon_t != succ_t).any(axis=0),
                                dtype=jnp.uint32,
                            )
                    else:
                        lo, hi = fingerprint_u32v_t(succ_t, jnp)
                    lo, hi = clamp_keys(lo, hi)
                    lo = jnp.where(ok, lo, jnp.uint32(_SENT))
                    hi = jnp.where(ok, hi, jnp.uint32(_SENT))
                    return lo, hi, ok, prow_b, eov, succ_t, hits

                if chunked:
                    # Chunked fingerprint pass: the [Ba, W] successor
                    # tensor is never materialized.
                    def fchunk(ti, acc):
                        cl, ch, nc, eov, rok, wvc = acc
                        off = ti * Bc
                        pidx_b = lax.dynamic_slice(pidx, (off,), (Bc,))
                        live_b = lax.dynamic_slice(live, (off,), (Bc,))
                        slot_b = lax.dynamic_slice(pslot, (off,), (Bc,))
                        lo, hi, ok, prow_b, ev, _succ, hits = eval_pairs(
                            pidx_b, live_b, slot_b
                        )
                        cl = lax.dynamic_update_slice(cl, lo, (off,))
                        ch = lax.dynamic_update_slice(ch, hi, (off,))
                        if hits is not None:
                            wvc = wvc + hits
                        if needs_scan:
                            nc = nc + jnp.sum(ok, dtype=jnp.uint32)
                            rok = rok.at[
                                jnp.where(ok, prow_b, jnp.uint32(F_f))
                            ].max(jnp.uint32(1), mode="drop")
                        return cl, ch, nc, eov | ev, rok, wvc

                    (ck_lo, ck_hi, nc_acc, eov_acc, row_ok,
                     wv_canon) = lax.fori_loop(
                        0,
                        NC,
                        fchunk,
                        (
                            jnp.full(Ba, _SENT, jnp.uint32),
                            jnp.full(Ba, _SENT, jnp.uint32),
                            jnp.uint32(0),
                            jnp.bool_(False),
                            jnp.zeros(F_f if needs_scan else 1,
                                      jnp.uint32),
                            jnp.uint32(0),
                        ),
                    )
                    e_overflow = e_overflow | eov_acc
                    if needs_scan:
                        has_succ = row_ok != 0
                        n_cand = nc_acc
                    else:
                        has_succ = cnt > 0
                        n_cand = n_pairs
                else:
                    (ck_lo, ck_hi, pair_ok, prow, eov,
                     succ_t, wv_canon) = eval_pairs(pidx, live, pslot)
                    if pay_fetch:
                        # Without this barrier XLA fuses the pair-step
                        # producer (frontier/params/sendtab gathers +
                        # the whole transition ALU) separately into
                        # BOTH consumers — the fingerprint path and
                        # the payload concat — running every pair-stage
                        # gather twice per wave (seen in the round-5
                        # device trace as duplicate [Ba, *] gather
                        # fusions). Materialize once; the extra
                        # [W, Ba] write is bandwidth-cheap.
                        ck_lo, ck_hi, succ_t, prow = (
                            lax.optimization_barrier(
                                (ck_lo, ck_hi, succ_t, prow)
                            )
                        )
                    e_overflow = e_overflow | eov
                    if needs_scan:
                        # Terminal = no surviving successor at all:
                        # scatter-max surviving pairs onto their rows.
                        row_ok = jnp.zeros(F_f, jnp.uint32).at[
                            jnp.where(pair_ok, prow, jnp.uint32(F_f))
                        ].max(jnp.uint32(1), mode="drop")
                        has_succ = row_ok != 0
                        n_cand = jnp.sum(pair_ok, dtype=jnp.uint32)
                    else:
                        has_succ = cnt > 0
                        n_cand = n_pairs
                terminal = fval_f & ~has_succ & expand
                evt_cex = terminal & (eb != 0)
                exd = dict(
                    cond=cond, ebits=eb, evt_cex=evt_cex,
                    f_lo=f_lo, f_hi=f_hi,
                )
                disc_found, disc_lo, disc_hi = discovery_update(
                    props, exd, fval_f,
                    c["disc_found"], c["disc_lo"], c["disc_hi"],
                )

                if pay_fetch:
                    # Packed candidate payload kept alive through the
                    # merge: winners' states, key limbs, and parent
                    # meta (ebits + parent fp, pre-gathered per pair as
                    # one [Ba, 1-3] gather) ride ONE multi-lane fetch
                    # gather — on TPU a gather costs ~12ns/row
                    # regardless of lane count (PERF.md §gathers).
                    # Payload staging is the ONE place the successor
                    # block transposes back to rows: gathers win
                    # row-major (the sanctioned seam copy; payload
                    # gathers measured equal either way).
                    fr_meta = jnp.stack(
                        [eb] + ([f_lo, f_hi] if track_paths else []),
                        axis=1,
                    )
                    pm = fr_meta[prow]
                    pay = payload_pack(
                        jnp, succ_t.T, ck_lo, ck_hi, pm[:, 0],
                        pm[:, 1] if track_paths else None,
                        pm[:, 2] if track_paths else None,
                    )

                    def fetch(nf_row):
                        st, p_lo, p_hi, eb_w, k_lo_w, k_hi_w = (
                            payload_unpack(pay[nf_row], W, track_paths)
                        )
                        # seam transpose of the small winner block
                        return st.T, p_lo, p_hi, eb_w, k_lo_w, k_hi_w
                else:
                    # Recompute mode (chunked or over-budget payload):
                    # winners' successors are recomputed from their
                    # (row, slot) pairs — exact by the
                    # SparseEncodedModel purity contract. Index-feeding
                    # gathers stay 1-D, and step_cols hands back the
                    # [W, n] block directly — this path is
                    # transpose-free end to end.
                    def fetch(nf_row):
                        pidx_w = pidx[nf_row]
                        par_row = pidx_w // jnp.uint32(EV)
                        succ_w_t, _, _ = step_cols(
                            pair_states(par_row), pslot[nf_row]
                        )
                        return (
                            succ_w_t,
                            f_lo[par_row] if track_paths else None,
                            f_hi[par_row] if track_paths else None,
                            eb[par_row],
                            ck_lo[nf_row],
                            ck_hi[nf_row],
                        )

                return merge_stage(
                    c, v_class, Ba, ck_lo, ck_hi, fetch,
                    n_cand, disc_found, disc_lo, disc_hi,
                    c_overflow, e_overflow,
                    jnp.maximum(c["max_tile_cand"], tile_max),
                    jnp.maximum(c["max_rowen"], jnp.max(cnt)),
                    wv_pairs=n_pairs,
                    wv_canon=wv_canon,
                )

            return wave

        def body(c):
            n_f = c["n_frontier"]
            # tiered runs dispatch the v-ladder on the HOT count (the
            # rows actually resident) — the whole point of the tier:
            # the on-device membership/merge scale with hot, not with
            # the cumulative unique count
            u = c["n_hot"] if tier_mode else c["new"]
            f_class = jnp.int32(0)
            for F_i in f_ladder[:-1]:
                f_class = f_class + (n_f > jnp.uint32(F_i)).astype(jnp.int32)
            v_class = jnp.int32(0)
            for V_i in v_ladder[:-1]:
                v_class = v_class + (u > jnp.uint32(V_i)).astype(jnp.int32)
            mk = make_sparse_wave if use_sparse else make_wave
            c2 = lax.switch(
                f_class,
                [mk(fc, v_class) for fc in range(len(f_ladder))],
                c,
            )
            if trace_log and tier_mode:
                # the wave-log row can't be written yet — new/unique
                # settle at the NEXT dispatch's commit; stash the
                # wave-time lanes for it
                c2 = dict(
                    c2,
                    pstash=jnp.stack(
                        [
                            n_f,
                            c2["wv_pairs"],
                            c2["gen_lo"] - c["gen_lo"],
                            c["depth"].astype(jnp.uint32),
                            f_class.astype(jnp.uint32),
                            v_class.astype(jnp.uint32),
                            c2["wv_canon"],
                            jnp.uint32(0),
                        ]
                    ),
                )
                return c2
            if trace_log:
                # One wave-log row (telemetry.WAVE_LOG_FIELDS): the
                # pre/post carry delta gives candidates (gen counter)
                # and new states; wv_pairs carries the enabled
                # popcount out of the merge. Row index = wchunk (the
                # within-chunk wave number, always < waves_per_sync
                # while the loop runs).
                row = jnp.stack(
                    [
                        n_f,
                        c2["wv_pairs"],
                        c2["gen_lo"] - c["gen_lo"],
                        c2["new"] - c["new"],
                        c2["new"],
                        c["depth"].astype(jnp.uint32),
                        f_class.astype(jnp.uint32),
                        v_class.astype(jnp.uint32),
                        # optional lane 8 (WAVE_LOG_OPT_FIELDS):
                        # candidates whose canonical form differed
                        # from the raw successor this wave.
                        c2["wv_canon"],
                    ]
                )
                c2 = dict(
                    c2,
                    wlog=lax.dynamic_update_slice(
                        c2["wlog"], row[None, :],
                        (c["wchunk"], jnp.int32(0)),
                    ),
                )
            return c2

        # Tiered dispatches run exactly ONE wave: the commit phase
        # needs the host's membership verdict between waves.
        wps_eff = 1 if tier_mode else waves_per_sync

        def cond(c):
            return ~c["done"] & (c["wchunk"] < wps_eff)

        if not tier_mode:
            # Tooling hook: the un-jitted wave body, re-traceable on a
            # captured carry (stateright_tpu/wavewall.py times/lowers
            # ONE wave in isolation — the chunk program hides per-wave
            # structure inside the while_loop) or on eval_shape
            # abstract carries (stateright_tpu/analysis/lint.py walks
            # the traced switch branches for the no-branch-pad-concat
            # rule and the carry-copy-bytes estimator, never
            # allocating buffers). The tiered build must not clobber
            # the untiered hook the lint/profiler fixtures read.
            self._wave_body = body

        # Memory ledger (stateright_tpu/memplan.py): per-ladder-class
        # staging rows, recorded AT BUILD so the memory_plan event is
        # a function of the (f, v) class — the shapes come from the
        # SAME class_params/sparse_class_params the wave programs
        # compile from. CHUNKED memory-lean classes additionally land
        # an ``engine_mode`` record (emitted as a telemetry event at
        # run time): until round 12 that flip was observable only as
        # a docstring behavior.
        from ..memplan import buffer_entry, plan_total

        EPw = payload_width(W, track_paths)
        _classes = []
        _modes = []
        for fc in range(len(f_ladder)):
            if use_sparse:
                p = sparse_class_params(fc)
                staging = [
                    buffer_entry("enabled_bits",
                                 (p["F_f"], mask_words(K)), "uint32"),
                    buffer_entry("pair_index", (3, p["Ba"]), "uint32"),
                    buffer_entry("cand_keys", (2, p["Ba"]), "uint32"),
                ]
                if sym is not None:
                    # the canonicalization pass materializes the
                    # canonical successor block beside the concrete
                    # one (per chunk when memory-lean)
                    staging.append(buffer_entry(
                        "canonical_t",
                        (W, p["Bc"] if p["chunked"] else p["Ba"]),
                        "uint32",
                    ))
                if p["chunked"]:
                    mode = "chunked"
                    staging.append(
                        buffer_entry("succ_chunk", (W, p["Bc"]),
                                     "uint32")
                    )
                    _modes.append(dict(
                        engine=type(self).__name__, mode="chunked",
                        f_class=fc, buffer_rows=p["Ba"],
                        chunks=p["NC"], chunk_rows=p["Bc"],
                        row_pad_bytes=p["row_pad"],
                        flat_budget_bytes=self.flat_budget_bytes,
                    ))
                elif p["pay_fetch"]:
                    mode = "pay_fetch"
                    staging.append(
                        buffer_entry("cand_payload", (p["Ba"], EPw),
                                     "uint32")
                    )
                else:
                    mode = "recompute"
                    staging.append(
                        buffer_entry("succ_t", (W, p["Ba"]), "uint32")
                    )
                _classes.append(dict(
                    f_class=fc, mode=mode, frontier_rows=p["F_f"],
                    pair_width=p["EV"], budget_rows=p["B_p"],
                    tiles=p["NT"], buffer_rows=p["Ba"],
                    staging=staging, staging_bytes=plan_total(staging),
                ))
            else:
                (F_f, FK, NT_d, _T, _Bt, B_eff, Ba_d, B_class,
                 _compaction, full_flat) = class_params(fc)
                if full_flat:
                    mode = "full_flat"
                    rows = Ba_d
                    staging = [
                        buffer_entry("succ_flat", (FK, W), "uint32"),
                        buffer_entry("cand_keys", (3, rows), "uint32"),
                    ]
                    if sym is not None:
                        staging.append(buffer_entry(
                            "canonical_rows", (FK, W), "uint32"
                        ))
                else:
                    mode = "tile_payload"
                    rows = B_eff
                    staging = [
                        buffer_entry("cand_keys", (2, rows), "uint32"),
                        buffer_entry("cand_payload", (rows, EPw),
                                     "uint32"),
                    ]
                _classes.append(dict(
                    f_class=fc, mode=mode, frontier_rows=F_f,
                    budget_rows=B_class, tiles=NT_d, buffer_rows=rows,
                    staging=staging, staging_bytes=plan_total(staging),
                ))
        from ..memplan import v_class_entries

        _NFmax = min(F, max(c["buffer_rows"] for c in _classes))
        if not tier_mode:
            self._build_info = dict(
                classes=_classes,
                v_classes=v_class_entries(v_ladder, _NFmax),
                engine_modes=_modes,
            )

        def pack_stats(c):
            scalars = jnp.stack(
                [
                    c["done"].astype(jnp.uint32),
                    c["overflow"].astype(jnp.uint32),
                    c["f_overflow"].astype(jnp.uint32),
                    c["depth"].astype(jnp.uint32),
                    c["waves"],
                    jnp.sum(c["fval"]).astype(jnp.uint32),
                    c["gen_lo"],
                    c["gen_hi"],
                    c["new"],
                    c["c_overflow"].astype(jnp.uint32),
                    c["e_overflow"].astype(jnp.uint32),
                ]
            )
            parts = [
                scalars,
                c["disc_found"].astype(jnp.uint32),
                c["disc_lo"],
                c["disc_hi"],
                jnp.stack([c["max_cand"], c["max_tile_cand"],
                           c["max_rowen"]]),
            ]
            if trace_log:
                # The wave log rides the SAME packed readback — no
                # extra sync (waves_per_sync × WL uint32 ≈ 2 KB).
                parts.append(c["wlog"].reshape(-1))
            return jnp.concatenate(parts)

        def chunk(carry):
            c = dict(carry, wchunk=jnp.int32(0))
            c = lax.while_loop(cond, body, c)
            return c, pack_stats(c)

        if not tier_mode:
            return jax.jit(seed), jax.jit(chunk, donate_argnums=0)

        # -- the tiered chunk program (stateright_tpu/tier.py) -----------

        def tier_commit(c, keep):
            """Commit the PREVIOUS wave's survivors under the host's
            cold-membership ``keep`` mask: order-preserving compaction
            of the staged rows (one F-scale stable sort — kept rows
            stay in key order, the order every consumer shares), the
            hot-tier merge under the v-ladder switch sized by the HOT
            count, the parent-log append, and the counter/termination
            updates the untiered merge_stage would have made. A carry
            with ``pend_valid=False`` (the handoff dispatch) passes
            through untouched."""
            pv = c["pend_valid"]
            rowsF = jnp.arange(F, dtype=jnp.uint32)
            keepm = keep & (rowsF < c["pend_n"])
            conf = jnp.sum(keepm).astype(jnp.uint32)
            drop = jnp.where(keepm, jnp.uint32(0), jnp.uint32(1))
            _, perm = lax.sort((drop, rowsF), num_keys=1)
            confv = rowsF < conf
            front_c = jnp.where(
                confv[None, :], c["frontier"][:, perm], jnp.uint32(0)
            )
            eb_c = jnp.where(confv, c["ebits"][perm], jnp.uint32(0))
            k_lo = jnp.where(
                confv, c["pend_keys"][0][perm], jnp.uint32(_SENT)
            )
            k_hi = jnp.where(
                confv, c["pend_keys"][1][perm], jnp.uint32(_SENT)
            )

            v_class = jnp.int32(0)
            for V_i in v_ladder[:-1]:
                v_class = v_class + (
                    c["n_hot"] > jnp.uint32(V_i)
                ).astype(jnp.int32)

            def app(vc):
                V_v = v_ladder[vc]

                def br(_):
                    m_lo, m_hi = merge_sorted(
                        c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                        k_lo, k_hi, impl=self.merge_impl,
                    )
                    return lax.dynamic_update_slice(
                        c["vkeys"],
                        jnp.stack([m_lo, m_hi]),
                        (jnp.uint32(0), jnp.uint32(0)),
                    )

                return br

            vkeys_m = lax.switch(
                v_class, [app(vc) for vc in range(len(v_ladder))], 0
            )

            def sel(a, b):
                return jnp.where(pv, a, b)

            confp = jnp.where(pv, conf, jnp.uint32(0))
            new2 = c["new"] + confp
            n_hot2 = c["n_hot"] + confp
            all_disc = (
                jnp.all(c["disc_found"]) if n_props
                else jnp.bool_(False)
            )
            if target_states is None:
                target_hit = jnp.bool_(False)
            else:
                target_hit = new2 >= jnp.uint32(target_states)
            overflow = c["overflow"] | (
                pv & (n_hot2 > jnp.uint32(C))
            )
            cont = (
                pv & (conf > 0) & ~all_disc & ~target_hit
                & ~overflow & ~c["f_overflow"] & ~c["c_overflow"]
                & ~c["e_overflow"]
            )
            out = dict(
                c,
                vkeys=sel(vkeys_m, c["vkeys"]),
                frontier=sel(front_c, c["frontier"]),
                ebits=sel(eb_c, c["ebits"]),
                fval=sel(confv & cont, c["fval"]),
                n_frontier=sel(conf, c["n_frontier"]),
                n_hot=n_hot2,
                new=new2,
                depth=jnp.where(cont, c["depth"] + 1, c["depth"]),
                waves=c["waves"] + jnp.where(
                    pv, jnp.uint32(1), jnp.uint32(0)
                ),
                overflow=overflow,
                done=sel(~cont, c["done"]),
                pend_valid=jnp.bool_(False),
                pend_n=jnp.uint32(0),
            )
            if track_paths:
                p_lo = jnp.where(
                    confv, c["pend_par"][0][perm], jnp.uint32(0)
                )
                p_hi = jnp.where(
                    confv, c["pend_par"][1][perm], jnp.uint32(0)
                )
                rows4 = jnp.stack([
                    p_lo,
                    p_hi,
                    jnp.where(confv, k_lo, jnp.uint32(0)),
                    jnp.where(confv, k_hi, jnp.uint32(0)),
                ])
                plog2 = lax.dynamic_update_slice(
                    c["plog"], rows4, (jnp.uint32(0), c["pl_n"])
                )
                out["plog"] = sel(plog2, c["plog"])
                out["pl_n"] = c["pl_n"] + confp
            if trace_log:
                st = c["pstash"]
                row = jnp.stack([
                    st[0], st[1], st[2], conf, new2,
                    st[3], st[4], st[5], st[6],
                ])
                out["wlog"] = lax.dynamic_update_slice(
                    c["wlog"], row[None, :],
                    (jnp.int32(0), jnp.int32(0)),
                )
            return out

        def tier_chunk(carry, keep):
            c = dict(carry, wchunk=jnp.int32(0))
            c = tier_commit(c, keep)
            c = lax.while_loop(cond, body, c)
            return c, pack_stats(c)

        return jax.jit(tier_chunk, donate_argnums=0)

    def _vec_fp(self, row) -> int:
        """Host fingerprints use the same all-ones clamp as the device
        keys (clamp_keys): the parent log stores clamped child
        fingerprints, so host and device keys must be defined
        identically or a state whose true 64-bit fingerprint is
        all-ones (p ~ 2^-64, same class as the NonZero convention)
        would fail path reconstruction."""
        fp = super()._vec_fp(row)
        if fp == 0xFFFFFFFFFFFFFFFF:
            fp = (0xFFFFFFFE << 32) | 0xFFFFFFFF
        return fp

    def _consume_extra_stats(self, extra: np.ndarray) -> None:
        if extra.size >= 3:
            self.metrics["max_wave_candidates"] = int(extra[0])
            self.metrics["max_tile_candidates"] = int(extra[1])
            #: exact per-row enabled-slot peak (sparse mode), the
            #: auto-budget pair_width sizer — computed from the mask
            #: counts, so it is correct even on an overflow run.
            self.metrics["max_row_enabled"] = int(extra[2])

    # -- reconstruction ----------------------------------------------------

    def _capture_final(self, carry) -> None:
        self._final_tables = (
            carry["vkeys"],
            carry["plog"],
            carry["pl_n"],
            carry["new"],
        )

    def _build_generated(self):
        """Materialize child→parent from the append-only device log
        (the lazy download; roots are simply absent from the log).

        The log carries BOTH key pairs (round 10): parent limbs in
        lanes 0-1, child limbs in lanes 2-3. Round 9 derived the
        children positionally from ``vkeys`` (the visited append WAS
        the insertion order); the incrementally-sorted visited array
        re-orders its rows every wave, so the log is the insertion-
        order record again."""
        if self.generated is None:
            tier = self._tier_generated_map()
            if tier is not None:
                # tiered runs drain the log host-side per dispatch
                # (stateright_tpu/tier.py): the accumulation IS the
                # insertion-order record
                self.generated = tier
                return self.generated
            _vkeys, plog, pl_n, _new = (
                np.asarray(a) for a in self._final_tables
            )
            n = int(pl_n)
            child = (
                plog[3, :n].astype(np.uint64) << np.uint64(32)
            ) | plog[2, :n].astype(np.uint64)
            parent = (
                plog[1, :n].astype(np.uint64) << np.uint64(32)
            ) | plog[0, :n].astype(np.uint64)
            self.generated = {
                int(c): (int(p) if p else None)
                for c, p in zip(child.tolist(), parent.tolist())
            }
        return self.generated
