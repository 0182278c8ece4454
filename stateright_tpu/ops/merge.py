"""Streaming sorted-set kernels: the visited-dedup merge family.

Round 10 (PERF.md §merge-kernel) makes the engines' visited set
INCREMENTALLY SORTED, which turns the per-wave dedup from a
from-scratch ``(V + B)``-row stable 3-lane ``lax.sort`` — the
irreducible b·V term the round-5..9 work left standing (~3-20ms at
C=2²¹ on chip) — into two O(V + B) streaming passes over sorted runs:

* :func:`member_sorted` — for each of B sorted query keys, is it
  present in the sorted visited prefix (the dedup membership test);
* :func:`merge_sorted` — merge the ≤F sorted winner keys into the
  sorted visited prefix (the visited append).

Keys are 2-limb SoA ``uint32`` pairs ordered lexicographically by
``(hi, lo)`` with the all-ones pair as the trailing padding sentinel
(the engines' ``clamp_keys`` convention keeps real fingerprints off
it). Both inputs must be sorted ascending; ties order A-first (the
"visited wins" rule the old stable concat-sort implemented). A may
contain duplicates and sentinel tails; semantics are exact multiset
membership, so callers mask sentinel queries themselves (the engines
already gate on ``real``).

Each op ships two implementations, selected by the engines'
``merge_impl`` knob (auto: Pallas on TPU, XLA on the CPU):

* ``impl="pallas"`` / ``"pallas_interpret"`` — a hand-written Pallas
  kernel: the merged output is partitioned into ``block``-row tiles by
  a Merge Path diagonal search (:func:`merge_path_starts`, computed in
  plain XLA — G+1 binary searches, negligible), and each grid step
  DMAs one lane-aligned window of each input from HBM and produces
  its tile with a rank-based block merge (broadcast compare + one-hot
  reduce — all VPU-shaped work, no sort, no data-dependent control
  flow). The member kernel ORs each tile's bits into one
  VMEM-resident output, so the grid runs sequentially
  (``"arbitrary"``). ``pallas_interpret`` runs the SAME kernel
  through the Pallas interpreter, which is what lets a CPU-only CI
  pin the kernel's semantics in tier-1 (tests/test_merge.py);
  tests/test_chip_compile.py compiles it for a v5e chip.

* ``impl="xla"`` — a pure-XLA O(B log V + M) implementation for the
  CPU: membership is a vectorized 2-limb binary search
  (log₂ V unrolled gather steps — fast on CPU where the sequential
  gathers are cache-friendly, catastrophic on TPU per the round-5
  primitive microbenchmarks — PERF.md "Primitive costs", re-runnable
  via ``tools/profile_stages.py --micro`` — which is exactly why
  the Pallas path exists); the merge computes winner destinations by
  binary search, scatters the ≤F winner flags, and assembles the
  merged array with one cumsum + two gathers — no sort anywhere.

Neither implementation contains an O(V)-row ``lax.sort``; the
codegen-shape audit (tests/test_merge.py::test_no_visited_scale_sort)
pins that for the whole steady-state wave body.
"""

from __future__ import annotations

from functools import partial

_SENT = 0xFFFFFFFF

#: merged rows per Pallas grid step. 512 keeps the block-merge's
#: [block, block] compare/one-hot temporaries at 1 MB (uint32) — VPU
#: lane-aligned and far under VMEM — while amortizing the per-step
#: window loads.
DEFAULT_BLOCK = 512

#: the merge_impl vocabulary the engines accept (None = auto).
IMPLS = ("xla", "pallas", "pallas_interpret")


def default_impl() -> str:
    """Auto policy, by backend: the compiled Pallas kernel on the TPU
    (the XLA binary search's log₂ V sequential gathers are what it
    replaces there), the XLA implementation on the CPU (where the
    binary search is cache-friendly and the kernel could only be
    interpreted, orders of magnitude slower)."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_impl(impl):
    if impl is None:
        return default_impl()
    if impl not in IMPLS:
        raise ValueError(
            f"merge_impl must be one of {IMPLS} or None (auto), "
            f"got {impl!r}"
        )
    return impl


# -- 2-limb key compares ---------------------------------------------------


def _lt(ah, al, bh, bl):
    """(ah, al) < (bh, bl) lexicographic."""
    return (ah < bh) | ((ah == bh) & (al < bl))


def _le(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al <= bl))


# -- XLA implementation ---------------------------------------------------


def _count_in_sorted(a_lo, a_hi, q_lo, q_hi, strict: bool):
    """Per query, how many A keys compare {<, <=} it — a vectorized
    2-limb binary search (the log₂ V unrolled gather ladder; 1-D lane
    ops only, pinned by the lint's merge:xla trace)."""
    import jax.numpy as jnp

    Na = a_lo.shape[0]
    nq = q_lo.shape[0]
    lo = jnp.zeros(nq, jnp.uint32)
    hi = jnp.full(nq, Na, jnp.uint32)
    for _ in range(max(1, int(Na).bit_length())):
        mid = (lo + hi) >> 1
        am_lo = a_lo[mid]
        am_hi = a_hi[mid]
        if strict:
            go_right = _lt(am_hi, am_lo, q_hi, q_lo)
        else:
            go_right = _le(am_hi, am_lo, q_hi, q_lo)
        upd = lo < hi
        lo = jnp.where(upd & go_right, mid + jnp.uint32(1), lo)
        hi = jnp.where(upd & ~go_right, mid, hi)
    return lo


def _member_xla(a_lo, a_hi, q_lo, q_hi):
    import jax.numpy as jnp

    Na = a_lo.shape[0]
    if Na == 0:
        return jnp.zeros(q_lo.shape[0], bool)
    cnt = _count_in_sorted(a_lo, a_hi, q_lo, q_hi, strict=True)
    idx = jnp.minimum(cnt, jnp.uint32(Na - 1))
    return (
        (a_lo[idx] == q_lo) & (a_hi[idx] == q_hi)
        & (cnt < jnp.uint32(Na))
    )


def _merge_xla(a_lo, a_hi, b_lo, b_hi):
    """Sorted merge with NO sort: B-side destinations by binary
    search (B is the small side — the ≤F winner block), then one
    M-row flag scatter + cumsum + two gathers assemble the output."""
    import jax.numpy as jnp

    Na, Nb = a_lo.shape[0], b_lo.shape[0]
    if Nb == 0:
        return a_lo, a_hi
    if Na == 0:
        return b_lo, b_hi
    M = Na + Nb
    cnt_le = _count_in_sorted(a_lo, a_hi, b_lo, b_hi, strict=False)
    # strictly increasing (j + #A<=b_j), so the scatter is collision-
    # free and every destination is < M.
    dest_b = jnp.arange(Nb, dtype=jnp.uint32) + cnt_le
    from_b = (
        jnp.zeros(M, jnp.uint32)
        .at[dest_b]
        .set(jnp.uint32(1), unique_indices=True)
    )
    k = jnp.cumsum(from_b, dtype=jnp.uint32)  # inclusive B-rank
    is_b = from_b != 0
    bi = jnp.minimum(
        jnp.maximum(k, jnp.uint32(1)) - jnp.uint32(1),
        jnp.uint32(Nb - 1),
    )
    ai = jnp.minimum(
        jnp.arange(M, dtype=jnp.uint32) - k, jnp.uint32(Na - 1)
    )
    return (
        jnp.where(is_b, b_lo[bi], a_lo[ai]),
        jnp.where(is_b, b_hi[bi], a_hi[ai]),
    )


# -- Merge Path partition (shared by both Pallas kernels) ------------------


def merge_path_starts(a_lo, a_hi, b_lo, b_hi, block: int):
    """``int32[G + 1]`` A-side splits of the merged sequence at every
    ``block``-row output boundary (G = ceil((Na+Nb)/block)): output
    tile ``g`` is the merge of ``A[starts[g]:starts[g+1]]`` with
    ``B[g*block - starts[g] : (g+1)*block - starts[g+1]]``, each range
    at most ``block`` wide. Ties split A-first (the stable "visited
    wins" order). Plain XLA — G+1 parallel diagonal binary searches."""
    import jax.numpy as jnp

    Na, Nb = int(a_lo.shape[0]), int(b_lo.shape[0])
    M = Na + Nb
    G = max(1, -(-M // block))
    d = jnp.minimum(jnp.arange(G + 1, dtype=jnp.int32) * block, M)
    lo = jnp.maximum(d - Nb, 0)
    hi = jnp.minimum(d, Na)
    for _ in range(max(1, int(Na).bit_length() + 1)):
        mid = (lo + hi) >> 1
        ai = jnp.clip(mid, 0, max(Na - 1, 0))
        bj = jnp.clip(d - mid - 1, 0, max(Nb - 1, 0))
        # split <= mid  <=>  b[d-mid-1] merges before a[mid]
        p = _lt(b_hi[bj], b_lo[bj], a_hi[ai], a_lo[ai])
        upd = lo < hi
        hi = jnp.where(upd & p, mid, hi)
        lo = jnp.where(upd & ~p, mid + 1, lo)
    return lo


# -- Pallas kernels --------------------------------------------------------
#
# Layout rules the TPU compiler (Mosaic) imposes, each of which the
# interpreter never checked (PERF.md §bring-up):
#
# * the Merge Path ``starts`` table is a scalar-prefetch operand (SMEM):
#   a scalar read from a VMEM ref must sit on a 128-aligned lane;
# * both inputs stay in HBM (``memory_space=ANY``) and each grid step
#   DMAs a ``block + 128``-wide window whose start is rounded DOWN to a
#   multiple of 128: the real window rows are masked by global index,
#   so the widening costs compares, never correctness. The visited
#   prefix at paxos-4 sizes (2 x 2.6M uint32, ~21 MB) outgrows VMEM;
# * reductions run over int32 (Mosaic has no unsigned reductions);
#   keys are selected by a one-hot whose sum has one non-zero term, so
#   a bitcast round-trip through int32 is exact;
# * every pairwise matrix is [column operand, row operand]; the column
#   copies come from an in-kernel transpose of the (2, W) window.

_LANE = 128


def _window(block: int) -> int:
    """Rows per DMA window: a ``block``-wide range whose start is
    rounded down to the lane tile spans at most ``block + 127`` rows;
    the window stays a whole number of lane tiles."""
    return -(-block // _LANE) * _LANE + _LANE


def _pad_soa(lo, hi, block: int):
    """``(2, round_up(n, 128) + window)`` sentinel-padded SoA copy: every
    aligned window a grid step may DMA lies inside it."""
    import jax.numpy as jnp

    n = lo.shape[0]
    pad_to = -(-n // _LANE) * _LANE + _window(block)
    out = jnp.full((2, pad_to), _SENT, jnp.uint32)
    return out.at[0, :n].set(lo).at[1, :n].set(hi)


def _load_windows(a_hbm, a_al, a_buf, b_hbm, b_al, b_buf, sem):
    """DMA the two aligned windows into VMEM; returns them row-major
    ``(2, W)`` and column-major ``(W, 2)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W = a_buf.shape[1]
    ca = pltpu.make_async_copy(
        a_hbm.at[:, pl.ds(a_al, W)], a_buf, sem.at[0]
    )
    cb = pltpu.make_async_copy(
        b_hbm.at[:, pl.ds(b_al, W)], b_buf, sem.at[1]
    )
    ca.start()
    cb.start()
    ca.wait()
    cb.wait()
    a = a_buf[...]
    b = b_buf[...]
    return a, a.T, b, b.T


def _aligned(x):
    from jax.experimental import pallas as pl

    return pl.multiple_of((x // _LANE) * _LANE, _LANE)


def _merge_kernel(starts_ref, a_hbm, b_hbm, out_ref, a_buf, b_buf, sem,
                  *, block, M):
    """One output tile of the streaming merge: rank-based block merge
    of the tile's A/B windows. Validity masks (by global row index)
    keep rows outside ``[a_s, a_e)`` / ``[b_s, b_e)`` from counting;
    a valid row's rank is its offset in its own range plus the number
    of valid other-side rows that merge before it (ties A-first), so
    the one-hot assembly hits each of the tile's ``rows`` outputs
    exactly once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    d0 = g * block
    a_s = starts_ref[g]
    a_e = starts_ref[g + 1]
    b_s = d0 - a_s
    b_e = jnp.minimum(d0 + block, M) - a_e
    a_al = _aligned(a_s)
    b_al = _aligned(b_s)
    a, at, b, bt = _load_windows(a_hbm, a_al, a_buf, b_hbm, b_al, b_buf,
                                 sem)
    W = a_buf.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    a_ok_c = (a_al + col >= a_s) & (a_al + col < a_e)
    b_ok_c = (b_al + col >= b_s) & (b_al + col < b_e)
    a_ok_r = (a_al + row >= a_s) & (a_al + row < a_e)
    b_ok_r = (b_al + row >= b_s) & (b_al + row < b_e)
    # [A rows, B cols]: valid B strictly before each A (A-first ties)
    b_lt_a = _lt(b[1:2, :], b[0:1, :], at[:, 1:2], at[:, 0:1]) & b_ok_r
    # [B rows, A cols]: valid A at-or-before each B
    a_le_b = _le(a[1:2, :], a[0:1, :], bt[:, 1:2], bt[:, 0:1]) & a_ok_r
    pos_a = a_al + col - a_s + jnp.sum(
        b_lt_a.astype(jnp.int32), axis=1, keepdims=True
    )
    pos_b = b_al + col - b_s + jnp.sum(
        a_le_b.astype(jnp.int32), axis=1, keepdims=True
    )
    out_pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    oh_a = (pos_a == out_pos) & a_ok_c  # [W, block]
    oh_b = (pos_b == out_pos) & b_ok_c
    covered = jnp.sum(
        oh_a.astype(jnp.int32), axis=0, keepdims=True
    ) + jnp.sum(oh_b.astype(jnp.int32), axis=0, keepdims=True)
    for lane in range(2):
        ka = jax.lax.bitcast_convert_type(at[:, lane:lane + 1], jnp.int32)
        kb = jax.lax.bitcast_convert_type(bt[:, lane:lane + 1], jnp.int32)
        merged = jnp.sum(
            jnp.where(oh_a, ka, 0), axis=0, keepdims=True
        ) + jnp.sum(jnp.where(oh_b, kb, 0), axis=0, keepdims=True)
        out_ref[lane:lane + 1, :] = jnp.where(
            covered > 0,
            jax.lax.bitcast_convert_type(merged, jnp.uint32),
            jnp.uint32(_SENT),
        )


def _member_kernel(starts_ref, a_hbm, q_hbm, out_ref, a_buf, q_buf, sem,
                   *, block, M):
    """One merged tile's membership bits: a query in ``[q_s, q_e)``
    matches iff an equal A key sits in ``[a_s - 1, a_e)`` — ties order
    A-first, so the equal A key (A is sorted) is the nearest A at or
    before the query's merge position, and Merge Path puts it no
    earlier than one row left of the tile's A range. The A window is
    aligned below ``a_s - 1`` so that predecessor is always in it.
    Each query belongs to exactly one tile: the tile ORs its masked
    bits into the VMEM-resident ``(rows, 128)`` output."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

    d0 = g * block
    a_s = starts_ref[g]
    a_e = starts_ref[g + 1]
    q_s = d0 - a_s
    q_e = jnp.minimum(d0 + block, M) - a_e
    a_al = _aligned(jnp.maximum(a_s - 1, 0))
    q_al = _aligned(q_s)
    _, at, q, _ = _load_windows(a_hbm, a_al, a_buf, q_hbm, q_al, q_buf,
                                sem)
    W = a_buf.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    a_ok_c = (a_al + col >= a_s - 1) & (a_al + col < a_e)
    q_ok_r = (q_al + row >= q_s) & (q_al + row < q_e)
    eq = (
        (at[:, 0:1] == q[0:1, :]) & (at[:, 1:2] == q[1:2, :]) & a_ok_c
    )
    hit = jnp.where(
        q_ok_r, jnp.max(eq.astype(jnp.int32), axis=0, keepdims=True), 0
    )
    r0 = q_al // _LANE
    for r in range(W // _LANE):
        rows = pl.ds(r0 + r, 1)
        out_ref[rows, :] = out_ref[rows, :] | hit[:, r * _LANE:(r + 1) * _LANE]


def _vmem_limit(block: int, resident: int) -> int:
    """Scoped-VMEM request: the ``[W, W]`` and ``[W, block]`` int32
    temporaries the block merge keeps live (a generous 12 of them), the
    two DMA windows, and the caller's resident output block (twice, for
    the pipeline's buffers), plus 4 MiB of slack."""
    W = _window(block)
    temps = 12 * W * max(W, block) * 4 + 2 * 2 * W * 4
    return temps + 2 * resident + (4 << 20)


def _pallas(kernel, starts, x, y, out_spec, out_shape, out_dtype, block,
            interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G = starts.shape[0] - 1
    W = _window(block)
    # inside the sharded engine's shard_map the output varies over the
    # mesh axes its inputs vary over
    vma = jax.typeof(x).vma | jax.typeof(y).vma
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((2, W), jnp.uint32),
                pltpu.VMEM((2, W), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            # the member kernel's output accumulates across steps
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                block, out_spec.block_shape[0] * out_spec.block_shape[1] * 4
            ),
        ),
        interpret=interpret,
    )(starts, x, y)


def _merge_pallas(a_lo, a_hi, b_lo, b_hi, block, interpret):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    Na, Nb = int(a_lo.shape[0]), int(b_lo.shape[0])
    M = Na + Nb
    if Nb == 0:
        return a_lo, a_hi
    if Na == 0:
        return b_lo, b_hi
    G = max(1, -(-M // block))
    out = _pallas(
        partial(_merge_kernel, block=block, M=M),
        merge_path_starts(a_lo, a_hi, b_lo, b_hi, block),
        _pad_soa(a_lo, a_hi, block),
        _pad_soa(b_lo, b_hi, block),
        pl.BlockSpec((2, block), lambda g, starts: (0, g)),
        (2, G * block), jnp.uint32, block, interpret,
    )
    return out[0, :M], out[1, :M]


def _member_pallas(a_lo, a_hi, q_lo, q_hi, block, interpret):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    Na, Nq = int(a_lo.shape[0]), int(q_lo.shape[0])
    if Nq == 0:
        return jnp.zeros(0, bool)
    if Na == 0:
        return jnp.zeros(Nq, bool)
    M = Na + Nq
    q = _pad_soa(q_lo, q_hi, block)
    rows = q.shape[1] // _LANE
    out = _pallas(
        partial(_member_kernel, block=block, M=M),
        merge_path_starts(a_lo, a_hi, q_lo, q_hi, block),
        _pad_soa(a_lo, a_hi, block),
        q,
        pl.BlockSpec((rows, _LANE), lambda g, starts: (0, 0)),
        (rows, _LANE), jnp.int32, block, interpret,
    )
    return out.reshape(-1)[:Nq] != 0


# -- public entry points ---------------------------------------------------


def member_sorted(a_lo, a_hi, q_lo, q_hi, *, impl: str = "xla",
                  block: int = DEFAULT_BLOCK):
    """``bool[Nq]``: for each sorted query key, whether it occurs in
    the sorted array A. Exact multiset semantics (sentinel queries
    match A's sentinel tail; callers mask)."""
    if impl == "xla":
        return _member_xla(a_lo, a_hi, q_lo, q_hi)
    return _member_pallas(
        a_lo, a_hi, q_lo, q_hi, block,
        interpret=(impl == "pallas_interpret"),
    )


def merge_sorted(a_lo, a_hi, b_lo, b_hi, *, impl: str = "xla",
                 block: int = DEFAULT_BLOCK):
    """``(lo[Na+Nb], hi[Na+Nb])``: the sorted merge of two sorted
    2-limb key arrays, A-first on ties; sentinel tails merge to the
    tail. No O(Na)-row sort on either implementation."""
    if impl == "xla":
        return _merge_xla(a_lo, a_hi, b_lo, b_hi)
    return _merge_pallas(
        a_lo, a_hi, b_lo, b_hi, block,
        interpret=(impl == "pallas_interpret"),
    )


def compact_winners(is_new, pos, lo, hi, nf: int, *, impl: str):
    """``(nf_pos[nf], w_lo[nf], w_hi[nf])``: the winner rows
    (``is_new``) of the key-sorted candidate arrays, compacted
    order-preserving to the first ``nf`` rows and sentinel-padded —
    winners stay in KEY order, the order the engines' fetch gather,
    parent-log append, and visited merge all share.

    Implementation-adaptive like the streaming passes: ``xla`` (the
    CPU implementation) uses an O(B) rank scatter — collision-free
    destinations from an inclusive-rank cumsum, non-winners routed
    past the output and dropped — which on CPU replaces the 4-lane
    B-row compaction sort that was the XLA path's single
    costliest dedup stage (736 ms/wave at paxos-4 shapes, PERF.md
    §merge-kernel). The ``pallas`` impls keep the 4-lane stable sort:
    TPU scatters serialize, B-scale sorts do not."""
    import jax.numpy as jnp
    from jax import lax

    B = pos.shape[0]
    if impl == "xla":
        rank = jnp.cumsum(is_new.astype(jnp.uint32))
        # winner dests rank-1 are unique in [0, B); non-winners are
        # routed to unique slots in [B, 2B). Both tails land past the
        # nf-row output and drop (nf <= B), so indices stay globally
        # unique — the contract `unique_indices` asserts.
        dest = jnp.where(
            is_new,
            rank - jnp.uint32(1),
            jnp.uint32(B) + jnp.arange(B, dtype=jnp.uint32),
        )
        out = jnp.full((3, nf), _SENT, jnp.uint32)
        out = out.at[:, dest].set(
            jnp.stack([pos, lo, hi]),
            mode="drop", unique_indices=True,
        )
        return out[0], out[1], out[2]
    okey = jnp.where(
        is_new,
        jnp.arange(B, dtype=jnp.uint32),
        jnp.uint32(_SENT),
    )
    _, nf_pos, w_lo, w_hi = lax.sort((okey, pos, lo, hi), num_keys=1)
    # rows past the winner count carry arbitrary non-winner lanes
    # after the sort; sentinel them like the scatter path does.
    valid = jnp.arange(nf, dtype=jnp.uint32) < jnp.sum(
        is_new, dtype=jnp.uint32
    )
    s = jnp.uint32(_SENT)
    return (
        jnp.where(valid, nf_pos[:nf], s),
        jnp.where(valid, w_lo[:nf], s),
        jnp.where(valid, w_hi[:nf], s),
    )
