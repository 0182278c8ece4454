"""Vectorized 64-bit state fingerprinting over uint32 lanes.

The device counterpart of the reference's stable fixed-key hasher
(src/lib.rs:329-375): encoded states are fixed-width ``uint32``
vectors; their digest is a splitmix64-style fold over the lanes with
hard-coded keys, built from the limb arithmetic in
:mod:`stateright_tpu.ops.u64` so jax.numpy (device) and numpy (host)
produce bit-identical results. Zero is reserved as the empty-slot
marker in the visited table, so a zero digest maps to 1 (the
``NonZeroU64`` convention, src/lib.rs:329-337).
"""

from __future__ import annotations

from typing import Any, Tuple

import numpy as np

from .u64 import U64, u64_add, u64_const, u64_mul_const, u64_shr, u64_xor

_SEED = 0x51A7E12D_0BADC0DE
_GOLDEN = 0x9E3779B97F4A7C15
_MIX1 = 0xBF58476D1CE4E5B9
_MIX2 = 0x94D049BB133111EB


def splitmix64(z: U64, xp=np) -> U64:
    """The splitmix64 finalizer (Steele et al.), elementwise."""
    z = u64_xor(z, u64_shr(z, 30))
    z = u64_mul_const(z, _MIX1, xp)
    z = u64_xor(z, u64_shr(z, 27))
    z = u64_mul_const(z, _MIX2, xp)
    z = u64_xor(z, u64_shr(z, 31))
    return z


def _golden(i: int) -> int:
    return _GOLDEN * (i + 1) & 0xFFFFFFFFFFFFFFFF


def _unrolled_lanes(w: int, xp) -> int:
    """How many lanes the fold unrolls: all of them on numpy and on
    the TPU, only the first under XLA:CPU.

    XLA:CPU's fusion emitters evaluate an unrolled fold that is fused
    into an indexed consumer (a dynamic-update-slice, a gather, a
    scalar dynamic-slice) without sharing its subexpressions, so the
    cost per element grows exponentially with W: 29 ms per element at
    W=11 against about 2 us at W=4 (jaxlib 0.9.0). A chunk that
    writes parent fingerprints into the parent log ran 30-60 s per
    wave that way. A loop cannot fuse, so on the CPU the remaining
    lanes fold in a ``fori_loop`` and every consumer reads one
    materialized digest. The first lane stays unrolled so the carry
    varies over the same mesh axes as the lanes inside a shard_map."""
    if xp is np:
        return w
    import jax

    return min(w, 1) if jax.default_backend() == "cpu" else w


def _fold_loop(start: int, w: int, lane, zero, h: U64) -> U64:
    """Lanes ``[start, w)`` of the fold as a ``fori_loop`` (jax only)."""
    import jax.numpy as jnp
    from jax import lax

    g = np.array([_golden(i) for i in range(w)], dtype=np.uint64)
    g_lo = jnp.asarray((g & 0xFFFFFFFF).astype(np.uint32))
    g_hi = jnp.asarray((g >> 32).astype(np.uint32))

    def body(i, h):
        lane_i = u64_add(U64(lane(i), zero), U64(g_lo[i], g_hi[i]))
        return tuple(splitmix64(u64_xor(U64(*h), lane_i), jnp))

    return U64(*lax.fori_loop(start, w, body, tuple(h)))


def _fold_lanes(w: int, lane, zero, xp) -> Tuple[Any, Any]:
    """THE fingerprint fold — one body for every lane layout.

    ``lane(i)`` returns lane ``i`` of the batch (the only thing the
    row-major and lane-major entry points differ in); the seed, the
    per-lane GOLDEN offsets, the splitmix64 chain, and the NonZeroU64
    zero-reservation live here exactly once, so a hash change cannot
    silently fork the host-side digests (drain, seeds) from the
    device-side ones (the transposed engines).
    """
    h = U64(zero + xp.uint32(_SEED & 0xFFFFFFFF), zero + xp.uint32(_SEED >> 32))
    unrolled = _unrolled_lanes(w, xp)
    for i in range(unrolled):
        lane_i = u64_add(U64(lane(i), zero), u64_const(_golden(i), xp))
        h = splitmix64(u64_xor(h, lane_i), xp)
    if unrolled < w:
        h = _fold_loop(unrolled, w, lane, zero, h)
    # Reserve 0 as "empty" (NonZeroU64 convention).
    both_zero = (h.lo == 0) & (h.hi == 0)
    lo = xp.where(both_zero, xp.uint32(1), h.lo)
    return lo, h.hi


def fingerprint_u32v(vec: Any, xp=np) -> Tuple[Any, Any]:
    """Digest uint32 state vectors along the last axis.

    ``vec``: uint32[..., W] → ``(lo, hi)``: uint32[...] each, never
    both zero. The fold is sequential over the W lanes (W is small and
    static; it unrolls everywhere but XLA:CPU, see
    :func:`_unrolled_lanes`) and vectorized over every leading axis.
    """
    vec = xp.asarray(vec, dtype=xp.uint32)
    zero = xp.zeros(vec.shape[:-1], dtype=xp.uint32)
    return _fold_lanes(
        vec.shape[-1], lambda i: vec[..., i], zero, xp
    )


def fingerprint_u32v_t(vec_t: Any, xp=np) -> Tuple[Any, Any]:
    """Digest TRANSPOSED (lane-major) state blocks along axis 0.

    ``vec_t``: uint32[W, ...] → ``(lo, hi)``: uint32[...] each —
    bit-identical to ``fingerprint_u32v(vec_t.T)`` (same
    :func:`_fold_lanes` body, only the lane accessor differs). This
    is the fold the engines run over the column-major ``[W, N]``
    resident layout (PERF.md §layout): lane ``i`` is the contiguous
    row ``vec_t[i]``, so the per-lane splitmix64 pass streams
    coalesced instead of striding through T(8,128)-tiled rows (the
    measured 1.65x fold, PERF.md §tile-padding).
    """
    vec_t = xp.asarray(vec_t, dtype=xp.uint32)
    zero = xp.zeros(vec_t.shape[1:], dtype=xp.uint32)
    return _fold_lanes(
        vec_t.shape[0], lambda i: vec_t[i], zero, xp
    )


def fingerprint_u32v_int(vec: Any) -> Any:
    """Host helper: digests as Python-friendly uint64 numpy array."""
    lo, hi = fingerprint_u32v(np.asarray(vec, dtype=np.uint32), np)
    return (np.asarray(hi, dtype=np.uint64) << np.uint64(32)) | np.asarray(
        lo, dtype=np.uint64
    )
