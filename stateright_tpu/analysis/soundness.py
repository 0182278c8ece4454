"""Reduction soundness analyzer: statically certify symmetry specs
and ample masks before the device path trusts them.

Round 20 landed device symmetry + ample-set reduction with the
soundness argument carried in prose (the 2pc encoding's
``ample_mask_host`` docstring). That scales to exactly one encoding:
every new ``DeviceRewriteSpec`` meant a fresh hand proof, which is why
only 2pc declared one. This module converts the proof burden into a
static pass — no state-space enumeration — that discharges the
standard sufficient obligations and emits a machine-readable
certificate (``SOUND_r*.json``, the LINT_r*/COMM_r* shape
conventions). The engines consult the certificate at spawn: a
certified spec runs, an uncertifiable one refuses loudly with the
failed obligation (checkers/common.soundness_refusal), and
``--unsound-ok`` / ``CheckerBuilder.unsound_ok()`` preserves research
workflows.

Obligations (each is one certificate record; names are stable — the
refusal message and the tests key on them):

symmetry scope (a declared ``DeviceRewriteSpec``):
  ``group-closure``           the rewrite set is a permutation-group
                              ACTION on the limb layout: structural
                              bounds (ops/canonical.validate_spec)
                              plus cross-field per-lane bit
                              disjointness — overlapping fields make
                              the "permutation" non-bijective, so the
                              orbit map is not an action at all;
  ``orbit-structure``         canonicalization is idempotent and maps
                              each row to a MEMBER PERMUTATION of
                              itself (member-tuple multiset preserved,
                              non-group bits untouched) with every
                              declared field in the sort key — the
                              perfect-canonicalizer contract
                              (constant on orbits);
  ``fingerprint-invariance``  the canonical form — hence the
                              fingerprint fold over it — is invariant
                              under every generator transposition;
  ``property-invariance``     every registered Property predicate is
                              group-invariant: a STATIC member-uniform
                              bit-footprint check over the predicate
                              jaxprs (walked via analysis/walker.py,
                              abstract bit-level interpretation) plus
                              a semantic P(τ·v) == P(v) battery;
  ``transition-equivariance`` the successor SET commutes with the
                              group: multiset{τ·succ(v)} ==
                              multiset{succ(τ·v)} per battery row.

ample scope (a declared ``ample_mask_host``):
  ``ample-enabledness``       enabledness preservation (the C0-style
                              condition): whenever a dropped slot is
                              enabled, some KEPT slot is enabled —
                              proven by exhaustive enumeration over
                              the union guard-footprint cone (the
                              guards provably depend on no other
                              bits), sampled when the cone is large;
  ``ample-non-suppression``   no property-relevant transition is
                              suppressed: every dropped slot whose
                              WRITE footprint meets a property READ
                              footprint must have a symmetric kept
                              image — a kept slot ``k`` and a group
                              element π with g_d(v) == g_k(π·v) and
                              succ_d(v) == π·succ_k(π·v) on the
                              battery (the "by symmetry such a path
                              maps to one using rm 0's" step of the
                              round-20 hand argument, made checkable).

The bit-level abstract interpreter evaluates the encoding's traced
jaxprs over a domain of per-bit codes (CONST0/CONST1, "equals input
bit b", or "depends on mask D") — precise through the shift/mask/
select idiom every encoding path is written in (the lint rules pin
those paths gather-free, which is exactly what keeps this analysis
exact), and soundly over-approximate elsewhere: an unsupported
primitive collapses to depends-on-everything, which can only REFUSE a
sound spec, never certify an unsound one.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ..encoding import ample_mask_host as _probe_ample
from ..encoding import device_rewrite_spec as _probe_spec
from .rules import Finding
from .walker import SiteWalk, source_of

#: per-bit abstract codes: >= 0 is "provably equals input bit
#: lane*32+bit"; the negatives are unknown-but-bounded.
_DEP, _CONST0, _CONST1 = -1, -2, -3

#: ample-enabledness cones up to this many bits enumerate
#: exhaustively (2^bits rows); larger cones fall back to sampling and
#: the certificate records method="sampled".
_EXHAUSTIVE_CONE_BITS = 12
_SAMPLE_ROWS = 2048

#: memoized certificates — the engines' spawn gates run per checker
#: construction, and tier-1 constructs hundreds.
_CERT_CACHE: dict = {}


class _Abs:
    """One abstract array: ``codes`` int64[S + (32,)] per-bit codes,
    ``deps`` uint32[S + (32, W)] per-bit input-bit dependency masks
    (an over-approximation; CONST bits carry empty masks)."""

    __slots__ = ("codes", "deps")

    def __init__(self, codes, deps):
        self.codes = codes
        self.deps = deps


def _seed(W: int) -> _Abs:
    codes = (
        np.arange(W, dtype=np.int64)[:, None] * 32
        + np.arange(32, dtype=np.int64)[None, :]
    )
    deps = np.zeros((W, 32, W), np.uint32)
    for lane in range(W):
        deps[lane, :, lane] = np.uint32(1) << np.arange(
            32, dtype=np.uint32
        )
    return _Abs(codes, deps)


def _const_abs(val, W: int) -> _Abs:
    v = np.asarray(val)
    u = v.astype(np.int64) & 0xFFFFFFFF
    bits = (u[..., None] >> np.arange(32, dtype=np.int64)) & 1
    codes = np.where(bits == 1, _CONST1, _CONST0).astype(np.int64)
    deps = np.zeros(v.shape + (32, W), np.uint32)
    return _Abs(codes, deps)


#: primitives interpreted per-element (result depends on the whole
#: element, never on individual bit structure) — arithmetic and
#: comparisons collapse to element-level dependency masks.
_ELEMWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg",
    "sign", "abs", "max", "min", "eq", "ne", "lt", "le", "gt", "ge",
    "floor", "ceil", "round", "clamp", "population_count", "clz",
})

_REDUCE = frozenset({
    "reduce_and", "reduce_or", "reduce_xor", "reduce_sum",
    "reduce_max", "reduce_min", "reduce_prod", "argmax", "argmin",
})

_CALL_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "remat", "checkpoint",
    "custom_jvp_call", "custom_vjp_call",
})

_IDENTITY = frozenset({"copy", "stop_gradient", "device_put"})


class _BitInterp:
    """Abstract interpreter over one encoding's traced jaxprs."""

    def __init__(self, W: int):
        self.W = W
        #: primitive names we collapsed on (recorded in the
        #: certificate so "certified via over-approximation" is
        #: visible)
        self.collapsed: list = []

    # -- plumbing --------------------------------------------------------

    def lift(self, x) -> _Abs:
        return x if isinstance(x, _Abs) else _const_abs(x, self.W)

    def _bcast(self, vals, shape):
        out = []
        for v in vals:
            a = self.lift(v)
            out.append(_Abs(
                np.broadcast_to(a.codes, tuple(shape) + (32,)),
                np.broadcast_to(a.deps,
                                tuple(shape) + (32, self.W)),
            ))
        return out

    def _elem_deps(self, a) -> np.ndarray:
        """Per-element dependency mask: OR over the 32 bit slots —
        shape S + (W,)."""
        if not isinstance(a, _Abs):
            return np.zeros(np.shape(a) + (self.W,), np.uint32)
        return np.bitwise_or.reduce(a.deps, axis=-2)

    def _all_deps(self, vals) -> np.ndarray:
        acc = np.zeros(self.W, np.uint32)
        for v in vals:
            if isinstance(v, _Abs):
                ed = self._elem_deps(v)
                acc |= np.bitwise_or.reduce(
                    ed.reshape(-1, self.W), axis=0
                ) if ed.size else 0
        return acc

    def _dep_abs(self, shape, elem_deps, dtype=None) -> _Abs:
        """All-bits-DEP output with one dependency mask per element
        (``elem_deps`` shape S + (W,)); bool dtypes keep bits 1..31
        CONST0 — the value is 0 or 1."""
        shape = tuple(shape)
        codes = np.full(shape + (32,), _DEP, np.int64)
        deps = np.broadcast_to(
            elem_deps[..., None, :], shape + (32, self.W)
        ).copy()
        if dtype is not None and np.dtype(dtype) == np.bool_:
            codes[..., 1:] = _CONST0
            deps[..., 1:, :] = 0
        return _Abs(codes, deps)

    def collapse(self, eqn, invals) -> list:
        self.collapsed.append(eqn.primitive.name)
        alldeps = self._all_deps(invals)
        outs = []
        for ov in eqn.outvars:
            sh = tuple(getattr(ov.aval, "shape", ()) or ())
            ed = np.broadcast_to(alldeps, sh + (self.W,))
            outs.append(self._dep_abs(
                sh, ed, getattr(ov.aval, "dtype", None)
            ))
        return outs

    # -- evaluation ------------------------------------------------------

    def run_closed(self, closed, args) -> list:
        return self.run(closed.jaxpr, closed.consts, args)

    def run(self, jaxpr, consts, args) -> list:
        env: dict = {}

        def read(v):
            if not hasattr(v, "count"):  # Literal
                return np.asarray(v.val)
            return env[id(v)]

        for v, c in zip(jaxpr.constvars, consts):
            env[id(v)] = np.asarray(c)
        for v, a in zip(jaxpr.invars, args):
            env[id(v)] = a
        for eqn in jaxpr.eqns:
            invals = [read(v) for v in eqn.invars]
            outvals = self.eval_eqn(eqn, invals)
            for v, o in zip(eqn.outvars, outvals):
                env[id(v)] = o
        return [read(v) for v in jaxpr.outvars]

    def eval_eqn(self, eqn, invals) -> list:
        name = eqn.primitive.name

        if name in _CALL_PRIMS or name == "cond":
            return self._eval_control(eqn, name, invals)

        if all(not isinstance(v, _Abs) for v in invals):
            # constant folding: bind eagerly (this is how the slot
            # arithmetic of a concrete-slot step trace folds away)
            try:
                import jax.numpy as jnp

                res = eqn.primitive.bind(
                    *[jnp.asarray(v) for v in invals], **eqn.params
                )
                res = (list(res) if eqn.primitive.multiple_results
                       else [res])
                return [np.asarray(r) for r in res]
            except Exception:
                return self.collapse(eqn, invals)

        if name in ("and", "or", "xor"):
            return [self._bitwise(name, invals[0], invals[1])]
        if name == "not":
            return [self._not(invals[0])]
        if name in ("shift_left", "shift_right_logical",
                    "shift_right_arithmetic"):
            return self._shift(eqn, name, invals)
        if name == "select_n":
            return [self._select(eqn, invals)]
        if name in _ELEMWISE:
            out = eqn.outvars[0]
            sh = tuple(getattr(out.aval, "shape", ()) or ())
            ed = np.zeros(sh + (self.W,), np.uint32)
            for v in invals:
                ed = ed | np.broadcast_to(
                    self._elem_deps(v), sh + (self.W,)
                )
            return [self._dep_abs(sh, ed, out.aval.dtype)]
        if name in _REDUCE:
            return [self._reduce(eqn, invals[0])]
        if name == "convert_element_type":
            return [self._convert(eqn, invals[0])]
        if name in _IDENTITY:
            return [invals[0]]
        if name in ("broadcast_in_dim", "reshape", "squeeze", "slice",
                    "concatenate", "transpose", "rev",
                    "expand_dims"):
            return self._structural(eqn, name, invals)
        return self.collapse(eqn, invals)

    def _eval_control(self, eqn, name, invals) -> list:
        if name == "cond":
            pred, ops = invals[0], invals[1:]
            branches = eqn.params["branches"]
            if not isinstance(pred, _Abs):
                idx = int(np.asarray(pred).reshape(()))
                idx = min(max(idx, 0), len(branches) - 1)
                b = branches[idx]
                return self.run(b.jaxpr, b.consts, ops)
            results = [
                self.run(b.jaxpr, b.consts, ops) for b in branches
            ]
            pd = np.bitwise_or.reduce(
                self._elem_deps(pred).reshape(-1, self.W), axis=0
            )
            return [
                self._join(list(vals), pd)
                for vals in zip(*results)
            ]
        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            p = eqn.params.get(key)
            if p is not None:
                sub = p
                break
        if sub is None:
            return self.collapse(eqn, invals)
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        consts = getattr(sub, "consts", [])
        if len(inner.invars) != len(invals):
            return self.collapse(eqn, invals)
        return self.run(inner, consts, invals)

    def _join(self, vals, extra_deps) -> Any:
        """Join abstract values from alternative branches: bits whose
        codes agree everywhere keep the code (no predicate
        dependency — the value is the same either way); disagreeing
        bits go DEP and inherit the predicate's dependency mask."""
        if all(not isinstance(v, _Abs) for v in vals):
            arrs = [np.asarray(v) for v in vals]
            if all(np.array_equal(arrs[0], a) for a in arrs[1:]):
                return arrs[0]
        shape = np.broadcast_shapes(*[
            (v.codes.shape[:-1] if isinstance(v, _Abs)
             else np.shape(v))
            for v in vals
        ])
        bs = self._bcast(vals, shape)
        codes = bs[0].codes.copy()
        deps = bs[0].deps.copy()
        for b in bs[1:]:
            same = codes == b.codes
            deps = np.where(
                same[..., None], deps | b.deps,
                deps | b.deps | extra_deps,
            )
            codes = np.where(same, codes, _DEP)
        deps = np.where(
            codes[..., None] == _DEP, deps | extra_deps, deps
        )
        deps[(codes == _CONST0) | (codes == _CONST1)] = 0
        return _Abs(codes, deps)

    def _bitwise(self, name, a, b) -> _Abs:
        shape = np.broadcast_shapes(
            (a.codes.shape[:-1] if isinstance(a, _Abs)
             else np.shape(a)),
            (b.codes.shape[:-1] if isinstance(b, _Abs)
             else np.shape(b)),
        )
        a, b = self._bcast([a, b], shape)
        ca, cb, da, db = a.codes, b.codes, a.deps, b.deps
        both = da | db
        if name == "and":
            zero = (ca == _CONST0) | (cb == _CONST0)
            codes = np.where(
                zero, _CONST0,
                np.where(cb == _CONST1, ca,
                         np.where(ca == _CONST1, cb,
                                  np.where((ca == cb) & (ca >= 0),
                                           ca, _DEP))))
            deps = np.where(
                zero[..., None], 0,
                np.where((cb == _CONST1)[..., None], da,
                         np.where((ca == _CONST1)[..., None], db,
                                  np.where(((ca == cb)
                                            & (ca >= 0))[..., None],
                                           da, both))))
        elif name == "or":
            one = (ca == _CONST1) | (cb == _CONST1)
            codes = np.where(
                one, _CONST1,
                np.where(cb == _CONST0, ca,
                         np.where(ca == _CONST0, cb,
                                  np.where((ca == cb) & (ca >= 0),
                                           ca, _DEP))))
            deps = np.where(
                one[..., None], 0,
                np.where((cb == _CONST0)[..., None], da,
                         np.where((ca == _CONST0)[..., None], db,
                                  np.where(((ca == cb)
                                            & (ca >= 0))[..., None],
                                           da, both))))
        else:  # xor
            same_id = (ca == cb) & (ca >= 0)
            both_const = ((ca == _CONST0) | (ca == _CONST1)) & (
                (cb == _CONST0) | (cb == _CONST1)
            )
            codes = np.where(
                same_id, _CONST0,
                np.where(both_const,
                         np.where(ca == cb, _CONST0, _CONST1),
                         np.where(cb == _CONST0, ca,
                                  np.where(ca == _CONST0, cb,
                                           _DEP))))
            deps = np.where(
                (same_id | both_const)[..., None], 0,
                np.where((cb == _CONST0)[..., None], da,
                         np.where((ca == _CONST0)[..., None], db,
                                  both)))
        return _Abs(codes.astype(np.int64), deps.astype(np.uint32))

    def _not(self, a) -> _Abs:
        a = self.lift(a)
        codes = np.where(
            a.codes == _CONST0, _CONST1,
            np.where(a.codes == _CONST1, _CONST0, _DEP)
        ).astype(np.int64)
        deps = np.where(
            (codes == _DEP)[..., None], a.deps, 0
        ).astype(np.uint32)
        return _Abs(codes, deps)

    def _shift(self, eqn, name, invals) -> list:
        a, s = invals
        if isinstance(s, _Abs):
            out = eqn.outvars[0]
            sh = tuple(getattr(out.aval, "shape", ()) or ())
            ed = np.broadcast_to(self._elem_deps(a), sh + (self.W,)) \
                | np.broadcast_to(self._elem_deps(s),
                                  sh + (self.W,))
            return [self._dep_abs(sh, ed, out.aval.dtype)]
        a = self.lift(a)
        shape = np.broadcast_shapes(a.codes.shape[:-1], np.shape(s))
        (a,) = self._bcast([a], shape)
        s = np.broadcast_to(np.asarray(s).astype(np.int64), shape)
        arith = name == "shift_right_arithmetic"
        signed = np.dtype(eqn.outvars[0].aval.dtype).kind == "i"
        codes = np.empty(tuple(shape) + (32,), np.int64)
        deps = np.empty(tuple(shape) + (32, self.W), np.uint32)
        for idx in np.ndindex(*shape):
            sh_amt = int(s[idx]) & 63
            c, d = a.codes[idx], a.deps[idx]
            oc = np.full(32, _CONST0, np.int64)
            od = np.zeros((32, self.W), np.uint32)
            if sh_amt < 32:
                if name == "shift_left":
                    oc[sh_amt:] = c[:32 - sh_amt]
                    od[sh_amt:] = d[:32 - sh_amt]
                else:
                    oc[:32 - sh_amt] = c[sh_amt:]
                    od[:32 - sh_amt] = d[sh_amt:]
                    if arith and signed and sh_amt:
                        oc[32 - sh_amt:] = c[31]
                        od[32 - sh_amt:] = d[31]
            elif arith and signed:
                oc[:] = c[31]
                od[:] = d[31]
            codes[idx], deps[idx] = oc, od
        return [_Abs(codes, deps)]

    def _select(self, eqn, invals) -> Any:
        pred, cases = invals[0], invals[1:]
        out = eqn.outvars[0]
        shape = tuple(getattr(out.aval, "shape", ()) or ())
        if not isinstance(pred, _Abs):
            idx = np.broadcast_to(
                np.asarray(pred).astype(np.int64), shape
            )
            if all(not isinstance(c, _Abs) for c in cases):
                stacked = np.stack(
                    [np.broadcast_to(np.asarray(c), shape)
                     for c in cases]
                )
                return np.take_along_axis(
                    stacked, idx[None], axis=0
                )[0]
            bs = self._bcast(cases, shape)
            codes = bs[0].codes.copy()
            deps = bs[0].deps.copy()
            for i in range(1, len(bs)):
                m = idx == i
                codes[m] = bs[i].codes[m]
                deps[m] = bs[i].deps[m]
            return _Abs(codes, deps)
        pd = self._elem_deps(pred)
        pd = np.broadcast_to(pd, shape + (self.W,))
        bs = self._bcast(cases, shape)
        codes = bs[0].codes.copy()
        deps = bs[0].deps.copy()
        for b in bs[1:]:
            same = codes == b.codes
            deps = np.where(
                same[..., None], deps | b.deps,
                deps | b.deps | pd[..., None, :],
            )
            codes = np.where(same, codes, _DEP)
        deps = np.where(
            codes[..., None] == _DEP,
            deps | pd[..., None, :], deps,
        )
        deps[(codes == _CONST0) | (codes == _CONST1)] = 0
        return _Abs(codes.astype(np.int64), deps.astype(np.uint32))

    def _reduce(self, eqn, a) -> _Abs:
        out = eqn.outvars[0]
        sh = tuple(getattr(out.aval, "shape", ()) or ())
        axes = tuple(eqn.params.get("axes", ()))
        ed = self._elem_deps(a)
        if axes:
            ed = np.bitwise_or.reduce(
                ed, axis=tuple(a for a in axes)
            ) if len(axes) == 1 else ed
            if len(axes) > 1:
                ed = self._elem_deps(a)
                for ax in sorted(axes, reverse=True):
                    ed = np.bitwise_or.reduce(ed, axis=ax)
        ed = np.broadcast_to(ed.reshape(sh + (self.W,)),
                             sh + (self.W,))
        return self._dep_abs(sh, ed, out.aval.dtype)

    def _convert(self, eqn, a) -> _Abs:
        a = self.lift(a)
        nd = np.dtype(eqn.params["new_dtype"])
        od = np.dtype(eqn.invars[0].aval.dtype)
        shape = a.codes.shape[:-1]
        if nd == np.bool_:
            high0 = (a.codes[..., 1:] == _CONST0).all(-1)
            ed = self._elem_deps(a)
            codes = np.full(shape + (32,), _CONST0, np.int64)
            deps = np.zeros(shape + (32, self.W), np.uint32)
            codes[..., 0] = np.where(high0, a.codes[..., 0], _DEP)
            deps[..., 0, :] = np.where(
                high0[..., None], a.deps[..., 0, :], ed
            )
            return _Abs(codes, deps)
        if nd.kind in "ui" and od.kind in "uib":
            if od.kind == "i" and nd.itemsize > od.itemsize:
                # sign extension of a possibly-negative value —
                # collapse rather than model it
                ed = self._elem_deps(a)
                return self._dep_abs(shape, ed, nd)
            keep = min(32, nd.itemsize * 8)
            codes = np.full(shape + (32,), _CONST0, np.int64)
            deps = np.zeros(shape + (32, self.W), np.uint32)
            codes[..., :keep] = a.codes[..., :keep]
            deps[..., :keep, :] = a.deps[..., :keep, :]
            return _Abs(codes, deps)
        ed = self._elem_deps(a)
        return self._dep_abs(shape, ed, nd)

    def _structural(self, eqn, name, invals) -> list:
        a = self.lift(invals[0])
        p = eqn.params
        if name == "broadcast_in_dim":
            shape = tuple(p["shape"])
            bdims = tuple(p["broadcast_dimensions"])
            ns = [1] * len(shape)
            for i, d in enumerate(bdims):
                ns[d] = a.codes.shape[:-1][i]
            codes = np.broadcast_to(
                a.codes.reshape(tuple(ns) + (32,)), shape + (32,)
            )
            deps = np.broadcast_to(
                a.deps.reshape(tuple(ns) + (32, self.W)),
                shape + (32, self.W),
            )
            return [_Abs(codes, deps)]
        if name == "reshape":
            if p.get("dimensions") is not None:
                return self.collapse(eqn, invals)
            shape = tuple(p["new_sizes"])
            return [_Abs(a.codes.reshape(shape + (32,)),
                         a.deps.reshape(shape + (32, self.W)))]
        if name == "squeeze":
            dims = tuple(p["dimensions"])
            return [_Abs(np.squeeze(a.codes, axis=dims),
                         np.squeeze(a.deps, axis=dims))]
        if name == "expand_dims":
            dims = tuple(p["dimensions"])
            c, d = a.codes, a.deps
            for ax in sorted(dims):
                c = np.expand_dims(c, ax)
                d = np.expand_dims(d, ax)
            return [_Abs(c, d)]
        if name == "slice":
            sl = tuple(
                slice(int(s), int(l), int(st))
                for s, l, st in zip(
                    p["start_indices"], p["limit_indices"],
                    p["strides"] or [1] * len(p["start_indices"]),
                )
            )
            return [_Abs(a.codes[sl], a.deps[sl])]
        if name == "concatenate":
            dim = int(p["dimension"])
            bs = [self.lift(v) for v in invals]
            return [_Abs(
                np.concatenate([b.codes for b in bs], axis=dim),
                np.concatenate([b.deps for b in bs], axis=dim),
            )]
        if name == "transpose":
            perm = tuple(p["permutation"])
            n = len(perm)
            return [_Abs(
                np.transpose(a.codes, perm + (n,)),
                np.transpose(a.deps, perm + (n, n + 1)),
            )]
        if name == "rev":
            dims = tuple(p["dimensions"])
            return [_Abs(np.flip(a.codes, axis=dims),
                         np.flip(a.deps, axis=dims))]
        return self.collapse(eqn, invals)


# -- footprint extraction ---------------------------------------------------

def _make_closed(fn, *examples):
    import jax

    return jax.make_jaxpr(fn)(*examples)


def _abs_eval(enc, fn):
    """Trace ``fn`` on one zero example state, walk the jaxpr
    (analysis/walker.py — branches and closed-over constants
    included), then abstract-interpret it from the input-bit seed.
    Returns ``(outputs, interp, walk)``."""
    closed = _make_closed(fn, np.zeros(enc.width, np.uint32))
    walk = SiteWalk(closed)
    interp = _BitInterp(enc.width)
    outs = interp.run_closed(closed, [_seed(enc.width)])
    return outs, interp, walk


def _mask_of_bits(a: _Abs, lane_bits) -> np.ndarray:
    """OR the deps of the listed ``(index...)`` bit positions."""
    acc = np.zeros(a.deps.shape[-1], np.uint32)
    for idx in lane_bits:
        acc |= a.deps[idx]
    return acc


def guard_footprints(enc) -> tuple:
    """Per-slot guard read-footprints (uint32[W] masks) from the
    packed ``enabled_bits_vec`` words, plus the interpreter (for its
    collapse record)."""
    import jax.numpy as jnp  # noqa: F401 — encoding paths trace jnp

    outs, interp, walk = _abs_eval(enc, enc.enabled_bits_vec)
    words = outs[0]
    W, K = enc.width, enc.max_actions
    fps = []
    for k in range(K):
        if isinstance(words, _Abs):
            fps.append(np.array(words.deps[k // 32, k % 32],
                                np.uint32))
        else:
            fps.append(np.zeros(W, np.uint32))
    return fps, interp, walk


def property_footprints(enc) -> tuple:
    """Per-property read-footprints over
    ``property_conditions_vec``."""
    outs, interp, walk = _abs_eval(enc, enc.property_conditions_vec)
    props = outs[0]
    names = [p.name for p in enc.host_model.properties()]
    fps = []
    for p in range(len(names)):
        if isinstance(props, _Abs):
            fps.append(np.bitwise_or.reduce(props.deps[p], axis=0))
        else:
            fps.append(np.zeros(enc.width, np.uint32))
    return names, fps, interp, walk


def step_slot_footprints(enc, slot: int) -> tuple:
    """``(write_mask, read_mask)`` uint32[W] for one concrete slot:
    bits the transition may CHANGE (abstract code differs from the
    identity) and bits it may READ."""
    import jax.numpy as jnp

    outs, interp, _walk = _abs_eval(
        enc, lambda v: enc.step_slot_vec(v, jnp.uint32(slot))
    )
    succ = outs[0]
    W = enc.width
    write = np.zeros(W, np.uint32)
    read = np.zeros(W, np.uint32)
    ident = _seed(W).codes
    if isinstance(succ, _Abs):
        changed = succ.codes != ident
        shifts = np.arange(32, dtype=np.uint64)
        for lane in range(W):
            write[lane] = np.uint32(
                (changed[lane].astype(np.uint64) << shifts).sum()
                & 0xFFFFFFFF
            )
        read = np.bitwise_or.reduce(
            succ.deps.reshape(-1, W), axis=0
        )
    else:
        # a constant successor: writes everything it disagrees on;
        # unknowable statically — treat all bits written
        write[:] = np.uint32(0xFFFFFFFF)
    for extra in outs[1:]:
        if isinstance(extra, _Abs):
            read = read | np.bitwise_or.reduce(
                extra.deps.reshape(-1, W), axis=0
            )
    return write, read


# -- permutations -----------------------------------------------------------

def apply_member_permutation(spec, rows, perm) -> np.ndarray:
    """Relabel members of encoded rows: output member ``p`` takes
    input member ``perm[p]``'s field values; non-group bits pass
    through. Pure numpy, any leading batch shape."""
    rows = np.asarray(rows, np.uint32)
    out = rows.copy()
    R = spec.n_members
    for f in spec.fields:
        fm = (1 << f.width) - 1
        fieldmask = 0
        for m in range(R):
            fieldmask |= fm << (f.shift + m * f.stride)
        lane = rows[..., f.lane]
        acc = out[..., f.lane] & np.uint32(~fieldmask & 0xFFFFFFFF)
        for p in range(R):
            src = perm[p]
            v = (lane >> np.uint32(f.shift + src * f.stride)) \
                & np.uint32(fm)
            acc = acc | (v << np.uint32(f.shift + p * f.stride))
        out[..., f.lane] = acc
    return out


def permute_mask(spec, mask, perm) -> np.ndarray:
    """Relabel a uint32[W] bit-mask the same way a state row would
    be (footprints live in the state's bit layout)."""
    return apply_member_permutation(
        spec, np.asarray(mask, np.uint32)[None, :], perm
    )[0]


def _transpositions(R: int) -> list:
    perms = []
    for a in range(R):
        for b in range(a + 1, R):
            p = list(range(R))
            p[a], p[b] = p[b], p[a]
            perms.append(tuple(p))
    return perms


def _generators(R: int) -> list:
    """Adjacent transpositions — they generate S_R, and invariance
    under generators composes to the whole group."""
    gens = []
    for a in range(R - 1):
        p = list(range(R))
        p[a], p[a + 1] = p[a + 1], p[a]
        gens.append(tuple(p))
    return gens


def _member_tuples(spec, row) -> list:
    out = []
    for m in range(spec.n_members):
        t = []
        for f in spec.fields:
            fm = (1 << f.width) - 1
            t.append(
                (int(row[f.lane]) >> (f.shift + m * f.stride)) & fm
            )
        out.append(tuple(t))
    return out


def _group_mask(spec, W: int) -> np.ndarray:
    gm = np.zeros(W, np.uint32)
    for f in spec.fields:
        fm = (1 << f.width) - 1
        for m in range(spec.n_members):
            gm[f.lane] |= np.uint32(
                fm << (f.shift + m * f.stride)
            )
    return gm


# -- the battery ------------------------------------------------------------

def battery_rows(enc, spec, extra_masks=()) -> np.ndarray:
    """Deterministic semantic-check battery: zeros, single-bit rows
    for every group-field and footprint bit, distinct-value member
    sweeps, and fixed-seed pseudorandom rows. Semantic obligations
    hold on EVERY uint32 state (the encodings are branchless total
    functions), so unreachable rows only make the check stronger."""
    W = enc.width
    rows = [np.zeros(W, np.uint32)]
    bits = set()
    if spec is not None:
        for f in spec.fields:
            for m in range(spec.n_members):
                for b in range(f.width):
                    bits.add((f.lane, f.shift + m * f.stride + b))
    for mask in extra_masks:
        for lane in range(W):
            mm = int(mask[lane])
            for j in range(32):
                if (mm >> j) & 1:
                    bits.add((lane, j))
    for lane, b in sorted(bits):
        r = np.zeros(W, np.uint32)
        r[lane] = np.uint32(1) << np.uint32(b)
        rows.append(r)
    if spec is not None:
        for salt in (1, 2):
            r = np.zeros(W, np.uint32)
            for f in spec.fields:
                fm = (1 << f.width) - 1
                for m in range(spec.n_members):
                    v = (m * salt + salt) & fm
                    r[f.lane] |= np.uint32(
                        v << (f.shift + m * f.stride)
                    )
            rows.append(r)
    rng = np.random.default_rng(0xC0FFEE)
    rows.extend(list(
        rng.integers(0, 1 << 32, size=(24, W), dtype=np.uint64)
        .astype(np.uint32)
    ))
    uniq, seen = [], set()
    for r in rows:
        key = tuple(int(x) for x in r)
        if key not in seen:
            seen.add(key)
            uniq.append(r)
    return np.stack(uniq)


# -- obligation checks ------------------------------------------------------

def _finding(enc_name, rule, ok, message, **data) -> Finding:
    return Finding(
        rule=rule,
        severity="info" if ok else "error",
        encoding=enc_name,
        path="soundness",
        message=message,
        data=data,
    )


def _enc_name(enc) -> str:
    key = getattr(enc, "cache_key", None)
    suffix = f"({key()})" if callable(key) else ""
    return type(enc).__qualname__ + suffix


def _check_group_closure(name, enc, spec) -> Finding:
    from ..ops.canonical import validate_spec

    try:
        validate_spec(spec, width=enc.width)
    except ValueError as e:
        return _finding(
            name, "group-closure", False,
            f"structural validation failed: {e}",
            scope="symmetry",
        )
    # cross-field bit disjointness per lane: overlapping fields make
    # the member relabeling non-bijective (two fields write one bit),
    # so the rewrite set is not a group action on the layout.
    R = spec.n_members
    for lane in sorted({f.lane for f in spec.fields}):
        occupied = 0
        for fi, f in enumerate(spec.fields):
            if f.lane != lane:
                continue
            fmask = 0
            fm = (1 << f.width) - 1
            for m in range(R):
                fmask |= fm << (f.shift + m * f.stride)
            if occupied & fmask:
                return _finding(
                    name, "group-closure", False,
                    f"fields overlap on lane {lane} (field {fi}: "
                    f"shift={f.shift} stride={f.stride} "
                    f"width={f.width} collides with an earlier "
                    "field's member bits) — the member relabeling "
                    "is not a bijection, so the rewrite set is not "
                    "a permutation-group action on the limb layout",
                    scope="symmetry", lane=lane,
                )
            occupied |= fmask
    return _finding(
        name, "group-closure", True,
        f"permutation-group action over {R} members proven: "
        "structural bounds hold and all member fields are pairwise "
        "bit-disjoint (bijective relabeling)",
        scope="symmetry",
    )


def _check_orbit_structure(name, enc, spec, rows) -> Finding:
    from ..ops.canonical import canonicalize_rows

    non_keys = [i for i, f in enumerate(spec.fields)
                if not f.sort_key]
    if non_keys:
        return _finding(
            name, "orbit-structure", False,
            f"fields {non_keys} are not in the sort key — a partial "
            "key is not constant on orbits, so the visited count "
            "becomes search-order-dependent (symmetry.py); declare "
            "the FULL per-member tuple as the key",
            scope="symmetry",
        )
    canon = canonicalize_rows(spec, rows, np)
    again = canonicalize_rows(spec, canon, np)
    if not np.array_equal(canon, again):
        bad = int(np.nonzero(
            (canon != again).any(axis=-1)
        )[0][0])
        return _finding(
            name, "orbit-structure", False,
            "canonicalization is not idempotent (battery row "
            f"{bad}: canon(canon(v)) != canon(v)) — the orbit map "
            "has no well-defined representatives",
            scope="symmetry", row=bad,
        )
    gm = _group_mask(spec, enc.width)
    for i in range(rows.shape[0]):
        if not np.array_equal(rows[i] & ~gm, canon[i] & ~gm):
            return _finding(
                name, "orbit-structure", False,
                f"canonicalization changed non-group bits on "
                f"battery row {i} — the rewrite leaks outside the "
                "declared member fields",
                scope="symmetry", row=i,
            )
        if sorted(_member_tuples(spec, rows[i])) != sorted(
            _member_tuples(spec, canon[i])
        ):
            return _finding(
                name, "orbit-structure", False,
                f"canonical form of battery row {i} is not a member "
                "permutation of the row (member-tuple multiset "
                "changed) — orbits are malformed over the declared "
                "field table",
                scope="symmetry", row=i,
            )
    return _finding(
        name, "orbit-structure", True,
        "well-formed orbit structure proven on the battery: full "
        "sort key, idempotent canonicalization, member-tuple "
        "multiset preserved, non-group bits untouched",
        scope="symmetry", battery_rows=int(rows.shape[0]),
    )


def _check_fingerprint_invariance(name, enc, spec, rows) -> Finding:
    from ..ops.canonical import canonicalize_rows

    base = canonicalize_rows(spec, rows, np)
    for g in _generators(spec.n_members):
        permuted = canonicalize_rows(
            spec, apply_member_permutation(spec, rows, g), np
        )
        if not np.array_equal(base, permuted):
            bad = int(np.nonzero(
                (base != permuted).any(axis=-1)
            )[0][0])
            return _finding(
                name, "fingerprint-invariance", False,
                f"canonical form (the fingerprint field-selection) "
                f"is NOT invariant under member transposition "
                f"{g} (battery row {bad}) — two states of one orbit "
                "fingerprint differently and the visited set "
                "under-merges",
                scope="symmetry", generator=list(g), row=bad,
            )
    return _finding(
        name, "fingerprint-invariance", True,
        "canonical form invariant under every generator "
        "transposition — orbit members share one fingerprint",
        scope="symmetry",
    )


def _check_property_invariance(name, enc, spec, rows,
                               prop_names, prop_fps) -> Finding:
    # static: each property's read footprint must be member-uniform
    # over every spec field — reading member 0's sub-field without
    # the others' is the asymmetric-predicate defect.
    for p, fp in zip(prop_names, prop_fps):
        for fi, f in enumerate(spec.fields):
            fm = (1 << f.width) - 1
            subs = [
                (int(fp[f.lane]) >> (f.shift + m * f.stride)) & fm
                for m in range(spec.n_members)
            ]
            if len(set(subs)) > 1:
                readers = [m for m, s in enumerate(subs) if s]
                return _finding(
                    name, "property-invariance", False,
                    f"property {p!r} reads member field {fi} "
                    f"asymmetrically (members {readers} of "
                    f"{spec.n_members} in its bit footprint) — the "
                    "predicate is not group-invariant, so quotient "
                    "counts would silently drop its witnesses",
                    scope="symmetry", property=p, field=fi,
                    members=readers,
                )
    # semantic: P(tau . v) == P(v) on the battery
    import jax
    import jax.numpy as jnp

    ref = np.asarray(jax.vmap(enc.property_conditions_vec)(
        jnp.asarray(rows)
    ))
    for g in _generators(spec.n_members):
        got = np.asarray(jax.vmap(enc.property_conditions_vec)(
            jnp.asarray(apply_member_permutation(spec, rows, g))
        ))
        if not np.array_equal(ref, got):
            bad = np.nonzero((ref != got).any(axis=-1))[0]
            pidx = int(np.nonzero(
                (ref[bad[0]] != got[bad[0]])
            )[0][0])
            return _finding(
                name, "property-invariance", False,
                f"property {prop_names[pidx]!r} changes truth "
                f"value under member transposition {g} (battery "
                f"row {int(bad[0])}) — not group-invariant",
                scope="symmetry", property=prop_names[pidx],
                generator=list(g),
            )
    return _finding(
        name, "property-invariance", True,
        f"all {len(prop_names)} properties group-invariant: "
        "member-uniform static footprints and semantic agreement "
        "under every generator",
        scope="symmetry", properties=list(prop_names),
    )


def _step_all(enc, rows):
    import jax
    import jax.numpy as jnp

    res = jax.vmap(enc.step_vec)(jnp.asarray(rows))
    succs = np.asarray(res[0])
    valids = np.asarray(res[1])
    return succs, valids


def _check_transition_equivariance(name, enc, spec, rows) -> Finding:
    succs, valids = _step_all(enc, rows)
    for g in _generators(spec.n_members):
        prows = apply_member_permutation(spec, rows, g)
        psuccs, pvalids = _step_all(enc, prows)
        for i in range(rows.shape[0]):
            a = apply_member_permutation(
                spec, succs[i][valids[i]], g
            )
            b = psuccs[i][pvalids[i]]
            a_sorted = sorted(map(tuple, a.tolist()))
            b_sorted = sorted(map(tuple, b.tolist()))
            if a_sorted != b_sorted:
                return _finding(
                    name, "transition-equivariance", False,
                    f"successor set does not commute with member "
                    f"transposition {g} on battery row {i}: "
                    "tau(succ(v)) != succ(tau(v)) as multisets — "
                    "the quotient graph is not the graph of the "
                    "quotient",
                    scope="symmetry", generator=list(g), row=i,
                )
    return _finding(
        name, "transition-equivariance", True,
        "successor sets commute with every generator transposition "
        "on the battery",
        scope="symmetry", battery_rows=int(rows.shape[0]),
    )


def _mask_bits(mask) -> list:
    out = []
    for lane in range(len(mask)):
        mm = int(mask[lane])
        for j in range(32):
            if (mm >> j) & 1:
                out.append((lane, j))
    return out


def _guard_values(enc, rows, slots) -> np.ndarray:
    """bool[rows, slots] — the packed guard words evaluated on each
    row, extracted at the listed slots."""
    import jax
    import jax.numpy as jnp

    words = np.asarray(jax.vmap(enc.enabled_bits_vec)(
        jnp.asarray(rows)
    ))
    out = np.zeros((rows.shape[0], len(slots)), bool)
    for i, s in enumerate(slots):
        out[:, i] = (words[:, s // 32] >> (s % 32)) & 1
    return out


def _cone_rows(enc, cone_bits, rng) -> tuple:
    """Assignment rows over a footprint cone: exhaustive when small
    (the guards provably depend on no other bits, so one zero
    background decides the implication), sampled otherwise."""
    W = enc.width
    if len(cone_bits) <= _EXHAUSTIVE_CONE_BITS:
        n = 1 << len(cone_bits)
        rows = np.zeros((n, W), np.uint32)
        for i in range(n):
            for j, (lane, b) in enumerate(cone_bits):
                if (i >> j) & 1:
                    rows[i, lane] |= np.uint32(1) << np.uint32(b)
        return rows, "exhaustive"
    rows = np.zeros((_SAMPLE_ROWS, W), np.uint32)
    picks = rng.integers(
        0, 2, size=(_SAMPLE_ROWS, len(cone_bits)), dtype=np.uint64
    )
    for j, (lane, b) in enumerate(cone_bits):
        rows[:, lane] |= (
            picks[:, j].astype(np.uint32) << np.uint32(b)
        )
    return rows, "sampled"


def _check_ample_enabledness(name, enc, mask_words,
                             guard_fps) -> Finding:
    K = enc.max_actions
    dropped = [
        k for k in range(K)
        if not (int(mask_words[k // 32]) >> (k % 32)) & 1
    ]
    kept = [
        k for k in range(K)
        if (int(mask_words[k // 32]) >> (k % 32)) & 1
    ]
    rng = np.random.default_rng(0xA3B1E)
    methods = set()
    for d in dropped:
        fpd = guard_fps[d]
        # candidates ordered by guard-footprint overlap with the
        # dropped slot (identical footprints first: 2pc's
        # rm_prepare shares choose_abort's guard exactly)
        ranked = sorted(
            kept,
            key=lambda k: (
                not np.array_equal(guard_fps[k], fpd),
                -int(sum(
                    bin(int(guard_fps[k][w] & fpd[w])).count("1")
                    for w in range(enc.width)
                )),
                k,
            ),
        )
        proven = False
        for k in ranked[:8]:
            cone = _mask_bits(fpd | guard_fps[k])
            rows, method = _cone_rows(enc, cone, rng)
            g = _guard_values(enc, rows, [d, k])
            if not np.any(g[:, 0] & ~g[:, 1]):
                methods.add(method)
                proven = True
                break
        if not proven:
            return _finding(
                name, "ample-enabledness", False,
                f"dropped slot {d} can be enabled while NO kept "
                "slot implied by its guard is (no kept slot k with "
                "g_d => g_k over the guard footprint cone) — the "
                "filtered search can stall in a state the full "
                "search would leave (enabledness preservation "
                "fails)",
                scope="ample", slot=d,
            )
    return _finding(
        name, "ample-enabledness", True,
        f"enabledness preserved: each of the {len(dropped)} "
        "dropped slots implies a kept slot's guard over its "
        "footprint cone",
        scope="ample", dropped=dropped,
        method=sorted(methods) or ["exhaustive"],
    )


def _step_slot_batch(enc, rows, slot: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    from ..encoding import normalize_step_slot_result

    res = jax.vmap(
        lambda v: enc.step_slot_vec(v, jnp.uint32(slot))
    )(jnp.asarray(rows))
    succ, _t, _h = normalize_step_slot_result(res)
    return np.asarray(succ)


def _check_ample_non_suppression(name, enc, spec, mask_words,
                                 guard_fps, prop_fps,
                                 rows) -> Finding:
    K = enc.max_actions
    dropped = [
        k for k in range(K)
        if not (int(mask_words[k // 32]) >> (k % 32)) & 1
    ]
    kept = [
        k for k in range(K)
        if (int(mask_words[k // 32]) >> (k % 32)) & 1
    ]
    prop_read = np.zeros(enc.width, np.uint32)
    for fp in prop_fps:
        prop_read |= fp
    perms = [tuple(range(spec.n_members))] + _transpositions(
        spec.n_members
    ) if spec is not None else [()]
    write_fps: dict = {}

    def wfp(slot):
        if slot not in write_fps:
            write_fps[slot] = step_slot_footprints(enc, slot)[0]
        return write_fps[slot]

    guards_b = _guard_values(enc, rows, list(range(K)))
    relevant = [
        d for d in dropped if np.any(wfp(d) & prop_read)
    ]
    for d in relevant:
        ok = False
        ranked = sorted(
            kept,
            key=lambda k: (
                not np.array_equal(guard_fps[k], guard_fps[d]), k
            ),
        )
        succ_d = None
        for k in ranked:
            for pi in perms:
                if spec is not None and not np.array_equal(
                    permute_mask(spec, wfp(k), pi), wfp(d)
                ):
                    continue
                if spec is None and not np.array_equal(
                    wfp(k), wfp(d)
                ):
                    continue
                prows = (
                    apply_member_permutation(spec, rows, pi)
                    if spec is not None else rows
                )
                g_k = _guard_values(enc, prows, [k])[:, 0]
                if not np.array_equal(guards_b[:, d], g_k):
                    continue
                en = np.nonzero(guards_b[:, d])[0]
                if en.size == 0:
                    ok = True
                    break
                if succ_d is None:
                    succ_d = _step_slot_batch(enc, rows[en], d)
                succ_k = _step_slot_batch(enc, prows[en], k)
                mapped = (
                    apply_member_permutation(spec, succ_k, pi)
                    if spec is not None else succ_k
                )
                if np.array_equal(succ_d, mapped):
                    ok = True
                    break
            if ok:
                break
        if not ok:
            return _finding(
                name, "ample-non-suppression", False,
                f"dropped slot {d} is property-relevant (its write "
                "footprint meets a property read footprint) and has "
                "NO symmetric kept image — no kept slot k and group "
                "element pi with g_d(v) == g_k(pi.v) and succ_d(v) "
                "== pi.succ_k(pi.v) on the battery — the mask "
                "suppresses an enabled property-relevant transition",
                scope="ample", slot=d,
            )
    return _finding(
        name, "ample-non-suppression", True,
        f"{len(relevant)} property-relevant dropped slots each have "
        "a symmetric kept image (guard and successor agree under a "
        "group element on the battery)",
        scope="ample", relevant=relevant, dropped=dropped,
    )


# -- certification ----------------------------------------------------------

@dataclass
class SoundnessResult:
    """The certificate for one encoding's declared reductions."""

    encoding: str
    #: None when the encoding declares no DeviceRewriteSpec
    sym_certified: Optional[bool]
    #: None when the encoding declares no ample mask
    ample_certified: Optional[bool]
    obligations: list = field(default_factory=list)
    #: primitives the abstract interpreter over-approximated
    collapsed: list = field(default_factory=list)
    analyzer_sec: float = 0.0

    @property
    def certified(self) -> bool:
        return (self.sym_certified is not False
                and self.ample_certified is not False)

    def failed(self, scope: Optional[str] = None):
        """The first failed obligation Finding (optionally within one
        scope), or None."""
        for f in self.obligations:
            if f.severity != "error":
                continue
            if scope is None or f.data.get("scope") == scope:
                return f
        return None

    def as_dict(self) -> dict:
        return dict(
            encoding=self.encoding,
            status="certified" if self.certified else "refused",
            symmetry=self.sym_certified,
            ample=self.ample_certified,
            analyzer_sec=round(self.analyzer_sec, 4),
            collapsed_primitives=sorted(set(self.collapsed)),
            obligations=[f.as_dict() for f in self.obligations],
        )


def certify_encoding(enc, use_cache: bool = True) -> SoundnessResult:
    """Run every applicable obligation over one encoding. Memoized on
    the encoding class + cache_key (the engines' spawn gates run per
    checker construction); pass ``use_cache=False`` to re-measure
    ``analyzer_sec``."""
    cls = type(enc)
    ck = getattr(enc, "cache_key", None)
    key = (
        cls.__module__, cls.__qualname__,
        ck() if callable(ck) else (enc.width, enc.max_actions),
    )
    if use_cache and key in _CERT_CACHE:
        return _CERT_CACHE[key]
    t0 = time.perf_counter()
    name = _enc_name(enc)
    obligations: list = []
    collapsed: list = []

    try:
        spec = _probe_spec(enc)
        spec_error = None
    except ValueError as e:
        spec, spec_error = None, e
    mask = _probe_ample(enc)

    sym_certified: Optional[bool] = None
    if spec_error is not None:
        obligations.append(_finding(
            name, "group-closure", False,
            f"structural validation failed: {spec_error}",
            scope="symmetry",
        ))
        sym_certified = False
    elif spec is not None:
        f = _check_group_closure(name, enc, spec)
        obligations.append(f)
        if f.severity == "error":
            sym_certified = False
        else:
            pnames, pfps, pinterp, _ = property_footprints(enc)
            collapsed += pinterp.collapsed
            rows = battery_rows(enc, spec, pfps)
            checks = [
                _check_orbit_structure(name, enc, spec, rows),
                _check_fingerprint_invariance(
                    name, enc, spec, rows
                ),
                _check_property_invariance(
                    name, enc, spec, rows, pnames, pfps
                ),
                _check_transition_equivariance(
                    name, enc, spec, rows
                ),
            ]
            obligations += checks
            sym_certified = all(
                c.severity != "error" for c in checks
            )

    ample_certified: Optional[bool] = None
    if mask is not None:
        if not hasattr(enc, "enabled_bits_vec") or not hasattr(
            enc, "step_slot_vec"
        ):
            obligations.append(_finding(
                name, "ample-enabledness", False,
                "ample mask declared but the encoding has no sparse "
                "dispatch path (enabled_bits_vec/step_slot_vec) — "
                "the guard obligations cannot be stated, let alone "
                "proven",
                scope="ample",
            ))
            ample_certified = False
        else:
            gfps, ginterp, _ = guard_footprints(enc)
            collapsed += ginterp.collapsed
            pnames, pfps, pinterp, _ = property_footprints(enc)
            collapsed += pinterp.collapsed
            rows = battery_rows(
                enc, spec if sym_certified else None,
                list(pfps) + list(gfps),
            )
            f1 = _check_ample_enabledness(name, enc, mask, gfps)
            obligations.append(f1)
            f2 = _check_ample_non_suppression(
                name, enc, spec if sym_certified else None, mask,
                gfps, pfps, rows,
            )
            obligations.append(f2)
            ample_certified = (
                f1.severity != "error" and f2.severity != "error"
            )

    res = SoundnessResult(
        encoding=name,
        sym_certified=sym_certified,
        ample_certified=ample_certified,
        obligations=obligations,
        collapsed=sorted(set(collapsed)),
        analyzer_sec=time.perf_counter() - t0,
    )
    if use_cache:
        _CERT_CACHE[key] = res
    return res


def soundness_status(enc) -> Optional[bool]:
    """Best-effort certificate status for telemetry lane configs:
    True/False when the analyzer ran, None when it cannot (no
    declared reductions, or the analysis itself raised — telemetry
    must never take an engine down)."""
    try:
        res = certify_encoding(enc)
    except Exception:
        return None
    if res.sym_certified is None and res.ample_certified is None:
        return None
    return res.certified


# -- the engine gates -------------------------------------------------------

def gate_symmetry(enc, engine: str,
                  unsound_ok: bool = False) -> bool:
    """Spawn-time certificate gate for ``--symmetry``: returns True
    when the declared ``DeviceRewriteSpec`` is certified, False when
    uncertified but ``unsound_ok`` waives the refusal, and raises the
    unified :func:`checkers.common.soundness_refusal` otherwise."""
    from ..checkers.common import soundness_refusal

    res = certify_encoding(enc)
    if res.sym_certified is not False:
        return True
    if unsound_ok:
        return False
    f = res.failed("symmetry")
    raise soundness_refusal(
        engine, "symmetry", f.rule if f else "group-closure",
        f.message if f else "uncertified spec",
    )


def gate_ample(enc, engine: str, unsound_ok: bool = False) -> bool:
    """Spawn-time certificate gate for ``--ample-set`` (same contract
    as :func:`gate_symmetry`, ample scope)."""
    from ..checkers.common import soundness_refusal

    res = certify_encoding(enc)
    if res.ample_certified is not False:
        return True
    if unsound_ok:
        return False
    f = res.failed("ample")
    raise soundness_refusal(
        engine, "ample-set", f.rule if f else "ample-enabledness",
        f.message if f else "uncertified mask",
    )


# -- the artifact + CLI -----------------------------------------------------

def write_soundness_artifact(results, root=None) -> str:
    """``SOUND_rNN.json`` in the LINT_r*/COMM_r* shape conventions:
    own round sequence, clean flag, provenance block, per-spec
    certificates."""
    from ..artifacts import artifact_path, next_round, provenance

    path = artifact_path(
        "SOUND", root=root,
        round=next_round(root, stems=("SOUND",)),
    )
    report = {
        "schema": "soundness-cert/v1",
        "clean": all(r.certified for r in results),
        "specs": {r.encoding: r.as_dict() for r in results},
        "provenance": provenance(
            lane={"analyzer": "analysis/soundness.py"}
        ),
    }
    with open(path, "w") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def analyze_main(argv) -> int:
    """``stateright_tpu analyze soundness [MODEL] [COUNT]
    [--no-artifact]`` — certify the registered soundness targets (or
    one model) and write ``SOUND_rNN.json``. Exit 0 when every
    checked spec certifies, 1 otherwise."""
    argv = list(argv)
    if not argv or argv[0] != "soundness":
        print(
            "usage: stateright_tpu analyze soundness "
            "[MODEL] [COUNT] [--no-artifact]\n"
            "  MODEL: one of the registered soundness targets "
            "(analysis/registry.SOUNDNESS_TARGETS); default all"
        )
        return 2
    rest = argv[1:]
    no_artifact = "--no-artifact" in rest
    rest = [a for a in rest if a != "--no-artifact"]
    model = rest[0] if rest else None
    count = int(rest[1]) if len(rest) > 1 else None

    from .registry import SOUNDNESS_TARGETS

    targets = [
        (tname, factory) for tname, factory in SOUNDNESS_TARGETS
        if model is None or tname == model
    ]
    if not targets:
        known = [t for t, _ in SOUNDNESS_TARGETS]
        print(f"unknown model {model!r}; targets: {known}")
        return 2
    results = []
    for tname, factory in targets:
        enc = factory(count) if count is not None else factory(None)
        res = certify_encoding(enc, use_cache=False)
        results.append(res)
        status = "certified" if res.certified else "REFUSED"
        print(
            f"{tname} ({res.encoding}): {status} "
            f"[{res.analyzer_sec:.2f}s]"
        )
        for f in res.obligations:
            mark = "ok " if f.severity == "info" else "FAIL"
            print(f"  {mark} {f.rule}: {f.message}")
        if res.collapsed:
            print(
                "  over-approximated primitives: "
                f"{res.collapsed}"
            )
    if not no_artifact:
        path = write_soundness_artifact(results)
        print(f"wrote {os.path.basename(path)}")
    return 0 if all(r.certified for r in results) else 1
