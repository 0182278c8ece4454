"""Jaxpr traversal for the kernel-lint rules.

One walk, every consumer: :func:`iter_eqns` yields each equation of a
(closed) jaxpr together with the stack of enclosing higher-order
primitives — ``scan``/``while`` bodies, ``cond``/``switch`` branches
(with the branch index), ``jit``/``custom_jvp`` call bodies, anything
that stores sub-jaxprs in its params — so a rule can ask "is this pad
inside a switch branch?" without re-implementing the descent. The
codegen-shape audit (:func:`audit_jaxpr`) and the lint rules
(:mod:`.rules`) both run on this stream.

Source attribution: every yielded equation carries its jax
``source_info``; :func:`source_of` renders it as ``file:line (fn)``
(the innermost non-jax user frame), which is what a lint finding
prints so a flagged op points at the encoding/engine line that traced
it, not at the walker.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator


@dataclass(frozen=True)
class EqnSite:
    """One equation plus where the walk found it.

    ``stack`` is a tuple of ``(enclosing_primitive_name, branch_index
    or None, enclosing_eqn)`` from outermost to innermost — e.g. a pad
    inside the third branch of the class-ladder switch inside the wave
    while-loop walks in with ``(("while", None, <while eqn>),
    ("cond", 2, <switch eqn>))``. The enclosing eqn (round 13) is what
    lets the comms rules read a switch's INDEX operand — "is this
    collective under a shard-uniform switch?" needs the ``cond`` eqn
    itself, not just its name. ``jaxpr`` is the (sub-)jaxpr the
    equation belongs to, so a rule can ask whether an equation's
    result is one of its jaxpr's OUTPUTS (a branch returning a rebuilt
    buffer as its carry) versus an internal temporary (a sort lane
    that never leaves the branch).
    """

    eqn: Any
    stack: tuple
    jaxpr: Any = None

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    def in_branch(self) -> bool:
        """True when the equation sits inside a ``cond``/``switch``
        branch computation at any depth."""
        return any(name == "cond" for name, _, _ in self.stack)

    def enclosing_conds(self):
        """The ``cond``/``switch`` eqns this site is nested under,
        outermost first, as ``(cond_eqn, branch_index)`` pairs."""
        return [
            (eqn, idx)
            for name, idx, eqn in self.stack
            if name == "cond"
        ]

    def reaches_output(self) -> bool:
        """True when one of the equation's results is returned by its
        enclosing (sub-)jaxpr — directly, or through a chain of
        value-preserving unary ops (tables.PASSTHROUGH_PRIMS: a
        ``.astype(...)``/reshape between a rebuilt buffer and the
        branch return must not hide it). For an equation inside a
        branch, reaching the output means the value is part of the
        branch's returned carry.

        Known limitation: a value laundered through a BINARY ALU
        identity (``x | 0``, ``x + 0``) is not followed — treating
        ALU ops as passthrough would over-approximate reachability
        and flag legitimate in-branch compute whose result happens
        to be returned."""
        from .tables import PASSTHROUGH_PRIMS

        jx = self.jaxpr
        if jx is None:
            return False
        outs = set(map(id, jx.outvars))
        frontier = {id(v) for v in self.eqn.outvars}
        if frontier & outs:
            return True
        # follow pure passthroughs forward (the jaxpr is
        # topologically ordered, so one linear scan covers chains)
        for e in jx.eqns:
            if e.primitive.name not in PASSTHROUGH_PRIMS:
                continue
            if any(id(v) in frontier for v in e.invars
                   if hasattr(v, "count")):
                for v in e.outvars:
                    frontier.add(id(v))
                    if id(v) in outs:
                        return True
        return False

    def branch_path(self) -> str:
        return "/".join(
            name if idx is None else f"{name}[{idx}]"
            for name, idx, _ in self.stack
        )


def _sub_jaxprs(eqn) -> Iterator[tuple]:
    """Yield ``(sub_jaxpr, branch_index or None)`` for every sub-jaxpr
    stored in an equation's params. ``cond``'s ``branches`` param (the
    jaxpr form of both ``lax.cond`` and ``lax.switch``) is the one
    list whose position is meaningful — branch indices let the
    branch-shape rules name the offending class."""
    for key, p in eqn.params.items():
        if hasattr(p, "jaxpr"):
            yield p.jaxpr, None
        elif hasattr(p, "eqns"):
            # an open Jaxpr stored directly (e.g. shard_map's param)
            yield p, None
        elif isinstance(p, (list, tuple)):
            for i, q in enumerate(p):
                if hasattr(q, "jaxpr"):
                    yield q.jaxpr, (i if key == "branches" else None)
                elif hasattr(q, "eqns"):
                    yield q, (i if key == "branches" else None)


def iter_eqns(jaxpr, _stack: tuple = ()) -> Iterator[EqnSite]:
    """Depth-first over every equation of ``jaxpr`` (a ``Jaxpr`` — pass
    ``closed.jaxpr`` for a ``ClosedJaxpr``) including all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, _stack, jaxpr)
        name = eqn.primitive.name
        for sub, branch in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, _stack + ((name, branch, eqn),))


# -- whole-jaxpr dataflow (the comms rules' shared analyses) ---------------
#
# Two questions the collective rules ask need more than one equation's
# shapes: "is this switch's index shard-UNIFORM?" (a collective under a
# shard-varying switch deadlocks — branches diverge across the mesh)
# and "is this all_to_all's operand derived from the routing seam?"
# (an unsorted operand ships unrouted candidates). Both are forward
# dataflow marks over the whole (closed) jaxpr, sub-jaxprs included.
#
# Sub-jaxpr boundaries are mapped PRECISELY where jax fixes the
# convention — ``cond`` (invars[0] is the index, operands map 1:1 to
# every branch's invars, outvars positionally) and call-like
# primitives with matching arity — and OVER-APPROXIMATED elsewhere
# (scan/while carries: any marked operand marks all sub invars, any
# marked sub outvar marks all eqn outvars). Over-approximation is in
# the mark-MORE direction for both analyses, which errs toward
# flagging in the uniformity rule (a "maybe-varying" switch index
# flags) and toward NOT flagging in the seam rule (a "maybe-routed"
# operand passes); the deliberate-regression tests pin that both
# still catch the real defect shapes. The marking runs to fixpoint,
# so taint that only develops through a loop-carry round trip is not
# missed.

#: collectives whose RESULT is identical on every shard regardless of
#: operand variance — the uniformity analysis clears taint through
#: these (the engines' pmax class agreement is exactly this: a
#: shard-varying count goes in, a mesh-uniform class comes out).
_UNIFORM_RESULT_COLLECTIVES = frozenset({
    "psum", "psum2", "pmax", "pmin", "all_gather",
    "all_gather_invariant",
})

#: call-like primitives whose sub-jaxpr I/O maps positionally.
_CALL_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
    "custom_vjp_call", "remat", "checkpoint",
})


def _mark(marked: set, v) -> bool:
    if not hasattr(v, "count"):  # Literal
        return False
    if id(v) in marked:
        return False
    marked.add(id(v))
    return True


def _flow(jaxpr, marked: set, *, seeds, clears: frozenset,
          shard_map_seeds: bool) -> bool:
    """One forward pass over ``jaxpr`` and its sub-jaxprs; returns
    True when any new var was marked (the fixpoint driver re-runs
    until False)."""
    changed = False
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        tainted_in = any(
            hasattr(v, "count") and id(v) in marked
            for v in eqn.invars
        )
        subs = list(_sub_jaxprs(eqn))
        for sub, _branch in subs:
            if shard_map_seeds and name == "shard_map":
                # entering the mesh region: every per-shard view is a
                # taint source
                for sv in sub.invars:
                    changed |= _mark(marked, sv)
            elif name == "cond":
                for ev, sv in zip(eqn.invars[1:], sub.invars):
                    if hasattr(ev, "count") and id(ev) in marked:
                        changed |= _mark(marked, sv)
            elif name in _CALL_PRIMS and len(sub.invars) == len(
                eqn.invars
            ):
                for ev, sv in zip(eqn.invars, sub.invars):
                    if hasattr(ev, "count") and id(ev) in marked:
                        changed |= _mark(marked, sv)
            elif tainted_in:
                for sv in sub.invars:
                    changed |= _mark(marked, sv)
            changed |= _flow(
                sub, marked, seeds=seeds, clears=clears,
                shard_map_seeds=shard_map_seeds,
            )
            sub_out_marked = any(
                hasattr(sv, "count") and id(sv) in marked
                for sv in sub.outvars
            )
            # LOOP-CARRY FEEDBACK: in a while/scan body the outputs
            # feed the next iteration's inputs, so a mark born INSIDE
            # the body (an axis_index, a nested source) must taint the
            # carried invars too — without this edge, taint that only
            # develops through a loop round trip never reaches a
            # switch index read from the carry (over-approx: all sub
            # invars, since the carry position mapping is
            # primitive-specific). The global fixpoint then re-runs
            # the body with the carry tainted.
            if name in ("while", "scan") and sub_out_marked:
                for sv in sub.invars:
                    changed |= _mark(marked, sv)
            # sub outputs back to the eqn's outputs
            if name == "cond" or (
                name in _CALL_PRIMS
                and len(sub.outvars) == len(eqn.outvars)
            ):
                for sv, ev in zip(sub.outvars, eqn.outvars):
                    if hasattr(sv, "count") and id(sv) in marked:
                        changed |= _mark(marked, ev)
            elif sub_out_marked:
                for ev in eqn.outvars:
                    changed |= _mark(marked, ev)
        if name in clears:
            # result independent of operand variance (e.g. a psum is
            # mesh-uniform no matter what went in)
            continue
        if seeds(eqn) or tainted_in:
            for v in eqn.outvars:
                changed |= _mark(marked, v)
    return changed


def _fixpoint(closed, *, seeds, clears=frozenset(),
              shard_map_seeds=False) -> set:
    marked: set = set()
    while _flow(closed.jaxpr, marked, seeds=seeds, clears=clears,
                shard_map_seeds=shard_map_seeds):
        pass
    return marked


def shard_varying_vars(closed) -> set:
    """ids of vars that may differ across shards: everything flowing
    from a ``shard_map`` region's per-shard inputs or an
    ``axis_index``, EXCEPT through the uniform-result collectives
    (psum/pmax/pmin/all_gather), whose outputs every shard agrees on.
    The complement — a var NOT in this set — is provably mesh-uniform,
    which is what makes a ``lax.switch`` on it collective-safe."""
    return _fixpoint(
        closed,
        seeds=lambda eqn: eqn.primitive.name == "axis_index",
        clears=_UNIFORM_RESULT_COLLECTIVES,
        shard_map_seeds=True,
    )


def seam_derived_vars(closed, kind: str) -> set:
    """ids of vars data-dependent on the routing seam: ``kind="sort"``
    marks forward from multi-key ``sort`` eqns (the sharded sort-merge
    engine's (owner, fp) routing sort — ``num_keys >= 2`` excludes
    incidental single-key value sorts), ``kind="scatter"`` from
    scatter eqns (the hash engine's owner-position tile build). An
    ``all_to_all`` operand outside this set never went through the
    routing stage."""
    if kind == "sort":
        def seeds(eqn):
            return (
                eqn.primitive.name == "sort"
                and eqn.params.get("num_keys", 1) >= 2
            )
    elif kind == "scatter":
        def seeds(eqn):
            return eqn.primitive.name.startswith("scatter")
    else:
        raise ValueError(f"unknown routing seam kind {kind!r}")
    return _fixpoint(closed, seeds=seeds)


class SiteWalk(list):
    """The materialized equation walk of one closed jaxpr, plus the
    lazily-computed whole-jaxpr dataflow marks the comms rules share
    (one walk and at most one fixpoint per analysis per traced path —
    rules never re-run the traversal)."""

    def __init__(self, closed):
        super().__init__(iter_eqns(closed.jaxpr))
        self.closed = closed
        self._marks: dict = {}

    def shard_varying(self) -> set:
        if "varying" not in self._marks:
            self._marks["varying"] = shard_varying_vars(self.closed)
        return self._marks["varying"]

    def seam_derived(self, kind: str) -> set:
        key = f"seam:{kind}"
        if key not in self._marks:
            self._marks[key] = seam_derived_vars(self.closed, kind)
        return self._marks[key]


def source_of(eqn) -> str:
    """``file:line (function)`` of the user frame that traced the
    equation — the attribution a finding prints."""
    si = getattr(eqn, "source_info", None)
    if si is None:
        return "<unknown>"
    try:
        from jax._src import source_info_util

        return source_info_util.summarize(si)
    except Exception:
        return "<unknown>"


# -- the shared per-eqn shape predicates -----------------------------------
# One implementation each, consumed by BOTH the declarative rules
# (analysis/rules.py) and the codegen-shape audit below — the
# detection logic cannot drift between the lint and the tests.

def eqn_dense_bool_k(eqn, k: int) -> bool:
    """Any 2-D bool output whose LAST dim is ``k`` — the dense
    ``[rows, K]`` mask at any row count (frontier rows, pair-buffer
    rows, tile rows alike)."""
    import numpy as np

    for v in eqn.outvars:
        sh = getattr(v.aval, "shape", None)
        if (
            sh is not None
            and len(sh) == 2
            and sh[1] == k
            and getattr(v.aval, "dtype", None) == np.bool_
        ):
            return True
    return False


def eqn_alu_n1(eqn, n: int) -> bool:
    """An ALU primitive with a ``[n, 1]``-shaped output — real
    compute at 128x lane padding."""
    from .tables import ALU_PRIMS

    if eqn.primitive.name not in ALU_PRIMS:
        return False
    return any(
        getattr(v.aval, "shape", None) == (n, 1) for v in eqn.outvars
    )


def eqn_wide_concat_n1(eqn, n: int) -> int:
    """Count of ``[n, 1]`` operands when the eqn is a concatenate of
    ≥3 of them (the stack-of-lane-scalars pattern); else 0."""
    if eqn.primitive.name != "concatenate":
        return 0
    n1_ops = sum(
        1 for v in eqn.invars
        if getattr(v.aval, "shape", None) == (n, 1)
    )
    return n1_ops if n1_ops >= 3 else 0


def audit_jaxpr(closed, *, n: int, k: int):
    """The codegen-shape audit the tests calibrated (round 5/6),
    run over the shared walk and predicates: gather count,
    ``[n, 1]``-shaped ALU outputs, dense ``[*, k]`` bool outputs (any
    row count — tile- and pair-buffer-shaped dense masks count too),
    and concatenates of ≥3 ``[n, 1]`` operands (the
    stack-of-lane-scalars pattern).

    Returns ``dict(gathers, alu_n1, wide_concat_n1, bool_nk)`` with
    the same keys tests/test_codegen_shapes.py always asserted on,
    plus ``gather_sites`` / ``bool_nk_sites`` / ``alu_n1_sites``
    (``(primitive, source)`` pairs) so a failure names the traced
    line.
    """
    from .tables import is_gather

    stats = dict(
        gathers=0, alu_n1=[], wide_concat_n1=0, bool_nk=[],
        gather_sites=[], alu_n1_sites=[], bool_nk_sites=[],
        wide_concat_n1_sites=[],
    )
    for site in iter_eqns(closed.jaxpr):
        eqn = site.eqn
        name = site.primitive
        if is_gather(name):
            stats["gathers"] += 1
            stats["gather_sites"].append((name, source_of(eqn)))
        if eqn_wide_concat_n1(eqn, n):
            stats["wide_concat_n1"] += 1
            stats["wide_concat_n1_sites"].append(
                (name, source_of(eqn))
            )
        if eqn_alu_n1(eqn, n):
            stats["alu_n1"].append(name)
            stats["alu_n1_sites"].append((name, source_of(eqn)))
        if eqn_dense_bool_k(eqn, k):
            stats["bool_nk"].append(name)
            stats["bool_nk_sites"].append((name, source_of(eqn)))
    return stats
