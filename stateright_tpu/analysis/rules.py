"""The kernel-lint rule registry: the sparse-engine codegen contract
as declarative, source-attributed checks over traced jaxprs.

Two rounds of perf work (PERF.md §ordered, §wave-wall) priced exactly
these artifacts; each rule pins one of them:

* ``no-dense-mask`` — no ``[N, K]``/``[F, K]`` bool materialization on
  a sparse path (the 82x predicate-pass ablation: the engine consumes
  packed ``uint32[L]`` words, never the dense mask);
* ``no-mask-gather`` — the enabled-mask paths trace gather-free
  (shift-mask field extracts and word selects only; the 8x
  compiled-codegen tax was per-slot table gathers here);
* ``allowed-table-gather`` — step/fetch paths may gather only the
  intended table rows (params, flat transition, packed history, crash
  mask — at most the encoding's declared allowance);
* ``no-lane-padded-alu`` — no ``[N, 1]``-shaped ALU/compute outputs
  and no stack-of-scalars concats (≥3 ``[N, 1]`` operands): a
  ``[N, 1]`` elementwise op pays the full 128-lane tile-padding tax
  and XLA cannot fuse through the concatenate. The allowed residue is
  the hand-paxos calibration: ``[N, 1]`` SLICES from consuming
  multi-lane gather rows and 2-operand index-pair concats, which fuse;
* ``no-branch-pad-concat`` — ``cond``/``switch`` branches must update
  carried buffers with class-local ``dynamic_update_slice`` blocks,
  never rebuild a full-capacity tensor by padding/concatenating a
  small class result up to peak shape (the pre-round-6 carry pattern:
  a 2-row tail wave paying the 686k-row peak wave's copies);
* ``carry-copy-bytes`` — prices the switch-carry movement: bytes
  every ``cond``/``switch`` must materialize for its carry, and the
  carry-movement bytes inside each branch. The estimate is an info
  finding; fixtures listed in ``tables.CARRY_COPY_BYTE_BUDGETS`` are
  additionally GATED (round 9) — exceeding the per-fixture byte
  budget is an error, so the round-9 class collapse (PERF.md
  §layout: 1.42 MB → 0.24 MB per wave on the 2pc fixture) cannot
  silently regress.

A rule sees the shared walk (:mod:`.walker`) plus a :class:`TraceCtx`
describing the traced path, and yields :class:`Finding`\\ s. Rules
never import each other's state; adding a rule is appending to
``RULES``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

from .tables import (
    ALL_GATHER_ALLOWANCES,
    BRANCH_PAD_CONCAT_GROWTH,
    BRANCH_PAD_CONCAT_MIN_BYTES,
    CARRY_COPY_BYTE_BUDGETS,
    CARRY_MOVE_PRIMS,
    COMMS_BYTE_BUDGETS,
    REDUCTION_CATEGORIES,
    SCALAR_REDUCTION_MAX_ELEMS,
    collective_bytes,
    collective_category,
    is_collective,
    is_gather,
    output_bytes,
)
from .walker import (
    EqnSite,
    SiteWalk,
    eqn_alu_n1,
    eqn_dense_bool_k,
    eqn_wide_concat_n1,
    iter_eqns,  # noqa: F401 — re-exported for external walkers
    source_of,
)


@dataclass(frozen=True)
class TraceCtx:
    """What the lint driver knows about one traced path."""

    #: path label ("bits", "mask", "step", "engine:single",
    #: "engine:sharded", "wave-body")
    path: str
    #: encoding (or engine fixture) the path was traced from
    encoding: str
    #: batch rows of the trace (N frontier rows / vmap batch)
    n: int
    #: the encoding's action count K (dense-mask last dim)
    k: int
    #: dense [n, k] bool is banned on this path (packed-words paths
    #: and the engine pipeline; enabled_mask_vec's dense view is the
    #: CONTRACT on the "mask" path, so it sets False)
    sparse: bool = True
    #: gathers allowed (0 on mask paths; the table-row allowance on
    #: step paths; None = gathers unaudited, e.g. the wave body whose
    #: winner-fetch gathers are the intended idiom)
    allow_gathers: Optional[int] = 0
    #: True on table-fetch paths (step): gather findings report under
    #: allowed-table-gather with the table-row diagnosis, even at
    #: allowance 0 — a mask-path message for a step-path defect sends
    #: the maintainer to the wrong contract
    table_path: bool = False
    #: audit [n, 1] ALU / stack-of-scalars concats on this path
    check_lane_alu: bool = True
    #: audit cond/switch branch shapes + price carry movement
    check_branches: bool = False
    #: run the comms rule family (COMMS_RULES, round 13): collective
    #: placement/accounting over sharded wave paths — off on the
    #: single-chip contract paths, which trace no axis context
    check_comms: bool = False
    #: routing seam the no-unsorted-all-to-all rule requires every
    #: all_to_all operand to derive from: "sort" (the mesh engine's
    #: (owner, fp) routing sort), or None (rule off — paths with no
    #: shuffle)
    routing_seam: Optional[str] = None


@dataclass(frozen=True)
class Finding:
    """One rule hit, attributed to the source equation."""

    rule: str
    severity: str  # "error" | "info"
    encoding: str
    path: str
    message: str
    primitive: Optional[str] = None
    source: Optional[str] = None
    data: dict = field(default_factory=dict)

    def format(self) -> str:
        loc = f" @ {self.source}" if self.source else ""
        return (
            f"[{self.rule}] {self.encoding} / {self.path}: "
            f"{self.message}{loc}"
        )

    def as_dict(self) -> dict:
        """The JSON-artifact record of one finding — the ONE
        serialization every report writer uses (run_lint,
        run_comms_lint, the --hlo pass), so a new Finding field can't
        land in some artifacts and not others."""
        return dict(
            rule=self.rule,
            severity=self.severity,
            encoding=self.encoding,
            path=self.path,
            message=self.message,
            primitive=self.primitive,
            source=self.source,
            **({"data": self.data} if self.data else {}),
        )


@dataclass(frozen=True)
class Rule:
    name: str
    description: str
    run: Callable[[TraceCtx, list], Iterable[Finding]]


def _out_shapes(eqn):
    for v in eqn.outvars:
        sh = getattr(v.aval, "shape", None)
        if sh is not None:
            yield v.aval, sh


# -- no-dense-mask ---------------------------------------------------------

def _no_dense_mask(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    if not ctx.sparse:
        return
    for site in sites:
        if not eqn_dense_bool_k(site.eqn, ctx.k):
            continue
        shapes = [
            sh for _, sh in _out_shapes(site.eqn)
            if len(sh) == 2 and sh[1] == ctx.k
        ]
        yield Finding(
            rule="no-dense-mask",
            severity="error",
            encoding=ctx.encoding,
            path=ctx.path,
            message=(
                f"dense bool[{shapes[0][0]}, K={ctx.k}] mask "
                f"materialized by `{site.primitive}` on a "
                "sparse path (the engine consumes packed "
                "uint32 words; PERF.md §wave-wall priced this "
                "pass 82x)"
            ),
            primitive=site.primitive,
            source=source_of(site.eqn),
        )


# -- no-mask-gather / allowed-table-gather ---------------------------------

def _no_mask_gather(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    # mask-class paths only: a step-path gather is a table-fetch
    # defect and reports under allowed-table-gather below.
    if ctx.allow_gathers != 0 or ctx.table_path:
        return
    engine = ctx.path.startswith("engine:")
    for site in sites:
        if is_gather(site.primitive):
            yield Finding(
                rule="no-mask-gather",
                severity="error",
                encoding=ctx.encoding,
                path=ctx.path,
                message=(
                    f"`{site.primitive}` on a gather-free path — "
                    + (
                        "the engine's pair pipeline (bitmap "
                        "predicate, peel, packed-append compaction) "
                        "is elementwise + sort only; one Ba-row "
                        "gather costs a whole extra sort (PERF.md "
                        "§gathers)"
                        if engine
                        else "mask paths must be shift-mask field "
                        "extracts and word selects only (the 8x "
                        "compiled-codegen tax, PERF.md §ordered)"
                    )
                ),
                primitive=site.primitive,
                source=source_of(site.eqn),
            )


def _allowed_table_gather(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    # table-fetch (step) paths only, at ANY allowance including 0 —
    # hand 2pc's step is pure slot arithmetic, so its allowance IS 0
    # and a gather there must still get the table-row diagnosis.
    if not ctx.table_path or ctx.allow_gathers is None:
        return
    gathers = [s for s in sites if is_gather(s.primitive)]
    if len(gathers) > ctx.allow_gathers:
        srcs = ", ".join(source_of(s.eqn) for s in gathers)
        yield Finding(
            rule="allowed-table-gather",
            severity="error",
            encoding=ctx.encoding,
            path=ctx.path,
            message=(
                f"{len(gathers)} gathers on a table-fetch path whose "
                f"allowance is {ctx.allow_gathers} (the intended "
                "idiom is one multi-lane gather per table row — "
                "params, flat transition, packed history, crash "
                f"mask); gather sites: {srcs}"
            ),
            primitive=gathers[0].primitive,
            source=source_of(gathers[0].eqn),
            data={"gathers": len(gathers),
                  "allowance": ctx.allow_gathers},
        )


# -- no-lane-padded-alu ----------------------------------------------------

def _no_lane_padded_alu(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    if not ctx.check_lane_alu:
        return
    n = ctx.n
    for site in sites:
        eqn = site.eqn
        name = site.primitive
        if eqn_alu_n1(eqn, n):
            yield Finding(
                rule="no-lane-padded-alu",
                severity="error",
                encoding=ctx.encoding,
                path=ctx.path,
                message=(
                    f"[{n}, 1]-shaped `{name}` — real compute "
                    "at 128x lane padding (PERF.md §ordered); "
                    "keep lane math 1-D [N]-shaped and "
                    "reshape only at the very end"
                ),
                primitive=name,
                source=source_of(eqn),
            )
        n1_ops = eqn_wide_concat_n1(eqn, n)
        if n1_ops:
            yield Finding(
                rule="no-lane-padded-alu",
                severity="error",
                encoding=ctx.encoding,
                path=ctx.path,
                message=(
                    f"stack-of-scalars concatenate of {n1_ops} "
                    f"[{n}, 1] lanes — XLA cannot fuse through a "
                    "wide concatenate (the ~470ms/run artifact, "
                    "PERF.md §ordered); 2-operand index-pair "
                    "concats are the calibrated residue"
                ),
                primitive=name,
                source=source_of(eqn),
                data={"n1_operands": n1_ops},
            )


# -- no-branch-pad-concat --------------------------------------------------

def _axis0(sh) -> int:
    return int(sh[0]) if sh else 1


def _zeroish_rows(site: EqnSite, eqn) -> tuple:
    """Split a concatenate's axis-0 operand rows into (filler, real):
    filler operands are literals, jaxpr constants, or values a
    ``broadcast_in_dim`` of a scalar produced inside the same
    sub-jaxpr — the static signature of a ``zeros(...)`` pad block."""
    producers = {}
    if site.jaxpr is not None:
        for e in site.jaxpr.eqns:
            if e.primitive.name == "broadcast_in_dim" and not getattr(
                e.invars[0].aval, "shape", ()
            ):
                for v in e.outvars:
                    producers[id(v)] = "scalar-broadcast"
        consts = set(map(id, site.jaxpr.constvars))
    else:
        consts = set()
    filler = real = 0
    for v in eqn.invars:
        sh = getattr(v.aval, "shape", None)
        rows = _axis0(sh) if sh else 1
        if (
            not hasattr(v, "count")  # Literal
            or id(v) in consts
            or id(v) in producers
        ):
            filler += rows
        else:
            real += rows
    return filler, real


def _no_branch_pad_concat(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    if not ctx.check_branches:
        return
    for site in sites:
        # Only a pad/concat RETURNED as part of a branch's carry
        # (directly or through convert/reshape passthroughs) is the
        # priced pattern (rebuilding a carried buffer at peak shape);
        # internal temporaries — merge sort lanes, index plumbing —
        # are the engine's legitimate concats.
        if not site.in_branch():
            continue
        eqn = site.eqn
        name = site.primitive
        if name not in ("pad", "concatenate"):
            continue
        if not site.reaches_output():
            continue
        outs = list(_out_shapes(eqn))
        if not outs:
            continue
        out_aval, out_sh = outs[0]
        nbytes = output_bytes(out_aval)
        if nbytes < BRANCH_PAD_CONCAT_MIN_BYTES or not out_sh:
            continue
        if name == "concatenate" and eqn.params.get("dimension") != 0:
            continue
        in0 = max(
            (_axis0(getattr(v.aval, "shape", ()))
             for v in eqn.invars
             if getattr(v.aval, "shape", None)),
            default=1,
        )
        grown = _axis0(out_sh) >= BRANCH_PAD_CONCAT_GROWTH * max(in0, 1)
        padded = False
        if name == "pad":
            cfg = eqn.params.get("padding_config") or ()
            if cfg:
                lo, hi, _ = cfg[0]
                padded = lo + hi >= max(in0, 1)
        else:
            filler, real = _zeroish_rows(site, eqn)
            padded = filler >= max(real, 1)
        if not (grown or padded):
            continue
        yield Finding(
            rule="no-branch-pad-concat",
            severity="error",
            encoding=ctx.encoding,
            path=ctx.path,
            message=(
                f"branch carry built by `{name}` inside "
                f"{site.branch_path()}: axis 0 {in0} -> "
                f"{_axis0(out_sh)} rows ({nbytes / 1e6:.2f} MB out)"
                " — switch branches must write class-local "
                "dynamic_update_slice blocks into the carried "
                "buffer, not pad a class result to peak shape (the "
                "round-6 carry rework, PERF.md §wave-wall)"
            ),
            primitive=name,
            source=source_of(eqn),
            data={"in_rows": in0, "out_rows": _axis0(out_sh),
                  "out_bytes": nbytes},
        )


# -- carry-copy-bytes (estimator) ------------------------------------------

def _carry_copy_bytes(ctx: TraceCtx, sites: list) -> Iterable[Finding]:
    """Price the carry each ``cond``/``switch`` materializes: the
    bytes of every branch's returned carry (the movement XLA performs
    between classes) plus the carry-movement primitive bytes inside
    branches. The estimate always lands as an info finding; since
    round 9 the rule is also GATED — a fixture listed in
    ``tables.CARRY_COPY_BYTE_BUDGETS`` whose switch-carry total
    exceeds its budget yields an ERROR, so a refactor can't silently
    re-inflate the switch carries the round-9 class collapse removed
    (the 2pc fixture went 1.42 MB → 0.24 MB/wave; PERF.md §layout)."""
    if not ctx.check_branches:
        return
    switch_bytes = 0
    n_switches = 0
    move_bytes = 0
    top = None  # fattest switch
    for site in sites:
        eqn = site.eqn
        if site.primitive == "cond":
            n_switches += 1
            b = sum(output_bytes(v.aval) for v in eqn.outvars)
            switch_bytes += b
            if top is None or b > top[0]:
                top = (b, len(eqn.params.get("branches", ())),
                       source_of(eqn))
        elif site.in_branch() and site.primitive in CARRY_MOVE_PRIMS:
            move_bytes += sum(
                output_bytes(v.aval) for v in eqn.outvars
            )
    if n_switches == 0:
        return
    top_b, top_nb, top_src = top
    budget = CARRY_COPY_BYTE_BUDGETS.get(ctx.encoding)
    yield Finding(
        rule="carry-copy-bytes",
        severity="info",
        encoding=ctx.encoding,
        path=ctx.path,
        message=(
            f"{n_switches} cond/switch eqns carry "
            f"{switch_bytes / 1e6:.2f} MB of outputs (fattest: "
            f"{top_b / 1e6:.2f} MB x {top_nb} branches @ {top_src}); "
            f"{move_bytes / 1e6:.2f} MB of pad/slice/concat/"
            "dynamic_update_slice outputs inside branches"
            + (f"; budget {budget / 1e6:.2f} MB"
               if budget is not None else "")
        ),
        primitive="cond",
        source=top_src,
        data={
            "switches": n_switches,
            "switch_carry_bytes": switch_bytes,
            "fattest_switch_bytes": top_b,
            "branch_move_bytes": move_bytes,
            **({"budget_bytes": budget} if budget is not None else {}),
        },
    )
    if budget is not None and switch_bytes > budget:
        yield Finding(
            rule="carry-copy-bytes",
            severity="error",
            encoding=ctx.encoding,
            path=ctx.path,
            message=(
                f"switch-carry bytes {switch_bytes:,} exceed this "
                f"fixture's budget {budget:,} "
                "(analysis/tables.CARRY_COPY_BYTE_BUDGETS) — the "
                "class ladder is copying carry tuples between "
                "branches again; keep merge cores returning the "
                "shared SoA result and resident-buffer updates in "
                "ONE fetch switch per wave (the round-9 collapse, "
                "PERF.md §layout). Raise the budget only for a "
                "deliberate, priced carry addition."
            ),
            primitive="cond",
            source=top_src,
            data={
                "switch_carry_bytes": switch_bytes,
                "budget_bytes": budget,
            },
        )


# -- the comms rule family (round 13: comms-lint) --------------------------
#
# Static collective accounting and shard-safety over the sharded wave
# paths (analysis/comms.py traces them; ISSUE: a misplaced collective
# only surfaces as a deadlock or a silent traffic blow-up ON CHIP,
# where debugging is most expensive). Each rule pins one documented
# invariant of parallel/engine_sortmerge.py:
#
# * ``no-collective-in-switch`` — a collective under a cond/switch
#   whose index is SHARD-VARYING deadlocks: collectives are
#   collective, so every shard must take the same branch. The engines'
#   class switches are legal exactly because their indices are
#   pmax-agreed (walker.shard_varying_vars proves it);
# * ``no-unsorted-all-to-all`` — every all_to_all operand must be
#   data-dependent on the routing seam (the (owner, fp) sort), or the
#   shuffle ships unrouted candidates;
# * ``scalar-only-reductions`` — psum/pmax/pmin operands stay rank-0/
#   tiny; a reduction over a resident buffer is an accidental
#   replication (S x the buffer per wave in all-reduce bandwidth);
# * ``no-all-gather`` — the wave path never all-gathers (visited state
#   is owner-sharded BY CONSTRUCTION; gathering it back is the 8x
#   traffic blow-up), gated with a per-fixture allowance table for
#   legitimate drain paths (tables.ALL_GATHER_ALLOWANCES);
# * ``comms-bytes`` — the collective analog of carry-copy-bytes:
#   price every collective from operand shapes, report per-category
#   totals + the PER-WAVE PEAK (fattest class branch + out-of-branch
#   collectives), GATED against tables.COMMS_BYTE_BUDGETS.


def _walk_of(sites) -> SiteWalk:
    """The SiteWalk (dataflow-capable) view of a rule's site list.
    The comms rules NEED the whole-jaxpr dataflow marks; a plain
    hand-built list can't recover the root jaxpr, and silently
    treating it as 'nothing is shard-varying / nothing is
    seam-derived' would pass the deadlock shape and flag every
    legitimate all_to_all — fail loudly instead (run_rules always
    constructs a SiteWalk; only bespoke callers can hit this)."""
    if isinstance(sites, SiteWalk):
        return sites
    raise TypeError(
        "comms rules require the SiteWalk from run_rules/"
        "run_rules_with_stats (whole-jaxpr dataflow marks); got a "
        "plain site list, whose root jaxpr is not recoverable"
    )


def _no_collective_in_switch(ctx: TraceCtx, sites) -> Iterable[Finding]:
    if not ctx.check_comms:
        return
    varying = _walk_of(sites).shard_varying()
    for site in sites:
        if not is_collective(site.primitive):
            continue
        for cond_eqn, idx in site.enclosing_conds():
            iv = cond_eqn.invars[0]
            if not hasattr(iv, "count"):
                continue  # literal index: trivially uniform
            if id(iv) in varying:
                yield Finding(
                    rule="no-collective-in-switch",
                    severity="error",
                    encoding=ctx.encoding,
                    path=ctx.path,
                    message=(
                        f"`{site.primitive}` nested under "
                        f"{site.branch_path()} whose switch index is "
                        "SHARD-VARYING — shards take different "
                        "branches and the collective deadlocks on "
                        "chip (the engine invariant: class switches "
                        "are pmax-agreed so every shard runs the "
                        f"same branch; switch @ {source_of(cond_eqn)})"
                    ),
                    primitive=site.primitive,
                    source=source_of(site.eqn),
                    data={"branch": idx,
                          "switch_source": source_of(cond_eqn)},
                )
                break


def _no_unsorted_all_to_all(ctx: TraceCtx, sites) -> Iterable[Finding]:
    if not ctx.check_comms or ctx.routing_seam is None:
        return
    seam = _walk_of(sites).seam_derived()
    for site in sites:
        if site.primitive != "all_to_all":
            continue
        routed = any(
            hasattr(v, "count") and id(v) in seam
            for v in site.eqn.invars
        )
        if not routed:
            yield Finding(
                rule="no-unsorted-all-to-all",
                severity="error",
                encoding=ctx.encoding,
                path=ctx.path,
                message=(
                    "all_to_all operand is not data-dependent on "
                    "the (owner, fp) routing sort — the shuffle ships "
                    "unrouted candidates, so rows land on shards that "
                    "do not own their fingerprints and the owner-local "
                    "dedup contract breaks silently "
                    "(engine_sortmerge.py wave step 2-3)"
                ),
                primitive=site.primitive,
                source=source_of(site.eqn),
                data={"seam": ctx.routing_seam},
            )


def _scalar_only_reductions(ctx: TraceCtx, sites) -> Iterable[Finding]:
    if not ctx.check_comms:
        return
    for site in sites:
        if not is_collective(site.primitive):
            continue
        if collective_category(site.primitive) \
                not in REDUCTION_CATEGORIES:
            continue
        for v in site.eqn.invars:
            sh = getattr(getattr(v, "aval", None), "shape", None)
            if sh is None:
                continue
            elems = 1
            for d in sh:
                elems *= int(d)
            if elems <= SCALAR_REDUCTION_MAX_ELEMS:
                continue
            yield Finding(
                rule="scalar-only-reductions",
                severity="error",
                encoding=ctx.encoding,
                path=ctx.path,
                message=(
                    f"`{site.primitive}` over a {list(sh)} operand "
                    f"({elems:,} elements > "
                    f"{SCALAR_REDUCTION_MAX_ELEMS}) — an accidental "
                    "replication: every shard pays the full buffer's "
                    "all-reduce bandwidth per wave; the engines "
                    "psum SCALARS (counters, flags) and tiny "
                    "per-property vectors only"
                ),
                primitive=site.primitive,
                source=source_of(site.eqn),
                data={"shape": [int(d) for d in sh],
                      "elements": elems},
            )
            break


def _no_all_gather(ctx: TraceCtx, sites) -> Iterable[Finding]:
    if not ctx.check_comms:
        return
    gsites = [
        s for s in sites
        if is_collective(s.primitive)
        and collective_category(s.primitive) == "all-gather"
    ]
    allowance = ALL_GATHER_ALLOWANCES.get(ctx.encoding, 0)
    if len(gsites) <= allowance:
        return
    srcs = ", ".join(source_of(s.eqn) for s in gsites)
    yield Finding(
        rule="no-all-gather",
        severity="error",
        encoding=ctx.encoding,
        path=ctx.path,
        message=(
            f"{len(gsites)} all_gather eqn(s) on a wave path whose "
            f"allowance is {allowance} — visited state is "
            "owner-sharded by construction; gathering it back onto "
            "every shard is the S-fold traffic blow-up sharding "
            "exists to avoid. Register a drain-path allowance in "
            "tables.ALL_GATHER_ALLOWANCES only for a deliberate, "
            f"priced collection; sites: {srcs}"
        ),
        primitive=gsites[0].primitive,
        source=source_of(gsites[0].eqn),
        data={"all_gathers": len(gsites), "allowance": allowance},
    )


def _branch_tree_peak(node: dict) -> int:
    """Per-wave peak of a branch tree: a node's own collective bytes
    plus, for EVERY nested cond below it, the fattest of that cond's
    branches — at any depth exactly one branch of each switch runs
    per wave, so siblings take max while distinct conds (which all
    execute) sum."""
    total = node["bytes"]
    for branches in node["conds"].values():
        total += max(
            _branch_tree_peak(child) for child in branches.values()
        )
    return total


def _comms_bytes(ctx: TraceCtx, sites) -> Iterable[Finding]:
    """Price every collective from operand shapes. Collectives are
    attributed to their FULL cond/switch branch path and the per-wave
    peak takes the fattest branch at every nesting level (mutually
    exclusive siblings max, sequential conds sum) plus everything
    outside any switch — the number the byte budget gates
    (tables.COMMS_BYTE_BUDGETS) and the one a mesh trace's routed-byte
    counters reconcile against (telemetry.shard_balance comms_static;
    PERF.md §comms-lint)."""
    if not ctx.check_comms:
        return
    per_cat: dict = {}
    # branch tree: bytes at this nesting level + per nested cond a
    # {branch_idx: subtree} map (see _branch_tree_peak)
    tree = {"bytes": 0, "conds": {}}
    a2a_rows_max = 0
    a2a_row_bytes = None
    a2a_eqns = 0
    n_coll = 0
    top = None
    for site in sites:
        if not is_collective(site.primitive):
            continue
        n_coll += 1
        b = collective_bytes(site.eqn)
        cat = collective_category(site.primitive)
        slot = per_cat.setdefault(cat, {"eqns": 0, "bytes": 0})
        slot["eqns"] += 1
        slot["bytes"] += b
        if top is None or b > top[0]:
            top = (b, site.primitive, source_of(site.eqn))
        node = tree
        for ce, idx in site.enclosing_conds():
            node = node["conds"].setdefault(id(ce), {}).setdefault(
                idx, {"bytes": 0, "conds": {}}
            )
        node["bytes"] += b
        if site.primitive == "all_to_all":
            a2a_eqns += 1
            for v in site.eqn.invars:
                sh = getattr(getattr(v, "aval", None), "shape", None)
                if sh and len(sh) >= 2:
                    rows = int(sh[0])
                    lanes = 1
                    for d in sh[1:]:
                        lanes *= int(d)
                    rb = lanes * v.aval.dtype.itemsize
                    a2a_rows_max = max(a2a_rows_max, rows)
                    a2a_row_bytes = (
                        rb if a2a_row_bytes is None
                        else max(a2a_row_bytes, rb)
                    )
    if n_coll == 0:
        return
    per_wave_peak = _branch_tree_peak(tree)
    budget = COMMS_BYTE_BUDGETS.get(ctx.encoding)
    top_b, top_prim, top_src = top
    yield Finding(
        rule="comms-bytes",
        severity="info",
        encoding=ctx.encoding,
        path=ctx.path,
        message=(
            f"{n_coll} collective eqns move "
            f"{sum(s['bytes'] for s in per_cat.values()) / 1e6:.3f}"
            " MB (static program total); per-wave peak "
            f"{per_wave_peak / 1e6:.3f} MB (fattest branch at every "
            f"switch level + unswitched collectives); fattest: "
            f"{top_prim} {top_b / 1e6:.3f} MB @ {top_src}"
            + (f"; budget {budget / 1e6:.3f} MB"
               if budget is not None else "")
        ),
        primitive=top_prim,
        source=top_src,
        data={
            "collectives": n_coll,
            "per_category": per_cat,
            "bytes_total": sum(
                s["bytes"] for s in per_cat.values()
            ),
            "per_wave_peak_bytes": per_wave_peak,
            "all_to_all_eqns": a2a_eqns,
            **({"all_to_all_row_bytes": a2a_row_bytes,
                "all_to_all_rows_max": a2a_rows_max}
               if a2a_row_bytes is not None else {}),
            **({"budget_bytes": budget}
               if budget is not None else {}),
        },
    )
    if budget is not None and per_wave_peak > budget:
        yield Finding(
            rule="comms-bytes",
            severity="error",
            encoding=ctx.encoding,
            path=ctx.path,
            message=(
                f"per-wave collective bytes {per_wave_peak:,} exceed "
                f"this fixture's budget {budget:,} "
                "(analysis/tables.COMMS_BYTE_BUDGETS) — the wave "
                "body grew cross-chip traffic (a second shuffle, a "
                "buffer-sized reduction, an S-fold gather). Raise "
                "the budget only for a deliberate, priced "
                "communication addition."
            ),
            primitive=top_prim,
            source=top_src,
            data={
                "per_wave_peak_bytes": per_wave_peak,
                "budget_bytes": budget,
            },
        )


#: the comms rule family — run alongside RULES by the shared driver,
#: active only on paths whose TraceCtx sets ``check_comms``
#: (analysis/comms.py's sharded fixtures; the kernel lint's engine
#: paths enable it too, as belt-and-braces against a collective
#: sneaking into the pair pipeline via sharding propagation).
COMMS_RULES: tuple = (
    Rule(
        name="no-collective-in-switch",
        description=(
            "collectives only under shard-UNIFORM (pmax-agreed) "
            "switch indices — a shard-varying branch deadlocks the "
            "mesh"
        ),
        run=_no_collective_in_switch,
    ),
    Rule(
        name="no-unsorted-all-to-all",
        description=(
            "every all_to_all operand derives from the (owner, fp) "
            "routing sort, never raw candidates"
        ),
        run=_no_unsorted_all_to_all,
    ),
    Rule(
        name="scalar-only-reductions",
        description=(
            "psum/pmax/pmin operands rank-0/tiny (<= "
            f"{SCALAR_REDUCTION_MAX_ELEMS} elements); buffer-sized "
            "reductions are accidental replication"
        ),
        run=_scalar_only_reductions,
    ),
    Rule(
        name="no-all-gather",
        description=(
            "no all_gather on wave paths (S-fold traffic); gated by "
            "the drain-path allowance table"
        ),
        run=_no_all_gather,
    ),
    Rule(
        name="comms-bytes",
        description=(
            "price collectives from operand shapes; per-wave peak "
            "GATED against tables.COMMS_BYTE_BUDGETS"
        ),
        run=_comms_bytes,
    ),
)


def _soundness_rule(obligation: str):
    """Lazy delegate for the soundness rule family: these rules run
    over ENCODINGS (the declared reduction specs), not traced paths —
    the analyzer drives them through ``certify_encoding``
    (analysis/soundness.py) and this registry entry filters its
    Finding stream to one obligation, so ``run_rules``-style drivers
    and ``analyze soundness`` report through the same Rule names."""

    def run(ctx, sites):
        enc = getattr(ctx, "encoded", None)
        if enc is None:
            return []
        from .soundness import certify_encoding

        return [
            f for f in certify_encoding(enc).obligations
            if f.rule == obligation
        ]

    return run


#: the reduction soundness obligation family (analysis/soundness.py,
#: certificate SOUND_r*.json): per-encoding STATIC proofs the engine
#: gates consult before trusting a declared DeviceRewriteSpec or
#: ample mask. Registered here so the obligation names and
#: descriptions live in the same registry as the codegen rules — the
#: refusal messages (checkers/common.soundness_refusal) and the
#: fixture tests key on these names.
SOUNDNESS_RULES: tuple = (
    Rule(
        name="group-closure",
        description=(
            "the rewrite set is a permutation-group action on the "
            "limb layout: structural bounds plus cross-field member "
            "bit disjointness (bijective relabeling)"
        ),
        run=_soundness_rule("group-closure"),
    ),
    Rule(
        name="orbit-structure",
        description=(
            "canonicalization is idempotent, member-permuting "
            "(tuple multiset preserved, non-group bits untouched), "
            "and keyed on the FULL per-member tuple"
        ),
        run=_soundness_rule("orbit-structure"),
    ),
    Rule(
        name="fingerprint-invariance",
        description=(
            "the canonical form — hence the fingerprint fold — is "
            "invariant under every generator transposition"
        ),
        run=_soundness_rule("fingerprint-invariance"),
    ),
    Rule(
        name="property-invariance",
        description=(
            "every registered Property predicate is group-invariant "
            "(member-uniform static bit footprint + semantic "
            "battery agreement)"
        ),
        run=_soundness_rule("property-invariance"),
    ),
    Rule(
        name="transition-equivariance",
        description=(
            "the successor set commutes with the group: "
            "multiset{tau.succ(v)} == multiset{succ(tau.v)}"
        ),
        run=_soundness_rule("transition-equivariance"),
    ),
    Rule(
        name="ample-enabledness",
        description=(
            "enabledness preservation: a dropped slot's guard "
            "implies some kept slot's guard over the footprint cone"
        ),
        run=_soundness_rule("ample-enabledness"),
    ),
    Rule(
        name="ample-non-suppression",
        description=(
            "no property-relevant dropped transition lacks a "
            "symmetric kept image (guard and successor agree under "
            "a group element)"
        ),
        run=_soundness_rule("ample-non-suppression"),
    ),
)


#: the registry — ``tools/lint_kernels.py`` and ``pytest -m lint``
#: both run exactly this list.
RULES: tuple = (
    Rule(
        name="no-dense-mask",
        description=(
            "no [N, K]/[F, K] bool materialization on the sparse "
            "path (packed uint32 words are the mask)"
        ),
        run=_no_dense_mask,
    ),
    Rule(
        name="no-mask-gather",
        description=(
            "enabled-mask paths trace gather-free (shift-mask field "
            "extracts + word selects only)"
        ),
        run=_no_mask_gather,
    ),
    Rule(
        name="allowed-table-gather",
        description=(
            "step paths gather at most the encoding's declared "
            "table-row allowance (the four intended fetches)"
        ),
        run=_allowed_table_gather,
    ),
    Rule(
        name="no-lane-padded-alu",
        description=(
            "no [N, 1]-shaped ALU outputs, no >=3-operand [N, 1] "
            "concats (hand-paxos fuse-through residue allowed)"
        ),
        run=_no_lane_padded_alu,
    ),
    Rule(
        name="no-branch-pad-concat",
        description=(
            "switch branches update carries with class-local "
            "dynamic_update_slice, never full-capacity pad+concat"
        ),
        run=_no_branch_pad_concat,
    ),
    Rule(
        name="carry-copy-bytes",
        description=(
            "price the carry bytes each switch materializes; GATED "
            "against per-fixture byte budgets "
            "(tables.CARRY_COPY_BYTE_BUDGETS)"
        ),
        run=_carry_copy_bytes,
    ),
)


def run_rules(ctx: TraceCtx, closed) -> list:
    """Run every registered rule over one traced path. ``closed`` is
    a ``ClosedJaxpr`` (``jax.make_jaxpr`` output)."""
    return run_rules_with_stats(ctx, closed)[0]


def run_rules_with_stats(ctx: TraceCtx, closed) -> tuple:
    """``(findings, n_eqns)`` — one walk serves both the rules and
    the coverage stats (the lint driver's per-path eqn counts; big
    traces run to thousands of eqns, so the walk is not re-done just
    to count). The walk is a :class:`walker.SiteWalk`, so the comms
    rules' whole-jaxpr dataflow marks compute at most once per path;
    COMMS_RULES run after RULES and self-gate on ``ctx.check_comms``."""
    sites = SiteWalk(closed)
    findings: list = []
    for rule in RULES + COMMS_RULES:
        findings.extend(rule.run(ctx, sites))
    return findings, len(sites)
