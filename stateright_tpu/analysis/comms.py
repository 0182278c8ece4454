"""The comms-lint driver: static collective accounting and
shard-safety verification over the sharded wave paths.

Second rule family of the kernel-lint framework (PR 3's walker + rule
registry + fixtures): where the codegen rules pin per-shard COMPUTE
shapes, the comms rules (rules.COMMS_RULES) pin the mesh's
COMMUNICATION contract — the invariants parallel/engine_sortmerge.py
documents in comments ("collectives are collective: every shard must
take the same switch branch or the all_to_all deadlocks"; the
all_to_all only ever fed from the (owner, fp) routing sort; psum'd
scalars only; no all_gather anywhere on a wave path), now
machine-checked on CPU before any chip time is spent.

Fixtures (``comms_fixture_params`` / :func:`trace_comms_fixture`):

* the mesh engine's full wave body (parallel/engine_sortmerge.py,
  routing SORT seam) in its traced (per-shard ``slog`` mesh log
  compiled in) and untraced form, on a real multi-shard mesh (S=2:
  the tile math, Bd cap and all_to_all shapes are
  shard-count-dependent, unlike the kernel lint's 1-device
  axis-plumbing fixture), and its TIERED chunk program;
* the RECONCILIATION fixture: the sort-merge body at the exact
  ``dryrun_multichip`` 2pc rm=5 / S=8 / traced config TRACE_r16 was
  recorded under, so the static ``all_to_all_row_bytes`` in the COMM
  artifact is the number the committed trace's routed-rows counters
  multiply against (tests/test_comms_lint.py pins the product equals
  telemetry.shard_balance's ``routed_bytes_total`` exactly);
* every registry encoding's ``engine:sharded`` pair pipeline — zero
  collectives today, and the comms rules pin exactly that (an
  all_gather materialized by sharding propagation in a future
  encoding change fails here first).

The ``--hlo`` cross-check (:func:`hlo_collective_crosscheck`) compiles
a fixture's wave body on the live mesh and reconciles the optimized
module's collective ops (tables.parse_hlo_collectives — the SAME
category vocabulary as the jaxpr walk's COLLECTIVE_PRIMS) against the
jaxpr estimate: per-category op counts must match exactly; MORE HLO
collectives than the jaxpr accounts for means the SPMD partitioner
respecified something behind the walk's back and is a gated error,
fewer is an info (XLA folded a degenerate collective). Bytes are
reported per side with their ratio — measured 1.0 exactly on XLA:CPU
at the S=2 fixtures (PERF.md §comms-lint); a backend that types the
exchange per-participant would show a clean S-factor here, which is
why the ratio is reported rather than gated.

Everything except ``--hlo`` runs on abstract traces — no device
buffers, CPU-only CI.
"""

from __future__ import annotations

from typing import Optional

from .lint import LINT_N, trace_engine_pipeline
from .registry import ENCODINGS
from .rules import COMMS_RULES, Finding, TraceCtx, run_rules_with_stats

#: shard count of the default wave-body comms fixtures: the smallest
#: REAL mesh (S=1 degenerates the shuffle; the kernel lint keeps that
#: 1-device fixture for axis plumbing, this family needs live tiles).
COMMS_WAVE_SHARDS = 2

#: the reconciliation fixture's name — the sort-merge wave body at the
#: committed TRACE_r16 dryrun config (2pc rm=5, S=8, traced).
RECONCILIATION_FIXTURE = "comms(2pc-rm5,sortmerge,S8,traced)"

#: the exact engine config of dryrun_multichip's flagship workload
#: (__graft_entry__.py spawn_2pc5) — TRACE_r16's provenance lane.
RECONCILIATION_CONFIG = dict(
    rm_count=5,
    n_shards=8,
    capacity=1 << 12,
    frontier_capacity=512,
    cand_capacity=2048,
    bucket_capacity=1024,
    waves_per_sync=32,
    track_paths=True,
)


def _mesh(n_shards: int):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n_shards:
        raise RuntimeError(
            f"comms fixture needs {n_shards} devices, have "
            f"{len(devices)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards}"
        )
    return Mesh(np.array(devices[:n_shards]), ("shard",))


def comms_fixture_params(reconciliation: bool = True) -> list:
    """The wave-body fixture matrix: untraced and traced at the
    default 2pc-rm3/S=2 config, plus the TIERED chunk program (round
    16, stateright_tpu/tier.py — the deferred-commit wave + commit
    path holds to the same five comms rules: the commit's v-ladder
    switch must stay collective-free, its termination psums
    scalar-only), plus the rm=5/S=8 reconciliation config."""
    out = [dict(traced=False), dict(traced=True),
           dict(traced=True, tiered=True)]
    if reconciliation:
        out.append(dict(traced=True, config=RECONCILIATION_CONFIG))
    return out


def comms_fixture_name(traced: bool, config: Optional[dict] = None,
                       tiered: bool = False) -> str:
    cfg = config or {}
    rm = cfg.get("rm_count", 3)
    s = cfg.get("n_shards", COMMS_WAVE_SHARDS)
    return (
        f"comms(2pc-rm{rm},sortmerge,S{s}"
        + (",tiered" if tiered else "")
        + (",traced" if traced else "")
        + ")"
    )


def trace_comms_fixture(traced: bool = False,
                        config: Optional[dict] = None,
                        tiered: bool = False) -> dict:
    """Build the mesh engine on a real S-shard mesh and trace its full
    wave body (the ``_wave_body_sm`` hook) on the seed program's carry
    shapes — abstract (``eval_shape``), no buffers. Returns the
    fixture dict the driver and the --hlo pass share: ``name``,
    ``closed`` (the jaxpr), ``fn`` + ``carry`` (the compilable
    callable for --hlo), ``seam`` (the routing idiom the
    no-unsorted-all-to-all rule requires), and ``lane`` (the engine's
    telemetry lane config — ``dest_tile_lanes`` is the runtime side
    of the row-bytes reconciliation)."""
    import jax
    import jax.numpy as jnp

    from ..models.two_phase_commit import TwoPhaseSys

    cfg = dict(
        rm_count=3,
        n_shards=COMMS_WAVE_SHARDS,
        capacity=1 << 11,
        frontier_capacity=1 << 9,
        cand_capacity=1 << 11,
        bucket_capacity=1 << 10,
        waves_per_sync=4,
        track_paths=True,
    )
    if config:
        cfg.update(config)
    name = comms_fixture_name(traced, cfg, tiered)
    rm = cfg.pop("rm_count")
    mesh = _mesh(cfg.pop("n_shards"))
    checker = TwoPhaseSys(rm_count=rm).checker().spawn_tpu_sharded_sortmerge(
        mesh=mesh, f_min=64, v_min=256, merge_impl="xla", **cfg
    )
    if traced:
        # a truthy tracer stand-in flips _wave_log_enabled exactly as
        # a real RunTracer would — the per-shard slog path compiles in
        checker._tracer = object()
    init = jnp.asarray(checker.encoded.init_vecs())
    seed_fn, _chunk_fn = checker._build_programs(init.shape[0])
    carry_shapes = jax.eval_shape(seed_fn, init)
    if tiered:
        # The TIERED chunk program (stateright_tpu/tier.py): the
        # deferred-commit carry adds the pend/hot staging lanes and
        # the tier-shaped trace logs, and the program takes the
        # host's keep mask as a second, shard-split input. Traced as
        # one (carry, keep) pytree arg so the --hlo pass's
        # single-operand lower() keeps working.
        from ..telemetry import SHARD_LOG_LANES as SL
        from ..telemetry import WAVE_LOG_LANES as WL

        tier_fn = checker._build_programs(
            init.shape[0], tiered=True
        )
        S = checker.n_shards
        F = checker.frontier_capacity
        sds = jax.ShapeDtypeStruct
        ct = dict(carry_shapes)
        ct["pend_keys"] = sds((2, S * F), jnp.uint32)
        if checker.track_paths:
            ct["pend_par"] = sds((2, S * F), jnp.uint32)
        ct["pend_n"] = sds((S,), jnp.uint32)
        ct["pend_valid"] = sds((), jnp.bool_)
        ct["h_loc"] = sds((S,), jnp.uint32)
        if traced:
            ct["wlog"] = sds((1, WL), jnp.uint32)
            ct["pstash"] = sds((8,), jnp.uint32)
            ct["slog"] = sds((S, SL), jnp.uint32)
            ct["swave"] = sds((S * SL,), jnp.uint32)
        keep = sds((S * F,), jnp.bool_)

        def fn(args):
            return tier_fn(args[0], args[1])

        carry = (ct, keep)
        return dict(
            name=name,
            closed=jax.make_jaxpr(fn)(carry),
            fn=fn,
            carry=carry,
            seam="sort",
            lane=checker._lane_config(),
            n_shards=int(mesh.devices.size),
        )
    fn = checker._wave_body_sm
    return dict(
        name=name,
        closed=jax.make_jaxpr(fn)(carry_shapes),
        fn=fn,
        carry=carry_shapes,
        seam="sort",
        lane=checker._lane_config(),
        n_shards=int(mesh.devices.size),
    )


def _wave_body_ctx(name: str, seam: str) -> TraceCtx:
    # comms rules only: the codegen rules' gates are all off (the
    # kernel lint's own wave-body fixtures carry those; this family
    # prices communication).
    return TraceCtx(
        path="wave-body",
        encoding=name,
        n=LINT_N,
        k=0,
        sparse=False,
        allow_gathers=None,
        check_lane_alu=False,
        check_branches=False,
        check_comms=True,
        routing_seam=seam,
    )


def lint_comms_fixture(fixture: dict) -> tuple:
    """``(findings, stats_row, comms_summary)`` for one traced wave
    body. The comms summary is the comms-bytes info finding's data
    block plus the fixture's mesh/lane cross-reference fields — the
    COMM artifact's per-fixture record, and what shard_balance's
    ``comms_static`` block reconciles against at runtime."""
    ctx = _wave_body_ctx(fixture["name"], fixture["seam"])
    findings, n_eqns = run_rules_with_stats(ctx, fixture["closed"])
    est = [
        f for f in findings
        if f.rule == "comms-bytes" and f.severity == "info"
    ]
    lane = fixture["lane"]
    summary = dict(
        n_shards=fixture["n_shards"],
        seam=fixture["seam"],
        dest_tile_lanes=lane.get("dest_tile_lanes"),
        **(est[0].data if est else {"collectives": 0}),
    )
    stats = dict(
        encoding=fixture["name"],
        path="wave-body",
        eqns=n_eqns,
        errors=sum(1 for f in findings if f.severity == "error"),
    )
    return findings, stats, summary


def run_comms_lint(wave_bodies: bool = True,
                   encodings: Optional[tuple] = None,
                   reconciliation: bool = True,
                   n: int = LINT_N,
                   fixtures_out: Optional[list] = None) -> dict:
    """The whole comms gate. Returns the ``COMM_r*.json`` report dict:
    ``clean`` (no gated finding anywhere), ``findings`` (every comms
    finding incl. the per-fixture comms-bytes estimates), ``paths``
    (coverage rows), and ``comms`` (per-fixture collective accounting
    — categories, per-wave peak, all_to_all row bytes).

    ``fixtures_out``: pass a list to receive the traced wave-body
    fixture dicts — building a fixture constructs a full sharded
    engine and traces its body (the tool's most expensive step), so
    the ``--hlo`` pass reuses these instead of re-tracing."""
    all_findings: list = []
    all_stats: list = []
    comms: dict = {}
    if wave_bodies:
        for params in comms_fixture_params(reconciliation):
            fixture = trace_comms_fixture(**params)
            if fixtures_out is not None:
                fixtures_out.append(fixture)
            fs, st, summary = lint_comms_fixture(fixture)
            all_findings.extend(fs)
            all_stats.append(st)
            comms[fixture["name"]] = summary
    specs = encodings if encodings is not None else ENCODINGS
    for spec in specs:
        enc = spec.factory()
        closed = trace_engine_pipeline(enc, "sharded", n)
        ctx = TraceCtx(
            path="engine:sharded",
            encoding=spec.name,
            n=n,
            k=enc.max_actions,
            sparse=False,
            allow_gathers=None,
            check_lane_alu=False,
            check_comms=True,
            # the pair pipeline has no shuffle; the rule is off and
            # pins nothing here — an all_to_all appearing at all
            # would land in comms-bytes and the placement rules
            routing_seam=None,
        )
        fs, n_eqns = run_rules_with_stats(ctx, closed)
        all_findings.extend(fs)
        all_stats.append(dict(
            encoding=spec.name,
            path="engine:sharded",
            eqns=n_eqns,
            errors=sum(1 for f in fs if f.severity == "error"),
        ))
    errors = [f for f in all_findings if f.severity == "error"]
    return dict(
        clean=not errors,
        n=n,
        rules=[
            dict(name=r.name, description=r.description)
            for r in COMMS_RULES
        ],
        paths=all_stats,
        comms=comms,
        findings=[f.as_dict() for f in all_findings],
    )


# -- the HLO-level cross-check (the --hlo seam) ----------------------------


def reconcile_collective_categories(name: str, hlo: dict,
                                    jaxpr_categories: dict) -> dict:
    """Pure reconciliation of one fixture's per-category collective
    accounting (the --hlo pass's verdict logic, factored out so the
    deliberate-regression tests exercise it without a compile):
    MORE HLO ops than jaxpr eqns in a category is a gated finding (a
    collective XLA introduced — SPMD partitioner respecification —
    that the jaxpr walk can't see), fewer is an info (XLA folded a
    degenerate collective). Byte totals are reported with their
    per-category ratio, never gated (backend-dependent typing)."""
    findings: list = []
    ratios: dict = {}
    for cat in sorted(set(hlo) | set(jaxpr_categories)):
        h = hlo.get(cat, {"ops": 0, "bytes": 0})
        j = jaxpr_categories.get(cat, {"eqns": 0, "bytes": 0})
        if j["bytes"]:
            ratios[cat] = round(h["bytes"] / j["bytes"], 3)
        if h["ops"] > j["eqns"]:
            findings.append(Finding(
                rule="hlo-collective-reconcile",
                severity="error",
                encoding=name,
                path="hlo",
                message=(
                    f"compiled module has {h['ops']} '{cat}' "
                    f"collective op(s) but the jaxpr walk accounts "
                    f"for {j['eqns']} — XLA (SPMD partitioner "
                    "respecification) introduced collectives the "
                    "static estimate can't see; the comms budget "
                    "no longer bounds real traffic"
                ),
                primitive=cat,
                data={"hlo_ops": h["ops"], "jaxpr_eqns": j["eqns"]},
            ))
        elif h["ops"] < j["eqns"]:
            findings.append(Finding(
                rule="hlo-collective-reconcile",
                severity="info",
                encoding=name,
                path="hlo",
                message=(
                    f"compiled module has {h['ops']} '{cat}' op(s) "
                    f"vs {j['eqns']} jaxpr eqns — XLA folded "
                    "degenerate collectives (static estimate is an "
                    "upper bound here)"
                ),
                primitive=cat,
                data={"hlo_ops": h["ops"], "jaxpr_eqns": j["eqns"]},
            ))
    return dict(
        hlo=hlo,
        jaxpr=jaxpr_categories,
        byte_ratio=ratios,
        findings=findings,
    )


def hlo_collective_crosscheck(fixture: dict,
                              jaxpr_categories: dict) -> dict:
    """Compile one wave-body fixture on the live mesh and reconcile
    the optimized module's collective ops against the jaxpr-level
    accounting (see :func:`reconcile_collective_categories` for the
    verdict rules)."""
    import jax

    from .tables import parse_hlo_collectives

    txt = (
        jax.jit(fixture["fn"])
        .lower(fixture["carry"])
        .compile()
        .as_text()
    )
    return reconcile_collective_categories(
        fixture["name"], parse_hlo_collectives(txt), jaxpr_categories
    )


def format_comms_report(report: dict) -> str:
    """Human-readable comms-lint report (tools/lint_comms.py)."""
    lines = [
        f"comms-lint: {len(report['rules'])} rules x "
        f"{len(report['paths'])} traced paths (N={report['n']})"
    ]
    lines.append(
        f"  {'fixture':40s} {'path':16s} {'eqns':>6s} {'errors':>7s}"
    )
    for p in report["paths"]:
        lines.append(
            f"  {p['encoding']:40s} {p['path']:16s} "
            f"{p['eqns']:6d} {p['errors']:7d}"
        )
    for name, c in report.get("comms", {}).items():
        if not c.get("collectives"):
            lines.append(f"  {name}: no collectives")
            continue
        cats = ", ".join(
            f"{cat} x{s['eqns']} ({s['bytes']:,} B)"
            for cat, s in sorted(c["per_category"].items())
        )
        lines.append(
            f"  {name}: S={c['n_shards']} seam={c['seam']} "
            f"per-wave peak {c['per_wave_peak_bytes']:,} B"
            + (f" (budget {c['budget_bytes']:,})"
               if "budget_bytes" in c else "")
            + (f"; a2a row {c['all_to_all_row_bytes']} B x "
               f"<= {c['all_to_all_rows_max']} rows"
               if "all_to_all_row_bytes" in c else "")
            + f"; {cats}"
        )
    errors = [f for f in report["findings"]
              if f["severity"] == "error"]
    for f in errors:
        loc = f" @ {f['source']}" if f.get("source") else ""
        lines.append(
            f"ERROR [{f['rule']}] {f['encoding']} / {f['path']}: "
            f"{f['message']}{loc}"
        )
    lines.append(
        "CLEAN — the mesh communication contract holds"
        if report["clean"]
        else f"{len(errors)} comms violation(s)"
    )
    return "\n".join(lines)
