"""The comms-lint gate (``pytest -m lint``, round 13).

Same two-halves structure as the codegen gate (tests/test_lint.py):

* the GATE — the comms rule family over the mesh engine's wave
  body (traced + untraced + tiered, real S=2 mesh), the rm=5/S=8
  reconciliation fixture, and every registry encoding's sharded pair
  pipeline comes back clean (what ``tools/lint_comms.py`` exits 0 on);
* the TEETH — deliberate regressions (a collective moved inside a
  shard-varying switch, a psum over a resident-shaped buffer, an
  all_to_all fed by unsorted candidates, an injected all_gather, an
  over-budget shuffle) each caught by the NAMED rule with source
  attribution;
* the RECONCILIATION — the static per-row byte price from the traced
  all_to_all equals the committed TRACE_r16 mesh trace's
  ``dest_tile_lanes``-derived price, so measured routed bytes ARE
  routed_rows x the static row_bytes, exactly (the estimate-vs-
  measured bound PERF.md §comms-lint states).
"""

import json
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from stateright_tpu.analysis import (  # noqa: E402
    COMMS_RULES,
    ENCODINGS,
    TraceCtx,
    reconcile_collective_categories,
    run_comms_lint,
    run_rules,
)
from stateright_tpu.analysis.comms import (  # noqa: E402
    RECONCILIATION_CONFIG,
    RECONCILIATION_FIXTURE,
    comms_fixture_name,
)
from stateright_tpu.analysis.tables import (  # noqa: E402
    COMMS_BYTE_BUDGETS,
    SCALAR_REDUCTION_MAX_ELEMS,
)

pytestmark = pytest.mark.lint

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _errors(findings):
    return [f for f in findings if f.severity == "error"]


def _shard_map(fn, mesh, in_specs, out_specs):
    from jax import shard_map

    # synthetic bodies return replicated-by-construction values the
    # varying-ness checker cannot prove replicated
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("shard",))


def _ctx(name="synthetic", seam=None):
    return TraceCtx(
        path="wave-body", encoding=name, n=64, k=0, sparse=False,
        allow_gathers=None, check_lane_alu=False,
        check_branches=False, check_comms=True, routing_seam=seam,
    )


# -- the gate --------------------------------------------------------------


@pytest.fixture(scope="module")
def gate_report():
    """ONE full run_comms_lint() serves every gate assertion: each
    run rebuilds the mesh engine traced/untraced/tiered (incl. the
    S=8 rm=5 reconciliation engine) and harvests every registry
    encoding, and the output is deterministic (pinned by the verify
    skill's json-compare probe) — re-running per test only re-buys
    the build cost."""
    return run_comms_lint()


def test_comms_lint_clean_all_fixtures(gate_report):
    """The mesh engine's traced/untraced bodies, the
    reconciliation fixture, and every registry encoding's sharded
    pipeline: zero gated comms findings. This is the tier-1 mesh
    communication-contract gate."""
    report = gate_report
    errors = [
        f for f in report["findings"] if f["severity"] == "error"
    ]
    assert report["clean"], errors
    covered = {(p["encoding"], p["path"]) for p in report["paths"]}
    for traced in (False, True):
        name = comms_fixture_name(traced)
        assert (name, "wave-body") in covered, name
    # the TIERED chunk program (round 16, stateright_tpu/tier.py)
    # rides the same gate: its deferred-commit phase must stay
    # collective-clean too
    assert (
        comms_fixture_name(True, tiered=True),
        "wave-body",
    ) in covered
    assert (RECONCILIATION_FIXTURE, "wave-body") in covered
    for spec in ENCODINGS:
        assert (spec.name, "engine:sharded") in covered, spec.name
    # every wave-body fixture's accounting made it into the comms
    # block with the reconciliation fields present where a shuffle is
    for name, c in report["comms"].items():
        assert c["collectives"] > 0, name
        assert c["all_to_all_row_bytes"] == 4 * c["dest_tile_lanes"]


def test_comms_registry_names_all_rules():
    assert {r.name for r in COMMS_RULES} == {
        "no-collective-in-switch", "no-unsorted-all-to-all",
        "scalar-only-reductions", "no-all-gather", "comms-bytes",
    }


def test_comms_budgets_have_teeth(gate_report):
    """Every wave-body fixture is budgeted, under budget, and the
    budget is not slack past 2x the measured per-wave peak (the same
    has-teeth policy as the carry-copy budgets)."""
    for name, c in gate_report["comms"].items():
        budget = COMMS_BYTE_BUDGETS[name]
        assert c["budget_bytes"] == budget, name
        assert c["per_wave_peak_bytes"] <= budget, name
        assert budget < 2 * c["per_wave_peak_bytes"], name


def test_traced_mesh_log_adds_no_collective_traffic(gate_report):
    """The per-shard mesh log's contract is 'never psum-collapsed':
    the traced wave body's per-wave collective peak may exceed the
    untraced one by at most ONE scalar psum (the global wave row's
    n_tot back-fill, 4 bytes) — the telemetry layer rides the
    existing sync, it does not add traffic."""
    plain = gate_report["comms"][comms_fixture_name(False)]
    traced = gate_report["comms"][comms_fixture_name(True)]
    delta = (
        traced["per_wave_peak_bytes"]
        - plain["per_wave_peak_bytes"]
    )
    assert 0 <= delta <= 4, delta


# -- the teeth -------------------------------------------------------------


def test_comms_catches_collective_in_varying_switch():
    """A collective under a switch whose index is derived from
    SHARD-LOCAL data (not pmax-agreed) is the deadlock hazard the
    documented invariant forbids — caught with both the collective's
    and the switch's source attribution. The same body with a
    pmax-agreed index passes."""
    mesh = _mesh2()

    def br(v):
        return (
            lax.psum(jnp.sum(v) * 0, "shard") + jnp.sum(v)
        ).reshape(1)

    def bad(x):
        # index from the shard-LOCAL row count: shards can disagree
        idx = (jnp.sum(x) % 2).astype(jnp.int32)
        return lax.switch(idx, [br, br], x)

    def good(x):
        agreed = lax.pmax(jnp.sum(x) % 2, "shard").astype(jnp.int32)
        return lax.switch(agreed, [br, br], x)

    arg = jnp.zeros((2, 8), jnp.uint32)
    jx_bad = jax.make_jaxpr(
        _shard_map(bad, mesh, (P("shard"),), P("shard"))
    )(arg)
    hits = [
        f for f in _errors(run_rules(_ctx(), jx_bad))
        if f.rule == "no-collective-in-switch"
    ]
    assert hits, "shard-varying switch index not caught"
    assert hits[0].primitive == "psum"
    assert hits[0].source
    assert hits[0].data["switch_source"]
    jx_good = jax.make_jaxpr(
        _shard_map(good, mesh, (P("shard"),), P("shard"))
    )(arg)
    assert not [
        f for f in _errors(run_rules(_ctx(), jx_good))
        if f.rule == "no-collective-in-switch"
    ], "pmax-agreed switch index must pass"


def test_comms_catches_varying_switch_via_loop_carry():
    """Taint that only develops through a while-loop round trip still
    reaches a carried switch index (review finding: without the
    loop-carry feedback edge in walker._flow, a carry that starts
    uniform but is overwritten with axis_index-derived data inside
    the body read as uniform forever — and the rule passed the
    deadlock shape clean)."""
    mesh = _mesh2()

    def br(v):
        return (
            lax.psum(jnp.sum(v) * 0, "shard") + jnp.sum(v)
        ).reshape(1)

    def looped(x):
        def body(carry):
            i, idx, acc = carry
            picked = lax.switch(idx, [br, br], x)
            # from iteration 2 on, the carried index is shard-LOCAL
            next_idx = (
                lax.axis_index("shard") % 2
            ).astype(jnp.int32)
            return (i + 1, next_idx, acc + picked)

        _, _, out = lax.while_loop(
            lambda c: c[0] < 3,
            body,
            (jnp.int32(0), jnp.int32(0), jnp.zeros(1, jnp.uint32)),
        )
        return out

    jx = jax.make_jaxpr(
        _shard_map(looped, mesh, (P("shard"),), P("shard"))
    )(jnp.zeros((2, 8), jnp.uint32))
    hits = [
        f for f in _errors(run_rules(_ctx(), jx))
        if f.rule == "no-collective-in-switch"
    ]
    assert hits, "loop-carried shard-varying switch index not caught"


def test_comms_catches_buffer_sized_reduction():
    """A psum over a resident-shaped [W, F] buffer is accidental
    replication — caught with the operand shape in the finding; the
    engines' scalar psums pass."""
    mesh = _mesh2()
    W, F = 20, 512
    assert W * F > SCALAR_REDUCTION_MAX_ELEMS

    def bad(x):
        return lax.psum(x, "shard")

    jx = jax.make_jaxpr(
        _shard_map(bad, mesh, (P(None, "shard"),), P())
    )(jnp.zeros((W, 2 * F), jnp.uint32))
    hits = [
        f for f in _errors(run_rules(_ctx(), jx))
        if f.rule == "scalar-only-reductions"
    ]
    assert hits, "buffer-sized psum not caught"
    assert hits[0].data["elements"] == W * F
    assert str(F) in hits[0].message
    assert hits[0].source

    def good(x):
        return lax.psum(jnp.sum(x), "shard")

    jx2 = jax.make_jaxpr(
        _shard_map(good, mesh, (P(None, "shard"),), P())
    )(jnp.zeros((W, 2 * F), jnp.uint32))
    assert not [
        f for f in _errors(run_rules(_ctx(), jx2))
        if f.rule == "scalar-only-reductions"
    ]


def test_comms_catches_unsorted_all_to_all():
    """An all_to_all fed raw candidates (no routing sort upstream)
    breaks the owner-local dedup contract — caught under the "sort"
    seam; the sorted variant passes, including when the sort sits in
    an enclosing scope and flows in through a switch branch."""
    mesh = _mesh2()
    rows = jnp.zeros((8, 4), jnp.uint32)

    def bad(x):
        return lax.all_to_all(
            x, "shard", split_axis=0, concat_axis=0, tiled=True
        )

    def good(x):
        owner = x[:, 0] % 2
        _, s_row = lax.sort(
            (owner, jnp.arange(x.shape[0], dtype=jnp.uint32)),
            num_keys=2,
        )
        routed = x[s_row]
        return lax.all_to_all(
            routed, "shard", split_axis=0, concat_axis=0, tiled=True
        )

    for fn, should_hit in ((bad, True), (good, False)):
        jx = jax.make_jaxpr(
            _shard_map(fn, mesh, (P("shard"),), P("shard"))
        )(rows)
        hits = [
            f for f in _errors(run_rules(_ctx(seam="sort"), jx))
            if f.rule == "no-unsorted-all-to-all"
        ]
        assert bool(hits) == should_hit, (fn.__name__, hits)
        if hits:
            assert hits[0].source


def test_comms_catches_injected_all_gather():
    """An all_gather on a wave path is the S-fold traffic blow-up —
    caught at the default zero allowance; a registered drain-path
    allowance (tables.ALL_GATHER_ALLOWANCES) lets the same trace
    pass."""
    from stateright_tpu.analysis.tables import ALL_GATHER_ALLOWANCES

    mesh = _mesh2()

    def gathers(x):
        return lax.all_gather(x, "shard")

    jx = jax.make_jaxpr(
        _shard_map(gathers, mesh, (P("shard"),), P())
    )(jnp.zeros((8, 4), jnp.uint32))
    hits = [
        f for f in _errors(run_rules(_ctx(), jx))
        if f.rule == "no-all-gather"
    ]
    assert hits, "injected all_gather not caught"
    assert hits[0].data["all_gathers"] >= 1
    assert hits[0].source
    name = "synthetic-drain"
    ALL_GATHER_ALLOWANCES[name] = hits[0].data["all_gathers"]
    try:
        assert not [
            f for f in _errors(run_rules(_ctx(name=name), jx))
            if f.rule == "no-all-gather"
        ], "drain-path allowance not honored"
    finally:
        del ALL_GATHER_ALLOWANCES[name]


def test_comms_catches_byte_budget_regression():
    """A wave body whose per-wave collective payload exceeds its
    fixture budget fails the gated comms-bytes rule naming both
    numbers (the silent-8x-traffic failure mode, now loud)."""
    mesh = _mesh2()
    name = "synthetic-budgeted"
    COMMS_BYTE_BUDGETS[name] = 1024

    def fat(x):
        owner = x[:, 0] % 2
        _, s_row = lax.sort(
            (owner, jnp.arange(x.shape[0], dtype=jnp.uint32)),
            num_keys=2,
        )
        return lax.all_to_all(
            x[s_row], "shard", split_axis=0, concat_axis=0,
            tiled=True,
        )

    try:
        jx = jax.make_jaxpr(
            _shard_map(fat, mesh, (P("shard"),), P("shard"))
        )(jnp.zeros((512, 8), jnp.uint32))
        hits = [
            f for f in _errors(
                run_rules(_ctx(name=name, seam="sort"), jx)
            )
            if f.rule == "comms-bytes"
        ]
        assert hits, "over-budget shuffle not gated"
        assert hits[0].data["per_wave_peak_bytes"] > 1024
        assert "1,024" in hits[0].message
    finally:
        del COMMS_BYTE_BUDGETS[name]


def test_comms_peak_maxes_nested_switch_siblings():
    """Per-wave peak accounting at NESTED switches (review finding):
    two collectives in mutually exclusive branches of an inner cond
    must contribute max(), not sum() — only one runs per wave — while
    collectives under distinct sequential conds still sum."""
    mesh = _mesh2()

    def br_coll(rows):
        def br(v):
            return (
                lax.psum(jnp.sum(v) * 0, "shard") + jnp.sum(v)
            ).reshape(1)

        return br

    def nested(x):
        agreed = lax.pmax(jnp.sum(x) % 2, "shard").astype(jnp.int32)

        def outer0(v):
            def inner(w):
                # two sibling branches, one 512-row all_to_all each
                def ib(u):
                    owner = u[:, 0] % 2
                    _, s_row = lax.sort(
                        (owner,
                         jnp.arange(u.shape[0], dtype=jnp.uint32)),
                        num_keys=2,
                    )
                    return lax.all_to_all(
                        u[s_row], "shard", split_axis=0,
                        concat_axis=0, tiled=True,
                    )

                return lax.switch(
                    lax.pmax(
                        jnp.sum(w) % 2, "shard"
                    ).astype(jnp.int32),
                    [ib, ib],
                    w,
                )

            return inner(v)

        def outer1(v):
            return v

        return lax.switch(agreed, [outer0, outer1], x)

    rows = jnp.zeros((512, 8), jnp.uint32)
    jx = jax.make_jaxpr(
        _shard_map(nested, mesh, (P("shard"),), P("shard"))
    )(rows)
    findings = run_rules(_ctx(seam="sort"), jx)
    assert not _errors(findings)
    est = [f for f in findings if f.rule == "comms-bytes"][0]
    a2a_bytes = est.data["per_category"]["all-to-all"]["bytes"]
    # two sibling all_to_alls in the program total, ONE in the peak
    assert est.data["all_to_all_eqns"] == 2
    peak = est.data["per_wave_peak_bytes"]
    assert peak < a2a_bytes  # not the sum of both siblings
    assert peak >= a2a_bytes // 2  # but at least the fattest one


def test_hlo_reconcile_flags_introduced_collectives():
    """The --hlo cross-check's verdict logic: an HLO category with
    MORE ops than the jaxpr accounts for (SPMD respecification) is a
    gated finding; fewer is an info; equal counts with any byte ratio
    are clean."""
    jaxpr_side = {
        "all-to-all": {"eqns": 4, "bytes": 204288},
        "reduction": {"eqns": 55, "bytes": 348},
    }
    clean = reconcile_collective_categories(
        "fx", {
            "all-to-all": {"ops": 4, "bytes": 204288},
            "reduction": {"ops": 55, "bytes": 348},
        }, jaxpr_side,
    )
    assert not clean["findings"]
    assert clean["byte_ratio"]["all-to-all"] == 1.0
    introduced = reconcile_collective_categories(
        "fx", {
            "all-to-all": {"ops": 4, "bytes": 204288},
            "reduction": {"ops": 55, "bytes": 348},
            "all-gather": {"ops": 1, "bytes": 8192},
        }, jaxpr_side,
    )
    errs = _errors(introduced["findings"])
    assert errs and errs[0].rule == "hlo-collective-reconcile"
    assert errs[0].data == {"hlo_ops": 1, "jaxpr_eqns": 0}
    folded = reconcile_collective_categories(
        "fx", {
            "all-to-all": {"ops": 4, "bytes": 204288},
            "reduction": {"ops": 50, "bytes": 300},
        }, jaxpr_side,
    )
    assert not _errors(folded["findings"])
    assert any(
        f.severity == "info" for f in folded["findings"]
    )


# -- the reconciliation ----------------------------------------------------


def test_comms_static_reconciles_trace_r16(gate_report):
    """The static comms-bytes estimate vs the committed 2pc rm=5 mesh
    trace (TRACE_r16, the dryrun_multichip flagship run): the traced
    all_to_all's per-row byte price equals the runtime lane's
    dest_tile_lanes price EXACTLY, so the trace's routed-byte total
    IS routed_rows x the static row_bytes, and every wave's routed
    rows sit under the static per-wave row ceiling (S x dest_cap =
    the all_to_all's operand rows). The static side comes from the
    gate's own reconciliation fixture (same engine config the trace
    ran under) — no rebuild."""
    from stateright_tpu.telemetry import shard_balance

    with open(os.path.join(_REPO, "TRACE_r16.jsonl")) as fh:
        events = [json.loads(line) for line in fh]
    bal = shard_balance(events)
    assert bal is not None and bal["n_shards"] == 8

    summary = gate_report["comms"][RECONCILIATION_FIXTURE]
    assert summary["n_shards"] == RECONCILIATION_CONFIG["n_shards"]

    # static row price == runtime lane price, exactly
    row_bytes = summary["all_to_all_row_bytes"]
    cs = bal["comms_static"]
    assert row_bytes == cs["row_bytes"] == 28
    # measured routed bytes ARE routed rows x the static price
    assert bal["routed_rows_total"] == 32580
    assert (
        bal["routed_bytes_total"]
        == cs["measured_routed_bytes"]
        == bal["routed_rows_total"] * row_bytes
    )
    # the static per-wave ceiling holds wave for wave: S x dest_cap
    # rows is what the all_to_all exchanges, and the traced operand
    # agrees with it
    assert summary["all_to_all_rows_max"] == 8 * 1024
    for w in bal["per_wave"]:
        bound = w["shards"] * w["dest_cap"]
        assert w["routed_rows"] <= bound
        assert bound <= summary["all_to_all_rows_max"]
    assert cs["bytes_bound_total"] == (
        cs["bound_rows_total"] * row_bytes
    )
    assert 0 < cs["bound_util"] <= 1


# -- layout-separation pin (satellite: payload_pack claim) -----------------


def test_sharded_engine_never_calls_payload_pack():
    """payload_pack's docstring claims the single-chip payload layout
    and the sharded routed-tile layout never meet (dest_tile_pack is
    the sharded home). The comms walk found no reuse; this pins the
    claim at the AST level so a future call-site can't quietly merge
    the two layouts without updating both docstrings."""
    import ast

    path = os.path.join(
        _REPO, "stateright_tpu", "parallel", "engine_sortmerge.py"
    )
    with open(path) as fh:
        tree = ast.parse(fh.read())
    calls = {
        node.func.id
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
    }
    assert "payload_pack" not in calls
    assert "dest_tile_pack" in calls


# -- artifact cross-reference (COMM_r*.json) -------------------------------


def test_latest_comms_summary_reads_committed_artifact():
    """The committed COMM_r01.json parses into the cross-reference
    block bench.py / lint_kernels.py embed: artifact name, clean flag,
    and the per-fixture reconciliation numbers (the 28 B/row price
    TRACE_r16's routed counters multiply against)."""
    from stateright_tpu.artifacts import latest_comms_summary

    ref = latest_comms_summary()
    assert ref is not None
    assert ref["artifact"].startswith("COMM_r")
    assert ref["clean"] is True
    fx = ref["fixtures"][RECONCILIATION_FIXTURE]
    assert fx["all_to_all_row_bytes"] == 28
    assert fx["per_wave_peak_bytes"] > 0


def test_latest_comms_summary_best_effort(tmp_path):
    """Missing, truncated, or structurally mangled COMM artifacts
    degrade to None — same contract as latest_lint_summary (a
    hand-edited artifact must never abort bench.py at startup)."""
    from stateright_tpu.artifacts import latest_comms_summary

    root = str(tmp_path)
    assert latest_comms_summary(root) is None
    p = tmp_path / "COMM_r01.json"
    p.write_text("{ truncated")
    assert latest_comms_summary(root) is None
    p.write_text(json.dumps({"clean": True, "comms": "not-a-dict"}))
    assert latest_comms_summary(root) is None
    p.write_text(json.dumps({
        "clean": True,
        "comms": {"fx": {"per_wave_peak_bytes": 7,
                         "all_to_all_row_bytes": 28}},
        "provenance": {"git_sha": "f" * 40},
    }))
    ref = latest_comms_summary(root)
    assert ref == {
        "artifact": "COMM_r01.json",
        "clean": True,
        "git_sha": "f" * 40,
        # foreign SHA against this checkout's HEAD (and a dirty tree
        # during development): the pairing claim stays unknown/False,
        # never a crash
        "sha_matches_head": ref["sha_matches_head"],
        "fixtures": {"fx": {"per_wave_peak_bytes": 7,
                            "all_to_all_row_bytes": 28}},
    }
    assert ref["sha_matches_head"] in (None, False)


def test_comm_artifacts_number_in_own_sequence(tmp_path):
    """COMM rounds count independently of the shared
    BENCH/LINT/TRACE sequence (the MEM pattern): a repo at shared
    round 9 still writes COMM_r01 first."""
    from stateright_tpu import artifacts

    root = str(tmp_path)
    open(os.path.join(root, "TRACE_r08.jsonl"), "w").close()
    assert artifacts.next_round(root, stems=("COMM",)) == 1
    open(os.path.join(root, "COMM_r01.json"), "w").close()
    assert artifacts.next_round(root, stems=("COMM",)) == 2
    assert artifacts.next_round(root) == 9
