"""Latency-observability gate (``pytest -m latency``).

Covers the round-14 tentpole surface end to end on CPU:

* the compile-cache ledger — a traced engine run emits schema-valid
  ``program_build`` events at the ``_programs`` seam and the lazy
  compile sites, warm in-process fetches tier as ``in_process``, and
  the tiers/walls land in the run-end ``latency_profile``;
* the verdict timeline — one ``verdict`` event per property on both
  the device engines (settle wave/depth from the chunk stats) and the
  host checkers (``_discover`` + the run-end exhaustion sweep), with
  tracing never changing the explored counts;
* the latency differ behind tools/trace_diff.py — deliberate
  regressions (an injected host stall at the chunk-sync readback, a
  forced cold compile via a cache-key perturbation) are each caught
  by the latency alignment and attributed to the RIGHT bucket, while
  pre-round-14 baseline traces skip the block entirely;
* tools/latency_report.py — exit codes, the LAT_r* artifact's own
  round sequence, and the derived-summary round trip.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu import telemetry  # noqa: E402
from stateright_tpu.checkers.tpu_sortmerge import (  # noqa: E402
    SortMergeTpuBfsChecker,
)
from stateright_tpu.models.two_phase_commit import TwoPhaseSys  # noqa: E402
from stateright_tpu.telemetry import (  # noqa: E402
    BUILD_TIERS,
    RunTracer,
    diff_traces,
    format_diff,
    latency_summary,
    load_trace,
    validate_events,
    write_artifacts,
    write_latency_artifact,
)

pytestmark = pytest.mark.latency

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CAPS = dict(capacity=1 << 10, frontier_capacity=256,
             cand_capacity=1024, track_paths=False)


def _spawn(**kw):
    cfg = dict(_CAPS, **kw)
    return TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(**cfg)


def _trace_run(spawn, runs=1):
    tr = RunTracer()
    checkers = []
    with tr.activate():
        for _ in range(runs):
            checkers.append(spawn().join())
    validate_events(tr.events)
    return tr, checkers


# -- compile-cache ledger -------------------------------------------------


def test_traced_run_emits_latency_layer():
    """The tentpole smoke: a traced run carries the full latency
    layer — ledger rows with valid tiers, one verdict per property,
    and the run-end profile — at UNCHANGED exploration counts."""
    c0 = _spawn().join()
    tr, (c1, c2) = _trace_run(_spawn, runs=2)
    assert c1.unique_state_count() == c0.unique_state_count() == 288
    assert c2.state_count() == c0.state_count()

    builds = [e for e in tr.events if e["ev"] == "program_build"]
    assert builds, "a traced run must emit compile-cache ledger rows"
    assert all(b["tier"] in BUILD_TIERS for b in builds)
    assert all(b["wall_sec"] >= 0 for b in builds)
    # run 1 fetched the programs warm: the in_process tier at the
    # _programs seam is the warm-start attribution BENCH_r06 reads
    r1 = [b for b in builds if b["run"] == 1]
    assert any(b["program"] == "programs"
               and b["tier"] == "in_process" for b in r1)
    # ledger keys pair the runs to the SAME compiled program
    keys = {b.get("key") for b in builds}
    assert len(keys) == 1 and None not in keys

    props = {p.name: p for p in c1.model.properties()}
    for run in (0, 1):
        verdicts = [e for e in tr.events
                    if e["ev"] == "verdict" and e["run"] == run]
        assert {v["property"] for v in verdicts} == set(props)
        for v in verdicts:
            exp = props[v["property"]].expectation.name.lower()
            assert v["expectation"] == exp
            # 2pc: both sometimes-properties discover, the always
            # property settles by exhaustion
            assert v["kind"] == (
                "exhaustion" if exp == "always" else "discovery"
            )
            assert v["depth"] >= 1

    profs = [e for e in tr.events if e["ev"] == "latency_profile"]
    assert [p["run"] for p in profs] == [0, 1]
    for p in profs:
        assert p["chunks"] >= 1 and p["waves"] == 11
        assert p["dispatch_net_sec"] <= p["dispatch_sec"] + 1e-9
        assert p["fetch_min_sec"] <= p["fetch_sec"] + 1e-9
        assert 0 <= p["sync_share"] <= 1
        assert p["compile"]["builds"]
    # the warm run's ledger shows no cold wall
    assert profs[1]["compile"]["cold_sec"] == 0.0


def test_untraced_run_has_no_events_but_keeps_accounting():
    """Untraced runs emit nothing — and still expose the host-side
    dispatch/sync split (the bench.py seam) for free."""
    c = _spawn().join()
    lat = c.latency_accounting()
    assert lat is not None and lat["chunks"] >= 1
    assert lat["fetch_sec"] >= 0 and lat["dispatch_sec"] > 0
    assert lat["time_to_first_wave_sec"] > 0


def test_host_checker_verdict_timeline():
    """The host BFS settles its sometimes-properties by discovery
    (with the BFS depth) and the holding always-property by
    exhaustion at run end — all inside one trace run."""
    tr = RunTracer()
    with tr.activate():
        c = TwoPhaseSys(rm_count=2).checker().spawn_bfs().join()
    validate_events(tr.events)
    verdicts = {e["property"]: e for e in tr.events
                if e["ev"] == "verdict"}
    assert set(verdicts) == {p.name for p in c.model.properties()}
    assert verdicts["consistent"]["kind"] == "exhaustion"
    assert verdicts["commit agreement"]["kind"] == "discovery"
    assert verdicts["commit agreement"]["depth"] >= 1
    # discoveries settle before the exhaustion sweep
    assert (verdicts["commit agreement"]["t"]
            <= verdicts["consistent"]["t"])
    # host runs have no chunks: no latency_profile, and that's valid
    assert not [e for e in tr.events
                if e["ev"] == "latency_profile"]


def test_simulation_discovery_verdicts():
    """The simulation engines settle properties too: a traced random
    walk's discovery emits its verdict (with the walk depth), and the
    run-end sweep covers the rest — no engine is outside the
    one-verdict-per-property contract."""
    from stateright_tpu.fixtures import BinaryClock

    tr = RunTracer()
    with tr.activate():
        c = BinaryClock().checker().spawn_simulation(seed=1).join()
    validate_events(tr.events)
    verdicts = {e["property"]: e for e in tr.events
                if e["ev"] == "verdict"}
    assert set(verdicts) == {p.name for p in c.model.properties()}
    assert verdicts["can be zero"]["kind"] == "discovery"
    assert verdicts["in bounds"]["kind"] == "exhaustion"


def test_on_demand_run_to_completion_brackets_verdicts():
    """The on-demand checker bypasses the base ``_ensure_run``; its
    exhaustive pass must still open its own trace run and settle
    every property inside it (the Explorer's run-to-completion path —
    direction 4's metered service is backed by exactly this
    engine)."""
    tr = RunTracer()
    with tr.activate():
        c = TwoPhaseSys(rm_count=2).checker().spawn_on_demand()
        c.run_to_completion()
    validate_events(tr.events)
    runs = {e["run"] for e in tr.events if e["ev"] == "run_begin"}
    assert runs == {0}
    verdicts = [e for e in tr.events if e["ev"] == "verdict"]
    assert {v["property"] for v in verdicts} == {
        p.name for p in c.model.properties()
    }
    assert all(v["run"] == 0 for v in verdicts)
    kinds = {v["property"]: v["kind"] for v in verdicts}
    assert kinds["consistent"] == "exhaustion"
    assert [e for e in tr.events if e["ev"] == "run_end"]


def test_cancelled_run_emits_no_exhaustion_verdicts():
    """A cancelled run (the hybrid racer's losing side) returns early
    with PARTIAL results — it has not exhausted anything, so the
    run-end sweep must stay silent rather than falsely settling
    undiscovered properties."""
    import threading

    tr = RunTracer()
    with tr.activate():
        c = _spawn()
        c.cancel_event = threading.Event()
        c.cancel_event.set()
        c.join()
    assert c.cancelled
    assert not [e for e in tr.events if e["ev"] == "verdict"]


def test_chrome_trace_has_sync_counter_and_verdict_instants(tmp_path):
    tr, _ = _trace_run(_spawn)
    path = tr.write_chrome_trace(str(tmp_path / "t.trace.json"))
    ct = json.load(open(path))
    names = [e.get("name") for e in ct["traceEvents"]]
    assert "host_blocked_ms" in names
    assert any(str(n).startswith("verdict ") for n in names)


# -- derived summary / LAT artifacts / report CLI -------------------------


def test_latency_summary_and_artifact(tmp_path):
    tr, _ = _trace_run(_spawn)
    s = latency_summary(tr.events)
    assert s is not None and s["profile"] is not None
    assert s["builds"] and s["verdicts"]
    assert all(v["t_since_run"] >= 0 for v in s["verdicts"])
    path = write_latency_artifact(
        dict(s, trace="TRACE_rXX.jsonl"), root=str(tmp_path)
    )
    assert os.path.basename(path) == "LAT_r01.json"
    doc = json.load(open(path))
    assert doc["trace"] == "TRACE_rXX.jsonl"
    assert doc["provenance"]["backend"] == "cpu"
    # own round sequence: the next LAT lands at r02 regardless of
    # other artifact families in the root
    path2 = write_latency_artifact(dict(s), root=str(tmp_path))
    assert os.path.basename(path2) == "LAT_r02.json"


def test_latency_report_cli(tmp_path):
    tr, _ = _trace_run(_spawn)
    jsonl, _ = write_artifacts(tr, root=str(tmp_path))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "latency_report.py"),
         jsonl, "--json", "--root", str(tmp_path)],
        capture_output=True, text=True, env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "compile-cache ledger" in out.stdout
    assert "sync floor" in out.stdout
    assert "time to verdict" in out.stdout
    assert os.path.exists(tmp_path / "LAT_r01.json")

    # a trace without latency events (host-only run pre-dating the
    # layer, synthesized) exits 2
    old = RunTracer()
    with old.activate():
        old.begin_run(lane=dict(engine="X"))
        old.end_run()
    # strip the round-14 events a real end_run no longer adds for
    # chunkless runs (none here), then drop verdicts if any
    bare = [e for e in old.events
            if e["ev"] in ("run_begin", "run_end")]
    p = tmp_path / "bare.jsonl"
    with open(p, "w") as fh:
        for e in bare:
            fh.write(json.dumps(e) + "\n")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "latency_report.py"),
         str(p)],
        capture_output=True, text=True, env=env,
    )
    assert out.returncode == 2
    assert "no latency events" in out.stderr

    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "tools", "latency_report.py"),
         os.path.join(REPO_ROOT, "ROADMAP.md")],
        capture_output=True, text=True, env=env,
    )
    assert out.returncode == 2


def test_pre_round14_baseline_skips_latency_block():
    """Committed pre-round-14 traces keep diffing: no latency events
    on either side means the block is empty and the verdict is
    unaffected (the compatibility contract)."""
    path = os.path.join(REPO_ROOT, "TRACE_r07.jsonl")
    events = load_trace(path)
    validate_events(events)
    report = diff_traces(events, events)
    assert report["ok"]
    assert report["latency"]["lanes"] == {}
    assert report["latency"]["divergences"] == []


# -- deliberate regressions: caught by the NAMED bucket -------------------


class _SlowStats:
    """Wraps a chunk's stats handle so the blocking readback
    (``np.asarray`` → ``__array__``) pays an injected host stall —
    a real sync-floor regression at the real seam."""

    def __init__(self, inner, stall_sec):
        self._inner = inner
        self._stall = stall_sec

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._stall)
        a = np.asarray(self._inner)
        return a.astype(dtype) if dtype is not None else a


class _StallChecker(SortMergeTpuBfsChecker):
    STALL_SEC = 0.12

    def _lookup_programs(self, n0):
        seed_fn, chunk_fn = super()._lookup_programs(n0)

        def slow_chunk(carry):
            out = chunk_fn(carry)
            return (out[0], _SlowStats(out[1], self.STALL_SEC),
                    *out[2:])

        # the traced run's memory analysis compiles the real program
        slow_chunk.lower = chunk_fn.lower
        return seed_fn, slow_chunk


def test_injected_sync_stall_attributed_to_fetch():
    """A host stall injected at the chunk-sync readback must be
    caught by trace_diff's latency alignment and attributed to the
    sync-floor bucket (``fetch_sec``) — with ZERO counter
    divergence, because the stall changes nothing about
    exploration."""
    tr_a, (ca,) = _trace_run(_spawn)

    # warm the stall class's TRACED program cache first (its cache
    # key differs from _spawn's by checker type): the B side must
    # differ from a warm baseline by ONLY the injected stall, not by
    # a fresh build's residual dispatch overhead
    with RunTracer().activate():
        _StallChecker(TwoPhaseSys(rm_count=3).checker(),
                      **_CAPS).join()
    tr_b = RunTracer()
    with tr_b.activate():
        cb = _StallChecker(
            TwoPhaseSys(rm_count=3).checker(), **_CAPS
        ).join()
    validate_events(tr_b.events)
    assert cb.unique_state_count() == ca.unique_state_count()

    report = diff_traces(tr_a.events, tr_b.events)
    assert report["divergences"] == []
    assert not report["ok"]
    assert "fetch_sec" in report["latency"]["regressions"]
    assert "REGRESSION" in format_diff(report)
    # the bucket is RIGHT: dispatch (net of compile) did not flag
    assert "dispatch_net_sec" not in report["latency"]["regressions"]
    # the stall also shows in the engine's untraced accounting
    assert cb.latency_accounting()["fetch_sec"] >= \
        _StallChecker.STALL_SEC


def test_forced_cold_compile_attributed_to_compile():
    """A cache-key perturbation (a waves_per_sync the program cache
    has never seen — time-salted so the persistent XLA disk cache
    can't have it either) forces a genuinely cold compile; the diff
    must attribute the regression to the compile lanes, not to
    dispatch, again at zero counter divergence."""
    # warm side: second run of the standard config (in-process fetch)
    tr_a, (ca,) = _trace_run(_spawn)

    # counts are invariant to waves_per_sync (it only sets the sync
    # cadence); the salt exists purely to defeat the PERSISTENT XLA
    # disk cache across test sessions — it must be wide enough that
    # no earlier session compiled this loop bound (a 16-value salt
    # collided within a day of development)
    wps = 100 + (os.getpid() ^ (time.time_ns() // 1000)) % 4000
    tr_b = RunTracer()
    with tr_b.activate():
        cb = _spawn(waves_per_sync=wps).join()
    validate_events(tr_b.events)
    assert cb.unique_state_count() == ca.unique_state_count()

    builds_b = [e for e in tr_b.events if e["ev"] == "program_build"]
    # the cold compile lands at the FIRST seam to need the program —
    # the memory-analysis AOT pass when traced (the chunk dispatch
    # then loads the executable from the XLA disk cache); what
    # matters is that SOME ledger row carries the real cold wall
    assert any(b["tier"] == "cold" and (b["cold_sec"] or 0) > 0.3
               for b in builds_b), builds_b

    report = diff_traces(tr_a.events, tr_b.events)
    assert report["divergences"] == []
    assert not report["ok"]
    assert "compile_cold_sec" in report["latency"]["regressions"]
    assert "compile_total_sec" in report["latency"]["regressions"]
    # attributed to compile, NOT to dispatch: the subtraction of
    # ledger-attributed compile walls is what keeps this lane quiet
    assert "dispatch_net_sec" not in report["latency"]["regressions"]


def test_verdict_kind_flip_is_divergence():
    """Two runs that settle a property differently (discovery vs
    exhaustion) are not a timing delta — the latency alignment
    reports a divergence and fails the gate."""
    tr_a, _ = _trace_run(_spawn)
    events_b = []
    for e in tr_a.events:
        e = dict(e)
        if e["ev"] == "verdict" and e["property"] == "consistent":
            e["kind"] = "discovery"
        events_b.append(e)
    report = diff_traces(tr_a.events, events_b)
    assert not report["ok"]
    assert any(d["field"] == "verdict_kind"
               and d["property"] == "consistent"
               for d in report["latency"]["divergences"])
    assert "verdict divergence" in format_diff(report)
