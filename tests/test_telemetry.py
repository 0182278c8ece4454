"""Run-telemetry gate (``pytest -m trace``).

Covers the tentpole surface end to end on CPU:

* the tracer core — spans, phase accumulators, activation exclusivity,
  JSONL round-trip + schema validation, Chrome-trace export;
* the engine wave log — a traced sparse sort-merge run produces one
  ``wave`` event per wave whose counters reconcile exactly with the
  checker's final counts, and tracing NEVER changes the counts (the
  smoke contract: traced paxos check == untraced paxos check);
* the sharded engine's log (psum'd global counters, enabled_pairs
  null), the deep level (one wave per chunk, real walls), the
  auto-budget retry event + warning, and the host-phase spans in the
  host checkers;
* the trace differ behind tools/trace_diff.py — wave alignment,
  per-phase regression thresholds, and the CLI's exit codes;
* the shared artifact numbering/provenance helper
  (stateright_tpu/artifacts.py) both exporters ride.
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu import artifacts, telemetry  # noqa: E402
from stateright_tpu.telemetry import (  # noqa: E402
    RunTracer,
    WAVE_LOG_FIELDS,
    diff_traces,
    format_diff,
    load_trace,
    validate_events,
    write_artifacts,
)

pytestmark = pytest.mark.trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _twopc_engine(rm=3, **kw):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    kw.setdefault("capacity", 1 << 10)
    kw.setdefault("frontier_capacity", 256)
    kw.setdefault("cand_capacity", 1024)
    kw.setdefault("track_paths", False)
    return TwoPhaseSys(rm_count=rm).checker().spawn_tpu_sortmerge(**kw)


# -- tracer core ---------------------------------------------------------


def test_tracer_spans_events_and_roundtrip(tmp_path):
    tr = RunTracer()
    with tr.activate():
        assert telemetry.current_tracer() is tr
        tr.begin_run(lane=dict(engine="X"))
        with telemetry.span("compile", engine="X"):
            pass
        acc = tr.phase_acc("property_check")
        for _ in range(3):
            with acc:
                pass
        tr.event("auto_budget_retry", kind="cand_capacity",
                 old=8, new=64, attempt=1)
        tr.end_run(error=None, total_states=5, unique_states=5,
                   max_depth=2, duration_sec=0.01)
    assert telemetry.current_tracer() is None

    path = tr.write_jsonl(str(tmp_path / "t.jsonl"))
    evs = load_trace(path)
    validate_events(evs)
    kinds = [e["ev"] for e in evs]
    assert kinds[0] == "run_begin"
    assert "span" in kinds and "phase_total" in kinds
    assert kinds[-1] == "run_end"
    span = next(e for e in evs if e["ev"] == "span")
    assert span["phase"] == "compile" and span["dur"] >= 0
    acc_ev = next(e for e in evs if e["ev"] == "phase_total")
    assert acc_ev["phase"] == "property_check" and acc_ev["count"] == 3
    begin = evs[0]
    # provenance embedded in every run (the satellite contract)
    assert begin["provenance"]["jax"] == jax.__version__
    assert begin["provenance"]["backend"] == "cpu"
    assert begin["lane"] == {"engine": "X"}

    chrome = tr.write_chrome_trace(str(tmp_path / "t.trace.json"))
    ct = json.load(open(chrome))
    assert isinstance(ct["traceEvents"], list) and ct["traceEvents"]
    assert any(e.get("ph") == "X" for e in ct["traceEvents"])


def test_tracer_activation_is_exclusive():
    a, b = RunTracer(), RunTracer()
    with a.activate():
        with pytest.raises(RuntimeError):
            with b.activate():
                pass
    # released after exit
    with b.activate():
        assert telemetry.current_tracer() is b


def test_span_is_noop_without_tracer():
    with telemetry.span("anything"):
        pass
    telemetry.emit("ignored", x=1)  # no tracer: swallowed


def test_validate_rejects_inconsistent_wave_counters(tmp_path):
    tr = RunTracer()
    with tr.activate():
        tr.begin_run()
        tr.record_chunk(
            chunk=0, wave0=0, t0=0.0, t1=1.0,
            dispatch_sec=0.1, fetch_sec=0.9,
            wave_rows=np.array([[1, 2, 2, 2, 10, 1, 0, 0],
                                [2, 4, 4, 4, 99, 2, 0, 0]]),
        )
        tr.end_run()
    with pytest.raises(ValueError, match="unique_total"):
        validate_events(tr.events)


def test_unknown_level_rejected():
    with pytest.raises(ValueError):
        RunTracer(level="verbose")


# -- engine wave log (single chip) ---------------------------------------


def test_traced_run_counts_unchanged_and_schema_valid(tmp_path):
    """The smoke contract: a traced sparse engine run explores the
    SAME space as an untraced one and its artifacts are schema-valid
    (the paxos lane rides the identical code path; see
    test_trace_smoke_paxos for the paxos-shaped version)."""
    c0 = _twopc_engine().join()
    tr = RunTracer()
    with tr.activate():
        c1 = _twopc_engine().join()
    assert c1.unique_state_count() == c0.unique_state_count() == 288
    assert c1.state_count() == c0.state_count()

    jsonl, chrome = write_artifacts(tr, root=str(tmp_path))
    assert os.path.basename(jsonl).startswith("TRACE_r")
    evs = load_trace(jsonl)
    validate_events(evs)
    waves = [e for e in evs if e["ev"] == "wave"]
    assert waves, "a traced engine run must produce wave events"
    # exact reconciliation with the checker's final counters
    assert waves[-1]["unique_total"] == c1.unique_state_count()
    n0 = waves[0]["unique_total"] - waves[0]["new_states"]
    assert n0 + sum(w["new_states"] for w in waves) == (
        c1.unique_state_count()
    )
    assert n0 + sum(w["candidates"] for w in waves) == c1.state_count()
    assert waves[0]["depth"] == 1
    assert all(w["enabled_pairs"] >= w["candidates"] for w in waves)
    for field in WAVE_LOG_FIELDS:
        assert field in waves[0]
    # lane config names the engine and its budgets
    lane = evs[0]["lane"]
    assert lane["engine"] == "SortMergeTpuBfsChecker"
    assert lane["sparse"] is True
    ct = json.load(open(chrome))
    assert any(e.get("name", "").startswith("wave")
               for e in ct["traceEvents"])


def test_trace_smoke_paxos(tmp_path):
    """Traced ``paxos check`` smoke on CPU (the tier-1-sized 2-client
    lane; the full check-3/check-4 shapes run the identical traced
    program and are exercised by the slow-marked test below): JSONL +
    Chrome artifacts, identical state counts to untraced."""
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

    def spawn():
        return (
            paxos_model(PaxosModelCfg(client_count=2, server_count=3))
            .checker()
            .spawn_tpu_sortmerge(
                capacity=1 << 15,
                frontier_capacity=1 << 12,
                cand_capacity=1 << 14,
                track_paths=False,
            )
        )

    c0 = spawn().join()
    tr = RunTracer()
    with tr.activate():
        c1 = spawn().join()
    assert c1.unique_state_count() == c0.unique_state_count() == 16668
    jsonl, chrome = write_artifacts(tr, root=str(tmp_path))
    evs = load_trace(jsonl)
    validate_events(evs)
    waves = [e for e in evs if e["ev"] == "wave"]
    assert waves[-1]["unique_total"] == 16668
    assert json.load(open(chrome))["traceEvents"]


@pytest.mark.slow
def test_trace_smoke_paxos_check_3(tmp_path):
    """The full satellite smoke at `paxos check 3` scale (1,194,428
    states on CPU — slow-marked)."""
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
    from stateright_tpu.models.paxos_tpu import STRUCTURAL_SIZES

    def spawn():
        return (
            paxos_model(PaxosModelCfg(client_count=3, server_count=3))
            .checker()
            .spawn_tpu_sortmerge(
                track_paths=False, cand_capacity=1 << 22,
                **STRUCTURAL_SIZES[3],
            )
        )

    c0 = spawn().join()
    tr = RunTracer()
    with tr.activate():
        c1 = spawn().join()
    assert c1.unique_state_count() == c0.unique_state_count() == 1194428
    jsonl, _ = write_artifacts(tr, root=str(tmp_path))
    evs = load_trace(jsonl)
    validate_events(evs)
    waves = [e for e in evs if e["ev"] == "wave"]
    assert waves[-1]["unique_total"] == 1194428


def test_deep_level_gives_real_per_wave_walls():
    tr = RunTracer(level="deep")
    with tr.activate():
        c = _twopc_engine().join()
    assert c.unique_state_count() == 288
    chunks = [e for e in tr.events if e["ev"] == "chunk"]
    waves = [e for e in tr.events if e["ev"] == "wave"]
    assert len(chunks) == len(waves)  # one wave per chunk
    assert all(ch["device_sec"] is not None for ch in chunks)
    assert all(w["t_est"] is False for w in waves)
    assert any(e["ev"] == "deep_sync_override" for e in tr.events)


def test_traced_sharded_engine_wave_log():
    from jax.sharding import Mesh

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs the virtual multi-device CPU mesh")
    mesh = Mesh(np.array(devices[:4]), ("shard",))
    tr = RunTracer()
    with tr.activate():
        c = (
            TwoPhaseSys(rm_count=3)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                mesh=mesh,
                capacity=1 << 10,
                frontier_capacity=256,
                cand_capacity=1024,
                bucket_capacity=512,
                waves_per_sync=8,
                track_paths=False,
            )
            .join()
        )
    assert c.unique_state_count() == 288
    validate_events(tr.events)
    waves = [e for e in tr.events if e["ev"] == "wave"]
    assert waves and waves[-1]["unique_total"] == 288
    # global (psum'd) frontier rows, not per-shard
    assert waves[0]["frontier_rows"] == 1
    # the GLOBAL log wrapper still can't see the enabled popcount,
    # but the per-shard mesh log can: the wave event's enabled_pairs
    # is back-filled from the shard sum (the round-11 hole closure)
    shard_waves = [e for e in tr.events if e["ev"] == "shard_wave"]
    assert shard_waves
    for w in waves:
        rows = [e for e in shard_waves if e["wave"] == w["wave"]]
        assert len(rows) == 4
        assert w["enabled_pairs"] == sum(
            r["enabled_pairs"] for r in rows
        )
        assert w["enabled_pairs"] >= w["candidates"]
    assert tr.events[0]["lane"]["n_shards"] == 4
    assert tr.events[0]["lane"]["dest_tile_lanes"] > 0


def test_traced_sharded_parity_and_shard_log_8_mesh():
    """The round-11 acceptance gate: on the virtual 8-CPU mesh, a
    TRACED sharded run explores exactly the space an untraced one does
    (the per-shard log must not perturb the search), every wave gets
    one ``shard_wave`` event per shard, the per-shard counters
    reconcile with the global log lane for lane, and the derived
    shard_balance summary agrees with the engine's own shuffle
    metric."""
    from jax.sharding import Mesh

    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import shard_balance

    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the virtual 8-device CPU mesh")
    mesh = Mesh(np.array(devices[:8]), ("shard",))

    def spawn():
        return (
            TwoPhaseSys(rm_count=3)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                mesh=mesh,
                capacity=1 << 10,
                frontier_capacity=256,
                cand_capacity=1024,
                bucket_capacity=512,
                waves_per_sync=8,
                track_paths=False,
            )
        )

    c0 = spawn().join()
    tr = RunTracer()
    with tr.activate():
        c1 = spawn().join()
    assert c1.unique_state_count() == c0.unique_state_count() == 288
    assert c1.state_count() == c0.state_count()
    validate_events(tr.events)
    waves = {e["wave"]: e for e in tr.events if e["ev"] == "wave"}
    shard_waves = [e for e in tr.events if e["ev"] == "shard_wave"]
    assert waves and shard_waves
    for w, ev in waves.items():
        rows = [e for e in shard_waves if e["wave"] == w]
        assert len(rows) == 8
        assert sum(r["frontier_rows"] for r in rows) == \
            ev["frontier_rows"]
        assert sum(r["candidates"] for r in rows) == ev["candidates"]
        assert sum(r["new_states"] for r in rows) == ev["new_states"]
        assert sum(r["visited_total"] for r in rows) == \
            ev["unique_total"]
        # the Bd cap gates all_to_all correctness: a logged fill can
        # never exceed it on a completed (non-overflow) run
        assert all(r["dest_fill_peak"] <= r["dest_cap"] for r in rows)
    bal = shard_balance(tr.events)
    assert bal is not None and bal["n_shards"] == 8
    assert bal["waves"] == len(waves)
    assert sum(bal["visited_per_shard"]) == 288
    # trace-derived routed volume == the engine's psum'd shuffle
    # counter (two independent paths to the same number)
    assert bal["routed_rows_total"] == c1.metrics["shuffle_volume"]
    # a self-diff of the sharded trace is clean (shard-aware
    # alignment included)
    rep = diff_traces(tr.events, tr.events)
    assert rep["ok"], rep["divergences"]


def test_auto_budget_retry_event_and_warning(tmp_path):
    """Satellite: a forced overflow on the geometric capacity ladder
    must produce a telemetry event AND a one-line warning naming the
    old/new capacity (the retry used to be silent)."""
    tr = RunTracer()
    with tr.activate(), warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        c = _twopc_engine(cand_capacity="auto")
        c._budget_store = lambda: str(tmp_path / "budgets.json")
        c.cand_capacity = 8  # force the first wave over budget
        c.join()
    assert c.unique_state_count() == 288
    msgs = [str(w.message) for w in rec
            if "auto-budget" in str(w.message)]
    assert msgs and "8 ->" in msgs[0]
    evs = [e for e in tr.events if e["ev"] == "auto_budget_retry"]
    assert evs and evs[0]["old"] == 8 and evs[0]["new"] > 8
    assert evs[0]["kind"] == "cand_capacity"
    # the clean re-run's waves overwrite the failed attempt's indices:
    # the final wave still reconciles
    waves = [e for e in tr.events if e["ev"] == "wave"]
    assert waves[-1]["unique_total"] == 288
    # a retried run's trace is a LEGITIMATE artifact: the validator
    # treats a non-advancing wave index as an attempt restart (and
    # trace_diff's last-occurrence alignment reads the clean attempt)
    validate_events(tr.events)
    rep = diff_traces(tr.events, tr.events)
    assert not rep["divergences"]


def test_untraced_run_keeps_wave_log_out_of_carry():
    c = _twopc_engine()
    c.keep_final_carry = True
    c.join()
    assert "wlog" not in c._final_carry
    assert "wv_pairs" not in c._final_carry


# -- host-phase spans ----------------------------------------------------


def test_host_bfs_phase_totals_and_reconstruction_span():
    from stateright_tpu.models.increment import Increment

    tr = RunTracer()
    with tr.activate():
        c = Increment(thread_count=2).checker().spawn_bfs().join()
    assert "fin" in c.discoveries()
    kinds = {e["ev"] for e in tr.events}
    assert {"run_begin", "run_end"} <= kinds
    totals = {e["phase"] for e in tr.events if e["ev"] == "phase_total"}
    assert "property_check" in totals
    spans = {e["phase"] for e in tr.events if e["ev"] == "span"}
    assert "counterexample_reconstruction" in spans
    end = next(e for e in tr.events if e["ev"] == "run_end")
    assert end["unique_states"] == c.unique_state_count()
    assert end["error"] is None


def test_host_dfs_symmetry_span():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    tr = RunTracer()
    with tr.activate():
        TwoPhaseSys(rm_count=2).checker().symmetry().spawn_dfs().join()
    totals = {e["phase"] for e in tr.events if e["ev"] == "phase_total"}
    assert "symmetry_canonicalization" in totals
    assert "property_check" in totals


def test_device_engine_spans_and_chunk_split():
    tr = RunTracer()
    with tr.activate():
        _twopc_engine().join()
    spans = {e["phase"] for e in tr.events if e["ev"] == "span"}
    assert {"compile", "seed_upload"} <= spans
    chunks = [e for e in tr.events if e["ev"] == "chunk"]
    assert chunks
    for ch in chunks:
        assert ch["dispatch_sec"] >= 0 and ch["fetch_sec"] >= 0
        assert ch["device_sec"] is None  # default level: no extra sync


def test_failed_run_ends_with_error():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    tr = RunTracer()
    with tr.activate():
        c = (
            TwoPhaseSys(rm_count=3)
            .checker()
            .spawn_tpu_sortmerge(
                capacity=64, frontier_capacity=64, cand_capacity=256,
                track_paths=False,
            )
        )
        with pytest.raises(RuntimeError, match="overflow"):
            c.join()
    end = next(e for e in tr.events if e["ev"] == "run_end")
    assert end["error"] and "overflow" in end["error"]


# -- trace diff ----------------------------------------------------------


def _synthetic_trace(tmp_path, name, *, fetch=0.9, new=(9, 40, 100),
                     total=2.0):
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane=dict(engine="T"))
        with telemetry.span("compile"):
            pass
        u = 1
        rows = []
        for i, n in enumerate(new):
            u += n
            rows.append([max(n, 1), n + 2, n + 1, n, u, i + 1, 0, 0])
        tr.record_chunk(
            chunk=0, wave0=0, t0=0.0, t1=1.0,
            dispatch_sec=0.01, fetch_sec=fetch,
            wave_rows=np.array(rows),
        )
        tr.end_run(error=None, total_states=sum(new), unique_states=u,
                   max_depth=len(new), duration_sec=total)
    path = str(tmp_path / name)
    tr.write_jsonl(path)
    return path


def test_trace_diff_clean_and_regression(tmp_path):
    a = load_trace(_synthetic_trace(tmp_path, "a.jsonl"))
    b = load_trace(_synthetic_trace(tmp_path, "b.jsonl"))
    rep = diff_traces(a, b)
    assert rep["ok"] and not rep["divergences"]
    assert "verdict: OK" in format_diff(rep)

    slow = load_trace(
        _synthetic_trace(tmp_path, "slow.jsonl", fetch=2.0, total=4.0)
    )
    rep2 = diff_traces(a, slow)
    assert not rep2["ok"]
    assert "host_fetch" in rep2["regressions"]
    assert "run_total" in rep2["regressions"]
    assert "REGRESSION" in format_diff(rep2)
    # the faster direction is not a regression
    assert diff_traces(slow, a)["ok"]


def test_trace_diff_wave_divergence(tmp_path):
    a = load_trace(_synthetic_trace(tmp_path, "a.jsonl"))
    d = load_trace(
        _synthetic_trace(tmp_path, "d.jsonl", new=(9, 41, 100))
    )
    rep = diff_traces(a, d)
    assert not rep["ok"]
    fields = {x["field"] for x in rep["divergences"]}
    assert "new_states" in fields and "unique_total" in fields
    assert "DIVERGENCE" in format_diff(rep)


def test_trace_diff_cli_exit_codes(tmp_path):
    a = _synthetic_trace(tmp_path, "a.jsonl")
    b = _synthetic_trace(tmp_path, "b.jsonl", fetch=2.0, total=4.0)
    tool = os.path.join(REPO_ROOT, "tools", "trace_diff.py")

    def run(*argv):
        return subprocess.run(
            [sys.executable, tool, *argv],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )

    ok = run(a, a)
    assert ok.returncode == 0, ok.stderr
    assert "verdict: OK" in ok.stdout

    reg = run(a, b)
    assert reg.returncode == 1
    assert "REGRESSION" in reg.stdout

    loose = run(a, b, "--threshold", "10.0")
    assert loose.returncode == 0

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as fh:
        fh.write("not json\n")
    assert run(a, bad).returncode == 2


# -- mesh observability: shard_wave / shard_balance / shard_report -------


def _synthetic_shard_trace(tmp_path, name, per_shard_new, *,
                           permute=False, dest_cap=512, fill=8,
                           capacity=1024, visited0=1, recv_classes=None):
    """A schema-valid sharded trace: ``per_shard_new[wave][shard]`` is
    the post-dedup new count; global rows are the shard sums, so the
    two log levels reconcile the way a real engine's do. ``permute``
    reverses the shard numbering (the relabeling the multiset
    alignment must tolerate); ``fill``/``dest_cap`` set the dest-tile
    lanes; ``visited0`` seeds each shard's visited counter;
    ``recv_classes`` is the sharded sort-merge engine's receive-tile
    ladder in the lane config."""
    tr = RunTracer()
    with tr.activate():
        S = len(per_shard_new[0])
        extra = {} if recv_classes is None else dict(
            recv_classes=recv_classes)
        tr.begin_run(lane=dict(engine="T", n_shards=S,
                               capacity=capacity, dest_tile_lanes=10,
                               **extra))
        visited = [visited0] * S
        prev_front = [1] * S
        u = S * visited0
        rows_g, rows_s = [], []
        for i, new in enumerate(per_shard_new):
            cand = [n * 2 for n in new]
            u += sum(new)
            rows_g.append([sum(prev_front), sum(cand), sum(cand),
                           sum(new), u, i + 1, 0, 0])
            wave_rows = []
            for s in range(S):
                visited[s] += new[s]
                wave_rows.append([
                    prev_front[s], cand[s], cand[s],
                    cand[s] // 2, cand[s], fill, dest_cap,
                    new[s], visited[s],
                ])
            rows_s.append(wave_rows)
            prev_front = new
        sr = np.array(rows_s).transpose(1, 0, 2)  # [S, waves, lanes]
        if permute:
            sr = sr[::-1]
        tr.record_chunk(
            chunk=0, wave0=0, t0=0.0, t1=1.0,
            dispatch_sec=0.01, fetch_sec=0.9,
            wave_rows=np.array(rows_g), shard_rows=sr,
        )
        tr.end_run(error=None, total_states=u, unique_states=u,
                   max_depth=len(per_shard_new), duration_sec=2.0)
    path = str(tmp_path / name)
    tr.write_jsonl(path)
    return path


BALANCED = [[8, 8, 8, 8], [40, 40, 40, 40], [100, 100, 100, 100]]


def test_shard_wave_schema_valid_and_chrome_tracks(tmp_path):
    from stateright_tpu.telemetry import SHARD_LOG_FIELDS

    path = _synthetic_shard_trace(tmp_path, "s.jsonl", BALANCED)
    evs = load_trace(path)
    validate_events(evs)
    sws = [e for e in evs if e["ev"] == "shard_wave"]
    assert len(sws) == 3 * 4
    for field in SHARD_LOG_FIELDS:
        assert field in sws[0]
    # schema rejection: a broken per-shard running sum
    bad = [dict(e) for e in evs]
    victim = next(e for e in bad if e["ev"] == "shard_wave"
                  and e["wave"] == 2)
    victim["visited_total"] += 1
    with pytest.raises(ValueError, match="visited_total"):
        validate_events(bad)
    # missing-field rejection
    bad2 = [dict(e) for e in evs]
    del next(e for e in bad2
             if e["ev"] == "shard_wave")["routed_rows"]
    with pytest.raises(ValueError, match="routed_rows"):
        validate_events(bad2)
    # Chrome export renders one track per shard
    tr = RunTracer()
    tr.events = evs
    chrome = tr.write_chrome_trace(str(tmp_path / "s.trace.json"))
    ct = json.load(open(chrome))
    names = {e["args"]["name"] for e in ct["traceEvents"]
             if e.get("name") == "thread_name"}
    assert {"shard 0", "shard 3"} <= names


def test_shard_balance_flags_deliberate_imbalance(tmp_path):
    """The skew-metric satellite: one shard carrying the whole big
    waves must flag, a balanced run must not."""
    from stateright_tpu.telemetry import shard_balance

    ok = load_trace(
        _synthetic_shard_trace(tmp_path, "ok.jsonl", BALANCED)
    )
    bal = shard_balance(ok)
    assert bal["n_shards"] == 4 and bal["waves"] == 3
    assert bal["frontier_skew_weighted"] == 1.0
    assert not any("imbalance" in w for w in bal["warnings"])

    skewed = load_trace(
        _synthetic_shard_trace(
            tmp_path, "skew.jsonl",
            [[8, 8, 8, 8], [400, 0, 0, 0], [400, 0, 0, 0]],
        )
    )
    bal2 = shard_balance(skewed)
    assert bal2["frontier_skew_worst"]["skew"] == 4.0
    assert bal2["frontier_skew_weighted"] > 2.0
    assert any("imbalance" in w for w in bal2["warnings"])
    # routed volume prices bytes off the lane's tile width
    assert bal2["routed_bytes_total"] == \
        bal2["routed_rows_total"] * 10 * 4


def test_shard_balance_headroom_warnings(tmp_path):
    """dest-tile fill near the lossless Bd cap and a shard's visited
    occupancy near capacity both warn, via the SHARED formatter
    (stateright_tpu/occupancy.py)."""
    from stateright_tpu.telemetry import shard_balance

    tight = load_trace(
        _synthetic_shard_trace(
            tmp_path, "tight.jsonl", BALANCED,
            dest_cap=100, fill=95, capacity=200, visited0=40,
        )
    )
    bal = shard_balance(tight)
    assert bal["dest_fill_worst"]["util"] == 0.95
    assert any("dest tile" in w and "bucket_capacity" in w
               for w in bal["warnings"])
    assert bal["occupancy_max"] is not None
    assert any("visited array" in w and "overflows exactly" in w
               for w in bal["warnings"])

    # the sharded sort-merge engine logs each wave's receive class as
    # dest_cap, sized to the wave: a full class is no risk, the
    # ladder's top (the lossless cap) is what the run is held to
    roomy = load_trace(
        _synthetic_shard_trace(
            tmp_path, "roomy.jsonl", BALANCED,
            dest_cap=100, fill=95, recv_classes=[100, 400],
        )
    )
    bal_r = shard_balance(roomy)
    assert bal_r["per_wave"][0]["dest_util"] == 0.95
    assert bal_r["dest_fill_worst"]["util"] == 0.2375
    assert bal_r["dest_fill_worst"]["cap"] == 400
    assert not any("dest tile" in w for w in bal_r["warnings"])

    # 11 + 148 = 159/200 = 0.795: the exact-capacity watch (0.8
    # bar) stays quiet — threshold semantics, not noise
    mid_exact = load_trace(
        _synthetic_shard_trace(
            tmp_path, "mid_exact.jsonl", BALANCED,
            capacity=200, visited0=11,
        )
    )
    assert not any("visited array" in w
                   for w in shard_balance(mid_exact)["warnings"])


def test_occupancy_warning_shared_helper():
    """The deduplicated occupancy formatter: one home for the
    hash-engine probe-pressure warning AND the mesh report's
    exact-capacity headroom warnings."""
    from stateright_tpu.occupancy import (
        HEADROOM_THRESHOLD,
        occupancy_warning,
    )

    assert occupancy_warning(0.5) is None
    msg = occupancy_warning(0.8, used=800, capacity=1000)
    assert "80% full" in msg and "(800/1000)" in msg
    assert "probe failures" in msg  # the hash-engine default
    custom = occupancy_warning(
        0.95, kind="shard 3 visited array",
        threshold=HEADROOM_THRESHOLD,
        consequence="overflows at 100%",
    )
    assert custom.startswith("shard 3 visited array")
    assert "overflows at 100%" in custom
    # at-threshold is quiet (warn past, not at)
    assert occupancy_warning(HEADROOM_THRESHOLD,
                             threshold=HEADROOM_THRESHOLD) is None


def test_hash_engine_occupancy_warning_uses_helper():
    """checkers/tpu.py's probe-pressure warning now routes through
    the shared formatter (the dedup satellite) — same text, same
    threshold, absolute counts included."""
    from stateright_tpu.checkers.tpu import TpuBfsChecker
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(rm_count=3).checker().spawn_tpu(
        capacity=1 << 10, frontier_capacity=256, track_paths=False,
    )
    assert isinstance(c, TpuBfsChecker)
    c._unique_states = 800
    c.total_capacity = 1000
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        c._maybe_warn_occupancy(0.8)
        c._maybe_warn_occupancy(0.5)  # under threshold: quiet
    msgs = [str(w.message) for w in rec]
    assert len(msgs) == 1
    assert "visited table 80% full (800/1000)" in msgs[0]
    assert "probe failures" in msgs[0]


def test_trace_diff_shard_aware_alignment(tmp_path):
    """Shard-aware wave alignment: shard RENUMBERING must not
    false-positive (multiset comparison), a redistributed partition
    with identical GLOBAL counters must still diverge."""
    a = load_trace(_synthetic_shard_trace(tmp_path, "a.jsonl",
                                          BALANCED))
    # same rows, shards relabeled in reverse — a mesh relabeling
    perm = load_trace(
        _synthetic_shard_trace(tmp_path, "p.jsonl", BALANCED,
                               permute=True)
    )
    rep = diff_traces(a, perm)
    assert rep["ok"], rep["divergences"]

    # dest_cap is CONFIG, not exploration: a bucket_capacity-only
    # A/B (different Bd, same counts) must compare on timing, not
    # fail the alignment gate
    retuned = load_trace(
        _synthetic_shard_trace(tmp_path, "cap.jsonl", BALANCED,
                               dest_cap=2048)
    )
    assert diff_traces(a, retuned)["ok"]

    # redistribute wave 2 across shards: global sums identical, the
    # per-shard partition is not → shard_multiset divergence
    moved = load_trace(
        _synthetic_shard_trace(
            tmp_path, "m.jsonl",
            [[8, 8, 8, 8], [40, 40, 40, 40], [130, 70, 100, 100]],
        )
    )
    rep2 = diff_traces(a, moved)
    assert not rep2["ok"]
    fields = {d["field"] for d in rep2["divergences"]}
    # the redistribution preserves every GLOBAL counter — only the
    # shard-aware layer catches it
    assert fields == {"shard_multiset"}
    # one side sharded, the other not → shard_present divergence
    unsharded = load_trace(_synthetic_trace(tmp_path, "u.jsonl",
                                            new=(32, 160, 400)))
    rep3 = diff_traces(a, unsharded)
    assert not rep3["ok"]
    assert any(d["field"] == "shard_present"
               for d in rep3["divergences"])


def test_trace_diff_cli_shard_exit_codes(tmp_path):
    """The satellite's exit-code contract, through the real CLI."""
    a = _synthetic_shard_trace(tmp_path, "a.jsonl", BALANCED)
    perm = _synthetic_shard_trace(tmp_path, "p.jsonl", BALANCED,
                                  permute=True)
    moved = _synthetic_shard_trace(
        tmp_path, "m.jsonl",
        [[8, 8, 8, 8], [40, 40, 40, 40], [130, 70, 100, 100]],
    )
    tool = os.path.join(REPO_ROOT, "tools", "trace_diff.py")

    def run(*argv):
        return subprocess.run(
            [sys.executable, tool, *argv],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )

    assert run(a, perm).returncode == 0  # renumbering: clean
    div = run(a, moved)
    assert div.returncode == 1
    assert "shard_multiset" in div.stdout


def test_shard_report_cli(tmp_path):
    tool = os.path.join(REPO_ROOT, "tools", "shard_report.py")

    def run(*argv):
        return subprocess.run(
            [sys.executable, tool, *argv],
            capture_output=True, text=True, cwd=REPO_ROOT,
        )

    skewed = _synthetic_shard_trace(
        tmp_path, "skew.jsonl",
        [[8, 8, 8, 8], [400, 0, 0, 0], [400, 0, 0, 0]],
    )
    out = run(skewed)
    assert out.returncode == 0, out.stderr
    assert "shard balance: run #0, 4 shards" in out.stdout
    assert "worst-wave skew" in out.stdout
    assert "cumulative shuffle" in out.stdout
    assert "WARNING" in out.stdout  # the skew warning surfaces

    # a trace without shard events is a usage error, not a crash
    plain = _synthetic_trace(tmp_path, "plain.jsonl")
    bad = run(plain)
    assert bad.returncode == 2
    assert "no shard_wave events" in bad.stderr


# -- CLI flag ------------------------------------------------------------


def test_cli_pop_trace_flag():
    from stateright_tpu.cli import _pop_trace_flag

    assert _pop_trace_flag(["paxos", "check", "2"]) == (
        None, ["paxos", "check", "2"]
    )
    assert _pop_trace_flag(["paxos", "--trace", "check-tpu", "4"]) == (
        "default", ["paxos", "check-tpu", "4"]
    )
    assert _pop_trace_flag(["2pc", "check-tpu", "3", "--trace=deep"]) == (
        "deep", ["2pc", "check-tpu", "3"]
    )


def test_cli_rejects_unknown_trace_level():
    from stateright_tpu import cli

    with pytest.raises(SystemExit, match="verbose"):
        cli.main(["increment", "check-tpu", "2", "--trace=verbose"])


def test_cli_trace_writes_artifacts_on_failure(tmp_path, monkeypatch):
    """A traced run that raises must still leave its partial trace
    (the failure is what the trace is for)."""
    from stateright_tpu import cli

    monkeypatch.setattr(artifacts, "repo_root", lambda: str(tmp_path))

    def boom(sub, args):
        from stateright_tpu.models.two_phase_commit import TwoPhaseSys

        TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
            capacity=64, frontier_capacity=64, cand_capacity=256,
            track_paths=False,
        ).join()

    monkeypatch.setitem(cli._MODELS, "2pc", (boom, ["check-tpu"]))
    with pytest.raises(RuntimeError, match="overflow"):
        cli.main(["2pc", "check-tpu", "3", "--trace"])
    written = os.listdir(tmp_path)
    assert any(f.startswith("TRACE_r") and f.endswith(".jsonl")
               for f in written)
    jsonl = next(f for f in written if f.endswith(".jsonl"))
    evs = load_trace(str(tmp_path / jsonl))
    end = next(e for e in evs if e["ev"] == "run_end")
    assert end["error"] and "overflow" in end["error"]


def test_cli_trace_writes_artifacts(tmp_path, monkeypatch, capsys):
    from stateright_tpu import cli

    monkeypatch.setattr(artifacts, "repo_root", lambda: str(tmp_path))
    cli.main(["increment", "check-tpu", "2", "--trace"])
    out = capsys.readouterr()
    assert "Done." in out.out
    written = sorted(os.listdir(tmp_path))
    assert any(f.startswith("TRACE_r") and f.endswith(".jsonl")
               for f in written)
    assert any(f.endswith(".trace.json") for f in written)
    jsonl = next(f for f in written if f.endswith(".jsonl"))
    evs = load_trace(str(tmp_path / jsonl))
    validate_events(evs)
    assert any(e["ev"] == "wave" for e in evs)


# -- shared artifact numbering / provenance ------------------------------


def test_cli_trace_sharded_run_end_counters(tmp_path, monkeypatch):
    """``paxos check-tpu 2 --shards 4 --trace`` on the CPU mesh: the
    run_end event carries the shuffle's run-level counters, each equal
    to what the per-shard ``shard_wave`` events and the engine's own
    psum'd ``sent`` counter give."""
    from stateright_tpu import cli
    from stateright_tpu.telemetry import shard_balance

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh")
    monkeypatch.setattr(artifacts, "repo_root", lambda: str(tmp_path))
    got = []
    monkeypatch.setattr(cli._SESSION_HOOK, "hook", got.append,
                        raising=False)
    cli.main(["paxos", "check-tpu", "2", "--shards", "4", "--trace"])
    (checker,) = got
    assert checker.unique_state_count() == 16668
    jsonl = next(f for f in os.listdir(tmp_path) if f.endswith(".jsonl"))
    evs = load_trace(str(tmp_path / jsonl))
    validate_events(evs)
    assert {e["shard"] for e in evs if e["ev"] == "shard_wave"} == \
        {0, 1, 2, 3}
    end = next(e for e in evs if e["ev"] == "run_end")
    bal = shard_balance(evs)
    assert end["n_shards"] == bal["n_shards"] == 4
    assert end["routed_rows"] == checker.metrics["shuffle_volume"] == \
        bal["routed_rows_total"] > 0
    assert end["candidate_skew_worst"] == \
        bal["candidate_skew_worst"]["skew"] >= 1.0


def test_artifact_numbering_shared_across_families(tmp_path):
    root = str(tmp_path)
    assert artifacts.next_round(root) == 1
    open(os.path.join(root, "BENCH_r03.json"), "w").close()
    open(os.path.join(root, "TRACE_r05.jsonl"), "w").close()
    assert artifacts.next_round(root) == 6
    assert artifacts.artifact_path("LINT", "json", root=root).endswith(
        "LINT_r06.json"
    )
    p = artifacts.artifact_path("TRACE", "trace.json", root=root,
                                round=9)
    assert p.endswith("TRACE_r09.trace.json")


def test_lint_cli_uses_shared_numbering(tmp_path, monkeypatch):
    """tools/lint_kernels.py --json and the trace exporter share ONE
    numbering helper: both consult every artifact family."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_kernels", os.path.join(REPO_ROOT, "tools",
                                     "lint_kernels.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the module must not have grown a private numbering copy back
    assert not hasattr(mod, "_next_artifact_path")


def test_provenance_block():
    p = artifacts.provenance(lane={"headline": "x"})
    assert p["jax"] == jax.__version__
    assert p["backend"] == "cpu"
    assert p["device_count"] >= 1
    assert p["python"]
    assert p["lane"] == {"headline": "x"}
    # the repo is a git checkout: the SHA must resolve
    assert p["git_sha"] and len(p["git_sha"]) == 40
