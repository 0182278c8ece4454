"""Memory-observability gate (``pytest -m mem``).

Covers the round-12 tentpole surface end to end on CPU:

* the resident-buffer ledger — ``memory_plan`` totals match the
  ACTUAL device arrays' ``nbytes`` on real engine buffers, for both
  sort-merge engines AND the hash engine, single-chip and sharded
  (per-shard bytes checked against the arrays' addressable shards);
* event schema — memory_plan/memory_watermark validate, chunk events
  carry the polled ``mem_bytes`` lane, untraced runs emit nothing
  (but still expose ``checker.memory_plan``) and keep identical
  counts;
* the ``engine_mode`` satellite — the CHUNKED memory-lean flip lands
  as a telemetry event on the forced flip, with counts unchanged;
* occupancy warnings priced in bytes (the shared formatter, at both
  the hash-engine probe-pressure call site and shard_balance);
* tools/mem_report.py — report rendering, ``--json`` MEM_r* artifact
  numbering (own sequence, through artifacts.py), exit 2 on traces
  without memory events;
* trace_diff memory alignment — plan shapes exact (divergence fails
  the gate), measured temp/live bytes under ``--threshold``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu import memplan, telemetry  # noqa: E402
from stateright_tpu.telemetry import (  # noqa: E402
    RunTracer,
    diff_traces,
    format_diff,
    load_trace,
    memory_summary,
    validate_events,
)

pytestmark = pytest.mark.mem

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _twopc_builder(rm=3):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(rm_count=rm).checker()


def _traced_checker(spawn, **kw):
    tracer = RunTracer()
    with tracer.activate():
        checker = spawn(**kw)
        checker.keep_final_carry = True
        checker.join()
    return tracer, checker


def _check_plan_vs_nbytes(checker, n_shards=1):
    """THE acceptance contract: every resident ledger row matches the
    real device array the engine kept (shape, dtype, nbytes), and the
    totals add up."""
    plan = checker.memory_plan
    assert plan is not None
    carry = checker._final_carry
    assert set(e["name"] for e in plan["resident"]) == set(carry)
    total = 0
    for e in plan["resident"]:
        arr = carry[e["name"]]
        assert tuple(e["shape"]) == tuple(arr.shape), e["name"]
        assert e["dtype"] == str(np.dtype(arr.dtype)), e["name"]
        assert e["bytes"] == arr.nbytes, e["name"]
        total += arr.nbytes
        if n_shards > 1:
            shard_nbytes = arr.addressable_shards[0].data.nbytes
            assert e["per_shard_bytes"] == shard_nbytes, e["name"]
    assert plan["resident_bytes"] == total
    assert plan["n_shards"] == n_shards
    assert plan["total_bytes"] >= plan["resident_bytes"]
    assert plan["classes"], "per-ladder-class staging must exist"
    for c in plan["classes"]:
        assert c["staging_bytes"] == sum(
            s["bytes"] for s in c["staging"]
        )


# -- plan vs nbytes on real engine buffers (all four engines) ------------


def test_plan_matches_nbytes_sortmerge_single_chip():
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=True,
    )
    assert c.unique_state_count() == 288
    _check_plan_vs_nbytes(c)
    validate_events(tracer.events)


def test_plan_matches_nbytes_sortmerge_sharded():
    n = jax.device_count()
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sharded_sortmerge(
            **kw),
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=True,
    )
    assert c.unique_state_count() == 288
    _check_plan_vs_nbytes(c, n_shards=n)
    validate_events(tracer.events)
    # the sharded resident buffers really split: vkeys is the SoA
    # [2, S * C_pad] block, so per-shard is exactly 1/S of it
    vk = next(e for e in c.memory_plan["resident"]
              if e["name"] == "vkeys")
    assert vk["sharded"] and vk["per_shard_bytes"] * n == vk["bytes"]


def test_plan_matches_nbytes_hash_engines():
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu(**kw),
        capacity=1 << 12, frontier_capacity=256,
    )
    assert c.unique_state_count() == 288
    _check_plan_vs_nbytes(c)

    n = jax.device_count()
    tracer2, c2 = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sharded(**kw),
        capacity=1 << 10, frontier_capacity=256,
    )
    assert c2.unique_state_count() == 288
    _check_plan_vs_nbytes(c2, n_shards=n)


# -- event schema / polling ----------------------------------------------


def test_memory_events_schema_and_polling(tmp_path):
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=False,
    )
    validate_events(tracer.events)
    plans = [e for e in tracer.events if e["ev"] == "memory_plan"]
    assert len(plans) == 1
    plan = plans[0]
    assert plan["engine"] == "SortMergeTpuBfsChecker"
    # compiled-program analysis: reported on this backend (CPU XLA
    # answers memory_analysis) — or explicitly null, never missing
    assert "compiled" in plan
    wms = [e for e in tracer.events if e["ev"] == "memory_watermark"]
    assert len(wms) == 1
    wm = wms[0]
    # CPU: memory_stats() is None, so the live-array fallback polled
    assert wm["source"] == "live_arrays"
    assert wm["device_peak_bytes"] > 0
    assert wm["polls"] >= 1
    hr = wm["headroom"]
    assert hr["visited_rows"] == 288
    assert hr["visited_used_bytes"] == 288 * hr["bytes_per_row"]
    assert wm["projection"]["kind"] == "next_v_class"
    assert wm["projection"]["next_vkeys_bytes"] > 0
    # every chunk polled at the existing sync — no chunk without it
    chunks = [e for e in tracer.events if e["ev"] == "chunk"]
    assert chunks and all(
        isinstance(e.get("mem_bytes"), int) for e in chunks
    )
    # the peak is the max over the polls
    assert wm["device_peak_bytes"] == max(
        e["mem_bytes"] for e in chunks
    )
    # JSONL round-trip preserves the memory events
    path = tracer.write_jsonl(str(tmp_path / "t.jsonl"))
    evs = load_trace(path)
    validate_events(evs)
    summary = memory_summary(evs)
    assert summary is not None
    assert summary["plan"]["resident_bytes"] == plan["resident_bytes"]
    assert summary["chunk_mem"]
    # run peak lands in checker metrics too (bench embeds it)
    assert c.metrics["device_peak_bytes"] == wm["device_peak_bytes"]


def test_untraced_run_emits_nothing_but_keeps_plan():
    c = _twopc_builder().spawn_tpu_sortmerge(
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=False,
    ).join()
    assert c.unique_state_count() == 288
    # the ledger exists untraced (bench.py embeds it per lane) ...
    assert c.memory_plan is not None
    assert c.memory_plan["resident_bytes"] > 0
    # ... but no polling happened (no tracer: no watermark metric)
    assert "device_peak_bytes" not in c.metrics
    # untraced and traced explore identically (the smoke contract)
    tracer, c2 = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=False,
    )
    assert c2.unique_state_count() == c.unique_state_count()
    # the untraced plan has no wave-log lanes; the traced one does
    names = {e["name"] for e in c.memory_plan["resident"]}
    names2 = {e["name"] for e in c2.memory_plan["resident"]}
    assert "wlog" not in names and "wlog" in names2


def test_compiled_analysis_transient_failure_not_cached(tmp_path,
                                                        monkeypatch):
    """A FAILED lower/compile raises (the compiler's refusal is not
    hidden) and does not poison the persisted analysis cache — only a
    backend that genuinely can't report the analysis caches its
    None."""
    monkeypatch.setattr(
        memplan, "_analysis_store",
        lambda: str(tmp_path / "mem_analysis.json"),
    )
    memplan._ANALYSIS_CACHE.clear()

    class Broken:
        def lower(self, spec):
            raise RuntimeError("device busy")

    with pytest.raises(RuntimeError, match="device busy"):
        memplan.compiled_memory_analysis(Broken(), {}, "tok")
    assert "tok" not in str(memplan._ANALYSIS_CACHE)
    assert not os.path.exists(str(tmp_path / "mem_analysis.json"))
    # a working compile afterwards lands and persists
    f = jax.jit(lambda x: x + 1)
    spec = jax.ShapeDtypeStruct((4,), "uint32")
    result = memplan.compiled_memory_analysis(f, spec, "tok")
    assert result is not None
    assert os.path.exists(str(tmp_path / "mem_analysis.json"))
    memplan._ANALYSIS_CACHE.clear()


def test_validate_rejects_inconsistent_plan():
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane={})
        tr.event(
            "memory_plan", engine="X",
            resident=[dict(name="a", shape=[2, 4], dtype="uint32",
                           bytes=32)],
            resident_bytes=999,  # != 32
            classes=[], compiled=None, total_bytes=999,
        )
        tr.end_run()
    with pytest.raises(ValueError, match="resident_bytes"):
        validate_events(tr.events)


# -- the engine_mode satellite (CHUNKED memory-lean flip) ----------------


def test_engine_mode_event_fires_on_forced_chunked_flip():
    # Force the flip: a tiny flat budget makes every compaction class
    # exceed Ba * row_pad, so the sparse wave runs memory-lean.
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256, cand_capacity=512,
        flat_budget_bytes=1 << 12, track_paths=False,
    )
    assert c.unique_state_count() == 288  # the flip changes memory,
    # not exploration
    validate_events(tracer.events)
    modes = [e for e in tracer.events if e["ev"] == "engine_mode"]
    assert modes, "the CHUNKED flip must be observable as an event"
    m = modes[0]
    assert m["mode"] == "chunked"
    assert m["engine"] == "SortMergeTpuBfsChecker"
    assert m["chunks"] >= 1 and m["chunk_rows"] >= 1
    assert m["flat_budget_bytes"] == 1 << 12
    # the plan's class ledger agrees with the event
    plan = next(e for e in tracer.events if e["ev"] == "memory_plan")
    assert any(cl["mode"] == "chunked" for cl in plan["classes"])
    # ... and the default-budget run does NOT flip
    tracer2, _ = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256, cand_capacity=512,
        track_paths=False,
    )
    assert not [e for e in tracer2.events
                if e["ev"] == "engine_mode"]


# -- occupancy warnings with byte figures --------------------------------


def test_occupancy_warning_includes_bytes():
    from stateright_tpu.occupancy import occupancy_warning

    msg = occupancy_warning(
        0.9, used=900, capacity=1000, bytes_per_row=8,
    )
    assert msg is not None
    assert "(900/1000)" in msg
    # rendered by the ONE repo-wide byte formatter (memplan)
    assert "[7.03 KB of 7.81 KB]" in msg
    assert memplan.format_bytes(900 * 8) == "7.03 KB"
    # without the ledger's per-row cost the line stays as before
    msg2 = occupancy_warning(0.9, used=900, capacity=1000)
    assert "[" not in msg2
    # under threshold: silent either way
    assert occupancy_warning(0.5, bytes_per_row=8) is None


def test_hash_engine_probe_warning_prices_bytes():
    c = _twopc_builder().spawn_tpu(
        capacity=1 << 9, frontier_capacity=256, track_paths=False,
    ).join()
    assert c.unique_state_count() == 288
    with pytest.warns(RuntimeWarning, match=r"\[.*KB of .*KB\]"):
        c._maybe_warn_occupancy(0.9)


def test_shard_balance_warnings_price_bytes():
    # Synthetic mesh trace: one shard's visited array near capacity;
    # the lane carries the ledger's per-row costs.
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane=dict(
            engine="ShardedSortMergeTpuBfsChecker", capacity=100,
            visited_exact=True, dest_tile_lanes=10,
            visited_row_bytes=8,
        ))
        tr.record_chunk(
            chunk=0, wave0=0, t0=0.0, t1=1.0,
            dispatch_sec=0.5, fetch_sec=0.5,
            wave_rows=[[20, 10, 10, 10, 110, 1, 0, 0]],
            pairs_valid=False,
            shard_rows=[
                [[10, 5, 5, 2, 3, 9, 10, 5, 95]],
                [[10, 5, 5, 2, 3, 9, 10, 5, 15]],
            ],
        )
        tr.end_run()
    bal = telemetry.shard_balance(tr.events)
    assert bal is not None
    vis_warns = [w for w in bal["warnings"] if "visited array" in w]
    assert vis_warns, bal["warnings"]
    # 95 rows x 8 B of 100 x 8 B
    assert "[760 B of 800 B]" in vis_warns[0]
    tile_warns = [w for w in bal["warnings"] if "dest tile" in w]
    assert tile_warns and "[360 B of 400 B]" in tile_warns[0]


# -- mem_report CLI -------------------------------------------------------


def _write_toy_trace(tmp_path, name="mem.jsonl"):
    tracer, c = _traced_checker(
        lambda **kw: _twopc_builder().spawn_tpu_sortmerge(**kw),
        capacity=1 << 10, frontier_capacity=256,
        cand_capacity=1024, track_paths=False,
    )
    path = str(tmp_path / name)
    tracer.write_jsonl(path)
    return path


def _run_tool(tool, *args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", tool),
         *args],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_mem_report_renders_and_writes_artifact(tmp_path):
    trace = _write_toy_trace(tmp_path)
    r = _run_tool("mem_report.py", trace)
    assert r.returncode == 0, r.stderr
    assert "resident-buffer ledger" in r.stdout
    assert "vkeys" in r.stdout
    assert "run peak:" in r.stdout
    assert "projection (next v-class)" in r.stdout
    # --json: MEM numbers in its OWN sequence through artifacts.py
    out = str(tmp_path / "artifacts")
    os.makedirs(out)
    r1 = _run_tool("mem_report.py", trace, "--json", "--root", out)
    assert r1.returncode == 0, r1.stderr
    assert os.path.exists(os.path.join(out, "MEM_r01.json"))
    r2 = _run_tool("mem_report.py", trace, "--json", "--root", out)
    assert r2.returncode == 0
    assert os.path.exists(os.path.join(out, "MEM_r02.json"))
    with open(os.path.join(out, "MEM_r01.json")) as fh:
        doc = json.load(fh)
    assert doc["trace"] == os.path.basename(trace)
    assert doc["plan"]["resident_bytes"] > 0
    assert doc["provenance"]["backend"] == "cpu"


def test_mem_report_exit_2_without_memory_events(tmp_path):
    # a committed pre-round-12 trace has waves but no memory events
    r = _run_tool(
        "mem_report.py", os.path.join(REPO_ROOT, "TRACE_r07.jsonl")
    )
    assert r.returncode == 2
    assert "no memory events" in r.stderr
    assert memory_summary(
        load_trace(os.path.join(REPO_ROOT, "TRACE_r07.jsonl"))
    ) is None
    # bad input: exit 2 as well
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json\n")
    r2 = _run_tool("mem_report.py", str(bad))
    assert r2.returncode == 2
    # unknown run index
    trace = _write_toy_trace(tmp_path)
    r3 = _run_tool("mem_report.py", trace, "--run", "7")
    assert r3.returncode == 2


# -- trace_diff memory alignment -----------------------------------------


def _synthetic_mem_events(peak=10 << 20, shape=(2, 1280),
                          temp=5 << 20):
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane=dict(engine="X"))
        tr.event(
            "memory_plan", engine="X",
            resident=[dict(name="vkeys", shape=list(shape),
                           dtype="uint32",
                           bytes=int(np.prod(shape)) * 4)],
            resident_bytes=int(np.prod(shape)) * 4,
            classes=[dict(f_class=0, mode="sparse",
                          staging_bytes=64)],
            compiled=dict(temp_size_in_bytes=temp),
            total_bytes=int(np.prod(shape)) * 4 + 64,
        )
        tr.event(
            "memory_watermark", source="live_arrays",
            device_peak_bytes=peak, polls=3,
            headroom={}, projection={},
        )
        tr.end_run()
    return tr.events


def test_trace_diff_plan_shapes_exact():
    a = _synthetic_mem_events()
    # identical → clean
    rep = diff_traces(a, _synthetic_mem_events())
    assert rep["ok"] and not rep["memory"]["divergences"]
    # a changed resident shape is a DIVERGENCE, not a threshold miss
    b = _synthetic_mem_events(shape=(2, 2560))
    rep2 = diff_traces(a, b, threshold=100.0)
    assert not rep2["ok"]
    assert any(d["field"] == "memory_plan"
               for d in rep2["memory"]["divergences"])
    assert "memory-plan divergence" in format_diff(rep2).lower()
    # a changed CLASS names the class and the field that moved
    # (bare equal-length counts would be unactionable)
    c = _synthetic_mem_events()
    plan_c = next(e for e in c if e["ev"] == "memory_plan")
    plan_c["classes"][0]["staging_bytes"] = 999
    rep3 = diff_traces(a, c)
    assert not rep3["ok"]
    cls = [d for d in rep3["memory"]["divergences"]
           if d["field"] == "memory_plan_classes"]
    assert cls and cls[0]["name"] == "class 0.staging_bytes"
    assert cls[0]["a"] == 64 and cls[0]["b"] == 999


def test_trace_diff_skips_memory_against_pre_round12_baseline():
    """A side with no memory events (a committed pre-round-12 trace)
    is not comparable on the memory axis — the diff must SKIP it,
    not fail the gate (chip A/Bs run against old baselines)."""
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane={})
        tr.end_run()
    old = tr.events  # no memory events at all
    new = _synthetic_mem_events()
    for a, b in ((old, new), (new, old)):
        rep = diff_traces(a, b)
        assert not rep["memory"]["divergences"]
        assert not rep["memory"]["regressions"]
        assert rep["ok"]


def test_trace_diff_measured_bytes_under_threshold():
    a = _synthetic_mem_events(peak=10 << 20, temp=10 << 20)
    # +5% live peak and temp: inside the default 10% bar
    b = _synthetic_mem_events(peak=int(10.5 * (1 << 20)),
                              temp=int(10.5 * (1 << 20)))
    rep = diff_traces(a, b)
    assert rep["ok"], rep["memory"]
    assert rep["memory"]["bytes"]["device_peak_bytes"]["rel"] == 0.05
    # +50%: past the bar on both measured lanes
    c = _synthetic_mem_events(peak=15 << 20, temp=15 << 20)
    rep2 = diff_traces(a, c)
    assert not rep2["ok"]
    assert set(rep2["memory"]["regressions"]) == {
        "device_peak_bytes", "compiled_temp_bytes"
    }
    assert "REGRESSION" in format_diff(rep2)
    # tiny absolute sizes never regress (the byte noise floor)
    small_a = _synthetic_mem_events(peak=1000, temp=1000)
    small_b = _synthetic_mem_events(peak=2000, temp=2000)
    assert diff_traces(small_a, small_b)["ok"]


def test_trace_diff_cli_memory_divergence_exit_1(tmp_path):
    a_path = tmp_path / "a.jsonl"
    b_path = tmp_path / "b.jsonl"
    with open(a_path, "w") as fh:
        for ev in _synthetic_mem_events():
            fh.write(json.dumps(ev) + "\n")
    with open(b_path, "w") as fh:
        for ev in _synthetic_mem_events(shape=(2, 2560)):
            fh.write(json.dumps(ev) + "\n")
    r = _run_tool("trace_diff.py", str(a_path), str(b_path))
    assert r.returncode == 1
    assert "MEMORY-PLAN DIVERGENCE" in r.stdout
    r2 = _run_tool("trace_diff.py", str(a_path), str(a_path))
    assert r2.returncode == 0


def test_real_traced_ab_diffs_clean(tmp_path):
    """Two traced runs of one workload (cold + warm in one tracer —
    the bench shape) diff to zero divergence INCLUDING the memory
    counters; the timing threshold is loose (walls differ run to
    run), the memory comparison is what this pins."""
    tracer = RunTracer()
    with tracer.activate():
        for _ in range(2):
            c = _twopc_builder().spawn_tpu_sortmerge(
                capacity=1 << 10, frontier_capacity=256,
                cand_capacity=1024, track_paths=False,
            )
            c.join()
            assert c.unique_state_count() == 288
    path = str(tmp_path / "ab.jsonl")
    tracer.write_jsonl(path)
    evs = load_trace(path)
    validate_events(evs)
    rep = diff_traces(evs, evs, run_a=0, run_b=1, threshold=1e9)
    assert not rep["divergences"]
    assert not rep["memory"]["divergences"]
    assert not rep["memory"]["regressions"]
    assert rep["ok"]


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_root_follows_env(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR``, when set, is the compile cache's
    directory and the root of the budget and memory-analysis stores;
    unset, all three live in the checkout's fixed ``.stpu_cache``. A
    fresh process, because JAX reads the variable at import."""
    code = (
        "import jax\n"
        "from stateright_tpu import memplan\n"
        "from stateright_tpu.checkers.tpu import _enable_persistent_cache\n"
        "_enable_persistent_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(memplan._analysis_store())\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO_ROOT, ".stpu_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO_ROOT,
        capture_output=True, text=True, check=True,
    ).stdout.split()
    assert out[0] == want
    assert os.path.dirname(out[1]) == want
