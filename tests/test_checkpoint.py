"""Checkpoint/resume + fault-injection gate (``ckpt`` marker).

The durability contract (stateright_tpu/checkpoint.py +
faultinject.py): kill-and-resume COUNT PARITY — paxos 2c/3s killed at
every chunk boundary (and once mid-chunk via an injected fault under
supervision) resumes to the exact pinned 16,668; 2pc rm=7 kill/resume
reproduces the pinned 296,448; the 2pc rm=4 virtual mesh killed at
every boundary resumes both same-shard and through the 2→4
(owner, fp) re-shard to the host oracle's 1,568 — plus the
refuse-loudly cells (torn snapshot, stale manifest, incompatible
target), the supervised-retry/overflow boundary, the hardened
auto-budget store, the hybrid racer's clean loser cancellation on
resume, and the resumed-trace report/diff degradations.
"""

import json
import os
import subprocess
import sys
import threading
import time
import warnings

import pytest

from stateright_tpu import faultinject
from stateright_tpu.checkers.hybrid import HybridChecker
from stateright_tpu.checkpoint import (
    SnapshotCorruptError,
    SnapshotIncompatibleError,
    SnapshotStaleError,
    load_snapshot,
)
from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
from stateright_tpu.models.two_phase_commit import TwoPhaseSys

pytestmark = pytest.mark.ckpt

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.disarm_all()


def _twopc3(**kw):
    return TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
        capacity=1 << 10, frontier_capacity=128, cand_capacity=512,
        waves_per_sync=2, **kw,
    )


def _paxos2(**kw):
    return (
        paxos_model(PaxosModelCfg(client_count=2, server_count=3))
        .checker()
        .spawn_tpu_sortmerge(
            capacity=1 << 15, frontier_capacity=1 << 12,
            cand_capacity=1 << 14, waves_per_sync=8, **kw,
        )
    )


def _mesh2pc4(n_shards, **kw):
    kw.setdefault("cand_capacity", 4096)
    kw.setdefault("bucket_capacity", 2048)
    return TwoPhaseSys(rm_count=4).checker().spawn_tpu_sharded_sortmerge(
        n_shards=n_shards, capacity=1 << 11, frontier_capacity=256,
        waves_per_sync=4, **kw,
    )


def _kill_at(spawn, snap, chunk, **kw):
    """Run ``spawn(...)`` with per-chunk checkpointing and an injected
    chunk-boundary fault (retries off so the raise escapes): the
    in-process model of a kill — the run dies at the boundary, the
    snapshot written just before survives."""
    c = spawn(checkpoint_every=1, checkpoint_path=snap, **kw)
    c.max_fault_retries = 0
    faultinject.arm("raise", "chunk_boundary", chunk)
    with pytest.raises(faultinject.InjectedFault):
        c.join()
    faultinject.disarm_all()
    assert os.path.exists(snap)
    return c


# -- snapshot format ------------------------------------------------------


def test_snapshot_manifest_and_checksums(tmp_path):
    snap = str(tmp_path / "t.ckpt")
    _kill_at(_twopc3, snap, 1)
    manifest, buffers = load_snapshot(snap)
    assert manifest["version"] == 1
    assert manifest["family"] == "sortmerge"
    assert manifest["kind"] == "single"
    assert manifest["n_shards"] == 1
    assert manifest["track_paths"] is True
    assert manifest["wave"] > 0 and manifest["unique"] > 0
    # the declared buffer set IS the chunk carry the memory ledger
    # names: visited keys, frontier, ebits, parent log, counters,
    # cumulative discovery lanes
    for leaf in ("vkeys", "plog", "pl_n", "frontier", "fval",
                 "ebits", "n_frontier", "depth", "waves", "gen_lo",
                 "gen_hi", "new", "disc_found", "disc_lo",
                 "disc_hi"):
        assert leaf in buffers, leaf
        assert leaf in manifest["buffers"]
    assert manifest["snapshot_bytes"] == sum(
        b.nbytes for b in buffers.values()
    )
    # auto-budget state rides the manifest (the resume-side budget)
    assert "cand_capacity" in manifest["budget"]


# -- kill-and-resume count parity (pinned counts) -------------------------


def test_paxos_2c3s_killed_at_every_chunk_boundary(tmp_path):
    """paxos 2c/3s killed at EVERY chunk boundary resumes to the
    exact pinned 16,668 with the host discovery set and a replayable
    path (the parent log survives the snapshot)."""
    baseline = _paxos2().join()
    assert baseline.unique_state_count() == 16668
    n_chunks = baseline.latency_accounting()["chunks"]
    assert n_chunks >= 2  # several boundaries to kill at
    for k in range(n_chunks):
        snap = str(tmp_path / f"px_{k}.ckpt")
        _kill_at(_paxos2, snap, k)
        r = _paxos2()
        r.resume_from(snap)
        r.join()
        assert r.unique_state_count() == 16668, f"boundary {k}"
        assert sorted(r.discoveries()) == ["value chosen"], k
        path = r.discovery("value chosen")
        prop = r.model.property_by_name("value chosen")
        assert prop.condition(r.model, path.last_state())


def test_paxos_midchunk_fault_supervised_recovery(tmp_path):
    """A mid-chunk device fault under supervision self-recovers from
    the last snapshot in ONE join — bounded backoff, identical final
    count — instead of dying."""
    snap = str(tmp_path / "px_mid.ckpt")
    c = _paxos2(checkpoint_every=1, checkpoint_path=snap)
    c.retry_backoff_sec = 0.01
    faultinject.arm("raise", "mid_chunk", 1)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c.join()
    assert c.unique_state_count() == 16668
    assert any("supervised recovery" in str(x.message) for x in w)


def test_2pc_rm7_kill_resume_296448(tmp_path):
    """The largest CPU-feasible lane: 2pc rm=7 killed at a chunk
    boundary and resumed reproduces the pinned 296,448 exactly."""
    def spawn(**kw):
        return TwoPhaseSys(rm_count=7).checker().spawn_tpu_sortmerge(
            capacity=1 << 19, frontier_capacity=1 << 16,
            cand_capacity=1 << 19, track_paths=False,
            waves_per_sync=4, **kw,
        )

    snap = str(tmp_path / "rm7.ckpt")
    _kill_at(spawn, snap, 2)
    r = spawn()
    r.resume_from(snap)
    r.join()
    assert r.unique_state_count() == 296448
    r.assert_properties()


@pytest.fixture(scope="module")
def host_2pc4():
    return TwoPhaseSys(rm_count=4).checker().spawn_bfs().join()


def test_mesh_2pc4_every_boundary_same_shard_and_2_to_4(
        tmp_path, host_2pc4):
    """The elastic re-shard proof at tier-1 scale: 2pc rm=4 on the
    virtual S=2 mesh killed at every chunk boundary resumes to the
    host oracle's exact count — SAME-shard by direct upload, and at
    S=4 through the (owner, fp) re-route. Shard count is a
    resume-time choice."""
    expected = host_2pc4.unique_state_count()
    baseline = _mesh2pc4(2).join()
    assert baseline.unique_state_count() == expected
    n_chunks = baseline.latency_accounting()["chunks"]
    assert n_chunks >= 2
    for k in range(n_chunks):
        snap = str(tmp_path / f"mesh_{k}.ckpt")
        _kill_at(lambda **kw: _mesh2pc4(2, **kw), snap, k)
        # same-shard direct upload
        same = _mesh2pc4(2)
        same.resume_from(snap)
        same.join()
        assert same.unique_state_count() == expected, f"S=2 at {k}"
        # 2 -> 4 elastic re-shard
        re4 = _mesh2pc4(4)
        manifest = re4.resume_from(snap)
        assert manifest["n_shards"] == 2
        re4.join()
        assert re4.unique_state_count() == expected, f"S=4 at {k}"
        assert sorted(re4.discoveries()) == sorted(
            host_2pc4.discoveries()
        )
    # discovery paths replay through the host model after a re-shard
    for name, path in re4.discoveries().items():
        prop = re4.model.property_by_name(name)
        assert prop.condition(re4.model, path.last_state())


# -- refuse-loudly cells --------------------------------------------------


@pytest.fixture()
def twopc3_snapshot(tmp_path):
    snap = str(tmp_path / "cell.ckpt")
    _kill_at(_twopc3, snap, 1)
    return snap


def test_torn_snapshot_refused(tmp_path, twopc3_snapshot):
    import shutil

    for mode in ("truncate", "flip"):
        bad = str(tmp_path / f"bad_{mode}.ckpt")
        shutil.copy(twopc3_snapshot, bad)
        faultinject.corrupt_snapshot(bad, mode)
        with pytest.raises(SnapshotCorruptError):
            _twopc3().resume_from(bad)


def test_stale_manifest_refused(tmp_path, twopc3_snapshot):
    import shutil

    for field in ("git_sha", "encoding"):
        bad = str(tmp_path / f"stale_{field}.ckpt")
        shutil.copy(twopc3_snapshot, bad)
        faultinject.stale_manifest(bad, field)
        with pytest.raises(SnapshotStaleError):
            _twopc3().resume_from(bad)
    # a DIFFERENT model's checker is stale by encoding fingerprint
    with pytest.raises(SnapshotStaleError):
        _paxos2().resume_from(twopc3_snapshot)


def test_incompatible_targets_refused(twopc3_snapshot):
    # cross-family: the hash engine can't interpret a sorted prefix
    with pytest.raises(SnapshotIncompatibleError):
        TwoPhaseSys(rm_count=3).checker().spawn_tpu(
            capacity=1 << 10, frontier_capacity=128, waves_per_sync=2,
        ).resume_from(twopc3_snapshot)
    # track_paths flip: the parent log exists on one side only
    with pytest.raises(SnapshotIncompatibleError):
        _twopc3(track_paths=False).resume_from(twopc3_snapshot)
    # a re-shard target too small for the carried state refuses
    # loudly BEFORE any device work
    tiny = TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
        capacity=64, frontier_capacity=32, cand_capacity=128,
        waves_per_sync=2,
    )
    with pytest.raises(SnapshotIncompatibleError, match="capacity"):
        tiny.resume_from(twopc3_snapshot)


def test_hash_reshard_directions(tmp_path):
    """The degrade-and-continue round lifted PR 11's refuse-by-name
    for the sharded-hash -> sharded-hash case: the per-shard tables
    rebuild host-side by re-insertion through the (owner, fp) route.
    Single-chip ⇄ sharded hash keeps refusing — with a message that
    names the supported direction."""
    snap = str(tmp_path / "hash.ckpt")

    def spawn(n, **kw):
        return TwoPhaseSys(rm_count=3).checker().spawn_tpu_sharded(
            n_shards=n, capacity=1 << 10, frontier_capacity=128,
            cand_capacity=512, bucket_capacity=256, waves_per_sync=2,
            **kw,
        )

    _kill_at(lambda **kw: spawn(2, **kw), snap, 1)
    # same-config hash resume works (direct upload)...
    r = spawn(2)
    r.resume_from(snap)
    r.join()
    assert r.unique_state_count() == 288
    # ...and the sharded -> sharded re-shard now works too: 2 -> 4
    # by host-side re-insertion, exact count + discoveries
    re4 = spawn(4)
    manifest = re4.resume_from(snap)
    assert manifest["n_shards"] == 2
    re4.join()
    assert re4.unique_state_count() == 288
    assert sorted(re4.discoveries()) == sorted(r.discoveries())
    # single-chip ⇄ sharded hash keeps refusing BY NAME, and the
    # message says which direction IS supported
    single = TwoPhaseSys(rm_count=3).checker().spawn_tpu(
        capacity=1 << 10, frontier_capacity=128, waves_per_sync=2,
    )
    with pytest.raises(SnapshotIncompatibleError,
                       match="sharded-hash -> sharded-hash"):
        single.resume_from(snap)


def test_engine_overflow_is_not_supervised(tmp_path):
    """Engine overflow errors (plain RuntimeErrors with sizing
    advice) raise straight through the supervisor — the auto-budget
    retry owns those, and retrying them from a snapshot would loop."""
    c = TwoPhaseSys(rm_count=4).checker().spawn_tpu_sortmerge(
        capacity=1 << 11, frontier_capacity=256, cand_capacity=64,
        waves_per_sync=2, checkpoint_every=1,
        checkpoint_path=str(tmp_path / "ovf.ckpt"),
    )
    c.retry_backoff_sec = 0.01
    with pytest.raises(RuntimeError, match="overflow"):
        c.join()


# -- satellite: hardened auto-budget store --------------------------------


def test_corrupt_budget_store_falls_back_with_warning(
        tmp_path, monkeypatch):
    """A truncated/corrupt budget store (crash mid-write from a
    pre-atomic version, disk truncation) must fall back to defaults
    with a one-line warning instead of raising at engine start."""
    from stateright_tpu.checkers.tpu_sortmerge import (
        SortMergeTpuBfsChecker,
    )

    store = str(tmp_path / "budgets.json")
    with open(store, "w") as fh:
        fh.write('{"some/key": {"cand_capacity": 123')  # torn JSON
    monkeypatch.setattr(
        SortMergeTpuBfsChecker, "_budget_store", lambda self: store
    )
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c = TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
            capacity=1 << 10, frontier_capacity=128,
            cand_capacity="auto", waves_per_sync=2,
        )
    assert any("auto-budget store" in str(x.message)
               and "corrupt" in str(x.message) for x in w)
    assert c.cand_capacity  # the growth heuristic filled in
    c.join()
    assert c.unique_state_count() == 288
    # the clean run rewrote the store atomically: it parses again
    with open(store) as fh:
        assert json.load(fh)


# -- satellite: hybrid racer's loser cancelled cleanly on resume ----------


class _SlowHostTwoPhase(TwoPhaseSys):
    """Host enumeration held until a side has claimed the race, so the
    device side wins at any CPU load. The device side runs in the
    race's calling thread and calls actions() only to replay paths;
    the host DFS runs on a thread of its own, the one held here."""

    race_decided = None  # a threading.Event, set by _RaceHybrid
    device_thread = None

    def actions(self, state):
        if threading.current_thread() is not self.device_thread:
            self.race_decided.wait(60)  # bounded: a broken race fails
        return super().actions(state)


class _RaceHybrid(HybridChecker):
    """The hybrid racer, releasing the held host when a side claims."""

    @property
    def winner(self):
        return self.__dict__.get("_winner")

    @winner.setter
    def winner(self, name):
        self.__dict__["_winner"] = name
        if name is not None:
            self.model.race_decided.set()


def test_hybrid_resume_cancels_loser_cleanly(tmp_path):
    """A resumed hybrid race must not leave a stale host thread
    emitting events into the new trace run: the loser is cancelled
    AND joined on every exit path, its run stays CANCELLED (no
    exhaustion verdicts — the PR-10 pin), and no thread outlives
    join()."""
    from stateright_tpu.telemetry import RunTracer, validate_events

    snap = str(tmp_path / "hy.ckpt")
    _kill_at(_twopc3, snap, 1)

    dev_kw = dict(capacity=1 << 10, frontier_capacity=128,
                  cand_capacity=512, waves_per_sync=2)
    before = threading.active_count()
    tracer = RunTracer()
    with tracer.activate():
        model = _SlowHostTwoPhase(rm_count=3)
        model.race_decided = threading.Event()
        model.device_thread = threading.current_thread()
        hy = _RaceHybrid(model.checker(), **dev_kw)
        hy.resume_from(snap)
        hy.join()
    assert hy.winner == "device"
    assert hy.unique_state_count() == 288
    assert threading.active_count() == before  # loser joined
    validate_events(tracer.events)
    # the device run restored from the snapshot
    assert any(e["ev"] == "restore" for e in tracer.events)
    # the host loser's run emitted NO exhaustion verdicts (a
    # cancelled partial search settled nothing) and NO events after
    # the tracer deactivated (the thread is gone, not stale)
    host_runs = {
        e["run"] for e in tracer.events
        if e["ev"] == "run_begin"
        and e["lane"].get("engine") == "DfsChecker"
    }
    assert host_runs  # the race really ran a host side
    assert not [
        e for e in tracer.events
        if e["ev"] == "verdict" and e["run"] in host_runs
    ]
    n_events = len(tracer.events)
    time.sleep(0.05)
    assert len(tracer.events) == n_events


# -- satellite: resumed traces through diff + reports ---------------------


def _traced(fn):
    from stateright_tpu.telemetry import RunTracer

    tr = RunTracer()
    with tr.activate():
        c = fn()
    return tr, c


def test_resumed_trace_diff_and_reports(tmp_path):
    """End-to-end on a traced kill/resume pair: validate_events
    accepts the new event types, trace_diff aligns the resumed wave
    stream with the uninterrupted baseline at ZERO counter
    divergence, and mem_report/latency_report render a wave>0 run
    without crashing or misattributing time-to-first-wave."""
    from stateright_tpu.telemetry import (
        diff_traces,
        latency_summary,
        validate_events,
    )

    tr_base, b = _traced(lambda: _twopc3().join())
    assert b.unique_state_count() == 288
    validate_events(tr_base.events)

    snap = str(tmp_path / "tr.ckpt")
    _kill_at(_twopc3, snap, 1)

    def resumed():
        c = _twopc3()
        c.resume_from(snap)
        return c.join()

    tr_res, r = _traced(resumed)
    assert r.unique_state_count() == 288
    validate_events(tr_res.events)
    assert any(e["ev"] == "restore" for e in tr_res.events)

    rep = diff_traces(tr_base.events, tr_res.events)
    assert rep["resume_wave_b"] is not None
    assert not rep["divergences"], rep["divergences"]
    assert rep["ok"]
    # a resumed run missing waves AFTER its resume point still fails
    truncated = [
        e for e in tr_res.events
        if not (e["ev"] == "wave"
                and e["wave"] == max(
                    w["wave"] for w in tr_res.events
                    if w["ev"] == "wave"
                ))
    ]
    rep2 = diff_traces(tr_base.events, truncated)
    assert any(d["field"] == "present" for d in rep2["divergences"])

    lat = latency_summary(tr_res.events)
    assert lat["profile"]["resumed_from_wave"] == \
        rep["resume_wave_b"]
    assert lat["profile"]["time_to_first_wave_sec"] >= 0

    # the report CLIs on the resumed trace: exit 0, no crash
    trace = str(tmp_path / "resumed.jsonl")
    tr_res.write_jsonl(trace)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for tool, needle in (
        ("latency_report.py", "RESUMED from wave"),
        ("mem_report.py", "resident-buffer ledger"),
    ):
        p = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "tools", tool), trace],
            capture_output=True, text=True, env=env,
        )
        assert p.returncode == 0, (tool, p.stderr)
        assert needle in p.stdout, (tool, p.stdout)


def test_checkpoint_events_schema(tmp_path):
    """Traced checkpointed runs land schema-valid ``checkpoint`` /
    ``fault_injected`` / ``fault_recovery`` events."""
    from stateright_tpu.telemetry import validate_events

    snap = str(tmp_path / "ev.ckpt")

    def run():
        c = _twopc3(checkpoint_every=1, checkpoint_path=snap)
        c.retry_backoff_sec = 0.01
        faultinject.arm("raise", "mid_chunk", 1)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return c.join()

    tr, c = _traced(run)
    assert c.unique_state_count() == 288
    validate_events(tr.events)
    kinds = {e["ev"] for e in tr.events}
    assert {"checkpoint", "fault_injected",
            "fault_recovery"} <= kinds
    ck = next(e for e in tr.events if e["ev"] == "checkpoint")
    assert ck["snapshot_bytes"] > 0 and ck["wave"] > 0


# -- the real process-kill cell (subprocess; crash_matrix's territory) ----


@pytest.mark.slow
def test_subprocess_kill_and_resume_cli(tmp_path):
    """The real thing: a CLI check lane killed by ``os._exit`` at a
    chunk boundary (STPU_FAULTS), resumed by a second process to the
    exact count — the crash matrix's kill cell, pinned here too."""
    snap = str(tmp_path / "cli.ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               STPU_FAULTS="kill@chunk_boundary:1")
    args = [sys.executable, "-m", "stateright_tpu", "2pc",
            "check-tpu", "3", "--waves-per-sync=2",
            "--checkpoint-every=1", f"--checkpoint-path={snap}"]
    p = subprocess.run(args, capture_output=True, text=True,
                       env=env, cwd=REPO_ROOT)
    assert p.returncode == faultinject.KILL_EXIT_CODE, p.stderr
    assert os.path.exists(snap)
    env.pop("STPU_FAULTS")
    p2 = subprocess.run(
        [sys.executable, "-m", "stateright_tpu", "2pc", "check-tpu",
         "3", "--waves-per-sync=2", "--resume",
         f"--checkpoint-path={snap}"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT,
    )
    assert p2.returncode == 0, p2.stderr
    assert "resuming from" in p2.stdout
    assert "unique=288" in p2.stdout
