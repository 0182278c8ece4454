"""Degrade-and-continue gate (``fault`` marker).

The robustness policy layer (stateright_tpu/checkpoint.py
FailurePolicy + the hung-dispatch watchdog in checkers/tpu.py + the
shard-health straggler detector in telemetry.py): the classification
table, watchdog deadline derivation (rolling-max clamp + the
cold-compile first-chunk grace), and straggler-factor edge cases are
pinned as pure-host policy math; the engine cells pin the behaviors —
a PERSISTENT per-shard fault automatically degrades an S=2 mesh to
S=1 and completes to the exact host-oracle count with degrade-aware
trace_diff zero divergence, an injected dispatch hang is detected by
the watchdog within its derived deadline and either recovers from the
snapshot or refuses loudly with the attribution, a collective-seam
raise recovers like any chunk fault, the tiered frontier-headroom
bound pre-checks BEFORE device work (warn/bump/refuse), and a ^C
during the supervised backoff closes the trace run bracket instead of
dying mid-sleep.
"""

import os
import warnings

import numpy as np
import pytest

from stateright_tpu import faultinject
from stateright_tpu.checkpoint import (
    FailurePolicy,
    WatchdogTimeout,
    classify_failure,
    watchdog_deadline,
)
from stateright_tpu.faultinject import InjectedFault, InjectedShardFault
from stateright_tpu.models.two_phase_commit import TwoPhaseSys
from stateright_tpu.telemetry import (
    SHARD_LOG_FIELDS,
    RunTracer,
    detect_stragglers,
    diff_traces,
    validate_events,
)

pytestmark = pytest.mark.fault

HOST_2PC4 = 1568  # host-oracle count, pinned in the ckpt gate too


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.disarm_all()


def _twopc3(**kw):
    return TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
        capacity=1 << 10, frontier_capacity=128, cand_capacity=512,
        waves_per_sync=2, **kw,
    )


def _mesh2pc4(n_shards, **kw):
    # generous PER-SHARD budgets: the degrade cells land the whole
    # space on one surviving shard, which must hold every row
    kw.setdefault("cand_capacity", 4096)
    kw.setdefault("bucket_capacity", 2048)
    return TwoPhaseSys(rm_count=4).checker().spawn_tpu_sharded_sortmerge(
        n_shards=n_shards, capacity=1 << 12,
        frontier_capacity=1024, waves_per_sync=2, **kw,
    )


# -- policy math: the classification table (pure host) --------------------


def test_classification_table():
    assert classify_failure(WatchdogTimeout(3, 5.0)) == ("hang", None)
    assert classify_failure(MemoryError()) == ("oom", None)
    assert classify_failure(
        RuntimeError("RESOURCE_EXHAUSTED: hbm")
    ) == ("oom", None)
    assert classify_failure(
        InjectedShardFault("mid_chunk", 2, 5)
    ) == ("shard_fault", 5)
    assert classify_failure(
        InjectedFault("mid_chunk", 2)
    ) == ("transient", None)
    # exactly ONE sustained straggler attributes a transient fault;
    # an ambiguous signal attributes nothing
    assert classify_failure(
        InjectedFault("mid_chunk", 2), straggler_shards=(3,)
    ) == ("transient", 3)
    assert classify_failure(
        InjectedFault("mid_chunk", 2), straggler_shards=(1, 3)
    ) == ("transient", None)
    assert classify_failure(ValueError("no")) == ("unsupervised", None)


def test_policy_escalation_and_reset():
    p = FailurePolicy(persist_threshold=2)
    assert p.classify(
        InjectedShardFault("mid_chunk", 1, 1)
    ) == ("shard_fault", 1)
    assert p.should_degrade() is None  # one strike is not persistent
    p.classify(InjectedShardFault("mid_chunk", 2, 1))
    assert p.should_degrade() == 1  # same shard twice: persistent
    p.degraded(1)
    assert p.should_degrade() is None  # strikes left with the shard
    # unattributed failures never escalate
    p.classify(InjectedFault("mid_chunk", 3))
    p.classify(InjectedFault("mid_chunk", 4))
    assert p.should_degrade() is None
    with pytest.raises(ValueError):
        FailurePolicy(persist_threshold=0)


# -- policy math: watchdog deadline derivation ----------------------------


def test_watchdog_deadline_policy():
    # no measured chunk wall yet -> the first-chunk grace: the
    # TRACE_r21 17.9 s persistent-cache disk fetch must never be
    # misclassified as a hang
    assert watchdog_deadline(None, 8.0) == 300.0
    assert watchdog_deadline(None, 8.0) > 17.9
    assert watchdog_deadline(None, 8.0, first_grace_sec=42.0) == 42.0
    # a MEASURED near-zero wall (fully compile-attributed) gets the
    # floor, not the grace — the grace is for unmeasured chunk 0 only
    assert watchdog_deadline(0.0, 8.0) == 2.0
    # k x rolling max, clamped to [floor, cap]
    assert watchdog_deadline(1.0, 8.0) == 8.0
    assert watchdog_deadline(0.01, 8.0) == 2.0
    assert watchdog_deadline(1000.0, 8.0) == 600.0
    assert watchdog_deadline(
        0.01, 8.0, floor_sec=0.25, cap_sec=10.0
    ) == 0.25
    with pytest.raises(ValueError):
        watchdog_deadline(1.0, 0)


# -- policy math: straggler-factor edge cases -----------------------------


def _wave_rows(cands):
    ci = SHARD_LOG_FIELDS.index("candidates")
    r = np.zeros((len(cands), len(SHARD_LOG_FIELDS)), np.int64)
    r[:, ci] = cands
    return r


def test_detect_stragglers_edges():
    with pytest.raises(ValueError):
        detect_stragglers(_wave_rows([10, 10]), 1.0)
    # single shard: no median signal
    assert detect_stragglers(_wave_rows([900]), 4.0) == []
    # balanced mesh: clean
    assert detect_stragglers(_wave_rows([100] * 4), 4.0) == []
    # one heavy shard flags, with the ratio attached
    out = detect_stragglers(_wave_rows([100, 100, 100, 900]), 4.0)
    assert [r["shard"] for r in out] == [3]
    assert out[0]["ratio"] == pytest.approx(9.0)
    # just under the factor: clean
    assert detect_stragglers(
        _wave_rows([100, 100, 100, 399]), 4.0
    ) == []
    # the min-median floor: a near-empty seed wave flags nobody
    assert detect_stragglers(_wave_rows([0, 0, 0, 1]), 4.0) == []


def test_shard_health_events_and_sustained_evidence():
    """_note_shard_health emits schema-valid shard_health events and
    builds the sustained-straggler evidence the classifier reads."""
    tr = RunTracer()
    c = _mesh2pc4(4)  # spawn only: mesh + _shard_ids, no device work
    c.straggler_factor = 4.0
    c.straggler_sustain = 2
    ci = SHARD_LOG_FIELDS.index("candidates")
    srows = np.zeros((4, 3, len(SHARD_LOG_FIELDS)), np.int64)
    srows[:, :, ci] = 100
    srows[3, :, ci] = 900  # shard 3 drags every wave
    with tr.activate():
        tr.begin_run(lane={})
        c._note_shard_health(srows, wave0=5)
        tr.end_run()
    validate_events(tr.events)
    evs = [e for e in tr.events if e["ev"] == "shard_health"]
    assert len(evs) == 3
    assert all(e["shard"] == 3 and e["kind"] == "straggler"
               for e in evs)
    assert evs[0]["wave"] == 5 and evs[-1]["wave"] == 7
    assert evs[-1]["sustained"] == 3
    assert c._sustained_stragglers() == (3,)


# -- fault-spec parsing for the new kinds ---------------------------------


def test_parse_spec_new_kinds():
    f = faultinject.parse_spec("hang@mid_chunk:1:20")
    assert f["action"] == "hang" and f["hang_sec"] == 20.0
    f = faultinject.parse_spec("hang@mid_chunk:1")
    assert f["hang_sec"] == faultinject.DEFAULT_HANG_SEC
    f = faultinject.parse_spec("shard_fault@mid_chunk:2:3")
    assert f["shard"] == 3 and f["once"] is False
    with pytest.raises(ValueError):
        faultinject.parse_spec("raise@mid_chunk:1:9")  # stray arg
    with pytest.raises(ValueError):
        faultinject.parse_spec("hang@bogus_site:1")


# -- engine: watchdog detects the hang, recovers or refuses loudly --------


def test_watchdog_hang_recovers_from_snapshot(tmp_path):
    """An injected dispatch hang (no exception — only the watchdog
    can see it) is detected within the derived deadline and the run
    self-recovers from the last snapshot to the exact count."""
    c = _twopc3(checkpoint_every=1,
                checkpoint_path=str(tmp_path / "wd.ckpt"))
    c.retry_backoff_sec = 0.01
    c.watchdog_factor = 2.0
    c.watchdog_floor_sec = 0.3
    c.watchdog_grace_sec = 15.0
    faultinject.arm("hang", "mid_chunk", 1, hang_sec=6.0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c.join()
    assert c.unique_state_count() == 288
    assert any("hang" in str(x.message)
               and "supervised recovery" in str(x.message)
               for x in w)


def test_watchdog_refuses_loudly_without_snapshot(tmp_path):
    """With nothing to recover from, the breach raises the
    WatchdogTimeout with its latency attribution — refuse loudly,
    never a hang — and the traced run carries the schema-valid
    watchdog_timeout event."""
    tr = RunTracer()
    c = _twopc3()  # no checkpointing: the supervisor can't retry
    c.watchdog_factor = 2.0
    c.watchdog_floor_sec = 0.3
    c.watchdog_grace_sec = 15.0
    faultinject.arm("hang", "mid_chunk", 1, hang_sec=6.0)
    with pytest.raises(WatchdogTimeout) as ei:
        with tr.activate():
            c.join()
    assert ei.value.chunk == 1
    assert ei.value.deadline_sec <= 15.0
    assert ei.value.attribution["latency"]["chunks"] >= 1
    validate_events(tr.events)
    evs = [e for e in tr.events if e["ev"] == "watchdog_timeout"]
    assert evs and evs[0]["chunk"] == 1
    assert evs[0]["deadline_sec"] > 0


# -- engine: persistent shard fault -> automatic elastic degrade ----------


def test_persistent_shard_fault_degrades_and_continues(tmp_path):
    """The tentpole behavior at tier-1 scale: a persistent per-shard
    device fault on the S=2 virtual mesh strikes the same shard
    across retries, the policy classifies it persistent, and the
    supervisor drops the shard and re-shards the last snapshot onto
    the survivor — the degraded run completes to the exact
    host-oracle count, the fault_degrade event lands, and the
    degrade-aware trace_diff reports ZERO global-counter divergence
    vs the uninterrupted baseline."""
    tr_base = RunTracer()
    with tr_base.activate():
        base = _mesh2pc4(2).join()
    assert base.unique_state_count() == HOST_2PC4
    validate_events(tr_base.events)

    c = _mesh2pc4(2, checkpoint_every=1,
                  checkpoint_path=str(tmp_path / "deg.ckpt"))
    c.degrade_on_fault = True
    c.retry_backoff_sec = 0.01
    faultinject.arm("shard_fault", "mid_chunk", 1, shard=1)
    tr = RunTracer()
    with tr.activate():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            c.join()
    assert c.unique_state_count() == HOST_2PC4
    assert c.n_shards == 1 and c._shard_ids == (0,)
    assert any("DEGRADING" in str(x.message) for x in w)
    validate_events(tr.events)
    deg = [e for e in tr.events if e["ev"] == "fault_degrade"]
    assert deg and deg[0]["from_shards"] == 2 \
        and deg[0]["to_shards"] == 1
    assert deg[0]["excluded_shard"] == 1
    assert deg[0]["reason"] == "shard_fault"
    # counterexample paths survive the degrade (parent log re-routed)
    for name, path in c.discoveries().items():
        prop = c.model.property_by_name(name)
        assert prop.condition(c.model, path.last_state())
    # degrade-aware alignment: global counters EXACT, shard lanes
    # compare within each shard-count segment, verdict OK
    rep = diff_traces(tr_base.events, tr.events)
    assert rep["degrades_b"] and not rep["degrades_a"]
    assert not rep["divergences"], rep["divergences"]
    assert rep["ok"]


def test_degrade_needs_opt_in(tmp_path):
    """Without --degrade-on-fault the persistent fault exhausts the
    retry budget and raises through — the PR 11 contract unchanged."""
    c = _mesh2pc4(2, checkpoint_every=1,
                  checkpoint_path=str(tmp_path / "nodeg.ckpt"))
    c.retry_backoff_sec = 0.01
    c.max_fault_retries = 2
    faultinject.arm("shard_fault", "mid_chunk", 1, shard=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(InjectedShardFault):
            c.join()
    assert c.n_shards == 2  # nothing degraded


# -- engine: collective-seam raise recovers like any chunk fault ----------


def test_collective_seam_raise_recovers(tmp_path):
    c = _mesh2pc4(2, checkpoint_every=1,
                  checkpoint_path=str(tmp_path / "coll.ckpt"))
    c.retry_backoff_sec = 0.01
    faultinject.arm("raise", "collective_seam", 1, once=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        c.join()
    assert c.unique_state_count() == HOST_2PC4
    # the site is mesh-only: a single-chip run never reaches it
    faultinject.arm("raise", "collective_seam", 0, once=True)
    s = _twopc3()
    s.join()
    assert s.unique_state_count() == 288
    assert faultinject.armed()  # still armed: the site never fired


# -- tiered frontier-headroom pre-check (BEFORE device work) --------------


def test_tier_headroom_precheck_warn_bump_refuse():
    def spawn(**kw):
        kw.setdefault("frontier_capacity", 128)
        kw.setdefault("cand_capacity", 512)
        return TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
            capacity=1 << 10, waves_per_sync=2, tier_hot_rows=64,
            **kw,
        )

    # default ("warn"): the PR 12 known bound surfaces UP FRONT as a
    # warning naming the knobs, and the run still completes exactly
    c = spawn()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c.join()
    assert c.unique_state_count() == 288
    assert any("frontier-headroom" in str(x.message) for x in w)

    # "refuse": the pinned message, raised BEFORE any device work
    c2 = spawn()
    c2.tier_headroom_policy = "refuse"
    with pytest.raises(ValueError, match="frontier-headroom"):
        c2.join()

    # "bump": frontier_capacity raised to the provable bound (the
    # cand budget) before programs build; counts unchanged
    c3 = spawn()
    c3.tier_headroom_policy = "bump"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c3.join()
    assert c3.frontier_capacity == 512
    assert c3.unique_state_count() == 288
    assert any("bump" in str(x.message) for x in w)

    # a config where the bound provably holds warns nothing
    c4 = spawn(frontier_capacity=512, cand_capacity=512)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        c4.join()
    assert c4.unique_state_count() == 288
    assert not any("frontier-headroom" in str(x.message) for x in w)


def test_degrade_aware_diff_keeps_teeth():
    """The degrade-aware alignment skips shard lanes across a
    re-shard but the GLOBAL counters stay fully enforced: a doctored
    unique_total on the degraded side still fails the gate."""

    def mkrun(degrade, doctor=False):
        evs = [dict(ev="run_begin", run=0, t=0.0, schema=1,
                    level="default", provenance={}, lane={})]
        for w in range(4):
            evs.append(dict(
                ev="wave", run=0, wave=w, chunk=w, t0=0.0, t1=0.1,
                t_est=True, frontier_rows=10, enabled_pairs=None,
                candidates=20, new_states=10,
                unique_total=10 * (w + 1), depth=w + 1,
                f_class=0, v_class=0,
            ))
        if degrade:
            evs.insert(3, dict(ev="fault_degrade", run=0,
                               from_shards=2, to_shards=1,
                               reason="shard_fault", wave=1, t=0.0))
            evs.insert(4, dict(ev="restore", run=0, wave=1, depth=1,
                               from_shards=2, to_shards=1, t=0.0))
        if doctor:
            evs[-1]["unique_total"] += 5
        evs.append(dict(ev="run_end", run=0, t=1.0))
        return evs

    rep = diff_traces(mkrun(False), mkrun(True))
    assert rep["ok"] and not rep["divergences"]
    assert rep["degrades_b"] and not rep["degrades_a"]
    rep2 = diff_traces(mkrun(False), mkrun(True, doctor=True))
    assert not rep2["ok"]
    assert any(d["field"] == "unique_total"
               for d in rep2["divergences"])


def test_degrade_aware_shard_segments():
    """Shard lanes skip ONLY where each side's per-wave shard count
    is exactly what its own degrade history predicts: a shard-row
    loss the history does NOT explain (e.g. at a pre-degrade wave)
    still diverges."""

    def mkrun(degrade, shards_at=None):
        evs = [dict(ev="run_begin", run=0, t=0.0, schema=1,
                    level="default", provenance={},
                    lane=dict(n_shards=2))]
        for w in range(4):
            n_sh = (shards_at or {}).get(
                w, 2 if not degrade or w < 2 else 1
            )
            for s in range(n_sh):
                row = dict(ev="shard_wave", run=0, wave=w, chunk=w,
                           shard=s)
                for f in SHARD_LOG_FIELDS:
                    row[f] = 10
                evs.append(row)
            evs.append(dict(
                ev="wave", run=0, wave=w, chunk=w, t0=0.0, t1=0.1,
                t_est=True, frontier_rows=10, enabled_pairs=None,
                candidates=20, new_states=10,
                unique_total=10 * (w + 1), depth=w + 1,
                f_class=0, v_class=0,
            ))
        if degrade:
            evs.insert(1, dict(ev="fault_degrade", run=0,
                               from_shards=2, to_shards=1,
                               reason="shard_fault", wave=2, t=0.0))
        evs.append(dict(ev="run_end", run=0, t=1.0))
        return evs

    base = mkrun(False)
    # S=2 before the degrade wave, S=1 after: fully explained
    rep = diff_traces(base, mkrun(True))
    assert rep["ok"] and not rep["divergences"]
    # a shard row lost at a PRE-degrade wave is NOT explained
    rep2 = diff_traces(base, mkrun(True, shards_at={1: 1}))
    assert not rep2["ok"]
    assert {d["field"] for d in rep2["divergences"]} >= {
        "shard_count"
    }


# -- interruptible supervised backoff -------------------------------------


def test_backoff_interrupt_closes_trace_bracket(tmp_path,
                                                monkeypatch):
    """A ^C during the supervised backoff must close the trace run
    bracket with the error string instead of dying mid-sleep with a
    dangling run_begin (the drive-by hardening pin)."""
    import time as _time
    import types

    from stateright_tpu import checkpoint as ckpt

    def interrupted_sleep(sec):
        raise KeyboardInterrupt()

    # patch the checkpoint module's time reference only: a global
    # time.sleep patch would intercept unrelated subprocess polls
    monkeypatch.setattr(
        ckpt, "time",
        types.SimpleNamespace(sleep=interrupted_sleep,
                              monotonic=_time.monotonic,
                              time=_time.time),
    )
    c = _twopc3(checkpoint_every=1,
                checkpoint_path=str(tmp_path / "ki.ckpt"))
    c.retry_backoff_sec = 0.01
    faultinject.arm("raise", "mid_chunk", 1)
    tr = RunTracer()
    with pytest.raises(KeyboardInterrupt):
        with tr.activate():
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                c.join()
    ends = [e for e in tr.events if e["ev"] == "run_end"]
    assert ends, "the run bracket was closed"
    assert "KeyboardInterrupt" in (ends[-1].get("error") or "")
    validate_events(tr.events)


# -- CLI flag plumbing ----------------------------------------------------


def test_cli_runtime_flags():
    from stateright_tpu import cli

    try:
        rest = cli._pop_runtime_flags(
            ["2pc", "check-tpu", "3", "--degrade-on-fault",
             "--watchdog=6", "--straggler-factor=4"]
        )
        assert rest == ["2pc", "check-tpu", "3"]
        assert cli._RUNTIME["degrade_on_fault"] is True
        assert cli._RUNTIME["watchdog"] == 6.0
        assert cli._RUNTIME["straggler_factor"] == 4.0
        cli._pop_runtime_flags(["--watchdog"])
        assert cli._RUNTIME["watchdog"] == 8.0  # the default factor
        with pytest.raises(SystemExit):
            cli._pop_runtime_flags(["--watchdog=0"])
        with pytest.raises(SystemExit):
            cli._pop_runtime_flags(["--straggler-factor=1"])
        # the flags land on a spawned device engine
        c = _twopc3()
        cli._RUNTIME.update(degrade_on_fault=True, watchdog=6.0,
                            straggler_factor=4.0)
        cli._apply_runtime(c)
        assert c.degrade_on_fault is True
        assert c.watchdog_factor == 6.0
        assert c.straggler_factor == 4.0
    finally:
        cli._RUNTIME.update(
            checkpoint_every=None, checkpoint_path=None,
            resume=False, resume_any_sha=False, waves_per_sync=None,
            tier_hot_rows=None, degrade_on_fault=False,
            watchdog=None, straggler_factor=None,
        )


# -- the suite's own time limits (tests/conftest.py) ----------------------


def test_stalled_chunk_fails_only_its_own_test(monkeypatch):
    """A chunk that never syncs, in a checker built without watchdog
    options: the suite's chunk limit raises WatchdogTimeout in this
    test, well inside its wall limit, and a fresh check runs to its
    exact count while the abandoned chunk's thread still sleeps."""
    import threading
    import time

    import conftest

    monkeypatch.setattr(conftest, "CHUNK_LIMIT_SEC", 2.0)
    c = _twopc3()
    assert c.watchdog_cap_sec == c.watchdog_grace_sec == 2.0
    faultinject.arm("hang", "mid_chunk", 1, hang_sec=15.0)
    t0 = time.monotonic()
    with pytest.raises(WatchdogTimeout) as ei:
        c.join()
    assert ei.value.chunk == 1
    assert time.monotonic() - t0 < conftest.TEST_LIMIT_SEC / 10
    abandoned = [t for t in threading.enumerate()
                 if t.name == "stpu-watchdog-chunk1"]
    assert abandoned and abandoned[0].is_alive()
    fresh = _twopc3().join()
    assert fresh.unique_state_count() == 288
    assert abandoned[0].is_alive()


def test_wall_limit_interrupts_python_waits():
    """The per-test wall limit raises in the main thread out of a
    Python-level wait, then the enclosing test's own limit resumes."""
    import signal
    import threading
    import time

    from conftest import WallLimitExceeded, wall_limit

    outer_left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert outer_left > 0  # this test runs under its own limit
    t0 = time.monotonic()
    with pytest.raises(WallLimitExceeded, match="probe"):
        with wall_limit(0.5, "probe"):
            threading.Event().wait(60)
    assert time.monotonic() - t0 < 10
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] < outer_left


@pytest.mark.time_limit(900)
def test_time_limit_marker_gives_the_limit():
    import signal

    import conftest

    left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert conftest.TEST_LIMIT_SEC < left <= 901
