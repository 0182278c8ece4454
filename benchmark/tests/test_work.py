"""The yardstick's arithmetic: the references against upstream's
published counts, the configurations' recorded answers, the roofline
work functions and the metric readers."""

import glob
import json
import os
from types import SimpleNamespace

import pytest

import harness
import work
from reference import bfs, paxos, twopc

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("model,params,unique,depth", [
    # upstream examples/2pc.rs:153-159 and examples/paxos.rs:325, 349
    (twopc, {"rm_count": 3}, 288, 11),
    (twopc, {"rm_count": 5}, 8832, 17),
    (paxos, {"client_count": 2, "server_count": 3}, 16668, 21),
])
def test_reference_counts(model, params, unique, depth):
    ans = bfs.explore(model, params)
    assert (ans["unique"], ans["depth"]) == (unique, depth)


def test_paxos_properties_hold():
    ans = bfs.explore(paxos, {"client_count": 1, "server_count": 3})
    assert sorted(ans["discoveries"]) == ["value chosen"]


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(BENCH, "configs", "*.json"))
    + glob.glob(os.path.join(BENCH, "tests", "configs", "*.json"))))
def test_recorded_answers_add_up(path):
    with open(path) as fh:
        cfg = json.load(fh)
    waves = cfg["waves"]
    assert waves[0]["visited"] + sum(w["new"] for w in waves) == \
        cfg["expect"]["unique"]
    assert len(waves) == cfg["expect"]["depth"]
    for a, b in zip(waves, waves[1:]):
        assert b["visited"] == a["visited"] + a["new"]


@pytest.mark.parametrize("path", [
    os.path.join(BENCH, "tests", "configs", "2pc-rm3.json"),
    os.path.join(BENCH, "configs", "2pc-rm5.json"),
])
def test_recorded_answer_is_the_reference(path):
    """The recorded answer is what the plain reference computes now, for
    the configurations the CPU explores in seconds (``paxos-4c3s`` takes
    minutes: ``reference/record.py`` recomputes it)."""
    with open(path) as fh:
        cfg = json.load(fh)
    model = harness.reference_module(cfg)
    ans = bfs.explore(model, cfg["params"])
    assert {k: ans[k] for k in ("unique", "depth", "discoveries")} == \
        cfg["expect"]
    assert ans["waves"] == cfg["waves"]


def test_work_functions():
    waves = [dict(visited=1, new=3, candidates=4),
             dict(visited=4, new=0, candidates=2)]
    assert work.merge_bytes(waves, 8) == (1 + 3) * 8 * 2
    assert work.member_bytes(waves, 8) == (
        (1 + 4) * 8 + 4 / 8 + (4 + 2) * 8 + 2 / 8)


def fake_run(**kw):
    base = dict(trace=None, checks=[], ok=[], window_s=1.0, setup_s=3.0,
                reference={"unique": 100}, traced_checks=0,
                config={"waves": [dict(visited=1, new=3, candidates=4)],
                        "row_bytes": 8},
                peaks={"hbm_bytes_per_s": 819e9},
                counters={"setup": {"stage_sec": 2.0, "compile_sec": 1.5}})
    base.update(kw)
    return SimpleNamespace(**base)


def reader(name):
    return harness.load_module(harness.find_file("metrics", f"{name}.py"),
                               "t_" + name.replace(".", "_"))


def test_end_to_end_readers():
    checks = [dict(wall_s=0.01 * (i + 1)) for i in range(20)]
    ok = [True] * 20
    run = fake_run(checks=checks, ok=ok, window_s=2.0)
    assert reader("states_per_s").read(run) == pytest.approx(20 * 100 / 2.0)
    # nearest rank: the 19th of 20
    assert reader("verdict_ms_p95").read(run) == pytest.approx(190.0)
    assert reader("setup_s").read(run) == 3.0
    # a failed check adds no states and waited the whole window
    ok[-2] = False
    assert reader("states_per_s").read(run) == pytest.approx(19 * 100 / 2.0)
    assert reader("verdict_ms_p95").read(run) == pytest.approx(200.0)


def test_per_layer_readers():
    t = SimpleNamespace(busy_s=0.25, window_s=1.0, launches=6,
                        kernel_seconds=lambda k: 1e-6 if k else 0)
    run = fake_run(trace=t, traced_checks=3)
    assert reader("device.idle_share").read(run) == pytest.approx(75.0)
    assert reader("device.idle_share.latency").read(run) == \
        pytest.approx(75.0)
    assert reader("engine.launches_per_check").read(run) == 2
    assert reader("compile.stage_s").read(run) == 2.0
    assert reader("compile.backend_s").read(run) == 1.5
    need = (1 + 3) * 8 * 2 * 3
    assert reader("kernel.merge_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 1e-6)
    # no event of the kernel: nothing to read, never 0
    t.kernel_seconds = lambda k: 0
    assert reader("kernel.member_roofline").read(run) is None
    assert reader("device.idle_share").read(fake_run()) is None
