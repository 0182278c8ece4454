"""The four-chip cell's path on the CPU: a sharded configuration run by
the harness over four virtual CPU devices, and the ``mesh.collective_share``
reader on a synthetic trace of two device planes.

The harness run is a subprocess: the CPU backend's device count is set
by ``XLA_FLAGS`` before JAX starts, and this process has started it."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import harness
from conftest import BENCH, ROOT, TESTS
from tracereduce import Reduced

#: the tests' mesh cell, on ``configs/paxos-2c3s-mesh4.json``
MESH_CELL = "paxos-2c3s-mesh4.verify"

RUN_MESH_CELL = f"""
import json, sys
sys.path[:0] = [{BENCH!r}, {ROOT!r}]
import harness
spec = harness.bench_spec()
spec["workloads"] = [dict(name={MESH_CELL!r}, config="paxos-2c3s-mesh4",
                          traffic="verify", chips=4, why="tests")]
for m in spec["end_to_end"] + spec["per_layer"]:
    if "workloads" in m:
        m["workloads"] = [{MESH_CELL!r}]
for trace in (False, True):
    result, _ = harness.run_cell({MESH_CELL!r}, 2**31 + 17, 1.0, trace,
                                 require_tpu=False, spec=spec,
                                 extra={TESTS!r})
    print(json.dumps(result))
"""


def test_mesh_cell_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", RUN_MESH_CELL], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    untraced, traced = (json.loads(line)
                        for line in p.stdout.strip().splitlines()[-2:])
    for result in (untraced, traced):
        assert result["correct"] is True
        assert result["failed"] == 0
        assert result["device"]["count"] == 4
        assert result["compile"]["window"]["compiles"] == 0
        assert all(v["value"] == 0 and v["limit"] == 0
                   for v in result["compared"].values())
    assert {"states_per_s", "setup_s"} <= set(untraced["metrics"])
    # the CPU's trace has no device plane, so no collective to read
    assert "mesh.collective_share" not in traced["metrics"]


def test_mesh_config_is_the_single_chip_answer():
    """The mesh configuration runs the same model as ``paxos-4c3s``, so it
    carries the same recorded answer, and its engine budgets are the
    program's own table."""
    from stateright_tpu.models.paxos_tpu import SHARDED_SIZES

    one = harness.load_config("paxos-4c3s")
    mesh = harness.load_config("paxos-4c3s-mesh4")
    for key in ("system", "reference", "params", "row_bytes", "expect",
                "waves"):
        assert mesh[key] == one[key], key
    engine = dict(mesh["engine"])
    assert engine.pop("n_shards") == 4
    assert engine.pop("track_paths") is False
    assert engine == SHARDED_SIZES[4]
    small = harness.load_config("paxos-2c3s-mesh4", TESTS)
    assert {k: v for k, v in small["engine"].items()
            if k not in ("n_shards", "track_paths")} == SHARDED_SIZES[2]


def test_mesh_test_config_is_the_reference():
    cfg = harness.load_config("paxos-2c3s-mesh4", TESTS)
    from reference import bfs

    ans = bfs.explore(harness.reference_module(cfg), cfg["params"])
    assert {k: ans[k] for k in ("unique", "depth", "discoveries")} == \
        cfg["expect"]
    assert ans["waves"] == cfg["waves"]


def collective_share():
    return harness.load_module(
        harness.find_file("metrics", "mesh.collective_share.py"),
        "t_mesh_collective_share")


def test_collective_share_reader():
    """Two device planes, each busy 90 or 95 ns of a 100 ns window:
    collectives 30 ns on the first (an all-to-all and both halves of an
    asynchronous all-reduce) and 40 ns on the second; fusions and an
    asynchronous copy are not collectives."""
    events = [
        ["host", "window", 0, 100],
        ["ops/device:TPU:0", "fusion", 0, 60],
        ["ops/device:TPU:0", "all-to-all", 60, 20],
        ["ops/device:TPU:0", "all-reduce-start", 80, 5],
        ["ops/device:TPU:0", "all-reduce-done", 85, 5],
        ["ops/device:TPU:1", "fusion", 0, 50],
        ["ops/device:TPU:1", "all-to-all", 50, 40],
        ["ops/device:TPU:1", "copy-start", 90, 5],
    ]
    reduced = Reduced(events)
    assert reduced.busy_s == pytest.approx(92.5e-9)
    run = SimpleNamespace(trace=reduced, cell={"chips": 2})
    share = collective_share().read(run)
    assert share == pytest.approx(100.0 * 70 / (92.5 * 2))
    # nothing to read: no trace, or a trace without collectives
    assert collective_share().read(SimpleNamespace(trace=None)) is None
    alone = Reduced([e for e in events if not e[1].startswith("all-")])
    assert collective_share().read(
        SimpleNamespace(trace=alone, cell={"chips": 2})) is None
