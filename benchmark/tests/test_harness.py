"""The harness on the CPU: the device check, the loop, the result line,
and a throwaway configuration, traffic mix and metric found by name."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import harness
from conftest import BENCH, CELL, ROOT, TESTS


def run(spec, trace=False, seconds=1.0, extra=TESTS, cell=CELL):
    return harness.run_cell(cell, 2**31 + 11, seconds, trace,
                            require_tpu=False, spec=spec, extra=extra)


def test_cpu_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "paxos-4c3s.verify",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_device_checks(monkeypatch):
    import jax

    def devices(kind, n=1):
        return [SimpleNamespace(platform="tpu", device_kind=kind)] * n

    monkeypatch.setattr(jax, "devices", lambda: devices("TPU v9 unknown"))
    with pytest.raises(SystemExit, match="no entry"):
        harness.device_info(1)
    monkeypatch.setattr(jax, "devices", lambda: devices("TPU v5 lite"))
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.device_info(4)
    assert harness.device_info(1)[1] == "TPU v5 lite"


def test_untraced_run(spec):
    result, compared = run(spec)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"states_per_s", "verdict_ms_p95",
                                      "setup_s"}
    assert result["metrics"]["states_per_s"]["unit"] == "states/s"
    assert result["compile"]["window"]["compiles"] == 0
    assert result["seed"] == 2**31 + 11
    assert list(result)[-1] == "compared"
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in compared.values())
    assert "bad_paths" in compared
    json.dumps(result)


def test_traced_run(spec):
    result, _ = run(spec, trace=True)
    assert result["correct"] is True
    assert {"compile.stage_s", "compile.backend_s"} <= set(result["metrics"])
    assert "states_per_s" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_entries_found_by_name(spec, tmp_path):
    """A configuration, traffic mix and metric that only a temporary
    directory holds, run without editing any file of the benchmark."""
    for kind in ("configs", "traffic", "metrics"):
        (tmp_path / kind).mkdir()
    with open(os.path.join(TESTS, "configs", "2pc-rm3.json")) as fh:
        cfg = json.load(fh)
    cfg["name"] = "throwaway"
    (tmp_path / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(BENCH, "traffic", "verify.json"),
                tmp_path / "traffic" / "throwaway.json")
    (tmp_path / "metrics" / "throwaway.checks.py").write_text(
        "def read(run):\n    return len(run.checks)\n")
    spec["workloads"] = [dict(name="throwaway.cell", config="throwaway",
                              traffic="throwaway", chips=1, why="test")]
    spec["end_to_end"] = [
        m for m in spec["end_to_end"] if "workloads" not in m
    ] + [dict(name="throwaway.checks", unit="checks", better="higher",
              bound=0.05, source="host_clock")]
    result, _ = run(spec, extra=str(tmp_path), cell="throwaway.cell")
    assert result["correct"] is True
    assert result["metrics"]["throwaway.checks"]["value"] == \
        result["attempted"] - 1
