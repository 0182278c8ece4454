"""The benchmark's own tests: the loop, the metric arithmetic, the trace
reduction and the comparison, on the CPU, on the tiny configuration
``configs/2pc-rm3.json`` that only these tests use.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(BENCH, ".jax_cache"))
sys.path[:0] = [BENCH, ROOT]

#: the tests' cell
CELL = "2pc-rm3.verify"


def spec_with_test_cell():
    """``BENCHMARK.json`` with the tests' cell, on ``2pc-rm3``, standing
    in for every cell: it reports every metric."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["workloads"] = [dict(name=CELL, config="2pc-rm3", traffic="verify",
                              chips=1, why="tests")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    return spec


@pytest.fixture
def spec():
    return spec_with_test_cell()
