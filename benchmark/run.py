#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic mix and metric readers
are files under ``benchmark/`` found by name (see ``harness.py``).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled stretch of checks. The last line of
standard output is the JSON result; the numbers that decided
``correct`` are also the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the device kind has no entry in
``benchmark/peaks.json``.
"""

import os
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The persistent compile cache (and the program's budget and
# memory-analysis stores, which live beside it) stays inside the
# checkout, at a fixed path, whatever the machine sets: only the first
# run of a cell in a checkout compiles.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".jax_cache")

sys.path[:0] = [HERE, ROOT]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
