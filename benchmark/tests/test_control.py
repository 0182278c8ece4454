"""The controls at a size the CPU holds: each has to come out not correct,
by the same comparison that decides ``correct`` in a run."""

import control
from conftest import CELL, TESTS


def run_control(spec, name):
    return control.run_control(CELL, name, [11, 12, 13], 1,
                               require_tpu=False, spec=spec, extra=TESTS)


def test_narrow_fingerprint_control(spec):
    """At 288 states a 10-bit key collides (about 40 pairs expected), as
    a 32-bit key does at paxos-4's 2.37M states (about 650)."""
    out = run_control(spec, "fp10")
    for r in out.values():
        assert r["correct"] is False
        assert (r["compared"]["unique_gap"]["value"] > 0
                or r["compared"]["checks_failed"]["value"] > 0)


def test_no_paths_control(spec):
    out = run_control(spec, "no_paths")
    for r in out.values():
        assert r["correct"] is False
        assert r["compared"]["bad_paths"]["value"] > 0
        assert r["compared"]["unique_gap"]["value"] == 0
