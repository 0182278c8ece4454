"""``verdict_ms_p95``: the 95th percentile (nearest rank) of the time
from the builder's spawn call to the verdict, over every check of the
window. A check that failed counts as missing: it waited the whole
window."""

import math


def read(run):
    if run.trace is not None or not run.checks:
        return None
    walls = sorted(c["wall_s"] if ok else run.window_s
                   for c, ok in zip(run.checks, run.ok))
    rank = math.ceil(0.95 * len(walls))
    return walls[rank - 1] * 1000.0
