"""``engine.launches_per_check``: device program executions per check,
counted in the profiler trace of the traced stretch."""


def read(run):
    t = run.trace
    if t is None or not run.traced_checks or not t.launches:
        return None
    return t.launches / run.traced_checks
