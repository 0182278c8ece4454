"""``states_per_s``: the reference's unique states of every check that
answered correctly in the window, over the window from the start of
its first check to the end of its last."""


def read(run):
    if run.trace is not None or not run.checks:
        return None
    done = sum(run.ok)
    return done * run.reference["unique"] / run.window_s
