"""``setup_s``: process start to the first timed check: imports, the
device check, building the check and one warm-up check that builds or
fetches its programs."""


def read(run):
    return run.setup_s
