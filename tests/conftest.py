"""Test configuration.

Tests run on a virtual 8-device CPU mesh (fast, deterministic, no TPU
required); the sharded-engine tests in test_parallel.py exercise the
multi-chip path (shard_map + all_to_all frontier shuffles) on that
mesh. The chip is driven by ``chip_smoke.py`` (one process, through
the chip tool); ``STPU_TPU_TESTS=1`` runs this suite on an attached
device instead.

Forcing the CPU takes both (a) the XLA flag before any backend
initializes and (b) an explicit ``jax_platforms`` config update, so a
machine with a TPU attached still runs the suite on the CPU.

Time limits: a stalled test fails on its own and the worker goes on.

* Every device checker the suite builds without watchdog settings of
  its own runs each chunk under the hung-dispatch watchdog
  (``TpuBfsChecker._dispatch_supervised``) with a flat deadline of
  ``CHUNK_LIMIT_SEC``. A chunk that never syncs raises
  ``checkpoint.WatchdogTimeout`` in its test. XLA cannot cancel the
  abandoned execution: its daemon thread keeps a core until it ends.
* Every test runs under a wall limit of ``TEST_LIMIT_SEC``, or of the
  seconds its ``time_limit`` marker gives. At the limit every thread's
  stack is dumped to stderr; a second later SIGALRM raises
  :class:`WallLimitExceeded` in the main thread. The signal interrupts
  Python-level waits (joins, sockets, sleeps, the watchdog's own wait)
  but not a main thread blocked inside an XLA readback, which is why
  the chunk limit above moves every readback off the main thread.
"""

import faulthandler
import functools
import os
import signal
import threading
import time
from contextlib import contextmanager

import pytest

if os.environ.get("STPU_TPU_TESTS"):
    # Run the suite against the real device (the TPU-gated tests stop
    # skipping).
    import jax  # noqa: E402
else:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")

#: the deadline of every chunk dispatch+sync the suite runs
CHUNK_LIMIT_SEC = 120.0
#: the default wall limit of one test (setup, call and teardown)
TEST_LIMIT_SEC = 300.0


class WallLimitExceeded(BaseException):
    """A test ran past its wall limit. A BaseException, so the engine's
    ``except Exception`` recovery paths cannot swallow it."""


@contextmanager
def wall_limit(seconds: float, what: str = "test"):
    """Dump every thread's stack after ``seconds`` and raise
    :class:`WallLimitExceeded` in the main thread one second later.
    Nests: an enclosing limit resumes with the time it had left."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(signum, frame):
        raise WallLimitExceeded(
            f"{what} exceeded its wall limit of {seconds:g} s"
        )

    prev_handler = signal.signal(signal.SIGALRM, on_alarm)
    prev_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds + 1.0)
    faulthandler.dump_traceback_later(seconds)
    t0 = time.monotonic()
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, prev_handler)
        if prev_left:
            left = max(prev_left - (time.monotonic() - t0), 0.01)
            faulthandler.dump_traceback_later(max(left - 1.0, 0.01))
            signal.setitimer(signal.ITIMER_REAL, left)


def _install_chunk_limit():
    from stateright_tpu.checkers.tpu import TpuBfsChecker

    init = TpuBfsChecker.__init__

    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        init(self, *args, **kwargs)
        if self.watchdog_factor is None:
            # factor x any measured wall clamps to the flat limit
            self.watchdog_factor = 1.0
            self.watchdog_floor_sec = CHUNK_LIMIT_SEC
            self.watchdog_cap_sec = CHUNK_LIMIT_SEC
            self.watchdog_grace_sec = CHUNK_LIMIT_SEC

    TpuBfsChecker.__init__ = __init__


_install_chunk_limit()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_LIMIT_SEC
    with wall_limit(limit, item.nodeid):
        yield
