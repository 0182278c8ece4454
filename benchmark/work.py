"""What the visited-set kernels must move, from a configuration's
per-wave counts, and the roofline share that follows.

The counts (``waves`` in ``configs/<name>.json``) are the plain
reference's: for each expansion of a BFS level, the unique states
before it (``visited``), those it found new (``new``) and its distinct
successors (``candidates``). A visited row is one fingerprint of
``row_bytes``. The bytes are the least any implementation moves, so
padding to capacity classes counts as no work:

* merge: the wave's new rows merged into the sorted visited rows, read
  once and written once: ``(visited + new) * row_bytes * 2``;
* membership: the visited rows and the candidate rows read once, and
  one bit written per candidate: ``(visited + candidates) * row_bytes +
  candidates / 8``.

A wave with nothing to merge or to look up needs no kernel work.
"""


def merge_bytes(waves, row_bytes):
    return sum((w["visited"] + w["new"]) * row_bytes * 2
               for w in waves if w["new"])


def member_bytes(waves, row_bytes):
    return sum((w["visited"] + w["candidates"]) * row_bytes
               + w["candidates"] / 8
               for w in waves if w["candidates"])


def roofline_share(run, kernel, bytes_fn):
    """Percent of the roofline: bytes over the peak HBM bandwidth, per
    traced check, over the kernel's summed device time in the trace
    (``kernel`` is the ``KERNEL`` its reader declares). ``None`` where
    the trace holds no event of the kernel."""
    t = run.trace
    waves = run.config.get("waves")
    if t is None or not run.traced_checks or not waves:
        return None
    seconds = t.kernel_seconds(kernel)
    if not seconds:
        return None
    need = bytes_fn(waves, run.config["row_bytes"]) * run.traced_checks
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
