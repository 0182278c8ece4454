"""``device.idle_share.latency``: ``device.idle_share`` in the cells
judged by their verdict latency, where the host's work between
programs, not the device, bounds each check."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
