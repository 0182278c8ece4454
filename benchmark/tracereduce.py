"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

``load_events`` flattens an ``.xplane.pb`` into plain events ``[line,
label, start_ns, dur_ns]``: the operations (``ops``) and program
executions (``modules``) of each device plane, and the Python-level
host events (``host``), which hold the harness's spans (``window``,
``verify``, ``spawn``, ``join``, ``verdict``) and JAX's own (dispatch,
readback). An operation's label is its HLO opcode; a custom call keeps
its whole text (output shape, operands, target), since the program's
kernels carry no name in the trace. ``Reduced`` names a custom call
after the first kernel whose pattern it matches: each kernel metric
declares its own pattern (``KERNEL``, ``KERNEL_PATTERN`` in
``metrics/<name>.py``), so a new kernel needs no edit here. The
reduction works on small plain events and is tested on a recorded
trace kept beside the tests.

* busy: the union of the intervals in which an operation ran on a
  device, inside the harness's ``window`` span, averaged over devices;
* self time per label: an operation's time less that of the operations
  nested in it (a ``while`` holds its body's operations);
* kernel time: the summed time of the operations bearing the kernel's
  label;
* launches: device program executions that start inside the window;
* idle gaps: the stretches of the window in which no operation ran on
  the first device, each named by the innermost host events around it.
"""

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
#: the harness's host spans (``harness.run_check``, ``traced_stretch``)
SPANS = ("window", "verify", "spawn", "join", "verdict")


def _shape_and_opcode(text):
    """``(shape, opcode)`` of an HLO instruction's text
    ``%name = <shape> <opcode>(...)``."""
    _, _, rest = text.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return shape.split("{")[0], rest.split("(")[0]


def op_label(text):
    """An operation's opcode, or for a custom call its text less the
    instruction's name: ``<shape> custom-call(<operands>), ...``."""
    _shape, opcode = _shape_and_opcode(text)
    if opcode == "custom-call":
        return text.partition(" = ")[2]
    return opcode or text.split(" ")[0]


def kernel_label(label, kernels):
    """The name of the first kernel in ``kernels`` ({name: compiled
    pattern}) whose pattern matches a custom call's text; ``custom-call``
    where none does; any other label unchanged."""
    if " custom-call(" not in label:
        return label
    for name, pattern in kernels.items():
        if pattern.match(label):
            return name
    return "custom-call"


def load_events(path):
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == "XLA Ops":
                    kind, label = f"ops{plane.name}", op_label
                elif line.name == "XLA Modules":
                    kind, label = f"modules{plane.name}", str
                else:
                    continue
            elif (plane.name == HOST_PLANE
                  and any(ev.name in SPANS for ev in evs)):
                # the Python thread's line, named after the executable
                kind, label = "host", str
            else:
                continue
            for ev in evs:
                events.append([kind, label(ev.name), int(ev.start_ns),
                               int(ev.duration_ns)])
    return events


def _union(intervals):
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals, lo, hi):
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _self_times(ops):
    """``[(label, self_ns)]``: each operation's time less the time of the
    operations nested inside it."""
    ops = sorted(ops, key=lambda e: (e[2], -e[3]))
    child = [0] * len(ops)
    stack = []
    for i, (_k, _l, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    return [(e[1], max(e[3] - c, 0)) for e, c in zip(ops, child)]


class Reduced:
    """The reduced trace: ``busy_s``, ``window_s``, ``launches``,
    ``ops`` ({label: [self seconds, count]}, custom calls named by
    ``kernels``) and ``gaps`` ([(label, seconds)])."""

    def __init__(self, events, kernels=None):
        kernels = kernels or {}
        host = [e for e in events if e[0] == "host"]
        windows = [e for e in host if e[1] == "window"]
        if not windows:
            # the span around the whole stretch can be missing from the
            # host plane; the stretch is then its checks' extent
            checks = [e for e in host if e[1] == "verify"]
            if not checks:
                raise ValueError(
                    "trace holds no harness 'window' or 'verify' span; host "
                    f"events: {sorted({e[1] for e in host})[:40]}")
            lo = min(e[2] for e in checks)
            windows = [["host", "window", lo,
                        max(e[2] + e[3] for e in checks) - lo]]
        w = max(windows, key=lambda e: e[3])
        lo, hi = w[2], w[2] + w[3]
        self.window_s = (hi - lo) / 1e9
        devices = sorted({e[0][len("ops"):] for e in events
                          if e[0].startswith("ops")})
        busy = []
        first = None
        self.ops = {}
        self.launches = 0
        for dev in devices:
            ops = [e for e in events if e[0] == "ops" + dev
                   and e[2] < hi and e[2] + e[3] > lo]
            iv = [(max(e[2], lo), min(e[2] + e[3], hi)) for e in ops]
            busy.append(_union(iv))
            first = iv if first is None else first
            for label, ns in _self_times(ops):
                item = self.ops.setdefault(kernel_label(label, kernels),
                                           [0.0, 0])
                item[0] += ns / 1e9
                item[1] += 1
            self.launches += sum(1 for e in events if e[0] == "modules" + dev
                                 and lo <= e[2] < hi)
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0
        inner = [e for e in host if e is not w]
        self.gaps = []
        for s, e in _gaps(first or [], lo, hi):
            mid = (s + e) / 2
            around = sorted((x for x in inner if x[2] <= mid <= x[2] + x[3]),
                            key=lambda x: -x[3])
            label = "/".join(x[1] for x in around[-2:]) or "window"
            self.gaps.append((label, (e - s) / 1e9))

    def kernel_seconds(self, label):
        return self.ops.get(label, [0.0, 0])[0]

    def kernel_calls(self, label):
        return self.ops.get(label, [0.0, 0])[1]

    def breakdown(self):
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return dict(device_ops=[[label, sec] for label, (sec, _n) in ops],
                    idle_gaps=[[label, sec] for label, sec in gaps])


def reduce_dir(tdir, kernels=None):
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return Reduced(load_events(paths[-1]), kernels)
