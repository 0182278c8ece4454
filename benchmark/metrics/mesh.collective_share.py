"""``mesh.collective_share``: the share of the devices' busy time spent in
collective operations, from the profiler trace: 100 x the collectives'
self time, summed over the device planes, over ``busy_s x chips`` (busy
is averaged over the planes, so that product is the busy time of all
of them).

A collective is an operation whose HLO opcode is one of XLA's
collectives (``COLLECTIVE``), or the ``-start``/``-done`` halves of its
asynchronous form. A traced check of paxos-4c3s-mesh4 on a four-chip
v5e host shows two such labels: ``all-to-all`` (one a wave on each
chip, ``u32[4,524288,22]``: the sharded engine routing each wave's
candidates to the shard owning them) and ``all-reduce`` (the
``psum``/``pmax``/``pmin`` agreement on wave class, counts and
termination); its ``async-start``/``async-done`` there are slices, not
collectives. A collective's time includes waiting for the slowest
shard to reach it, so imbalance between shards reads as collective
time. Where the trace holds no collective (a one-chip cell, or no
trace), nothing is read."""

import re

COLLECTIVE = re.compile(
    r"(all-to-all|ragged-all-to-all|all-reduce|all-gather|reduce-scatter"
    r"|collective-permute|collective-broadcast)(-start|-done)?")


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    seconds = sum(sec for label, (sec, _n) in t.ops.items()
                  if COLLECTIVE.fullmatch(label))
    if not seconds:
        return None
    return 100.0 * seconds / (t.busy_s * run.cell["chips"])
