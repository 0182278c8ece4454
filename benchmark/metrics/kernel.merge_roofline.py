"""``kernel.merge_roofline``: the visited-set merge kernel's share of its
roofline: the least time the chip's HBM bandwidth allows for the bytes
the merge must move, over the kernel's summed device time in the trace.

The bytes come from the configuration's per-wave counts (``work.py``),
not from the program, so they are the same whatever implements the
merge. The kernel carries no name in the trace: it is the Pallas call
of ``stateright_tpu/ops/merge.py`` (one scalar-prefetch ``s32`` start
table and two two-limb key arrays in) that writes the merged two-limb
keys ``u32[2, M]``. Where no operation matches (another implementation
ran), nothing is read."""

import re

import work

KERNEL = "merge_sorted"
KERNEL_PATTERN = re.compile(
    r"u32\[2,\d+\]\S* custom-call\(s32\[\d+\]\S* %\S+, "
    r"u32\[2,\d+\]\S* %\S+, u32\[2,\d+\]\S* %\S+\), "
    r'custom_call_target="tpu_custom_call"')


def read(run):
    return work.roofline_share(run, KERNEL, work.merge_bytes)
