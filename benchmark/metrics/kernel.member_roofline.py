"""``kernel.member_roofline``: the visited-set membership kernel's share
of its roofline, by the same rule as ``kernel.merge_roofline``. The
kernel is the Pallas call of ``stateright_tpu/ops/merge.py`` with the
same operands as the merge that writes one int32 bit block
``s32[R, 128]``."""

import re

import work

KERNEL = "member_sorted"
KERNEL_PATTERN = re.compile(
    r"s32\[\d+,128\]\S* custom-call\(s32\[\d+\]\S* %\S+, "
    r"u32\[2,\d+\]\S* %\S+, u32\[2,\d+\]\S* %\S+\), "
    r'custom_call_target="tpu_custom_call"')


def read(run):
    return work.roofline_share(run, KERNEL, work.member_bytes)
