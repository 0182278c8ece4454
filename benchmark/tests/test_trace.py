"""The trace reduction, on a synthetic trace whose numbers are known and
on a recorded one: two 2pc rm=5 checks on a TPU v5e."""

import gzip
import json
import os

import pytest

import harness
import tracereduce
from tracereduce import Reduced, kernel_label, op_label

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"

WHILE = ("%while.1 = (u32[2]{0}, pred[]) while((u32[2]{0}, pred[]) %t), "
         "condition=%c, body=%b")
FUSION = "%fusion.3 = u32[8]{0:T(128)} fusion(u32[8]{0} %p), kind=kLoop"
# the two kernels as a 2pc rm=5 trace on a TPU v5e shows them
MERGE = ('%body.15 = u32[2,40960]{1,0:T(2,128)} custom-call(s32[81]{0:T(128)'
         'S(1)} %and_select_fusion.77, u32[2,33408]{1,0:T(2,128)S(1)} '
         '%dynamic-update-slice.25, u32[2,8832]{1,0:T(2,128)S(1)} '
         '%dynamic-update-slice.27), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={s32[81]{0}, u32[2,33408]{1,0}, '
         'u32[2,8832]{1,0}}, frontend_attributes={kernel_metadata={}}')
MEMBER = ('%body.14 = s32[113,128]{1,0:T(8,128)S(1)} custom-call(s32[92]{0:'
          'T(128)S(1)} %and_select_fusion.75, u32[2,33408]{1,0:T(2,128)S(1)} '
          '%dynamic-update-slice.25, u32[2,14464]{1,0:T(2,128)S(1)} '
          '%dynamic-update-slice.26), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={s32[92]{0}, u32[2,33408]{1,0}, '
          'u32[2,14464]{1,0}}, frontend_attributes={kernel_metadata={}}')
SORT = "%sort.2 = (u32[64]{0}, u32[64]{0}) sort(u32[64]{0} %x, u32[64]{0} %y)"


OTHER_CC = ('%cc = u32[2,64]{1,0} custom-call(u32[2,64]{1,0} %a), '
            'custom_call_target="tpu_custom_call"')


def readers(extra=None, names=("kernel.merge_roofline",
                               "kernel.member_roofline")):
    return harness.load_readers([dict(name=n) for n in names], extra)


#: the kernels the benchmark's kernel metrics declare
KERNELS = harness.kernel_patterns(readers())


def test_op_labels():
    assert op_label(WHILE) == "while"
    assert op_label(FUSION) == "fusion"
    assert op_label(SORT) == "sort"
    assert op_label(MERGE).startswith("u32[2,40960]{1,0:T(2,128)} custom-call(")


def test_kernel_labels():
    """Each kernel metric's pattern picks out its kernel and nothing
    else: a custom call of another shape stays ``custom-call``."""
    assert set(KERNELS) == {"merge_sorted", "member_sorted"}
    assert kernel_label(op_label(MERGE), KERNELS) == "merge_sorted"
    assert kernel_label(op_label(MEMBER), KERNELS) == "member_sorted"
    for text in (OTHER_CC, "%cc = f32[4]{0} custom-call(f32[4]{0} %a)"):
        assert kernel_label(op_label(text), KERNELS) == "custom-call"
    assert kernel_label("fusion", KERNELS) == "fusion"
    assert kernel_label(op_label(MERGE), {}) == "custom-call"


def test_kernel_declared_by_a_new_reader(tmp_path, recorded):
    """A kernel metric added as a file of its own names its kernel in
    the reduction, without an edit to ``tracereduce.py``."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "throwaway.kernel.py").write_text(
        "import re\nKERNEL = 'throwaway'\n"
        "KERNEL_PATTERN = re.compile(r's32\\[113,128\\]')\n"
        "def read(run):\n    return run.trace.kernel_calls(KERNEL)\n")
    kernels = harness.kernel_patterns(readers(str(tmp_path),
                                              ("throwaway.kernel",)))
    r = Reduced(recorded, kernels)
    assert r.kernel_calls("throwaway") == 34
    assert r.kernel_calls("member_sorted") == 0


def synthetic():
    ops, mods = "ops" + DEV, "modules" + DEV
    return [
        ["host", "window", 0, 100],
        ["host", "verify", 5, 40], ["host", "join", 8, 34],
        ["host", "spawn", 50, 8], ["host", "verify", 55, 20],
        [mods, "jit_chunk(1)", 10, 30], [mods, "jit_seed(2)", 60, 10],
        [ops, op_label(WHILE), 10, 30], [ops, op_label(FUSION), 12, 8],
        [ops, op_label(MERGE), 20, 10], [ops, op_label(SORT), 60, 10],
        # outside the window: not counted
        [ops, op_label(SORT), 150, 10], [mods, "jit_seed(2)", 150, 10],
    ]


def test_synthetic_reduction():
    r = Reduced(synthetic(), KERNELS)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.launches == 2
    assert r.kernel_seconds("merge_sorted") == pytest.approx(10e-9)
    assert r.kernel_calls("merge_sorted") == 1
    assert r.kernel_seconds("member_sorted") == 0
    # self time: the while's 30 ns less its fusion and kernel
    assert r.ops["while"][0] == pytest.approx(12e-9)
    assert r.ops["fusion"][0] == pytest.approx(8e-9)
    gaps = sorted(r.gaps, key=lambda g: -g[1])
    assert gaps == [("window", pytest.approx(30e-9)),
                    ("spawn", pytest.approx(20e-9)),
                    ("verify", pytest.approx(10e-9))]
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "while"
    assert len(bd["idle_gaps"]) == 3


def test_window_from_checks():
    """Without a ``window`` span the stretch is its checks' extent."""
    r = Reduced([e for e in synthetic() if e[1] != "window"])
    assert r.window_s == pytest.approx(70e-9)
    assert r.busy_s == pytest.approx(40e-9)
    with pytest.raises(ValueError):
        Reduced([e for e in synthetic() if e[1] not in ("window", "verify")])


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "trace_2pc-rm5.json.gz"), "rt") as fh:
        return json.load(fh)


def test_recorded_trace(recorded):
    """Two whole 2pc rm=5 checks, each one seed and one chunk program
    with 17 waves: one merge and one member call per wave."""
    r = Reduced(recorded, KERNELS)
    assert r.launches == 4
    assert r.kernel_calls("merge_sorted") == 34
    assert r.kernel_calls("member_sorted") == 34
    assert "custom-call" not in r.ops
    assert 0 < r.kernel_seconds("member_sorted") < r.busy_s
    assert 0 < r.busy_s < r.window_s
    # every operation runs inside a program execution
    w = [e for e in recorded if e[1] == "window"][0]
    mods = [(max(e[2], w[2]), min(e[2] + e[3], w[2] + w[3]))
            for e in recorded if e[0] == "modules" + DEV]
    assert r.busy_s == pytest.approx(
        tracereduce._union(mods) / 1e9, rel=1e-3)
    labels = {g[0] for g in r.gaps}
    assert any("join" in lab for lab in labels)
    assert len(r.breakdown()["device_ops"]) == 10
