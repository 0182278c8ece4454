"""The wave-wall profiler subsystem (stateright_tpu/wavewall.py):
the out-of-stage attribution VERDICT r5 item 1 asked for, pinned to
run on CPU CI — capture a mid-run carry, re-time one wave body,
measure the identity-switch carry baseline, and emit the per-HLO-
category op/byte breakdown."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stateright_tpu.models.two_phase_commit import TwoPhaseSys  # noqa: E402
from stateright_tpu.wavewall import (  # noqa: E402
    format_report,
    hlo_category,
    parse_hlo_categories,
    wave_wall_report,
)


def test_hlo_category_vocabulary():
    assert hlo_category("copy") == "data formatting"
    assert hlo_category("transpose") == "data formatting"
    assert hlo_category("pad") == "quantization padding"
    assert hlo_category("dynamic-update-slice") == "dynamic-update-slice"
    assert hlo_category("dynamic-slice") == "carry/slice movement"
    assert hlo_category("concatenate") == "carry/slice movement"
    assert hlo_category("sort") == "sort"
    assert hlo_category("gather") == "gather"
    assert hlo_category("fusion") == "fusion"
    assert hlo_category("add") == "elementwise compute"
    assert hlo_category("while") == "control"


def test_parse_hlo_categories_counts_and_bytes():
    text = "\n".join(
        [
            "HloModule jit_body",
            "ENTRY %main (p0: u32[8,4]) -> u32[8,4] {",
            "  %p0 = u32[8,4]{1,0} parameter(0)",
            "  %c = u32[8,4]{1,0} copy(%p0)",
            "  %s = (u32[128]{0}, u32[128]{0}) sort(%a, %b), dimensions={0}",
            "  %a2 = u32[128]{0} add(%x, %y)",
            "  ROOT %t = u32[8,4]{1,0} copy(%c)",
            "}",
        ]
    )
    cats = parse_hlo_categories(text)
    assert cats["data formatting"]["ops"] == 2
    assert cats["data formatting"]["bytes"] == 2 * 8 * 4 * 4
    assert cats["sort"]["ops"] == 1
    assert cats["sort"]["bytes"] == 2 * 128 * 4
    assert cats["elementwise compute"]["ops"] == 1
    assert cats["control"]["ops"] == 1  # the parameter


def test_wave_wall_report_on_cpu():
    """End-to-end on a real captured carry: the report carries the
    wall/carry-baseline timings and a non-empty category breakdown
    whose data-movement categories are populated (the wave writes
    class-local blocks via dynamic-update-slice by design). The
    checker runs the chip's merge (the Pallas kernels, interpreted),
    so the breakdown is of the chip's op set: the CPU ``xla`` merge
    scatters by design (ops/merge.py ``_merge_xla``,
    ``compact_winners``)."""
    c = (
        TwoPhaseSys(rm_count=4)
        .checker()
        .target_state_count(800)
        .spawn_tpu_sortmerge(
            capacity=1 << 11,
            frontier_capacity=1 << 9,
            cand_capacity=1 << 11,
            track_paths=False,
            merge_impl="pallas_interpret",
        )
    )
    c.keep_final_carry = True
    c.join()
    rep = wave_wall_report(c, reps=2)
    assert rep["n_rows"] > 0
    assert rep["wave_ms"] >= 0.0
    assert np.isfinite(rep["loop_floor_ms"])
    cats = rep["categories"]
    assert cats, "empty category breakdown"
    assert "dynamic-update-slice" in cats
    assert any(s["bytes"] > 0 for s in cats.values())
    # The engine path must stay scatter-free (the repo's core design
    # claim — PERF.md: XLA:TPU serializes scatters).
    assert "scatter" not in cats
    text = format_report(rep, stage_sum_ms=1.0)
    assert "hlo category" in text and "out-of-stage" in text


def test_merge_stage_estimate_smoke():
    """The bench-facing merge-stage attribution (round 10): runs off
    nothing but a finished checker, reports every stage key positive
    and the impl the checker ran — pinned here so a metrics/ladder/
    ops rename can't keep tier-1 green while crashing bench.py at
    the pending BENCH_r06 chip run."""
    from stateright_tpu.wavewall import merge_stage_estimate

    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sortmerge(
            capacity=1 << 10,
            frontier_capacity=1 << 8,
            cand_capacity=1 << 10,
            track_paths=False,
        )
    )
    est = merge_stage_estimate(c, reps=2)
    assert est["impl"] == c.merge_impl
    assert est["V_v"] > 0 and est["B"] > 0 and est["NF"] > 0
    for k in ("cand_sort_ms", "member_ms", "winner_compact_ms",
              "append_ms", "rebuild_sort_ms"):
        assert est[k] >= 0.0, k
    assert est["dedup_ms"] == pytest.approx(
        est["cand_sort_ms"] + est["member_ms"]
        + est["winner_compact_ms"] + est["append_ms"], abs=0.01,
    )
