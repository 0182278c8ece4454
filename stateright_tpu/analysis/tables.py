"""The single home of primitive/opcode classification.

Three consumers audit the sparse-engine codegen contract and each
needs to agree on what counts as "real compute", "a gather", or
"carry movement":

* the jaxpr-level kernel-lint rules (:mod:`.rules`, ``pytest -m
  lint``, ``tools/lint_kernels.py``),
* the codegen-shape tests (tests/test_codegen_shapes.py, which
  calibrated the allowed residue against the hand paxos encoding),
* the wave-wall profiler's per-HLO-category attribution
  (stateright_tpu/wavewall.py), which classifies optimized-HLO
  opcodes with the same vocabulary the round-5 device-trace analysis
  used.

Before round 7 the first two each carried a private copy of the ALU
set and the third its own opcode table; a primitive added to one and
not the others silently weakened the audit. Everything below is data
(frozensets / dicts) plus two pure classifiers so the tables cannot
drift per consumer.
"""

from __future__ import annotations

import re

# --------------------------------------------------------------------------
# jaxpr-primitive side (the lint rules and codegen-shape tests)
# --------------------------------------------------------------------------

#: elementwise/ALU primitives — a ``[N, 1]`` output from any of these
#: is real compute at 128x lane padding, the PERF.md §ordered tax.
#: (Shape-only ops — slice, reshape, broadcast, concatenate — are NOT
#: here: a ``[N, 1]`` slice from consuming a multi-lane gather row is
#: the intended sparse idiom and fuses; ``[N, 1]`` COMPUTE does not.)
ALU_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "and", "or", "xor",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "select_n", "eq", "ne", "lt", "le", "gt", "ge", "min", "max",
    "population_count", "convert_element_type", "neg", "not",
})

#: primitives that price as carry/block movement at the jaxpr level —
#: the static fingerprint of the between-stage wave wall (PERF.md
#: §wave-wall). The carry-copy-bytes estimator sums the output bytes
#: of these inside ``cond``/``switch`` branches.
CARRY_MOVE_PRIMS = frozenset({
    "concatenate", "pad", "slice", "dynamic_slice",
    "dynamic_update_slice", "copy",
})

#: minimum output bytes before a branch pad/concat counts as buffer
#: assembly rather than index plumbing (a 2-operand ``[N, 1]`` concat
#: that builds a gather index pair is the calibrated paxos residue and
#: fuses; a full-F frontier rebuild does not).
BRANCH_PAD_CONCAT_MIN_BYTES = 4096

#: axis-0 growth factor above which a branch pad/concat reads as
#: "pad small class result to full capacity" (the pre-round-6 pattern
#: the class-local dynamic_update_slice rework deleted) rather than a
#: merge-style append of comparably-sized halves.
BRANCH_PAD_CONCAT_GROWTH = 2.0

#: value-preserving unary ops a padded carry may pass through between
#: a pad/concat and its branch return (a ``.astype(...)`` or reshape
#: must not hide a peak-shape rebuild from the branch rule).
PASSTHROUGH_PRIMS = frozenset({
    "convert_element_type", "reshape", "copy", "bitcast_convert_type",
    "stop_gradient",
})

#: per-fixture budgets for the GATED carry-copy-bytes rule (round 9):
#: the total bytes the wave body's cond/switch eqns may carry as
#: branch outputs. The round-9 class collapse (slim merge cores +
#: one fetch switch per wave + SoA vkeys/plog, PERF.md §layout) took
#: the 2pc-rm3 fixture from 21 switches / 1,422,204 B to
#: 9 switches / 244,316 B. Round 10 (the incrementally-sorted
#: visited + streaming merge, PERF.md §merge-kernel) re-priced it to
#: 13 switches / 344,908 B — a deliberate, audited addition: the
#: membership v-switch returns a bool[B] mask, the visited-append
#: v-switch returns vkeys alone (the fetch switch stopped carrying
#: it — net zero there), and the parent log carries child limbs
#: again (the sorted merge destroyed the positional derivation);
#: every new branch output is still a single small mask or a single
#: resident buffer. The budget sits ~30% above the measured value so
#: incidental carry additions (a new counter lane) pass but a
#: structural regression — another full-carry switch boundary, a
#: peak-shape branch rebuild — fails the lint loudly instead of
#: silently re-inflating the wave wall. Keys are the fixture names
#: the lint driver traces (TraceCtx.encoding).
CARRY_COPY_BYTE_BUDGETS = {
    "engine-fixture(2pc-rm3)": 450_000,
    # the same wave body traced with the Pallas merge kernel (the
    # chip invocation style): identical switch structure, so the
    # same budget pins it.
    "engine-fixture(2pc-rm3,merge=pallas)": 450_000,
    # The SHARDED engine's wave body in its TRACED form (round 11,
    # registry.SHARDED_WAVE_BODY_FIXTURE): 9 switches / 215,286 B
    # measured at the fixture shapes (8 / 196,488 B before the pair
    # class, 9 / 153,780 B before the receive-tile class). The
    # per-shard mesh log's only switch-carry addition is the 40 B
    # ``swave`` row the merge stage returns (10 uint32 lanes; the
    # ``slog`` appends live OUTSIDE the switches, in the body wrapper,
    # so they price as loop-body DUS, not branch carry). The
    # frontier-class switch returns the pair buffer (keys + slots,
    # 2 x 2,336 rows, 18,688 B) and the frontier-row props, ebits and
    # fingerprints written into F-row blocks (8,192 B); the pair-class
    # switch returns the routed payload written into the ``route``
    # carry lane at the pair ladder's top (2,048 rows x 7 lanes,
    # 57,344 B); the receive-class switch returns the lean per-shard
    # carry (C=2^11 per shard) and its three classes hold one
    # membership and one append switch each; the dest tiles and recv
    # buffers are wave-local temporaries. The pair class was a
    # deliberate, priced addition of 18,798 B (a second send-side
    # switch boundary), and the budget sits ~16% above it: one more
    # payload-sized switch boundary still fails it.
    "engine-fixture(2pc-rm3,sharded+slog)": 250_000,
}


def is_gather(primitive_name: str) -> bool:
    """The gather classification every audit shares: any primitive
    whose name contains ``gather`` (``gather``, ``dynamic_gather``,
    batched variants) — at the jaxpr level take/``x[idx]``/
    ``take_along_axis`` all lower to one of these. Cross-device
    collectives (``all_gather``) are NOT memory gathers: they classify
    through :data:`COLLECTIVE_PRIMS` and the comms rules instead."""
    return "gather" in primitive_name and not is_collective(
        primitive_name
    )


# --------------------------------------------------------------------------
# collective classification (the comms-lint rule family, round 13)
# --------------------------------------------------------------------------

#: jaxpr collective primitives → comms category. ONE home for both
#: sides of the static collective accounting: the jaxpr walk
#: (analysis/comms.py, the comms rules) classifies with this table and
#: the ``--hlo`` cross-check classifies compiled modules with
#: :data:`HLO_COLLECTIVE_OPS` below — the two vocabularies share the
#: category strings, so "jaxpr reductions == HLO all-reduces" is one
#: dict comparison, not a per-consumer mapping. ``pvary`` (what
#: ``lax.pcast(..., to="varying")`` traces to) and
#: ``axis_index`` are deliberately absent: they are axis PLUMBING
#: (replication typing / shard identity), move zero bytes, and listing
#: them here would inflate every byte total.
COLLECTIVE_PRIMS = {
    "all_to_all": "all-to-all",
    "psum": "reduction",
    "psum2": "reduction",  # newer-jax spelling of the same reduce
    "pmax": "reduction",
    "pmin": "reduction",
    "ppermute": "permute",
    "pbroadcast": "broadcast",
    "all_gather": "all-gather",
    "all_gather_invariant": "all-gather",
    "reduce_scatter": "reduce-scatter",
}

#: the collective categories whose operands must stay rank-0/tiny
#: (the ``scalar-only-reductions`` rule): a psum over a resident
#: ``[W, F]`` buffer is an accidental replication — every shard pays
#: the full buffer's all-reduce bandwidth for a value the engine only
#: ever needs element-wise on one shard.
REDUCTION_CATEGORIES = frozenset({"reduction"})

#: max elements a psum/pmax/pmin operand may carry before the
#: scalar-only-reductions rule flags it. The engines' reductions are
#: scalars and per-property vectors (property count < 32 by the
#: eventually-bits contract); 64 leaves headroom for a property-family
#: growth while still sitting orders of magnitude below any resident
#: buffer.
SCALAR_REDUCTION_MAX_ELEMS = 64

#: per-fixture allowances for the GATED ``no-all-gather`` rule: how
#: many ``all_gather`` eqns a traced comms fixture may contain.
#: Default (unlisted) is 0 — the wave path never all-gathers: visited
#: state is owner-sharded by construction and an all_gather of it is
#: the 8x traffic blow-up the rule exists to catch. A DRAIN-path
#: fixture (host-side counterexample reconstruction staging, which
#: legitimately collects shard-local logs) would register its
#: allowance here, the way step-path gathers register theirs in
#: ``EncodingSpec.max_step_gathers``. No current fixture needs one.
ALL_GATHER_ALLOWANCES: dict = {}

#: per-fixture budgets for the GATED ``comms-bytes`` rule (the comms
#: analog of CARRY_COPY_BYTE_BUDGETS): the PER-WAVE PEAK collective
#: payload — the fattest single class branch's collective bytes plus
#: any collectives outside the class switch — measured at the comms
#: fixtures' shapes (analysis/comms.py) and budgeted ~30% above, so a
#: new counter psum passes but a structural regression (a second
#: all_to_all, a buffer-sized reduction) fails loudly. Keys are the
#: comms fixture names (TraceCtx.encoding).
#:
#: Measured at S=2 (2 shards), 2pc rm=3 fixture shapes
#: (dest_tile_width=7 lanes x 4 B rows, Bd=1024):
#: * sortmerge untraced: 57,436 B — the peak class's all_to_all
#:   [2*1024, 7] u32 tile exchange (57,344 B) + 54 scalar/property
#:   psums (344 B across all classes, ~92 B in the peak branch);
#: * sortmerge traced (+slog): 57,440 B — the per-shard mesh log is
#:   never psum-collapsed (its contract), so tracing adds exactly ONE
#:   scalar psum (the global wave row's n_tot back-fill, 4 B); the
#:   shared budget pins that zero-traffic claim;
#: * the reconciliation fixture (2pc rm=5 at the TRACE_r16 dryrun
#:   config, S=8): 229,472 B — all_to_all [8*1024, 7] = 229,376 B +
#:   scalar psums.
COMMS_BYTE_BUDGETS = {
    "comms(2pc-rm3,sortmerge,S2)": 75_000,
    "comms(2pc-rm3,sortmerge,S2,traced)": 75_000,
    "comms(2pc-rm5,sortmerge,S8,traced)": 300_000,
    # the TIERED chunk program (round 16, stateright_tpu/tier.py):
    # the same wave body plus the commit phase's scalar psums/pmax —
    # measured per-wave peak 57,452 B vs the untiered 57,436 B
    # (+16 B = one conf psum + the h_loc pmax), same ~30% headroom
    "comms(2pc-rm3,sortmerge,S2,tiered,traced)": 75_000,
}


def is_collective(primitive_name: str) -> bool:
    """Whether a jaxpr primitive is a cross-shard collective — the
    recognition every comms rule shares. Prefix-matched for the
    all_gather family so a renamed variant (``all_gather_invariant``)
    can't slip past the no-all-gather gate unclassified."""
    return (
        primitive_name in COLLECTIVE_PRIMS
        or primitive_name.startswith("all_gather")
    )


def collective_category(primitive_name: str) -> str:
    """jaxpr collective primitive → comms category."""
    if primitive_name in COLLECTIVE_PRIMS:
        return COLLECTIVE_PRIMS[primitive_name]
    if primitive_name.startswith("all_gather"):
        return "all-gather"
    return "other-collective"


def collective_bytes(eqn) -> int:
    """Static byte price of one collective eqn: the larger of its
    operand and result payloads (an all_to_all moves its operand, an
    all_gather materializes its S-times-larger RESULT on every shard
    — max covers both directions without a per-primitive table).
    Token/unit avals price as 0 through :func:`output_bytes`."""
    in_b = sum(
        output_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval")
    )
    out_b = sum(output_bytes(v.aval) for v in eqn.outvars)
    return max(in_b, out_b)


def output_bytes(aval) -> int:
    """Bytes of one jaxpr output aval (0 for abstract tokens)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


# --------------------------------------------------------------------------
# HLO-opcode side (the wave-wall profiler and the --hlo lint pass)
# --------------------------------------------------------------------------

#: dtype byte widths for HLO shape strings.
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: HLO opcode -> trace-category, the round-5 device-trace vocabulary
#: (PERF.md). Copies/transposes/converts are XLA's between-stage data
#: formatting; pad is class-quantization padding; slice/concat/
#: dynamic-(update-)slice are carry and block movement; fusion is the
#: actual stage compute.
HLO_CATEGORY = {}
for _op in ("copy", "copy-start", "copy-done", "bitcast",
            "bitcast-convert", "transpose", "reshape", "convert"):
    HLO_CATEGORY[_op] = "data formatting"
HLO_CATEGORY["pad"] = "quantization padding"
HLO_CATEGORY["dynamic-update-slice"] = "dynamic-update-slice"
for _op in ("dynamic-slice", "slice", "concatenate"):
    HLO_CATEGORY[_op] = "carry/slice movement"
HLO_CATEGORY["sort"] = "sort"
HLO_CATEGORY["gather"] = "gather"
HLO_CATEGORY["scatter"] = "scatter"
HLO_CATEGORY["fusion"] = "fusion"

#: HLO collective opcodes → the SAME comms-category vocabulary as
#: COLLECTIVE_PRIMS (one home: the --hlo collective cross-check in
#: analysis/comms.py reconciles per-category op counts across the two
#: tables). Async pairs: the ``-start`` op carries the payload and
#: counts; the ``-done`` op is completion plumbing and classifies as
#: control (counting both would double every TPU collective).
HLO_COLLECTIVE_OPS = {
    "all-to-all": "all-to-all",
    "all-to-all-start": "all-to-all",
    "all-reduce": "reduction",
    "all-reduce-start": "reduction",
    "reduce-scatter": "reduce-scatter",
    "reduce-scatter-start": "reduce-scatter",
    "all-gather": "all-gather",
    "all-gather-start": "all-gather",
    "collective-permute": "permute",
    "collective-permute-start": "permute",
    "collective-broadcast": "broadcast",
}
for _op in HLO_COLLECTIVE_OPS:
    HLO_CATEGORY[_op] = "collective"
for _op in ("all-to-all-done", "all-reduce-done", "all-gather-done",
            "reduce-scatter-done", "collective-permute-done"):
    HLO_CATEGORY[_op] = "control"
for _op in ("while", "conditional", "call", "tuple",
            "get-tuple-element", "parameter", "constant",
            "iota", "broadcast", "after-all", "partition-id",
            "replica-id"):
    HLO_CATEGORY[_op] = "control"
for _op in ("add", "subtract", "multiply", "divide", "remainder",
            "and", "or", "xor", "not", "negate", "compare",
            "select", "shift-left", "shift-right-logical",
            "shift-right-arithmetic", "popcnt", "clz",
            "maximum", "minimum", "abs", "sign", "clamp",
            "reduce", "reduce-window", "map", "exponential",
            "log", "power"):
    # XLA:CPU leaves elementwise ALU unfused where the TPU trace
    # shows loop fusions — same stage-compute category.
    HLO_CATEGORY[_op] = "elementwise compute"
del _op

#: the categories whose bytes ARE the wave wall (the carry-copy-bytes
#: estimator's HLO-level numerator).
HLO_WALL_CATEGORIES = frozenset({
    "data formatting", "quantization padding",
    "carry/slice movement", "dynamic-update-slice",
})

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s+"
    r"([a-z][a-z0-9\-]*)\("
)


def hlo_category(opcode: str) -> str:
    """Map an HLO opcode to the trace-category vocabulary."""
    return HLO_CATEGORY.get(opcode, "other")


def hlo_type_bytes(type_str: str) -> int:
    """Output bytes of an HLO instruction's (possibly tuple) type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        width = DTYPE_BYTES.get(dt)
        if width is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * width
    return total


def parse_hlo_categories(hlo_text: str) -> dict:
    """Per-category ``{"ops": count, "bytes": output_bytes}`` over
    every instruction of an optimized-HLO dump (sub-computations —
    fusion bodies, while bodies, branch computations — included; their
    instructions are what the categories exist to attribute)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        type_str, opcode = m.groups()
        cat = hlo_category(opcode)
        slot = out.setdefault(cat, {"ops": 0, "bytes": 0})
        slot["ops"] += 1
        slot["bytes"] += hlo_type_bytes(type_str)
    return out


def parse_hlo_collectives(hlo_text: str) -> dict:
    """Per-COMMS-category ``{"ops": count, "bytes": output_bytes}``
    over the collective instructions of an optimized-HLO dump — the
    compiled-module side of the collective cross-check
    (analysis/comms.py): categories here reconcile one-to-one against
    the jaxpr walk's COLLECTIVE_PRIMS totals, and any category XLA
    *introduced* (SPMD partitioner respecification) shows up as ops
    the jaxpr side can't account for. Bytes are the instruction's
    OUTPUT type — equal to the jaxpr operand estimate on XLA:CPU
    (measured ratio 1.0, PERF.md §comms-lint); a backend typing the
    exchange per-participant would show an S-factor, which is why the
    cross-check reports the ratio instead of gating on it."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        type_str, opcode = m.groups()
        cat = HLO_COLLECTIVE_OPS.get(opcode)
        if cat is None:
            continue
        slot = out.setdefault(cat, {"ops": 0, "bytes": 0})
        slot["ops"] += 1
        slot["bytes"] += hlo_type_bytes(type_str)
    return out
