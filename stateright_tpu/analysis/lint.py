"""The kernel-lint driver: trace every contract path, run the rules.

For each registered encoding (:mod:`.registry`) the driver traces

* ``bits`` — ``vmap(enabled_bits_vec)``: the word-native mask path
  the sparse engines consume,
* ``bits[t]`` — the TRANSPOSED invocation of the same path
  (``enabled_bits_cols`` over a ``[W, N]`` block — the round-9
  resident layout, registry.TRANSPOSED_PATHS), same rules and
  allowances,
* ``mask`` — ``vmap(enabled_mask_vec)``: the dense contract view
  (bool[K] IS its return type, so the dense-mask rule is off; the
  gather rule still applies),
* ``step`` — ``vmap(step_slot_vec)``: the per-pair transition path,
* ``step[t]`` / ``step[t1]`` — the transposed-successor pair step
  in BOTH backend seams (``step_slot_cols_fn``: row states in for
  the TPU invocation, ``[W, N]`` column states in for the XLA:CPU
  one; ``[W, N]`` successors out either way), same table-gather
  allowance,
* ``engine:single`` — the shared sparse pair pipeline
  (checkers/tpu_sortmerge.py ``sparse_pair_candidates``) exactly as
  the single-chip engine invokes it — with the ``[W, N]`` frontier
  (registry.ENGINE_LAYOUT),
* ``engine:sharded`` — the same pipeline under ``shard_map`` with
  ``axis_name="shard"``, exactly as the sharded engine
  (parallel/engine_sortmerge.py) invokes it,

and runs the full rule registry (:mod:`.rules`) over each. Separate
wave-body fixtures trace each engine's ENTIRE per-wave program
(class-ladder switch included) on a small 2pc model so the
branch-shape rule and the carry-copy-bytes estimator see the real
switch structure — the thing the per-path traces can't show: the
single-chip body once per merge implementation, and (round 11) the
SHARDED body in its TRACED form, so the per-shard mesh-log append
(``slog``/``swave``, telemetry.SHARD_LOG_FIELDS) is priced by the
same five gated rules and the carry-copy budget.

Everything here runs on CPU: jaxprs are backend-independent, which is
what lets a CPU-only CI run refuse an encoding or engine change that
re-introduces a priced codegen artifact before it ever reaches a
chip.
"""

from __future__ import annotations

from typing import Optional

from .registry import ENCODINGS, EncodingSpec
from .rules import (
    RULES,
    Finding,
    TraceCtx,
    run_rules,  # noqa: F401 — re-exported for single-path callers
    run_rules_with_stats,
)

#: batch rows in every traced vmap / engine trace — any fixed N works
#: (the banned shapes are N-relative); 64 matches the codegen-shape
#: tests' calibration.
LINT_N = 64


def trace_encoding_paths(enc, n: int = LINT_N) -> dict:
    """``{label: ClosedJaxpr}`` for the per-encoding contract paths,
    traced at ``n`` batch rows — the row-major contract views (bits /
    mask / step) AND the transposed ``[W, N]`` invocations the engines
    actually run (``bits[t]`` / ``step[t]``, registry.TRANSPOSED_PATHS
    — the round-9 resident layout; same rules, same allowances)."""
    import jax
    import jax.numpy as jnp

    from ..encoding import enabled_bits_cols, step_slot_cols_fn

    vecs = jnp.zeros((n, enc.width), jnp.uint32)
    vecs_t = jnp.zeros((enc.width, n), jnp.uint32)
    slots = jnp.zeros((n,), jnp.uint32)
    return {
        "bits": jax.make_jaxpr(jax.vmap(enc.enabled_bits_vec))(vecs),
        "bits[t]": jax.make_jaxpr(
            lambda v: enabled_bits_cols(enc, v)
        )(vecs_t),
        "mask": jax.make_jaxpr(jax.vmap(enc.enabled_mask_vec))(vecs),
        "step": jax.make_jaxpr(jax.vmap(enc.step_slot_vec))(
            vecs, slots
        ),
        # BOTH backend seams of the transposed pair step: states_axis
        # 0 is the TPU invocation (row states off the seam-transpose
        # gather), states_axis 1 the XLA:CPU one (resident columns
        # gathered directly) — the engines pick per backend
        # (tpu_sortmerge/engine_sortmerge), so the gate must pin both.
        "step[t]": jax.make_jaxpr(
            step_slot_cols_fn(enc, states_axis=0)
        )(vecs, slots),
        "step[t1]": jax.make_jaxpr(
            step_slot_cols_fn(enc, states_axis=1)
        )(vecs_t, slots),
    }


def _shard_map_1dev(fn, in_specs):
    """Wrap ``fn`` in ``shard_map`` over a 1-device mesh with
    ``axis_name="shard"`` — the sharded engines' axis plumbing, which
    is what a sharded trace pins; the varying-ness checker is off."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )


def trace_canonical_paths(enc, n: int = LINT_N) -> dict:
    """``{label: ClosedJaxpr}`` of the symmetry-canonicalization
    kernel paths (registry.CANONICAL_PATHS) — empty when the encoding
    declares no ``DeviceRewriteSpec``, so the audit is gated on the
    SAME capability probe the engines use. Three invocation styles:
    row-major ``canonicalize_rows`` (the host-replay contract view),
    the transposed ``canonicalize_t`` over ``[W, N]`` the engines run
    between step and fingerprint (``canon[t]``), and that invocation
    under ``shard_map`` (``canon:sharded`` — the sharded engine
    canonicalizes before the (owner, fp) routing seam)."""
    import jax
    import jax.numpy as jnp

    from ..encoding import device_rewrite_spec
    from ..ops.canonical import canonicalize_rows, canonicalize_t

    spec = device_rewrite_spec(enc)
    if spec is None:
        return {}
    from jax.sharding import PartitionSpec as P

    rows = jnp.zeros((n, enc.width), jnp.uint32)
    cols = jnp.zeros((enc.width, n), jnp.uint32)
    return {
        "canon": jax.make_jaxpr(
            lambda r: canonicalize_rows(spec, r, jnp)
        )(rows),
        "canon[t]": jax.make_jaxpr(
            lambda c: canonicalize_t(spec, c, jnp)
        )(cols),
        "canon:sharded": jax.make_jaxpr(
            _shard_map_1dev(
                lambda c: canonicalize_t(spec, c, jnp), (P(),)
            )
        )(cols),
    }


def engine_pair_width(enc) -> int:
    K = enc.max_actions
    return min(getattr(enc, "pair_width_hint", None) or K, K)


def engine_pipe_params(enc, n: int = LINT_N,
                       compact: bool = False) -> dict:
    """The ``sparse_pair_candidates`` kwargs of the traced engine
    invocation — ONE recipe shared by the jaxpr traces below and the
    tool's ``--hlo`` compile pass, so the two always price the same
    program.

    ``compact=False`` is the small-wave shape (``B_p == F*EV``, no
    compaction, whole-wave mask); ``compact=True`` forces the
    PRODUCTION branches the big bench lanes run — ``B_p < F*EV``
    (tiled packed-append compaction sorts) and a mask-cell budget
    below ``F*K`` (the tiled ``mtile`` mask loop) — which would
    otherwise never be audited."""
    EV = engine_pair_width(enc)
    K = enc.max_actions
    if compact:
        NT = 2
        T = n // NT
        B_p = max((n * EV) // 2, 1)
        return dict(
            EV=EV, B_p=B_p, NT=NT, T=T,
            mask_budget_cells=max(K, (n * K) // 4),
            Ba=B_p + T * EV,
        )
    return dict(
        EV=EV, B_p=n * EV, NT=1, T=n,
        mask_budget_cells=1 << 30, Ba=n * EV,
    )


def engine_trace_operands(enc, n: int = LINT_N) -> tuple:
    """``(frontier, fval, n_rows)`` of the traced engine invocation —
    the FULL resident ``[W, 2n]`` carry buffer with the class width
    ``n`` passed explicitly via ``n_rows``, exactly as both engines
    call ``sparse_pair_candidates`` since round 9 (capacity > class
    width on any real run, so the gated jaxpr must slice the larger
    buffer too: a codegen artifact specific to the n_rows path — a
    materialized strided-prefix copy, say — has to show up HERE, not
    first on a chip). Shared by the jaxpr traces and the tool's
    ``--hlo`` compile pass."""
    import jax.numpy as jnp

    frontier = jnp.zeros((enc.width, 2 * n), jnp.uint32)
    fval = jnp.zeros((n,), bool)
    return frontier, fval, n


def trace_engine_pipeline(enc, engine: str = "single",
                          n: int = LINT_N, compact: bool = False):
    """Trace ``sparse_pair_candidates`` at ``n`` frontier rows, in the
    exact invocation style of each engine: ``single`` is the
    single-chip call; ``sharded`` wraps the call in ``shard_map`` with
    ``axis_name="shard"`` over a 1-device mesh (the axis plumbing —
    ``lax.pcast`` carries etc. — is what differs, and is what this
    trace pins). ``compact`` selects the production
    compaction/tiled-mask branches (see :func:`engine_pipe_params`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..checkers.tpu_sortmerge import sparse_pair_candidates

    params = engine_pipe_params(enc, n, compact)
    # The [W, N] resident layout (registry.ENGINE_LAYOUT): the traced
    # pipeline IS the engines' transposed invocation — full carry
    # buffer, class width via n_rows (engine_trace_operands).
    frontier, fval, n_rows = engine_trace_operands(enc, n)

    def pipe(frontier_t, fval, axis_name=None):
        return sparse_pair_candidates(
            enc, frontier_t, fval, jnp.bool_(True),
            axis_name=axis_name, n_rows=n_rows, **params,
        )
    if engine == "single":
        return jax.make_jaxpr(pipe)(frontier, fval)
    if engine != "sharded":
        raise ValueError(f"unknown engine {engine!r}")

    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    # The varying-ness checker must be off (the pipeline's fori_loop
    # carries are shard-varying, same as the engine's own usage).
    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    sm = shard_map(
        lambda fr, fv: pipe(fr, fv, axis_name="shard"),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.make_jaxpr(sm)(frontier, fval)


def trace_wave_body_fixture(track_paths: bool = True,
                            merge_impl: str = "xla"):
    """``(name, ClosedJaxpr)`` of the single-chip sort-merge engine's
    full wave body — class ladders, merge switches, fetch-class
    branches — built (never run) on a small 2pc model with short
    ladders so the switch structure is multi-class. Abstract-traced
    via ``eval_shape`` on the seed program, so no device buffers are
    allocated. ``merge_impl`` selects the visited-dedup invocation
    style (round 10): the gate traces the wave body once per
    implementation so the branch rules and the carry-copy budget
    price both the XLA-fallback and the Pallas-kernel wave programs
    (tables.CARRY_COPY_BYTE_BUDGETS keys both names)."""
    import jax
    import jax.numpy as jnp

    from ..models.two_phase_commit import TwoPhaseSys

    checker = TwoPhaseSys(rm_count=3).checker().spawn_tpu_sortmerge(
        capacity=1 << 11,
        frontier_capacity=1 << 9,
        cand_capacity=1 << 11,
        f_min=64,
        v_min=256,
        track_paths=track_paths,
        waves_per_sync=4,
        merge_impl=merge_impl,
    )
    init = jnp.asarray(checker.encoded.init_vecs())
    seed_fn, _chunk_fn = checker._build_programs(init.shape[0])
    carry_shapes = jax.eval_shape(seed_fn, init)
    tag = "" if merge_impl == "xla" else f",merge={merge_impl}"
    return (
        f"engine-fixture(2pc-rm3{tag})",
        jax.make_jaxpr(checker._wave_body)(carry_shapes),
    )


def trace_sharded_wave_body_fixture(track_paths: bool = True):
    """``(name, ClosedJaxpr)`` of the SHARDED sort-merge engine's full
    wave body — the routing sort, dest tiles, ``all_to_all``, merge
    switches — in its TRACED form (round 11): the per-shard mesh log
    (``slog``/``swave``, telemetry.SHARD_LOG_FIELDS) is part of the
    program, so the five gated rules and the carry-copy-bytes budget
    price the log-append path the mesh runs actually execute
    (registry.SHARDED_WAVE_BODY_FIXTURE keys the budget). Built on a
    1-device mesh (the axis plumbing, not the device count, is what
    the trace pins) with the same small 2pc model and short ladders
    as the single-chip fixture; abstract-traced via ``eval_shape`` on
    the seed program, so no buffers are allocated."""
    import jax
    import jax.numpy as jnp

    from ..models.two_phase_commit import TwoPhaseSys
    from .registry import SHARDED_WAVE_BODY_FIXTURE

    checker = TwoPhaseSys(rm_count=3).checker().spawn_tpu_sharded_sortmerge(
        n_shards=1,
        capacity=1 << 11,
        frontier_capacity=1 << 9,
        cand_capacity=1 << 11,
        bucket_capacity=1 << 10,
        f_min=64,
        v_min=256,
        track_paths=track_paths,
        waves_per_sync=4,
        merge_impl="xla",
    )
    # Force the traced program: the per-shard log path is the thing
    # this fixture registers (a truthy tracer stand-in flips the
    # _wave_log_enabled gate exactly as a real RunTracer would).
    checker._tracer = object()
    init = jnp.asarray(checker.encoded.init_vecs())
    seed_fn, _chunk_fn = checker._build_programs(init.shape[0])
    carry_shapes = jax.eval_shape(seed_fn, init)
    return (
        SHARDED_WAVE_BODY_FIXTURE,
        jax.make_jaxpr(checker._wave_body_sm)(carry_shapes),
    )


def trace_merge_kernels(n: int = LINT_N) -> dict:
    """``{label: ClosedJaxpr}`` of the streaming-merge dedup ops
    (registry.MERGE_KERNEL_PATHS): membership and visited append,
    each in both implementations, at a production-shaped fixture —
    a sorted 8n-row visited prefix, 4n sorted candidates, an n-row
    winner block (jaxprs are shape-relative, so any fixed multiple
    works; these mirror the engines' V ≫ B ≫ NF ordering). Pallas
    paths trace on CPU too (``pallas_call`` abstract-evals without
    running), so the CPU CI audits the kernel invocation the chip
    will run."""
    import jax
    import jax.numpy as jnp

    from ..ops.merge import member_sorted, merge_sorted

    V, B, NF = 8 * n, 4 * n, n
    a = (jnp.zeros(V, jnp.uint32), jnp.zeros(V, jnp.uint32))
    q = (jnp.zeros(B, jnp.uint32), jnp.zeros(B, jnp.uint32))
    w = (jnp.zeros(NF, jnp.uint32), jnp.zeros(NF, jnp.uint32))
    out = {}
    for impl in ("xla", "pallas"):
        out[f"merge:member:{impl}"] = jax.make_jaxpr(
            lambda al, ah, ql, qh, _i=impl: member_sorted(
                al, ah, ql, qh, impl=_i
            )
        )(*a, *q)
        out[f"merge:append:{impl}"] = jax.make_jaxpr(
            lambda al, ah, bl, bh, _i=impl: merge_sorted(
                al, ah, bl, bh, impl=_i
            )
        )(*a, *w)
    return out


def lint_merge_kernels(n: int = LINT_N) -> tuple:
    """Run the rule registry over the merge-kernel invocations.
    Gathers are unaudited by design on these paths — the XLA
    fallback's vectorized binary search IS gathers, and the Pallas
    partition search is too; what the rules pin is the absence of
    dense masks and (on the XLA fallback) the 1-D lane discipline.
    The in-kernel [block, block] rank temporaries are the kernel's
    own idiom, so the lane-ALU rule stays off the pallas paths."""
    findings: list = []
    stats: list = []
    for label, closed in trace_merge_kernels(n).items():
        ctx = TraceCtx(
            path=label,
            encoding="ops/merge",
            n=n,
            k=0,
            sparse=False,
            allow_gathers=None,
            check_lane_alu=label.endswith(":xla"),
            check_branches=False,
        )
        fs, n_eqns = run_rules_with_stats(ctx, closed)
        findings.extend(fs)
        stats.append(
            dict(
                encoding="ops/merge",
                path=label,
                eqns=n_eqns,
                errors=sum(1 for f in fs if f.severity == "error"),
            )
        )
    return findings, stats


def _ctx_for_path(spec: EncodingSpec, enc, label: str,
                  n: int = LINT_N) -> TraceCtx:
    K = enc.max_actions
    if label in ("bits", "bits[t]"):
        # the transposed invocation runs the SAME rules at the same
        # allowances — the [W, N] batching must not re-grow a gather
        # or a lane-padded op the row-major view is pinned clean of.
        return TraceCtx(path=label, encoding=spec.name, n=n, k=K,
                        sparse=True, allow_gathers=0,
                        check_lane_alu=True)
    if label in ("canon", "canon[t]", "canon:sharded"):
        # the canonicalization kernel is held to the bits-path bar:
        # gather-free (rank via comparison counts + one-hot
        # select-sums — a permutation gather here is exactly the
        # priced artifact) and no lane-padded ALU; the sharded
        # invocation additionally runs the comms rules (the kernel is
        # collective-free by construction — canonicalization happens
        # BEFORE the routing seam, per shard, with no coordination).
        return TraceCtx(path=label, encoding=spec.name, n=n, k=K,
                        sparse=True, allow_gathers=0,
                        check_lane_alu=True,
                        check_comms=label == "canon:sharded")
    if label == "mask":
        # bool[K] is this path's CONTRACT (the dense view); only the
        # gather rule applies.
        return TraceCtx(path=label, encoding=spec.name, n=n, k=K,
                        sparse=False, allow_gathers=0,
                        check_lane_alu=False)
    if label in ("step", "step[t]", "step[t1]"):
        return TraceCtx(path=label, encoding=spec.name, n=n, k=K,
                        sparse=False,
                        allow_gathers=spec.max_step_gathers,
                        check_lane_alu=True, table_path=True)
    # engine pipelines: word ops are [N, L]-shaped by design (L == 1
    # collapses them to [N, 1] for small-K encodings), so the lane-ALU
    # rule stays off here; the dense-mask and gather bans are the
    # engine contract. The peel's [N, EV] pair-validity grid is by
    # design — when EV == K (tiny action sets) it is shape-identical
    # to the dense mask, so the dense-mask rule needs a real sparse
    # pair width (the same precondition the codegen-shape tests
    # calibrated). check_comms rides along (round 13): the pipeline
    # contains no collectives today, and the comms rules pin exactly
    # that — an all_gather sneaking in via sharding propagation (or a
    # buffer-sized psum added to the pair pipeline) fails here, not
    # first on a mesh.
    return TraceCtx(path=label, encoding=spec.name, n=n, k=K,
                    sparse=engine_pair_width(enc) < K,
                    allow_gathers=0, check_lane_alu=False,
                    check_comms=True)


def lint_encoding(spec: EncodingSpec,
                  engines: tuple = ("single", "sharded"),
                  n: int = LINT_N) -> tuple:
    """Run the rule registry over one encoding's contract paths.
    Returns ``(findings, path_stats)``."""
    enc = spec.factory()
    findings: list = []
    stats: list = []
    traced = trace_encoding_paths(enc, n)
    # capability-gated (registry.CANONICAL_PATHS): empty dict for
    # encodings without a DeviceRewriteSpec
    traced.update(trace_canonical_paths(enc, n))
    for engine in engines:
        # both the small-wave shape and the production
        # compaction/tiled-mask shape (the branch the big bench
        # lanes actually run) — see engine_pipe_params.
        traced[f"engine:{engine}"] = trace_engine_pipeline(
            enc, engine, n
        )
        traced[f"engine:{engine}+compact"] = trace_engine_pipeline(
            enc, engine, n, compact=True
        )
    for label, closed in traced.items():
        ctx = _ctx_for_path(spec, enc, label, n)
        fs, n_eqns = run_rules_with_stats(ctx, closed)
        if label.startswith("engine:") and not ctx.sparse:
            # EV == K: the peel's [N, EV] pair-validity grid is
            # shape-identical to the dense mask, so the dense-mask
            # rule cannot run on this path — record the skip loudly
            # instead of reporting an indistinguishable "0 errors"
            # (the coverage claim must stay honest).
            fs.append(Finding(
                rule="no-dense-mask",
                severity="info",
                encoding=spec.name,
                path=label,
                message=(
                    f"rule SKIPPED on this path: pair width EV == K "
                    f"= {enc.max_actions}, so the by-design [N, EV] "
                    "pair-validity grid is shape-identical to the "
                    "dense mask (the rule needs a real sparse pair "
                    "width; the bits-path audit still covers this "
                    "encoding's mask construction)"
                ),
            ))
        findings.extend(fs)
        stats.append(
            dict(
                encoding=spec.name,
                path=label,
                eqns=n_eqns,
                errors=sum(1 for f in fs if f.severity == "error"),
            )
        )
    return findings, stats


def lint_wave_body(merge_impl: str = "xla") -> tuple:
    """Run the branch-shape rule and the carry-copy-bytes estimator
    over the engine wave-body fixture (once per merge
    implementation; see trace_wave_body_fixture)."""
    name, closed = trace_wave_body_fixture(merge_impl=merge_impl)
    return _lint_traced_wave_body(name, closed)


def lint_sharded_wave_body() -> tuple:
    """Same rules over the sharded engine's TRACED wave body (the
    per-shard log path; see trace_sharded_wave_body_fixture)."""
    name, closed = trace_sharded_wave_body_fixture()
    return _lint_traced_wave_body(name, closed)


def _lint_traced_wave_body(name: str, closed) -> tuple:
    ctx = TraceCtx(
        path="wave-body",
        encoding=name,
        n=LINT_N,
        k=0,
        sparse=False,
        allow_gathers=None,  # winner-fetch gathers are the idiom
        check_lane_alu=False,
        check_branches=True,
    )
    findings, n_eqns = run_rules_with_stats(ctx, closed)
    stats = [
        dict(
            encoding=name,
            path="wave-body",
            eqns=n_eqns,
            errors=sum(1 for f in findings if f.severity == "error"),
        )
    ]
    return findings, stats


def run_lint(encodings: Optional[tuple] = None,
             engines: tuple = ("single", "sharded"),
             wave_body: bool = True,
             n: int = LINT_N) -> dict:
    """The whole gate: every registered encoding × the requested
    engine pipelines, plus the wave-body fixture. Returns a report
    dict (the ``--json`` artifact's content):

    ``clean``
        True iff no error-severity finding anywhere.
    ``findings``
        every finding (errors AND the informational carry-copy-bytes
        estimates), source-attributed.
    ``paths``
        per-(encoding, path) equation counts and error counts — the
        audit's coverage record.
    """
    specs = encodings if encodings is not None else ENCODINGS
    all_findings: list = []
    all_stats: list = []
    for spec in specs:
        fs, st = lint_encoding(spec, engines, n)
        all_findings.extend(fs)
        all_stats.extend(st)
    fs, st = lint_merge_kernels(n)
    all_findings.extend(fs)
    all_stats.extend(st)
    if wave_body:
        for impl in ("xla", "pallas"):
            fs, st = lint_wave_body(merge_impl=impl)
            all_findings.extend(fs)
            all_stats.extend(st)
        # the sharded engine's TRACED wave body — the per-shard mesh
        # log path (round 11, registry.SHARDED_WAVE_BODY_FIXTURE)
        fs, st = lint_sharded_wave_body()
        all_findings.extend(fs)
        all_stats.extend(st)
    errors = [f for f in all_findings if f.severity == "error"]
    return dict(
        clean=not errors,
        n=n,
        engines=list(engines),
        rules=[
            dict(name=r.name, description=r.description)
            for r in RULES
        ],
        paths=all_stats,
        findings=[f.as_dict() for f in all_findings],
    )


def format_report(report: dict) -> str:
    """Human-readable lint report (tools/lint_kernels.py prints
    this)."""
    lines = []
    lines.append(
        f"kernel-lint: {len(report['rules'])} rules x "
        f"{len(report['paths'])} traced paths "
        f"(N={report['n']}, engines={'+'.join(report['engines'])})"
    )
    lines.append(f"  {'encoding':28s} {'path':24s} {'eqns':>6s} "
                 f"{'errors':>7s}")
    for p in report["paths"]:
        lines.append(
            f"  {p['encoding']:28s} {p['path']:24s} "
            f"{p['eqns']:6d} {p['errors']:7d}"
        )
    errors = [f for f in report["findings"]
              if f["severity"] == "error"]
    infos = [f for f in report["findings"] if f["severity"] == "info"]
    for f in errors:
        loc = f" @ {f['source']}" if f.get("source") else ""
        lines.append(
            f"ERROR [{f['rule']}] {f['encoding']} / {f['path']}: "
            f"{f['message']}{loc}"
        )
    for f in infos:
        lines.append(
            f"info  [{f['rule']}] {f['encoding']} / {f['path']}: "
            f"{f['message']}"
        )
    lines.append(
        "CLEAN — the sparse-engine codegen contract holds"
        if report["clean"]
        else f"{len(errors)} contract violation(s)"
    )
    return "\n".join(lines)
