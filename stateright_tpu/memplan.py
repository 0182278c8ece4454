"""Memory observability: the resident-buffer ledger, compiled-program
memory analysis, and live watermark polling.

Run telemetry (telemetry.py) and mesh telemetry made *time* and
*counters* first-class; this module does the same for the axis that
gates the next two ROADMAP directions — **memory**. The tiered visited
set (direction 1b, "bounded by host memory, not HBM") and the
HBM-staged merge kernel (direction 2b, "once V outgrows VMEM
residency") are capacity decisions: GPUexplore's scalability study
(arXiv:1801.05857) frames device-memory capacity as the binding
constraint on state-space throughput, and the elastic-resource framing
of arXiv:1203.6806 assumes occupancy is *observable* before it can be
tiered. Until now both numbers lived as hand arithmetic in PERF.md.

Three layers, all threaded through the seams the tracer already owns
(untraced programs stay byte-identical — nothing here changes a
compiled program or adds a device sync):

* **Resident-buffer ledger** — each engine declares its resident chunk
  carry (frontier ``[W, F]``, ``vkeys [2, C_pad]``, ``plog``, ebits,
  the wave/shard device logs) with dtype/shape/bytes, derived from
  ``jax.eval_shape`` over the engine's OWN seed program — so the
  declaration cannot drift from the allocation (the plan-vs-``nbytes``
  test pins it on real device arrays). Per-wave *staging* (candidate
  buffers, payloads, mask words) is declared per **ladder class**: the
  plan is a function of the (f, v) class the adaptive ladder
  dispatches, not just the peak — the number that prices what the next
  class step costs. Emitted as a schema-validated ``memory_plan``
  telemetry event (telemetry.py) and kept on the checker
  (``checker.memory_plan``) for untraced consumers (bench.py lane
  details).
* **Compiled-program analysis** — ``Compiled.memory_analysis()``
  (temp/argument/output/alias bytes — XLA's own accounting of the wave
  program) captured at the existing ``compile`` span via an AOT
  lower+compile that the persistent XLA cache dedups, cached here (in
  process and on disk beside the XLA cache) so one traced run per
  config pays it, degrading to ``None`` where the backend doesn't
  report it.
* **Live watermarks** — device bytes-in-use polled ONLY at the
  existing per-chunk sync (no new syncs: the readback already blocked;
  ``device.memory_stats()`` where the backend reports it — TPU/GPU —
  and live-array accounting on CPU, where ``memory_stats()`` is None),
  recorded per chunk and summarized as a ``memory_watermark`` event:
  run peak, host-side visited bytes, observed-peak-vs-capacity
  headroom joined from the persisted auto-budget store, and the
  **capacity projection** — predicted resident bytes at the next
  visited ladder class, the number that decides when V stops fitting
  VMEM.

``tools/mem_report.py`` renders the plan/watermark/headroom table over
a TRACE and writes auto-numbered ``MEM_r*.json`` artifacts;
``tools/trace_diff.py`` aligns the memory counters between two traces
(plan shapes exactly, measured temp/live bytes under the relative
threshold so jax-version skew doesn't false-positive).

Import-light by design (numpy only): tools and tests read traces
without jax; everything touching a device imports jax lazily.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Optional

import numpy as np


# -- the ledger -----------------------------------------------------------


def buffer_entry(name: str, shape, dtype) -> dict:
    """One ledger row: ``{name, shape, dtype, bytes}``. ``bytes`` is
    the unpadded logical size (``prod(shape) * itemsize``) — exactly
    what a device array's ``nbytes`` reports, which is what the
    plan-vs-``nbytes`` consistency test compares against. (TPU tile
    PADDING — the ceil-to-(8,128) tax PERF.md §tile-padding measures —
    is a multiplier on top; the report prints logical bytes and leaves
    padding to the compiled-program analysis, which sees post-layout
    sizes.)"""
    shape = tuple(int(s) for s in shape)
    itemsize = np.dtype(dtype).itemsize
    n = 1
    for s in shape:
        n *= s
    return dict(
        name=name,
        shape=list(shape),
        dtype=str(np.dtype(dtype)),
        bytes=int(n * itemsize),
    )


def plan_entries(spec: dict, *, sharded=(), n_shards: int = 1) -> list:
    """Ledger rows for a carry pytree (a dict of arrays or
    ``ShapeDtypeStruct``s — the output of ``jax.eval_shape`` over an
    engine's seed program). Shapes are GLOBAL; entries named in
    ``sharded`` additionally carry ``per_shard_bytes = bytes /
    n_shards`` (their leading/sharded axis is split across the mesh),
    replicated entries carry their full size per shard."""
    out = []
    for name in sorted(spec):
        leaf = spec[name]
        e = buffer_entry(name, leaf.shape, leaf.dtype)
        if n_shards > 1:
            e["per_shard_bytes"] = (
                e["bytes"] // n_shards if name in sharded else e["bytes"]
            )
            e["sharded"] = name in sharded
        out.append(e)
    return out


def plan_total(entries) -> int:
    return int(sum(e["bytes"] for e in entries))


def session_resident_bytes(checker) -> dict:
    """Pre-run admission pricing for the resident service
    (stateright_tpu/serve.py): the dominant resident-buffer rows of a
    device checker, derivable from CONFIG ALONE — no program build, no
    device work — so the service can refuse an oversized session
    BEFORE it touches the device. Prices the same quantities the full
    ledger declares (visited keys + parent forest via the engine's own
    ``_visited_bytes_per_row``, the frontier block, the candidate
    buffer), as a documented FLOOR: per-ladder-class staging and
    compiled temp bytes land on top once programs build, which is why
    admission compares against a budget the operator sets with
    headroom. Returns ``{visited_bytes, frontier_bytes, cand_bytes,
    total_bytes}``."""
    bpr = int(checker._visited_bytes_per_row())
    n_shards = int(getattr(checker, "n_shards", 1))
    W = int(checker.encoded.width)
    K = int(checker.encoded.max_actions)
    F = int(checker.frontier_capacity)
    visited = int(checker.total_capacity) * bpr
    frontier = n_shards * F * W * 4
    cand = checker.cand_capacity
    if cand in (None, "auto"):
        cand = F * K  # the no-compaction static bound
    cand_bytes = n_shards * int(cand) * W * 4
    return dict(
        visited_bytes=int(visited),
        frontier_bytes=int(frontier),
        cand_bytes=int(cand_bytes),
        total_bytes=int(visited + frontier + cand_bytes),
    )


def fused_session_bytes(fused, n_sessions: int) -> dict:
    """Admission pricing for a FUSED multi-session plan
    (stateright_tpu/batch.py): :func:`session_resident_bytes` over the
    fused engine's config, plus the per-session amortized share — the
    number `CheckService._admit` compares against the device budget
    when deciding whether N sessions fuse or spill to the solo FIFO.
    Config-only, same as the solo pricing: no program build, no
    device work."""
    plan = session_resident_bytes(fused)
    plan["n_sessions"] = int(n_sessions)
    plan["per_session_bytes"] = plan["total_bytes"] // max(
        1, int(n_sessions)
    )
    return plan


def v_class_entries(v_ladder, nf_max: int) -> list:
    """Per-VISITED-ladder-class merge-scratch rows, shared by both
    sort-merge engines' ``_build_info`` (one pricing, no drift): the
    streaming member/merge passes read ``[0, V_v)`` and write the
    merged ``[0, V_v + NF)`` block back — two uint32 key limbs per
    row — so this is what a v-class step costs in class-local
    scratch."""
    return [
        dict(v_class=vc, visited_rows=int(v),
             merge_scratch_bytes=int((v + nf_max) * 8))
        for vc, v in enumerate(v_ladder)
    ]


def decide_hot_rows(capacity: int, v_min: int, v_ladder_step: int,
                    frontier_capacity: int,
                    budget_bytes: int) -> int:
    """The hot/cold split of the tiered visited set (ROADMAP direction
    1b, stateright_tpu/tier.py), decided by the SAME pricing the
    capacity projection reports (``next_vkeys_bytes`` +
    ``next_merge_scratch_bytes``, both ``(V + F) * 8``): the largest
    visited-ladder class whose resident vkeys block PLUS merge
    scratch fit ``budget_bytes`` becomes the hot-tier ceiling —
    everything past it spills to host DRAM.

    Returns ``capacity`` itself when the whole ladder fits (the tier
    stays dormant: the spill watermark is never crossed), and the
    ladder bottom ``v_min`` when even that class exceeds the budget
    (the engine still runs; the hot tier is just minimal). This is
    the ``tier_hot_rows="auto"`` policy — the projection is exactly
    the signal, as the round-12 ledger promised."""
    F = int(frontier_capacity)
    hot = int(min(v_min, capacity))
    v = hot
    while v < capacity:
        v = min(v * v_ladder_step, capacity)
        if 2 * (v + F) * 8 > budget_bytes:
            break
        hot = v
    return hot


def tier_frontier_headroom(capacity: int, frontier_capacity: int,
                           cand_capacity) -> dict:
    """The tiered-mode frontier-headroom bound (the PR 12 known
    bound), pre-checked from the SAME numbers the resident-buffer
    ledger declares — BEFORE any device work, instead of surfacing
    mid-run as an f_overflow message:

    in tiered mode the frontier bound applies to a wave's
    PROVISIONAL winners (hot-tier-new rows before the cold membership
    pass retires spilled duplicates), which can exceed the resident
    run's post-dedup new counts. The only static ceiling on
    provisional winners is the candidate budget ``B``
    (cand_capacity): when ``B <= F`` the bound PROVABLY holds — no
    tiered wave can overflow a frontier the candidate buffer can't
    outproduce; when ``B > F`` the bound is load-dependent and a
    frontier that fits the all-resident run may need headroom once
    the hot tier spills.

    Returns ``{holds, frontier_capacity, cand_capacity,
    required_frontier, message}`` — ``holds`` is True (provable),
    False (violated, ``message`` carries the pinned refuse/warn text
    and ``required_frontier`` the F that makes it provable, = B), or
    None when the budget is still unresolved (a literal ``"auto"``
    not yet replaced by the persisted/heuristic budget — nothing is
    provable or refutable yet, and no message is emitted: a false
    "None exceeds F" claim is worse than silence). Callers with
    ``cand_capacity=None`` (no compaction) should pass the true
    static bound ``F x K`` instead — the engines' ``_pre_run_check``
    does. The engines consume this through ``tier_headroom_policy``
    ("warn" — the documented PR 12 behavior, now surfaced BEFORE
    device work; "bump" — raise frontier_capacity to
    ``required_frontier`` before programs build; "refuse" — raise
    instead of risking a mid-run overflow)."""
    C = int(capacity)
    F = int(frontier_capacity)
    if cand_capacity in (None, "auto"):
        return dict(
            holds=None,
            capacity=C,
            frontier_capacity=F,
            cand_capacity=cand_capacity,
            required_frontier=None,
            message=None,
        )
    B = int(cand_capacity)
    holds = B <= F
    message = None
    if not holds:
        message = (
            "tiered-mode frontier-headroom bound: provisional "
            "winners (hot-tier-new rows before the cold membership "
            "pass) are bounded only by the candidate budget "
            f"cand_capacity={B}, which exceeds "
            f"frontier_capacity={F} — a frontier that fits the "
            "all-resident run may overflow once the hot tier "
            f"spills. Raise frontier_capacity to {B} "
            "(tier_headroom_policy='bump' does this before device "
            "work), or accept the mid-run f_overflow risk "
            "(tier_headroom_policy='warn', the default)."
        )
    return dict(
        holds=holds,
        capacity=C,
        frontier_capacity=F,
        cand_capacity=cand_capacity,
        required_frontier=B,
        message=message,
    )


# -- live watermarks ------------------------------------------------------


def device_bytes_in_use() -> tuple[Optional[int], Optional[str]]:
    """``(bytes, source)`` for the default device, polled at a point
    where the caller has ALREADY synced (the per-chunk stats readback)
    — this function never blocks on device work itself.

    * ``("memory_stats")`` — the backend reports allocator stats
      (TPU/GPU ``device.memory_stats()["bytes_in_use"]``);
    * ``("live_arrays")`` — CPU fallback: ``memory_stats()`` is None
      there, so sum ``nbytes`` over the process's live jax arrays
      (logical bytes; close enough to watch growth and headroom);
    * ``(None, None)`` — neither answerable (never raises)."""
    try:
        import jax

        dev = jax.devices()[0]
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"]), "memory_stats"
        total = 0
        for a in jax.live_arrays():
            total += int(getattr(a, "nbytes", 0))
        return total, "live_arrays"
    except Exception:
        return None, None


# -- compiled-program memory analysis -------------------------------------

#: the CompiledMemoryStats fields the ledger keeps (XLA's accounting of
#: one compiled wave program: scratch/temp, donated-alias, argument and
#: output buffers, plus the executable itself).
COMPILED_FIELDS = (
    "temp_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)

_ANALYSIS_CACHE: dict = {}


def compiled_memory(compiled) -> Optional[dict]:
    """Normalize one ``Compiled.memory_analysis()`` result to a plain
    dict of :data:`COMPILED_FIELDS`, or None where the backend doesn't
    report it (older jax, stripped runtimes — degrade, never raise)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    any_real = False
    for k in COMPILED_FIELDS:
        v = getattr(ma, k, None)
        if v is None:
            out[k] = None
        else:
            out[k] = int(v)
            any_real = True
    return out if any_real else None


def cache_root() -> str:
    """The one directory every on-disk cache of this package lives in
    (XLA compile cache, auto-budget store, memory-analysis store):
    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else the fixed,
    git-ignored ``.stpu_cache`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(here), ".stpu_cache")


def _analysis_store() -> str:
    return os.path.join(cache_root(), "stateright_tpu_memory_analysis.json")


def compiled_memory_analysis(chunk_fn, carry_spec,
                             cache_token,
                             on_build=None) -> Optional[dict]:
    """``memory_analysis()`` of an engine's compiled chunk program,
    via an AOT ``lower().compile()`` the persistent XLA compile cache
    dedups against the dispatch-path compile. Results are cached in
    process AND persisted beside the XLA cache (keyed by the engine's
    program cache token + backend), so one traced run per
    configuration pays the AOT pass and later runs — including the
    overhead-measurement pools — read it back. A backend that can't
    REPORT the analysis caches its None (that answer is stable); a
    FAILED lower/compile raises, uncached: the same program is about
    to run on the device, and its compiler's refusal is the finding.

    ``on_build`` (round 14, the compile-cache ledger): called exactly
    once per RESOLVED lookup as ``on_build(tier, wall_sec)`` —
    ``"in_process"`` / ``"disk"`` for this module's result caches,
    ``"aot"`` when the AOT lower+compile actually ran (the caller
    refines that tier from its compile monitor: the AOT pass itself
    may hit the persistent XLA cache). Not called when the compile
    raises — that produced nothing to ledger."""
    import time as _time

    import jax

    t0 = _time.monotonic()
    key = hashlib.sha1(
        f"{jax.default_backend()}/{cache_token!r}".encode()
    ).hexdigest()
    if key in _ANALYSIS_CACHE:
        if on_build is not None:
            on_build("in_process", _time.monotonic() - t0)
        return _ANALYSIS_CACHE[key]
    # disk: survives processes the way the XLA cache does
    store = _analysis_store()
    try:
        with open(store) as fh:
            disk = json.load(fh)
        if key in disk:
            _ANALYSIS_CACHE[key] = disk[key]
            if on_build is not None:
                on_build("disk", _time.monotonic() - t0)
            return disk[key]
    except (OSError, ValueError):
        pass
    compiled = chunk_fn.lower(carry_spec).compile()
    if on_build is not None:
        on_build("aot", _time.monotonic() - t0)
    result = compiled_memory(compiled)
    _ANALYSIS_CACHE[key] = result
    try:
        os.makedirs(os.path.dirname(store), exist_ok=True)
        data = {}
        try:
            with open(store) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            pass
        data[key] = result
        tmp = store + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(data, fh)
        os.replace(tmp, store)
    except OSError:
        pass
    return result


# -- rendering helpers ----------------------------------------------------


def format_bytes(n) -> str:
    """Human-readable byte count ('-' for None)."""
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return (f"{n:,.0f} {unit}" if unit == "B"
                    else f"{n:,.2f} {unit}")
        n /= 1024.0
    return f"{n:,.2f} GB"


# -- MEM artifacts --------------------------------------------------------


def write_memory_artifact(summary: dict, root: Optional[str] = None,
                          ) -> str:
    """Write one auto-numbered ``MEM_r*.json`` artifact (the memory
    summary of one traced run, tools/mem_report.py's ``--json``
    output). MEM numbers in its OWN round sequence (``MEM_r01`` first)
    rather than the shared BENCH/LINT/TRACE sequence: a MEM artifact
    is *derived from* a TRACE and names it (``summary["trace"]``), so
    the cross-reference — not a shared counter — is what pairs it with
    a perf round. Numbering still flows through the one home in
    artifacts.py."""
    from .artifacts import artifact_path, next_round, provenance, repo_root

    root = repo_root() if root is None else root
    path = artifact_path(
        "MEM", "json", root=root,
        round=next_round(root, stems=("MEM",)),
    )
    doc = dict(summary)
    doc.setdefault("provenance", provenance())
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path
