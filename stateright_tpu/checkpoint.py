"""Checkpoint/resume with elastic re-shard: durability for the wave
engines.

The memory ledger (memplan.py) already declares exactly which buffers
constitute a run — every engine's chunk carry is a named pytree the
seed program's ``eval_shape`` spec pins. This module is the other half
ROADMAP direction 1(c) names: *serialize* that declared carry at the
existing per-chunk sync (the stats readback already blocked — no new
device syncs, just a piggybacked download at the same seam), and
*restore* it so a preemption, OOM, or crash costs one chunk of
progress instead of the whole search (the elastic/preemptible
execution framing of arXiv:1203.6806's checking-as-a-cloud-service).

**Snapshot format.** One file: an ``.npz`` container holding every
chunk-carry leaf (visited ``vkeys``/hash tables, frontier, ebits, the
parent log, counters, and the cumulative discovery lanes) plus a JSON
manifest under the reserved ``__manifest__`` entry — version, git
SHA, encoding fingerprint, engine family, shard count, per-shard
capacities, wave/depth/unique at capture, the persisted auto-budget
state, and a per-buffer CRC-32. Writes are atomic: temp file →
flush → fsync → ``os.replace`` — a crash mid-write leaves the
previous snapshot intact, and a genuinely torn file (truncation,
bit rot) fails the zip/CRC checks and raises
:class:`SnapshotCorruptError` on load. No pickle anywhere
(``allow_pickle`` stays False): a snapshot is data, not code.

**Resume** (:func:`resume_from`):

* SAME configuration — direct upload: every leaf shape-checked
  against the current seed program's ``eval_shape`` spec, trace-gated
  leaves (the wave/shard logs) synthesized to match the resuming
  run's tracer state, sharded leaves placed with the engine's own
  ``PartitionSpec``\\ s;
* DIFFERENT shard count / capacity — elastic **re-shard**, on the
  sort-merge family (the single-chip sort-merge engine and the mesh
  engine): per-shard visited prefixes, frontier blocks, and
  parent-log entries are concatenated and re-routed host-side through
  the exact (owner, fp) ordering the mesh wave's routing sort uses
  (owner = ``fp_lo % S``, keys ordered ``(hi, lo)`` — the
  ``lax.sort`` seam of parallel/engine_sortmerge.py, as
  ``np.lexsort``), then re-uploaded at the new layout. Shard count
  becomes a resume-time choice, not a run-time constant; single-chip
  ⇄ sharded conversions ride the same path (single-chip is the
  S=1 layout). The hash family (the single-chip hash-table engine)
  resumes same-config only and refuses any re-layout loudly.

Staleness is refused, never guessed around: a manifest whose encoding
fingerprint disagrees with the target checker, or whose git SHA
differs from HEAD (override with ``allow_sha_mismatch=True`` when you
know the carry layout didn't change), raises
:class:`SnapshotStaleError` — the fault-injection matrix
(stateright_tpu/faultinject.py + tools/crash_matrix.py) pins all four
failure modes on recover-or-refuse-loudly, none on silent wrong
answers.

**Supervision** (:func:`supervised_run`): engines route ``_run``
through here. With checkpointing configured, a failed chunk — device
error, injected fault, OOM, watchdog hang — retries from the last
snapshot with bounded exponential backoff instead of dying; repeated
OOMs degrade the sort-merge engines to their CHUNKED memory-lean
classes (``_degrade_memory_lean``) before the next attempt. Engine
overflow errors are NOT supervised: the auto-budget retry
(tpu_sortmerge.py) owns those, one layer out.

**Degrade-and-continue** (the round-17 policy layer): every
supervised failure is CLASSIFIED by a :class:`FailurePolicy`
(:func:`classify_failure` — transient / oom / hang / shard_fault,
from the exception and the health layer's straggler evidence), and a
fault that persists on the same shard escalates — under
``degrade_on_fault`` — to an automatic elastic degrade: the shard is
dropped from the mesh and the last snapshot re-shards onto the
survivors through the same (owner, fp) seam, so a dead chip costs
capacity, not the run. :func:`watchdog_deadline` derives the
hung-dispatch watchdog's per-chunk deadline (checkers/tpu.py) from
the run's own measured chunk walls; a breach is a supervised
``hang`` that recovers from the snapshot or — when the runtime
can't be cancelled — refuses loudly with the latency attribution.
"""

from __future__ import annotations

import json
import os
import time
import warnings
import zlib
from typing import Any, Optional

import numpy as np

SNAPSHOT_VERSION = 1

_SENT = 0xFFFFFFFF

#: carry leaves resume may synthesize (zeros) when the snapshot lacks
#: them or disagrees on their shape (tracer state, waves_per_sync, a
#: re-shard): trace-gated telemetry rewritten inside the chunk before
#: any row is read, the sharded engine's per-pair-class and
#: per-receive-class wave counters (telemetry, restarting at zero)
#: and its routed-payload scratch.
_SYNTH_LEAVES = frozenset({"wlog", "slog", "swave", "wv_pairs",
                           "wv_canon", "pstash", "route", "recv_waves",
                           "pair_waves"})

#: carry leaves a snapshot leaves out: scratch rewritten inside every
#: wave before any row is read (the sharded engine's routed payload),
#: so resume synthesizes them like the trace-gated leaves above.
_SCRATCH_LEAVES = frozenset({"route"})

#: tiered-mode carry leaves a snapshot may carry on top of the
#: untiered spec (the deferred-commit staging of stateright_tpu/
#: tier.py). Resume FOLDS them host-side — the pending wave is
#: committed through the same cold-membership pass the device commit
#: would have run — so the restored carry is always the untiered
#: shape and re-shard sees only confirmed state.
_TIER_LEAVES = ("pend_keys", "pend_par", "pend_n", "pend_valid",
                "n_hot", "h_loc", "pstash")


def auto_cadence(snapshot_sec: float, chunk_sec: float,
                 target: float = 0.05, lo: int = 1,
                 hi: int = 256) -> int:
    """``--checkpoint-every=auto``: the cadence (chunks per snapshot)
    that keeps checkpoint overhead under ``target`` of run wall,
    from the two walls the run itself measures — the snapshot write
    wall and the per-chunk wall. Every N chunks, one snapshot costs
    ``snapshot_sec / (N * chunk_sec)`` relative overhead, so the
    smallest N meeting the target is
    ``ceil(snapshot_sec / (target * chunk_sec))``, clamped to
    ``[lo, hi]`` (hi bounds the progress lost to a crash; lo is
    every-chunk). Degenerate inputs answer conservatively: an
    unmeasured snapshot wall checkpoints every chunk, an unmeasured
    chunk wall checkpoints at the cap."""
    import math

    if not snapshot_sec or snapshot_sec <= 0:
        return lo
    if not chunk_sec or chunk_sec <= 0:
        return hi
    n = math.ceil(snapshot_sec / (target * chunk_sec))
    return max(lo, min(hi, int(n)))


class SnapshotError(RuntimeError):
    """Base of every named checkpoint/resume refusal."""


class SnapshotCorruptError(SnapshotError):
    """The snapshot file is torn or corrupt (failed zip read, missing
    buffer, or a per-buffer CRC mismatch)."""


class SnapshotStaleError(SnapshotError):
    """The manifest doesn't match the resuming checker (wrong encoding
    fingerprint or wrong git SHA)."""


class SnapshotIncompatibleError(SnapshotError):
    """The snapshot can't be restored into this engine configuration
    (family mismatch, track_paths flip, a target capacity too small
    for the carried state, or a re-layout of a hash-family snapshot)."""


# -- identity -------------------------------------------------------------


def encoding_fingerprint(checker) -> str:
    """The stable identity of what a snapshot's carry MEANS: the
    encoding (class, declared cache key, width, action count) plus the
    property list and eventually-bit seed. Two checkers with equal
    fingerprints interpret the same carry identically; anything else
    is a stale snapshot, not a resumable one."""
    enc = checker.encoded
    key_fn = getattr(enc, "cache_key", None)
    ident = repr(key_fn()) if key_fn is not None else ""
    props = tuple(
        (p.name, p.expectation.name)
        for p in checker.model.properties()
    )
    return (
        f"{type(enc).__name__}/{ident}/W{enc.width}/K{enc.max_actions}"
        f"/props{props!r}/ebits{checker._eventually_bits_init()}"
    )


def _git_sha() -> Optional[str]:
    from .artifacts import _git_sha as sha, repo_root

    return sha(repo_root())


def _engine_kind(checker) -> str:
    return "sharded" if getattr(checker, "mesh", None) is not None \
        else "single"


# -- file format ----------------------------------------------------------


def _write_file(path: str, manifest: dict, buffers: dict) -> None:
    """Atomic snapshot write: temp + fsync + rename. The manifest
    rides the same npz as a reserved uint8 entry so one rename commits
    both (a separate sidecar file could tear independently), and
    carries its own CRC-32 (``manifest_crc32``) — buffer bytes are
    covered by the per-buffer checksums, this covers the metadata
    region itself."""
    tmp = f"{path}.tmp.{os.getpid()}"
    manifest = dict(manifest)
    manifest.pop("manifest_crc32", None)
    manifest["manifest_crc32"] = (
        zlib.crc32(json.dumps(manifest, sort_keys=True).encode())
        & 0xFFFFFFFF
    )
    m = json.dumps(manifest, sort_keys=True).encode()
    try:
        with open(tmp, "wb") as fh:
            np.savez(
                fh,
                __manifest__=np.frombuffer(m, dtype=np.uint8),
                **buffers,
            )
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _read_raw(path: str) -> tuple[dict, dict]:
    """Parse the container WITHOUT checksum verification (the
    stale-manifest injection helper rewrites manifests through this;
    everyone else goes through :func:`load_snapshot`)."""
    if not os.path.exists(path):
        raise SnapshotError(f"no snapshot at {path}")
    try:
        with np.load(path, allow_pickle=False) as z:
            if "__manifest__" not in z.files:
                raise SnapshotCorruptError(
                    f"{path}: no manifest entry — not a snapshot, or "
                    "torn before the manifest landed"
                )
            manifest = json.loads(bytes(z["__manifest__"].tobytes()))
            buffers = {
                k: np.array(z[k]) for k in z.files
                if k != "__manifest__"
            }
    except SnapshotError:
        raise
    except Exception as exc:
        # zipfile.BadZipFile, ValueError, OSError, EOFError, json
        # decode errors — every torn-file shape lands here, named.
        raise SnapshotCorruptError(
            f"{path}: torn or corrupt snapshot ({type(exc).__name__}: "
            f"{exc})"
        ) from exc
    return manifest, buffers


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def load_snapshot(path: str) -> tuple[dict, dict]:
    """Read + verify a snapshot: container integrity, version, and the
    manifest's per-buffer CRC-32 over the loaded bytes. Raises the
    named errors; never returns partially-verified data."""
    manifest, buffers = _read_raw(path)
    declared_crc = manifest.pop("manifest_crc32", None)
    actual_crc = (
        zlib.crc32(json.dumps(manifest, sort_keys=True).encode())
        & 0xFFFFFFFF
    )
    if declared_crc != actual_crc:
        raise SnapshotCorruptError(
            f"{path}: manifest failed its CRC-32 check (bit rot or "
            "a hand edit — the metadata region is checksummed too)"
        )
    version = manifest.get("version")
    if version != SNAPSHOT_VERSION:
        raise SnapshotIncompatibleError(
            f"{path}: snapshot version {version} != reader "
            f"{SNAPSHOT_VERSION}"
        )
    declared = manifest.get("buffers") or {}
    for name, meta in declared.items():
        if name not in buffers:
            raise SnapshotCorruptError(
                f"{path}: buffer {name!r} declared in manifest but "
                "missing from the container"
            )
        arr = buffers[name]
        if list(arr.shape) != list(meta["shape"]) \
                or str(arr.dtype) != meta["dtype"]:
            raise SnapshotCorruptError(
                f"{path}: buffer {name!r} is {arr.dtype}"
                f"{list(arr.shape)}, manifest declares {meta['dtype']}"
                f"{meta['shape']}"
            )
        if _crc(arr) != int(meta["crc32"]):
            raise SnapshotCorruptError(
                f"{path}: buffer {name!r} failed its CRC-32 check "
                "(bit rot or a torn write)"
            )
    for name in buffers:
        if name not in declared:
            raise SnapshotCorruptError(
                f"{path}: undeclared buffer {name!r} in the container"
            )
    return manifest, buffers


# -- capture --------------------------------------------------------------


def write_snapshot(checker, carry, path: str, *, chunk: int,
                   wave: int, depth: int, unique: int,
                   tier=None, tier_plog=None) -> dict:
    """Serialize one chunk carry to an atomic on-disk snapshot. Called
    at the existing per-chunk sync (checkers/tpu.py) — the stats
    readback already blocked, so the carry download adds transfer, not
    a sync point. Returns the manifest; emits a ``checkpoint``
    telemetry event (which the tracer→metrics bridge folds into
    ``stpu_checkpoints_total`` / ``stpu_checkpoint_bytes_total`` —
    snapshot cadence and size are live signals on ``GET /.metrics``).

    ``tier`` (tiered-visited-set runs, stateright_tpu/tier.py) is the
    engine's :class:`~stateright_tpu.tier.ColdStore`: its sorted
    immutable runs ride the same npz as ``tier_run{shard}_{i}_lo/hi``
    buffers and the manifest gains a ``tier`` block (hot ceiling,
    spill count, per-run row counts) — a snapshot of a tiered run is
    the whole visited set, both tiers."""
    from . import telemetry

    t0 = time.monotonic()
    buffers = {k: np.asarray(v) for k, v in carry.items()
               if k not in _SCRATCH_LEAVES}
    tier_block = None
    if tier is not None:
        runs = tier.snapshot_runs()
        run_rows = []
        for s, shard in enumerate(runs):
            rows_s = []
            for i, (lo, hi) in enumerate(shard):
                buffers[f"tier_run{s}_{i}_lo"] = lo
                buffers[f"tier_run{s}_{i}_hi"] = hi
                rows_s.append(int(lo.size))
            run_rows.append(rows_s)
        plog_host = 0
        if tier_plog:
            # the host-drained parent-log accumulation (tiered runs
            # rewind the device log's cursor — these rows exist only
            # host-side and must survive the process)
            blk = np.concatenate(
                [np.asarray(b, np.uint32) for b in tier_plog], axis=1
            )
            buffers["tier_plog"] = blk
            plog_host = int(blk.shape[1])
        tier_block = dict(
            hot_rows=int(getattr(checker, "_tier_hot_ceiling", 0)
                         or 0) or None,
            max_runs=int(tier.max_runs),
            spills=int(tier.spills),
            run_rows=run_rows,
            plog_host_rows=plog_host,
            cold_rows_total=int(tier.rows()),
            cold_bytes_total=int(tier.bytes()),
        )
    total = int(sum(b.nbytes for b in buffers.values()))
    manifest = dict(
        version=SNAPSHOT_VERSION,
        created_at=time.time(),
        git_sha=_git_sha(),
        engine=type(checker).__name__,
        family=checker._checkpoint_family(),
        kind=_engine_kind(checker),
        encoding=encoding_fingerprint(checker),
        width=int(checker.encoded.width),
        n_shards=int(getattr(checker, "n_shards", 1)),
        capacity=int(checker.capacity),
        frontier_capacity=int(checker.frontier_capacity),
        track_paths=bool(checker.track_paths),
        waves_per_sync=int(checker.waves_per_sync),
        chunk=int(chunk),
        wave=int(wave),
        depth=int(depth),
        unique=int(unique),
        budget=dict(
            cand_capacity=checker.cand_capacity,
            pair_width=getattr(checker, "pair_width", None),
            auto_budget=bool(getattr(checker, "auto_budget", False)),
        ),
        merge_impl=getattr(checker, "merge_impl", None),
        tier=tier_block,
        snapshot_bytes=total,
        buffers={
            k: dict(shape=list(b.shape), dtype=str(b.dtype),
                    crc32=_crc(b))
            for k, b in buffers.items()
        },
    )
    _write_file(path, manifest, buffers)
    checker._last_snapshot = path
    telemetry.emit(
        "checkpoint", path=os.path.basename(path), chunk=int(chunk),
        wave=int(wave), depth=int(depth), unique=int(unique),
        snapshot_bytes=total,
        wall_sec=round(time.monotonic() - t0, 6),
    )
    return manifest


def retain_final_snapshot(checker, path: str) -> Optional[dict]:
    """The warm-start half of the resident service's incremental
    re-check (ROADMAP direction 4, stateright_tpu/serve.py): package a
    COMPLETED device run's final chunk carry as an ordinary snapshot.
    The carry of a finished search holds the whole visited set, the
    parent forest, the discovery lanes, and ``done=True`` — so a later
    checker whose :func:`encoding_fingerprint` matches can
    :func:`resume_from` it and settle in one chunk with zero new waves
    dispatched, counts bit-identical to the cold run (the same
    validation/re-shard seam applies: an EDITED model changes the
    fingerprint and refuses, which is the service's cue to run cold).

    Requires the run to have kept its final carry
    (``checker.keep_final_carry = True`` before join — the existing
    tools/profile_stages.py capture hook). Returns the manifest, or
    None when there is nothing retainable: no final carry or a run
    that raised. A TIERED run retains BOTH tiers — the snapshot
    format already carries the cold runs (``tier_run*`` buffers) and
    the host-drained parent log beside the device carry, so a tiered
    re-check warm-starts exactly like a flat one (the forced-spill
    regression test settles with zero new waves). Only a tiered run
    whose ColdStore is gone (spills recorded but ``_tier_state``
    cleared) still refuses: retaining the device carry alone would
    warm-start from a subset and silently re-explore.
    """
    carry = getattr(checker, "_final_carry", None)
    if carry is None or checker._run_error is not None:
        return None
    metrics = getattr(checker, "metrics", None) or {}
    tier = getattr(checker, "_tier_state", None)
    if metrics.get("tier_spills") and tier is None:
        return None
    lat = getattr(checker, "_lat", None) or {}
    return write_snapshot(
        checker, carry, path,
        chunk=int(lat.get("chunks") or 0),
        wave=int(metrics.get("waves") or 0),
        depth=int(checker._max_depth),
        unique=int(checker._unique_states),
        tier=tier,
        tier_plog=getattr(checker, "_tier_plog_rows", None),
    )


# -- resume ---------------------------------------------------------------


def resume_from(checker, path: str, *,
                allow_sha_mismatch: bool = False) -> dict:
    """Validate a snapshot against ``checker`` and stage it for the
    next run: the engine's ``_run_attempt`` builds its initial carry
    from the staged buffers instead of the seed program. Re-shards
    through the (owner, fp) seam when the sort-merge target's layout
    differs; refuses loudly (named errors) on corruption, staleness,
    or an incompatible target. Returns the manifest."""
    manifest, buffers = load_snapshot(path)

    enc_fp = encoding_fingerprint(checker)
    if manifest.get("encoding") != enc_fp:
        raise SnapshotStaleError(
            f"{path}: snapshot encodes "
            f"{manifest.get('encoding')!r}, this checker expects "
            f"{enc_fp!r} — a snapshot is only resumable into the "
            "same model/encoding"
        )
    head = _git_sha()
    snap_sha = manifest.get("git_sha")
    if (snap_sha is not None and head is not None
            and snap_sha != head and not allow_sha_mismatch):
        raise SnapshotStaleError(
            f"{path}: snapshot was written at git {snap_sha[:12]}, "
            f"HEAD is {head[:12]} — the carry layout may have "
            "changed; pass allow_sha_mismatch=True (CLI: "
            "--resume-any-sha) to resume anyway"
        )
    family = checker._checkpoint_family()
    if manifest.get("family") != family:
        raise SnapshotIncompatibleError(
            f"{path}: snapshot is from the {manifest.get('family')!r} "
            f"engine family, this checker is {family!r} — the visited "
            "structures are not interconvertible"
        )
    if bool(manifest.get("track_paths")) != bool(checker.track_paths):
        raise SnapshotIncompatibleError(
            f"{path}: snapshot track_paths="
            f"{manifest.get('track_paths')}, checker "
            f"track_paths={checker.track_paths} — the parent log "
            "exists on one side only"
        )

    same_layout = (
        int(manifest.get("n_shards", 1))
        == int(getattr(checker, "n_shards", 1))
        and int(manifest.get("capacity")) == int(checker.capacity)
        and int(manifest.get("frontier_capacity"))
        == int(checker.frontier_capacity)
        and manifest.get("kind") == _engine_kind(checker)
    )
    # Tiered snapshots (stateright_tpu/tier.py): fold the deferred-
    # commit staging host-side — the pending wave commits through the
    # SAME cold-membership verdict the device commit would have run —
    # so everything downstream (direct upload, the (owner, fp)
    # re-shard) sees only confirmed, untiered-shaped state; the cold
    # runs then re-route by the same owner seam.
    tier_m = manifest.get("tier")
    checker._tier_resume_state = None
    hot_src = None
    cold_src = None
    if tier_m:
        buffers, cold_src, hot_src, plog_host = _fold_tier_snapshot(
            checker, manifest, buffers, tier_m
        )
    if not same_layout:
        if family == "sortmerge":
            buffers = reshard_sortmerge(
                manifest, buffers, checker, visited_counts=hot_src
            )
        else:
            raise SnapshotIncompatibleError(
                f"{path}: shard/capacity re-layout (snapshot "
                f"S={manifest.get('n_shards')} "
                f"kind={manifest.get('kind')} "
                f"C={manifest.get('capacity')}, target "
                f"S={getattr(checker, 'n_shards', 1)} "
                f"kind={_engine_kind(checker)} "
                f"C={checker.capacity}) of a hash-family snapshot — "
                "the hash family resumes on its original "
                "configuration only; the sort-merge family "
                "(spawn_tpu_sortmerge, spawn_tpu_sharded_sortmerge) "
                "re-shards in every direction"
            )
    if tier_m:
        buffers = _route_tier_target(
            checker, path, manifest, buffers, cold_src, hot_src,
            same_layout, plog_host,
        )

    checker._resume = (manifest, buffers)
    checker._resume_path = path
    # remembered for the supervisor's retry re-stage: a run resumed
    # with allow_sha_mismatch must recover under the same policy
    checker._resume_allow_sha = allow_sha_mismatch
    return manifest


def _fold_tier_snapshot(checker, manifest: dict, buffers: dict,
                        tier_m: dict):
    """Restore a tiered snapshot's host state and COMMIT its pending
    wave host-side: rebuild the :class:`~stateright_tpu.tier.ColdStore`
    from the serialized runs, run the batched sort-merge membership
    over the staged provisional winners (exactly the verdict the next
    device dispatch would have received as its keep mask), and fold
    the survivors into the carry — hot-prefix merge, frontier
    compaction, parent-log append, counters — so the buffers leave
    here as a valid UNTIERED carry at the source layout whose visited
    prefix holds only the hot tier. Returns ``(buffers, cold_store,
    hot_counts_per_source_shard, host_plog_block_or_None)``."""
    from .tier import ColdStore

    W = int(manifest["width"])
    track_paths = bool(manifest["track_paths"])
    S_a = int(manifest.get("n_shards", 1))
    C_a = int(manifest["capacity"])
    F_a = int(manifest["frontier_capacity"])
    kind_a = manifest.get("kind", "single")
    C_pad_a = C_a + F_a
    L_a = C_a + F_a if track_paths else 0

    run_rows = tier_m.get("run_rows") or []
    per_shard_runs = []
    for s in range(S_a):
        shard = []
        rows_s = run_rows[s] if s < len(run_rows) else []
        for i, n in enumerate(rows_s):
            lo = buffers.pop(f"tier_run{s}_{i}_lo")
            hi = buffers.pop(f"tier_run{s}_{i}_hi")
            if int(n) != int(lo.size):
                raise SnapshotCorruptError(
                    f"tier run {s}/{i}: manifest declares {n} rows, "
                    f"buffer has {lo.size}"
                )
            shard.append((lo, hi))
        per_shard_runs.append(shard)
    cold = ColdStore.from_runs(
        per_shard_runs,
        max_runs=int(tier_m.get("max_runs") or 8),
        spills=int(tier_m.get("spills") or 0),
    )

    plog_host = buffers.pop("tier_plog", None)
    # pop the tiered-mode staging leaves (absent only if the snapshot
    # landed before the first tiered dispatch)
    staged = {k: buffers.pop(k) for k in _TIER_LEAVES
              if k in buffers}
    if kind_a == "sharded":
        hot = np.atleast_1d(
            staged.get("h_loc", buffers["u_loc"])
        ).astype(np.int64).reshape(-1).copy()
    else:
        h = staged.get("n_hot", buffers["new"])
        hot = np.array([int(h)], np.int64)

    pend_valid = bool(staged.get("pend_valid", False))
    if pend_valid:
        pend_n = np.atleast_1d(
            staged["pend_n"]
        ).astype(np.int64).reshape(-1)
        pend_keys = staged["pend_keys"]
        pend_par = staged.get("pend_par")
        vkeys = buffers["vkeys"]
        frontier = buffers["frontier"]
        ebits = buffers["ebits"]
        fval = buffers["fval"]
        plog = buffers.get("plog")
        pl_n = (np.atleast_1d(buffers["pl_n"]).astype(np.int64)
                .reshape(-1).copy() if track_paths else None)
        n_loc = np.zeros(S_a, np.int64)
        confs = np.zeros(S_a, np.int64)
        for s in range(S_a):
            n_p = int(pend_n[s]) if s < pend_n.size else 0
            fb = s * F_a
            frontier_blk = frontier[:, fb:fb + F_a].copy()
            eb_blk = ebits[fb:fb + F_a].copy()
            frontier[:, fb:fb + F_a] = 0
            ebits[fb:fb + F_a] = 0
            fval[fb:fb + F_a] = False
            if n_p == 0:
                continue
            sl = slice(fb, fb + n_p)
            klo = np.asarray(pend_keys[0, sl])
            khi = np.asarray(pend_keys[1, sl])
            keep = ~cold.member(s, klo, khi)
            conf = int(keep.sum())
            confs[s] = conf
            if conf == 0:
                continue
            h = int(hot[s])
            base = s * C_pad_a
            mlo = np.concatenate([vkeys[0, base:base + h], klo[keep]])
            mhi = np.concatenate([vkeys[1, base:base + h], khi[keep]])
            order = np.lexsort((mlo, mhi))
            vkeys[0, base:base + h + conf] = mlo[order]
            vkeys[1, base:base + h + conf] = mhi[order]
            hot[s] = h + conf
            frontier[:, fb:fb + conf] = frontier_blk[:, :n_p][:, keep]
            ebits[fb:fb + conf] = eb_blk[:n_p][keep]
            n_loc[s] = conf
            if track_paths and pend_par is not None:
                pl = int(pl_n[s])
                lb = s * L_a
                plog[0, lb + pl:lb + pl + conf] = \
                    np.asarray(pend_par[0, sl])[keep]
                plog[1, lb + pl:lb + pl + conf] = \
                    np.asarray(pend_par[1, sl])[keep]
                plog[2, lb + pl:lb + pl + conf] = klo[keep]
                plog[3, lb + pl:lb + pl + conf] = khi[keep]
                pl_n[s] = pl + conf
        conf_total = int(confs.sum())
        new_after = int(buffers["new"]) + conf_total
        n_props = int(np.asarray(buffers["disc_found"]).size)
        all_disc = (bool(np.asarray(buffers["disc_found"]).all())
                    if n_props else False)
        target = checker.builder._target_state_count
        target_hit = target is not None and new_after >= int(target)
        cont = conf_total > 0 and not all_disc and not target_hit
        for s in range(S_a):
            if cont and confs[s]:
                fval[s * F_a:s * F_a + int(confs[s])] = True
        buffers["new"] = np.uint32(new_after)
        buffers["waves"] = np.uint32(int(buffers["waves"]) + 1)
        if cont:
            buffers["depth"] = np.int32(int(buffers["depth"]) + 1)
        buffers["done"] = np.bool_(not cont)
        if track_paths:
            buffers["pl_n"] = (
                pl_n.astype(np.uint32) if kind_a == "sharded"
                else np.uint32(pl_n[0])
            )
        if kind_a == "sharded":
            buffers["n_loc"] = n_loc.astype(np.uint32)
        else:
            buffers["n_frontier"] = np.uint32(n_loc[0])
        # the manifest's capture point moves past the folded commit
        manifest["wave"] = int(buffers["waves"])
        manifest["depth"] = int(buffers["depth"])
        manifest["unique"] = new_after

    # the source-layout visited prefixes now hold HOT rows only; the
    # re-shard (if any) must slice by these, not the cumulative count
    if kind_a == "sharded":
        buffers["u_loc"] = hot.astype(np.uint32)
    return buffers, cold, hot, plog_host


def _route_tier_target(checker, path: str, manifest: dict,
                       buffers: dict, cold, hot_src, same_layout,
                       plog_host=None):
    """Land a folded tiered snapshot on the TARGET: re-route the cold
    runs by the new owner seam (``lo % S_new`` — filtering a sorted
    run preserves its order, so every piece stays a sorted immutable
    run), then either stage the tier for the resuming engine (tiering
    configured on the target: ``checker._tier_resume_state``) or
    UN-TIER — merge the cold rows back into the resident prefix when
    the target capacity holds the whole set and the target didn't ask
    for tiering. Refuses loudly when neither fits."""
    S_b = int(getattr(checker, "n_shards", 1))
    C_b = int(checker.capacity)
    F_b = int(checker.frontier_capacity)
    C_pad_b = C_b + F_b
    kind_b = _engine_kind(checker)

    cold_t = (cold if same_layout and cold.n_shards == S_b
              else cold.repartitioned(S_b))
    if kind_b == "sharded":
        hot_t = np.atleast_1d(buffers["u_loc"]).astype(
            np.int64
        ).reshape(-1).copy()
    else:
        hot_t = np.array([int(hot_src.sum())], np.int64)
    cold_rows = np.array(cold_t.shard_rows(), np.int64)

    tier_on = getattr(checker, "tier_hot_rows", None) is not None
    if not tier_on:
        # un-tier: the whole set must fit the target residency
        total = hot_t + cold_rows
        if int(total.max(initial=0)) > C_b:
            raise SnapshotIncompatibleError(
                f"{path}: tiered snapshot holds "
                f"{int(total.sum()):,} visited keys "
                f"({int(cold_rows.sum()):,} cold) but the target's "
                f"per-shard capacity is {C_b:,} and tiering is off — "
                "raise the capacity, or resume with tier_hot_rows "
                "set to keep the cold tier"
            )
        vkeys = buffers["vkeys"]
        from .tier import pack_u64

        for d in range(S_b):
            base = d * C_pad_b
            h = int(hot_t[d])
            lo = vkeys[0, base:base + h]
            hi = vkeys[1, base:base + h]
            packed = [pack_u64(lo, hi)]
            for run in cold_t.runs[d]:
                packed.append(run)
            merged = np.sort(np.concatenate(packed))
            n = merged.size
            vkeys[0, base:base + n] = (
                merged & np.uint64(0xFFFFFFFF)
            ).astype(np.uint32)
            vkeys[1, base:base + n] = (
                merged >> np.uint64(32)
            ).astype(np.uint32)
            hot_t[d] = n
        if kind_b == "sharded":
            buffers["u_loc"] = hot_t.astype(np.uint32)
        if plog_host is not None and plog_host.shape[1]:
            # re-home the host-drained parent-log rows into the
            # device log, per owner shard (row order within a shard
            # is irrelevant: every child appears exactly once)
            track_paths = bool(manifest["track_paths"])
            if track_paths:
                L_b = C_b + F_b
                plog = buffers["plog"]
                pl_n = np.atleast_1d(
                    buffers["pl_n"]
                ).astype(np.int64).reshape(-1).copy()
                owner = (
                    plog_host[2] % np.uint32(max(S_b, 1))
                ).astype(np.int64)
                for d in range(S_b):
                    rows_d = plog_host[:, owner == d]
                    n_d = rows_d.shape[1]
                    pl = int(pl_n[d] if d < pl_n.size else 0)
                    if pl + n_d > L_b:
                        raise SnapshotIncompatibleError(
                            f"{path}: un-tiering needs "
                            f"{pl + n_d:,} parent-log rows on shard "
                            f"{d} but the per-shard log holds "
                            f"{L_b:,} — raise the target capacity"
                        )
                    lb = d * L_b
                    plog[:, lb + pl:lb + pl + n_d] = rows_d
                    pl_n[d] = pl + n_d
                buffers["pl_n"] = (
                    pl_n.astype(np.uint32) if kind_b == "sharded"
                    else np.uint32(pl_n[0])
                )
        return buffers

    # stay tiered: per-shard cumulative counts join hot + owned cold
    if kind_b == "sharded":
        buffers["u_loc"] = (hot_t + cold_rows).astype(np.uint32)
    checker._tier_resume_state = dict(
        cold=cold_t, hot=hot_t.astype(np.int64),
        plog_rows=([plog_host] if plog_host is not None
                   and plog_host.shape[1] else []),
    )
    return buffers


def reshard_sortmerge(manifest: dict, buffers: dict,
                      checker, visited_counts=None) -> dict:
    """The elastic re-shard: rebuild the sort-merge carry at the
    target (shard count, per-shard capacity) layout by re-routing
    every row through the (owner, fp) seam the mesh wave's routing
    sort already defines — owner = ``fp_lo % S`` (the all_to_all
    destination function, parallel/engine_sortmerge.py
    ``seed_local``/``make_wave``), keys ordered ``(hi, lo)`` (the
    ``lax.sort(num_keys=2)`` the incrementally-sorted visited
    invariant is built on). Host-side ``np.lexsort`` implements the
    identical ordering, so the rebuilt per-shard prefixes satisfy the
    engine's sorted invariant by construction.

    Handles single-chip ⇄ sharded in both directions (single-chip is
    the S=1 layout with scalar counters) and capacity changes at the
    same shard count. Raises :class:`SnapshotIncompatibleError` when
    the carried state does not fit the target layout (per-shard
    visited/frontier/parent-log overflow) — loudly, before any device
    work."""
    W = int(manifest["width"])
    track_paths = bool(manifest["track_paths"])
    S_a = int(manifest.get("n_shards", 1))
    C_a = int(manifest["capacity"])
    F_a = int(manifest["frontier_capacity"])
    kind_a = manifest.get("kind", "single")
    C_pad_a = C_a + F_a
    L_a = C_a + F_a if track_paths else 0

    S_b = int(getattr(checker, "n_shards", 1))
    C_b = int(checker.capacity)
    F_b = int(checker.frontier_capacity)
    kind_b = _engine_kind(checker)
    C_pad_b = C_b + F_b
    L_b = C_b + F_b if track_paths else 0

    # -- extract the global state from the source layout ------------------
    if kind_a == "sharded":
        u_src = buffers["u_loc"].astype(np.int64).reshape(-1)
        n_src = buffers["n_loc"].astype(np.int64).reshape(-1)
        pl_src = buffers["pl_n"].astype(np.int64).reshape(-1)
    else:
        u_src = np.array([int(buffers["new"])], np.int64)
        n_src = np.array([int(buffers["n_frontier"])], np.int64)
        pl_src = np.array(
            [int(buffers["pl_n"])] if track_paths else [0], np.int64
        )
    if visited_counts is not None:
        # tiered snapshots (stateright_tpu/tier.py): the resident
        # prefix holds the HOT tier only — the cumulative counters
        # ("new") include spilled rows and must not size the slice
        u_src = np.asarray(visited_counts, np.int64).reshape(-1)

    vkeys = buffers["vkeys"]
    keys_lo = np.concatenate([
        vkeys[0, s * C_pad_a: s * C_pad_a + int(u_src[s])]
        for s in range(S_a)
    ])
    keys_hi = np.concatenate([
        vkeys[1, s * C_pad_a: s * C_pad_a + int(u_src[s])]
        for s in range(S_a)
    ])

    frontier = buffers["frontier"]
    ebits = buffers["ebits"]
    fr_cols = np.concatenate([
        frontier[:, s * F_a: s * F_a + int(n_src[s])]
        for s in range(S_a)
    ], axis=1)
    fr_ebits = np.concatenate([
        ebits[s * F_a: s * F_a + int(n_src[s])] for s in range(S_a)
    ])

    if track_paths:
        plog = buffers["plog"]
        pl_entries = np.concatenate([
            plog[:, s * L_a: s * L_a + int(pl_src[s])]
            for s in range(S_a)
        ], axis=1)
    else:
        pl_entries = np.zeros((4, 0), np.uint32)

    # -- route by the (owner, fp) seam -------------------------------------
    from .ops.fingerprint import fingerprint_u32v

    key_owner = (keys_lo % np.uint32(max(S_b, 1))).astype(np.int64)
    fr_lo, fr_hi = fingerprint_u32v(fr_cols.T, np)
    fr_owner = (fr_lo % np.uint32(max(S_b, 1))).astype(np.int64)
    pl_owner = (
        pl_entries[2] % np.uint32(max(S_b, 1))
    ).astype(np.int64)

    vkeys_t = np.full((2, S_b * C_pad_b), _SENT, np.uint32)
    frontier_t = np.zeros((W, S_b * F_b), np.uint32)
    ebits_t = np.zeros(S_b * F_b, np.uint32)
    fval_t = np.zeros(S_b * F_b, bool)
    plog_t = np.zeros((4, S_b * L_b), np.uint32)
    u_t = np.zeros(S_b, np.uint32)
    n_t = np.zeros(S_b, np.uint32)
    pl_t = np.zeros(S_b, np.uint32)
    for d in range(S_b):
        sel = key_owner == d
        kl, kh = keys_lo[sel], keys_hi[sel]
        if kl.size > C_b:
            raise SnapshotIncompatibleError(
                f"re-shard: shard {d} of {S_b} would own {kl.size:,} "
                f"visited keys but per-shard capacity is {C_b:,} — "
                "raise the target capacity"
            )
        order = np.lexsort((kl, kh))  # (hi, lo): the routing sort
        vkeys_t[0, d * C_pad_b: d * C_pad_b + kl.size] = kl[order]
        vkeys_t[1, d * C_pad_b: d * C_pad_b + kl.size] = kh[order]
        u_t[d] = kl.size

        fsel = fr_owner == d
        n_d = int(fsel.sum())
        if n_d > F_b:
            raise SnapshotIncompatibleError(
                f"re-shard: shard {d} of {S_b} would own {n_d:,} "
                f"frontier rows but frontier_capacity is {F_b:,} — "
                "raise the target frontier_capacity"
            )
        cols = fr_cols[:, fsel]
        eb = fr_ebits[fsel]
        # deterministic per-shard order (row order never affects
        # exploration — any order covers the same states — but a
        # stable layout keeps re-shard bit-reproducible)
        forder = np.lexsort((fr_lo[fsel], fr_hi[fsel]))
        frontier_t[:, d * F_b: d * F_b + n_d] = cols[:, forder]
        ebits_t[d * F_b: d * F_b + n_d] = eb[forder]
        fval_t[d * F_b: d * F_b + n_d] = True
        n_t[d] = n_d

        if track_paths:
            psel = pl_owner == d
            p_d = int(psel.sum())
            if p_d > L_b:
                raise SnapshotIncompatibleError(
                    f"re-shard: shard {d} of {S_b} would own "
                    f"{p_d:,} parent-log entries but the per-shard "
                    f"log holds {L_b:,} — raise the target capacity"
                )
            plog_t[:, d * L_b: d * L_b + p_d] = pl_entries[:, psel]
            pl_t[d] = p_d

    def src(name, default):
        b = buffers.get(name)
        return np.array(b) if b is not None else default

    out = dict(
        vkeys=vkeys_t,
        plog=plog_t,
        frontier=frontier_t,
        fval=fval_t,
        ebits=ebits_t,
        depth=np.int32(buffers["depth"]),
        wchunk=np.int32(0),
        waves=np.uint32(buffers["waves"]),
        gen_lo=np.uint32(buffers["gen_lo"]),
        gen_hi=np.uint32(buffers["gen_hi"]),
        new=np.uint32(buffers["new"]),
        disc_found=np.array(buffers["disc_found"], bool),
        disc_lo=np.uint32(buffers["disc_lo"]),
        disc_hi=np.uint32(buffers["disc_hi"]),
        overflow=np.bool_(buffers["overflow"]),
        f_overflow=np.bool_(buffers["f_overflow"]),
        c_overflow=np.bool_(buffers["c_overflow"]),
        e_overflow=np.bool_(buffers["e_overflow"]),
        done=np.bool_(buffers["done"]),
        max_cand=src("max_cand", np.uint32(0)),
    )
    if kind_b == "sharded":
        out.update(
            pl_n=pl_t,
            n_loc=n_t,
            u_loc=u_t,
            sent_lo=src("sent_lo", np.uint32(0)),
            sent_hi=src("sent_hi", np.uint32(0)),
        )
    else:
        # single-chip target: S_b is 1 (no n_shards attr → 1), so the
        # "per-shard" blocks above are one dense block already
        out.update(
            pl_n=np.uint32(pl_t.sum()),
            n_frontier=np.uint32(n_t.sum()),
            max_tile_cand=src("max_tile_cand", np.uint32(0)),
            max_rowen=src("max_rowen", np.uint32(0)),
        )
    return out


def build_resume_carry(checker, manifest: dict, buffers: dict,
                       spec: dict) -> dict:
    """Assemble the initial device carry for a resumed run from staged
    snapshot buffers, against the CURRENT seed program's eval_shape
    ``spec`` (so a restore can never hand the compiled chunk program a
    carry it wasn't built for): snapshot leaves are shape/dtype
    checked, trace-gated leaves are synthesized to the resuming run's
    shapes, and sharded leaves are placed with the engine's
    ``PartitionSpec``\\ s when available (plain arrays otherwise — jit
    re-shards uncommitted inputs)."""
    carry_np: dict[str, np.ndarray] = {}
    for name in spec:
        leaf = spec[name]
        want_shape = tuple(int(s) for s in leaf.shape)
        want_dtype = np.dtype(leaf.dtype)
        have = buffers.get(name)
        if name == "wchunk":
            carry_np[name] = np.zeros(want_shape, want_dtype)
            continue
        if have is not None and tuple(have.shape) == want_shape:
            carry_np[name] = np.asarray(have, dtype=want_dtype)
            continue
        if name in _SYNTH_LEAVES:
            carry_np[name] = np.zeros(want_shape, want_dtype)
            continue
        raise SnapshotIncompatibleError(
            f"resume: carry leaf {name!r} expects "
            f"{want_dtype}{list(want_shape)}, snapshot has "
            + (f"{have.dtype}{list(have.shape)}" if have is not None
               else "no such buffer")
            + " — the engine configuration differs from the snapshot"
        )

    # The restored leaves must be JAX-OWNED copies: the chunk program
    # donates its carry (donate_argnums=0), and on CPU a zero-copy
    # device_put/asarray of a numpy buffer can ALIAS the host memory
    # — donating an aliased buffer lets XLA reuse memory numpy still
    # references (observed as off-by-a-few duplicate counts and
    # occasional runtime crashes on the first resumed chunk). One
    # explicit on-device copy per leaf severs the alias; resume pays
    # it once.
    pspecs = getattr(checker, "_carry_pspecs", None)
    mesh = getattr(checker, "mesh", None)
    import jax
    import jax.numpy as jnp

    if pspecs is not None and mesh is not None:
        from jax.sharding import NamedSharding

        out = {}
        for k, v in carry_np.items():
            if k in pspecs:
                arr = jax.device_put(
                    v, NamedSharding(mesh, pspecs[k])
                )
                out[k] = jnp.copy(arr)
            else:
                out[k] = jnp.copy(jnp.asarray(v))
        return out
    return {k: jnp.copy(jnp.asarray(v)) for k, v in carry_np.items()}


# -- failure policy (the degrade-and-continue round) ----------------------
#
# PR 11's supervisor could only retry the same layout or refuse; this
# layer closes the loop ROADMAP direction 1 needs for multi-hour mesh
# runs, where the failure model is "a shard dies, a collective wedges,
# a dispatch hangs forever" (the worker-loss-as-first-class-event
# framing of arXiv:1203.6806 and arXiv:0901.0179): every supervised
# failure is CLASSIFIED (transient / persistent per-shard / OOM /
# hang) from the exception and the run's own health signals, and a
# fault that persists on the same shard across the bounded-backoff
# retries escalates to an automatic elastic degrade — the last
# snapshot re-shards onto the surviving shard count through the exact
# (owner, fp) seam PR 11 proved, cold tier and drained parent log
# included, so the degraded run reproduces bit-exact counts.

#: what a supervised failure classifies as (FailurePolicy.classify).
FAILURE_CLASSES = ("transient", "oom", "hang", "shard_fault",
                   "unsupervised")


class WatchdogTimeout(RuntimeError):
    """A chunk dispatch/sync exceeded its derived watchdog deadline
    (checkers/tpu.py ``watchdog_factor``) — the hung-dispatch shape
    (a chunk that never syncs), which no exception path ever
    surfaces. Supervised: the policy classifies it ``hang`` and
    retries from the last snapshot where checkpointing allows
    (CPython cannot cancel a wedged XLA
    sync — the hung worker thread is abandoned as a daemon — so
    in-process recovery re-dispatches and a genuinely wedged runtime
    exhausts the retry budget and raises this error through:
    refuse-loudly-with-diagnosis, the contract). ``attribution``
    carries the run's full latency split at the breach."""

    def __init__(self, chunk: int, deadline_sec: float,
                 attribution: Optional[dict] = None):
        super().__init__(
            f"watchdog: chunk {chunk} exceeded its derived deadline "
            f"of {deadline_sec:.2f}s with no sync — a hung dispatch. "
            "The dispatch thread is abandoned (XLA offers no "
            "cancellation); recover from the last snapshot or "
            "investigate the attribution."
        )
        self.chunk = int(chunk)
        self.deadline_sec = float(deadline_sec)
        self.attribution = attribution or {}


def watchdog_deadline(rolling_max_sec: Optional[float],
                      factor: float = 8.0, *,
                      floor_sec: float = 2.0,
                      cap_sec: float = 600.0,
                      first_grace_sec: float = 300.0) -> float:
    """The per-chunk watchdog deadline, re-derived per chunk from the
    run's OWN measured chunk walls (the auto_cadence pattern):
    ``clamp(factor x rolling max chunk wall)`` to ``[floor, cap]``.
    A run with no measured wall yet (chunk 0, where the lazy jit
    compile or a persistent-cache disk fetch lands inside the first
    dispatch — a 17.9 s retrieval was measured in TRACE_r21) gets
    ``first_grace_sec`` instead, so a cold compile is never
    misclassified as a hang; the engine additionally feeds the roll
    chunk walls NET of ledger-attributed build time for the same
    reason."""
    if not factor or factor <= 0:
        raise ValueError(f"watchdog factor must be > 0: {factor}")
    if rolling_max_sec is None:
        # None means UNMEASURED (chunk 0); a measured-but-tiny wall
        # (e.g. fully attributed to a compile fetch) is a real
        # measurement and gets the floor, not the grace — otherwise a
        # fast first chunk would re-grant the grace forever
        return float(first_grace_sec)
    return float(min(
        cap_sec, max(floor_sec, factor * max(rolling_max_sec, 0.0))
    ))


def classify_failure(exc: BaseException,
                     straggler_shards=()) -> tuple:
    """``(class, shard | None)`` for one supervised failure — the
    classification table FailurePolicy keys escalation on:

    * :class:`WatchdogTimeout` -> ``hang`` (never shard-attributed:
      a wedged sync has no shard signal);
    * an OOM-shaped error -> ``oom`` (the memory-lean degrade path);
    * :class:`~stateright_tpu.faultinject.InjectedShardFault` ->
      ``shard_fault`` with its shard id — the persistent per-shard
      class real per-chip ECC/interconnect faults land in;
    * any other supervised fault -> ``transient``, attributed to a
      shard only when the health layer's sustained-straggler evidence
      names exactly ONE suspect (an ambiguous signal attributes
      nothing — degrading the wrong shard helps nobody);
    * everything else -> ``unsupervised`` (the supervisor re-raises
      before classification normally; this row exists for the policy
      unit tests)."""
    from .faultinject import InjectedShardFault

    if isinstance(exc, WatchdogTimeout):
        return "hang", None
    if isinstance(exc, InjectedShardFault):
        return "shard_fault", exc.shard
    if _is_oom(exc):
        return "oom", None
    if is_supervised_fault(exc):
        shard = (int(straggler_shards[0])
                 if len(straggler_shards) == 1 else None)
        return "transient", shard
    return "unsupervised", None


class FailurePolicy:
    """Per-run failure bookkeeping for the supervisor: classify each
    failure, count shard-attributed strikes, and decide when a fault
    is PERSISTENT — the same shard failing ``persist_threshold``
    times — at which point :func:`supervised_run` escalates from
    retry-same-layout to an automatic elastic degrade onto the
    surviving shards."""

    def __init__(self, persist_threshold: int = 2):
        if persist_threshold < 1:
            raise ValueError(
                f"persist_threshold must be >= 1: {persist_threshold}"
            )
        self.persist_threshold = int(persist_threshold)
        #: (class, shard) per classified failure, in order.
        self.history: list[tuple] = []
        #: shard id -> consecutive attributed failures.
        self.strikes: dict[int, int] = {}

    def classify(self, exc: BaseException,
                 straggler_shards=()) -> tuple:
        """Classify AND record one failure. A shard-attributed
        failure strikes its shard; a failure attributed to no shard
        resets nothing (evidence about one shard is not evidence the
        others recovered)."""
        kind, shard = classify_failure(exc, straggler_shards)
        self.history.append((kind, shard))
        if shard is not None:
            self.strikes[shard] = self.strikes.get(shard, 0) + 1
        return kind, shard

    def should_degrade(self) -> Optional[int]:
        """The shard to drop (most strikes first), or None while no
        shard has reached the persistence threshold."""
        over = [(n, s) for s, n in self.strikes.items()
                if n >= self.persist_threshold]
        if not over:
            return None
        return max(over)[1]

    def degraded(self, shard: int) -> None:
        """The run dropped this shard — its strikes go with it."""
        self.strikes.pop(shard, None)


# -- supervision ----------------------------------------------------------


def is_supervised_fault(exc: BaseException) -> bool:
    """Whether the supervisor may retry this failure from a snapshot:
    injected faults, OOMs, and runtime errors surfacing from the XLA
    dispatch/readback path. Engine overflow errors (plain
    RuntimeErrors with sizing advice) are NOT supervised — the
    auto-budget retry owns those, and re-running them from a snapshot
    would loop."""
    from .faultinject import InjectedFault

    if isinstance(exc, (InjectedFault, MemoryError, WatchdogTimeout)):
        return True
    name = type(exc).__name__
    if name in ("XlaRuntimeError", "JaxRuntimeError", "InternalError"):
        return True
    s = str(exc)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s


def _is_oom(exc: BaseException) -> bool:
    if isinstance(exc, MemoryError):
        return True
    s = str(exc)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s


def _interruptible_backoff(delay: float, checker) -> None:
    """The supervisor's backoff sleep, in small slices so a cancel
    event (the hybrid racer) ends it early — and with the trace run
    bracket CLOSED on KeyboardInterrupt: a ^C mid-backoff used to die
    mid-sleep with the run_begin left dangling (the checker's
    ``_ensure_run`` catches ``Exception`` only, so the BaseException
    escaped without a run_end), leaving the partial trace unreadable
    by the run-aligned tools."""
    from . import telemetry

    deadline = time.monotonic() + delay
    try:
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            ev = getattr(checker, "cancel_event", None)
            if ev is not None and ev.is_set():
                return
            time.sleep(min(remaining, 0.05))
    except KeyboardInterrupt:
        tracer = telemetry.current_tracer()
        if tracer is not None:
            tracer.end_run(
                error="KeyboardInterrupt: interrupted during "
                "supervised backoff"
            )
        raise


def supervised_run(checker, reporter=None) -> None:
    """The retry loop around one engine run (``checker._run`` routes
    here) — since the degrade-and-continue round, a POLICY ENGINE:
    every supervised fault (device error, injected fault, OOM,
    watchdog hang) is classified by a :class:`FailurePolicy` from the
    exception and the health layer's straggler evidence, then

    * **retried** from the last snapshot (or the seed when the fault
      landed before the first snapshot) with bounded exponential
      backoff — the PR 11 behavior, now the ``transient`` class;
    * after two ``oom``-classified failures the engine degrades to
      its CHUNKED memory-lean classes before the next attempt;
    * a fault that PERSISTS on the same shard across retries (the
      ``shard_fault`` class, or ``transient`` faults the straggler
      evidence attributes) escalates — when ``degrade_on_fault`` is
      set on a multi-shard engine — to an automatic ELASTIC DEGRADE:
      the faulted shard is dropped from the mesh and the last
      snapshot re-shards onto the survivors through the (owner, fp)
      seam (cold tier runs and the drained parent log carry through
      resume_from's existing paths), recorded as a ``fault_degrade``
      event. The degraded run continues to bit-exact counts;
    * a ``hang`` (WatchdogTimeout) retries from the snapshot like a
      device error — a genuinely wedged runtime exhausts the retry
      budget and the WatchdogTimeout raises through with its latency
      attribution: refuse-loudly-with-diagnosis.

    Unsupervised errors — and supervised ones past
    ``max_fault_retries`` — raise through unchanged."""
    from . import telemetry

    policy = FailurePolicy(
        persist_threshold=getattr(
            checker, "fault_persist_threshold", 2
        )
    )
    attempts = 0
    ooms = 0
    while True:
        try:
            return checker._run_attempt(reporter)
        except Exception as exc:
            if not is_supervised_fault(exc):
                raise
            kind, shard = policy.classify(
                exc, straggler_shards=checker._sustained_stragglers()
            )
            snap = (getattr(checker, "_last_snapshot", None)
                    or getattr(checker, "_resume_path", None))
            retries = getattr(checker, "max_fault_retries", 3)
            if (not checker.checkpoint_every and snap is None) \
                    or attempts >= retries:
                raise
            attempts += 1
            if kind == "oom":
                ooms += 1
            victim = None
            if (getattr(checker, "degrade_on_fault", False)
                    and checker._can_degrade_shards()):
                victim = policy.should_degrade()
            delay = min(
                getattr(checker, "retry_backoff_sec", 0.5)
                * (2 ** (attempts - 1)),
                30.0,
            )
            warnings.warn(
                f"supervised recovery [{kind}"
                + (f", shard {shard}" if shard is not None else "")
                + f"]: {type(exc).__name__} on chunk execution "
                f"({exc}); "
                + (f"DEGRADING: dropping shard {victim} "
                   f"({checker.n_shards} -> {checker.n_shards - 1} "
                   "shards) and " if victim is not None else "")
                + f"retry {attempts}/{retries} from "
                + (f"snapshot {os.path.basename(snap)}" if snap
                   else "the seed")
                + f" after {delay:.2f}s backoff",
                RuntimeWarning,
                stacklevel=2,
            )
            telemetry.emit(
                "fault_recovery",
                attempt=attempts,
                error=f"{type(exc).__name__}: {exc}",
                snapshot=(os.path.basename(snap) if snap else None),
                backoff_sec=round(delay, 3),
                oom=(kind == "oom"),
                failure_class=kind,
                shard=shard,
            )
            if ooms >= 2:
                checker._degrade_memory_lean()
            _interruptible_backoff(delay, checker)
            checker._reset_for_resume()
            old_shards = int(getattr(checker, "n_shards", 1))
            if victim is not None:
                checker._degrade_shards(exclude_shard=victim)
                policy.degraded(victim)
            manifest = None
            if snap is not None:
                manifest = resume_from(
                    checker, snap,
                    # the caller's staleness policy carries over: a
                    # run started with allow_sha_mismatch must not
                    # die on the same check mid-recovery
                    allow_sha_mismatch=getattr(
                        checker, "_resume_allow_sha", False
                    ),
                )
            if victim is not None:
                telemetry.emit(
                    "fault_degrade",
                    from_shards=old_shards,
                    to_shards=int(checker.n_shards),
                    excluded_shard=int(victim),
                    reason=kind,
                    wave=(int(manifest["wave"])
                          if manifest is not None else 0),
                    rerouted_rows=(int(manifest["unique"])
                                   if manifest is not None else 0),
                    snapshot=(os.path.basename(snap) if snap
                              else None),
                )
