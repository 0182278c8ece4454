#!/usr/bin/env python
"""Crash matrix: the end-to-end proof that checkpoint/resume recovers.

Runs every cell of the fault-injection matrix
(stateright_tpu/faultinject.py) against one workload and verdicts each
as **recovered** (kill or device fault → resumed/retried to the exact
baseline count), **refused** (torn snapshot, stale manifest → the
named Snapshot* error), or **continue-degraded** (a persistent
per-shard fault → the supervisor dropped the shard, re-sharded the
last snapshot onto the survivors, and the degraded run completed to
the identical count) — the contract is
continue-degraded-or-recover-or-refuse-loudly, never a silent wrong
answer and never a hang:

* ``kill`` — a SUBPROCESS runs the real CLI check lane with
  ``--checkpoint-every`` and an armed ``STPU_FAULTS`` process kill at
  a seeded chunk boundary (``os._exit(137)``, no cleanup — a real
  preemption), then a second subprocess ``--resume``\\ s from the
  snapshot; the resumed run's final count must equal the baseline's;
* ``device_fault`` — in-process: an injected mid-chunk exception under
  supervision (checkpoint.supervised_run) must self-recover from the
  last snapshot to the identical count in ONE join;
* ``torn_truncate`` / ``torn_flip`` — a valid snapshot damaged on disk
  must be detected (``SnapshotCorruptError``) at resume;
* ``stale_sha`` / ``stale_encoding`` — a rewritten manifest must be
  refused (``SnapshotStaleError``) at resume;
* ``shard_fault_degrade`` (degrade-and-continue round) — a PERSISTENT
  per-shard device fault on an S=2 virtual mesh under
  ``degrade_on_fault``: the FailurePolicy classifies the repeat
  offender, drops the shard, re-shards the snapshot onto the
  survivor, and the run must complete to the identical count
  (**continue-degraded**);
* ``collective_raise`` — an injected raise at the mesh collective
  seam under supervision must recover like any chunk fault;
* ``hang_watchdog`` — an injected chunk-dispatch hang (a wedged
  dispatch: a sleep, no exception) under an armed watchdog: the breach
  must be DETECTED within the derived deadline, and the run either
  recovers from the snapshot (**recovered**) or raises the
  WatchdogTimeout with its attribution (**refused** — loudly, never
  a hang).

``--mesh-degrade`` additionally runs the flagship acceptance pair: a
TRACED 8-shard 2pc rm=5 mesh run with a persistent shard fault at a
mid-run chunk must automatically degrade and complete to the
identical 8,832, with the resume/degrade-aware
``tools/trace_diff.py`` alignment reporting ZERO global-counter
divergence vs the uninterrupted traced baseline; the TRACE pair and
the diff verdict are embedded in the artifact.

``--trace`` additionally runs the baseline and the resumed half
traced (``TRACE_r*`` artifacts land in the repo root) and embeds the
``tools/trace_diff.py`` verdict: the resumed run's wave stream must
align with the uninterrupted baseline at ZERO counter divergence
(telemetry's resume-aware alignment — pre-kill waves died with the
killed process, the overlap must match exactly).

``--json`` writes an auto-numbered ``CKPT_r*.json`` artifact (its own
round sequence like MEM/LAT/COMM, via stateright_tpu/artifacts.py)
carrying the per-cell verdicts, the snapshot byte size vs the memory
ledger's predicted resident bytes, and the trace-diff block.
bench.py embeds the newest CKPT artifact beside LINT/COMM
(``artifacts.latest_ckpt_summary``).

Usage:
  python tools/crash_matrix.py                       # 2pc rm=4, fast
  python tools/crash_matrix.py --workload paxos --count 4 --trace --json
  python tools/crash_matrix.py --seed 7 --json

Exit status: 0 all cells recover-or-refuse, 1 any cell failed,
2 bad input.
"""

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_DONE_RE = re.compile(r"Done\. states=(\d+), unique=(\d+)")


def _cli_env(extra_faults=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if extra_faults:
        env["STPU_FAULTS"] = extra_faults
    else:
        env.pop("STPU_FAULTS", None)
    return env

def _run_cli(args, faults=None, timeout=1800):
    """One CLI subprocess; returns (returncode, unique_count|None,
    new TRACE basenames)."""
    before = set(glob.glob(os.path.join(REPO, "TRACE_r*.jsonl")))
    proc = subprocess.run(
        [sys.executable, "-m", "stateright_tpu"] + args,
        cwd=REPO, env=_cli_env(faults),
        capture_output=True, text=True, timeout=timeout,
    )
    unique = None
    m = _DONE_RE.search(proc.stdout)
    if m:
        unique = int(m.group(2))
    after = set(glob.glob(os.path.join(REPO, "TRACE_r*.jsonl")))
    traces = sorted(os.path.basename(p) for p in after - before)
    return proc, unique, traces


def _spawn_mesh(count, wps, n_shards, **kw):
    """A 2pc virtual-mesh sort-merge checker (the degrade cells —
    the sharded engine refuses cand_capacity='auto', so budgets are
    explicit)."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    import math

    capacity = 1 << max(10, math.ceil(2.6 * count + 1.5))
    kw.setdefault("cand_capacity", 4096)
    kw.setdefault("bucket_capacity", 2048)
    return TwoPhaseSys(rm_count=count).checker() \
        .spawn_tpu_sharded_sortmerge(
            n_shards=n_shards,
            capacity=capacity,
            frontier_capacity=max(256, capacity // 4),
            waves_per_sync=wps,
            **kw,
        )


def _spawn(workload, count, wps, **kw):
    if workload == "2pc":
        from stateright_tpu.models.two_phase_commit import TwoPhaseSys

        import math

        capacity = 1 << max(10, math.ceil(2.6 * count + 1.5))
        return TwoPhaseSys(rm_count=count).checker().spawn_tpu_sortmerge(
            capacity=capacity,
            frontier_capacity=max(256, capacity // 4),
            cand_capacity="auto",
            waves_per_sync=wps,
            **kw,
        )
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
    from stateright_tpu.models.paxos_tpu import STRUCTURAL_SIZES

    return (
        paxos_model(PaxosModelCfg(client_count=count, server_count=3))
        .checker()
        .spawn_tpu_sortmerge(
            track_paths=count <= 2,
            cand_capacity="auto",
            waves_per_sync=wps,
            **STRUCTURAL_SIZES[count],
            **kw,
        )
    )


def _mesh_degrade_proof(cell):
    """The flagship acceptance pair (``--mesh-degrade``): a TRACED
    8-shard 2pc rm=5 virtual-mesh run with a PERSISTENT per-shard
    fault injected at a mid-run chunk must automatically degrade to
    fewer shards and complete to the identical 8,832, with the
    resume/degrade-aware trace_diff reporting ZERO global-counter
    divergence vs the uninterrupted traced baseline. Writes the
    TRACE pair as committed artifacts and returns the block the CKPT
    artifact embeds."""
    import warnings as _warnings

    from stateright_tpu import faultinject
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import (
        RunTracer,
        diff_traces,
        validate_events,
        write_artifacts,
    )

    def spawn(**kw):
        # the dryrun_multichip flagship config (TRACE_r16), at a
        # chunk cadence that puts several boundaries before and
        # after the injected fault
        kw.setdefault("cand_capacity", 2048)
        kw.setdefault("bucket_capacity", 1024)
        return (
            TwoPhaseSys(rm_count=5)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                n_shards=8,
                capacity=1 << 12,
                frontier_capacity=512,
                waves_per_sync=2,
                track_paths=True,
                **kw,
            )
        )

    fault_chunk, fault_shard = 3, 5
    print("mesh degrade acceptance: traced 2pc rm=5 S=8, "
          f"persistent shard fault (shard {fault_shard}) from chunk "
          f"{fault_chunk}")
    tr_base = RunTracer()
    with tr_base.activate():
        b = spawn().join()
    base_n = b.unique_state_count()
    validate_events(tr_base.events)
    jsonl_a, _ = write_artifacts(tr_base)
    print(f"  baseline: {base_n:,} states "
          f"({os.path.basename(jsonl_a)})")

    tmp = tempfile.mkdtemp(prefix="stpu_mesh_degrade_")
    snap = os.path.join(tmp, "mesh.ckpt")
    c = spawn(checkpoint_every=1, checkpoint_path=snap)
    c.degrade_on_fault = True
    c.retry_backoff_sec = 0.01
    tr_deg = RunTracer()
    faultinject.arm("shard_fault", "mid_chunk", fault_chunk,
                    shard=fault_shard)
    err = None
    try:
        with tr_deg.activate():
            with _warnings.catch_warnings():
                _warnings.simplefilter("ignore")
                c.join()
    except Exception as exc:
        err = f"{type(exc).__name__}: {exc}"
    finally:
        faultinject.disarm_all()
        shutil.rmtree(tmp, ignore_errors=True)
    validate_events(tr_deg.events)
    jsonl_b, _ = write_artifacts(tr_deg)
    if err is not None:
        cell("mesh_degrade", "raised", error=err,
             degraded_trace=os.path.basename(jsonl_b))
        return dict(error=err)
    n = c.unique_state_count()
    rep = diff_traces(tr_base.events, tr_deg.events)
    degraded = bool(rep["degrades_b"]) and c.n_shards < 8
    good = (n == base_n and degraded
            and not rep["divergences"] and rep["ok"])
    cell(
        "mesh_degrade",
        "continue-degraded" if good else "count_mismatch",
        count=n, baseline=base_n, to_shards=c.n_shards,
        counter_divergences=len(rep["divergences"]),
    )
    print(f"  trace_diff: {os.path.basename(jsonl_a)} vs "
          f"{os.path.basename(jsonl_b)} — "
          f"{len(rep['divergences'])} counter divergences, "
          f"degraded at wave "
          f"{rep['degrades_b'][0]['wave'] if rep['degrades_b'] else '-'}, "
          f"{'OK' if rep['ok'] else 'FAIL'}")
    return dict(
        baseline_trace=os.path.basename(jsonl_a),
        degraded_trace=os.path.basename(jsonl_b),
        baseline_unique=base_n,
        degraded_unique=n,
        fault_chunk=fault_chunk,
        fault_shard=fault_shard,
        from_shards=8,
        to_shards=int(c.n_shards),
        degrade_wave=(rep["degrades_b"][0]["wave"]
                      if rep["degrades_b"] else None),
        counter_divergences=len(rep["divergences"]),
        diff_ok=bool(rep["ok"]),
    )


def main():
    ap = argparse.ArgumentParser(
        description="fault-injection crash matrix over the "
        "checkpoint/resume path"
    )
    ap.add_argument("--workload", choices=("2pc", "paxos"),
                    default="2pc")
    ap.add_argument("--count", type=int, default=4,
                    help="model size (2pc RMs / paxos clients; "
                    "default 4)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the kill/fault chunk choice "
                    "(faultinject.chunk_for_seed)")
    ap.add_argument("--chunks-hint", type=int, default=5,
                    help="upper bound fed to the seeded chunk pick "
                    "(keep below the workload's real chunk count)")
    ap.add_argument("--waves-per-sync", type=int, default=2,
                    help="chunk cadence for every cell (default 2: "
                    "many boundaries to kill at)")
    ap.add_argument("--trace", action="store_true",
                    help="trace the baseline + resumed runs and embed "
                    "the trace_diff zero-divergence verdict")
    ap.add_argument("--json", action="store_true",
                    help="write an auto-numbered CKPT_r*.json "
                    "artifact")
    ap.add_argument("--root", default=None,
                    help="artifact directory for --json (default: "
                    "the repo root)")
    ap.add_argument("--mesh-degrade", action="store_true",
                    help="additionally run the traced 8-shard 2pc "
                    "rm=5 degrade acceptance pair (TRACE artifacts + "
                    "zero-divergence diff embedded in the JSON)")
    args = ap.parse_args()

    # the mesh cells need virtual devices BEFORE jax initializes
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    from stateright_tpu import faultinject
    from stateright_tpu.checkpoint import (
        SnapshotCorruptError,
        SnapshotStaleError,
        load_snapshot,
    )

    wl_cli = {"2pc": "2pc", "paxos": "paxos"}[args.workload]
    wps = args.waves_per_sync
    kill_chunk = 1 + faultinject.chunk_for_seed(
        args.seed, max(args.chunks_hint - 1, 1)
    )
    tmp = tempfile.mkdtemp(prefix="stpu_crash_matrix_")
    snap = os.path.join(tmp, "matrix.ckpt")
    cells: dict = {}
    ok = True

    def cell(name, verdict, **detail):
        nonlocal ok
        # the degrade column's verdict vocabulary: every cell must
        # land on continue-degraded / recovered / refused-loudly —
        # anything else (incl. a hang, which the driver's timeout
        # would surface) fails the matrix
        good = verdict in ("recovered", "refused",
                           "continue-degraded")
        if not good:
            ok = False
        cells[name] = dict(verdict=verdict, **detail)
        print(f"  {name:20s} {verdict:18s} "
              + " ".join(f"{k}={v}" for k, v in detail.items()))

    print(f"crash matrix: {args.workload} count={args.count} "
          f"seed={args.seed} kill_chunk={kill_chunk} "
          f"waves_per_sync={wps}")

    # -- baseline (subprocess CLI, optionally traced) ---------------------
    base_args = [wl_cli, "check-tpu", str(args.count),
                 f"--waves-per-sync={wps}"]
    if args.trace:
        base_args.append("--trace")
    proc, baseline, base_traces = _run_cli(base_args)
    if proc.returncode != 0 or baseline is None:
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        print("crash_matrix: baseline run failed", file=sys.stderr)
        sys.exit(2)
    print(f"  baseline count: {baseline:,}"
          + (f" (trace {base_traces})" if base_traces else ""))

    # -- cell: process kill at a chunk boundary + resume ------------------
    proc, _, _ = _run_cli(
        base_args[:3] + [f"--waves-per-sync={wps}",
                         "--checkpoint-every=1",
                         f"--checkpoint-path={snap}"],
        faults=f"kill@chunk_boundary:{kill_chunk}",
    )
    if proc.returncode != faultinject.KILL_EXIT_CODE:
        cell("kill", "no_kill", returncode=proc.returncode,
             note="run completed before the seeded kill chunk — "
             "lower --chunks-hint")
    elif not os.path.exists(snap):
        cell("kill", "no_snapshot", returncode=proc.returncode)
    else:
        resume_args = base_args[:3] + [
            f"--waves-per-sync={wps}", "--resume",
            f"--checkpoint-path={snap}",
        ]
        if args.trace:
            resume_args.append("--trace")
        proc2, resumed, res_traces = _run_cli(resume_args)
        if proc2.returncode != 0 or resumed != baseline:
            print(proc2.stdout)
            print(proc2.stderr, file=sys.stderr)
            cell("kill", "count_mismatch", baseline=baseline,
                 resumed=resumed, returncode=proc2.returncode)
        else:
            cell("kill", "recovered", kill_chunk=kill_chunk,
                 baseline=baseline, resumed=resumed,
                 **({"trace": res_traces[0]} if res_traces else {}))
        if args.trace and base_traces and res_traces:
            from stateright_tpu.telemetry import (
                diff_traces,
                load_trace,
                validate_events,
            )

            a = load_trace(os.path.join(REPO, base_traces[0]))
            b = load_trace(os.path.join(REPO, res_traces[0]))
            validate_events(a)
            validate_events(b)
            rep = diff_traces(a, b)
            cells["kill"]["trace_diff"] = dict(
                baseline=base_traces[0],
                resumed=res_traces[0],
                resume_wave=rep["resume_wave_b"],
                counter_divergences=len(rep["divergences"]),
                ok=rep["ok"],
            )
            if rep["divergences"] or not rep["ok"]:
                ok = False
                cells["kill"]["verdict"] = "trace_divergence"
            print(f"  trace_diff: {base_traces[0]} vs "
                  f"{res_traces[0]} — "
                  f"{len(rep['divergences'])} counter divergences, "
                  f"resumed at wave {rep['resume_wave_b']}, "
                  f"{'OK' if rep['ok'] else 'FAIL'}")

    # -- snapshot bytes vs the memory ledger ------------------------------
    snapshot_bytes = plan_bytes = None
    if os.path.exists(snap):
        manifest, _ = load_snapshot(snap)
        snapshot_bytes = manifest.get("snapshot_bytes")

    # -- cell: mid-chunk device fault, supervised self-recovery -----------
    c = _spawn(args.workload, args.count, wps,
               checkpoint_every=1,
               checkpoint_path=os.path.join(tmp, "devfault.ckpt"))
    c.retry_backoff_sec = 0.01
    faultinject.arm("raise", "mid_chunk", kill_chunk)
    import warnings as _warnings

    try:
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            c.join()
        n = c.unique_state_count()
        if c.memory_plan:
            plan_bytes = c.memory_plan.get("resident_bytes")
        cell("device_fault",
             "recovered" if n == baseline else "count_mismatch",
             count=n)
    except Exception as exc:
        cell("device_fault", "raised",
             error=f"{type(exc).__name__}: {exc}")
    finally:
        faultinject.disarm_all()

    # -- cells: torn snapshot ---------------------------------------------
    for mode in ("truncate", "flip"):
        name = f"torn_{mode}"
        if not os.path.exists(snap):
            cell(name, "no_snapshot")
            continue
        bad = os.path.join(tmp, f"{name}.ckpt")
        shutil.copy(snap, bad)
        faultinject.corrupt_snapshot(bad, mode, seed=args.seed)
        try:
            _spawn(args.workload, args.count, wps).resume_from(bad)
            cell(name, "undetected")
        except SnapshotCorruptError as exc:
            cell(name, "refused", error=type(exc).__name__)
        except Exception as exc:
            cell(name, "wrong_error",
                 error=f"{type(exc).__name__}: {exc}")

    # -- cells: stale manifest --------------------------------------------
    for field in ("git_sha", "encoding"):
        name = f"stale_{field.replace('git_', '')}"
        if not os.path.exists(snap):
            cell(name, "no_snapshot")
            continue
        bad = os.path.join(tmp, f"{name}.ckpt")
        shutil.copy(snap, bad)
        faultinject.stale_manifest(bad, field)
        try:
            _spawn(args.workload, args.count, wps).resume_from(bad)
            cell(name, "undetected")
        except SnapshotStaleError as exc:
            cell(name, "refused", error=type(exc).__name__)
        except Exception as exc:
            cell(name, "wrong_error",
                 error=f"{type(exc).__name__}: {exc}")

    # -- cell: persistent per-shard fault -> automatic degrade ------------
    # (degrade-and-continue round: the FailurePolicy sees the same
    # shard fail across retries, drops it, re-shards the snapshot
    # onto the survivor — the run must complete to the exact count)
    c = _spawn_mesh(args.count, wps, n_shards=2,
                    checkpoint_every=1,
                    checkpoint_path=os.path.join(tmp, "deg.ckpt"))
    c.degrade_on_fault = True
    c.retry_backoff_sec = 0.01
    faultinject.arm("shard_fault", "mid_chunk", kill_chunk, shard=1)
    try:
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            c.join()
        n = c.unique_state_count()
        if n == baseline and c.n_shards == 1:
            cell("shard_fault_degrade", "continue-degraded",
                 count=n, from_shards=2, to_shards=c.n_shards)
        else:
            cell("shard_fault_degrade", "count_mismatch",
                 count=n, n_shards=c.n_shards, baseline=baseline)
    except Exception as exc:
        cell("shard_fault_degrade", "raised",
             error=f"{type(exc).__name__}: {exc}")
    finally:
        faultinject.disarm_all()

    # -- cell: collective-seam raise, supervised recovery -----------------
    c = _spawn_mesh(args.count, wps, n_shards=2,
                    checkpoint_every=1,
                    checkpoint_path=os.path.join(tmp, "coll.ckpt"))
    c.retry_backoff_sec = 0.01
    faultinject.arm("raise", "collective_seam", kill_chunk,
                    once=True)
    try:
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            c.join()
        n = c.unique_state_count()
        cell("collective_raise",
             "recovered" if n == baseline else "count_mismatch",
             count=n)
    except Exception as exc:
        cell("collective_raise", "raised",
             error=f"{type(exc).__name__}: {exc}")
    finally:
        faultinject.disarm_all()

    # -- cell: chunk-dispatch hang -> watchdog ----------------------------
    # (a wedged dispatch: a sleep at the dispatch site, no exception
    # — only the watchdog can see it; the verdict must be recovered
    # or refused-loudly-with-attribution, never a hang)
    from stateright_tpu.checkpoint import WatchdogTimeout

    c = _spawn(args.workload, args.count, wps,
               checkpoint_every=1,
               checkpoint_path=os.path.join(tmp, "hang.ckpt"))
    c.retry_backoff_sec = 0.01
    c.watchdog_factor = 5.0
    c.watchdog_floor_sec = 1.0
    c.watchdog_grace_sec = 20.0
    faultinject.arm("hang", "mid_chunk", kill_chunk, hang_sec=25.0)
    try:
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            c.join()
        n = c.unique_state_count()
        cell("hang_watchdog",
             "recovered" if n == baseline else "count_mismatch",
             count=n)
    except WatchdogTimeout as exc:
        # refuse-loudly-with-diagnosis: acceptable where in-process
        # recovery isn't (the attribution names the hung chunk)
        cell("hang_watchdog", "refused",
             error="WatchdogTimeout", chunk=exc.chunk,
             deadline_sec=round(exc.deadline_sec, 3))
    except Exception as exc:
        cell("hang_watchdog", "raised",
             error=f"{type(exc).__name__}: {exc}")
    finally:
        faultinject.disarm_all()

    # -- the flagship degrade acceptance pair (--mesh-degrade) ------------
    mesh_degrade = None
    if args.mesh_degrade:
        mesh_degrade = _mesh_degrade_proof(cell)

    print(f"verdict: {'CLEAN' if ok else 'FAIL'} "
          f"({sum(1 for c in cells.values() if c['verdict'] in ('recovered', 'refused', 'continue-degraded'))}"
          f"/{len(cells)} cells continue-degraded/recover/refuse)")
    if snapshot_bytes is not None:
        print(f"snapshot bytes: {snapshot_bytes:,}"
              + (f" (memplan resident: {plan_bytes:,})"
                 if plan_bytes else ""))

    if args.json:
        from stateright_tpu.artifacts import (
            artifact_path,
            next_round,
            provenance,
        )

        root = args.root or REPO
        path = artifact_path(
            "CKPT", "json", root=root,
            round=next_round(root, stems=("CKPT",)),
        )
        doc = dict(
            workload=args.workload,
            count=args.count,
            seed=args.seed,
            kill_chunk=kill_chunk,
            waves_per_sync=wps,
            baseline_unique=baseline,
            snapshot_bytes=snapshot_bytes,
            memplan_resident_bytes=plan_bytes,
            cells=cells,
            # the degrade column, summarized: which cells landed on
            # continue-degraded and where they degraded to
            degrade_cells={
                name: {k: c[k] for k in
                       ("from_shards", "to_shards") if k in c}
                for name, c in cells.items()
                if c["verdict"] == "continue-degraded"
            },
            mesh_degrade=mesh_degrade,
            clean=ok,
            provenance=provenance(),
        )
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {path}")

    shutil.rmtree(tmp, ignore_errors=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
