#!/usr/bin/env python
"""Drive the main path once on a TPU chip and check what comes out.

One process, through the entry points a user calls: ``cli.main`` for
``2pc check-tpu 5`` and ``paxos check-tpu 4`` (the north-star check at
its full ``STRUCTURAL_SIZES[4]`` width), each checked against its pin.
Each phase runs cold, then warm: the in-memory program caches are
dropped and the same CLI call runs again, so the warm run reads the
persistent compile cache. The auto-budget store is put back as the cold
run found it before the warm run: a budget the cold run shrank would be
a different program, compiled again (ROADMAP S5). Earlier lines say
what ran (device kind, resolved ``merge_impl``, walls, compile-cache
directory and hits); the last line is the JSON verdict, printed only
when every phase passed on a TPU.

``--chips 4`` runs only the sharded sort-merge engine over a 4-chip
mesh on paxos 4c/3s with the per-shard budgets of
``models/paxos_tpu.py`` ``SHARDED_SIZES[4]``, and shows that the
visited shards live on four distinct devices.

Exits non-zero, with no verdict, when JAX finds no TPU (including under
``JAX_PLATFORMS=cpu``), when a count or property set differs, or when a
phase raises.
"""

import argparse
import json
import sys
import time

#: the pinned unique-state counts (host-oracle pins: bench.py's lanes,
#: tests/test_paxos_tpu.py) and the discoveries the host search gives
TWOPC5_STATES = 8832
PAXOS4_STATES = 2372188
PAXOS4_DISCOVERIES = {"value chosen"}

def log(msg: str) -> None:
    print(msg, flush=True)


def run_cli(argv: list) -> tuple:
    """``cli.main(argv)`` in this process; returns the checker the CLI
    built (captured at its reporting seam), the wall seconds and the
    compile-cache counters it moved. Of the CLI's report (counterexample
    paths included) only its summary line is echoed."""
    import contextlib
    import io

    import jax

    from stateright_tpu import cli
    from stateright_tpu.checkers import tpu

    got = []
    cli._SESSION_HOOK.hook = got.append
    before = tpu.compile_ledger_totals()
    report = io.StringIO()
    t0 = time.monotonic()
    try:
        with contextlib.redirect_stdout(report):
            cli.main(argv)
    finally:
        cli._SESSION_HOOK.hook = None
    wall = time.monotonic() - t0
    for line in report.getvalue().splitlines():
        if line.startswith("Done."):
            log(f"  {line}")
    after = tpu.compile_ledger_totals()
    (checker,) = got
    delta = {k: round(after[k] - before[k], 3)
             for k in ("compiles", "disk_hits", "cold_compiles",
                       "compile_sec", "stage_sec")}
    log(f"  {' '.join(argv)}: wall {wall:.3f} s, merge_impl="
        f"{checker.merge_impl}, compile cache "
        f"{jax.config.jax_compilation_cache_dir}: {delta}")
    return checker, wall, delta


def drop_program_caches() -> None:
    """Forget every compiled program this process holds, so the next
    run builds through the persistent compile cache."""
    import jax

    from stateright_tpu.checkers import tpu

    tpu._CHUNK_CACHE.clear()
    jax.clear_caches()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def cold_and_warm(argv: list) -> list:
    import os

    from stateright_tpu.checkers.tpu_sortmerge import budget_store_path

    store = budget_store_path()
    found = open(store).read() if os.path.exists(store) else None
    log(f"phase {' '.join(argv)}")
    log(" cold:")
    runs = [run_cli(argv)]
    drop_program_caches()
    if found is None:
        if os.path.exists(store):
            os.remove(store)
    else:
        with open(store, "w") as fh:
            fh.write(found)
    log(" warm:")
    runs.append(run_cli(argv))
    return runs


def phase_2pc() -> None:
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    host = TwoPhaseSys(rm_count=5).checker().spawn_bfs().join()
    want = set(host.discoveries())
    check(host.unique_state_count() == TWOPC5_STATES,
          f"host BFS 2pc rm=5: {host.unique_state_count()}")
    for checker, _, _ in cold_and_warm(["2pc", "check-tpu", "5"]):
        n = checker.unique_state_count()
        got = checker.discovered_property_names()
        log(f"  unique={n} discoveries={sorted(got)} "
            f"(host BFS: {host.unique_state_count()} {sorted(want)})")
        check(n == TWOPC5_STATES, f"2pc rm=5 unique {n}")
        check(got == want, f"2pc rm=5 discoveries {got} != {want}")


def phase_paxos4() -> dict:
    runs = cold_and_warm(["paxos", "check-tpu", "4"])
    for checker, _, _ in runs:
        n = checker.unique_state_count()
        got = checker.discovered_property_names()
        log(f"  unique={n} discoveries={sorted(got)}")
        check(n == PAXOS4_STATES, f"paxos 4 unique {n}")
        check(got == PAXOS4_DISCOVERIES, f"paxos 4 discoveries {got}")
    return runs[-1][2]


def phase_sharded_paxos4() -> None:
    import numpy as np

    from stateright_tpu.checkers import tpu
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
    from stateright_tpu.models.paxos_tpu import SHARDED_SIZES

    log(f"phase sharded paxos 4c/3s over 4 chips: {SHARDED_SIZES[4]}")
    before = tpu.compile_ledger_totals()
    t0 = time.monotonic()
    checker = (
        paxos_model(PaxosModelCfg(client_count=4, server_count=3))
        .checker()
        .spawn_tpu_sharded_sortmerge(n_shards=4, track_paths=False,
                                     **SHARDED_SIZES[4])
        .join()
    )
    n = checker.unique_state_count()
    got = checker.discovered_property_names()
    vkeys, _, _, u_loc = checker._final_tables
    per_shard = [int(x) for x in np.asarray(u_loc)]
    devices = sorted({str(s.device) for s in vkeys.addressable_shards})
    wall = time.monotonic() - t0
    after = tpu.compile_ledger_totals()
    log(f"  wall {wall:.3f} s (cold; compile_sec "
        f"{after['compile_sec'] - before['compile_sec']:.3f}), merge_impl="
        f"{checker.merge_impl}, unique={n} discoveries={sorted(got)}")
    log(f"  per-shard unique {per_shard}; visited shards on {devices}")
    check(n == PAXOS4_STATES, f"sharded paxos 4 unique {n}")
    check(sum(per_shard) == n, f"per-shard sum {sum(per_shard)} != {n}")
    check(got == PAXOS4_DISCOVERIES, f"sharded discoveries {got}")
    check(len(devices) == 4, f"visited shards on {devices}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    import stateright_tpu  # noqa: F401  (fails outside the checkout)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    if args.chips == 4:
        phase_sharded_paxos4()
    else:
        phase_2pc()
        warm = phase_paxos4()
        check(warm["disk_hits"] > 0, f"warm paxos 4 had no cache hit: {warm}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
