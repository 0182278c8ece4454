#!/usr/bin/env python
"""Benchmark harness — the replacement for the reference's bench.sh
(/root/reference/bench.sh:18-33, which greps `sec=` out of 3 reporter
runs per workload).

Runs the encoded workloads on the TPU (JAX's default backend; the run
refuses any other backend unless ``JAX_PLATFORMS=cpu`` was set
explicitly) and prints exactly ONE JSON line on stdout:

    {"metric": ..., "value": N, "unit": "states/sec",
     "vs_baseline": N, "detail": {...}}

``value`` is unique-states/sec on the headline workload (largest
encoded state space), timed warm (second run; the XLA compile cache
makes re-runs and CLI invocations warm too). ``vs_baseline`` is the
speedup over the sequential host BFS oracle measured live on this same
machine — the reference publishes no numbers (BASELINE.md) and its
Rust toolchain isn't in this image, so the host oracle is the honest
stand-in for the reference's single-thread CPU search.

Per-workload details go to stderr; ``--verbose`` adds per-run wave
metrics (frontier size, occupancy, dedup ratio — plus shuffle volume
on SHARDED lanes, where the engines count routed rows; the standard
lanes are single-chip and have no shuffle). A lane whose checker
reports ``shuffle_volume`` gets it in its detail row, and a traced
lane whose TRACE carries per-shard ``shard_wave`` events additionally
gets the derived ``shard_balance`` skew/routing summary
(telemetry.shard_balance — the same block the MULTICHIP dryrun
embeds), so direction-1 mesh runs land with skew numbers attached.
Every lane also embeds its ``memory_plan`` totals (the resident-buffer
ledger, stateright_tpu/memplan.py) and — on traced lanes, where the
watermark polls — the run's device peak bytes, so BENCH artifacts
land with memory numbers attached the way they land with balance
numbers. Round 14 adds the LATENCY axis: every device lane embeds its
host-side dispatch/sync-floor wall split (``latency_accounting`` —
kept even untraced) and the per-lane compile-cache ledger delta
(XLA compile-or-fetch count, persistent-cache disk hits, total cold
wall — ``checkers.tpu.compile_ledger_totals``), and the provenance
block carries the process totals, so BENCH_r06's warm/cold A/B is
attributable from the artifact alone.
"""

import argparse
import json
import os
import sys
import time


def _stderr(*args):
    print(*args, file=sys.stderr, flush=True)


def time_checker(spawn, runs=2):
    """Spawn+join ``runs`` times; return (checker, best_seconds).

    The first run pays any residual compile cost (the persistent XLA
    cache usually absorbs it); the best run is reported, mirroring
    bench.sh's min-of-3 convention.
    """
    best = float("inf")
    checker = None
    for _ in range(runs):
        c = spawn()
        t0 = time.monotonic()
        c.join()
        dt = time.monotonic() - t0
        best = min(best, dt)
        checker = c
    return checker, best


def bench_host_oracle():
    """Sequential host BFS on 2pc rm=5 — the vs_baseline denominator.

    Caveat (VERDICT r4): this is a ONE-thread Python oracle (~2.3k
    st/s). The reference's Rust BFS on a many-core host would be
    orders of magnitude faster, so ``vs_baseline`` measures the gap to
    THIS repo's host engine, not to the reference binary (which isn't
    in the image; the reference also publishes no numbers,
    BASELINE.md). ``threads(n)`` exists and is real, but CPython's GIL
    makes pure-Python model callbacks serialize, so n>1 does not make
    this denominator honestly faster."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(rm_count=5).checker().spawn_bfs()
    t0 = time.monotonic()
    c.join()
    dt = time.monotonic() - t0
    sps = c.unique_state_count() / dt
    _stderr(
        f"host-oracle  2pc rm=5: unique={c.unique_state_count()} "
        f"sec={dt:.2f} states/sec={sps:,.0f}"
    )
    return sps


def tpu_workloads(quick=False):
    """(name, spawn, hybrid_spawn, expected_unique) for every encoded
    workload; the LAST entry is the headline. ``hybrid_spawn`` is set
    for every sub-100k lane (VERDICT r5 item 7): those lanes complete
    in ~one host<->device round trip on the wave engine, so their
    states/sec measures the sync floor — the hybrid racer's wall time
    is the product answer and is recorded alongside.

    Per-wave BUDGETS are auto-sized (``cand_capacity="auto"``: start
    from the persisted store or a growth heuristic, resize loudly from
    the measured peak on overflow — VERDICT r5 item 6 retired
    ``TUNED_ENGINE_CAPS`` and the per-lane caps tables). Only
    STRUCTURAL sizes remain per lane: ``capacity`` from the pinned
    state count, ``frontier_capacity`` from the measured wave peak.
    """
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    def twopc(rm, hybrid=False, **kw):
        def spawn():
            b = TwoPhaseSys(rm_count=rm).checker()
            fn = b.spawn_hybrid if hybrid else b.spawn_tpu_sortmerge
            return fn(track_paths=False, cand_capacity="auto", **kw)

        return spawn

    def twopc_sym(rm, **kw):
        # The device symmetry-reduction lane (ROADMAP 4(a)): the
        # same protocol with candidates canonicalized before dedup
        # (ops/canonical.py), so the engine explores the orbit
        # quotient — 8,832 -> 314 at rm=5. Counts are the PERFECT
        # canonicalizer's, order-independent and host-oracle-pinned
        # (tests/test_device_symmetry.py; the reference's 665 is a
        # DFS-order artifact, see symmetry.py).
        def spawn():
            return (
                TwoPhaseSys(rm_count=rm)
                .checker()
                .symmetry()
                .spawn_tpu_sortmerge(
                    track_paths=False, cand_capacity="auto", **kw
                )
            )

        return spawn

    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
    from stateright_tpu.models.paxos_tpu import STRUCTURAL_SIZES

    def paxos(clients, hybrid=False, **kw):
        def spawn():
            b = paxos_model(
                PaxosModelCfg(client_count=clients, server_count=3)
            ).checker()
            fn = b.spawn_hybrid if hybrid else b.spawn_tpu_sortmerge
            return fn(track_paths=False, cand_capacity="auto",
                      **dict(STRUCTURAL_SIZES[clients], **kw))

        return spawn

    # The literal driver configs (BASELINE.md:29-32) come first: tiny
    # spaces that measure the dispatch/sync floor more than compute
    # (the reference does these in ms on the host; the hybrid racer is
    # the right engine for them — these lanes keep the TPU engine
    # honest on breadth, not just the big-space headline).
    from stateright_tpu.models.increment import IncrementLock
    from stateright_tpu.models.single_copy_register import (
        SingleCopyRegisterCfg,
        single_copy_register_model,
    )

    def increment_lock(n, hybrid=False, **kw):
        def spawn():
            b = IncrementLock(thread_count=n).checker()
            fn = b.spawn_hybrid if hybrid else b.spawn_tpu_sortmerge
            return fn(track_paths=False, cand_capacity="auto", **kw)

        return spawn

    def single_copy(n, hybrid=False, **kw):
        def spawn():
            b = single_copy_register_model(
                SingleCopyRegisterCfg(client_count=n)
            ).checker()
            fn = b.spawn_hybrid if hybrid else b.spawn_tpu_sortmerge
            # Dense dispatch: in round 5 the SPARSE chunk program for
            # this compiled encoding got the remote compile helper of
            # that era killed; the dense program compiles and runs
            # fine, and at K=21 the dense wave is cheap anyway
            # (PERF.md §bring-up: the sparse program's v5e compile
            # rehearsal).
            return fn(track_paths=False, sparse=False,
                      cand_capacity="auto", **kw)

        return spawn

    # The COMPILED lanes (ROADMAP direction 5: the compiled encodings
    # AT PRODUCTION SHAPES, beside their hand-encoding denominators):
    # the count-comparable 2pc system actor model and actor paxos
    # through the generic actor->encoding compiler — zero hand device
    # code — at the SAME pinned counts as the hand lanes (8,832 /
    # 50,816 / 296,448 / 16,668), so the compiled-vs-hand gap is a
    # parity RATIO of like-for-like walls (COMPILED_PARITY below),
    # not a comparison across different state spaces. Encodings are
    # built ONCE and memoized outside the spawn closure: the compile
    # (and, for paxos, the reachable-mode host harvest) is a
    # one-time cost the timed A/B must not re-pay per pooled run.
    _compiled_enc_cache = {}

    def twopc_sys_compiled(rm, **kw):
        from stateright_tpu.models.two_phase_commit_actors import (
            two_phase_sys_actor_model,
            two_phase_sys_compiled_encoded,
        )

        def spawn():
            key = ("2pc-sys", rm)
            if key not in _compiled_enc_cache:
                _compiled_enc_cache[key] = (
                    two_phase_sys_compiled_encoded(rm)
                )
            return (
                two_phase_sys_actor_model(rm)
                .checker()
                .spawn_tpu_sortmerge(
                    encoded=_compiled_enc_cache[key],
                    track_paths=False, cand_capacity="auto", **kw,
                )
            )

        return spawn

    def paxos_compiled(clients, servers, **kw):
        def spawn():
            from stateright_tpu.models.paxos import (
                paxos_compiled_encoded,
            )

            cfg = PaxosModelCfg(
                client_count=clients, server_count=servers,
                put_count=1,
            )
            key = ("paxos", clients, servers)
            if key not in _compiled_enc_cache:
                _compiled_enc_cache[key] = paxos_compiled_encoded(cfg)
            return paxos_model(cfg).checker().spawn_tpu_sortmerge(
                encoded=_compiled_enc_cache[key], track_paths=False,
                cand_capacity="auto", **kw,
            )

        return spawn

    loads = [
        (
            # Driver config `2pc check 3` (examples/2pc.rs:153-154).
            "2pc rm=3",
            twopc(3, capacity=1 << 10, frontier_capacity=1 << 8),
            twopc(3, hybrid=True, capacity=1 << 10,
                  frontier_capacity=1 << 8),
            288,
        ),
        (
            # Driver config `increment_lock` (examples/increment_lock.rs
            # CLI default: 3 threads).
            "increment_lock n=3",
            increment_lock(3, capacity=1 << 10,
                           frontier_capacity=1 << 8),
            increment_lock(3, hybrid=True, capacity=1 << 10,
                           frontier_capacity=1 << 8),
            61,
        ),
        (
            # Driver config `single-copy-register check 3`
            # (examples/single-copy-register.rs; count host-pinned).
            "single-copy 3c",
            single_copy(3, capacity=1 << 13,
                        frontier_capacity=1 << 11),
            single_copy(3, hybrid=True, capacity=1 << 13,
                        frontier_capacity=1 << 11),
            4243,
        ),
        (
            "2pc rm=5",
            twopc(5, capacity=1 << 14, frontier_capacity=1 << 11),
            twopc(5, hybrid=True, capacity=1 << 14,
                  frontier_capacity=1 << 11),
            8832,
        ),
        (
            # The compiled 2pc lane AT the hand lane's shape (ISSUE
            # 20): the count-comparable system actor model
            # (two_phase_sys_actor_model — host-parity pinned at the
            # TwoPhaseSys counts) through the codegen-optimized
            # compiler. The "2pc rm=5" lane above is the parity
            # denominator (COMPILED_PARITY).
            "2pc-actors rm=5 (compiled)",
            twopc_sys_compiled(5, capacity=1 << 14,
                               frontier_capacity=1 << 11),
            None,
            8832,
        ),
        (
            # the rm=5..7 symmetry sweep rides beside its raw lanes:
            # same protocol, canonical-fingerprint dedup, the lane
            # detail records the reduction ratio (SYM_LANES below)
            "2pc rm=5 (sym)",
            twopc_sym(5, capacity=1 << 11, frontier_capacity=256),
            None,
            314,
        ),
        (
            "paxos 2c/3s",
            paxos(2),
            paxos(2, hybrid=True),
            16668,
        ),
        (
            # The compiled paxos lane at the hand "paxos 2c/3s"
            # shape (same PaxosModelCfg; reachable-mode harvest is
            # paid once at encoding build, outside the timed runs).
            "paxos 2c/3s (compiled)",
            paxos_compiled(2, 3, capacity=1 << 15,
                           frontier_capacity=1 << 12),
            None,
            16668,
        ),
        (
            "2pc rm=6",
            twopc(6, capacity=1 << 16, frontier_capacity=1 << 14),
            twopc(6, hybrid=True, capacity=1 << 16,
                  frontier_capacity=1 << 14),
            50816,
        ),
        (
            "2pc-actors rm=6 (compiled)",
            twopc_sys_compiled(6, capacity=1 << 16,
                               frontier_capacity=1 << 14),
            None,
            50816,
        ),
        (
            "2pc rm=6 (sym)",
            twopc_sym(6, capacity=1 << 12, frontier_capacity=512),
            None,
            553,
        ),
        (
            "2pc rm=7 (sym)",
            twopc_sym(7, capacity=1 << 13, frontier_capacity=1024),
            None,
            920,
        ),
        (
            # before the hand rm=7 so THAT lane stays the traced
            # headline (the compiled lane is a parity lane, not a
            # throughput headline)
            "2pc-actors rm=7 (compiled)",
            twopc_sys_compiled(7, capacity=1 << 19,
                               frontier_capacity=1 << 16),
            None,
            296448,
        ),
        (
            # stays LAST among the quick lanes: the raw rm=7 is the
            # --quick headline (the sym lanes are reduction lanes,
            # not throughput headlines)
            "2pc rm=7",
            twopc(7, capacity=1 << 19, frontier_capacity=1 << 16),
            None,
            296448,
        ),
    ]
    # Driver config family `linearizable-register check N ordered`
    # (BASELINE.md:32, bench.sh:33): ABD over FIFO channels, compiled
    # by the actor→encoding compiler in overapprox mode from DECLARED
    # queue bounds (abd_queue_bounds — no host exploration), budgets
    # AUTO-SIZED from measured peaks (no caps table). The 1,212,979
    # count is device-derived, pinned by the depth-prefix host
    # differential in tests/test_actor_compile.py and reproduced
    # across runs; the 4-client driver config's closure is the
    # round-5 frontier (see linearizable_register.py max_domain).
    from stateright_tpu.actor.network import Network
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    def abd_ordered(n, **kw):
        def spawn():
            return (
                abd_model(
                    AbdModelCfg(client_count=n, server_count=3),
                    Network.new_ordered(),
                )
                .checker()
                .spawn_tpu_sortmerge(track_paths=False, **kw)
            )

        return spawn

    if not quick:
        loads.append(
            (
                "abd 2c/3s ordered",
                abd_ordered(
                    2,
                    capacity=1 << 21,
                    frontier_capacity=1 << 18,
                    cand_capacity="auto",
                ),
                None,
                1212979,
            )
        )
        loads.append(
            (
                # The north-star workload family (examples/paxos.rs
                # check N): the generalized encoding runs check 3
                # exhaustively on chip. Count verified by host-BFS
                # differential at depths 6-12 (tests/test_paxos_tpu.py)
                # plus the STPU_EXHAUSTIVE host-DFS pin. Sparse action
                # dispatch (round 4): candidate budgets track ENABLED
                # (row, slot) pairs, not F*K slot cells.
                "paxos 3c/3s",
                paxos(3),
                None,
                1194428,
            )
        )
        loads.append(
            (
                "2pc rm=8",
                twopc(8, capacity=1 << 21,
                      frontier_capacity=1 << 19),
                None,
                1745408,
            )
        )
        loads.append(
            (
                # 10.34M states (~the 10^7 regime the north star lives
                # in). The count is reproduced by two independently
                # shaped engine configs (different class ladders, tile
                # counts, and merge programs) and extends the pinned
                # 2pc growth sequence smoothly (ratio 5.925 after
                # 5.754/5.833/5.888); the hash-table engine OOMs the
                # worker at this scale.
                "2pc rm=9",
                twopc(
                    9,
                    capacity=11 << 20,
                    frontier_capacity=3 << 19,
                    # Finer compaction tiles measured ~5% faster at this
                    # scale (lax.sort is superlinear; PERF.md).
                    tile_rows=1 << 20,
                ),
                None,
                10340352,
            )
        )
        loads.append(
            (
                # `paxos check 5`: five clients span two client lanes
                # (VERDICT r3 #6); sized by the padded-HBM rule
                # (PERF.md: a [N, W] state buffer costs ~512 B/row).
                "paxos 5c/3s",
                paxos(5),
                None,
                4711569,
            )
        )
        loads.append(
            (
                # THE north-star workload (BASELINE.md:27 defines the
                # target on `paxos check 4`, examples/paxos.rs:352-465).
                # The true space is 2,372,188 states at depth 28 — far
                # below the pre-measurement ~85M estimate, because the
                # 4th client shares leader 0, whose single-Put guard
                # (proposal-None) caps the ballot blowup. First
                # executed round 4, via sparse dispatch.
                "paxos 4c/3s",
                paxos(4),
                None,
                2372188,
            )
        )
    return loads


#: the symmetry sweep's raw-space denominators (ROADMAP 4(a)): lane
#: name -> (raw unique states, rm). The raw counts are the pinned
#: unreduced 2pc spaces (the non-sym lanes above them); the ratio the
#: lane detail records is raw / canonical.
SYM_LANES = {
    "2pc rm=5 (sym)": (8832, 5),
    "2pc rm=6 (sym)": (50816, 6),
    "2pc rm=7 (sym)": (296448, 7),
}

#: compiled lane -> its hand-encoding denominator lane (round 23):
#: both lanes explore the SAME pinned state space, so
#: parity_ratio = compiled pooled-min wall / hand pooled-min wall is
#: a like-for-like number. Embedded in every compiled lane's detail
#: (and the provenance block) by the post-loop pass in main() — the
#: gap ROADMAP direction 5 chases is a tracked metric from this
#: round on.
COMPILED_PARITY = {
    "2pc-actors rm=5 (compiled)": "2pc rm=5",
    "2pc-actors rm=6 (compiled)": "2pc rm=6",
    "2pc-actors rm=7 (compiled)": "2pc rm=7",
    "paxos 2c/3s (compiled)": "paxos 2c/3s",
}


def bench_sym_host_oracle(rm):
    """The host DFS symmetry oracle (the perfect canonicalizer,
    representative_full) — the device-vs-host-DFS comparison the
    "2pc rm=5 (sym)" lane records: same reduced count, host wall for
    the A/B."""
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    c = (
        TwoPhaseSys(rm_count=rm)
        .checker()
        .symmetry_fn(lambda s: s.representative_full())
        .spawn_dfs()
    )
    t0 = time.monotonic()
    c.join()
    dt = time.monotonic() - t0
    _stderr(
        f"host-dfs-sym 2pc rm={rm}: unique={c.unique_state_count()} "
        f"sec={dt:.2f}"
    )
    return c.unique_state_count(), dt


def bench_ttfc(runs=2):
    """Time-to-first-counterexample (BASELINE.md primary metric #2):
    wall-clock from spawn to discovery, host DFS vs the TPU engine.
    The increment lanes are true TTFC (their only property is violated,
    so both engines early-exit at the discovery; the wave engine stops
    at the end of the discovering wave). The paxos lane is labeled
    "full check": its always-property holds, so neither engine can
    early-exit — the time measured is verification to completion
    INCLUDING the deep sometimes-discovery."""
    from stateright_tpu.models.increment import Increment

    def host_increment(n):
        def spawn():
            return Increment(thread_count=n).checker().spawn_dfs()

        return spawn

    def tpu_increment(n):
        def spawn():
            return Increment(thread_count=n).checker().spawn_tpu_sortmerge(
                capacity=1 << 16,
                frontier_capacity=1 << 12,
                cand_capacity=1 << 14,
                track_paths=False,
            )

        return spawn

    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

    def host_paxos():
        return (
            paxos_model(PaxosModelCfg(client_count=2, server_count=3))
            .checker()
            .spawn_dfs()
        )

    def tpu_paxos():
        return (
            paxos_model(PaxosModelCfg(client_count=2, server_count=3))
            .checker()
            .spawn_tpu_sortmerge(
                capacity=1 << 15,
                frontier_capacity=1 << 12,
                cand_capacity=1 << 14,
                track_paths=False,
            )
        )

    def hybrid_increment(n):
        def spawn():
            return Increment(thread_count=n).checker().spawn_hybrid(
                capacity=1 << 16,
                frontier_capacity=1 << 12,
                cand_capacity=1 << 14,
                track_paths=False,
            )

        return spawn

    def hybrid_paxos():
        return (
            paxos_model(PaxosModelCfg(client_count=2, server_count=3))
            .checker()
            .spawn_hybrid(
                capacity=1 << 15,
                frontier_capacity=1 << 12,
                cand_capacity=1 << 14,
                track_paths=False,
            )
        )

    out = {}
    for name, host_spawn, tpu_spawn, hy_spawn, prop in [
        # Lost-update race: the racy counter violates "fin"
        # (examples/increment.rs semantics) a few steps in — host DFS
        # wins shallow bugs; the wave engine pays per-wave dispatch;
        # the HYBRID racer (spawn_hybrid) adopts whichever engine
        # finishes first, so it is host-or-tie here and device-or-tie
        # on the deep lane (VERDICT r3 weak #5).
        ("increment n=4", host_increment(4), tpu_increment(4),
         hybrid_increment(4), "fin"),
        ("increment n=6", host_increment(6), tpu_increment(6),
         hybrid_increment(6), "fin"),
        # Deep discovery + exhaustion: the chosen value needs a full
        # quorum round (examples/paxos.rs "value chosen") and the
        # holding always-property forces both engines to completion.
        ("paxos 2c/3s full check", host_paxos, tpu_paxos, hybrid_paxos,
         "value chosen"),
    ]:
        h, h_sec = time_checker(host_spawn, runs=runs)
        t, t_sec = time_checker(tpu_spawn, runs=runs)
        # Pair the reported winner with the run that produced the
        # reported (best) time.
        y = None
        y_sec = float("inf")
        y_winner = None
        for _ in range(runs):
            c = hy_spawn()
            t0 = time.monotonic()
            c.join()
            dt = time.monotonic() - t0
            if dt < y_sec:
                y_sec, y_winner = dt, c.winner
            y = c
        assert prop in h.discoveries(), (name, "host")
        assert prop in t.discovered_property_names(), (name, "tpu")
        assert prop in y.discovered_property_names(), (name, "hybrid")
        out[name] = {
            "host_sec": round(h_sec, 4),
            "tpu_sec": round(t_sec, 4),
            "hybrid_sec": round(y_sec, 4),
            "hybrid_winner": y_winner,
            "property": prop,
        }
        kind = (
            "verification to completion incl. the deep discovery"
            if "full check" in name
            else f"first {prop!r} counterexample"
        )
        _stderr(
            f"ttfc {name}: host={h_sec:.3f}s tpu={t_sec:.3f}s "
            f"hybrid={y_sec:.3f}s (winner={y_winner}; {kind})"
        )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip the largest workload")
    ap.add_argument("--verbose", action="store_true", help="per-run wave metrics")
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument(
        "--trace", nargs="?", const="default",
        choices=("default", "deep"), default=None,
        help="record run telemetry for the HEADLINE workload's timed "
        "runs (stateright_tpu/telemetry.py) and write auto-numbered "
        "TRACE_r*.jsonl + TRACE_r*.trace.json artifacts; 'deep' adds "
        "per-wave syncs (real per-wave walls, so do not read the "
        "headline states/sec off a deep-traced run)",
    )
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU (backend {backend!r}); set "
            "JAX_PLATFORMS=cpu explicitly to benchmark the CPU backend"
        )
    _stderr(f"backend: {jax.devices()}")

    # The host↔device sync floor: ANY blocking readback costs this
    # much (~110-130ms on the remote-TPU link of rounds 2-5; PERF.md
    # §sync-floor).
    # The small driver-config lanes (2pc rm=3/5, increment_lock,
    # single-copy, paxos 2c) complete in ~ONE such round trip warm —
    # their states/sec measures the link, not the engine.
    import jax.numpy as jnp
    import numpy as _np

    _tiny = jax.jit(lambda x: x + 1)
    _np.asarray(_tiny(jnp.uint32(0)))
    _t0 = time.monotonic()
    _np.asarray(_tiny(jnp.uint32(1)))
    sync_floor_ms = round((time.monotonic() - _t0) * 1000, 1)
    _stderr(f"sync floor (blocking readback RTT): {sync_floor_ms} ms")

    host_sps = bench_host_oracle()

    tracer = None
    if args.trace is not None:
        from stateright_tpu.telemetry import RunTracer

        tracer = RunTracer(level=args.trace)

    # LINT cross-reference (round 9): every lane's detail embeds the
    # newest LINT artifact's carry-copy-bytes totals, so a BENCH
    # number and the static switch-carry state it was measured under
    # pair up without hand-matching round numbers (the gated
    # carry-copy rule, stateright_tpu/analysis/).
    from stateright_tpu.artifacts import (
        latest_comms_summary,
        latest_lint_summary,
    )

    lint_ref = latest_lint_summary()
    if lint_ref is not None:
        _stderr(
            f"lint ref: {lint_ref['artifact']} "
            f"carry_copy_bytes={lint_ref['carry_copy_bytes']} "
            f"clean={lint_ref['clean']}"
        )
    # COMM cross-reference (round 13, same best-effort contract):
    # the newest comms-lint artifact — the static collective
    # accounting a traced mesh lane's shard_balance.comms_static
    # reconciles against (PERF.md §comms-lint).
    comms_ref = latest_comms_summary()
    if comms_ref is not None:
        _stderr(
            f"comms ref: {comms_ref['artifact']} "
            f"clean={comms_ref['clean']}"
        )
    # CKPT cross-reference (the durability layer, same best-effort
    # contract): the newest crash-matrix artifact — whether the
    # kill/fault/torn/stale cells all landed on recover-or-refuse at
    # the referenced SHA (tools/crash_matrix.py).
    from stateright_tpu.artifacts import latest_ckpt_summary

    ckpt_ref = latest_ckpt_summary()
    if ckpt_ref is not None:
        _stderr(
            f"ckpt ref: {ckpt_ref['artifact']} "
            f"clean={ckpt_ref['clean']}"
        )
    # SERVE cross-reference (the resident-service round, same
    # best-effort contract): the newest serve-report artifact — the
    # warm-vs-cold latency-per-query verdict measured at the
    # referenced SHA (tools/serve_report.py, stateright_tpu/serve.py).
    from stateright_tpu.artifacts import latest_serve_summary

    serve_ref = latest_serve_summary()
    if serve_ref is not None:
        _stderr(
            f"serve ref: {serve_ref['artifact']} "
            f"sessions={serve_ref['sessions']}"
        )
    # SLO cross-reference (the live-metrics round, same best-effort
    # contract): the newest service-level-objective gate evaluation —
    # whether the sustained-load p50/p99/refusal/queue-wait/cache-hit
    # objectives held at the referenced SHA (tools/slo_report.py,
    # stateright_tpu/metrics.py).
    from stateright_tpu.artifacts import latest_slo_summary

    slo_ref = latest_slo_summary()
    if slo_ref is not None:
        _stderr(
            f"slo ref: {slo_ref['artifact']} "
            f"ok={slo_ref['ok']}"
        )
    # SOUND cross-reference (the soundness-analyzer round, same
    # best-effort contract): the newest reduction soundness
    # certificate — whether every declared spec/mask the (sym) lanes
    # run was certified at the referenced SHA (analysis/soundness.py).
    from stateright_tpu.artifacts import latest_soundness_summary

    sound_ref = latest_soundness_summary()
    if sound_ref is not None:
        _stderr(
            f"sound ref: {sound_ref['artifact']} "
            f"clean={sound_ref['clean']}"
        )

    # Compile-cache ledger (round 14, checkers/tpu.py): per-lane
    # DELTAS of the process-cumulative compile-or-fetch counters, so
    # each lane's detail names what it paid (cold compiles vs disk
    # hits vs nothing) — the warm/cold attribution the BENCH_r06 chip
    # A/B reads; the provenance block carries the process totals.
    from stateright_tpu.checkers.tpu import compile_ledger_totals

    def _ledger_delta(before, after):
        return {
            k: (round(after[k] - before[k], 6)
                if isinstance(after[k], float)
                else after[k] - before[k])
            for k in ("compiles", "disk_hits", "cold_compiles",
                      "compile_sec", "stage_sec")
        }

    detail = {}
    headline_name, headline_sps = None, 0.0
    loads = tpu_workloads(quick=args.quick)
    for i, (name, spawn, hybrid_spawn, expected) in enumerate(loads):
        ledger_before = compile_ledger_totals()
        # ONE definition of "the traced lane" (the headline), shared
        # by the tracing block and the shard_balance attachment below
        lane_traced = tracer is not None and i == len(loads) - 1
        if lane_traced:
            # Trace the headline lane's timed runs (warm run last, so
            # trace_diff's default last-run view reads the warm one).
            # Artifacts land in a finally: a failed/interrupted run's
            # partial trace is the one needed for diagnosis.
            from stateright_tpu.telemetry import write_artifacts

            try:
                with tracer.activate():
                    checker, sec = time_checker(spawn, runs=args.runs)
            finally:
                if tracer.events:
                    jsonl, chrome = write_artifacts(tracer)
                    detail["trace_artifacts"] = [jsonl, chrome]
                    _stderr(f"trace: wrote {jsonl} + {chrome}")
        else:
            checker, sec = time_checker(spawn, runs=args.runs)
        unique = checker.unique_state_count()
        if unique != expected:
            _stderr(f"ERROR {name}: unique={unique} != expected {expected}")
            sys.exit(1)
        checker.assert_properties()
        sps = unique / sec
        detail[name] = {
            "unique": unique,
            "sec": round(sec, 4),
            "states_per_sec": round(sps),
            # name only — the full cross-reference block lives once
            # in provenance, not N+1 times per artifact line
            **({"lint": lint_ref["artifact"]}
               if lint_ref is not None else {}),
            **({"comms": comms_ref["artifact"]}
               if comms_ref is not None else {}),
            # sharded lanes: routed shuffle volume (the module
            # docstring's promise — recorded where a shuffle exists)
            **({"shuffle_volume": checker.metrics["shuffle_volume"]}
               if "shuffle_volume" in checker.metrics else {}),
        }
        # Codegen-optimizer provenance (round 23): compiled lanes
        # record WHAT the optimizer emitted (fused switch, elided
        # gathers, table widths) via the engine seam — the numbers
        # the parity ratio below is explained by.
        cg = getattr(checker, "codegen_opt", None)
        if cg is not None:
            detail[name]["codegen_opt"] = cg
        # Latency split (round 14): the lane's host dispatch vs
        # sync-floor wall — measured untraced too — plus the lane's
        # compile-cache ledger delta (cold AND warm runs: both ran
        # inside this lane's bracket). The accounting describes the
        # LAST (warm) run, so the share divides by THAT run's own
        # wall (checker.duration_sec()), not the best-of-N `sec`:
        # mixing runs could report sync_share > 1.
        lat = (checker.latency_accounting()
               if hasattr(checker, "latency_accounting") else None)
        if lat is not None:
            run_wall = checker.duration_sec()
            detail[name]["latency"] = {
                **lat,
                "run_wall_sec": round(run_wall, 4),
                "sync_share": (round(lat["fetch_sec"] / run_wall, 4)
                               if run_wall else None),
            }
        ledger = _ledger_delta(ledger_before, compile_ledger_totals())
        detail[name]["compile_cache"] = ledger
        if args.verbose or ledger["compiles"]:
            _stderr(
                f"     compile-cache: {ledger['compiles']} "
                f"compile-or-fetch ({ledger['disk_hits']} disk, "
                f"{ledger['cold_compiles']} cold, "
                f"{ledger['compile_sec']:.2f}s)"
                + (f"; sync floor {lat['fetch_sec']:.3f}s over "
                   f"{lat['chunks']} chunk(s)"
                   if lat is not None else "")
            )
        # Memory ledger (round 12, stateright_tpu/memplan.py): every
        # lane embeds its resident/staging plan totals — the engines
        # compute the plan untraced too (eval_shape, no device work)
        # — and the run peak where the watermark polled it (traced
        # lanes only: polling rides the tracer gate).
        mp = getattr(checker, "memory_plan", None)
        if mp is not None:
            detail[name]["memory_plan"] = {
                "resident_bytes": mp["resident_bytes"],
                "class_peak_bytes": mp["class_peak_bytes"],
                "total_bytes": mp["total_bytes"],
            }
            _stderr(
                f"     memory: resident "
                f"{mp['resident_bytes']:,} B + class peak "
                f"{mp['class_peak_bytes']:,} B = "
                f"{mp['total_bytes']:,} B planned"
                + (f"; device peak "
                   f"{checker.metrics['device_peak_bytes']:,} B"
                   if "device_peak_bytes" in checker.metrics else "")
            )
        if "device_peak_bytes" in getattr(checker, "metrics", {}):
            detail[name]["device_peak_bytes"] = (
                checker.metrics["device_peak_bytes"]
            )
        if lane_traced:
            # a traced MESH lane leaves its skew numbers in the lane
            # detail (single-chip traces have no shard_wave events
            # and skip this)
            from stateright_tpu.telemetry import shard_balance

            bal = shard_balance(tracer.events)
            if bal is not None:
                detail[name]["shard_balance"] = {
                    k: v for k, v in bal.items() if k != "per_wave"
                }
        if name in SYM_LANES:
            # the reduction record (ROADMAP 4(a)): raw space vs the
            # canonical quotient this lane explored, plus — on the
            # rm=5 lane — the live host-DFS-sym oracle A/B (count
            # parity asserted; the deeper parity matrix lives in
            # tests/test_device_symmetry.py)
            raw, rm = SYM_LANES[name]
            detail[name]["symmetry"] = {
                "raw_unique": raw,
                "canonical_unique": unique,
                "reduction_ratio": round(raw / unique, 2),
            }
            # certificate provenance (analysis/soundness.py): the
            # pending BENCH_r06 chip outing must carry proof that
            # the reductions it prices were certified — re-run the
            # analyzer uncached so the wall-time is real, not a
            # memo hit from the spawn gate.
            from stateright_tpu.analysis.soundness import (
                certify_encoding,
            )

            cert = certify_encoding(checker.encoded, use_cache=False)
            detail[name]["symmetry"]["soundness_certified"] = (
                cert.certified
            )
            detail[name]["symmetry"]["soundness_analyzer_sec"] = (
                round(cert.analyzer_sec, 4)
            )
            _stderr(
                f"     symmetry: {raw:,} raw -> {unique:,} canonical "
                f"(x{raw / unique:.1f} reduction); soundness "
                f"{'certified' if cert.certified else 'REFUSED'} "
                f"in {cert.analyzer_sec:.2f}s"
            )
            if rm == 5:
                o_unique, o_sec = bench_sym_host_oracle(rm)
                if o_unique != unique:
                    _stderr(
                        f"ERROR {name}: host DFS sym oracle "
                        f"{o_unique} != device {unique}"
                    )
                    sys.exit(1)
                detail[name]["symmetry"]["host_dfs_sym_sec"] = round(
                    o_sec, 4
                )
        _stderr(
            f"tpu  {name}: unique={unique} sec={sec:.3f} "
            f"states/sec={sps:,.0f}"
        )
        if hasattr(checker, "merge_impl"):
            # merge_impl + merge-stage share (round 10): which
            # visited-dedup implementation this lane ran (pallas |
            # xla fallback) and an isolated re-timing of the dedup
            # stage at the lane's converged class shapes
            # (wavewall.merge_stage_estimate — synthetic keys, same
            # program), so the pending BENCH_r06 chip run can A/B
            # the kernel against these rows with trace_diff. The
            # retired rebuild-sort path is re-timed alongside as the
            # denominator; share_est = dedup_ms x waves / wall.
            from stateright_tpu.wavewall import merge_stage_estimate

            est = merge_stage_estimate(checker)
            waves = checker.metrics.get("waves")
            detail[name]["merge_impl"] = est["impl"]
            detail[name]["merge_stage"] = {
                **est,
                "waves": waves,
                "share_est": (
                    round(est["dedup_ms"] * (waves or 0) / 1000.0
                          / sec, 4)
                    if sec else None
                ),
            }
            _stderr(
                f"     merge[{est['impl']}]: dedup "
                f"{est['dedup_ms']:.2f} ms/wave (sort "
                f"{est['cand_sort_ms']:.2f} + member "
                f"{est['member_ms']:.2f} + wcompact "
                f"{est['winner_compact_ms']:.2f} + append "
                f"{est['append_ms']:.2f}) vs retired rebuild "
                f"{est['rebuild_sort_ms']:.2f}; share~"
                f"{detail[name]['merge_stage']['share_est']}"
            )
        if hybrid_spawn is not None:
            # Sub-100k lanes finish in ~one sync round trip on the wave
            # engine, so their states/sec row reads as hundreds where
            # the product answer (the hybrid racer, usually the host
            # side for these) is single-digit ms — record the hybrid
            # wall time so the ladder tells the truth (VERDICT r5
            # item 7).
            hy, hy_sec, hy_winner = None, float("inf"), None
            for _ in range(args.runs):
                h = hybrid_spawn()
                t0 = time.monotonic()
                h.join()
                dt = time.monotonic() - t0
                if dt < hy_sec:
                    hy_sec, hy_winner = dt, h.winner
                hy = h
            if hy.unique_state_count() != expected:
                _stderr(
                    f"ERROR {name} hybrid: unique="
                    f"{hy.unique_state_count()} != {expected}"
                )
                sys.exit(1)
            detail[name]["hybrid_sec"] = round(hy_sec, 4)
            detail[name]["hybrid_winner"] = hy_winner
            _stderr(
                f"     hybrid: sec={hy_sec:.4f} (winner={hy_winner})"
            )
        if args.verbose:
            _stderr(f"     metrics: {checker.metrics}")
        headline_name, headline_sps = name, sps

    # Compiled-vs-hand parity (round 23, ROADMAP direction 5): every
    # compiled lane embeds the ratio of its pooled-min wall to its
    # hand-encoding denominator's — same pinned state space, so the
    # number is the compiled codegen's gap and nothing else. A
    # post-loop pass (not in-lane) so lane ORDER stays free: the
    # rm=7 denominator runs after its compiled lane to keep the hand
    # lane the traced headline.
    compiled_parity = {}
    for cname, hname in COMPILED_PARITY.items():
        if cname not in detail or hname not in detail:
            continue
        ratio = round(detail[cname]["sec"] / detail[hname]["sec"], 3)
        detail[cname]["parity"] = {
            "hand_lane": hname,
            "hand_sec": detail[hname]["sec"],
            "parity_ratio": ratio,
        }
        compiled_parity[cname] = detail[cname]["parity"]
        _stderr(
            f"parity {cname}: {ratio}x vs {hname} "
            f"({detail[cname]['sec']:.3f}s / "
            f"{detail[hname]['sec']:.3f}s)"
        )

    if not args.quick:
        detail["ttfc"] = bench_ttfc(runs=args.runs)

    # Provenance block (stateright_tpu/artifacts.py): the BENCH
    # artifact the driver captures from this line must name the
    # toolchain/device/SHA it was measured under — a states/sec with
    # no context is not comparable across rounds.
    from stateright_tpu.artifacts import provenance

    print(
        json.dumps(
            {
                "metric": f"unique states/sec ({headline_name}, 1 chip)",
                "value": round(headline_sps),
                "unit": "states/sec",
                "vs_baseline": round(headline_sps / host_sps, 2),
                "sync_floor_ms": sync_floor_ms,
                "provenance": provenance(
                    lane={
                        "headline": headline_name,
                        **({
                            "merge_impl":
                                detail[headline_name]["merge_impl"],
                            "merge_stage":
                                detail[headline_name]["merge_stage"],
                        } if headline_name in detail
                            and "merge_impl" in detail[headline_name]
                            else {}),
                        # the headline's memory ledger totals + run
                        # peak (round 12): the BENCH artifact carries
                        # the numbers a chip run's capacity decisions
                        # read, the way it carries merge_stage
                        **({
                            "memory_plan":
                                detail[headline_name]["memory_plan"],
                        } if headline_name in detail
                            and "memory_plan" in detail[headline_name]
                            else {}),
                        **({
                            "device_peak_bytes":
                                detail[headline_name][
                                    "device_peak_bytes"],
                        } if headline_name in detail
                            and "device_peak_bytes"
                            in detail[headline_name]
                            else {}),
                        # the headline's dispatch/sync-floor split +
                        # the PROCESS compile-cache totals (round 14):
                        # hit-tier counts and the total cold-compile
                        # wall, so warm/cold attribution reads off
                        # the artifact alone
                        **({"latency":
                                detail[headline_name]["latency"]}
                           if headline_name in detail
                           and "latency" in detail[headline_name]
                           else {}),
                        # compiled-vs-hand ratio table (round 23):
                        # the artifact alone answers "how far is the
                        # generic compiler from the hand encodings"
                        **({"compiled_parity": compiled_parity}
                           if compiled_parity else {}),
                        "compile_cache": compile_ledger_totals(),
                        **({"lint": lint_ref}
                           if lint_ref is not None else {}),
                        **({"comms": comms_ref}
                           if comms_ref is not None else {}),
                        **({"ckpt": ckpt_ref}
                           if ckpt_ref is not None else {}),
                        **({"serve": serve_ref}
                           if serve_ref is not None else {}),
                        **({"slo": slo_ref}
                           if slo_ref is not None else {}),
                        **({"soundness": sound_ref}
                           if sound_ref is not None else {}),
                    }
                ),
                "detail": detail,
            }
        )
    )


if __name__ == "__main__":
    main()
