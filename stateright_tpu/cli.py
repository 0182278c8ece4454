"""Per-example CLI entry points.

The reference ships each example as a binary with ``check`` /
``check-sym`` / ``explore`` / ``spawn`` subcommands (e.g.
examples/paxos.rs:352-465, examples/2pc.rs:172-251); here one module
dispatches the same surface for every bundled workload:

    python -m stateright_tpu 2pc check 3
    python -m stateright_tpu 2pc check-sym 5
    python -m stateright_tpu 2pc check-tpu 6          (wave engine)
    python -m stateright_tpu 2pc-actors check-tpu 5   (compiled encoding)
    python -m stateright_tpu paxos check 2 [network]
    python -m stateright_tpu paxos-compiled check-tpu (compiled encoding)
    python -m stateright_tpu paxos check-tpu 4 --trace    (run telemetry)
    python -m stateright_tpu paxos check-tpu 4 --shards 4 (four-device mesh)
    python -m stateright_tpu paxos explore 2 localhost:3000
    python -m stateright_tpu paxos spawn

``check`` engines mirror the reference's per-example choices (DFS
everywhere except interaction-style BFS cases); ``check-tpu`` — the
addition this framework exists for — runs the same workload on the
accelerator wave engine. Output goes through ``WriteReporter`` so the
report shape (``Done. states=… unique=… …``) matches report.rs:60-98.

``--trace`` (anywhere on the line) records run telemetry
(stateright_tpu/telemetry.py): per-wave events from the engine's
device wave log, host-phase spans, and the chunk dispatch/fetch wall
split, exported as auto-numbered ``TRACE_r*.jsonl`` +
``TRACE_r*.trace.json`` (Chrome trace) in the repo root.
``--trace=deep`` adds per-wave syncs for real per-wave wall times.
Diff two trace artifacts with ``tools/trace_diff.py``.
"""

from __future__ import annotations

import sys
import threading

from .actor.network import Network
from .report import WriteReporter


def _opt(args: list[str], index: int, default, parse=int):
    if len(args) > index:
        return parse(args[index])
    return default


def _network(args: list[str], index: int) -> Network:
    name = _opt(args, index, None, parse=str)
    if name is None:
        return Network.new_unordered_nonduplicating()
    return Network.from_name(name)


class _ThreadLocalRuntime:
    """Dict-like, PER-THREAD runtime-flag store: ``main()`` is
    re-entered in-process (tests, embedders) and — since the resident
    service (stateright_tpu/serve.py) runs sessions on concurrent
    HTTP threads — a process-global dict would let one invocation's
    reset silently wipe another thread's popped flags between its pop
    and its ``_apply_runtime``. Each thread sees its own copy,
    initialized to the defaults; supports exactly the dict surface
    the flag plumbing uses (``[]`` get/set, ``update(**kw)``)."""

    def __init__(self, **defaults):
        self._defaults = dict(defaults)
        self._tls = threading.local()

    def _cfg(self) -> dict:
        cfg = getattr(self._tls, "cfg", None)
        if cfg is None:
            cfg = dict(self._defaults)
            self._tls.cfg = cfg
        return cfg

    def __getitem__(self, key):
        return self._cfg()[key]

    def __setitem__(self, key, value):
        self._cfg()[key] = value

    def update(self, **kw) -> None:
        self._cfg().update(kw)


#: runtime flags popped by main() and applied at the one reporting
#: seam every check lane shares (_report): checkpoint/resume
#: (stateright_tpu/checkpoint.py) and the waves-per-sync override
#: (sets the chunk cadence — and therefore the checkpoint cadence —
#: without a per-lane knob). Thread-scoped — see _ThreadLocalRuntime.
_RUNTIME = _ThreadLocalRuntime(
    checkpoint_every=None, checkpoint_path=None, resume=False,
    resume_any_sha=False, waves_per_sync=None, tier_hot_rows=None,
    degrade_on_fault=False, watchdog=None, straggler_factor=None,
    symmetry=False, ample_set=False, unsound_ok=False,
)


def _maybe_symmetry(builder):
    """``--symmetry``: arm the builder's symmetry reduction BEFORE the
    spawn (the capability refusal fires in the engine constructor,
    checkers/common.symmetry_refusal, and the soundness-certificate
    gate right after it, analysis/soundness.gate_symmetry) — device
    engines canonicalize candidate fingerprints through the encoding's
    DeviceRewriteSpec (ops/canonical.py). ``--unsound-ok`` is armed
    here too: the waiver must reach the builder before the spawn-time
    gate fires."""
    if _RUNTIME["unsound_ok"]:
        builder = builder.unsound_ok()
    if _RUNTIME["symmetry"]:
        return builder.symmetry()
    return builder


def _apply_runtime(checker) -> None:
    """Apply the popped runtime flags to a freshly-spawned checker
    (before its first join). Device engines only: the flags configure
    the chunk loop, which host checkers don't have."""
    cfg = _RUNTIME
    if cfg["symmetry"]:
        # pre-spawn flag (_maybe_symmetry); by the time the checker
        # reaches this seam the builder must already carry it — a lane
        # that never called _maybe_symmetry would otherwise silently
        # run unreduced
        builder = getattr(checker, "builder", None)
        if builder is not None and builder._symmetry is None:
            raise SystemExit(
                "--symmetry: this lane does not arm the symmetry "
                "reduction (supported: 2pc check-tpu — the device "
                "canonicalization lane — and the host check-sym lanes)"
            )
    if cfg["ample_set"]:
        if not hasattr(checker, "ample_set"):
            raise SystemExit(
                "--ample-set needs a sort-merge check-tpu lane (the "
                "filter ANDs the encoding's ample mask into the "
                "sparse enabled bitmap, checkers/tpu_sortmerge.py)"
            )
        checker.ample_set = True
    if cfg["unsound_ok"] and hasattr(checker, "unsound_ok"):
        # the ample certificate gate fires at program-build time
        # (_resolve_ample_words), after this seam — the waiver must
        # land on the checker, not just the builder
        checker.unsound_ok = True
    if not (cfg["checkpoint_every"] or cfg["resume"]
            or cfg["waves_per_sync"] or cfg["tier_hot_rows"]
            or cfg["degrade_on_fault"] or cfg["watchdog"]
            or cfg["straggler_factor"]):
        return
    if not hasattr(checker, "_run_attempt"):
        raise SystemExit(
            "--checkpoint-every/--resume/--waves-per-sync/"
            "--tier-hot-rows/--degrade-on-fault/--watchdog/"
            "--straggler-factor need a device engine: use a "
            "check-tpu lane"
        )
    if cfg["degrade_on_fault"]:
        # the degrade path needs a snapshot to re-shard; it engages
        # only on multi-shard engines (single-chip has nothing to
        # drop), but configuring it there is harmless — the policy
        # gate is _can_degrade_shards
        checker.degrade_on_fault = True
    if cfg["watchdog"]:
        checker.watchdog_factor = float(cfg["watchdog"])
    if cfg["straggler_factor"]:
        checker.straggler_factor = float(cfg["straggler_factor"])
    if cfg["tier_hot_rows"]:
        if not hasattr(checker, "tier_hot_rows"):
            raise SystemExit(
                "--tier-hot-rows needs a sort-merge engine (the "
                "tiered visited set lives in the sorted-prefix "
                "family, stateright_tpu/tier.py)"
            )
        checker.tier_hot_rows = cfg["tier_hot_rows"]
        checker._tier_hot_ceiling = None
    if cfg["waves_per_sync"]:
        checker.waves_per_sync = cfg["waves_per_sync"]
    path = cfg["checkpoint_path"] or "stateright_tpu.ckpt"
    if cfg["checkpoint_every"]:
        checker.checkpoint_every = cfg["checkpoint_every"]
        checker.checkpoint_path = path
    if cfg["resume"]:
        manifest = checker.resume_from(
            path, allow_sha_mismatch=cfg["resume_any_sha"]
        )
        print(
            f"resuming from {path}: wave {manifest['wave']}, depth "
            f"{manifest['depth']}, {manifest['unique']:,} unique "
            f"states (snapshot S={manifest['n_shards']})"
        )


#: thread-scoped session hook (the resident service,
#: stateright_tpu/serve.py): the service installs a callback here
#: around each session's handler call, and ``_report`` runs it on the
#: freshly-spawned checker BEFORE the first join — admission, warm
#: start, the FIFO device gate, retention arming. Thread-local so
#: concurrent service sessions (and a plain in-process ``main()``
#: embedder on another thread) never see each other's hook. This is
#: also why a second same-config check in one process provably hits
#: the ``in_process`` compile-ledger tier: every lane funnels its
#: checker through THIS one seam, whose engines share the process
#: program cache (tests/test_serve.py pins the tier).
_SESSION_HOOK = threading.local()


def _report(checker, out=None) -> None:
    """The one reporting path every check lane shares: the reference-
    format ``Reporter`` (report.rs:60-98) — no lane formats privately
    (tests/test_report.py pins the format through this seam). Also
    the seam the popped runtime flags (checkpoint/resume) land on —
    and the seam the resident service intercepts (``_SESSION_HOOK``):
    every check lane passes its checker through here before the first
    join."""
    _apply_runtime(checker)
    hook = getattr(_SESSION_HOOK, "hook", None)
    if hook is not None:
        hook(checker)
    checker.report(WriteReporter(out if out is not None else sys.stdout))


def _explore(builder, args: list[str], index: int) -> None:
    address = _opt(args, index, "localhost:3000", parse=str)
    builder.serve(address)


# -- workloads -----------------------------------------------------------


def _2pc(sub: str, args: list[str]) -> None:
    from .models.two_phase_commit import TwoPhaseSys

    rm_count = _opt(args, 0, 2)
    sys_model = TwoPhaseSys(rm_count=rm_count)
    if sub == "check":
        print(f"Checking two phase commit with {rm_count} resource managers.")
        _report(sys_model.checker().spawn_dfs())
    elif sub == "check-sym":
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            "using symmetry reduction."
        )
        _report(sys_model.checker().symmetry().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Checking two phase commit with {rm_count} resource managers "
            "on the TPU wave engine."
        )
        # The 2pc space grows ~2.53 bits/RM (288 @ 3 → 296,448 @ 7).
        # The sort-merge visited array has no load-factor pressure, so
        # a snug capacity works; this is the engine bench.py records
        # (the hash-table engine measured ~10x slower on chip, PERF.md).
        import math

        capacity = 1 << max(10, math.ceil(2.6 * rm_count + 1.5))
        _report(
            _maybe_symmetry(sys_model.checker()).spawn_tpu_sortmerge(
                capacity=capacity,
                frontier_capacity=max(256, capacity // 4),
                cand_capacity="auto",
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        print(
            f"Exploring state space for two phase commit with {rm_count} "
            f"resource managers on {address}."
        )
        sys_model.checker().serve(address)
    else:
        _usage("2pc")


def _paxos(sub: str, args: list[str]) -> None:
    from .models.paxos import PaxosModelCfg, paxos_model

    shards, args = _pop_shards(args)
    client_count = _opt(args, 0, 2)
    cfg = PaxosModelCfg(client_count=client_count, server_count=3)
    if shards is not None and sub != "check-tpu":
        raise SystemExit("--shards needs paxos check-tpu")
    if sub == "check":
        network = _network(args, 1)
        print(f"Model checking Single Decree Paxos with {client_count} clients.")
        _report(paxos_model(cfg, network).checker().spawn_dfs())
    elif sub == "check-tpu" and shards is not None:
        # the sharded sort-merge engine over a mesh of the first
        # `shards` devices, with the per-shard budgets a run has proven
        from .models.paxos_tpu import SHARDED_SIZES

        if client_count not in SHARDED_SIZES:
            raise SystemExit(
                f"paxos check-tpu --shards has per-shard budgets for "
                f"{sorted(SHARDED_SIZES)} clients (got {client_count}): "
                "SHARDED_SIZES in models/paxos_tpu.py"
            )
        try:
            checker = paxos_model(cfg).checker().spawn_tpu_sharded_sortmerge(
                n_shards=shards,
                track_paths=client_count <= 2,
                **SHARDED_SIZES[client_count],
            )
        except ValueError as exc:
            raise SystemExit(f"paxos check-tpu --shards {shards}: {exc}")
        print(
            f"Model checking Single Decree Paxos with {client_count} "
            f"clients on the TPU wave engine over {shards} shards."
        )
        _report(checker)
    elif sub == "check-tpu":
        print(
            f"Model checking Single Decree Paxos with {client_count} "
            "clients on the TPU wave engine."
        )
        # STRUCTURAL sizes from the one shared table; per-wave budgets
        # auto-size from measured peaks (cand_capacity="auto") — the
        # round-5 TUNED_ENGINE_CAPS budget table is retired (VERDICT
        # r5 item 6).
        from .models.paxos_tpu import STRUCTURAL_SIZES as sizes

        if client_count not in sizes:
            raise SystemExit(
                f"paxos check-tpu supports 1-5 clients (got "
                f"{client_count}): the TPU encoding's client-lane "
                "packing caps at 5 (models/paxos_tpu.py)"
            )
        _report(
            paxos_model(cfg)
            .checker()
            .spawn_tpu_sortmerge(
                track_paths=client_count <= 2,
                cand_capacity="auto",
                **sizes[client_count],
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        network = _network(args, 2)
        print(
            f"Exploring state space for Single Decree Paxos with "
            f"{client_count} clients on {address}."
        )
        paxos_model(cfg, network).checker().serve(address)
    elif sub == "spawn":
        from .actor.spawn import spawn_paxos_cluster

        spawn_paxos_cluster()
    else:
        _usage("paxos")


def _2pc_actors(sub: str, args: list[str]) -> None:
    """The COMPILED 2pc family (round 23): the count-comparable
    system actor model (models/two_phase_commit_actors.py
    two_phase_sys_actor_model — host-parity pinned at the TwoPhaseSys
    counts, 8,832 @ rm=5) through the generic actor→encoding
    compiler's optimized codegen. Routing through ``_report`` gives
    the compiled path ``--trace`` / ``--checkpoint-every`` / resume
    for free, same as every hand lane."""
    from .models.two_phase_commit_actors import (
        two_phase_sys_actor_model,
        two_phase_sys_compiled_encoded,
    )

    rm_count = _opt(args, 0, 2)
    model = two_phase_sys_actor_model(rm_count)
    if sub == "check":
        print(
            f"Checking two phase commit (compiled actor model) with "
            f"{rm_count} resource managers."
        )
        _report(model.checker().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Checking two phase commit (compiled actor model) with "
            f"{rm_count} resource managers on the TPU wave engine."
        )
        # Same pinned counts as the hand `2pc` lanes (~2.53 bits/RM),
        # same snug-capacity sizing; the encoding comes from the
        # compiler, not models/two_phase_commit_tpu.py.
        import math

        capacity = 1 << max(10, math.ceil(2.6 * rm_count + 1.5))
        _report(
            model.checker().spawn_tpu_sortmerge(
                encoded=two_phase_sys_compiled_encoded(rm_count),
                capacity=capacity,
                frontier_capacity=max(256, capacity // 4),
                cand_capacity="auto",
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        print(
            f"Exploring state space for two phase commit (compiled "
            f"actor model) with {rm_count} resource managers on "
            f"{address}."
        )
        model.checker().serve(address)
    else:
        _usage("2pc-actors")


def _paxos_compiled(sub: str, args: list[str]) -> None:
    """Compiled paxos (round 23): the actor paxos model through the
    compiler in reachable mode — the compile pays ONE host
    exploration of the space to harvest bounds, so this lane caps at
    2 clients (the bench's production shape, 16,668 states)."""
    from .models.paxos import (
        PaxosModelCfg,
        paxos_compiled_encoded,
        paxos_model,
    )

    client_count = _opt(args, 0, 2)
    cfg = PaxosModelCfg(client_count=client_count, server_count=3)
    if sub == "check":
        print(
            f"Model checking Single Decree Paxos (compiled) with "
            f"{client_count} clients."
        )
        _report(paxos_model(cfg).checker().spawn_dfs())
    elif sub == "check-tpu":
        if client_count > 2:
            raise SystemExit(
                f"paxos-compiled check-tpu supports 1-2 clients (got "
                f"{client_count}): reachable-mode compilation "
                "explores the space once on the host to harvest "
                "bounds (models/paxos.py paxos_compiled_encoded), "
                "which is impractical beyond the 16,668-state "
                "2-client config"
            )
        print(
            f"Model checking Single Decree Paxos (compiled) with "
            f"{client_count} clients on the TPU wave engine."
        )
        _report(
            paxos_model(cfg)
            .checker()
            .spawn_tpu_sortmerge(
                encoded=paxos_compiled_encoded(cfg),
                track_paths=client_count <= 2,
                capacity=1 << 15,
                frontier_capacity=1 << 13,
                cand_capacity="auto",
            )
        )
    else:
        _usage("paxos-compiled")


def _increment(sub: str, args: list[str]) -> None:
    from .models.increment import Increment

    thread_count = _opt(args, 0, 2)
    model = Increment(thread_count=thread_count)
    if sub == "check":
        print(f"Model checking increment with {thread_count} threads.")
        _report(model.checker().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Model checking increment with {thread_count} threads "
            "on the TPU wave engine."
        )
        _report(
            model.checker().spawn_tpu_sortmerge(
                capacity=1 << 12, frontier_capacity=256, cand_capacity=1024
            )
        )
    elif sub == "check-sym":
        print(
            f"Model checking increment with {thread_count} threads "
            "using symmetry reduction."
        )
        _report(model.checker().symmetry().spawn_dfs())
    elif sub == "explore":
        _explore(model.checker(), args, 1)
    else:
        _usage("increment")


def _increment_lock(sub: str, args: list[str]) -> None:
    from .models.increment import IncrementLock

    thread_count = _opt(args, 0, 3)
    model = IncrementLock(thread_count=thread_count)
    if sub == "check":
        print(f"Model checking increment_lock with {thread_count} threads.")
        _report(model.checker().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Model checking increment_lock with {thread_count} threads "
            "on the TPU wave engine."
        )
        _report(
            model.checker().spawn_tpu_sortmerge(
                capacity=1 << 12, frontier_capacity=256, cand_capacity=1024
            )
        )
    elif sub == "check-sym":
        print(
            f"Model checking increment_lock with {thread_count} threads "
            "using symmetry reduction."
        )
        _report(model.checker().symmetry().spawn_dfs())
    elif sub == "explore":
        _explore(model.checker(), args, 1)
    else:
        _usage("increment-lock")


def _single_copy(sub: str, args: list[str]) -> None:
    from .models.single_copy_register import (
        SingleCopyRegisterCfg,
        single_copy_register_model,
    )

    client_count = _opt(args, 0, 2)
    cfg = SingleCopyRegisterCfg(client_count=client_count)
    if sub == "check":
        network = _network(args, 1)
        print(
            f"Model checking a single-copy register with {client_count} "
            "clients."
        )
        _report(single_copy_register_model(cfg, network).checker().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Model checking a single-copy register with {client_count} "
            "clients on the TPU wave engine."
        )
        _report(
            single_copy_register_model(cfg)
            .checker()
            .spawn_tpu_sortmerge(
                capacity=256, frontier_capacity=64, cand_capacity=256
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        network = _network(args, 2)
        print(
            f"Exploring state space for a single-copy register with "
            f"{client_count} clients on {address}."
        )
        single_copy_register_model(cfg, network).checker().serve(address)
    elif sub == "spawn":
        from .actor.spawn import spawn_single_copy_cluster

        spawn_single_copy_cluster()
    else:
        _usage("single-copy-register")


def _linearizable(sub: str, args: list[str]) -> None:
    from .models.linearizable_register import AbdModelCfg, abd_model

    client_count = _opt(args, 0, 2)
    cfg = AbdModelCfg(client_count=client_count)
    if sub == "check":
        network = _network(args, 1)
        print(
            f"Model checking a linearizable register with {client_count} "
            "clients."
        )
        _report(abd_model(cfg, network).checker().spawn_dfs())
    elif sub == "check-tpu":
        network = _network(args, 1)
        print(
            f"Model checking a linearizable register with {client_count} "
            "clients on the TPU wave engine (compiled actor encoding)."
        )
        _report(
            abd_model(cfg, network)
            .checker()
            .spawn_tpu_sortmerge(
                capacity=1 << (9 + 2 * client_count),
                frontier_capacity=1 << (7 + client_count),
                cand_capacity=1 << (9 + client_count),
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        network = _network(args, 2)
        print(
            f"Exploring state space for a linearizable register with "
            f"{client_count} clients on {address}."
        )
        abd_model(cfg, network).checker().serve(address)
    elif sub == "spawn":
        from .actor.spawn import spawn_abd_cluster

        spawn_abd_cluster()
    else:
        _usage("linearizable-register")


def _timers(sub: str, args: list[str]) -> None:
    from .models.timers import PingerModelCfg, pinger_model

    server_count = _opt(args, 0, 3)
    cfg = PingerModelCfg(server_count=server_count)
    if sub == "check":
        network = _network(args, 1)
        print("Model checking Pingers")
        # The pinger space is unbounded (timers.rs runs it the same
        # way); interrupt or pass a depth bound via `explore`.
        _report(pinger_model(cfg, network).checker().spawn_dfs())
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        network = _network(args, 2)
        print(f"Exploring state space for Pingers on {address}.")
        pinger_model(cfg, network).checker().serve(address)
    else:
        _usage("timers")


def _interaction(sub: str, args: list[str]) -> None:
    from .models.interaction import interaction_model

    if sub == "check":
        # interaction.rs:44 bounds the loosely-bounded space at depth
        # 30; an optional DEPTH argument trades coverage for time (the
        # reference explores this space with a Rust thread pool).
        depth = _opt(args, 0, 30)
        checker = (
            interaction_model().checker().target_max_depth(depth).spawn_bfs()
        )
        _report(checker)
        checker.assert_properties()
    elif sub == "explore":
        address = _opt(args, 0, "localhost:3000", parse=str)
        print(f"Exploring the interaction model on {address}.")
        interaction_model().checker().target_max_depth(30).serve(address)
    else:
        _usage("interaction")


def _panic(sub: str, args: list[str]) -> None:
    """Counterpart of examples/panic.rs: a model whose action
    enumeration raises mid-search. The reference uses it to verify a
    worker-thread panic propagates out of ``join()`` instead of
    hanging the checker; here the search runs in-process and the
    checker must surface the error to the caller unchanged."""
    from .model import Model, Property

    class Adder(Model):
        def init_states(self):
            return [0]

        def actions(self, state):
            if state >= 5000:
                raise RuntimeError(
                    "panic! (the examples/panic.rs trigger: action "
                    f"enumeration raised at state {state})"
                )
            return [1, 2, 3, 4, 5]

        def next_state(self, state, action):
            return state + action

        def properties(self):
            return [Property.always("true", lambda m, s: True)]

    if sub == "check":
        print(
            "Checking the panicking adder (examples/panic.rs): the "
            "search must fail loudly, not hang."
        )
        try:
            Adder().checker().spawn_dfs().join()
        except RuntimeError as e:
            if "panic!" not in str(e):
                raise  # an unrelated checker failure, not the trigger
            print(f"Checker propagated the panic: {e}")
            return
        raise SystemExit("ERROR: the panic did not propagate")
    else:
        _usage("panic")


def _register(sub: str, args: list[str]) -> None:
    from .models.nclient_register import NClientRegSys

    n_clients = _opt(args, 0, 3)
    sys_model = NClientRegSys(n_clients=n_clients)
    if sub == "check":
        print(
            f"Checking the write-once register with {n_clients} clients."
        )
        _report(sys_model.checker().spawn_dfs())
    elif sub == "check-sym":
        print(
            f"Checking the write-once register with {n_clients} clients "
            "using symmetry reduction."
        )
        _report(sys_model.checker().symmetry().spawn_dfs())
    elif sub == "check-tpu":
        print(
            f"Checking the write-once register with {n_clients} clients "
            "on the TPU wave engine."
        )
        # raw space is 1 + 2n*3^(n-1) states (models/nclient_register)
        # — tiny; snug pow-2 capacity over the closed form
        raw = 1 + 2 * n_clients * 3 ** max(0, n_clients - 1)
        capacity = max(1 << 10, 1 << (raw - 1).bit_length())
        _report(
            _maybe_symmetry(sys_model.checker()).spawn_tpu_sortmerge(
                capacity=capacity,
                frontier_capacity=max(256, capacity // 4),
                cand_capacity="auto",
            )
        )
    elif sub == "explore":
        address = _opt(args, 1, "localhost:3000", parse=str)
        print(
            f"Exploring state space for the write-once register with "
            f"{n_clients} clients on {address}."
        )
        sys_model.checker().serve(address)
    else:
        _usage("register")


_MODELS = {
    "2pc": (_2pc, ["check", "check-sym", "check-tpu", "explore"]),
    "2pc-actors": (_2pc_actors, ["check", "check-tpu", "explore"]),
    "register": (_register, ["check", "check-sym", "check-tpu", "explore"]),
    "paxos": (_paxos, ["check", "check-tpu", "explore", "spawn"]),
    "paxos-compiled": (_paxos_compiled, ["check", "check-tpu"]),
    "increment": (_increment, ["check", "check-sym", "check-tpu", "explore"]),
    "increment-lock": (_increment_lock, ["check", "check-sym", "check-tpu", "explore"]),
    "single-copy-register": (_single_copy, ["check", "check-tpu", "explore", "spawn"]),
    "linearizable-register": (_linearizable, ["check", "check-tpu", "explore", "spawn"]),
    "timers": (_timers, ["check", "explore"]),
    "interaction": (_interaction, ["check", "explore"]),
    "panic": (_panic, ["check"]),
}


def _usage(model: str | None = None) -> None:
    print("USAGE:")
    if model is None:
        for name, (_, subs) in _MODELS.items():
            print(f"  python -m stateright_tpu {name} {{{'|'.join(subs)}}} ...")
    else:
        _, subs = _MODELS[model]
        for sub in subs:
            extra = {
                "check": "[COUNT] [NETWORK]",
                "check-sym": "[COUNT]",
                "check-tpu": "[COUNT]",
                "explore": "[COUNT] [ADDRESS] [NETWORK]",
                "spawn": "",
            }[sub]
            if model == "panic":
                extra = ""  # fixed harness: no count, no network
            print(f"  python -m stateright_tpu {model} {sub} {extra}")
    if model is None:
        print(
            "  python -m stateright_tpu serve [HOST:PORT] "
            "[--explore=MODEL[,COUNT]] [--program-budget-bytes=N] "
            "[--device-budget-bytes=N] [--no-warm-start] "
            "[--batch-sessions[=N]] [--batch-window-sec=S] "
            "[--snapshot-budget-bytes=N] [--metrics-interval=N "
            "[--metrics-path=FILE]]"
        )
    print(f"NETWORK: {' | '.join(Network.names())}")
    print(
        "FLAGS: --trace[=deep] on any check lane writes TRACE_r*.jsonl"
        " + TRACE_r*.trace.json run telemetry (tools/trace_diff.py "
        "compares two)"
    )
    print(
        "       --shards S on paxos check-tpu shards the search over the "
        "first S devices (the sharded sort-merge engine: all_to_all "
        "frontier shuffles and an owner-sharded visited set; per-shard "
        "budgets in models/paxos_tpu.py SHARDED_SIZES)"
    )
    print(
        "       --checkpoint-every=N|auto [--checkpoint-path=P] on "
        "check-tpu lanes snapshots the chunk carry every N chunks "
        "(atomic; supervised fault retry; 'auto' picks the cadence "
        "from the measured snapshot-vs-chunk walls, <=5% overhead); "
        "--resume restores from the snapshot — elastically, onto a "
        "different shard count on the sort-merge engines "
        "(--resume-any-sha skips the git-SHA staleness refusal; "
        "--waves-per-sync=N sets the chunk cadence)"
    )
    print(
        "       --tier-hot-rows=N|auto on sort-merge check-tpu "
        "lanes caps the device-resident visited HOT tier at N rows "
        "and spills the rest to host-DRAM cold runs "
        "(stateright_tpu/tier.py; 'auto' = the memplan capacity "
        "projection decides the split) — reachability bounded by "
        "host memory, not HBM"
    )
    print(
        "       --degrade-on-fault on check-tpu lanes lets the "
        "supervisor DROP a persistently-faulting shard and re-shard "
        "the last snapshot onto the survivors (degrade-and-continue,"
        " checkpoint.FailurePolicy); --watchdog[=factor] arms the "
        "hung-dispatch watchdog (deadline = clamp(factor x rolling "
        "max chunk wall), default 8 — a breach emits "
        "watchdog_timeout + recovers from the snapshot or refuses "
        "loudly); --straggler-factor=F emits shard_health events "
        "when a shard's wave work exceeds F x the shard median "
        "(traced mesh runs; sustained stragglers feed the failure "
        "classifier)"
    )
    print(
        "       --symmetry on 2pc/register check-tpu runs the device "
        "symmetry reduction (canonical-form fingerprints before "
        "dedup, ops/canonical.py; 2pc rm=5: 8,832 -> 314 states); "
        "--ample-set on sort-merge check-tpu lanes ANDs the "
        "encoding's partial-order ample mask into the sparse "
        "enabled-bits pass (fewer interleavings, same verdicts). "
        "Both consult the reduction soundness certificate "
        "(analysis/soundness.py): uncertifiable specs refuse at "
        "spawn with the failed obligation; --unsound-ok waives the "
        "gate (no soundness guarantee)"
    )
    print(
        "       `analyze soundness [MODEL] [COUNT] [--no-artifact]` "
        "runs the reduction soundness analyzer over the registered "
        "targets (2pc, register) and writes the SOUND_r*.json "
        "certificate the engine gates consult"
    )
    print(
        "       `serve` runs the resident multi-tenant checking "
        "service (stateright_tpu/serve.py): one warm process, a FIFO "
        "device queue, a byte-budget LRU of compiled programs, "
        "fingerprint-stable warm-start re-checks, and an optional "
        "Explorer mount; --connect=HOST:PORT on any check lane ships "
        "it to a running service (counts bit-identical, compile "
        "amortized); --batch-sessions[=N] fuses up to N concurrent "
        "compatible sessions into ONE wave-program dispatch "
        "(stateright_tpu/batch.py — per-session counts/verdicts/paths "
        "stay bit-exact, the dispatch+sync floor is amortized 1/N; "
        "--batch-window-sec=S sets the admission batching window); "
        "--snapshot-budget-bytes=N caps the warm-start snapshot spool "
        "with byte-budget LRU eviction (snapshot_evict events)"
    )
    print(
        "       --metrics-interval=N [--metrics-path=FILE] on any "
        "check lane (and on `serve`) appends one cumulative "
        "metrics_rollup JSONL line every N seconds — the live "
        "metrics plane (stateright_tpu/metrics.py: counters/gauges/"
        "log-bucket histograms, bridge-derived from telemetry) for "
        "headless runs; the serve daemon also answers GET /.metrics "
        "in Prometheus text format, and tools/slo_report.py "
        "exit-code-gates a rollup or live endpoint against a "
        "declarative SLO spec (p50/p99 time-to-verdict, refusal "
        "rate, queue wait, cache-hit rate)"
    )


def _pop_connect_flag(argv: list[str]) -> tuple[str | None, list[str]]:
    """Strip ``--connect=HOST:PORT`` from anywhere in argv: client
    mode — the remaining lane argv ships to a resident checking
    service (stateright_tpu/serve.py) instead of running cold in this
    process. Counts are bit-identical (the service runs the same
    handler, warm); latency skips the per-process compile."""
    addr = None
    rest = []
    for a in argv:
        if a.startswith("--connect="):
            addr = a.split("=", 1)[1]
        elif a == "--connect":
            raise SystemExit(
                "--connect needs an address: --connect=HOST:PORT"
            )
        else:
            rest.append(a)
    return addr, rest


def _pop_shards(args: list[str]) -> tuple[int | None, list[str]]:
    """Strip ``--shards S`` / ``--shards=S`` from a lane's arguments:
    the number of devices a check-tpu lane shards its search over."""
    shards = None
    rest = []
    it = iter(args)
    for a in it:
        if a == "--shards" or a.startswith("--shards="):
            val = a.split("=", 1)[1] if "=" in a else next(it, "")
            if not val.isdigit() or int(val) < 1:
                raise SystemExit(
                    f"--shards {val}: needs a whole number of devices >= 1"
                )
            shards = int(val)
        else:
            rest.append(a)
    return shards, rest


def _pop_trace_flag(argv: list[str]) -> tuple[str | None, list[str]]:
    """Strip ``--trace`` / ``--trace=deep`` from anywhere in argv."""
    level = None
    rest = []
    for a in argv:
        if a == "--trace":
            level = "default"
        elif a.startswith("--trace="):
            level = a.split("=", 1)[1]
        else:
            rest.append(a)
    return level, rest


def _pop_metrics_flags(
    argv: list[str],
) -> tuple[float | None, str | None, list[str]]:
    """Strip ``--metrics-interval=N`` / ``--metrics-path=FILE`` from
    anywhere in argv: the headless metrics export
    (stateright_tpu/metrics.py) — a tracer runs for the lane (even
    without ``--trace``) and every N seconds its events are folded
    through the tracer→metrics bridge into one cumulative
    ``metrics_rollup`` JSONL line (default path
    ``stateright_tpu.metrics.jsonl``), plus a final line at exit.
    TRACE artifacts are still only written when ``--trace`` asked
    for them."""
    interval = None
    path = None
    rest = []
    for a in argv:
        if a.startswith("--metrics-interval="):
            val = a.split("=", 1)[1]
            interval = float(val)
            if interval <= 0:
                raise SystemExit(
                    f"--metrics-interval={val}: must be > 0 seconds"
                )
        elif a == "--metrics-interval":
            raise SystemExit(
                "--metrics-interval needs a cadence: "
                "--metrics-interval=N (seconds)"
            )
        elif a.startswith("--metrics-path="):
            path = a.split("=", 1)[1]
        else:
            rest.append(a)
    if interval is None and path is not None:
        raise SystemExit(
            "--metrics-path requires --metrics-interval=N"
        )
    return interval, path, rest


def _pop_runtime_flags(argv: list[str]) -> list[str]:
    """Strip the checkpoint/resume flags from anywhere in argv into
    :data:`_RUNTIME` (the durability layer,
    stateright_tpu/checkpoint.py): ``--checkpoint-every=N`` (snapshot
    the chunk carry every N chunks + supervised fault retry),
    ``--checkpoint-path=PATH`` (default ``stateright_tpu.ckpt``),
    ``--resume`` (restore from the checkpoint path — elastic: a
    sort-merge snapshot resumes onto a different shard count),
    ``--resume-any-sha`` (skip the git-SHA staleness refusal), and
    ``--waves-per-sync=N`` (chunk cadence override — the knob that
    sets how much progress one snapshot covers)."""
    rest = []
    for a in argv:
        if a.startswith("--checkpoint-every="):
            val = a.split("=", 1)[1]
            # "auto": cadence from the measured snapshot write wall
            # vs chunk wall (checkpoint.auto_cadence, <=5% overhead)
            _RUNTIME["checkpoint_every"] = (
                "auto" if val == "auto" else int(val)
            )
        elif a.startswith("--tier-hot-rows="):
            val = a.split("=", 1)[1]
            # tiered visited set (stateright_tpu/tier.py): hot-tier
            # ceiling in rows, or "auto" for the memplan-projection
            # split. Validated HERE: a 0 would be silently dropped
            # by the apply-time truthiness gate instead of refused.
            if val != "auto" and int(val) < 1:
                raise SystemExit(
                    f"--tier-hot-rows={val}: the hot ceiling must "
                    "be >= 1 row (or 'auto')"
                )
            _RUNTIME["tier_hot_rows"] = (
                "auto" if val == "auto" else int(val)
            )
        elif a.startswith("--checkpoint-path="):
            _RUNTIME["checkpoint_path"] = a.split("=", 1)[1]
        elif a == "--resume":
            _RUNTIME["resume"] = True
        elif a == "--resume-any-sha":
            _RUNTIME["resume"] = True
            _RUNTIME["resume_any_sha"] = True
        elif a.startswith("--waves-per-sync="):
            _RUNTIME["waves_per_sync"] = int(a.split("=", 1)[1])
        elif a == "--degrade-on-fault":
            # degrade-and-continue (checkpoint.FailurePolicy): a
            # fault that persists on one shard drops that shard and
            # re-shards the last snapshot onto the survivors
            _RUNTIME["degrade_on_fault"] = True
        elif a == "--watchdog" or a.startswith("--watchdog="):
            # hung-dispatch watchdog (checkers/tpu.py): deadline =
            # clamp(factor x rolling max chunk wall), default factor 8
            val = a.split("=", 1)[1] if "=" in a else "8"
            f = float(val)
            if f <= 0:
                raise SystemExit(
                    f"--watchdog={val}: the factor must be > 0"
                )
            _RUNTIME["watchdog"] = f
        elif a == "--symmetry":
            # device symmetry reduction (ops/canonical.py): canonical
            # fingerprints before dedup — armed on the builder pre-
            # spawn (_maybe_symmetry); engines that can't honor it
            # refuse loudly at spawn
            _RUNTIME["symmetry"] = True
        elif a == "--ample-set":
            # partial-order-reduction enabled-bits filter: AND the
            # encoding's ample mask into the sparse bitmap pass
            _RUNTIME["ample_set"] = True
        elif a == "--unsound-ok":
            # waive the reduction soundness-certificate gates
            # (analysis/soundness.py): an UNCERTIFIED spec or mask
            # runs anyway — the counts carry no soundness guarantee
            _RUNTIME["unsound_ok"] = True
        elif a.startswith("--straggler-factor="):
            val = a.split("=", 1)[1]
            f = float(val)
            if f <= 1:
                raise SystemExit(
                    f"--straggler-factor={val}: must be > 1 (a shard "
                    "flags when its wave work exceeds factor x the "
                    "shard median)"
                )
            _RUNTIME["straggler_factor"] = f
        else:
            rest.append(a)
    return rest


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    # reset per invocation: main() is re-entered in-process (tests,
    # embedders) and a previous call's checkpoint/resume flags must
    # not leak into a lane that never asked for them
    _RUNTIME.update(
        checkpoint_every=None, checkpoint_path=None, resume=False,
        resume_any_sha=False, waves_per_sync=None,
        tier_hot_rows=None, degrade_on_fault=False, watchdog=None,
        straggler_factor=None, symmetry=False, ample_set=False,
        unsound_ok=False,
    )
    # resident-service lanes (ROADMAP direction 4, serve.py): the
    # daemon, and the client mode that ships a lane to one
    connect, argv = _pop_connect_flag(argv)
    if argv and argv[0] == "serve":
        from . import serve

        raise SystemExit(serve.daemon_main(argv[1:]))
    if connect is not None:
        from . import serve

        raise SystemExit(serve.client_main(connect, argv))
    # the static-analysis lanes: `analyze soundness [MODEL]` runs the
    # reduction soundness analyzer and writes SOUND_r*.json
    if argv and argv[0] == "analyze":
        from .analysis.soundness import analyze_main

        raise SystemExit(analyze_main(argv[1:]))
    trace_level, argv = _pop_trace_flag(argv)
    metrics_interval, metrics_path, argv = _pop_metrics_flags(argv)
    argv = _pop_runtime_flags(argv)
    if not argv or argv[0] not in _MODELS:
        _usage()
        return
    model, rest = argv[0], argv[1:]
    handler, subs = _MODELS[model]
    if not rest or rest[0] not in subs:
        _usage(model)
        return
    if trace_level is None and metrics_interval is None:
        handler(rest[0], rest[1:])
        return
    if trace_level not in (None, "default", "deep"):
        raise SystemExit(
            f"--trace={trace_level}: unknown level "
            "(use --trace or --trace=deep)"
        )
    from .telemetry import RunTracer, write_artifacts

    # --metrics-interval implies a tracer (the rollup is bridge-
    # derived from telemetry events) but NOT trace artifacts: those
    # stay --trace's call
    tracer = RunTracer(level=trace_level or "default")
    rollup = None
    if metrics_interval is not None:
        from .metrics import Rollup, bridge_events

        def _registry_now(tracer=tracer):
            with tracer._lock:
                events = list(tracer.events)
            return bridge_events(events)

        rollup = Rollup(
            metrics_path or "stateright_tpu.metrics.jsonl",
            metrics_interval, source=_registry_now,
        ).start()
    try:
        with tracer.activate():
            handler(rest[0], rest[1:])
    finally:
        if rollup is not None:
            # the final rollup: even a run shorter than one interval
            # leaves the cumulative totals line
            rollup.stop()
        # A failed/interrupted run's partial trace is the one you
        # need for diagnosis — write whatever was collected.
        if trace_level is not None and tracer.events:
            jsonl, chrome = write_artifacts(tracer)
            print(f"trace: wrote {jsonl} + {chrome}", file=sys.stderr)
