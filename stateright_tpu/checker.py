"""Checker configuration and the common checker interface.

``CheckerBuilder`` mirrors the reference's fluent builder
(stateright src/checker.rs:64-267): configure symmetry, bounded targets,
worker count and visitors, then spawn a specific engine. ``Checker``
mirrors the result interface (src/checker.rs:273-557): counts,
discoveries as replayable :class:`~stateright_tpu.path.Path` objects,
reporting, and assertion helpers for tests.

Departures from the reference, by design:

* Host engines run the search *synchronously* on first demand (``join``
  or any accessor) instead of spawning OS threads — Python threads
  cannot parallelize this CPU-bound loop. Parallelism lives in the TPU
  engine (``spawn_tpu``), where a whole frontier wave is one device
  program and scale-out is a sharded mesh, replacing the reference's
  thread pool + work-stealing job market (src/job_market.rs).
* ``threads(n)`` drives a real worker pool in the host BFS (1,500-state
  work-share blocks over a shared pending deque, mirroring the
  reference's job-market granularity) — though CPython's GIL keeps
  pure-Python model callbacks serialized, so wall-clock gains are
  bounded by the callbacks' native time (hashing, dataclass compare).
"""

from __future__ import annotations

import time
from enum import Enum
from typing import Any, Callable, Optional, Sequence

from .model import Expectation, Model, Property, State
from .path import Path
from .report import ReportData, Reporter
from .visitor import CheckerVisitor, as_visitor


class DiscoveryClassification(str, Enum):
    """Whether a discovery proves or refutes a property (checker.rs:38-52)."""

    EXAMPLE = "example"
    COUNTEREXAMPLE = "counterexample"


class CheckerBuilder:
    """Fluent checker configuration (checker.rs:64-267)."""

    def __init__(self, model: Model):
        self.model = model
        self._symmetry: Optional[Callable[[State], State]] = None
        self._target_state_count: Optional[int] = None
        self._target_max_depth: Optional[int] = None
        self._threads: int = 1
        self._visitor: Optional[CheckerVisitor] = None
        self._unsound_ok: bool = False

    def unsound_ok(self) -> "CheckerBuilder":
        """Waive the reduction soundness-certificate gates
        (analysis/soundness.py): a declared ``DeviceRewriteSpec`` or
        ample mask that FAILS its obligations runs anyway instead of
        refusing at spawn. Research escape hatch (``--unsound-ok`` on
        the CLI) — the run's counts carry no soundness guarantee."""
        self._unsound_ok = True
        return self

    def symmetry(self) -> "CheckerBuilder":
        """Enable symmetry reduction via the state's own ``representative``
        method (checker.rs:217-222)."""
        return self.symmetry_fn(lambda state: state.representative())

    def symmetry_fn(self, f: Callable[[State], State]) -> "CheckerBuilder":
        """Enable symmetry reduction with an explicit representative
        function (checker.rs:225-232)."""
        self._symmetry = f
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        """Stop after visiting approximately ``count`` unique states
        (checker.rs:236-241)."""
        self._target_state_count = count
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        """Do not expand states deeper than ``depth`` (checker.rs:244-249)."""
        self._target_max_depth = depth
        return self

    def threads(self, n: int) -> "CheckerBuilder":
        """Worker threads for the host BFS (checker.rs:253-258):
        ``spawn_bfs`` runs n workers over the shared pending deque in
        1,500-state blocks (the reference's work-share granularity).
        Counts and the discovered property SET match the sequential
        run; which state discovers a property can differ run-to-run,
        as in the reference's thread race. Note CPython's GIL: on
        pure-Python models this is parity, not speedup — device
        engines (spawn_tpu*) are the parallelism story here."""
        self._threads = n
        return self

    def visitor(self, v) -> "CheckerBuilder":
        """Attach a visitor called with every evaluated state's path
        (checker.rs:261-266)."""
        self._visitor = as_visitor(v)
        return self

    # -- spawn methods (checker.rs:157-212) ------------------------------

    def spawn_bfs(self) -> "Checker":
        from .checkers.bfs import BfsChecker

        return BfsChecker(self)

    def spawn_dfs(self) -> "Checker":
        from .checkers.dfs import DfsChecker

        return DfsChecker(self)

    def spawn_simulation(self, seed: int = 0, chooser=None) -> "Checker":
        from .checkers.simulation import SimulationChecker, UniformChooser

        return SimulationChecker(self, chooser or UniformChooser(), seed)

    def spawn_on_demand(self) -> "Checker":
        from .checkers.on_demand import OnDemandChecker

        return OnDemandChecker(self)

    def spawn_tpu(self, **kwargs) -> "Checker":
        """Spawn the TPU wave engine — the reference's ``spawn_bfs``
        re-imagined for an accelerator (see BASELINE.json north star)."""
        from .checkers.tpu import TpuBfsChecker

        return TpuBfsChecker(self, **kwargs)

    def spawn_tpu_sortmerge(self, **kwargs) -> "Checker":
        """Spawn the sort-merge wave engine: visited set as a sorted
        fingerprint array merged per wave, no scatters in the hot loop
        — the TPU-idiomatic dedup (see checkers/tpu_sortmerge.py)."""
        from .checkers.tpu_sortmerge import SortMergeTpuBfsChecker

        return SortMergeTpuBfsChecker(self, **kwargs)

    def spawn_tpu_simulation(self, **kwargs) -> "Checker":
        """Spawn the device simulation checker: N parallel random walks
        under vmap, advancing in lockstep inside one jitted loop — the
        accelerator re-design of the reference's simulation checker
        (see checkers/tpu_simulation.py for semantics deltas)."""
        from .checkers.tpu_simulation import TpuSimulationChecker

        return TpuSimulationChecker(self, **kwargs)

    def spawn_hybrid(self, **kwargs) -> "Checker":
        """Spawn the hybrid racer: host DFS in a thread vs the device
        sort-merge engine, first to complete wins and the loser is
        cancelled — TPU-or-tie on shallow bugs, the full device win on
        deep verification (see checkers/hybrid.py). kwargs go to the
        device engine."""
        from .checkers.hybrid import HybridChecker

        return HybridChecker(self, **kwargs)

    def spawn_tpu_sharded_sortmerge(self, **kwargs) -> "Checker":
        """Spawn the multi-chip wave engine: the frontier and visited
        set sharded over a ``jax.sharding.Mesh``, every wave's
        candidates routed to the shard owning their fingerprint by one
        ``all_to_all`` (replacing the reference's work-stealing job
        market, src/job_market.rs), owner-local dedup by the
        single-chip sort-merge engine's sorted visited array, parent
        forest as an append-only log. No scatters in the hot loop (see
        parallel/engine_sortmerge.py)."""
        from .parallel import ShardedSortMergeTpuBfsChecker

        return ShardedSortMergeTpuBfsChecker(self, **kwargs)

    def serve(self, addr: str):
        """Serve the Explorer web UI for this model (checker.rs:139-146)."""
        from .explorer.server import serve

        return serve(self, addr)


class Checker:
    """Common checker result interface (checker.rs:273-557)."""

    def __init__(self, builder: CheckerBuilder):
        self.builder = builder
        self.model = builder.model
        self._discoveries: dict[str, Path] = {}
        self._total_states = 0
        self._unique_states = 0
        self._max_depth = 0
        self._done = False
        self._run_error: Optional[Exception] = None
        self._started_at: Optional[float] = None
        self._finished_at: Optional[float] = None

    # -- engine hook -----------------------------------------------------

    def _run(self, reporter: Optional[Reporter] = None) -> None:
        """Run the search to completion. Implemented by engines."""
        raise NotImplementedError

    def _ensure_run(self, reporter: Optional[Reporter] = None) -> None:
        if self._done:
            if self._run_error is not None:
                raise self._run_error
            return
        # Run telemetry (stateright_tpu/telemetry.py): when a tracer
        # is active, every engine's execution is bracketed by
        # run_begin/run_end events here — the one place all engines
        # pass through — so host and device checkers trace alike.
        from . import telemetry

        tracer = telemetry.current_tracer()
        if tracer is not None:
            tracer.begin_run(lane=self._lane_config())
        self._started_at = time.monotonic()
        try:
            self._run(reporter)
        except Exception as exc:
            # A failed run is terminal: remember the error and replay
            # it on every later accessor instead of re-executing the
            # whole search (which would raise the same error again
            # after repaying the full runtime — and, on the TPU
            # engines, would also discard discoveries recorded before
            # an overflow raise).
            self._finished_at = time.monotonic()
            self._done = True
            self._run_error = exc
            if tracer is not None:
                tracer.end_run(
                    error=f"{type(exc).__name__}: {exc}",
                    **self._run_stats(),
                )
            raise
        self._finished_at = time.monotonic()
        self._done = True
        if tracer is not None:
            self._emit_settlement_verdicts(tracer)
            tracer.end_run(error=None, **self._run_stats())

    def _emit_settlement_verdicts(self, tracer) -> None:
        """Round-14 verdict timeline, the exhaustion half: a property
        with NO discovery settles only when the configured search
        completes (the same completion semantics assert_properties
        applies — bounded closures count as complete). Discovery
        verdicts land earlier, at the engines' own settle points (the
        device chunk loop, the host checkers' ``_discover``); this
        run-end sweep covers the rest, so every property of a clean
        run has exactly one ``verdict`` event and time-to-verdict is
        a measured number per property. Error paths skip it (a run
        that raised settled nothing it didn't already emit), and so
        do CANCELLED runs — the hybrid racer's losing side returns
        early with partial results, and a partial search has not
        exhausted anything."""
        if getattr(self, "cancelled", False):
            return
        discovered = set(self._discoveries) | set(
            getattr(self, "_discovered_fps", None) or {}
        )
        metrics = getattr(self, "metrics", None)
        waves = (metrics.get("waves")
                 if isinstance(metrics, dict) else None)
        for prop in self.model.properties():
            if prop.name in discovered:
                continue
            tracer.event(
                "verdict",
                property=prop.name,
                expectation=prop.expectation.name.lower(),
                kind="exhaustion",
                wave=(int(waves) if waves is not None else None),
                depth=self._max_depth,
            )

    def _lane_config(self) -> dict:
        """The run's lane description, embedded in the trace
        run_begin event (engines extend with shapes/budgets)."""
        return dict(
            engine=type(self).__name__,
            model=type(self.model).__name__,
            target_state_count=self.builder._target_state_count,
            target_max_depth=self.builder._target_max_depth,
        )

    def _run_stats(self) -> dict:
        """The run's outcome summary for the trace run_end event."""
        return dict(
            total_states=self._total_states,
            unique_states=self._unique_states,
            max_depth=self._max_depth,
            duration_sec=round(self.duration_sec(), 6),
        )

    # -- status (checker.rs:287-314) -------------------------------------

    def state_count(self) -> int:
        self._ensure_run()
        return self._total_states

    def unique_state_count(self) -> int:
        self._ensure_run()
        return self._unique_states

    def max_depth(self) -> int:
        self._ensure_run()
        return self._max_depth

    def is_done(self) -> bool:
        return self._done

    def join(self) -> "Checker":
        self._ensure_run()
        return self

    def duration_sec(self) -> float:
        if self._started_at is None:
            return 0.0
        end = self._finished_at if self._finished_at is not None else time.monotonic()
        return end - self._started_at

    # -- on-demand hooks (checker.rs:278-285); overridden by OnDemand ----

    def check_fingerprint(self, fp: int) -> None:
        pass

    def run_to_completion(self) -> None:
        self._ensure_run()

    # -- discoveries (checker.rs:287-300) --------------------------------

    def discoveries(self) -> dict[str, Path]:
        self._ensure_run()
        return dict(self._discoveries)

    def discovery(self, name: str) -> Optional[Path]:
        return self.discoveries().get(name)

    def discovery_classification(self, name: str) -> DiscoveryClassification:
        prop = self.model.property_by_name(name)
        if prop.expectation == Expectation.SOMETIMES:
            return DiscoveryClassification.EXAMPLE
        return DiscoveryClassification.COUNTEREXAMPLE

    # -- reporting (checker.rs:330-431) ----------------------------------

    def report(self, reporter: Reporter) -> "Checker":
        self._ensure_run(reporter)
        reporter.report_checking(
            ReportData(
                total_states=self._total_states,
                unique_states=self._unique_states,
                max_depth=self._max_depth,
                duration_sec=self.duration_sec(),
                done=self.is_done(),
            )
        )
        reporter.report_discoveries(self)
        return self

    def join_and_report(self, reporter: Reporter) -> "Checker":
        return self.report(reporter)

    # -- assertion helpers (checker.rs:447-556) --------------------------

    def assert_properties(self) -> None:
        """Assert no always/eventually counterexamples and an example for
        every sometimes property (checker.rs:447-473)."""
        for prop in self.model.properties():
            if prop.expectation == Expectation.SOMETIMES:
                self.assert_any_discovery(prop.name)
            else:
                self.assert_no_discovery(prop.name)

    def assert_any_discovery(self, name: str) -> Path:
        path = self.discovery(name)
        if path is None:
            raise AssertionError(f"expected a discovery for {name!r}")
        return path

    def assert_no_discovery(self, name: str) -> None:
        path = self.discovery(name)
        if path is not None:
            raise AssertionError(
                f"unexpected discovery for {name!r}: {path.encode()}\n{path!r}"
            )

    def assert_discovery(self, name: str, actions: Sequence[Any]) -> None:
        """Assert a discovery exists and matches the given action sequence
        (checker.rs:506-556)."""
        path = self.assert_any_discovery(name)
        if list(path.actions()) != list(actions):
            raise AssertionError(
                f"discovery for {name!r} has actions {path.actions()!r}, "
                f"expected {list(actions)!r}"
            )

    # -- shared engine internals ----------------------------------------

    def _eventually_bits_init(self) -> int:
        """Bitmask with one bit per eventually property, all set.

        Mirrors ``EventuallyBits`` seeding (checker.rs:559-566,
        bfs.rs:61-73): bits clear as conditions are met along a path;
        any bit surviving to a terminal state is a counterexample.
        """
        bits = 0
        for i, prop in enumerate(self.model.properties()):
            if prop.expectation == Expectation.EVENTUALLY:
                bits |= 1 << i
        return bits

    def _properties(self) -> Sequence[Property]:
        return self.model.properties()

    def _all_discovered(self) -> bool:
        """Early-exit condition: every property has a discovery
        (bfs.rs:128-135)."""
        props = self.model.properties()
        return len(props) > 0 and all(
            p.name in self._discoveries for p in props
        )
