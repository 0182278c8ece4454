"""The sharded sort-merge wave engine: multi-chip BFS over a device mesh.

Every device owns the fingerprints with ``fp_lo % n_shards ==
shard_index``: its slice of the visited set (a sorted fingerprint
array, the single-chip engine's dedup of checkers/tpu_sortmerge.py),
its block of the frontier and its part of the parent log. This is the
one mesh engine, ``CheckerBuilder.spawn_tpu_sharded_sortmerge()``.

Per wave, inside one ``shard_map``-wrapped ``lax.while_loop``:

1. each shard expands its local frontier block (vmap step → property
   bitmaps → fingerprints),
2. a 4-lane ``lax.sort`` keyed ``(owner, fp_hi, fp_lo)`` groups valid
   candidates by destination shard — routing and compaction in ONE
   sort, no per-destination scatters (the job_market.rs:66-147
   replacement, with the communication pattern chosen for ICI),
3. the routed payload is written, in that order, into the shard's
   fixed-shape ``route`` carry lane; each destination's contiguous run
   is sliced out of it into a tile of the wave's RECEIVE CLASS
   (``dynamic_slice`` at the run offset — contiguous copies, never
   scatters) and one ``lax.all_to_all`` swaps tiles so every
   candidate lands on the shard owning ``fp_lo % n_shards``,
4. owner-local dedup is the streaming sort-merge (round 10, shared
   with the single-chip engine): each shard's visited array is kept
   INCREMENTALLY SORTED, one B-scale sort orders the received
   candidates, membership + the visited append are O(V + B) streaming
   passes (``ops/merge.py`` — the Pallas kernel or the sort-free XLA
   fallback, per the inherited ``merge_impl``) — the role DashMap
   sharding plays in the reference BFS (bfs.rs:28-29) with zero
   cross-shard contention by construction, and no per-wave O(C)-row
   sort anywhere,
5. the parent forest is a per-shard append-only (child, parent) log
   written with ``dynamic_update_slice`` — no scatters — drained
   lazily on the host only when a counterexample path is
   reconstructed,
6. termination, counters, discovery folding, and overflow flags are
   ``psum``/``pmin`` reductions: every device agrees on ``done``
   without touching the host.

**Adaptive classes.** Waves dispatch through the same frontier and
visited class ladders as the single-chip engine, and every shard
agrees on each class via ``lax.pmax`` over its local count
(collectives are collective: a ``lax.switch`` must take the same
branch on every shard). A wave passes three class switches in
sequence, each sized by what the wave holds:

* the FRONTIER class (``F_c`` rows, from the largest frontier block):
  the property bits and frontier fingerprints, the enabled bits, the
  peel and the compaction of the live (pair, slot) indices into the
  dense prefix of a fixed-shape pair buffer;
* the PAIR class (``P_r`` rows, from the largest pair count): the pair
  step, the candidate fingerprints, the 4-lane routing sort and the
  routed payload, written into the ``route`` lane. ``F_c * EV`` fills
  the candidate budget in every frontier class at paxos-4's mesh
  budgets, so the pair class is what sizes the send side to the wave;
* the RECEIVE class (``S * Bd_r`` rows, from the largest
  per-destination run): the tiles sliced from ``route``, the
  ``all_to_all`` and the owner-local merge (candidate sort,
  membership, winner compaction, visited append, winner fetch).

The pair and receive ladders are ``top, top/4, top/16, top/64`` (no
rung below 128 rows; :func:`class_ladder`, :func:`class_of`) over the
candidate budget and the lossless per-destination cap ``Bd``. Each
class holds the largest shard's count and the compaction keeps the
pairs in pair-index order, so no candidate is dropped and every
wave's candidates, routed rows, visited set, winners and their order
are those of the largest class. The dense path (encodings without
pair extraction) has no pair class: its frontier class expands,
fingerprints and routes. Encodings implementing
``SparseEncodedModel`` get sparse action dispatch here too: pairs are
extracted and stepped shard-locally (the shared pipeline in
checkers/tpu_sortmerge.py — including the round-6 WORD-NATIVE enabled
predicate: encodings providing ``enabled_bits_vec`` never materialize
a dense ``[F, K]`` bool on any shard, hand paxos/2pc and the compiled
actor encodings alike), and only real candidates enter the routing
sort and the shuffle. This engine's invocation style of that pipeline
— inside ``shard_map`` with ``axis_name="shard"``, which changes the
traced program (``lax.pcast`` carry plumbing) — is pinned separately
by the kernel lint's ``engine:sharded`` trace
(stateright_tpu/analysis/lint.py, ``pytest -m lint``).

On one device the shuffle degenerates to the identity and results are
state-identical to the single-chip engines; tests pin identical
results for shard counts 1/2/8 on the CPU mesh, with
``track_paths=True`` paths replaying through the host model.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..checker import CheckerBuilder
from ..encoding import EncodedModel
from ..model import Expectation
from ..ops.fingerprint import fingerprint_u32v
from ..ops.merge import compact_winners, member_sorted, merge_sorted
from ..ops.u64 import U64, u64_add
from ..checkers.tpu import expand_frontier, wave_hits
from ..checkers.tpu_sortmerge import SortMergeTpuBfsChecker

_SENT = 0xFFFFFFFF


def class_ladder(top: int, step: int = 4, rungs: int = 4,
                 floor: int = 128) -> list:
    """The row classes of a per-wave count, smallest first: ``top``
    (the lossless per-destination cap for the receive classes, the
    largest candidate budget for the pair classes), then ``top/4``,
    ``top/16``, ``top/64``, no rung below ``floor`` rows (nor below
    ``top`` where that is smaller)."""
    lo = min(floor, top)
    return sorted({max(lo, top // step ** i) for i in range(rungs)})


def class_of(n, ladder):
    """Index of the smallest rung of ``ladder`` that holds ``n`` rows,
    for a Python int or a traced scalar alike. A count above the top
    rung picks the top; the wave's overflow check (routing for the
    receive class, the candidate budget for the pair class) has then
    already refused it."""
    return sum((n > rung) * 1 for rung in ladder[:-1])


def dest_tile_width(w: int, track_paths: bool) -> int:
    """Lanes of a routed destination tile (see dest_tile_pack)."""
    return (w + 3 if track_paths else w + 1) + 2


def dest_tile_pack(jnp, state, par_lo, par_hi, ebits, key_lo, key_hi):
    """THE sharded routed-tile lane layout: ``[state 0:W | par_lo W |
    par_hi W+1 (paths only) | ebits E-1 | key_lo E | key_hi E+1]``
    with ``E = W+3`` (paths) or ``W+1`` — the routed payload and the
    recomputed (memory-lean) tiles both pack through this helper, and
    ``merge_stage`` unpacks by the same offsets (``recv[:, E]``/
    ``recv[:, EB]``), so the tile layout can't drift between the pack
    sites and the post-shuffle merge.
    NOT the single-chip payload layout: ``payload_pack``
    (checkers/tpu_sortmerge.py) orders key limbs before parent meta
    and is unpacked by ``payload_unpack`` at the merge fetch.

    Columns accept 1-D ``[B]`` or already-sliced 2-D ``[B, 1]``
    arrays; ``par_lo``/``par_hi`` are None when paths are off."""

    def col(x):
        return x if x.ndim == 2 else x[:, None]

    parts = [state]
    if par_lo is not None:
        parts += [col(par_lo), col(par_hi)]
    parts += [col(ebits), col(key_lo), col(key_hi)]
    return jnp.concatenate(parts, axis=1)


class ShardedSortMergeTpuBfsChecker(SortMergeTpuBfsChecker):
    """``CheckerBuilder.spawn_tpu_sharded_sortmerge()`` — the sort-merge
    wave engine over a ``jax.sharding.Mesh``. Inherits the result /
    reconstruction surface (including the clamped host fingerprints)
    from the single-chip sort-merge engine; the device programs and the
    parent-log layout differ. It also inherits both reduction
    soundness-certificate gates (analysis/soundness.py): the symmetry
    gate fires in the base ``TpuBfsChecker.__init__`` and the ample
    gate in the base ``_resolve_ample_words``, so a sharded run can
    only arm ``--symmetry``/``--ample-set`` against a certified spec."""

    _engine_name = "spawn_tpu_sharded_sortmerge"

    def __init__(
        self,
        builder: CheckerBuilder,
        encoded: Optional[EncodedModel] = None,
        mesh=None,
        n_shards: Optional[int] = None,
        capacity: int = 1 << 13,
        frontier_capacity: Optional[int] = None,
        track_paths: bool = True,
        waves_per_sync: int = 16,
        cand_capacity: Optional[int] = None,
        bucket_capacity: Optional[int] = None,
        **kwargs,
    ):
        import jax
        from jax.sharding import Mesh

        if mesh is None:
            devices = jax.devices()
            if n_shards is None:
                n_shards = len(devices)
            if n_shards > len(devices):
                raise ValueError(
                    f"n_shards={n_shards} > {len(devices)} available devices"
                )
            mesh = Mesh(np.array(devices[:n_shards]), ("shard",))
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"expected a 1-axis mesh, got axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        if cand_capacity == "auto":
            raise ValueError(
                'cand_capacity="auto" is single-chip only: the sharded '
                "engine's budgets are per shard and its overflow "
                "message/metrics differ — pass explicit capacities "
                "(the single-chip auto run's persisted budget is a "
                "good starting point)"
            )
        super().__init__(
            builder,
            encoded=encoded,
            capacity=capacity,
            frontier_capacity=frontier_capacity,
            track_paths=track_paths,
            waves_per_sync=waves_per_sync,
            cand_capacity=cand_capacity,
            **kwargs,
        )
        self.total_capacity = capacity * self.n_shards
        self.bucket_capacity = bucket_capacity
        #: live shard ids in ORIGINAL numbering (the degrade-and-
        #: continue layer: faultinject filters persistent shard
        #: faults against this, and a supervised degrade removes the
        #: dropped shard — checkers/tpu.py _degrade_shards).
        self._shard_ids = tuple(range(self.n_shards))

    def _cache_extras(self) -> tuple:
        # Includes the single-chip extras: the ladder/sparse/tile knobs
        # shape the compiled sharded program too.
        return (
            "sharded-sortmerge",
            self.n_shards,
            self.bucket_capacity,
            self.mesh,
        ) + super()._cache_extras()

    def _cand_overflow_message(self) -> str:
        return (
            "candidate/bucket overflow: a wave generated more successors "
            f"than fit the per-shard buffers (cand_capacity="
            f"{self.cand_capacity}, bucket_capacity={self.bucket_capacity});"
            " re-run with larger capacities — the max_wave_candidates "
            "metric reports the observed per-shard peak"
        )

    def _consume_extra_stats(self, extra: np.ndarray) -> None:
        if extra.size >= 3:
            self.metrics["shuffle_volume"] = int(extra[0]) | (
                int(extra[1]) << 32
            )
            self.metrics["max_wave_candidates"] = int(extra[2])
        info = self._build_info or {}
        n_recv = len(info.get("recv_classes", ()))
        n_pair = len(info.get("pair_classes", ()))
        if n_recv and extra.size >= 3 + n_pair + n_recv:
            # waves per pair class, then per receive class, smallest
            # first (pack_stats' last lanes)
            self.metrics["recv_class_waves"] = [
                int(x) for x in extra[-n_recv:]
            ]
            if n_pair:
                self.metrics["pair_class_waves"] = [
                    int(x) for x in extra[-(n_pair + n_recv):-n_recv]
                ]

    def _run_stats(self) -> dict:
        """The trace's run_end summary plus the shuffle's run-level
        counters: the shard count, the rows routed off-shard (the
        psum'd ``sent`` counter), the worst per-wave candidate skew
        (max/mean over shards) of the run's ``shard_wave`` events, and
        the waves run at each pair class and each receive class,
        smallest first."""
        from .. import telemetry

        stats = super()._run_stats()
        worst = None
        tracer = telemetry.current_tracer()
        if tracer is not None:
            with tracer._lock:
                events = list(tracer.events)
            bal = telemetry.shard_balance(events, run=tracer._run_idx)
            worst = (bal or {}).get("candidate_skew_worst")
        stats.update(
            n_shards=self.n_shards,
            routed_rows=self.metrics.get("shuffle_volume"),
            candidate_skew_worst=worst["skew"] if worst else None,
            pair_class_waves=self.metrics.get("pair_class_waves"),
            recv_class_waves=self.metrics.get("recv_class_waves"),
        )
        return stats

    def _wave_log_pairs_valid(self) -> bool:
        # The sharded GLOBAL log wrapper can't see the enabled-pair
        # popcount (it lives inside the per-shard wave switch): lane 1
        # is 0. The per-shard mesh log DOES see it (swave lane 1), so
        # the tracer back-fills the wave event from the shard sum
        # instead of recording enabled_pairs=null.
        return False

    def _pair_ladder(self) -> list:
        """The pair classes, smallest first: the class ladder over the
        largest frontier class's candidate budget (sparse only; the
        dense path has no pairs)."""
        if not self._use_sparse():
            return []
        return class_ladder(max(p[4] for p in self._f_class_params()))

    def _plan_sharded_names(self) -> tuple:
        # Mirrors the shard_map out_specs in _build_programs: these
        # carry leaves shard along their row axis, so their ledger
        # rows report per_shard_bytes = bytes / n_shards.
        return ("vkeys", "plog", "pl_n", "route", "frontier", "fval",
                "ebits", "n_loc", "u_loc", "slog", "swave")

    def _lane_config(self) -> dict:
        lane = super()._lane_config()
        lane.update(
            n_shards=self.n_shards,
            bucket_capacity=self.bucket_capacity,
            # routed-tile lanes: what telemetry.shard_balance prices
            # routed-byte volume with (rows x lanes x 4 B)
            dest_tile_lanes=dest_tile_width(
                self.encoded.width, self.track_paths
            ),
            # the receive-tile classes; the shard log's dest_cap is
            # the wave's class, the top the lossless cap the routing
            # overflow check holds a run to
            recv_classes=class_ladder(
                max(p[5] for p in self._f_class_params())
            ),
        )
        return lane

    # -- tiered visited set (stateright_tpu/tier.py) -----------------------
    #
    # The shared takeover loop lives on the single-chip base class;
    # these hooks adapt it to the mesh layout: per-shard hot counts
    # (h_loc), per-shard pend lanes, NamedSharding placement for the
    # carry surgery (spill reset, handoff lanes), and the per-shard
    # keep-mask upload.

    def _tier_resident_counts(self, carry) -> np.ndarray:
        return np.asarray(
            carry["u_loc"]
        ).astype(np.int64).reshape(-1)

    def _tier_hot_lane(self) -> str:
        return "h_loc"

    def _tier_zero_hot(self):
        return np.zeros(self.n_shards, np.uint32)

    def _tier_hot_value(self, h_np):
        return np.asarray(h_np, np.uint32).reshape(-1)

    def _tier_zero_pl(self):
        return np.zeros(self.n_shards, np.uint32)

    def _tier_pend_zero(self):
        return np.zeros(self.n_shards, np.uint32)

    def _tier_place(self, name, arr):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        spec = (getattr(self, "_tier_pspecs", None) or {}).get(name)
        if spec is None:
            spec = P()
        return jnp.copy(jax.device_put(
            np.asarray(arr), NamedSharding(self.mesh, spec)
        ))

    def _tier_mask_dev(self, mask_np: np.ndarray):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return jax.device_put(
            np.ascontiguousarray(mask_np.reshape(-1)),
            NamedSharding(self.mesh, P("shard")),
        )

    def _tier_shard_rows(self, shard_log):
        if shard_log is None:
            return None
        from ..telemetry import SHARD_LOG_LANES as SL

        return np.asarray(shard_log).reshape(self.n_shards, 1, SL)

    def _tier_extend_trace(self, ext) -> None:
        from ..telemetry import SHARD_LOG_LANES as SL

        S = self.n_shards
        ext["slog"] = self._tier_place(
            "slog", np.zeros((S, SL), np.uint32)
        )
        ext["swave"] = self._tier_place(
            "swave", np.zeros(S * SL, np.uint32)
        )

    # -- device programs ---------------------------------------------------

    def _f_class_params(self) -> list:
        """Static per-shard shapes of each frontier class, smallest
        first: ``(F_c, NT, T, R_src, B_c, Bd_c)`` — frontier rows,
        pair tiles and their rows, the candidate buffer (sparse: the
        rows the class's compaction may write), the candidate budget,
        and the lossless per-destination cap."""
        from ..checkers.tpu_sortmerge import _divisor_at_least, _ladder

        K, F, S = (self.encoded.max_actions, self.frontier_capacity,
                   self.n_shards)
        B_user = min(self.cand_capacity or F * K, F * K)
        use_sparse = self._use_sparse()
        EV = self._pair_width() if use_sparse else 0
        out = []
        for F_c in _ladder(self.f_min, F, self.ladder_step):
            if use_sparse:
                # Every class compacts its live pairs into a dense
                # prefix (the pair class steps only that prefix).
                # Keep the packed-append headroom ≤ B_c/4 (see the
                # single-chip engine's make_sparse_wave); a grid that
                # fits the budget never writes past its own rows.
                NPg = F_c * EV
                B_c = min(B_user, NPg)
                want = max(-(-NPg // self.tile_rows),
                           -(-(4 * NPg) // max(B_c, 1)))
                NT = _divisor_at_least(F_c, want)
                T = F_c // NT
                R_src = min(NPg, B_c + T * EV)
            else:
                NT = T = 1
                R_src = F_c * K
                B_c = min(B_user, R_src)
            if self.bucket_capacity is not None:
                Bd_c = min(self.bucket_capacity, B_c)
            elif S == 1:
                Bd_c = B_c
            else:
                # Near-uniform fingerprint split: 4x the expected share.
                Bd_c = min(B_c, max(128, (4 * B_c + S - 1) // S))
            out.append((F_c, NT, T, R_src, B_c, Bd_c))
        return out

    def _build_programs(self, n0: int, tiered: bool = False):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        from ..checkers.tpu import frontier_props_t
        from ..checkers.tpu_sortmerge import (
            _ladder,
            sparse_pair_candidates,
        )
        from ..encoding import (
            has_trivial_boundary,
            pair_step_seam,
            within_boundary_cols,
        )
        from ..ops.fingerprint import fingerprint_u32v_t

        tier_mode = bool(tiered)
        enc = self.encoded
        # Device symmetry (ops/canonical.py, see the single-chip
        # engine): fingerprints fold the CANONICAL block, the resident
        # frontier keeps concrete states. The routing ownership below
        # (k_lo % S) then hashes the canonical key, so all members of
        # an orbit route to ONE shard and the per-shard dedup is a
        # global orbit dedup — no cross-shard coordination added.
        sym = self.sym_spec
        if sym is not None:
            from ..ops.canonical import canonicalize_rows, canonicalize_t
        ample_words = self._resolve_ample_words()
        props = list(self.model.properties())
        n_props = len(props)
        cpu_backend = jax.default_backend() == "cpu"
        evt_idx = [
            i for i, p in enumerate(props)
            if p.expectation == Expectation.EVENTUALLY
        ]
        if evt_idx and max(evt_idx) >= 32:
            raise ValueError(
                "the TPU engine supports eventually properties only at "
                "property indices < 32; reorder properties() so eventually "
                f"properties come first (got index {max(evt_idx)})"
            )
        K, W, F = enc.max_actions, enc.width, self.frontier_capacity
        S = self.n_shards
        C = self.capacity
        use_sparse = self._use_sparse()
        EV = self._pair_width() if use_sparse else 0
        sparse_has_trunc = sparse_boundary = False
        if use_sparse:
            sparse_has_trunc = isinstance(
                jax.eval_shape(
                    enc.step_slot_vec,
                    jax.ShapeDtypeStruct((W,), jnp.uint32),
                    jax.ShapeDtypeStruct((), jnp.uint32),
                ),
                tuple,
            )
            sparse_boundary = not has_trivial_boundary(enc)
            # Transposed pair step: [W, n] successor block out — the
            # shape the lane-major fingerprint fold consumes. The
            # input seam is the shared backend policy
            # (encoding.pair_step_seam, PERF.md §layout).
            step_cols, make_pair_states = pair_step_seam(
                enc, cpu_backend
            )
        if n0 > C:
            raise ValueError(
                f"per-shard capacity {C} < {n0} init states"
            )
        target_states = self.builder._target_state_count
        target_depth = self.builder._target_max_depth
        waves_per_sync = self.waves_per_sync
        ebits_init = self._eventually_bits_init()
        track_paths = self.track_paths
        # Per-wave trace logs (telemetry.py). TWO of them since round
        # 11:
        # * the GLOBAL log (psum'd frontier rows, the replicated
        #   gen/new deltas), appended by a wrapper around the wave
        #   body — the inner wave/merge builders never see it, so the
        #   replicated row stays out of the shard-varying carry
        #   plumbing. The enabled-pair popcount is not visible at
        #   this level: lane 1 logs 0 (_wave_log_pairs_valid; the
        #   host back-fills the wave event from the shard log's sum);
        # * the PER-SHARD mesh log (SHARD_LOG_FIELDS) — NOT
        #   psum-collapsed: each shard's wave row (local frontier/
        #   pairs/candidates, routed and received rows, dest-tile
        #   fill vs the wave's receive class, local post-dedup new,
        #   local visited count) is assembled INSIDE the wave
        #   switches (where those quantities exist) as the ``swave``
        #   carry lane, and the body wrapper appends it to ``slog``. Both logs ride
        #   the chunk carry and download at the existing per-chunk
        #   sync (slog as a second, shard-sharded stats output — same
        #   dispatch, same blocking point, no extra round trip).
        # Gated on an active tracer and cache-keyed (_cache_extras),
        # so untraced programs compile exactly as before.
        from ..telemetry import SHARD_LOG_LANES as SL
        from ..telemetry import WAVE_LOG_LANES as WL

        trace_log = self._wave_log_enabled()

        # Class ladders, agreed across shards per wave via lax.pmax
        # (collectives are collective: every shard must take the same
        # lax.switch branch or the all_to_all deadlocks).
        f_ladder = _ladder(self.f_min, F, self.ladder_step)
        v_ladder = _ladder(self.v_min, C, self.v_ladder_step)

        # Per-shard parent-log rows: every unique state a shard owns
        # (<= C) gets one entry; the append block is F rows (the
        # next-frontier width).
        L = C + F if track_paths else 0
        # Payload lanes: state + (parent fp) + ebits + own fp (owners
        # don't re-hash after the shuffle). All-zero fp lanes mark
        # unused bucket slots (fingerprints are never 0).
        # Routed-tile lane offsets, tied to dest_tile_pack's layout:
        # key limbs at [E, E+1], ebits at EB.
        E = dest_tile_width(W, track_paths) - 2
        EB = E - 1
        mesh = self.mesh

        # The receive side's own ladder (see the module docstring): its
        # top is the largest lossless per-destination cap, and the
        # per-class route_ovf checks keep refusing a wave whose run
        # exceeds its frontier class's cap.
        f_params = self._f_class_params()
        r_ladder = class_ladder(max(p[5] for p in f_params))
        # The pair classes (sparse only, see the module docstring):
        # each frontier class compacts its live pairs into the dense
        # prefix of an R_pair-row buffer, and the pair step,
        # fingerprints, routing sort and payload run at the smallest
        # rung of this ladder that holds every shard's pair count.
        p_ladder = self._pair_ladder()
        R_pair = max(p[3] for p in f_params) if use_sparse else 0
        # The routed payload lands in the fixed-shape ``route`` carry
        # lane, rows [0, R) of the wave's pair class (dense: frontier
        # class) written in routing order; R_route covers the largest
        # class. Where a class fingerprints in chunks (the memory-lean
        # mode: the [R, W] successor tensor would blow the flat
        # budget) the lane holds (pair, slot, parent meta, key) rows
        # instead and the receive class recomputes each tile's
        # successors — one layout for every class, so the receive
        # branches need not know the sending class.
        row_pad = -(-W // 128) * 512
        recompute = use_sparse and (
            p_ladder[-1] * row_pad > self.flat_budget_bytes
        )
        n_meta = 3 if track_paths else 1  # ebits (+ parent fp limbs)
        R_route = (p_ladder[-1] if use_sparse
                   else max(p[3] for p in f_params))
        route_lanes = 2 + n_meta + 2 if recompute else E + 2

        def bool_any(x):
            return lax.psum(x.astype(jnp.uint32), "shard") > 0

        def clamp_keys(lo, hi):
            both = (lo == jnp.uint32(_SENT)) & (hi == jnp.uint32(_SENT))
            return lo, jnp.where(both, jnp.uint32(_SENT - 1), hi)

        # INCREMENTALLY SORTED per-shard visited arrays (round 10,
        # see the C_pad notes in checkers/tpu_sortmerge.py): rows
        # [0, u_loc) are a dense sorted run; each wave linear-merges
        # the shard's winner keys into the prefix. F rows of headroom
        # cover the [0, V_v + NF) merged-block write at V_v == C.
        C_pad = C + F

        def seed_local(init_rows):
            # Host upload boundary: rows arrive row-major and
            # transpose ONCE into the [W, F] resident layout
            # (PERF.md §layout).
            me = lax.axis_index("shard").astype(jnp.uint32)
            # canonical keys from wave zero (ownership included)
            fp_rows = (canonicalize_rows(sym, init_rows, jnp)
                       if sym is not None else init_rows)
            lo0, hi0 = fingerprint_u32v(fp_rows, jnp)
            lo0, hi0 = clamp_keys(lo0, hi0)
            mine = (lo0 % jnp.uint32(S)) == me
            pos = jnp.cumsum(mine) - 1
            sp = jnp.where(mine, pos, F)
            frontier = jnp.zeros((W, F), dtype=jnp.uint32).at[
                :, sp
            ].set(init_rows.T, mode="drop")
            n_mine = jnp.sum(mine).astype(jnp.uint32)
            fval = jnp.arange(F) < n_mine
            ebits = jnp.where(fval, jnp.uint32(ebits_init), jnp.uint32(0))
            # Compact this shard's init keys to a dense SORTED prefix
            # (the round-10 invariant: rows [0, u_loc) are a sorted
            # run) — validity bit leads the key so dropped rows sort
            # last, then (hi, lo) orders the kept prefix.
            mk = jnp.where(mine, jnp.uint32(0), jnp.uint32(1))
            _, sk_hi, sk_lo = lax.sort((mk, hi0, lo0), num_keys=3)
            live_pref = n_mine > jnp.arange(
                sk_lo.shape[0], dtype=jnp.uint32
            )
            sk_lo = jnp.where(live_pref, sk_lo, jnp.uint32(_SENT))
            sk_hi = jnp.where(live_pref, sk_hi, jnp.uint32(_SENT))
            pad = C_pad - sk_lo.shape[0]
            vkeys = jnp.stack([
                jnp.concatenate(
                    [sk_lo, jnp.full(pad, _SENT, jnp.uint32)]
                ),
                jnp.concatenate(
                    [sk_hi, jnp.full(pad, _SENT, jnp.uint32)]
                ),
            ])
            return dict(
                **(
                    dict(
                        wlog=jnp.zeros((waves_per_sync, WL),
                                       jnp.uint32),
                        slog=jnp.zeros((waves_per_sync, SL),
                                       jnp.uint32),
                        swave=jnp.zeros(SL, jnp.uint32),
                    )
                    if trace_log else {}
                ),
                vkeys=vkeys,
                plog=jnp.zeros((4, L), jnp.uint32),
                pl_n=jnp.zeros(1, jnp.uint32),
                route=jnp.zeros((R_route, route_lanes), jnp.uint32),
                recv_waves=jnp.zeros(len(r_ladder), jnp.uint32),
                **(
                    dict(pair_waves=jnp.zeros(len(p_ladder), jnp.uint32))
                    if use_sparse else {}
                ),
                frontier=frontier,
                fval=fval,
                ebits=ebits,
                n_loc=n_mine.reshape(1),
                u_loc=n_mine.reshape(1),
                depth=jnp.int32(1),
                wchunk=jnp.int32(0),
                waves=jnp.uint32(0),
                gen_lo=jnp.uint32(n0),
                gen_hi=jnp.uint32(0),
                new=jnp.uint32(n0),
                sent_lo=jnp.uint32(0),
                sent_hi=jnp.uint32(0),
                max_cand=jnp.uint32(0),
                disc_found=jnp.zeros(n_props, dtype=bool),
                disc_lo=jnp.zeros(n_props, dtype=jnp.uint32),
                disc_hi=jnp.zeros(n_props, dtype=jnp.uint32),
                overflow=jnp.bool_(n0 > C),
                f_overflow=jnp.bool_(False),
                c_overflow=jnp.bool_(False),
                e_overflow=jnp.bool_(False),
                done=jnp.bool_(n0 == 0),
            )

        def merge_stage(c, v_class, R_c, recv, n_cand, sent, disc, ovf,
                        shard_log=None):
            """Owner-local streaming-merge dedup (the DashMap-shard
            role, bfs.rs:28-29, on the TPU-fast path), round 10: the
            shard's visited array is incrementally sorted, so dedup is
            ONE R_c-row candidate order sort (B-scale; the old
            ``(V_v + R_c)``-row concat sort is gone) + a streaming
            membership pass, and the visited append is a linear merge
            of the ≤F winner keys (``ops/merge.py``, the inherited
            ``merge_impl``). Intra-wave duplicates resolve on the
            adjacent-equal check of the sorted candidates (stable
            sort ⇒ lowest received position wins — the old
            stable-concat winner).

            Class-collapsed (round 9, PERF.md §layout): the v-ladder
            switches' branch outputs stay small/single-buffer — the
            membership switch returns ``bool[R_c]``, the append
            switch returns ``vkeys`` alone — and the winner gather,
            frontier/ebits/plog writes, and carry assembly happen
            ONCE at wave level. Collectives (psum/pmax) stay out of
            the branches: every shard takes the same branch (the
            classes are pmax-agreed), but uniform collectives outside
            the switch are the simpler contract.

            ``shard_log`` (traced runs only) is the wave's
            ``(enabled_pairs, routed_rows, dest_fill_peak, dest_cap,
            pair_cap)`` per-shard scalars from the routing stage and
            the two classes; this stage adds
            the quantities it owns (received rows, post-dedup new,
            the visited total) and returns the assembled
            ``swave: uint32[SHARD_LOG_LANES]`` row in the carry — 40
            bytes of extra switch output, priced by the lint's
            sharded wave-body fixture (analysis/tables.py)."""
            disc_found, disc_lo, disc_hi = disc
            overflow0, f_overflow0, c_overflow, e_overflow = ovf

            r_lo = recv[:, E]
            r_hi = recv[:, E + 1]
            r_val = (r_lo != 0) | (r_hi != 0)
            ck_lo = jnp.where(r_val, r_lo, jnp.uint32(_SENT))
            ck_hi = jnp.where(r_val, r_hi, jnp.uint32(_SENT))

            NFs = min(F, R_c)
            cpos = jnp.arange(1, R_c + 1, dtype=jnp.uint32)
            s_hi, s_lo, s_pos = lax.sort(
                (ck_hi, ck_lo, cpos), num_keys=2
            )
            real = ~(
                (s_hi == jnp.uint32(_SENT))
                & (s_lo == jnp.uint32(_SENT))
            )
            prev_same = jnp.concatenate(
                [
                    jnp.zeros(1, bool),
                    (s_hi[1:] == s_hi[:-1])
                    & (s_lo[1:] == s_lo[:-1]),
                ]
            )
            fresh = real & ~prev_same

            def member_core(vc):
                V_v = v_ladder[vc]

                def br(_):
                    return member_sorted(
                        c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                        s_lo, s_hi, impl=self.merge_impl,
                    )

                return br

            in_visited = lax.switch(
                v_class,
                [member_core(vc) for vc in range(len(v_ladder))],
                0,
            )
            is_new = fresh & ~in_visited
            new_count = jnp.sum(is_new)
            # Order-preserving winner compaction (ops/merge.py,
            # impl-adaptive: O(R_c) rank scatter on the XLA fallback,
            # one 4-lane R_c-scale sort on the Pallas/TPU path):
            # winners lead in KEY order, the order the routed-tile
            # gather, plog append, and visited merge all share.
            nf_pos, w_lo, w_hi = compact_winners(
                is_new, s_pos, s_lo, s_hi, NFs, impl=self.merge_impl
            )

            if tier_mode:
                # the commit phase (next dispatch) owns the per-shard
                # capacity check against the HOT count
                overflow = overflow0
            else:
                overflow = overflow0 | bool_any(
                    c["u_loc"][0] + new_count.astype(jnp.uint32)
                    > jnp.uint32(C)
                )
            nf_valid = jnp.arange(F) < new_count
            f_overflow = f_overflow0 | bool_any(new_count > F)
            # At most NFs winners: the fetch gathers NFs rows and the
            # resident frontier/ebits/parent-log take class-local
            # blocks at offset 0. Rows past NFs keep stale values that
            # fval masks, as every reader of the frontier does.
            nf_ok = nf_valid[:NFs]
            nf_row = jnp.where(nf_ok, nf_pos - 1, jnp.uint32(0))
            next_fe = recv[nf_row]
            # The winners come off the routed-tile row gather; one
            # seam transpose feeds the [W, F] resident frontier.
            z2 = (jnp.uint32(0), jnp.uint32(0))
            next_frontier = lax.dynamic_update_slice(
                c["frontier"],
                jnp.where(
                    nf_ok[:, None], next_fe[:, :W], jnp.uint32(0)
                ).T,
                z2,
            )
            next_ebits = lax.dynamic_update_slice(
                c["ebits"],
                jnp.where(nf_ok, next_fe[:, EB], jnp.uint32(0)),
                (jnp.uint32(0),),
            )

            def fe_lane(j):
                return jnp.where(nf_ok, next_fe[:, j], jnp.uint32(0))

            # Visited append (sorted invariant): linear-merge the
            # sorted winner block into the shard's sorted prefix and
            # write it back as one class-local block at offset 0
            # (rows past V_v + NFs stay sentinel by the C_pad
            # headroom). vkeys is the branch's only output.
            def append_core(vc):
                V_v = v_ladder[vc]

                def br(_):
                    m_lo, m_hi = merge_sorted(
                        c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                        w_lo, w_hi, impl=self.merge_impl,
                    )
                    return lax.dynamic_update_slice(
                        c["vkeys"],
                        jnp.stack([m_lo, m_hi]),
                        (jnp.uint32(0), jnp.uint32(0)),
                    )

                return br

            if tier_mode:
                vkeys_new = c["vkeys"]  # the commit phase merges
            else:
                vkeys_new = lax.switch(
                    v_class,
                    [append_core(vc) for vc in range(len(v_ladder))],
                    0,
                )

            pend_extra = {}
            if tier_mode and track_paths:
                # stage the parent limbs for the commit's append —
                # no false-new row ever reaches the parent-log drain
                plog_new = c["plog"]
                pl_n = c["pl_n"]
                pend_extra = dict(
                    pend_par=lax.dynamic_update_slice(
                        c["pend_par"],
                        jnp.stack([fe_lane(W), fe_lane(W + 1)]),
                        z2,
                    )
                )
            elif track_paths:
                # Parent AND child limbs (round 10): the sorted merge
                # re-orders vkeys rows every wave, so the round-9
                # positional child derivation is gone — the log is
                # the insertion-order record again (_build_generated).
                plog_new = lax.dynamic_update_slice(
                    c["plog"],
                    jnp.stack([
                        fe_lane(W), fe_lane(W + 1),
                        fe_lane(E), fe_lane(E + 1),
                    ]),
                    (jnp.uint32(0), c["pl_n"][0]),
                )
                # Clamp to the NFs rows the block write actually
                # wrote (on an f_overflow wave new_count can exceed
                # F; _run raises before reconstruction, but the
                # live-count invariant should hold regardless).
                pl_n = c["pl_n"] + jnp.minimum(
                    new_count.astype(jnp.uint32), jnp.uint32(NFs)
                )
            else:
                plog_new = c["plog"]
                pl_n = c["pl_n"]

            g_new = lax.psum(new_count.astype(jnp.uint32), "shard")
            g_cand = lax.psum(n_cand, "shard")
            g = u64_add(
                U64(c["gen_lo"], c["gen_hi"]),
                U64(g_cand, jnp.uint32(0)),
            )
            new = c["new"] + g_new
            max_cand = jnp.maximum(
                c["max_cand"], lax.pmax(n_cand, "shard")
            )

            if tier_mode:
                # DEFERRED COMMIT (stateright_tpu/tier.py): stage the
                # shard's provisional winners and leave vkeys, the
                # parent log, and every committed counter untouched —
                # the next dispatch's commit phase folds in the host's
                # per-shard cold-membership verdict. The staged key
                # block keeps compact_winners' (hi, lo) order with a
                # sentinel tail, exactly what the commit merge wants.
                nc_u32 = new_count.astype(jnp.uint32)
                pk_lo = lax.dynamic_update_slice(
                    jnp.full(F, _SENT, jnp.uint32), w_lo[:NFs], (0,)
                )
                pk_hi = lax.dynamic_update_slice(
                    jnp.full(F, _SENT, jnp.uint32), w_hi[:NFs], (0,)
                )
                trace_extra = {}
                if shard_log is not None:
                    (wv_pairs, cross_rows, fill_peak, dest_cap,
                     pair_cap) = shard_log
                    # provisional lanes 7/8 — the commit patches them
                    # with the confirmed count before the slog write
                    trace_extra = dict(
                        swave=jnp.stack(
                            [
                                c["n_loc"][0],
                                wv_pairs.astype(jnp.uint32),
                                n_cand.astype(jnp.uint32),
                                cross_rows.astype(jnp.uint32),
                                jnp.sum(r_val, dtype=jnp.uint32),
                                fill_peak.astype(jnp.uint32),
                                dest_cap,
                                nc_u32,
                                c["u_loc"][0] + nc_u32,
                                pair_cap,
                            ]
                        )
                    )
                return dict(
                    **trace_extra,
                    **pend_extra,
                    vkeys=vkeys_new,
                    plog=plog_new,
                    pl_n=pl_n,
                    frontier=next_frontier,
                    fval=nf_valid,
                    ebits=next_ebits,
                    n_loc=nc_u32.reshape(1),
                    u_loc=c["u_loc"],
                    h_loc=c["h_loc"],
                    pend_keys=jnp.stack([pk_lo, pk_hi]),
                    pend_n=nc_u32.reshape(1),
                    pend_valid=jnp.bool_(True),
                    depth=c["depth"],
                    wchunk=c["wchunk"] + 1,
                    waves=c["waves"],
                    gen_lo=g.lo,
                    gen_hi=g.hi,
                    new=c["new"],
                    sent_lo=sent.lo,
                    sent_hi=sent.hi,
                    max_cand=max_cand,
                    disc_found=disc_found,
                    disc_lo=disc_lo,
                    disc_hi=disc_hi,
                    overflow=overflow,
                    f_overflow=f_overflow,
                    c_overflow=c_overflow,
                    e_overflow=e_overflow,
                    done=c["done"],
                )

            all_disc = (
                jnp.all(disc_found) if n_props else jnp.bool_(False)
            )
            if target_states is None:
                target_hit = jnp.bool_(False)
            else:
                target_hit = new >= jnp.uint32(target_states)
            cont = (
                (g_new > 0)
                & ~all_disc
                & ~target_hit
                & ~overflow
                & ~f_overflow
                & ~c_overflow
                & ~e_overflow
            )
            nc_u32 = new_count.astype(jnp.uint32)
            trace_extra = {}
            if shard_log is not None:
                # The per-shard mesh wave row (SHARD_LOG_FIELDS),
                # assembled where the local quantities exist — lanes
                # 0-1 close the sharded enabled_pairs=null hole.
                (wv_pairs, cross_rows, fill_peak, dest_cap,
                 pair_cap) = shard_log
                trace_extra = dict(
                    swave=jnp.stack(
                        [
                            c["n_loc"][0],
                            wv_pairs.astype(jnp.uint32),
                            n_cand.astype(jnp.uint32),
                            cross_rows.astype(jnp.uint32),
                            jnp.sum(r_val, dtype=jnp.uint32),
                            fill_peak.astype(jnp.uint32),
                            dest_cap,
                            nc_u32,
                            c["u_loc"][0] + nc_u32,
                            pair_cap,
                        ]
                    )
                )
            return dict(
                **trace_extra,
                vkeys=vkeys_new,
                plog=plog_new,
                pl_n=pl_n,
                frontier=next_frontier,
                fval=nf_valid & cont,
                ebits=next_ebits,
                n_loc=jnp.where(
                    cont, nc_u32, jnp.uint32(0)
                ).reshape(1),
                u_loc=c["u_loc"] + nc_u32,
                depth=jnp.where(cont, c["depth"] + 1, c["depth"]),
                wchunk=c["wchunk"] + 1,
                waves=c["waves"] + 1,
                gen_lo=g.lo,
                gen_hi=g.hi,
                new=new,
                sent_lo=sent.lo,
                sent_hi=sent.hi,
                max_cand=max_cand,
                disc_found=disc_found,
                disc_lo=disc_lo,
                disc_hi=disc_hi,
                overflow=overflow,
                f_overflow=f_overflow,
                c_overflow=c_overflow,
                e_overflow=e_overflow,
                done=~cont,
            )

        def fold_discoveries(c, ex, fval_x):
            """This wave's property hits over the local frontier block,
            folded globally (the lowest hitting shard index wins,
            mirroring whichever racing thread lands first in the
            reference)."""
            if not n_props:
                return c["disc_found"], c["disc_lo"], c["disc_hi"]
            me = lax.axis_index("shard").astype(jnp.uint32)
            hits, los, his = wave_hits(props, ex, fval_x)
            ghits = bool_any(hits)
            pri = jnp.where(hits, me, jnp.uint32(S))
            winner = lax.pmin(pri, "shard")
            sel = hits & (pri == winner)
            g_lo = lax.psum(jnp.where(sel, los, jnp.uint32(0)), "shard")
            g_hi = lax.psum(jnp.where(sel, his, jnp.uint32(0)), "shard")
            fresh = ghits & ~c["disc_found"]
            return (
                c["disc_found"] | ghits,
                jnp.where(fresh, g_lo, c["disc_lo"]),
                jnp.where(fresh, g_hi, c["disc_hi"]),
            )

        def route_stage(c, R, k_lo, k_hi, cand_valid, n_cand, Bd,
                        payload):
            """Route+compact ``R`` candidate rows in ONE sort ordered by
            (owner, key): valid candidates form S contiguous
            destination runs (invalid rows carry owner=S and sort
            last); ``payload(s_row, s_lo, s_hi)`` builds the routed
            rows in that order, written into the ``route`` lane.
            Returns the stage's carry part, the local ``route_ovf``
            (a run above the per-destination cap ``Bd``) and the rows
            routed off-shard."""
            me = lax.axis_index("shard").astype(jnp.uint32)
            owner = jnp.where(
                cand_valid, k_lo % jnp.uint32(S), jnp.uint32(S)
            )
            rows = jnp.arange(R, dtype=jnp.uint32)
            s_owner, s_hi, s_lo, s_row = lax.sort(
                (owner, k_hi, k_lo, rows), num_keys=3
            )
            # s_owner is sorted: all destination-run boundaries in one
            # searchsorted pass.
            edges = jnp.searchsorted(
                s_owner, jnp.arange(S + 1, dtype=jnp.uint32)
            ).astype(jnp.uint32)
            starts = edges[:-1]
            counts = edges[1:] - starts
            route_ovf = jnp.any(counts > Bd)
            cross = n_cand - counts[me]
            g_cross = lax.psum(cross.astype(jnp.uint32), "shard")
            sent = u64_add(
                U64(c["sent_lo"], c["sent_hi"]),
                U64(g_cross, jnp.uint32(0)),
            )
            out = dict(
                route=lax.dynamic_update_slice(
                    c["route"], payload(s_row, s_lo, s_hi),
                    (jnp.uint32(0), jnp.uint32(0)),
                ),
                starts=starts,
                counts=counts,
                n_cand=n_cand.astype(jnp.uint32),
                sent_lo=sent.lo,
                sent_hi=sent.hi,
            )
            return out, route_ovf, cross.astype(jnp.uint32)

        def wave_expand(c):
            if target_depth is None:
                return jnp.bool_(True)
            return c["depth"] < target_depth

        def make_route(fc: int):
            """The dense send side at frontier class ``fc``: expansion,
            fingerprints, the routing sort and the routed payload,
            written into the ``route`` lane."""
            F_c, _NT, _T, R_src, _B_c, Bd_c = f_params[fc]

            def route(c):
                fval_c = c["fval"][:F_c]
                expand = wave_expand(c)
                # Dense expansion: one seam transpose of the class
                # prefix (step_vec is the row contract).
                frontier_rows = c["frontier"][:, :F_c].T
                ex = expand_frontier(
                    enc, props, evt_idx, frontier_rows, fval_c,
                    c["ebits"][:F_c], expand, with_repeats=False,
                    sym_spec=sym,
                )
                e_overflow = c["e_overflow"] | bool_any(
                    jnp.any(ex["trunc"])
                )
                cand_state, cand_valid = ex["flat"], ex["v"]
                n_cand = jnp.sum(cand_valid).astype(jnp.uint32)
                fp_flat = (canonicalize_rows(sym, cand_state, jnp)
                           if sym is not None else cand_state)
                k_lo, k_hi = fingerprint_u32v(fp_flat, jnp)
                k_lo, k_hi = clamp_keys(k_lo, k_hi)
                disc_found, disc_lo, disc_hi = fold_discoveries(
                    c, ex, fval_c
                )
                # Parent meta (ebits + parent fp) packs into the
                # payload as a broadcast: candidate // K is a K-fold
                # repeat, no gather. The routed payload is ONE gather
                # (PERF.md §gathers: TPU gathers cost ~12ns/row
                # regardless of lane count; the receive class's tiles
                # are then slices).
                fr_meta = jnp.stack(
                    [ex["ebits"]]
                    + ([ex["f_lo"], ex["f_hi"]] if track_paths else []),
                    axis=1,
                )
                pmeta = jnp.broadcast_to(
                    fr_meta[:, None, :], (F_c, K, fr_meta.shape[1]),
                ).reshape(R_src, fr_meta.shape[1])

                def payload(s_row, _s_lo, _s_hi):
                    return dest_tile_pack(
                        jnp, cand_state,
                        pmeta[:, 1:2] if track_paths else None,
                        pmeta[:, 2:3] if track_paths else None,
                        pmeta[:, 0:1], k_lo, k_hi,
                    )[s_row]

                out, route_ovf, cross = route_stage(
                    c, R_src, k_lo, k_hi, cand_valid, n_cand,
                    jnp.uint32(Bd_c), payload,
                )
                out.update(
                    disc_found=disc_found,
                    disc_lo=disc_lo,
                    disc_hi=disc_hi,
                    c_overflow=c["c_overflow"] | bool_any(route_ovf),
                    e_overflow=e_overflow,
                )
                if trace_log:
                    # Routing-stage lanes of the per-shard log: the
                    # candidate count (the dense path has no pairs,
                    # mirroring the single-chip convention) and the
                    # rows routed off-shard.
                    out.update(wv_pairs=n_cand, cross=cross,
                               pair_cap=jnp.uint32(0))
                return out

            return route

        def make_pairs(fc: int):
            """The sparse send side's frontier-class half at class
            ``fc``: properties, the enabled bits, the peel and the
            compaction of the live (pair, slot) indices into the dense
            prefix of the ``R_pair``-row pair buffer — the shared pair
            pipeline (checkers/tpu_sortmerge.py) with compaction
            forced. The frontier-row outputs (property bits, ebits,
            fingerprints, rows with enabled pairs) are written into
            F-row blocks at offset 0; rows past ``F_c`` are dead
            (``fval`` is False there)."""
            F_c, NT, T, _R_src, B_c, Bd_c = f_params[fc]

            def to_f(x):
                if F_c == F:
                    return x
                return lax.dynamic_update_slice(
                    jnp.zeros((F,) + x.shape[1:], x.dtype), x,
                    (jnp.uint32(0),) * x.ndim,
                )

            def pairs(c):
                cond, eb, fp_lo, fp_hi = frontier_props_t(
                    enc, props, evt_idx, c["frontier"][:, :F_c],
                    c["fval"][:F_c], c["ebits"][:F_c], sym_spec=sym,
                )
                (
                    pidx, live, pslot, cnt, n_pairs, pair_ovf, _tm,
                ) = sparse_pair_candidates(
                    # full resident buffer + explicit class width (a
                    # strided column-prefix slice as a loop operand
                    # would copy per wave — see the n_rows note on the
                    # shared pipeline)
                    enc, c["frontier"], c["fval"][:F_c], wave_expand(c),
                    EV=EV, B_p=B_c, NT=NT, T=T,
                    mask_budget_cells=self.mask_budget_cells,
                    Ba=R_pair, axis_name="shard", n_rows=F_c,
                    ample_words=ample_words, compact=True,
                )
                return dict(
                    pkey=jnp.where(live, pidx, jnp.uint32(_SENT)),
                    pslot=pslot,
                    n_pairs=n_pairs,
                    pair_ovf=pair_ovf,
                    dest_cap=jnp.uint32(Bd_c),
                    cond=to_f(cond),
                    ebits=to_f(eb),
                    f_lo=to_f(fp_lo),
                    f_hi=to_f(fp_hi),
                    has_succ=to_f(cnt > 0),
                )

            return pairs

        def make_pair_route(pc: int):
            """The sparse send side's pair-class half at pair class
            ``pc``: the first ``P_r`` rows of the pair buffer are
            stepped and fingerprinted, routed by the (owner, key) sort
            and their payload written into the ``route`` lane."""
            P_r = p_ladder[pc]
            # Memory-lean mode (mirrors the single-chip chunked path):
            # when the [P_r, W] successor tensor would blow the flat
            # budget, fingerprint pairs in chunks and RECOMPUTE the
            # routed tiles' successors in the receive class
            # (step_slot purity makes this exact).
            chunked = P_r * row_pad > self.flat_budget_bytes
            needs_scan = sparse_boundary or sparse_has_trunc

            def pair_route(args):
                c, fo = args
                pkey = fo["pkey"][:P_r]
                live = pkey != jnp.uint32(_SENT)
                pidx = jnp.where(live, pkey, jnp.uint32(0))
                pslot = fo["pslot"][:P_r]
                prow = pidx // jnp.uint32(EV)
                # Pair-state gather seam: the shared backend policy
                # (encoding.pair_step_seam), over the whole resident
                # frontier (this class does not know the frontier
                # class; prow < F_c).
                pair_states = make_pair_states(c["frontier"],
                                               c["frontier"])

                def eval_pairs(pidx_b, live_b, slot_b):
                    prow_b = pidx_b // jnp.uint32(EV)
                    succ_t, ptr_b, hard_b = step_cols(
                        pair_states(prow_b), slot_b
                    )
                    # hard trunc (unrepresentable successor, e.g. an
                    # un-harvested history transition) is raised
                    # regardless of the boundary — the garbage
                    # successor can't faithfully evaluate it.
                    eov = jnp.bool_(False)
                    if hard_b is not None:
                        eov = jnp.any(live_b & hard_b)
                        live_b = live_b & ~hard_b
                    if sparse_boundary:
                        ok = live_b & within_boundary_cols(enc, succ_t)
                    else:
                        ok = live_b
                    if ptr_b is not None:
                        eov = eov | jnp.any(ok & ptr_b)
                        ok = ok & ~ptr_b
                    if sym is not None:
                        # canonical key, concrete successor block
                        canon_t = canonicalize_t(sym, succ_t, jnp)
                        lo, hi = fingerprint_u32v_t(canon_t, jnp)
                    else:
                        lo, hi = fingerprint_u32v_t(succ_t, jnp)
                    lo, hi = clamp_keys(lo, hi)
                    return succ_t, lo, hi, ok, prow_b, eov

                row_ok = None
                if chunked:
                    NC = -(-(P_r * row_pad) // self.flat_budget_bytes)
                    Bc = -(-P_r // NC)
                    pad = NC * Bc - P_r
                    pidx_p = jnp.pad(pidx, (0, pad))
                    live_p = jnp.pad(live, (0, pad))
                    pslot_p = jnp.pad(pslot, (0, pad))

                    def fchunk(ti, acc):
                        kl, kh, pok, nc, eov, rok = acc
                        off = ti * Bc
                        pb = lax.dynamic_slice(pidx_p, (off,), (Bc,))
                        lb = lax.dynamic_slice(live_p, (off,), (Bc,))
                        sb = lax.dynamic_slice(pslot_p, (off,), (Bc,))
                        _, lo, hi, ok, prow_b, ev = eval_pairs(
                            pb, lb, sb
                        )
                        kl = lax.dynamic_update_slice(kl, lo, (off,))
                        kh = lax.dynamic_update_slice(kh, hi, (off,))
                        pok = lax.dynamic_update_slice(pok, ok, (off,))
                        if needs_scan:
                            nc = nc + jnp.sum(ok, dtype=jnp.uint32)
                            rok = rok.at[
                                jnp.where(ok, prow_b, jnp.uint32(F))
                            ].max(jnp.uint32(1), mode="drop")
                        return kl, kh, pok, nc, eov | ev, rok

                    def pv(x):
                        return lax.pcast(x, "shard", to="varying")

                    kl, kh, pok, nc_acc, eov, rok = lax.fori_loop(
                        0,
                        NC,
                        fchunk,
                        (
                            pv(jnp.zeros(NC * Bc, jnp.uint32)),
                            pv(jnp.zeros(NC * Bc, jnp.uint32)),
                            pv(jnp.zeros(NC * Bc, bool)),
                            pv(jnp.uint32(0)),
                            pv(jnp.bool_(False)),
                            pv(jnp.zeros(F if needs_scan else 1,
                                         jnp.uint32)),
                        ),
                    )
                    k_lo, k_hi, pair_ok = kl[:P_r], kh[:P_r], pok[:P_r]
                    if needs_scan:
                        row_ok, n_cand = rok, nc_acc
                    cand_state = None  # the receive class recomputes
                else:
                    (succ_t, k_lo, k_hi, pair_ok, _,
                     eov) = eval_pairs(pidx, live, pslot)
                    if needs_scan:
                        row_ok = jnp.zeros(F, jnp.uint32).at[
                            jnp.where(pair_ok, prow, jnp.uint32(F))
                        ].max(jnp.uint32(1), mode="drop")
                        n_cand = jnp.sum(pair_ok, dtype=jnp.uint32)
                    # Routed-tile staging is a gather seam: the
                    # successor block transposes back to rows once
                    # (PERF.md §layout — row-major gathers win).
                    cand_state = succ_t.T
                if not needs_scan:
                    n_cand = fo["n_pairs"]
                # Parent meta (ebits + parent fp) of each pair's
                # frontier row, one packed gather.
                pmeta = jnp.stack(
                    [fo["ebits"]]
                    + ([fo["f_lo"], fo["f_hi"]] if track_paths else []),
                    axis=1,
                )[prow]

                def payload(s_row, s_lo, s_hi):
                    if recompute:
                        # successors are never materialized at
                        # [P_r, W] here: the receive class recomputes
                        # its tiles' successors from (pair, slot)
                        return jnp.concatenate(
                            [
                                jnp.concatenate(
                                    [pidx[:, None], pslot[:, None],
                                     pmeta],
                                    axis=1,
                                )[s_row],
                                jnp.stack([s_lo, s_hi], axis=1),
                            ],
                            axis=1,
                        )
                    # The routed payload in ONE gather (PERF.md
                    # §gathers: ~12ns/row regardless of lane count).
                    return dest_tile_pack(
                        jnp, cand_state,
                        pmeta[:, 1:2] if track_paths else None,
                        pmeta[:, 2:3] if track_paths else None,
                        pmeta[:, 0:1], k_lo, k_hi,
                    )[s_row]

                out, route_ovf, cross = route_stage(
                    c, P_r, k_lo, k_hi, pair_ok, n_cand, fo["dest_cap"],
                    payload,
                )
                out.update(route_ovf=bool_any(route_ovf),
                           eov=bool_any(eov))
                if needs_scan:
                    out.update(has_succ=row_ok != 0)
                if trace_log:
                    out.update(cross=cross, pair_cap=jnp.uint32(P_r))
                return out

            return pair_route

        def send_sparse(c, f_class):
            """The sparse send side: the frontier-class switch, the
            pair class agreed by every shard (the smallest rung that
            holds the largest shard's pair count), the pair-class
            switch, then the wave's discoveries and overflow flags
            outside both switches."""
            fo = lax.switch(
                f_class, [make_pairs(fc) for fc in range(len(f_ladder))],
                c,
            )
            p_class = jnp.asarray(
                class_of(lax.pmax(fo["n_pairs"], "shard"),
                                p_ladder),
                jnp.int32,
            )
            pr = lax.switch(
                p_class,
                [make_pair_route(pc) for pc in range(len(p_ladder))],
                (c, fo),
            )
            has_succ = pr.pop("has_succ", fo["has_succ"])
            terminal = c["fval"] & ~has_succ & wave_expand(c)
            ex = dict(
                cond=fo["cond"], ebits=fo["ebits"],
                evt_cex=terminal & (fo["ebits"] != 0),
                f_lo=fo["f_lo"], f_hi=fo["f_hi"],
            )
            disc_found, disc_lo, disc_hi = fold_discoveries(
                c, ex, c["fval"]
            )
            route_ovf, eov = pr.pop("route_ovf"), pr.pop("eov")
            rt = dict(
                pr,
                disc_found=disc_found,
                disc_lo=disc_lo,
                disc_hi=disc_hi,
                c_overflow=c["c_overflow"] | bool_any(fo["pair_ovf"])
                | route_ovf,
                e_overflow=c["e_overflow"] | eov,
            )
            if trace_log:
                # the local enabled-pair popcount: the quantity the
                # global log can't see
                rt.update(wv_pairs=fo["n_pairs"].astype(jnp.uint32))
            return rt, p_class

        def make_recv(rc: int, v_class):
            """The receive side at receive class ``rc``: per-destination
            tiles of ``Bd_r`` rows sliced from the ``route`` lane, the
            ``all_to_all``, and the owner-local merge at ``S * Bd_r``
            rows."""
            Bd_r = r_ladder[rc]

            def recv_side(args):
                c, rt = args
                route = rt["route"]
                starts, counts = rt["starts"], rt["counts"]
                if recompute:
                    pair_states = make_pair_states(
                        c["frontier"], c["frontier"]
                    )
                pos = jnp.arange(Bd_r, dtype=jnp.uint32)

                def dest_tile(d):
                    # The slice start is clamped to keep the tile inside
                    # the lane, so a run near its end lands ``skip``
                    # rows into the tile; its rows keep their order,
                    # and so the stable winner order of the merge.
                    start = jnp.minimum(
                        starts[d], jnp.uint32(R_route - Bd_r)
                    )
                    skip = starts[d] - start
                    tile = lax.dynamic_slice(
                        route, (start, jnp.uint32(0)),
                        (Bd_r, route_lanes),
                    )
                    if recompute:
                        succ_t, _, _ = step_cols(
                            pair_states(tile[:, 0] // jnp.uint32(EV)),
                            tile[:, 1],
                        )
                        m = 2 + n_meta
                        tile = dest_tile_pack(
                            jnp, succ_t.T,
                            tile[:, 3:4] if track_paths else None,
                            tile[:, 4:5] if track_paths else None,
                            tile[:, 2:3], tile[:, m:m + 1],
                            tile[:, m + 1:m + 2],
                        )
                    live = (pos >= skip) & (pos < skip + counts[d])
                    return jnp.where(live[:, None], tile, jnp.uint32(0))

                send = jnp.concatenate(
                    [dest_tile(d) for d in range(S)], axis=0
                )
                recv = lax.all_to_all(
                    send, "shard", split_axis=0, concat_axis=0,
                    tiled=True,
                )
                shard_log = None
                if trace_log:
                    # The receive stage's lanes of the per-shard log:
                    # the peak destination run and the tile it
                    # travelled in (the receive class).
                    shard_log = (
                        rt["wv_pairs"], rt["cross"], jnp.max(counts),
                        jnp.uint32(Bd_r), rt["pair_cap"],
                    )
                return merge_stage(
                    c, v_class, S * Bd_r, recv, rt["n_cand"],
                    U64(rt["sent_lo"], rt["sent_hi"]),
                    (rt["disc_found"], rt["disc_lo"], rt["disc_hi"]),
                    (c["overflow"], c["f_overflow"],
                     rt["c_overflow"], rt["e_overflow"]),
                    shard_log=shard_log,
                )

            return recv_side

        # Memory ledger (stateright_tpu/memplan.py): per-ladder-class
        # staging rows, PER SHARD (the shard_map body's view), from
        # the same _f_class_params the wave programs compile from.
        # Sparse classes price the pair buffer and the stepped rows at
        # the largest pair class a wave of the frontier class can take
        # (its candidate budget B_c); the chunked memory-lean gate
        # mirrors make_pair_route's (P_r rows at the padded ~512 B/row
        # cost vs the flat budget), and chunked classes land an
        # ``engine_mode`` record like the single-chip engine's. The
        # tiles are priced at the largest receive class a wave of the
        # frontier class can take.
        from ..memplan import buffer_entry, plan_total

        from ..ops.bitmask import mask_words as _mask_words

        _classes = []
        _modes = []
        for fc in range(len(f_ladder)):
            F_c, NT_c, _T_c, R_src, B_c, Bd_c = f_params[fc]
            Bd_hi = r_ladder[class_of(Bd_c, r_ladder)]
            R_step = (p_ladder[class_of(B_c, p_ladder)]
                      if use_sparse else R_src)
            staging = [
                buffer_entry("cand_keys", (2, R_step), "uint32"),
                buffer_entry("send_tiles", (S * Bd_hi, E + 2),
                             "uint32"),
                buffer_entry("recv_tiles", (S * Bd_hi, E + 2),
                             "uint32"),
            ]
            chunked_c = False
            if use_sparse:
                staging.insert(0, buffer_entry(
                    "enabled_bits", (F_c, _mask_words(K)), "uint32"
                ))
                staging.insert(1, buffer_entry(
                    "pair_buffer", (2, R_pair), "uint32"
                ))
                chunked_c = R_step * row_pad > self.flat_budget_bytes
                if chunked_c:
                    NC_c = -(-(R_step * row_pad)
                             // self.flat_budget_bytes)
                    Bc_c = -(-R_step // NC_c)
                    staging.append(buffer_entry(
                        "succ_chunk", (W, Bc_c), "uint32"
                    ))
                    _modes.append(dict(
                        engine=type(self).__name__, mode="chunked",
                        f_class=fc, buffer_rows=R_step, chunks=NC_c,
                        chunk_rows=Bc_c, row_pad_bytes=row_pad,
                        flat_budget_bytes=self.flat_budget_bytes,
                    ))
                else:
                    staging.append(buffer_entry(
                        "succ_t", (W, R_step), "uint32"
                    ))
            else:
                staging.insert(0, buffer_entry(
                    "succ_flat", (F_c * K, W), "uint32"
                ))
            _classes.append(dict(
                f_class=fc,
                mode=("chunked" if chunked_c
                      else "sparse" if use_sparse else "dense"),
                frontier_rows=F_c, budget_rows=B_c, tiles=NT_c,
                buffer_rows=R_src, step_rows=R_step, dest_cap=Bd_c,
                staging=staging, staging_bytes=plan_total(staging),
            ))
        from ..memplan import v_class_entries

        self._build_info = dict(
            classes=_classes,
            v_classes=v_class_entries(v_ladder, min(F, S * r_ladder[-1])),
            pair_classes=p_ladder,
            recv_classes=r_ladder,
            engine_modes=_modes,
        )

        def body(c):
            n_max = lax.pmax(c["n_loc"][0], "shard")
            # tiered runs dispatch the v-ladder on the HOT count (the
            # rows actually resident per shard), pmax-agreed like
            # every class decision
            u_max = lax.pmax(
                c["h_loc"][0] if tier_mode else c["u_loc"][0], "shard"
            )
            f_class = jnp.int32(0)
            for F_i in f_ladder[:-1]:
                f_class = f_class + (
                    n_max > jnp.uint32(F_i)
                ).astype(jnp.int32)
            v_class = jnp.int32(0)
            for V_i in v_ladder[:-1]:
                v_class = v_class + (
                    u_max > jnp.uint32(V_i)
                ).astype(jnp.int32)
            if trace_log:
                n_tot = lax.psum(c["n_loc"][0], "shard")
            ci = {k: v for k, v in c.items()
                  if k not in ("wlog", "slog", "pstash")}
            if use_sparse:
                rt, p_class = send_sparse(ci, f_class)
            else:
                rt = lax.switch(
                    f_class,
                    [make_route(fc) for fc in range(len(f_ladder))],
                    ci,
                )
            # The receive class, agreed like the others: the smallest
            # rung holding every shard's largest destination run.
            r_class = jnp.asarray(
                class_of(
                    lax.pmax(jnp.max(rt["counts"]), "shard"), r_ladder
                ),
                jnp.int32,
            )
            cr = {k: v for k, v in ci.items() if k != "route"}
            c2 = lax.switch(
                r_class,
                [make_recv(rc, v_class) for rc in range(len(r_ladder))],
                (cr, rt),
            )
            c2 = dict(
                c2,
                route=rt["route"],
                recv_waves=c["recv_waves"] + (
                    jnp.arange(len(r_ladder)) == r_class
                ).astype(jnp.uint32),
            )
            if use_sparse:
                c2["pair_waves"] = c["pair_waves"] + (
                    jnp.arange(len(p_ladder)) == p_class
                ).astype(jnp.uint32)
            if trace_log and tier_mode:
                # the wave-log/shard-log rows can't be written yet —
                # the confirmed counts settle at the NEXT dispatch's
                # commit; stash the wave-time lanes for it (lane 1 is
                # 0 at the global level, as untiered: the tracer
                # back-fills enabled pairs from the shard rows)
                c2 = dict(
                    c2,
                    wlog=c["wlog"],
                    slog=c["slog"],
                    pstash=jnp.stack(
                        [
                            n_tot,
                            jnp.uint32(0),
                            c2["gen_lo"] - c["gen_lo"],
                            c["depth"].astype(jnp.uint32),
                            f_class.astype(jnp.uint32),
                            v_class.astype(jnp.uint32),
                            jnp.uint32(0),
                            jnp.uint32(0),
                        ]
                    ),
                )
                return c2
            if tier_mode:
                return c2
            if trace_log:
                # Every lane here is replicated (psum/pmax results and
                # the engine's replicated run counters), so the log
                # matches the stats' P() out-spec.
                row = jnp.stack(
                    [
                        n_tot,
                        jnp.uint32(0),  # enabled pairs: not visible
                        c2["gen_lo"] - c["gen_lo"],
                        c2["new"] - c["new"],
                        c2["new"],
                        c["depth"].astype(jnp.uint32),
                        f_class.astype(jnp.uint32),
                        v_class.astype(jnp.uint32),
                    ]
                )
                c2 = dict(
                    c2,
                    wlog=lax.dynamic_update_slice(
                        c["wlog"], row[None, :],
                        (c["wchunk"], jnp.int32(0)),
                    ),
                    # the per-shard row merge_stage assembled inside
                    # the wave switch (shard-varying, never psum'd)
                    slog=lax.dynamic_update_slice(
                        c["slog"], c2["swave"][None, :],
                        (c["wchunk"], jnp.int32(0)),
                    ),
                )
            return c2

        # Tiered dispatches run exactly ONE wave: the commit phase
        # needs the host's membership verdict between waves.
        wps_eff = 1 if tier_mode else waves_per_sync

        def cond(c):
            return ~c["done"] & (c["wchunk"] < wps_eff)

        def pack_stats(c):
            frontier_total = lax.psum(
                jnp.sum(c["fval"]).astype(jnp.uint32), "shard"
            )
            scalars = jnp.stack(
                [
                    c["done"].astype(jnp.uint32),
                    c["overflow"].astype(jnp.uint32),
                    c["f_overflow"].astype(jnp.uint32),
                    c["depth"].astype(jnp.uint32),
                    c["waves"],
                    frontier_total,
                    c["gen_lo"],
                    c["gen_hi"],
                    c["new"],
                    c["c_overflow"].astype(jnp.uint32),
                    c["e_overflow"].astype(jnp.uint32),
                ]
            )
            parts = [
                scalars,
                c["disc_found"].astype(jnp.uint32),
                c["disc_lo"],
                c["disc_hi"],
                jnp.stack(
                    [c["sent_lo"], c["sent_hi"], c["max_cand"]]
                ),
            ]
            if trace_log:
                parts.append(c["wlog"].reshape(-1))
            # waves per pair class, then per receive class, smallest
            # first: the last lanes (_consume_extra_stats)
            if use_sparse:
                parts.append(c["pair_waves"])
            parts.append(c["recv_waves"])
            stats = jnp.concatenate(parts)
            if trace_log:
                # The per-shard mesh log returns as a SECOND stats
                # output, sharded along the device axis (the packed
                # stats stay replicated) — same dispatch, same sync.
                return c, stats, c["slog"].reshape(-1)
            return c, stats

        def chunk(carry):
            c = dict(carry, wchunk=jnp.int32(0))
            c = lax.while_loop(cond, body, c)
            return pack_stats(c)

        P_shard = P("shard")
        specs = dict(
            **(
                dict(wlog=P(), slog=P("shard", None), swave=P_shard)
                if trace_log else {}
            ),
            # SoA resident buffers shard along their ROW axis (axis 1
            # of the [lanes, rows] layout).
            vkeys=P(None, "shard"),
            plog=P(None, "shard"),
            pl_n=P_shard,
            route=P("shard", None),
            recv_waves=P(),
            **(dict(pair_waves=P()) if use_sparse else {}),
            frontier=P(None, "shard"),
            fval=P_shard,
            ebits=P_shard,
            n_loc=P_shard,
            u_loc=P_shard,
            depth=P(),
            wchunk=P(),
            waves=P(),
            gen_lo=P(),
            gen_hi=P(),
            new=P(),
            sent_lo=P(),
            sent_hi=P(),
            max_cand=P(),
            disc_found=P(),
            disc_lo=P(),
            disc_hi=P(),
            overflow=P(),
            f_overflow=P(),
            c_overflow=P(),
            e_overflow=P(),
            done=P(),
        )

        if tier_mode:
            # -- the tiered chunk program (stateright_tpu/tier.py) -------
            specs_t = dict(specs)
            specs_t["pend_keys"] = P(None, "shard")
            if track_paths:
                specs_t["pend_par"] = P(None, "shard")
            specs_t["pend_n"] = P_shard
            specs_t["pend_valid"] = P()
            specs_t["h_loc"] = P_shard
            if trace_log:
                specs_t["pstash"] = P()

            def tier_commit(c, keep):
                """Commit the previous wave's survivors, shard-local,
                under the host's per-shard ``keep`` mask — the mirror
                of the single-chip commit with the global verdicts
                (cont/done/new) psum-agreed like every other
                termination decision."""
                pv = c["pend_valid"]
                rowsF = jnp.arange(F, dtype=jnp.uint32)
                keepm = keep & (rowsF < c["pend_n"][0])
                conf = jnp.sum(keepm).astype(jnp.uint32)
                drop = jnp.where(keepm, jnp.uint32(0), jnp.uint32(1))
                _, perm = lax.sort((drop, rowsF), num_keys=1)
                confv = rowsF < conf
                front_c = jnp.where(
                    confv[None, :], c["frontier"][:, perm],
                    jnp.uint32(0),
                )
                eb_c = jnp.where(
                    confv, c["ebits"][perm], jnp.uint32(0)
                )
                k_lo = jnp.where(
                    confv, c["pend_keys"][0][perm], jnp.uint32(_SENT)
                )
                k_hi = jnp.where(
                    confv, c["pend_keys"][1][perm], jnp.uint32(_SENT)
                )

                h_max = lax.pmax(c["h_loc"][0], "shard")
                v_class = jnp.int32(0)
                for V_i in v_ladder[:-1]:
                    v_class = v_class + (
                        h_max > jnp.uint32(V_i)
                    ).astype(jnp.int32)

                def app(vc):
                    V_v = v_ladder[vc]

                    def br(_):
                        m_lo, m_hi = merge_sorted(
                            c["vkeys"][0, :V_v], c["vkeys"][1, :V_v],
                            k_lo, k_hi, impl=self.merge_impl,
                        )
                        return lax.dynamic_update_slice(
                            c["vkeys"],
                            jnp.stack([m_lo, m_hi]),
                            (jnp.uint32(0), jnp.uint32(0)),
                        )

                    return br

                vkeys_m = lax.switch(
                    v_class,
                    [app(vc) for vc in range(len(v_ladder))], 0,
                )

                def sel(a, b):
                    return jnp.where(pv, a, b)

                conf_g = lax.psum(conf, "shard")
                confp = jnp.where(pv, conf, jnp.uint32(0))
                confp_g = jnp.where(pv, conf_g, jnp.uint32(0))
                new2 = c["new"] + confp_g
                h_loc2 = c["h_loc"] + confp.reshape(1)
                u_loc2 = c["u_loc"] + confp.reshape(1)
                all_disc = (
                    jnp.all(c["disc_found"]) if n_props
                    else jnp.bool_(False)
                )
                if target_states is None:
                    target_hit = jnp.bool_(False)
                else:
                    target_hit = new2 >= jnp.uint32(target_states)
                overflow = c["overflow"] | (
                    pv & bool_any(h_loc2[0] > jnp.uint32(C))
                )
                cont = (
                    pv & (conf_g > 0) & ~all_disc & ~target_hit
                    & ~overflow & ~c["f_overflow"]
                    & ~c["c_overflow"] & ~c["e_overflow"]
                )
                out = dict(
                    c,
                    vkeys=sel(vkeys_m, c["vkeys"]),
                    frontier=sel(front_c, c["frontier"]),
                    ebits=sel(eb_c, c["ebits"]),
                    fval=sel(confv & cont, c["fval"]),
                    n_loc=sel(conf.reshape(1), c["n_loc"]),
                    h_loc=h_loc2,
                    u_loc=u_loc2,
                    new=new2,
                    depth=jnp.where(cont, c["depth"] + 1, c["depth"]),
                    waves=c["waves"] + jnp.where(
                        pv, jnp.uint32(1), jnp.uint32(0)
                    ),
                    overflow=overflow,
                    done=sel(~cont, c["done"]),
                    pend_valid=jnp.bool_(False),
                    # per-shard lane: varying, like the wave body's
                    pend_n=lax.pcast(
                        jnp.zeros(1, jnp.uint32), "shard", to="varying"
                    ),
                )
                if track_paths:
                    p_lo = jnp.where(
                        confv, c["pend_par"][0][perm], jnp.uint32(0)
                    )
                    p_hi = jnp.where(
                        confv, c["pend_par"][1][perm], jnp.uint32(0)
                    )
                    rows4 = jnp.stack([
                        p_lo,
                        p_hi,
                        jnp.where(confv, k_lo, jnp.uint32(0)),
                        jnp.where(confv, k_hi, jnp.uint32(0)),
                    ])
                    plog2 = lax.dynamic_update_slice(
                        c["plog"], rows4, (jnp.uint32(0), c["pl_n"][0])
                    )
                    out["plog"] = sel(plog2, c["plog"])
                    out["pl_n"] = c["pl_n"] + confp.reshape(1)
                if trace_log:
                    st = c["pstash"]
                    row = jnp.stack([
                        st[0], st[1], st[2], conf_g, new2,
                        st[3], st[4], st[5],
                    ])
                    out["wlog"] = lax.dynamic_update_slice(
                        c["wlog"], row[None, :],
                        (jnp.int32(0), jnp.int32(0)),
                    )
                    # patch the stashed per-shard row's confirmed
                    # lanes (7 = post-dedup new, 8 = cumulative
                    # per-shard visited) before the slog write
                    sw = jnp.concatenate([
                        c["swave"][:7],
                        jnp.stack([conf, u_loc2[0]]),
                        c["swave"][9:],
                    ])
                    out["slog"] = lax.dynamic_update_slice(
                        c["slog"], sw[None, :],
                        (jnp.int32(0), jnp.int32(0)),
                    )
                return out

            def tier_chunk(carry, keep):
                c = dict(carry, wchunk=jnp.int32(0))
                c = tier_commit(c, keep)
                c = lax.while_loop(cond, body, c)
                return pack_stats(c)

            self._tier_pspecs = dict(specs_t)
            chunk_out_t = (
                (specs_t, P(), P_shard) if trace_log
                else (specs_t, P())
            )
            tier_sm = shard_map(
                tier_chunk, mesh=mesh,
                in_specs=(specs_t, P_shard), out_specs=chunk_out_t,
            )
            return jax.jit(tier_sm, donate_argnums=0)

        # Checkpoint/resume (stateright_tpu/checkpoint.py): a resumed
        # run places its snapshot buffers with these exact shardings —
        # kept beside the programs (rides the program cache via
        # _lookup_programs) so restore and carry layout can't drift.
        self._carry_pspecs = dict(specs)
        chunk_out = (
            (specs, P(), P_shard) if trace_log else (specs, P())
        )
        seed_sm = shard_map(
            seed_local, mesh=mesh, in_specs=P(), out_specs=specs,
        )
        chunk_sm = shard_map(
            chunk, mesh=mesh, in_specs=(specs,), out_specs=chunk_out,
        )
        # Tooling hook (analysis/lint.py): the shard_map-wrapped wave
        # body, re-traceable on the GLOBAL carry shapes — the sharded
        # analog of the single-chip engine's ``_wave_body`` (the lint's
        # sharded wave-body fixture prices the per-shard log path).
        self._wave_body_sm = shard_map(
            body, mesh=mesh, in_specs=(specs,), out_specs=specs,
        )
        return jax.jit(seed_sm), jax.jit(chunk_sm, donate_argnums=0)

    # -- reconstruction ----------------------------------------------------

    def _capture_final(self, carry) -> None:
        self._final_tables = (
            carry["vkeys"],
            carry["plog"],
            carry["pl_n"],
            carry["u_loc"],
        )

    def _build_generated(self):
        """Concatenate each shard's append-only parent log. The SoA
        buffers come back concatenated along their sharded ROW axis
        ([2, S*C_pad] / [4, S*L]); ``pl_n[s]`` entries of shard ``s``
        are live. The log carries BOTH key pairs (round 10): parent
        limbs in lanes 0-1, child limbs in lanes 2-3 — the
        incrementally-sorted visited array re-orders its rows every
        wave, so the round-9 positional child derivation is gone."""
        if self.generated is None:
            tier = self._tier_generated_map()
            if tier is not None:
                # tiered runs drain the log host-side per dispatch
                # (stateright_tpu/tier.py)
                self.generated = tier
                return self.generated
            _vkeys, plog, pl_n, _u_loc = (
                np.asarray(a) for a in self._final_tables
            )
            S = self.n_shards
            L = plog.shape[1] // S
            generated: dict = {}
            for s in range(S):
                n = int(pl_n[s])
                psl = slice(s * L, s * L + n)
                child = (
                    plog[3, psl].astype(np.uint64) << np.uint64(32)
                ) | plog[2, psl].astype(np.uint64)
                parent = (
                    plog[1, psl].astype(np.uint64) << np.uint64(32)
                ) | plog[0, psl].astype(np.uint64)
                for ch, pa in zip(child.tolist(), parent.tolist()):
                    generated[int(ch)] = int(pa) if pa else None
            self.generated = generated
        return self.generated
