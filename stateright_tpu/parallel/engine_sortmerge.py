"""The sharded SORT-MERGE wave engine: multi-chip BFS on the fast path.

Round 2 shipped two engines pulling in opposite directions: the
single-chip sort-merge engine (checkers/tpu_sortmerge.py) — dedup via
``lax.sort`` merges, ~10x faster on chip than scatter-based open
addressing — and a sharded engine (parallel/engine.py) whose
owner-local dedup still used the hash-table scatters. This module
closes that gap (VERDICT r2 item 4): the scale-out path now runs the
same sorted-visited-array dedup the repo benchmarks and recommends.

Per wave, inside one ``shard_map``-wrapped ``lax.while_loop``:

1. each shard expands its local frontier block (vmap step → property
   bitmaps → fingerprints),
2. a 4-lane ``lax.sort`` keyed ``(owner, fp_hi, fp_lo)`` groups valid
   candidates by destination shard — routing and compaction in ONE
   sort, no per-destination scatters (the job_market.rs:66-147
   replacement, with the communication pattern chosen for ICI),
3. each destination's contiguous run is sliced into its fixed-size
   tile of the send buffer (``dynamic_slice`` at the run offset —
   contiguous copies, never scatters) and one ``lax.all_to_all`` swaps
   tiles so every candidate lands on the shard owning
   ``fp_lo % n_shards``,
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

**Adaptive classes (round 4).** Round 3's sharded waves compiled ONE
worst-case shape, re-importing the flat-wave cost profile whose
single-chip version caused the round-2 rm=8 cliff. Waves now dispatch
through the same frontier/visited class ladders as the single-chip
engine — every shard agrees on the class via ``lax.pmax`` over local
frontier/unique counts (collectives are collective: the ``lax.switch``
must take the same branch on every shard) — and the routing sort,
per-destination tiles, the ``all_to_all`` itself, and the merge all
scale with the running wave. Encodings implementing
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


def dest_tile_width(w: int, track_paths: bool) -> int:
    """Lanes of a routed destination tile (see dest_tile_pack)."""
    return (w + 3 if track_paths else w + 1) + 2


def dest_tile_pack(jnp, state, par_lo, par_hi, ebits, key_lo, key_hi):
    """THE sharded routed-tile lane layout: ``[state 0:W | par_lo W |
    par_hi W+1 (paths only) | ebits E-1 | key_lo E | key_hi E+1]``
    with ``E = W+3`` (paths) or ``W+1`` — every ``dest_block`` variant
    packs through this helper, and ``merge_stage`` unpacks by the same
    offsets (``recv[:, E]``/``recv[:, EB]``), so the tile layout can't
    drift between the three pack sites and the post-shuffle merge.
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

    def _wave_log_pairs_valid(self) -> bool:
        # The sharded GLOBAL log wrapper can't see the enabled-pair
        # popcount (it lives inside the per-shard wave switch): lane 1
        # is 0. The per-shard mesh log DOES see it (swave lane 1), so
        # the tracer back-fills the wave event from the shard sum
        # instead of recording enabled_pairs=null.
        return False

    def _plan_sharded_names(self) -> tuple:
        # Mirrors the shard_map out_specs in _build_programs: these
        # carry leaves shard along their row axis, so their ledger
        # rows report per_shard_bytes = bytes / n_shards.
        return ("vkeys", "plog", "pl_n", "frontier", "fval", "ebits",
                "n_loc", "u_loc", "slog", "swave")

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
            # sorted arrays work to exactly 100%: shard_balance's
            # occupancy watch uses the exact-capacity headroom
            # threshold (stateright_tpu/occupancy.py)
            visited_exact=True,
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

    def _build_programs(self, n0: int, tiered: bool = False):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        from ..checkers.tpu import frontier_props_t
        from ..checkers.tpu_sortmerge import (
            _divisor_at_least,
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
        # XLA:CPU keeps its own gather arrangement in the tile build
        # (see dest_block below).
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
        B_user = min(self.cand_capacity or F * K, F * K)
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
        #   fill vs the lossless Bd cap, local post-dedup new, local
        #   visited count) is assembled INSIDE the wave switch (where
        #   those quantities exist) as the ``swave`` carry lane, and
        #   the body wrapper appends it to ``slog``. Both logs ride
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

        def class_params(fc: int):
            """Static per-frontier-class shapes (per shard)."""
            F_c = f_ladder[fc]
            if use_sparse:
                NPg = F_c * EV
                B_c = min(B_user, NPg)
                compaction = NPg > B_c
                want = -(-NPg // self.tile_rows)
                if compaction:
                    # Keep the packed-append headroom ≤ B_c/4 (see
                    # the single-chip engine's make_sparse_wave).
                    want = max(want, -(-(4 * NPg) // max(B_c, 1)))
                NT = _divisor_at_least(F_c, want) if compaction else 1
                T = F_c // NT
                R_src = (B_c + T * EV) if compaction else NPg
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
            return F_c, NT, T, R_src, B_c, Bd_c

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
            ``(enabled_pairs, routed_rows, dest_fill_peak, dest_cap)``
            per-shard scalars from the routing stage; this stage adds
            the quantities it owns (received rows, post-dedup new,
            the visited total) and returns the assembled
            ``swave: uint32[SHARD_LOG_LANES]`` row in the carry — 36
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
            if R_c < F:
                nf_pos = jnp.concatenate(
                    [nf_pos, jnp.full(F - R_c, _SENT, jnp.uint32)]
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
            nf_row = jnp.where(nf_valid, nf_pos - 1, jnp.uint32(0))
            next_fe = recv[nf_row]
            # The winners come off the routed-tile row gather; one
            # seam transpose feeds the [W, F] resident frontier.
            next_frontier = jnp.where(
                nf_valid[:, None], next_fe[:, :W], jnp.uint32(0)
            ).T
            next_ebits = jnp.where(nf_valid, next_fe[:, EB], 0)

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
                    pend_par=jnp.stack([
                        jnp.where(nf_valid, next_fe[:, W], 0),
                        jnp.where(nf_valid, next_fe[:, W + 1], 0),
                    ])
                )
            elif track_paths:
                # Parent AND child limbs (round 10): the sorted merge
                # re-orders vkeys rows every wave, so the round-9
                # positional child derivation is gone — the log is
                # the insertion-order record again (_build_generated).
                plog_new = lax.dynamic_update_slice(
                    c["plog"],
                    jnp.stack([
                        jnp.where(nf_valid, next_fe[:, W], 0),
                        jnp.where(nf_valid, next_fe[:, W + 1], 0),
                        jnp.where(nf_valid, next_fe[:, E], 0),
                        jnp.where(nf_valid, next_fe[:, E + 1], 0),
                    ]),
                    (jnp.uint32(0), c["pl_n"][0]),
                )
                # Clamp to the F rows the block write actually wrote
                # (on an f_overflow wave new_count can exceed F; _run
                # raises before reconstruction, but the live-count
                # invariant should hold regardless).
                pl_n = c["pl_n"] + jnp.minimum(
                    new_count.astype(jnp.uint32), jnp.uint32(F)
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
                    wv_pairs, cross_rows, fill_peak, dest_cap = \
                        shard_log
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
                wv_pairs, cross_rows, fill_peak, dest_cap = shard_log
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

        def make_wave(fc: int, v_class):
            F_c, NT, T, R_src, B_c, Bd_c = class_params(fc)
            R_c = S * Bd_c

            def wave(c):
                frontier_t = c["frontier"][:, :F_c]
                fval_c = c["fval"][:F_c]
                ebits_c = c["ebits"][:F_c]
                me = lax.axis_index("shard").astype(jnp.uint32)

                if target_depth is None:
                    expand = jnp.bool_(True)
                else:
                    expand = c["depth"] < target_depth

                e_overflow = c["e_overflow"]
                c_overflow = c["c_overflow"]

                if use_sparse:
                    # Sparse action dispatch, shard-local: the shared
                    # pair pipeline (checkers/tpu_sortmerge.py), then
                    # per-pair transitions — only real candidates
                    # enter the routing sort and the shuffle.
                    cond, eb, fp_lo, fp_hi = frontier_props_t(
                        enc, props, evt_idx, frontier_t, fval_c,
                        ebits_c, sym_spec=sym,
                    )
                    (
                        pidx, live, pslot, cnt, n_pairs, pair_ovf, _tm,
                    ) = sparse_pair_candidates(
                        # full resident buffer + explicit class width
                        # (a strided column-prefix slice as a loop
                        # operand would copy per wave — see the
                        # n_rows note on the shared pipeline)
                        enc, c["frontier"], fval_c, expand,
                        EV=EV, B_p=B_c, NT=NT, T=T,
                        mask_budget_cells=self.mask_budget_cells,
                        Ba=R_src, axis_name="shard", n_rows=F_c,
                        ample_words=ample_words,
                    )
                    # Pair-state gather seam: the shared backend
                    # policy (encoding.pair_step_seam).
                    pair_states = make_pair_states(c["frontier"],
                                                   frontier_t)
                    c_overflow = c_overflow | bool_any(pair_ovf)
                    prow = pidx // jnp.uint32(EV)
                    needs_scan = sparse_boundary or sparse_has_trunc

                    def eval_pairs(pidx_b, live_b, slot_b):
                        prow_b = pidx_b // jnp.uint32(EV)
                        succ_t, ptr_b, hard_b = step_cols(
                            pair_states(prow_b), slot_b
                        )
                        # hard trunc (unrepresentable successor, e.g.
                        # an un-harvested history transition) is raised
                        # regardless of the boundary — the garbage
                        # successor can't faithfully evaluate it.
                        eov = jnp.bool_(False)
                        if hard_b is not None:
                            eov = jnp.any(live_b & hard_b)
                            live_b = live_b & ~hard_b
                        if sparse_boundary:
                            inb = within_boundary_cols(enc, succ_t)
                            ok = live_b & inb
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

                    # Memory-lean mode (mirrors the single-chip chunked
                    # path): when the [R_src, W] successor tensor would
                    # blow the flat budget, fingerprint pairs in chunks
                    # and RECOMPUTE the routed tiles' successors inside
                    # dest_tile (step_slot purity makes this exact).
                    row_pad = -(-W // 128) * 512
                    chunked = (
                        R_src * row_pad > self.flat_budget_bytes
                    )
                    if chunked:
                        NC = -(-(R_src * row_pad)
                               // self.flat_budget_bytes)
                        Bc = -(-R_src // NC)
                        pad = NC * Bc - R_src
                        pidx_p = jnp.pad(pidx, (0, pad))
                        live_p = jnp.pad(live, (0, pad))
                        pslot_p = jnp.pad(pslot, (0, pad))

                        def fchunk(ti, acc):
                            kl, kh, pok, nc, eov, rok = acc
                            off = ti * Bc
                            pb = lax.dynamic_slice(pidx_p, (off,), (Bc,))
                            lb = lax.dynamic_slice(live_p, (off,), (Bc,))
                            sb = lax.dynamic_slice(
                                pslot_p, (off,), (Bc,)
                            )
                            _, lo, hi, ok, prow_b, ev = eval_pairs(
                                pb, lb, sb
                            )
                            kl = lax.dynamic_update_slice(kl, lo, (off,))
                            kh = lax.dynamic_update_slice(kh, hi, (off,))
                            pok = lax.dynamic_update_slice(
                                pok, ok, (off,)
                            )
                            if needs_scan:
                                nc = nc + jnp.sum(ok, dtype=jnp.uint32)
                                rok = rok.at[
                                    jnp.where(
                                        ok, prow_b, jnp.uint32(F_c)
                                    )
                                ].max(jnp.uint32(1), mode="drop")
                            return kl, kh, pok, nc, eov | ev, rok

                        def pv(x):
                            return lax.pcast(x, "shard", to="varying")

                        kl, kh, pok, nc_acc, eov_acc, row_ok = (
                            lax.fori_loop(
                                0,
                                NC,
                                fchunk,
                                (
                                    pv(jnp.zeros(NC * Bc, jnp.uint32)),
                                    pv(jnp.zeros(NC * Bc, jnp.uint32)),
                                    pv(jnp.zeros(NC * Bc, bool)),
                                    pv(jnp.uint32(0)),
                                    pv(jnp.bool_(False)),
                                    pv(jnp.zeros(
                                        F_c if needs_scan else 1,
                                        jnp.uint32,
                                    )),
                                ),
                            )
                        )
                        k_lo = kl[:R_src]
                        k_hi = kh[:R_src]
                        pair_ok = pok[:R_src]
                        e_overflow = e_overflow | bool_any(eov_acc)
                        if needs_scan:
                            has_succ = row_ok != 0
                            n_cand = nc_acc
                        else:
                            has_succ = cnt > 0
                            n_cand = n_pairs
                        cand_state = None  # recomputed per dest_tile
                    else:
                        (succ_t, k_lo, k_hi, pair_ok, _,
                         eov) = eval_pairs(pidx, live, pslot)
                        e_overflow = e_overflow | bool_any(eov)
                        if needs_scan:
                            row_ok = jnp.zeros(F_c, jnp.uint32).at[
                                jnp.where(
                                    pair_ok, prow, jnp.uint32(F_c)
                                )
                            ].max(jnp.uint32(1), mode="drop")
                            has_succ = row_ok != 0
                            n_cand = jnp.sum(pair_ok, dtype=jnp.uint32)
                        else:
                            has_succ = cnt > 0
                            n_cand = n_pairs
                        # Routed-tile staging is a gather seam: the
                        # successor block transposes back to rows once
                        # (PERF.md §layout — row-major gathers win).
                        cand_state = succ_t.T
                    terminal = fval_c & ~has_succ & expand
                    evt_cex = terminal & (eb != 0)
                    ex = dict(
                        cond=cond, ebits=eb, evt_cex=evt_cex,
                        f_lo=fp_lo, f_hi=fp_hi,
                    )
                    cand_valid = pair_ok
                    cand_par = prow
                else:
                    # Dense expansion: one seam transpose of the class
                    # prefix (step_vec is the row contract).
                    frontier_rows = frontier_t.T
                    ex = expand_frontier(
                        enc, props, evt_idx, frontier_rows, fval_c,
                        ebits_c, expand, with_repeats=False,
                        sym_spec=sym,
                    )
                    e_overflow = e_overflow | bool_any(
                        jnp.any(ex["trunc"])
                    )
                    cand_state, cand_valid = ex["flat"], ex["v"]
                    n_cand = jnp.sum(cand_valid).astype(jnp.uint32)
                    fp_flat = (canonicalize_rows(sym, cand_state, jnp)
                               if sym is not None else cand_state)
                    k_lo, k_hi = fingerprint_u32v(fp_flat, jnp)
                    k_lo, k_hi = clamp_keys(k_lo, k_hi)
                    cand_par = None  # parent row = candidate // K

                # Discoveries: local per-wave hits, globally folded
                # (the lowest hitting shard index wins, mirroring
                # whichever racing thread lands first in the
                # reference).
                if n_props:
                    hits, los, his = wave_hits(props, ex, fval_c)
                    ghits = bool_any(hits)
                    pri = jnp.where(hits, me, jnp.uint32(S))
                    winner = lax.pmin(pri, "shard")
                    sel = hits & (pri == winner)
                    g_lo = lax.psum(
                        jnp.where(sel, los, jnp.uint32(0)), "shard"
                    )
                    g_hi = lax.psum(
                        jnp.where(sel, his, jnp.uint32(0)), "shard"
                    )
                    fresh = ghits & ~c["disc_found"]
                    disc_found = c["disc_found"] | ghits
                    disc_lo = jnp.where(fresh, g_lo, c["disc_lo"])
                    disc_hi = jnp.where(fresh, g_hi, c["disc_hi"])
                else:
                    disc_found = c["disc_found"]
                    disc_lo = c["disc_lo"]
                    disc_hi = c["disc_hi"]

                owner = jnp.where(
                    cand_valid, k_lo % jnp.uint32(S), jnp.uint32(S)
                )

                # Route+compact in ONE sort: order by (owner, key);
                # valid candidates form S contiguous destination runs
                # (invalid rows carry owner=S and sort last).
                rows = jnp.arange(R_src, dtype=jnp.uint32)
                s_owner, s_hi, s_lo, s_row = lax.sort(
                    (owner, k_hi, k_lo, rows), num_keys=3
                )
                # s_owner is sorted: all destination-run boundaries in
                # one searchsorted pass.
                edges = jnp.searchsorted(
                    s_owner, jnp.arange(S + 1, dtype=jnp.uint32)
                ).astype(jnp.uint32)
                starts = edges[:-1]
                counts = edges[1:] - starts
                route_ovf = jnp.any(counts > jnp.uint32(Bd_c))
                c_overflow = c_overflow | bool_any(route_ovf)

                # Build the send tiles from ONE routed payload gather +
                # per-destination SLICES (PERF.md §gathers: TPU gathers
                # cost ~12ns/row regardless of lane count, so the old
                # per-destination payload/fp/ebits/key gathers — ~6×
                # R_src rows per wave — collapse into a single
                # [R_src, E+2] multi-lane gather; slices are free).
                # Parent meta (ebits + parent fp) packs into the same
                # payload: broadcast for dense (candidate // K is a
                # K-fold repeat, no gather), one packed gather for
                # sparse. Buffers are padded by one tile so a
                # destination run ending at R_src slices without the
                # dynamic_slice start-clamp silently shifting live rows.
                fr_meta = jnp.stack(
                    [ex["ebits"]]
                    + ([ex["f_lo"], ex["f_hi"]] if track_paths else []),
                    axis=1,
                )
                if cand_par is None:
                    pmeta = jnp.broadcast_to(
                        fr_meta[:, None, :],
                        (F_c, K, fr_meta.shape[1]),
                    ).reshape(R_src, fr_meta.shape[1])
                else:
                    pmeta = fr_meta[cand_par]
                if cpu_backend:
                    # XLA:CPU: per-destination separate gathers, same
                    # math. The packed arrangement below pads a class
                    # result inside the switch branch, which the
                    # kernel lint (run on the CPU) refuses
                    # (no-branch-pad-concat); kept until that pad
                    # leaves the chip's program (ROADMAP D5).
                    def dest_block(start):
                        idx = jnp.clip(
                            start + jnp.arange(Bd_c, dtype=jnp.uint32),
                            0,
                            jnp.uint32(R_src - 1),
                        )
                        srow = s_row[idx]
                        if cand_par is None:
                            par = srow // jnp.uint32(K)
                        else:
                            par = cand_par[srow]
                        if cand_state is not None:
                            st = cand_state[srow]
                        else:
                            st_t, _, _ = step_cols(
                                pair_states(par), pslot[srow]
                            )
                            st = st_t.T
                        return dest_tile_pack(
                            jnp, st,
                            ex["f_lo"][par] if track_paths else None,
                            ex["f_hi"][par] if track_paths else None,
                            ex["ebits"][par], s_lo[idx], s_hi[idx],
                        )
                elif cand_state is not None:
                    cpay = dest_tile_pack(
                        jnp, cand_state,
                        pmeta[:, 1:2] if track_paths else None,
                        pmeta[:, 2:3] if track_paths else None,
                        pmeta[:, 0:1], k_lo, k_hi,
                    )
                    spay = jnp.pad(
                        cpay[s_row], ((0, Bd_c), (0, 0))
                    )

                    def dest_block(start):
                        return lax.dynamic_slice(
                            spay, (start, jnp.uint32(0)), (Bd_c, E + 2)
                        )
                else:
                    # Chunked sparse: successors are never materialized
                    # at [R_src, W]; recompute per destination from a
                    # packed (pair, slot, meta, key) gather.
                    mparts = [pidx[:, None], pslot[:, None], pmeta]
                    smeta = jnp.pad(
                        jnp.concatenate(mparts, axis=1)[s_row],
                        ((0, Bd_c), (0, 0)),
                    )
                    skeys = jnp.pad(
                        jnp.stack([s_lo, s_hi], axis=1),
                        ((0, Bd_c), (0, 0)),
                    )
                    NM = 2 + fr_meta.shape[1]

                    def dest_block(start):
                        z = jnp.uint32(0)
                        m = lax.dynamic_slice(
                            smeta, (start, z), (Bd_c, NM)
                        )
                        kk = lax.dynamic_slice(
                            skeys, (start, z), (Bd_c, 2)
                        )
                        par = m[:, 0] // jnp.uint32(EV)
                        succ_d_t, _, _ = step_cols(
                            pair_states(par), m[:, 1]
                        )
                        return dest_tile_pack(
                            jnp, succ_d_t.T,
                            m[:, 3:4] if track_paths else None,
                            m[:, 4:5] if track_paths else None,
                            m[:, 2:3], kk[:, 0:1], kk[:, 1:2],
                        )

                def dest_tile(d):
                    start = starts[d]
                    cnt_d = counts[d]
                    live_d = jnp.arange(Bd_c, dtype=jnp.uint32) < cnt_d
                    tile = dest_block(start)
                    return jnp.where(
                        live_d[:, None], tile, jnp.uint32(0)
                    )

                send = jnp.concatenate(
                    [dest_tile(d) for d in range(S)], axis=0
                )
                cross = n_cand - counts[me]
                g_cross = lax.psum(cross.astype(jnp.uint32), "shard")
                sent = u64_add(
                    U64(c["sent_lo"], c["sent_hi"]),
                    U64(g_cross, jnp.uint32(0)),
                )

                recv = lax.all_to_all(
                    send, "shard", split_axis=0, concat_axis=0,
                    tiled=True,
                )

                shard_log = None
                if trace_log:
                    # Routing-stage lanes of the per-shard log: the
                    # local enabled-pair popcount (the quantity the
                    # global log can't see; candidates on the dense
                    # path, mirroring the single-chip convention),
                    # rows routed off-shard, and the peak destination
                    # run vs this class's lossless tile cap.
                    shard_log = (
                        n_pairs if use_sparse else n_cand,
                        cross,
                        jnp.max(counts),
                        jnp.uint32(Bd_c),
                    )
                return merge_stage(
                    c, v_class, R_c, recv, n_cand, sent,
                    (disc_found, disc_lo, disc_hi),
                    (c["overflow"], c["f_overflow"],
                     c_overflow, e_overflow),
                    shard_log=shard_log,
                )

            return wave

        # Memory ledger (stateright_tpu/memplan.py): per-ladder-class
        # staging rows, PER SHARD (the shard_map body's view), from
        # the same class_params the wave programs compile from. The
        # chunked memory-lean gate mirrors make_wave's (R_src rows at
        # the padded ~512 B/row cost vs the flat budget); chunked
        # classes land an ``engine_mode`` record like the single-chip
        # engine's.
        from ..memplan import buffer_entry, plan_total

        from ..ops.bitmask import mask_words as _mask_words

        _row_pad = -(-W // 128) * 512
        _classes = []
        _modes = []
        for fc in range(len(f_ladder)):
            F_c, NT_c, _T_c, R_src, B_c, Bd_c = class_params(fc)
            staging = [
                buffer_entry("cand_keys", (2, R_src), "uint32"),
                buffer_entry("send_tiles", (S * Bd_c, E + 2),
                             "uint32"),
                buffer_entry("recv_tiles", (S * Bd_c, E + 2),
                             "uint32"),
            ]
            chunked_c = False
            if use_sparse:
                staging.insert(0, buffer_entry(
                    "enabled_bits", (F_c, _mask_words(K)), "uint32"
                ))
                staging.insert(1, buffer_entry(
                    "pair_index", (3, R_src), "uint32"
                ))
                chunked_c = R_src * _row_pad > self.flat_budget_bytes
                if chunked_c:
                    NC_c = -(-(R_src * _row_pad)
                             // self.flat_budget_bytes)
                    Bc_c = -(-R_src // NC_c)
                    staging.append(buffer_entry(
                        "succ_chunk", (W, Bc_c), "uint32"
                    ))
                    _modes.append(dict(
                        engine=type(self).__name__, mode="chunked",
                        f_class=fc, buffer_rows=R_src, chunks=NC_c,
                        chunk_rows=Bc_c, row_pad_bytes=_row_pad,
                        flat_budget_bytes=self.flat_budget_bytes,
                    ))
                else:
                    staging.append(buffer_entry(
                        "succ_t", (W, R_src), "uint32"
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
                buffer_rows=R_src, dest_cap=Bd_c,
                staging=staging, staging_bytes=plan_total(staging),
            ))
        from ..memplan import v_class_entries

        _NFmax = min(F, max(c["buffer_rows"] for c in _classes))
        self._build_info = dict(
            classes=_classes,
            v_classes=v_class_entries(v_ladder, _NFmax),
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
            c2 = lax.switch(
                f_class,
                [make_wave(fc, v_class) for fc in range(len(f_ladder))],
                ci,
            )
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
