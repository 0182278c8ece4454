"""The sharded wave engine: BFS over a device mesh via ``shard_map``.

Design (the job_market.rs:66-147 replacement promised in SURVEY §2.5):

* Every device owns the fingerprint residues ``fp_lo % n_shards ==
  shard_index``: its slice of the visited table, the parent forest, and
  the frontier rows it discovered.
* A wave runs entirely inside one ``shard_map``-wrapped
  ``lax.while_loop``: each device expands its local frontier block
  (vmap step → property bitmaps → candidate compaction → vectorized
  fingerprints), then routes each candidate to its owner with one
  ``lax.all_to_all`` keyed by ``fp % n_shards`` — dedup (sort-unique +
  table insert) is thereafter shard-local, exactly the role DashMap
  sharding plays in the reference BFS (bfs.rs:28-29), but with the
  *communication* pattern chosen for ICI: one balanced collective per
  wave instead of work stealing.
* Termination, state counters, discovery folding, and overflow flags
  are ``psum``/``pmin`` reductions, so every device agrees on ``done``
  without touching the host (the distributed-termination condvar dance
  of job_market.rs:66-101 becomes a single all-reduce).
* The host syncs once per ``waves_per_sync`` waves via the same packed
  stats vector as the single-chip engine.

Shapes are per-shard: ``capacity``/``frontier_capacity``/
``cand_capacity`` size each device's slice. ``bucket_capacity`` bounds
the rows routed to any single destination per wave (the all_to_all's
fixed tile size); overflow is detected and reported, never silent.

On one device the shuffle degenerates to the identity and the engine
matches the single-chip one state for state; tests pin identical
results for shard counts 1/2/8 on the CPU mesh.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..checker import CheckerBuilder
from ..encoding import EncodedModel
from ..model import Expectation
from ..ops.fingerprint import fingerprint_u32v
from ..ops.hashset import DeviceHashSet, insert
from ..ops.u64 import U64, u64_add
from ..checkers.tpu import (
    TpuBfsChecker,
    expand_frontier,
    wave_hits,
)


def payload_tile_width(w: int, track_paths: bool) -> int:
    """Lanes of this engine's routed candidate payload (``E2`` in
    ``_build_programs``): state + (parent fp when tracked) + ebits +
    the candidate's own fp limbs. ONE formula for the device program
    and the lane config's ``dest_tile_lanes`` (what
    telemetry.shard_balance prices routed bytes with)."""
    return (w + 3 if track_paths else w + 1) + 2


class ShardedTpuBfsChecker(TpuBfsChecker):
    """``CheckerBuilder.spawn_tpu_sharded()`` — the wave engine over a
    ``jax.sharding.Mesh``. Inherits the whole result/reconstruction
    surface from the single-chip engine; only the device programs (and
    their shard_map wrapping) differ."""

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
        probe_rounds: int = 16,
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
        super().__init__(
            builder,
            encoded=encoded,
            capacity=capacity,
            frontier_capacity=frontier_capacity,
            track_paths=track_paths,
            waves_per_sync=waves_per_sync,
            cand_capacity=cand_capacity,
            probe_rounds=probe_rounds,
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
        # Mesh hashes by devices + axis names, so equivalent meshes
        # share compiled programs and distinct device sets never alias.
        # Traced runs carry the wave/shard logs: a different program.
        return (
            self.n_shards,
            self.bucket_capacity,
            self.mesh,
            self._wave_log_enabled(),
        )

    def _cand_overflow_message(self) -> str:
        return (
            "candidate/bucket overflow: a wave generated more successors "
            f"than fit the per-shard buffers (cand_capacity="
            f"{self.cand_capacity}, bucket_capacity={self.bucket_capacity});"
            " re-run with larger capacities (or None for never-overflow "
            "sizes)"
        )

    def _consume_extra_stats(self, extra: np.ndarray) -> None:
        if extra.size >= 2:
            self.metrics["shuffle_volume"] = int(extra[0]) | (
                int(extra[1]) << 32
            )

    # -- telemetry (stateright_tpu/telemetry.py) ---------------------------

    def _wave_log_rows(self, s: np.ndarray, n_props: int):
        if not self._wave_log_enabled():
            return None
        from ..telemetry import WAVE_LOG_LANES as WL

        off = 11 + 3 * n_props + 2  # scalars + discovery + sent lanes
        return s[off:off + self.waves_per_sync * WL].reshape(
            self.waves_per_sync, WL
        )

    def _wave_log_pairs_valid(self) -> bool:
        # Dense hash-table waves have no (row, slot) pair extraction;
        # the shard log's lane 1 carries the candidate count (the
        # single-chip dense convention) and back-fills the wave event.
        return False

    def _plan_sharded_names(self) -> tuple:
        # Mirrors the shard_map out_specs below: these carry leaves
        # are split across the mesh, so their ledger rows report
        # per_shard_bytes = bytes / n_shards (memplan.plan_entries).
        return ("t_lo", "t_hi", "p_lo_t", "p_hi_t", "frontier",
                "fval", "ebits", "slog", "u_loc")

    def _lane_config(self) -> dict:
        lane = super()._lane_config()
        lane.update(
            n_shards=self.n_shards,
            bucket_capacity=self.bucket_capacity,
            # routed-payload lanes (E2): what telemetry.shard_balance
            # prices routed-byte volume with (rows x lanes x 4 B)
            dest_tile_lanes=payload_tile_width(
                self.encoded.width, self.track_paths
            ),
            # open addressing: shard_balance's occupancy watch uses
            # the probe-pressure threshold, not exact-capacity
            # headroom (stateright_tpu/occupancy.py)
            visited_exact=False,
        )
        return lane

    # -- device programs ---------------------------------------------------

    def _build_programs(self, n0: int):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        enc = self.encoded
        props = list(self.model.properties())
        n_props = len(props)
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
        capacity = self.capacity
        B = min(self.cand_capacity or F * K, F * K)
        # Rows routable to one destination per wave. B is the
        # never-overflow bound (every local candidate bound for one
        # shard); the fingerprint split is near-uniform, so the default
        # gives each destination 4x its expected share (overflow is
        # detected, reported with the sizing knob, and never silent).
        if self.bucket_capacity is not None:
            Bd = min(self.bucket_capacity, B)
        elif S == 1:
            Bd = B
        else:
            Bd = min(B, max(128, (4 * B + S - 1) // S))
        probe_rounds = self.probe_rounds
        target_states = self.builder._target_state_count
        target_depth = self.builder._target_max_depth
        waves_per_sync = self.waves_per_sync
        ebits_init = self._eventually_bits_init()
        track_paths = self.track_paths
        # Payload lanes: state + (parent fp) + ebits, + the candidate's
        # own fingerprint so owners don't re-hash after the shuffle.
        # All-zero rows mark unused bucket slots (fingerprints are
        # never 0, ops/fingerprint.py).
        E2 = payload_tile_width(W, track_paths)
        E = E2 - 2
        EB = E - 1
        mesh = self.mesh
        # Per-wave trace logs (telemetry.py, round 11): the GLOBAL
        # wave log (psum'd counters — this engine's body is monolithic,
        # so the row is assembled in place) and the PER-SHARD mesh log
        # (SHARD_LOG_FIELDS: local frontier/candidates, routed and
        # received rows, bucket fill vs the lossless Bd cap, local
        # new/visited). Gated on an active tracer and cache-keyed so
        # untraced programs compile exactly as before. ``u_loc`` (the
        # per-shard visited counter the log's last lane reports) only
        # exists on traced runs.
        from ..telemetry import SHARD_LOG_LANES as SL
        from ..telemetry import WAVE_LOG_LANES as WL

        trace_log = self._wave_log_enabled()

        def bool_any(x):
            """Global OR of per-shard bools (replicated result)."""
            return lax.psum(x.astype(jnp.uint32), "shard") > 0

        def seed_local(init_rows):
            me = lax.axis_index("shard").astype(jnp.uint32)
            lo0, hi0 = fingerprint_u32v(init_rows, jnp)
            mine = (lo0 % jnp.uint32(S)) == me
            pos = jnp.cumsum(mine) - 1
            sp = jnp.where(mine, pos, F)
            frontier = jnp.zeros((F, W), dtype=jnp.uint32).at[sp].set(
                init_rows, mode="drop"
            )
            n_mine = jnp.sum(mine)
            fval = jnp.arange(F) < n_mine
            ebits = jnp.where(fval, jnp.uint32(ebits_init), jnp.uint32(0))
            table = DeviceHashSet.empty(capacity, jnp)
            table, _, pending, _ = insert(
                table, lo0, hi0, mine, jnp, rounds=probe_rounds
            )
            overflow = bool_any(jnp.any(pending))
            return dict(
                **(
                    dict(
                        wlog=jnp.zeros((waves_per_sync, WL),
                                       jnp.uint32),
                        slog=jnp.zeros((waves_per_sync, SL),
                                       jnp.uint32),
                        u_loc=n_mine.astype(jnp.uint32).reshape(1),
                    )
                    if trace_log else {}
                ),
                t_lo=table.lo,
                t_hi=table.hi,
                p_lo_t=jnp.zeros(capacity if track_paths else 0, jnp.uint32),
                p_hi_t=jnp.zeros(capacity if track_paths else 0, jnp.uint32),
                frontier=frontier,
                fval=fval,
                ebits=ebits,
                depth=jnp.int32(1),
                wchunk=jnp.int32(0),
                waves=jnp.uint32(0),
                gen_lo=jnp.uint32(n0),
                gen_hi=jnp.uint32(0),
                new=jnp.uint32(n0),
                sent_lo=jnp.uint32(0),
                sent_hi=jnp.uint32(0),
                disc_found=jnp.zeros(n_props, dtype=bool),
                disc_lo=jnp.zeros(n_props, dtype=jnp.uint32),
                disc_hi=jnp.zeros(n_props, dtype=jnp.uint32),
                overflow=overflow,
                f_overflow=jnp.bool_(False),
                c_overflow=jnp.bool_(False),
                e_overflow=jnp.bool_(False),
                done=jnp.bool_(n0 == 0) | overflow,
            )

        def body(c):
            table = DeviceHashSet(c["t_lo"], c["t_hi"])
            ebits = c["ebits"]
            fval = c["fval"]
            me = lax.axis_index("shard").astype(jnp.uint32)
            if trace_log:
                n_f_loc = jnp.sum(fval, dtype=jnp.uint32)

            if target_depth is None:
                expand = jnp.bool_(True)
            else:
                expand = c["depth"] < target_depth

            ex = expand_frontier(
                enc, props, evt_idx, c["frontier"], fval, ebits, expand
            )
            e_overflow = c["e_overflow"] | bool_any(jnp.any(ex["trunc"]))

            # Discoveries: local per-wave hits, globally folded. The
            # winning fingerprint comes from the lowest shard index
            # that hit (any racing thread wins in the reference).
            if n_props:
                hits, los, his = wave_hits(props, ex, fval)
                ghits = bool_any(hits)
                pri = jnp.where(hits, me, jnp.uint32(S))
                winner = lax.pmin(pri, "shard")
                sel = hits & (pri == winner)
                g_lo = lax.psum(jnp.where(sel, los, jnp.uint32(0)), "shard")
                g_hi = lax.psum(jnp.where(sel, his, jnp.uint32(0)), "shard")
                fresh = ghits & ~c["disc_found"]
                disc_found = c["disc_found"] | ghits
                disc_lo = jnp.where(fresh, g_lo, c["disc_lo"])
                disc_hi = jnp.where(fresh, g_hi, c["disc_hi"])
            else:
                disc_found = c["disc_found"]
                disc_lo = c["disc_lo"]
                disc_hi = c["disc_hi"]

            # Local candidate compaction (identical to single-chip).
            n_cand = jnp.sum(ex["v"])
            parts = [ex["flat"]]
            if track_paths:
                parts += [ex["p_lo"][:, None], ex["p_hi"][:, None]]
            parts.append(ex["child_ebits"][:, None])
            ext = jnp.concatenate(parts, axis=1)
            if B < F * K:
                cpos = jnp.cumsum(ex["v"]) - 1
                csp = jnp.where(ex["v"], cpos, B)
                b_ext = jnp.zeros((B, E), jnp.uint32).at[csp].set(
                    ext, mode="drop"
                )
                b_val = jnp.arange(B) < n_cand
                c_overflow = c["c_overflow"] | bool_any(n_cand > B)
            else:
                b_ext = ext
                b_val = ex["v"]
                c_overflow = c["c_overflow"]

            b_lo, b_hi = fingerprint_u32v(b_ext[:, :W], jnp)
            owner = b_lo % jnp.uint32(S)
            payload = jnp.concatenate(
                [
                    b_ext,
                    jnp.where(b_val, b_lo, jnp.uint32(0))[:, None],
                    jnp.where(b_val, b_hi, jnp.uint32(0))[:, None],
                ],
                axis=1,
            )

            # Route: compact each destination's candidates into its
            # fixed Bd-row tile of the send buffer, then one all_to_all
            # swaps tiles so every candidate lands on its owner.
            send = jnp.zeros((S * Bd, E2), dtype=jnp.uint32)
            route_ovf = jnp.bool_(False)
            fill_peak = jnp.uint32(0)
            for d in range(S):
                m = b_val & (owner == d)
                pos = jnp.cumsum(m) - 1
                sp = jnp.where(m, d * Bd + pos, S * Bd)
                send = send.at[sp].set(payload, mode="drop")
                cnt_d = jnp.sum(m)
                route_ovf = route_ovf | (cnt_d > Bd)
                if trace_log:
                    # peak destination-bucket fill for the shard log
                    fill_peak = jnp.maximum(
                        fill_peak, cnt_d.astype(jnp.uint32)
                    )
            c_overflow = c_overflow | bool_any(route_ovf)
            cross = n_cand - jnp.sum(b_val & (owner == me))
            g_cross = lax.psum(cross.astype(jnp.uint32), "shard")
            sent = u64_add(
                U64(c["sent_lo"], c["sent_hi"]), U64(g_cross, jnp.uint32(0))
            )

            recv = lax.all_to_all(
                send, "shard", split_axis=0, concat_axis=0, tiled=True
            )

            # Owner-local insert-if-absent (bfs.rs:292-306 semantics,
            # with zero cross-shard contention by construction);
            # duplicate keys in the received batch resolve inside the
            # probe loop, so no sort-unique pass is needed.
            r_lo = recv[:, E]
            r_hi = recv[:, E + 1]
            r_val = (r_lo != 0) | (r_hi != 0)
            table, is_new, pending, slots = insert(
                table, r_lo, r_hi, r_val, jnp, rounds=probe_rounds
            )
            overflow = c["overflow"] | bool_any(jnp.any(pending))
            s_ext = recv

            if track_paths:
                par_idx = jnp.where(is_new, slots, jnp.uint32(capacity))
                p_lo_t = c["p_lo_t"].at[par_idx].set(
                    s_ext[:, W], mode="drop"
                )
                p_hi_t = c["p_hi_t"].at[par_idx].set(
                    s_ext[:, W + 1], mode="drop"
                )
            else:
                p_lo_t, p_hi_t = c["p_lo_t"], c["p_hi_t"]

            new_count = jnp.sum(is_new)
            pos = jnp.cumsum(is_new) - 1
            scatter_pos = jnp.where(is_new, pos, F)
            next_fe = jnp.zeros((F, E2), dtype=jnp.uint32).at[
                scatter_pos
            ].set(s_ext, mode="drop")
            next_frontier = next_fe[:, :W]
            next_ebits = next_fe[:, EB]
            next_fval = jnp.arange(F) < new_count
            f_overflow = c["f_overflow"] | bool_any(new_count > F)

            g_new = lax.psum(new_count.astype(jnp.uint32), "shard")
            g_cand = lax.psum(n_cand.astype(jnp.uint32), "shard")
            g = u64_add(
                U64(c["gen_lo"], c["gen_hi"]), U64(g_cand, jnp.uint32(0))
            )
            new = c["new"] + g_new

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
            trace_extra = {}
            if trace_log:
                u_loc = c["u_loc"] + new_count.astype(jnp.uint32)
                # GLOBAL wave row (replicated lanes only): lane 1 is 0
                # — the dense wave has no pair popcount; the host
                # back-fills the event from the shard log's candidate
                # lane (_wave_log_pairs_valid).
                row = jnp.stack(
                    [
                        lax.psum(n_f_loc, "shard"),
                        jnp.uint32(0),
                        g_cand,
                        g_new,
                        new,
                        c["depth"].astype(jnp.uint32),
                        jnp.uint32(0),  # no frontier ladder here
                        jnp.uint32(0),  # no visited ladder here
                    ]
                )
                # PER-SHARD mesh row (SHARD_LOG_FIELDS): never psum'd.
                srow = jnp.stack(
                    [
                        n_f_loc,
                        n_cand.astype(jnp.uint32),  # dense: candidates
                        n_cand.astype(jnp.uint32),
                        cross.astype(jnp.uint32),
                        jnp.sum(r_val, dtype=jnp.uint32),
                        fill_peak,
                        jnp.uint32(Bd),
                        new_count.astype(jnp.uint32),
                        u_loc[0],
                    ]
                )
                trace_extra = dict(
                    wlog=lax.dynamic_update_slice(
                        c["wlog"], row[None, :],
                        (c["wchunk"], jnp.int32(0)),
                    ),
                    slog=lax.dynamic_update_slice(
                        c["slog"], srow[None, :],
                        (c["wchunk"], jnp.int32(0)),
                    ),
                    u_loc=u_loc,
                )
            return dict(
                **trace_extra,
                t_lo=table.lo,
                t_hi=table.hi,
                p_lo_t=p_lo_t,
                p_hi_t=p_hi_t,
                frontier=next_frontier,
                fval=next_fval & cont,
                ebits=next_ebits,
                depth=jnp.where(cont, c["depth"] + 1, c["depth"]),
                wchunk=c["wchunk"] + 1,
                waves=c["waves"] + 1,
                gen_lo=g.lo,
                gen_hi=g.hi,
                new=new,
                sent_lo=sent.lo,
                sent_hi=sent.hi,
                disc_found=disc_found,
                disc_lo=disc_lo,
                disc_hi=disc_hi,
                overflow=overflow,
                f_overflow=f_overflow,
                c_overflow=c_overflow,
                e_overflow=e_overflow,
                done=~cont,
            )

        def cond(c):
            return ~c["done"] & (c["wchunk"] < waves_per_sync)

        # Memory ledger (memplan.py): no ladder here either — one
        # fixed-shape class per shard; the routed send/recv tiles are
        # the staging this engine adds over the single-chip one.
        from ..memplan import buffer_entry, plan_total

        _staging = [
            buffer_entry("cand_payload", (F * K, E), "uint32"),
            buffer_entry("cand_compact", (B, E2), "uint32"),
            buffer_entry("send_tiles", (S * Bd, E2), "uint32"),
            buffer_entry("recv_tiles", (S * Bd, E2), "uint32"),
        ]
        self._build_info = dict(
            classes=[dict(
                f_class=0, v_class=0, mode="hash-sharded",
                frontier_rows=F, visited_rows=capacity,
                dest_cap=Bd, staging=_staging,
                staging_bytes=plan_total(_staging),
            )],
            v_classes=[],
            engine_modes=[],
        )

        def chunk(carry):
            from jax import lax as _lax

            c = dict(carry, wchunk=jnp.int32(0))
            c = _lax.while_loop(cond, body, c)
            frontier_total = _lax.psum(
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
                jnp.stack([c["sent_lo"], c["sent_hi"]]),
            ]
            if trace_log:
                parts.append(c["wlog"].reshape(-1))
            stats = jnp.concatenate(parts)
            if trace_log:
                # the per-shard mesh log: a second, shard-sharded
                # stats output — same dispatch, same sync point
                return c, stats, c["slog"].reshape(-1)
            return c, stats

        P_shard = P("shard")
        specs = dict(
            **(
                dict(wlog=P(), slog=P("shard", None), u_loc=P_shard)
                if trace_log else {}
            ),
            t_lo=P_shard,
            t_hi=P_shard,
            p_lo_t=P_shard,
            p_hi_t=P_shard,
            frontier=P("shard", None),
            fval=P_shard,
            ebits=P_shard,
            depth=P(),
            wchunk=P(),
            waves=P(),
            gen_lo=P(),
            gen_hi=P(),
            new=P(),
            sent_lo=P(),
            sent_hi=P(),
            disc_found=P(),
            disc_lo=P(),
            disc_hi=P(),
            overflow=P(),
            f_overflow=P(),
            c_overflow=P(),
            e_overflow=P(),
            done=P(),
        )
        # Checkpoint/resume (stateright_tpu/checkpoint.py): a resumed
        # run places snapshot buffers with these exact shardings.
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
        # Tooling hook (analysis/comms.py): the shard_map-wrapped wave
        # body, re-traceable on the GLOBAL carry shapes — the hash
        # engine's analog of the sort-merge engines' ``_wave_body_sm``,
        # so comms-lint prices this engine's scatter-routed all_to_all
        # path too. Never called by the run loop: no behavioral change.
        self._wave_body_sm = shard_map(
            body, mesh=mesh, in_specs=(specs,), out_specs=specs,
        )
        return jax.jit(seed_sm), jax.jit(chunk_sm, donate_argnums=0)
