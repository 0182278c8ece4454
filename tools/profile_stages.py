#!/usr/bin/env python
"""Per-STAGE profile of the sparse sort-merge wave at real workload
shapes with REAL mid-run data (VERDICT r4: explain where the
~76ms/wave at paxos check 4 goes, and why check 5 runs at half the
per-state rate of check 4).

Method: run the real engine with ``target_state_count`` ≈ half the
space and ``keep_final_carry`` set, so the final carry's frontier is a
genuine mid-growth wave's new-state set and the visited array holds
the genuine prefix. Then re-run each wave stage in isolation on that
data, amortized over REPS in-jit repetitions (host dispatch latency would
hide a single op's execution, so each measured op runs inside one
jitted fori_loop with a full-reduction fold that defeats DCE).

Usage:
  python tools/profile_stages.py --paxos 4
  python tools/profile_stages.py --paxos 5
  python tools/profile_stages.py --twopc 8
  python tools/profile_stages.py --paxos 4 --wave-profile   # per-wave ms
  python tools/profile_stages.py --paxos 4 --wave-wall      # out-of-stage
                                  # wall + per-HLO-category attribution
                                  # (stateright_tpu/wavewall.py)
  python tools/profile_stages.py --micro    # primitive costs at engine
                                  # row counts, synthetic keys (the
                                  # retired profile_sortmerge.py's
                                  # post-round-10 successor)

Per-wave WALL times for a real run come from ``--trace=deep`` +
tools/latency_report.py these days — this tool is for isolating
stages, not timing runs.
"""

import argparse
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

REPS = 8


def _timed_raw(build, args, reps=REPS):
    """Time `reps` sequential applications of build inside one jitted
    program. build(i, carry) -> carry MUST fold a FULL reduction of
    each stage output back into the carry — folding a single element
    lets XLA dead-code-eliminate the rest of the stage (the round-5
    profiler bug: step+fp showed 1.5ms because only succ[0] was
    live)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(*arrs):
        out = lax.fori_loop(0, reps, build, arrs)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        # Consume EVERY carry element — returning only out[0] lets XLA
        # DCE stages that fold their work into a later carry slot.
        return sum(
            jnp.sum(a.reshape(-1)[:1].astype(jnp.uint32)) for a in out
        )

    f = jax.jit(run)
    float(f(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        float(f(*args))
        best = min(best, time.monotonic() - t0)
    return best


def _timed(build, args, reps=REPS):
    """ms per op, empty-loop baseline (dispatch floor + carry
    movement at the same shapes) subtracted."""
    base = _timed_raw(lambda i, c: c, args, reps)
    return (_timed_raw(build, args, reps) - base) / reps * 1000.0


def _fold(x):
    """Full-output reduction to defeat DCE: cheap relative to any
    measured stage (one pass over x)."""
    import jax.numpy as jnp

    return jnp.sum(x.reshape(-1).astype(jnp.uint32)) % jnp.uint32(2)


def _spawn(kind, n, caps, target=None, waves_per_sync=64,
           optimize=True):
    encoded = None
    if kind == "paxos":
        from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

        b = paxos_model(
            PaxosModelCfg(client_count=n, server_count=3)
        ).checker()
    elif kind == "paxos-compiled":
        # The compiled paxos lane (round 23): same actor model, the
        # encoding comes from the generic compiler (reachable-mode
        # harvest — paid here, outside the profiled stages).
        from stateright_tpu.models.paxos import (
            PaxosModelCfg, paxos_compiled_encoded, paxos_model,
        )

        cfg = PaxosModelCfg(client_count=n, server_count=3)
        b = paxos_model(cfg).checker()
        encoded = paxos_compiled_encoded(cfg, optimize=optimize)
    elif kind == "twopc-compiled":
        # Compiled count-comparable 2pc system model; ``optimize``
        # toggles the codegen optimizer for per-stage ablation A/Bs
        # (the PERF.md §compiled-parity before/after rows).
        from stateright_tpu.models.two_phase_commit_actors import (
            two_phase_sys_actor_model,
            two_phase_sys_compiled_encoded,
        )

        b = two_phase_sys_actor_model(n).checker()
        encoded = two_phase_sys_compiled_encoded(n, optimize=optimize)
    else:
        from stateright_tpu.models.two_phase_commit import TwoPhaseSys

        b = TwoPhaseSys(rm_count=n).checker()
    if target is not None:
        b = b.target_state_count(target)
    return b.spawn_tpu_sortmerge(
        track_paths=False, waves_per_sync=waves_per_sync,
        **({"encoded": encoded} if encoded is not None else {}),
        **caps
    )


def stage_profile(kind, n, caps, target, optimize=True):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from stateright_tpu.checkers.tpu_sortmerge import (
        _SENT,
        _divisor_at_least,
        _ladder,
        sparse_pair_candidates,
    )
    from stateright_tpu.checkers.tpu import frontier_props_t
    from stateright_tpu.encoding import pair_step_seam
    from stateright_tpu.ops.fingerprint import fingerprint_u32v_t

    print(f"\n## stage profile: {kind} {n} (target={target})")
    c = _spawn(kind, n, caps, target=target, optimize=optimize)
    c.keep_final_carry = True
    c.join()
    carry = c._final_carry
    enc = c.encoded
    # The resident frontier is the transposed [W, F] block (round 9,
    # PERF.md §layout); every stage below mirrors the engine's
    # transposed invocation, including the one row-major seam
    # transpose feeding the pair-step gathers.
    frontier = carry["frontier"]
    # Frontier rows past the last wave's class-local block are STALE
    # (round 6 carry rework) — the carried n_frontier is the live-row
    # count (the live rows are always a dense prefix).
    n_rows = int(np.asarray(carry["n_frontier"]))
    V_cnt = int(np.asarray(carry["new"]))
    print(f"captured frontier rows={n_rows}  visited={V_cnt}  "
          f"depth={int(np.asarray(carry['depth']))}")

    K, W = enc.max_actions, enc.width
    F = c.frontier_capacity
    f_ladder = _ladder(c.f_min, F, c.ladder_step)
    v_ladder = _ladder(c.v_min, c.capacity, c.v_ladder_step)
    F_f = next(v for v in f_ladder if v >= n_rows)
    V_v = next(v for v in v_ladder if v >= V_cnt)
    EV = c._pair_width()
    B_user = min(c.cand_capacity or F * K, F * K)
    NPg = F_f * EV
    B_p = min(B_user, NPg)
    compaction = NPg > B_p
    want_tiles = -(-NPg // c.tile_rows)
    if F_f == F:
        want_tiles = max(want_tiles, c.tiles)
    if compaction:
        # Mirror make_sparse_wave's packed-append headroom clamp so
        # the profiled Ba/NT/T match what the engine actually runs.
        want_tiles = max(want_tiles, -(-(4 * NPg) // max(B_p, 1)))
    NT = _divisor_at_least(F_f, want_tiles) if compaction else 1
    T = F_f // NT
    Ba = (B_p + T * EV) if compaction else NPg
    # Chunk gate mirrors the engine: PADDED row cost (~512 B per
    # 128-lane group on TPU), not unpadded W*4.
    row_pad = -(-W // 128) * 512
    chunked = compaction and (Ba * row_pad > c.flat_budget_bytes)
    NC = Bc = 0
    if chunked:
        NC = -(-(Ba * row_pad) // c.flat_budget_bytes)
        Bc = -(-Ba // NC)
        Ba = NC * Bc
    print(f"class: F_f={F_f} V_v={V_v} K={K} W={W} EV={EV} "
          f"B_p={B_p} NT={NT} Ba={Ba} chunked={chunked}")

    frontier_f = frontier[:, :F_f]
    fval_f = jnp.arange(F_f) < n_rows
    ebits_f = carry["ebits"][:F_f]
    props = list(c.model.properties())
    from stateright_tpu.model import Expectation

    evt_idx = [i for i, p in enumerate(props)
               if p.expectation == Expectation.EVENTUALLY]

    results = {}
    acc0 = jnp.zeros(8, jnp.uint32)

    # -- stage: property conditions over the frontier -------------------
    def s_props(i, a):
        fr, acc = a
        fr = fr.at[0, 0].set(fr[0, 0] ^ i.astype(jnp.uint32))
        cond, eb, f_lo, f_hi = frontier_props_t(
            enc, props, evt_idx, fr, fval_f, ebits_f
        )
        acc = acc.at[0].add(
            _fold(cond) + _fold(eb) + _fold(f_lo) + _fold(f_hi)
        )
        return fr, acc

    results["props(frontier)"] = _timed(s_props, (frontier_f, acc0))

    # -- stage: enabled mask only (the [F,K] predicate pass) ------------
    from stateright_tpu.checkers.tpu_sortmerge import (
        frontier_enabled_bits,
    )

    mb = c.mask_budget_cells

    def mask_only(fr):
        # THE engine's mask pass (one shared home, the way
        # encoding.pair_step_seam is the one pair-seam home): the
        # profiler times the exact pipeline sparse_pair_candidates
        # runs, transposed invocation, tiling and all.
        return frontier_enabled_bits(
            enc, fr, fval_f, jnp.bool_(True),
            mask_budget_cells=c.mask_budget_cells,
        )

    def s_mask(i, a):
        fr, acc = a
        fr = fr.at[0, 0].set(fr[0, 0] ^ i.astype(jnp.uint32))
        bits, cnt = mask_only(fr)
        acc = acc.at[0].add(_fold(bits) + _fold(cnt))
        return fr, acc

    results["enabled-mask [F,K]"] = _timed(s_mask, (frontier_f, acc0))

    # -- stage: full pair pipeline (mask + peel + compaction) -----------
    def s_pairs(i, a):
        fr, acc = a
        fr = fr.at[0, 0].set(fr[0, 0] ^ i.astype(jnp.uint32))
        pidx, live, pslot, cnt, n_pairs, ovf, tmax = (
            sparse_pair_candidates(
                enc, fr, fval_f, jnp.bool_(True),
                EV=EV, B_p=B_p, NT=NT, T=T,
                mask_budget_cells=mb, Ba=Ba,
            )
        )
        acc = acc.at[0].add(
            _fold(pidx) + _fold(pslot) + _fold(cnt) + n_pairs
        )
        return fr, acc

    results["pairs(mask+peel+compact)"] = _timed(
        s_pairs, (frontier_f, acc0)
    )

    # materialize real pairs once for the downstream stages
    pidx, live, pslot, cnt, n_pairs, ovf, tmax = jax.jit(
        lambda fr: sparse_pair_candidates(
            enc, fr, fval_f, jnp.bool_(True),
            EV=EV, B_p=B_p, NT=NT, T=T, mask_budget_cells=mb, Ba=Ba,
        )
    )(frontier_f)
    n_pairs_i = int(np.asarray(n_pairs))
    print(f"real pairs this wave: {n_pairs_i} (Ba={Ba})")

    # The engine's backend-adaptive pair-state seam, from its ONE
    # home (encoding.pair_step_seam) — the profiler times exactly the
    # policy the engines run.
    cpu_backend = jax.default_backend() == "cpu"
    step_cols, make_pair_states = pair_step_seam(enc, cpu_backend)

    def pair_states(fr, idx):
        return make_pair_states(fr, fr)(idx)

    has_boundary = not getattr(enc, "trivial_boundary", False)

    # -- stage: step + fingerprint over Ba pairs ------------------------
    def eval_block(fr, pidx_b, live_b, slot_b):
        from stateright_tpu.encoding import within_boundary_cols

        prow_b = pidx_b // jnp.uint32(EV)
        succ_t, ptr_b, hard_b = step_cols(
            pair_states(fr, prow_b), slot_b
        )
        ok = live_b
        if hard_b is not None:
            ok = ok & ~hard_b
        if has_boundary:
            inb = within_boundary_cols(enc, succ_t)
            ok = ok & inb
        if ptr_b is not None:
            ok = ok & ~ptr_b
        lo, hi = fingerprint_u32v_t(succ_t, jnp)
        lo = jnp.where(ok, lo, jnp.uint32(_SENT))
        hi = jnp.where(ok, hi, jnp.uint32(_SENT))
        return lo, hi

    if chunked:
        def s_stepfp(i, a):
            fr, pi, acc = a
            pi = pi.at[0].set(pi[0] ^ (i.astype(jnp.uint32) & 1))

            def fchunk(ti, fc_acc):
                cl, ch = fc_acc
                off = ti * Bc
                lo, hi = eval_block(
                    fr,
                    lax.dynamic_slice(pi, (off,), (Bc,)),
                    lax.dynamic_slice(live, (off,), (Bc,)),
                    lax.dynamic_slice(pslot, (off,), (Bc,)),
                )
                return (
                    lax.dynamic_update_slice(cl, lo, (off,)),
                    lax.dynamic_update_slice(ch, hi, (off,)),
                )

            cl, ch = lax.fori_loop(
                0, NC, fchunk,
                (jnp.full(Ba, _SENT, jnp.uint32),
                 jnp.full(Ba, _SENT, jnp.uint32)),
            )
            acc = acc.at[0].add(_fold(cl) + _fold(ch))
            return fr, pi, acc
    else:
        def s_stepfp(i, a):
            fr, pi, acc = a
            pi = pi.at[0].set(pi[0] ^ (i.astype(jnp.uint32) & 1))
            lo, hi = eval_block(fr, pi, live, pslot)
            acc = acc.at[0].add(_fold(lo) + _fold(hi))
            return fr, pi, acc

    results[f"step+fp ({Ba} pairs)"] = _timed(
        s_stepfp, (frontier_f, pidx, acc0)
    )

    # real candidate keys for the merge stages
    ck_lo, ck_hi = jax.jit(
        lambda fr: eval_block(fr, pidx, live, pslot)
    )(frontier_f)

    # -- stage: symmetry canonicalization over the successor block ------
    # (only when the encoding declares a device rewrite spec; the
    # engine runs this between step and fingerprint when --symmetry is
    # armed, so its cost rides the same [W, B] transposed layout)
    from stateright_tpu.encoding import device_rewrite_spec

    sym_spec = device_rewrite_spec(enc)
    if sym_spec is not None:
        from stateright_tpu.ops.canonical import canonicalize_t

        Bcn = Bc if chunked else Ba
        succ_t_d = jax.jit(
            lambda fr: step_cols(
                pair_states(fr, pidx[:Bcn] // jnp.uint32(EV)),
                pslot[:Bcn],
            )[0]
        )(frontier_f)

        def s_canon(i, a):
            st, acc = a
            st = st.at[0, 0].set(st[0, 0] ^ (i.astype(jnp.uint32) & 1))
            ct = canonicalize_t(sym_spec, st, jnp)
            acc = acc.at[0].add(_fold(ct))
            return st, acc

        results[f"canonicalize ({Bcn} succ)"] = _timed(
            s_canon, (succ_t_d, acc0)
        )

    v_lo_full, v_hi_full = carry["vkeys"][0], carry["vkeys"][1]
    M = V_v + Ba

    # -- merge_kernel stages (round 10, PERF.md §merge-kernel): the
    # streaming visited-dedup path the engine actually runs — B-row
    # candidate order sort, membership pass, winner merge append —
    # with the RETIRED (V_v + B)-row rebuild path re-timed below as
    # the per-wave A/B denominator.
    from stateright_tpu.ops.merge import (
        compact_winners, member_sorted, merge_sorted,
    )

    mi = c.merge_impl
    NF = min(F, Ba)
    print(f"merge_impl: {mi}")

    def s_csort(i, a):
        kh, kl, acc = a
        kh = kh.at[0].set(kh[0] ^ (i.astype(jnp.uint32) & 1))
        pos = jnp.arange(1, Ba + 1, dtype=jnp.uint32)
        s_hi, s_lo, s_pos = lax.sort((kh, kl, pos), num_keys=2)
        acc = acc.at[0].add(_fold(s_hi) + _fold(s_lo) + _fold(s_pos))
        return kh, kl, acc

    results[f"merge_kernel: cand-sort3 ({Ba})"] = _timed(
        s_csort, (ck_hi, ck_lo, acc0)
    )

    s_hi_d, s_lo_d = jax.jit(
        lambda kh, kl: lax.sort((kh, kl), num_keys=2)
    )(ck_hi, ck_lo)

    def s_member(i, a):
        vh, vl, qh, ql, acc = a
        vl = vl.at[0].set(vl[0] ^ (i.astype(jnp.uint32) & 1))
        m = member_sorted(vl[:V_v], vh[:V_v], ql, qh, impl=mi)
        acc = acc.at[0].add(_fold(m))
        return vh, vl, qh, ql, acc

    results[f"merge_kernel: member ({V_v} | {Ba})"] = _timed(
        s_member, (v_hi_full, v_lo_full, s_hi_d, s_lo_d, acc0)
    )

    def s_wcompact(i, a):
        # the order-preserving winner compaction (ops/merge.py,
        # impl-adaptive: O(B) rank scatter on the XLA fallback, one
        # 4-lane B-row sort on Pallas/TPU): part of the streaming
        # path's per-wave bill
        nw, sp, sl, sh, acc = a
        nw = nw.at[0].set(nw[0] ^ (i & 1).astype(bool))
        np_, wl, wh = compact_winners(nw, sp, sl, sh, NF, impl=mi)
        acc = acc.at[0].add(_fold(np_) + _fold(wl) + _fold(wh))
        return nw, sp, sl, sh, acc

    isnew_d = jnp.arange(Ba, dtype=jnp.uint32) % 5 != 0
    spos_d = jnp.arange(1, Ba + 1, dtype=jnp.uint32)
    results[f"merge_kernel: winner-compact ({Ba})"] = _timed(
        s_wcompact, (isnew_d, spos_d, s_lo_d, s_hi_d, acc0)
    )

    w_hi_d = s_hi_d[:NF]
    w_lo_d = s_lo_d[:NF]

    def s_append(i, a):
        vh, vl, wh, wl, acc = a
        vl = vl.at[0].set(vl[0] ^ (i.astype(jnp.uint32) & 1))
        m_lo, m_hi = merge_sorted(
            vl[:V_v], vh[:V_v], wl, wh, impl=mi
        )
        acc = acc.at[0].add(_fold(m_lo) + _fold(m_hi))
        return vh, vl, wh, wl, acc

    results[f"merge_kernel: append ({V_v}+{NF})"] = _timed(
        s_append, (v_hi_full, v_lo_full, w_hi_d, w_lo_d, acc0)
    )

    # -- RETIRED rebuild path (rounds 5-9), kept as the A/B record:
    # the (V_v + B)-row stable 3-lane concat sort + the (V_v + B)-row
    # winner-position sort the streaming path replaced ------------------
    def s_merge3(i, a):
        vh, vl, kh, kl, acc = a
        kh = kh.at[0].set(kh[0] ^ (i.astype(jnp.uint32) & 1))
        m_hi = jnp.concatenate([vh[:V_v], kh])
        m_lo = jnp.concatenate([vl[:V_v], kl])
        m_pos = jnp.concatenate([
            jnp.zeros(V_v, jnp.uint32),
            jnp.arange(1, Ba + 1, dtype=jnp.uint32),
        ])
        m_hi, m_lo, m_pos = lax.sort((m_hi, m_lo, m_pos), num_keys=2)
        acc = acc.at[0].add(_fold(m_hi) + _fold(m_lo) + _fold(m_pos))
        return vh, vl, kh, kl, acc

    results[f"retired: merge3 ({V_v}+{Ba})"] = _timed(
        s_merge3, (v_hi_full, v_lo_full, ck_hi, ck_lo, acc0)
    )

    # -- stage: 1-lane winner-position sort (retired with the merge) ---
    def s_nfpos(i, a):
        pos, acc = a
        pos = pos.at[0].set(pos[0] ^ (i.astype(jnp.uint32) & 1))
        (pos2,) = lax.sort((pos,), num_keys=1)
        acc = acc.at[0].add(_fold(pos2))
        return pos, acc

    nf_pos = jnp.arange(M, dtype=jnp.uint32)
    results[f"retired: nfpos1 ({M})"] = _timed(s_nfpos, (nf_pos, acc0))

    # -- stage: fetch winners (round 5: packed gathers — payload mode
    # when the padded [Ba, W+3] fits the flat budget, else a packed
    # 4-lane meta gather + successor recompute; PERF.md §gathers) -----
    pay_fetch = (not chunked) and (Ba * 512 <= c.flat_budget_bytes)
    ebits_dummy = jnp.zeros(F_f, jnp.uint32)

    if pay_fetch:
        # Mirror the engine's packed payload (succ ++ keys ++ meta —
        # the one seam transpose back to rows at the gather staging);
        # profile the fetch at BOTH the max width and a typical
        # NF-class width (the engine's third ladder axis).
        succ_all = jax.jit(
            lambda fr: step_cols(
                pair_states(fr, pidx // jnp.uint32(EV)), pslot
            )[0].T
        )(frontier_f)
        pay = jnp.concatenate(
            [succ_all, ck_lo[:, None], ck_hi[:, None],
             ebits_dummy[pidx // jnp.uint32(EV)][:, None]],
            axis=1,
        )

        for NF_c in sorted({min(F, Ba), min(131072, Ba)}, reverse=True):
            nf_row = jnp.arange(NF_c, dtype=jnp.uint32) % jnp.uint32(Ba)

            def s_fetch(i, a):
                py, nf, acc = a
                nf = (nf + i.astype(jnp.uint32)) % jnp.uint32(Ba)
                p = py[nf]
                acc = acc.at[0].add(_fold(p))
                return py, nf, acc

            results[f"fetch ({NF_c} winners, payload)"] = _timed(
                s_fetch, (pay, nf_row, acc0)
            )
    else:
        def s_fetch(i, a):
            fr, nf, acc = a
            nf = (nf + i.astype(jnp.uint32)) % jnp.uint32(Ba)
            par_row = pidx[nf] // jnp.uint32(EV)
            succ_w_t, _, _ = step_cols(
                pair_states(fr, par_row), pslot[nf]
            )
            q = ebits_dummy[par_row]
            acc = acc.at[0].add(_fold(succ_w_t) + _fold(q))
            return fr, nf, acc

        nf_row = jnp.arange(min(F, Ba), dtype=jnp.uint32) % jnp.uint32(Ba)
        results[f"fetch ({min(F, Ba)} winners, recompute)"] = _timed(
            s_fetch, (frontier_f, nf_row, acc0)
        )

    print(f"\n{'stage':42s} {'ms/wave':>9s}  (baseline-subtracted)")
    total = 0.0
    for k, v in results.items():
        print(f"  {k:40s} {v:9.2f}")
        if not k.startswith("retired:"):
            # the retired rebuild-path rows are the A/B record, not
            # part of the running wave — keep them out of the
            # out-of-stage wall arithmetic
            total += v
    print(f"  {'SUM (stage compute)':40s} {total:9.2f}")
    return c, total


def wave_wall(kind, n, caps, target, optimize=True):
    """--wave-wall: the out-of-stage attribution (VERDICT r5 items
    1-2). Runs the stage profile for the in-stage sum, then re-times
    ONE full wave body on the same captured carry and attributes the
    compiled one-wave program per HLO category
    (stateright_tpu/wavewall.py)."""
    from stateright_tpu.wavewall import format_report, wave_wall_report

    c, stage_sum = stage_profile(kind, n, caps, target)
    print(f"\n## wave-wall profile: {kind} {n}")
    rep = wave_wall_report(c)
    print(format_report(rep, stage_sum_ms=stage_sum))


def wave_profile(kind, n, caps, optimize=True):
    from stateright_tpu.report import Reporter

    rows = []

    class Rec(Reporter):
        def __init__(self):
            self.last = time.monotonic()

        def delay(self):
            return 0.0

        def report_checking(self, data):
            now = time.monotonic()
            rows.append(
                (now - self.last, data.unique_states, data.max_depth)
            )
            self.last = now

    _spawn(kind, n, caps, optimize=optimize).join()  # warm compile at the same shapes? (no:
    # waves_per_sync differs; still warms the persistent XLA cache)
    c2 = _spawn(kind, n, caps, waves_per_sync=1, optimize=optimize)
    rec = Rec()
    t0 = time.monotonic()
    c2._ensure_run(rec)
    total = time.monotonic() - t0
    rows.append((time.monotonic() - rec.last, c2.unique_state_count(),
                 c2.max_depth()))
    print(f"\n## wave profile: {kind} {n} (total {total:.3f}s incl "
          f"per-wave sync, unique={c2.unique_state_count()})")
    prev = 0
    for i, (dt, u, d) in enumerate(rows):
        print(f"  wave {i:3d}: {dt*1000:8.1f} ms  new={u-prev:8d}  "
              f"unique={u:9d} depth={d}")
        prev = u


def micro():
    """--micro: primitive microbench at engine row counts on
    SYNTHETIC keys (folded in from the retired
    tools/profile_sortmerge.py, round 14 — its sort#1/2/3 labels
    timed the per-wave visited re-sort the round-10 streaming merge
    killed). These rows price the CURRENT stage seams' primitives
    without engine data: the B-row 3-lane candidate sort, the
    streaming binary-search membership into the sorted visited
    prefix, the O(V + NF) linear merge append, and the winner-fetch
    row gather. ``stage_profile`` times the same seams on REAL
    mid-run data; use this one to separate primitive cost from
    data-shape effects."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from stateright_tpu.ops.merge import member_sorted, merge_sorted

    key = jax.random.PRNGKey(0)

    def rnd(shape, i=0):
        return jax.random.bits(jax.random.fold_in(key, i), shape,
                               dtype=jnp.uint32)

    V, B, NF, W = 1 << 21, 1 << 20, 1 << 19, 19
    acc0 = jnp.zeros(1, jnp.uint32)
    # 2-limb sorted visited prefix (the (hi, lo) key order the
    # engines keep)
    v_hi, v_lo = jax.jit(
        lambda h, l: lax.sort((h, l), num_keys=2)
    )(rnd((V,), 1), rnd((V,), 2))
    b_hi, b_lo = rnd((B,), 3), rnd((B,), 4)
    s_hi, s_lo = jax.jit(
        lambda h, l: lax.sort((h, l), num_keys=2)
    )(b_hi, b_lo)
    w_hi, w_lo = s_hi[:NF], s_lo[:NF]
    print(f"\n## primitive microbench (V={V}, B={B}, NF={NF}, "
          f"per-op ms, in-loop amortized over {REPS} reps)")
    rows = {}

    def s_csort(i, a):
        kh, kl, acc = a
        kh = kh.at[0].set(kh[0] ^ (i.astype(jnp.uint32) & 1))
        pos = jnp.arange(1, B + 1, dtype=jnp.uint32)
        o_hi, o_lo, o_pos = lax.sort((kh, kl, pos), num_keys=2)
        acc = acc.at[0].add(_fold(o_hi) + _fold(o_lo) + _fold(o_pos))
        return kh, kl, acc

    rows[f"cand sort3 (B={B})"] = _timed(s_csort, (b_hi, b_lo, acc0))

    def s_member(i, a):
        vh, vl, qh, ql, acc = a
        vl = vl.at[0].set(vl[0] ^ (i.astype(jnp.uint32) & 1))
        m = member_sorted(vl, vh, ql, qh, impl="xla")
        acc = acc.at[0].add(_fold(m))
        return vh, vl, qh, ql, acc

    rows[f"member binsearch (V={V} | B={B})"] = _timed(
        s_member, (v_hi, v_lo, s_hi, s_lo, acc0)
    )

    def s_append(i, a):
        vh, vl, wh, wl, acc = a
        vl = vl.at[0].set(vl[0] ^ (i.astype(jnp.uint32) & 1))
        m_lo, m_hi = merge_sorted(vl, vh, wl, wh, impl="xla")
        acc = acc.at[0].add(_fold(m_lo) + _fold(m_hi))
        return vh, vl, wh, wl, acc

    rows[f"linear merge (V={V}+{NF})"] = _timed(
        s_append, (v_hi, v_lo, w_hi, w_lo, acc0)
    )

    pay = rnd((B, W), 5)
    idx = jnp.arange(NF, dtype=jnp.uint32) % jnp.uint32(B)

    def s_gather(i, a):
        py, nf, acc = a
        nf = (nf + i.astype(jnp.uint32)) % jnp.uint32(B)
        acc = acc.at[0].add(_fold(py[nf]))
        return py, nf, acc

    rows[f"fetch gather ({NF} rows W={W} from {B})"] = _timed(
        s_gather, (pay, idx, acc0)
    )

    for k, v in rows.items():
        print(f"  {k:44s} {v:9.2f} ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paxos", type=int)
    ap.add_argument("--twopc", type=int)
    ap.add_argument(
        "--twopc-compiled", type=int,
        help="compiled 2pc system lane (two_phase_sys_compiled_encoded)"
        " at rm=N — the round-23 parity lane; pair with "
        "--no-optimize for the codegen-ablation denominator",
    )
    ap.add_argument(
        "--paxos-compiled", type=int,
        help="compiled paxos lane at N clients (reachable-mode "
        "harvest runs once before the profiled stages)",
    )
    ap.add_argument(
        "--no-optimize", action="store_true",
        help="compile the *-compiled lanes with optimize=False (the "
        "naive per-action codegen) — the per-stage A/B denominator "
        "for PERF.md §compiled-parity",
    )
    ap.add_argument("--target", type=int)
    ap.add_argument("--wave-profile", action="store_true")
    ap.add_argument("--wave-wall", action="store_true")
    ap.add_argument(
        "--micro", action="store_true",
        help="primitive microbench at engine row counts on synthetic "
        "keys (no model needed; the retired profile_sortmerge.py's "
        "successor)",
    )
    ap.add_argument(
        "--trace", nargs="?", const="default",
        choices=("default", "deep"), default=None,
        help="record run telemetry for the profiled engine runs and "
        "write TRACE_r*.jsonl + TRACE_r*.trace.json artifacts "
        "(stateright_tpu/telemetry.py)",
    )
    args = ap.parse_args()

    import jax

    print(f"backend: {jax.devices()}")

    if args.micro:
        micro()
        return

    # Structural sizes from the one shared table (capacity from the
    # pinned state counts, frontier from measured wave peaks);
    # per-wave BUDGETS are auto-sized — TUNED_ENGINE_CAPS and the
    # per-lane caps tables are gone (VERDICT r5 item 6).
    if args.paxos:
        from stateright_tpu.models.paxos_tpu import STRUCTURAL_SIZES

        caps = dict(STRUCTURAL_SIZES[args.paxos])
        caps["cand_capacity"] = "auto"
        kind, n = "paxos", args.paxos
        default_target = {3: 600_000, 4: 1_200_000, 5: 2_400_000}.get(
            args.paxos, 1_000_000
        )
    elif args.twopc:
        kind, n = "twopc", args.twopc
        caps = {
            8: dict(capacity=1 << 21, frontier_capacity=1 << 19,
                    cand_capacity="auto"),
            9: dict(capacity=11 << 20, frontier_capacity=3 << 19,
                    cand_capacity="auto", tile_rows=1 << 20),
        }[n]
        default_target = {8: 900_000, 9: 5_000_000}[n]
    elif args.twopc_compiled:
        kind, n = "twopc-compiled", args.twopc_compiled
        # The bench parity-lane shapes (identical to the hand "2pc
        # rm=N" lanes — the space is count-identical, so the wave
        # peaks are too); other rm counts fall back to the same
        # ~2.53 bits/RM growth the hand lanes follow.
        import math

        bench_caps = {
            5: dict(capacity=1 << 14, frontier_capacity=1 << 11),
            6: dict(capacity=1 << 16, frontier_capacity=1 << 14),
            7: dict(capacity=1 << 19, frontier_capacity=1 << 16),
        }
        if n in bench_caps:
            caps = dict(bench_caps[n], cand_capacity="auto")
        else:
            cap = 1 << max(10, math.ceil(2.6 * n + 1.5))
            caps = dict(capacity=cap,
                        frontier_capacity=max(256, cap // 4),
                        cand_capacity="auto")
        default_target = {5: 4_000, 6: 25_000, 7: 150_000}.get(
            n, max(512, caps["capacity"] // 4)
        )
    elif args.paxos_compiled:
        kind, n = "paxos-compiled", args.paxos_compiled
        caps = dict(capacity=1 << 15, frontier_capacity=1 << 12,
                    cand_capacity="auto")
        default_target = 8_000
    else:
        raise SystemExit(
            "pass --paxos N, --twopc N, --twopc-compiled N or "
            "--paxos-compiled N"
        )
    spawn_kw = (
        {"optimize": False} if args.no_optimize else {}
    )
    if args.no_optimize and not kind.endswith("compiled"):
        raise SystemExit("--no-optimize only applies to the "
                         "*-compiled lanes")

    def dispatch():
        if args.wave_profile:
            wave_profile(kind, n, caps, **spawn_kw)
        elif args.wave_wall:
            wave_wall(kind, n, caps, args.target or default_target,
                      **spawn_kw)
        else:
            stage_profile(kind, n, caps,
                          args.target or default_target, **spawn_kw)

    if args.trace is None:
        dispatch()
        return
    import sys as _sys

    _sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from stateright_tpu.telemetry import RunTracer, write_artifacts

    tracer = RunTracer(level=args.trace)
    try:
        with tracer.activate():
            dispatch()
    finally:
        # a failed/interrupted profile's partial trace still lands
        if tracer.events:
            jsonl, chrome = write_artifacts(tracer)
            print(f"trace: wrote {jsonl} + {chrome}")


if __name__ == "__main__":
    main()
