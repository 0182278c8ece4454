"""Vectorized single-decree Paxos: the north-star TPU workload.

Encodes the full actor-model state of :mod:`stateright_tpu.models.paxos`
(reference examples/paxos.rs + actor/model_state.rs) — three server
``PaxosState``s, the register clients, the unordered-nonduplicating
network, and the in-state ``LinearizabilityTester`` history — as a
7-lane ``uint32`` vector, with every deliverable envelope compiled to a
branchless lane-update (SURVEY.md §7 step 5: the actor→encoding
compilation this framework exists for).

Three structural discoveries (validated by exhaustive host-model probes
over the pinned 16,668-state space) make a tight encoding possible:

1. **The envelope universe is finite and small.** With ``put_count=1``
   the reachable (src, dst, msg) alphabet is 68 envelopes; the
   provably-sound overapproximation enumerated here (coexistence +
   choosable-proposal closure over ballots) has 70. Every envelope is
   one bit: the network — a multiset in the reference
   (network.rs:55) — degenerates to a *set* here (max multiplicity 1,
   probe-verified), so three ``uint32`` lanes hold it canonically and
   "deliver envelope k" is a static per-bit transition: src, dst and
   message content are compile-time constants folded into each of the
   K=70 action slots.

2. **History phases.** The model prunes actor-no-op deliveries before
   the history hook runs (model.rs:317-319), so stale ``PutOk``/
   ``GetOk`` never corrupt the tester: each client's tester state
   follows the strict progression ``W in-flight → W done + R in-flight
   → W+R done``, and — because only one proposal is ever decided — the
   cross-thread snapshots of linearizability.rs:114-126 are always
   empty. Two bits of phase + two bits of read-value per client encode
   the tester exactly.

3. **The linearizability verdict is a 144-entry truth table.** Because
   the tester state is (phase, read_value) per client, the reference's
   backtracking serializer (linearizability.rs:196-284) has only
   ``(4*3)^2`` possible inputs. The table is precomputed host-side *by
   the real serializer* at encoding-build time and the device-side
   ``always linearizable`` condition is a single gather — the
   device-filters/host-confirms split SURVEY §7 step 6 calls for,
   taken to its limit.

Unreachable-by-proof code paths (e.g. a Put at ballot round ≥ 2, an
out-of-universe ``last_accepted``) set a poison bit that perturbs the
fingerprint, so any soundness gap surfaces as a differential-test
failure instead of a silent wrong answer; ``encode()`` raises on any
host state outside the bounded universe.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np

from ..actor import Id
from ..actor.register import Get, GetOk, Internal, Put, PutOk
from ..encoding import EncodedModelBase
from ..ops.bitmask import mask_words
from ..semantics.register import ReadOk, ReadOp, WriteOk, WriteOp
from .paxos import (
    Accept,
    Accepted,
    Decided,
    PaxosModelCfg,
    Prepare,
    Prepared,
    paxos_model,
)

# -- lane layout ---------------------------------------------------------
# The layout is COMPUTED per configuration (bit widths grow with the
# client count): each server gets a main lane [ballot enum | proposal
# code | accepted la-code | is_decided | accepts id-mask | prepares]
# and, when the prepares map no longer fits (client_count=4), a second
# per-server lane holding prepares alone. The client/history lane packs
# per client j at bit j*stride:
#   +0 (2b)     actor phase: 0 awaiting PutOk, 1 awaiting GetOk, 2 done
#   +2 (2b)     history phase: 0 W-inflight, 1 W-done, 2 +R-inflight,
#               3 done
#   +4 (W_RV b) read value code (0 '\x00', 1+ value index)
_B_POISON = 30


def _bits(n: int) -> int:
    """Bits to hold values 0..n."""
    return max(1, n.bit_length())


def _field(lane, shift, width, xp):
    return (lane >> xp.uint32(shift)) & xp.uint32((1 << width) - 1)


def _set_field(lane, shift, width, value, xp):
    mask = xp.uint32(((1 << width) - 1) << shift)
    return (lane & ~mask) | (
        (value.astype(xp.uint32) if hasattr(value, "astype") else xp.uint32(value))
        << xp.uint32(shift)
    ) & mask


@dataclass(frozen=True)
class EnvSpec:
    """One envelope of the bounded universe; all fields are host-side
    constants folded into the compiled transition."""

    src: int
    dst: int
    kind: str  # put|get|putok|getok|prepare|prepared|accept|accepted|decided
    ballot: int = 0      # ballot enum
    prop: int = 0        # proposal code
    la: int = 0          # last_accepted la-code (prepared)
    value: int = 0       # value code (getok)


class PaxosEncoded(EncodedModelBase):
    """EncodedModel for ``paxos_model(PaxosModelCfg(...))``.

    Supports the reference benchmark shapes: 3 servers, 1 put per
    client, 1-4 clients (``paxos check N``, examples/paxos.rs:352-465;
    2c/3s pinned at 16,668, paxos.rs:325). The lane layout, ballot
    universe, and coexistence closure are computed per configuration —
    client_count=4 puts two proposals on leader 0 and moves the
    prepares maps to dedicated per-server lanes.
    """

    def __init__(self, cfg: PaxosModelCfg, network=None):
        if cfg.server_count != 3 or cfg.put_count != 1:
            raise ValueError(
                "PaxosEncoded supports server_count=3, put_count=1 "
                f"(got {cfg})"
            )
        if not (1 <= cfg.client_count <= 5):
            raise ValueError(
                f"PaxosEncoded supports 1-5 clients (got {cfg.client_count})"
            )
        if network is not None and type(network).__name__ != (
            "UnorderedNonDuplicating"
        ):
            raise ValueError(
                "PaxosEncoded models the unordered non-duplicating network"
            )
        self.cfg = cfg
        self.S = cfg.server_count
        self.C = cfg.client_count
        self.clients = list(range(self.S, self.S + self.C))
        self.host_model = paxos_model(cfg)

        # Proposals: client i's single put (req_id=i, requester=i,
        # value chr(ord('A')+i-S)); code = 1 + index (req-id order).
        self.values = [chr(ord("A") + i - self.S) for i in self.clients]
        self.proposals = [
            (i, Id(i), self.values[j]) for j, i in enumerate(self.clients)
        ]
        self.P = len(self.proposals)

        # Ballots. Leaders = put-target servers (client i -> i % S);
        # with 4 clients on 3 servers, leader 0 serves two clients but
        # still Puts at most once (proposal-None guard), so each leader
        # owns exactly one put-ballot and rounds cap at the LEADER
        # count: a Put at round r requires the server to have adopted
        # some round-(r-1) ballot first, and the support chain
        # 1, 2, ..., r needs r distinct leaders.
        self.leaders = sorted({i % self.S for i in self.clients})
        ballots = [(r, l) for r in range(1, len(self.leaders) + 1)
                   for l in self.leaders]
        ballots.sort()
        #: ballot enum: 0 = initial (0, Id(0)); 1.. = sorted reachable
        self.ballots = ballots
        self.ballot_enum = {(0, Id(0)): 0}
        for n, (r, l) in enumerate(ballots):
            self.ballot_enum[(r, Id(l))] = n + 1
        self.NB = len(ballots)

        # Joint feasibility: an assignment round[l] (or None = l never
        # Put) is realizable iff every assigned round r >= 2 is
        # supported by some OTHER leader assigned exactly r-1. Two
        # ballots coexist iff some realizable assignment contains both
        # — computed by brute force over the <= (R+1)^|leaders|
        # assignments instead of a hand-derived pair rule (the round-2
        # rule was specific to two leaders).
        import itertools as _it

        R = len(self.leaders)
        feasible_pairs: set = set()
        for rounds_assign in _it.product(
            [None] + list(range(1, R + 1)), repeat=R
        ):
            ok = True
            for l_idx, r in enumerate(rounds_assign):
                if r is not None and r >= 2 and not any(
                    r2 == r - 1
                    for l2_idx, r2 in enumerate(rounds_assign)
                    if l2_idx != l_idx and r2 is not None
                ):
                    ok = False
                    break
            if not ok:
                continue
            assigned = [
                self.ballot_enum[(r, Id(self.leaders[l_idx]))]
                for l_idx, r in enumerate(rounds_assign)
                if r is not None
            ]
            for b1 in assigned:
                for b2 in assigned:
                    feasible_pairs.add((b1, b2))

        def coexists(b1: int, b2: int) -> bool:
            """May ballot enums b1 < b2 both exist in one run?"""
            return (b1, b2) in feasible_pairs

        # choosable(b): proposals a leader can drive under ballot b —
        # its own put, or any adoptable last_accepted from a lower
        # coexisting ballot (closure).
        own_prop = {}
        for j, i in enumerate(self.clients):
            own_prop.setdefault(i % self.S, []).append(j + 1)
        choosable: dict[int, set] = {}
        la_universe: dict[int, list] = {}
        for b in range(1, self.NB + 1):
            _, l = ballots[b - 1]
            ch = set(own_prop.get(l, []))
            las = [0]
            for b2 in range(1, b):
                if coexists(b2, b):
                    for p in sorted(choosable[b2]):
                        las.append(1 + (b2 - 1) * self.P + (p - 1))
                        ch.add(p)
            choosable[b] = ch
            la_universe[b] = las
        self.choosable = {b: sorted(ch) for b, ch in choosable.items()}
        self.la_universe = la_universe

        # -- computed lane layout (widths scale with NB and P) -----------
        la_max = self.NB * self.P          # la codes 0..la_max
        self.W_BALLOT = _bits(self.NB)
        self.W_PROP = _bits(self.P)
        self.W_ACC = _bits(la_max)
        self.W_ACCEPTS = self.S
        self.W_PREP = _bits(1 + la_max)    # prepares entry: 0 | 1+la
        self.B_BALLOT = 0
        self.B_PROP = self.B_BALLOT + self.W_BALLOT
        self.B_ACC = self.B_PROP + self.W_PROP
        self.B_DEC = self.B_ACC + self.W_ACC
        self.B_ACCEPTS = self.B_DEC + 1
        main_bits = self.B_ACCEPTS + self.W_ACCEPTS
        # prepares ride in the main lane when they fit, else each
        # server gets a dedicated prepares lane (client_count=4).
        self.two_lane = main_bits + self.S * self.W_PREP > 32
        self.B_PREP = 0 if self.two_lane else main_bits
        #: client/history lane stride and read-value width
        self.W_RV = _bits(self.P)
        self.CST = 4 + self.W_RV
        #: clients per client-lane (bit 30 of lane 0 is the poison
        #: bit); 5 clients spill onto a second client lane.
        self.CPL = _B_POISON // self.CST
        self.n_client_lanes = -(-self.C // self.CPL)
        #: linearizability-table radix per client: phase * TBV + rv
        self.TBV = self.P + 1
        self.TB = 4 * self.TBV

        self.universe = self._build_universe()
        self.index = {self._env_key(e): k for k, e in enumerate(self.universe)}
        self.K = len(self.universe)
        self.net_lanes = mask_words(self.K)
        self.n_state_lanes = (
            self.S * (2 if self.two_lane else 1) + self.n_client_lanes
        )
        self.width = self.n_state_lanes + self.net_lanes
        self.max_actions = self.K
        self._lin_table = self._build_lin_table()

    # -- computed-layout accessors ----------------------------------------

    def _clane_index(self, j: int = 0) -> int:
        """Lane of client j's fields (j // CPL picks the client lane);
        the poison bit lives on client lane 0."""
        return self.S * (2 if self.two_lane else 1) + j // self.CPL

    def _coff(self, j: int) -> int:
        """Bit offset of client j inside its client lane."""
        return (j % self.CPL) * self.CST

    def _prep_lane(self, server: int) -> int:
        return self.S + server if self.two_lane else server

    def cache_key(self):
        return (self.C, self.S, self.cfg.put_count)

    # -- universe ----------------------------------------------------------

    def _build_universe(self) -> list:
        u: list[EnvSpec] = []
        S, P = self.S, self.P
        # Puts and Gets (register.rs:144-236 request scheme).
        for j, c in enumerate(self.clients):
            u.append(EnvSpec(c, c % S, "put", prop=j + 1))
        for j, c in enumerate(self.clients):
            u.append(EnvSpec(c, (c + 1) % S, "get"))
        # PutOk from any leader that can drive this client's proposal.
        for l in self.leaders:
            for j, c in enumerate(self.clients):
                if any(j + 1 in self.choosable[b]
                       for b in range(1, self.NB + 1)
                       if self.ballots[b - 1][1] == l):
                    u.append(EnvSpec(l, c, "putok", prop=j + 1))
        # GetOk from the get-target server, any decided value.
        for j, c in enumerate(self.clients):
            for v in range(1, P + 1):
                u.append(EnvSpec((c + 1) % S, c, "getok", value=v))
        # Internal protocol messages.
        for b in range(1, self.NB + 1):
            _, l = self.ballots[b - 1]
            peers = [d for d in range(S) if d != l]
            for d in peers:
                u.append(EnvSpec(l, d, "prepare", ballot=b))
            for d in peers:
                for la in self.la_universe[b]:
                    u.append(EnvSpec(d, l, "prepared", ballot=b, la=la))
            for p in self.choosable[b]:
                for d in peers:
                    u.append(EnvSpec(l, d, "accept", ballot=b, prop=p))
            for d in peers:
                u.append(EnvSpec(d, l, "accepted", ballot=b))
            for p in self.choosable[b]:
                for d in peers:
                    u.append(EnvSpec(l, d, "decided", ballot=b, prop=p))
        return u

    def _env_key(self, e: EnvSpec) -> tuple:
        return (e.src, e.dst, e.kind, e.ballot, e.prop, e.la, e.value)

    # -- host <-> codes ----------------------------------------------------

    def _ballot_code(self, ballot: Tuple) -> int:
        code = self.ballot_enum.get((ballot[0], ballot[1]))
        if code is None:
            raise ValueError(f"ballot outside universe: {ballot!r}")
        return code

    def _prop_code(self, proposal: Optional[Tuple]) -> int:
        if proposal is None:
            return 0
        for j, p in enumerate(self.proposals):
            if p == proposal:
                return j + 1
        raise ValueError(f"proposal outside universe: {proposal!r}")

    def _la_code(self, la: Optional[Tuple]) -> int:
        if la is None:
            return 0
        b = self._ballot_code(la[0])
        p = self._prop_code(la[1])
        if b == 0 or p == 0:
            raise ValueError(f"last_accepted outside universe: {la!r}")
        return 1 + (b - 1) * self.P + (p - 1)

    def _value_code(self, value: str) -> int:
        if value == "\x00":
            return 0
        try:
            return 1 + self.values.index(value)
        except ValueError:
            raise ValueError(f"value outside universe: {value!r}")

    def _msg_env_key(self, src: int, dst: int, msg: Any) -> tuple:
        if isinstance(msg, Put):
            return (src, dst, "put", 0, self._prop_code((msg.req_id, Id(src), msg.value)), 0, 0)
        if isinstance(msg, Get):
            return (src, dst, "get", 0, 0, 0, 0)
        if isinstance(msg, PutOk):
            j = self.clients.index(msg.req_id)  # first-op req_id == client id
            return (src, dst, "putok", 0, j + 1, 0, 0)
        if isinstance(msg, GetOk):
            return (src, dst, "getok", 0, 0, 0, self._value_code(msg.value))
        if isinstance(msg, Internal):
            m = msg.msg
            if isinstance(m, Prepare):
                return (src, dst, "prepare", self._ballot_code(m.ballot), 0, 0, 0)
            if isinstance(m, Prepared):
                return (
                    src, dst, "prepared", self._ballot_code(m.ballot),
                    0, self._la_code(m.last_accepted), 0,
                )
            if isinstance(m, Accept):
                return (
                    src, dst, "accept", self._ballot_code(m.ballot),
                    self._prop_code(m.proposal), 0, 0,
                )
            if isinstance(m, Accepted):
                return (src, dst, "accepted", self._ballot_code(m.ballot), 0, 0, 0)
            if isinstance(m, Decided):
                return (
                    src, dst, "decided", self._ballot_code(m.ballot),
                    self._prop_code(m.proposal), 0, 0,
                )
        raise ValueError(f"message outside universe: {msg!r}")

    # -- encode ------------------------------------------------------------

    def encode(self, state) -> np.ndarray:
        vec = np.zeros(self.width, dtype=np.uint32)
        for i in range(self.S):
            s = state.actor_states[i].state
            lane = 0
            lane |= self._ballot_code(s.ballot) << self.B_BALLOT
            lane |= self._prop_code(s.proposal) << self.B_PROP
            lane |= self._la_code(s.accepted) << self.B_ACC
            lane |= (1 if s.is_decided else 0) << self.B_DEC
            mask = 0
            for sid in s.accepts:
                mask |= 1 << int(sid)
            lane |= mask << self.B_ACCEPTS
            prep = 0
            for sid, la in s.prepares.items():
                prep |= (1 + self._la_code(la)) << (
                    self.B_PREP + self.W_PREP * int(sid)
                )
            if self.two_lane:
                vec[self._prep_lane(i)] = prep
                vec[i] = lane
            else:
                vec[i] = lane | prep
        for j, c in enumerate(self.clients):
            cs = state.actor_states[c]
            if cs.awaiting == c and cs.op_count == 1:
                phase = 0
            elif cs.awaiting == 2 * c and cs.op_count == 2:
                phase = 1
            elif cs.awaiting is None and cs.op_count == 3:
                phase = 2
            else:
                raise ValueError(f"client state outside universe: {cs!r}")
            hphase, rval = self._history_phase(state.history, Id(c))
            off = self._coff(j)
            vec[self._clane_index(j)] |= np.uint32(
                (phase << off) | (hphase << (off + 2))
                | (rval << (off + 4))
            )
        for env, count in self._network_items(state.network):
            if count != 1:
                raise ValueError(
                    f"envelope multiplicity {count} outside universe: {env!r}"
                )
            key = self._msg_env_key(int(env.src), int(env.dst), env.msg)
            k = self.index.get(key)
            if k is None:
                raise ValueError(f"envelope outside universe: {env!r}")
            vec[self.n_state_lanes + k // 32] |= np.uint32(1 << (k % 32))
        if any(state.crashed) or any(t for t in state.timers_set):
            raise ValueError("crashes/timers outside the paxos universe")
        return vec

    def _network_items(self, network):
        from collections import Counter

        return Counter(network.iter_all()).items()

    def _history_phase(self, history, thread: Id) -> tuple[int, int]:
        if not history.is_valid:
            raise ValueError("invalid history outside universe")
        completed = dict(history.history_by_thread).get(thread, ())
        in_flight = dict(history.in_flight_by_thread).get(thread)
        j = self.clients.index(int(thread))
        wv = self.values[j]
        rval = 0
        if len(completed) == 0 and in_flight is not None:
            snap, op = in_flight
            if snap != () or not isinstance(op, WriteOp) or op.value != wv:
                raise ValueError(f"history outside universe: {in_flight!r}")
            phase = 0
        elif len(completed) >= 1:
            snap, op, ret = completed[0]
            if (
                snap != ()
                or not isinstance(op, WriteOp)
                or op.value != wv
                or not isinstance(ret, WriteOk)
            ):
                raise ValueError(f"history outside universe: {completed!r}")
            if len(completed) == 1 and in_flight is None:
                phase = 1
            elif len(completed) == 1:
                snap, op = in_flight
                if snap != () or not isinstance(op, ReadOp):
                    raise ValueError(f"history outside universe: {in_flight!r}")
                phase = 2
            elif len(completed) == 2 and in_flight is None:
                snap, op, ret = completed[1]
                if snap != () or not isinstance(op, ReadOp) or not isinstance(ret, ReadOk):
                    raise ValueError(f"history outside universe: {completed!r}")
                phase = 3
                rval = self._value_code(ret.value)
            else:
                raise ValueError(f"history outside universe: {completed!r}")
        else:
            raise ValueError(f"history outside universe: thread {thread!r}")
        return phase, rval

    def init_vecs(self) -> np.ndarray:
        return np.stack(
            [self.encode(s) for s in self.host_model.init_states()]
        )

    # -- linearizability truth table --------------------------------------

    def _build_lin_table(self) -> np.ndarray:
        """Evaluate the REAL serializer on every (phase, rval) combo.

        Reachable states have at most one client past phase 0 (only one
        proposal is ever decided); combos with both clients progressed
        are marked not-linearizable so that, were one ever produced,
        it would surface as a loud counterexample rather than pass
        silently.
        """
        from ..semantics import LinearizabilityTester, Register

        size = self.TB ** self.C
        table = np.zeros(size, dtype=bool)

        def fill(phases, rvals):
            idx = 0
            for ph, rv in zip(phases, rvals):
                idx = idx * self.TB + ph * self.TBV + rv
            tester = LinearizabilityTester(Register("\x00"))
            for j in range(self.C):
                tester = tester.on_invoke(
                    Id(self.clients[j]), WriteOp(self.values[j])
                )
            for j in range(self.C):
                t = Id(self.clients[j])
                ph, rv = phases[j], rvals[j]
                if ph >= 1:
                    tester = tester.on_return(t, WriteOk())
                if ph >= 2:
                    tester = tester.on_invoke(t, ReadOp())
                if ph >= 3:
                    v = "\x00" if rv == 0 else self.values[rv - 1]
                    tester = tester.on_return(t, ReadOk(v))
            table[idx] = tester.serialized_history() is not None

        # Only all-zero and single-progressed combos can be reached
        # (single decree: one proposal is ever decided, so one client
        # ever advances); everything else stays False so it would
        # surface as a loud counterexample — and the fill is C*12
        # serializer runs instead of (4*TBV)^C (8M at 5 clients).
        fill([0] * self.C, [0] * self.C)
        for j in range(self.C):
            for ph in (1, 2, 3):
                for rv in range(self.TBV):
                    phases = [0] * self.C
                    rvals = [0] * self.C
                    phases[j] = ph
                    rvals[j] = rv
                    fill(phases, rvals)
        return table

    # -- device step -------------------------------------------------------

    def _bit(self, vec, k, xp):
        lane = vec[self.n_state_lanes + k // 32]
        return ((lane >> xp.uint32(k % 32)) & xp.uint32(1)) != 0

    def _net_update(self, vec, clear_k, send_masks, xp):
        """Clear bit ``clear_k``; OR per-lane ``send_masks`` in."""
        out = vec
        for ln in range(self.net_lanes):
            lane = vec[self.n_state_lanes + ln]
            if clear_k // 32 == ln:
                lane = lane & ~xp.uint32(1 << (clear_k % 32))
            m = send_masks.get(ln)
            if m is not None:
                lane = lane | m
            out = out.at[self.n_state_lanes + ln].set(lane)
        return out

    def _const_mask(self, keys) -> dict:
        """Per-lane OR mask for a set of universe keys (host consts)."""
        masks: dict[int, int] = {}
        for key in keys:
            k = self.index[key]
            masks[k // 32] = masks.get(k // 32, 0) | (1 << (k % 32))
        return masks

    def step_vec(self, vec):
        import jax.numpy as jnp

        succs, valids = [], []
        for k, e in enumerate(self.universe):
            s, valid = self._deliver(vec, k, e, jnp)
            succs.append(s)
            valids.append(valid)
        return jnp.stack(succs), jnp.stack(valids)

    def _deliver(self, vec, k, e: EnvSpec, xp):
        present = self._bit(vec, k, xp)
        handler = getattr(self, f"_on_{e.kind}")
        new_vec, handled = handler(vec, k, e, xp)
        return new_vec, present & handled

    # Per-kind handlers: return (successor_vec, handled). All message
    # fields are Python constants; only lane contents are traced.

    def _on_put(self, vec, k, e: EnvSpec, xp):
        lane = vec[e.dst]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        prop = _field(lane, self.B_PROP, self.W_PROP, xp)
        ballot = _field(lane, self.B_BALLOT, self.W_BALLOT, xp)
        acc = _field(lane, self.B_ACC, self.W_ACC, xp)
        handled = (~decided) & (prop == 0)
        # New ballot: (round+1, dst). Rounds for this leader:
        rounds = sorted(
            r for (r, l) in self.ballots if l == e.dst
        )
        round_of = xp.asarray(
            [0] + [r for (r, _) in self.ballots], dtype=xp.uint32
        )
        cur_round = round_of[ballot]
        nb = xp.uint32(0)
        poison = handled & xp.bool_(True)
        for r in rounds:
            hit = cur_round == (r - 1)
            nb = xp.where(hit, xp.uint32(self.ballot_enum[(r, Id(e.dst))]), nb)
            poison = poison & ~hit
        new_lane = xp.uint32(0)
        new_lane = new_lane | (nb << self.B_BALLOT)
        new_lane = new_lane | (xp.uint32(e.prop) << self.B_PROP)
        new_lane = new_lane | (acc << self.B_ACC)
        # Put RESETS prepares to {self: accepted} (paxos.rs:160-176).
        prep = (acc + 1) << xp.uint32(self.W_PREP * e.dst)
        # Sends: Prepare(nb) to both peers — select the mask by round.
        masks: dict = {}
        for r in rounds:
            b = self.ballot_enum[(r, Id(e.dst))]
            keys = [
                (e.dst, d, "prepare", b, 0, 0, 0)
                for d in range(self.S)
                if d != e.dst
            ]
            cm = self._const_mask(keys)
            hit = cur_round == (r - 1)
            for ln, m in cm.items():
                masks[ln] = masks.get(ln, xp.uint32(0)) | xp.where(
                    hit, xp.uint32(m), xp.uint32(0)
                )
        if self.two_lane:
            out = vec.at[e.dst].set(xp.where(handled, new_lane, lane))
            pl = self._prep_lane(e.dst)
            out = out.at[pl].set(xp.where(handled, prep, vec[pl]))
        else:
            new_lane = new_lane | (prep << xp.uint32(self.B_PREP))
            out = vec.at[e.dst].set(xp.where(handled, new_lane, lane))
        out = self._poison(out, poison, xp)
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_get(self, vec, k, e: EnvSpec, xp):
        lane = vec[e.dst]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        acc = _field(lane, self.B_ACC, self.W_ACC, xp)
        handled = decided
        # Reply GetOk(value of accepted proposal).
        val = xp.where(acc > 0, ((acc - 1) % xp.uint32(self.P)) + 1, 0)
        masks: dict = {}
        for v in range(1, self.P + 1):
            key = (e.dst, e.src, "getok", 0, 0, 0, v)
            if key not in self.index:
                continue
            cm = self._const_mask([key])
            hit = handled & (val == v)
            for ln, m in cm.items():
                masks[ln] = masks.get(ln, xp.uint32(0)) | xp.where(
                    hit, xp.uint32(m), xp.uint32(0)
                )
        out = self._net_update(vec, k, masks, xp)
        return out, handled

    def _on_putok(self, vec, k, e: EnvSpec, xp):
        j = self.clients.index(e.dst)
        cl = self._clane_index(j)
        off = self._coff(j)
        lane = vec[cl]
        phase = _field(lane, off, 2, xp)
        handled = phase == 0
        new_lane = _set_field(lane, off, 2, xp.uint32(1), xp)
        # History: W returns, R invoked (phases 0 -> 2).
        new_lane = _set_field(
            new_lane, off + 2, 2, xp.uint32(2), xp
        )
        out = vec.at[cl].set(xp.where(handled, new_lane, lane))
        get_key = (e.dst, (e.dst + 1) % self.S, "get", 0, 0, 0, 0)
        cm = self._const_mask([get_key])
        masks = {
            ln: xp.where(handled, xp.uint32(m), xp.uint32(0))
            for ln, m in cm.items()
        }
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_getok(self, vec, k, e: EnvSpec, xp):
        j = self.clients.index(e.dst)
        cl = self._clane_index(j)
        off = self._coff(j)
        lane = vec[cl]
        phase = _field(lane, off, 2, xp)
        handled = phase == 1
        new_lane = _set_field(lane, off, 2, xp.uint32(2), xp)
        new_lane = _set_field(
            new_lane, off + 2, 2, xp.uint32(3), xp
        )
        new_lane = _set_field(
            new_lane, off + 4, self.W_RV, xp.uint32(e.value), xp
        )
        out = vec.at[cl].set(xp.where(handled, new_lane, lane))
        out = self._net_update(out, k, {}, xp)
        return out, handled

    def _on_prepare(self, vec, k, e: EnvSpec, xp):
        lane = vec[e.dst]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        ballot = _field(lane, self.B_BALLOT, self.W_BALLOT, xp)
        acc = _field(lane, self.B_ACC, self.W_ACC, xp)
        handled = (~decided) & (ballot < e.ballot)
        new_lane = _set_field(
            lane, self.B_BALLOT, self.W_BALLOT, xp.uint32(e.ballot), xp
        )
        # Send Prepared(b, la=accepted) to the leader; select the
        # envelope by the acceptor's current accepted code.
        masks: dict = {}
        covered = handled & xp.bool_(False)
        for la in self.la_universe[e.ballot]:
            key = (e.dst, e.src, "prepared", e.ballot, 0, la, 0)
            cm = self._const_mask([key])
            hit = handled & (acc == la)
            covered = covered | hit
            for ln, m in cm.items():
                masks[ln] = masks.get(ln, xp.uint32(0)) | xp.where(
                    hit, xp.uint32(m), xp.uint32(0)
                )
        poison = handled & ~covered
        out = vec.at[e.dst].set(xp.where(handled, new_lane, lane))
        out = self._poison(out, poison, xp)
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_prepared(self, vec, k, e: EnvSpec, xp):
        l = e.dst
        lane = vec[l]
        plane = vec[self._prep_lane(l)]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        ballot = _field(lane, self.B_BALLOT, self.W_BALLOT, xp)
        prop = _field(lane, self.B_PROP, self.W_PROP, xp)
        handled = (~decided) & (ballot == e.ballot)
        # prepares[src] = 1 + la.
        new_plane = _set_field(
            plane, self.B_PREP + self.W_PREP * e.src, self.W_PREP,
            xp.uint32(1 + e.la), xp,
        )
        entries = [
            _field(new_plane, self.B_PREP + self.W_PREP * i,
                   self.W_PREP, xp)
            for i in range(self.S)
        ]
        count = sum((en != 0).astype(xp.uint32) for en in entries)
        fire = handled & (count == 2)  # majority(3) (paxos.rs:144)
        # best la among present entries (la codes order by (ballot,
        # proposal), None lowest — matches _accepted_sort_key).
        best = xp.uint32(0)
        for en in entries:
            la = xp.where(en != 0, en - 1, 0)
            best = xp.maximum(best, la)
        chosen = xp.where(
            best > 0, ((best - 1) % xp.uint32(self.P)) + 1, prop
        )
        acc_code = 1 + (e.ballot - 1) * self.P + (chosen - 1)
        fired_lane = lane
        fired_lane = _set_field(
            fired_lane, self.B_PROP, self.W_PROP, chosen, xp
        )
        fired_lane = _set_field(
            fired_lane, self.B_ACC, self.W_ACC, acc_code, xp
        )
        fired_lane = _set_field(
            fired_lane, self.B_ACCEPTS, self.W_ACCEPTS,
            xp.uint32(1 << l), xp,
        )
        new_lane = xp.where(fire, fired_lane, lane)
        masks: dict = {}
        covered = fire & xp.bool_(False)
        for p in self.choosable[e.ballot]:
            keys = [
                (l, d, "accept", e.ballot, p, 0, 0)
                for d in range(self.S)
                if d != l
            ]
            cm = self._const_mask(keys)
            hit = fire & (chosen == p)
            covered = covered | hit
            for ln, m in cm.items():
                masks[ln] = masks.get(ln, xp.uint32(0)) | xp.where(
                    hit, xp.uint32(m), xp.uint32(0)
                )
        poison = fire & ~covered
        if self.two_lane:
            out = vec.at[l].set(xp.where(handled, new_lane, lane))
            out = out.at[self._prep_lane(l)].set(
                xp.where(handled, new_plane, plane)
            )
        else:
            # Main lane and prepares share one lane: merge the updated
            # prepares field range into the (possibly fired) main bits.
            pmask = xp.uint32(
                ((1 << (self.S * self.W_PREP)) - 1) << self.B_PREP
            )
            merged = (new_lane & ~pmask) | (new_plane & pmask)
            out = vec.at[l].set(xp.where(handled, merged, lane))
        out = self._poison(out, poison, xp)
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_accept(self, vec, k, e: EnvSpec, xp):
        lane = vec[e.dst]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        ballot = _field(lane, self.B_BALLOT, self.W_BALLOT, xp)
        handled = (~decided) & (ballot <= e.ballot)
        acc_code = 1 + (e.ballot - 1) * self.P + (e.prop - 1)
        new_lane = _set_field(
            lane, self.B_BALLOT, self.W_BALLOT, xp.uint32(e.ballot), xp
        )
        new_lane = _set_field(
            new_lane, self.B_ACC, self.W_ACC, xp.uint32(acc_code), xp
        )
        out = vec.at[e.dst].set(xp.where(handled, new_lane, lane))
        cm = self._const_mask([(e.dst, e.src, "accepted", e.ballot, 0, 0, 0)])
        masks = {
            ln: xp.where(handled, xp.uint32(m), xp.uint32(0))
            for ln, m in cm.items()
        }
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_accepted(self, vec, k, e: EnvSpec, xp):
        l = e.dst
        lane = vec[l]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        ballot = _field(lane, self.B_BALLOT, self.W_BALLOT, xp)
        prop = _field(lane, self.B_PROP, self.W_PROP, xp)
        handled = (~decided) & (ballot == e.ballot)
        accepts = _field(
            lane, self.B_ACCEPTS, self.W_ACCEPTS, xp
        ) | xp.uint32(1 << e.src)
        count = sum(
            ((accepts >> xp.uint32(i)) & 1).astype(xp.uint32)
            for i in range(self.S)
        )
        fire = handled & (count == 2)
        new_lane = _set_field(
            lane, self.B_ACCEPTS, self.W_ACCEPTS, accepts, xp
        )
        new_lane = xp.where(
            fire, new_lane | xp.uint32(1 << self.B_DEC), new_lane
        )
        masks: dict = {}
        covered = fire & xp.bool_(False)
        for p in self.choosable[e.ballot]:
            keys = [
                (l, d, "decided", e.ballot, p, 0, 0)
                for d in range(self.S)
                if d != l
            ]
            # PutOk to the proposal's requester.
            keys.append((l, self.clients[p - 1], "putok", 0, p, 0, 0))
            cm = self._const_mask(keys)
            hit = fire & (prop == p)
            covered = covered | hit
            for ln, m in cm.items():
                masks[ln] = masks.get(ln, xp.uint32(0)) | xp.where(
                    hit, xp.uint32(m), xp.uint32(0)
                )
        poison = fire & ~covered
        out = vec.at[l].set(xp.where(handled, new_lane, lane))
        out = self._poison(out, poison, xp)
        out = self._net_update(out, k, masks, xp)
        return out, handled

    def _on_decided(self, vec, k, e: EnvSpec, xp):
        lane = vec[e.dst]
        decided = _field(lane, self.B_DEC, 1, xp) != 0
        handled = ~decided
        acc_code = 1 + (e.ballot - 1) * self.P + (e.prop - 1)
        new_lane = _set_field(
            lane, self.B_BALLOT, self.W_BALLOT, xp.uint32(e.ballot), xp
        )
        new_lane = _set_field(
            new_lane, self.B_ACC, self.W_ACC, xp.uint32(acc_code), xp
        )
        new_lane = new_lane | xp.uint32(1 << self.B_DEC)
        out = vec.at[e.dst].set(xp.where(handled, new_lane, lane))
        out = self._net_update(out, k, {}, xp)
        return out, handled

    def _poison(self, vec, cond, xp):
        cl = self._clane_index(0)
        lane = vec[cl]
        return vec.at[cl].set(
            xp.where(cond, lane | xp.uint32(1 << _B_POISON), lane)
        )

    # -- sparse action dispatch (SparseEncodedModel) -----------------------
    #
    # The dense step_vec pays for all K slots per frontier row; with
    # K=284 at check 3 that is ~200x padding (PERF.md §paxos). The
    # sparse interface gives the engine (a) a cheap per-slot enabled
    # predicate — the envelope's presence bit AND the handler's guard,
    # which for every paxos handler is a small function of the DST
    # actor's fields — and (b) a table-driven per-pair transition where
    # every per-slot constant of the dense handlers (_on_*) becomes a
    # gather by slot index. Send masks unify into one [K*SEL]-row table
    # indexed by (slot, selector): the selector is the single
    # state-dependent value each handler's send depends on (put: dst's
    # ballot enum; get: read value; prepare: acceptor's accepted code;
    # prepared: chosen proposal; accepted: leader's proposal).

    _KINDS = (
        "put", "get", "putok", "getok", "prepare", "prepared", "accept",
        "accepted", "decided",
    )

    #: Measured max enabled slots per reachable state: 5 (1c), 8 (2c),
    #: 8 (3c d<=9, 4c d<=7) — 16 gives 2x headroom; the engine detects
    #: overflow loudly.
    pair_width_hint = 16

    def _sparse_tables(self) -> dict:
        if hasattr(self, "_sp"):
            return self._sp
        K, S, P, NB = self.K, self.S, self.P, self.NB
        la_max = NB * P
        SEL = max(NB, P, la_max) + 1
        kindno = {k: n for n, k in enumerate(self._KINDS)}
        kind = np.zeros(K, np.uint32)
        dst = np.zeros(K, np.uint32)
        src = np.zeros(K, np.uint32)
        ballot = np.zeros(K, np.uint32)
        prop = np.zeros(K, np.uint32)
        la = np.zeros(K, np.uint32)
        value = np.zeros(K, np.uint32)
        dst_srv = np.zeros(K, np.uint32)
        dst_cli = np.zeros(K, np.uint32)
        prep_lane = np.zeros(K, np.uint32)
        send = np.zeros((K * SEL, self.net_lanes), np.uint32)
        poison = np.zeros(K * SEL, np.uint32)
        aux = np.zeros(K * SEL, np.uint32)

        def orkey(row: int, key: tuple) -> None:
            kk = self.index[key]
            send[row, kk // 32] |= np.uint32(1 << (kk % 32))

        for k, e in enumerate(self.universe):
            kind[k] = kindno[e.kind]
            dst[k], src[k] = e.dst, e.src
            ballot[k], prop[k], la[k], value[k] = (
                e.ballot, e.prop, e.la, e.value,
            )
            dst_srv[k] = min(e.dst, S - 1)
            dst_cli[k] = (
                self.clients.index(e.dst) if e.dst in self.clients else 0
            )
            prep_lane[k] = self._prep_lane(int(dst_srv[k]))
            row0 = k * SEL
            if e.kind == "put":
                rounds = sorted(r for (r, l) in self.ballots if l == e.dst)
                round_of = [0] + [r for (r, _) in self.ballots]
                for sel in range(NB + 1):
                    nr = round_of[sel] + 1
                    if nr in rounds:
                        b = self.ballot_enum[(nr, Id(e.dst))]
                        aux[row0 + sel] = b
                        for d in range(S):
                            if d != e.dst:
                                orkey(
                                    row0 + sel,
                                    (e.dst, d, "prepare", b, 0, 0, 0),
                                )
                    else:
                        poison[row0 + sel] = 1
            elif e.kind == "get":
                for v in range(1, P + 1):
                    key = (e.dst, e.src, "getok", 0, 0, 0, v)
                    if key in self.index:
                        orkey(row0 + v, key)
            elif e.kind == "putok":
                orkey(
                    row0, (e.dst, (e.dst + 1) % S, "get", 0, 0, 0, 0)
                )
            elif e.kind == "prepare":
                las = set(self.la_universe[e.ballot])
                for sel in range(la_max + 1):
                    if sel in las:
                        orkey(
                            row0 + sel,
                            (e.dst, e.src, "prepared", e.ballot, 0, sel, 0),
                        )
                    else:
                        poison[row0 + sel] = 1
            elif e.kind == "prepared":
                ch = set(self.choosable[e.ballot])
                for sel in range(P + 1):
                    if sel in ch:
                        for d in range(S):
                            if d != e.dst:
                                orkey(
                                    row0 + sel,
                                    (e.dst, d, "accept", e.ballot, sel,
                                     0, 0),
                                )
                    else:
                        poison[row0 + sel] = 1
            elif e.kind == "accept":
                orkey(
                    row0, (e.dst, e.src, "accepted", e.ballot, 0, 0, 0)
                )
            elif e.kind == "accepted":
                ch = set(self.choosable[e.ballot])
                for sel in range(P + 1):
                    if sel in ch:
                        for d in range(S):
                            if d != e.dst:
                                orkey(
                                    row0 + sel,
                                    (e.dst, d, "decided", e.ballot, sel,
                                     0, 0),
                                )
                        orkey(
                            row0 + sel,
                            (e.dst, self.clients[sel - 1], "putok", 0,
                             sel, 0, 0),
                        )
                    else:
                        poison[row0 + sel] = 1
        # Pack per-slot params into ONE table row and the (slot, sel)
        # tables into another: per-pair fetches then cost two row
        # gathers instead of twelve scalar gathers (~10ns/row each on
        # TPU regardless of table size — measured 95ms/wave at 1M
        # pairs before packing).
        params = np.stack(
            [kind, dst_srv, dst_cli, src, ballot, prop, la, value,
             prep_lane],
            axis=1,
        )
        sendtab = np.concatenate(
            [send, poison[:, None], aux[:, None]], axis=1
        )
        self._sp = dict(
            SEL=SEL, kind=kind, ballot=ballot,
            dst_srv=dst_srv, dst_cli=dst_cli,
            k_lane=(np.arange(K) // 32).astype(np.uint32),
            k_shift=(np.arange(K) % 32).astype(np.uint32),
            params=params, sendtab=sendtab,
        )
        return self._sp

    def _bits_word_tables(self) -> dict:
        """Host-constant guard-CLASS masks for the word-native enabled
        predicate (ops/bitmask.py builders): each slot's handler guard
        depends on host constants (kind, dst, ballot) and a SMALL
        state-dependent selector of its destination actor — so slots
        group into classes sharing one enabling condition, and the
        packed mask is an OR of condition-gated class masks instead of
        a per-slot evaluation."""
        if hasattr(self, "_bw"):
            return self._bw
        from ..ops.bitmask import slot_mask_host

        K, S, NB = self.K, self.S, self.NB
        get_s = {d: [] for d in range(S)}
        put_s = {d: [] for d in range(S)}
        dec_s = {d: [] for d in range(S)}
        bal_s = {d: [[] for _ in range(NB + 1)] for d in range(S)}
        putok_c = {j: [] for j in range(self.C)}
        getok_c = {j: [] for j in range(self.C)}
        for k, e in enumerate(self.universe):
            if e.kind == "put":
                put_s[e.dst].append(k)
            elif e.kind == "get":
                get_s[e.dst].append(k)
            elif e.kind == "putok":
                putok_c[self.clients.index(e.dst)].append(k)
            elif e.kind == "getok":
                getok_c[self.clients.index(e.dst)].append(k)
            elif e.kind == "decided":
                dec_s[e.dst].append(k)
            else:
                # Ballot-relation kinds, all guarded by ~decided[dst]:
                # tabulate, per destination server and per possible
                # adopted-ballot value v, the slots whose relation
                # holds — the runtime then SELECTS one [L]-word row by
                # the server's ballot field.
                bt = e.ballot
                for v in range(NB + 1):
                    if (
                        (e.kind == "prepare" and v < bt)
                        or (e.kind == "prepared" and v == bt)
                        or (e.kind == "accept" and v <= bt)
                        or (e.kind == "accepted" and v == bt)
                    ):
                        bal_s[e.dst][v].append(k)
        self._bw = dict(
            # decided-kind slots merge into every ballot row: both are
            # gated by ~decided[dst], so one select covers them.
            nd={
                d: tuple(
                    slot_mask_host(K, bal_s[d][v] + dec_s[d])
                    for v in range(NB + 1)
                )
                for d in range(S)
            },
            get={d: slot_mask_host(K, get_s[d]) for d in range(S)},
            put={d: slot_mask_host(K, put_s[d]) for d in range(S)},
            putok={j: slot_mask_host(K, putok_c[j])
                   for j in range(self.C)},
            getok={j: slot_mask_host(K, getok_c[j])
                   for j in range(self.C)},
        )
        return self._bw

    def enabled_bits_vec(self, vec):
        """``uint32[ceil(K/32)]`` packed enabled mask, built
        WORD-NATIVE (round 6): the net lanes already hold the envelope
        presence bitmap in the ops/bitmask.py layout (slot k at bit
        k%32 of word k//32 — the same layout ``orkey`` packs), and the
        handler guard assembles from O(S·NB + C) condition-gated
        host-constant class masks. No gather, no dense ``bool[K]``
        anywhere — a vmapped caller stays ``[N, L]``-shaped, so the
        engine's [F, K] predicate pass (the largest in-stage term at
        paxos-4 shapes, PERF.md §wave-wall) collapses to [F, L] word
        lanes."""
        import jax.numpy as jnp

        from ..ops.bitmask import (
            const_words,
            or_class_words,
            select_words_host,
        )

        t = self._bits_word_tables()
        net = vec[self.n_state_lanes:]
        handled = None
        for d in range(self.S):
            lane = vec[d]
            dec = ((lane >> jnp.uint32(self.B_DEC)) & jnp.uint32(1)) != 0
            bal = (lane >> jnp.uint32(self.B_BALLOT)) & jnp.uint32(
                (1 << self.W_BALLOT) - 1
            )
            # Undecided guards: the ballot-relation row selected by
            # this server's adopted ballot (decided-kind bits ride the
            # same rows), plus its put slots when no proposal is open.
            w = select_words_host(jnp, t["nd"][d], bal)
            if any(t["put"][d]):
                prp = (lane >> jnp.uint32(self.B_PROP)) & jnp.uint32(
                    (1 << self.W_PROP) - 1
                )
                w = w | jnp.where(
                    prp == 0,
                    const_words(jnp, t["put"][d]),
                    jnp.uint32(0),
                )
            w = jnp.where(dec, const_words(jnp, t["get"][d]), w)
            handled = w if handled is None else handled | w
        cls = []
        for j in range(self.C):
            ph = (
                vec[self._clane_index(j)] >> jnp.uint32(self._coff(j))
            ) & jnp.uint32(3)
            cls += [(ph == 0, t["putok"][j]), (ph == 1, t["getok"][j])]
        handled = handled | or_class_words(jnp, cls, self.net_lanes)
        return net & handled

    def enabled_mask_vec(self, vec):
        """bool[K]: the dense view of :meth:`enabled_bits_vec` (the
        words are the source of truth, so the two cannot drift) — must
        match ``step_vec``'s validity exactly (pinned by an exhaustive
        differential test over the 2-client space)."""
        import jax.numpy as jnp

        from ..ops.bitmask import words_to_mask

        return words_to_mask(
            jnp, self.enabled_bits_vec(vec), self.max_actions
        )

    def step_slot_vec(self, vec, slot):
        """Successor for one enabled (state, slot) pair; every dense
        handler's per-slot constant is a table gather, every branch a
        select — one straight-line program, no lax.switch (all branches
        would execute under vmap anyway; sharing the gathered params
        across kinds is cheaper)."""
        import jax.numpy as jnp

        t = self._sparse_tables()
        xp = jnp
        SEL = t["SEL"]
        P, S = self.P, self.S
        slot = slot.astype(xp.uint32)
        prow = xp.asarray(t["params"])[slot]
        kind, dsrv, dcli, src, bt, pt, lat, vt, pl_idx = (
            prow[i] for i in range(9)
        )

        is_put = kind == 0
        is_get = kind == 1
        is_putok = kind == 2
        is_getok = kind == 3
        is_prepare = kind == 4
        is_prepared = kind == 5
        is_accept = kind == 6
        is_accepted = kind == 7
        is_decided = kind == 8

        def fget(lane, shift, width):
            return (lane >> shift) & xp.uint32((1 << width) - 1)

        def fset(lane, shift, width, val):
            mask = xp.uint32((1 << width) - 1) << shift
            return (lane & ~mask) | (
                (val.astype(xp.uint32) & xp.uint32((1 << width) - 1))
                << shift
            )

        def u(x):
            return xp.uint32(x)

        # Dynamic-index reads also become static selects (same TPU
        # lowering hazard class as the writes below).
        lane = vec[0]
        for j in range(1, self.S):
            lane = xp.where(dsrv == j, vec[j], lane)
        if self.two_lane:
            plane = vec[self.S]
            for j in range(self.S + 1, 2 * self.S):
                plane = xp.where(pl_idx == j, vec[j], plane)
        else:
            plane = lane  # prepares share the main lane
        # Client lane for this pair's dst client: traced dcli picks
        # lane cl0 + dcli//CPL and offset (dcli%CPL)*CST — static
        # per-lane selects (never dynamic-index reads; PERF.md).
        cl0 = self._clane_index(0)
        cl_rel = dcli // u(self.CPL)
        clane = vec[cl0]
        for q in range(1, self.n_client_lanes):
            clane = xp.where(cl_rel == q, vec[cl0 + q], clane)
        dec = fget(lane, u(self.B_DEC), 1) != 0
        bal = fget(lane, u(self.B_BALLOT), self.W_BALLOT)
        prp = fget(lane, u(self.B_PROP), self.W_PROP)
        acc = fget(lane, u(self.B_ACC), self.W_ACC)
        accepts = fget(lane, u(self.B_ACCEPTS), self.W_ACCEPTS)

        # prepared: record prepares[src] = 1 + la, majority fire.
        pshift = u(self.B_PREP) + u(self.W_PREP) * src
        new_plane = fset(plane, pshift, self.W_PREP, u(1) + lat)
        entries = [
            fget(new_plane, u(self.B_PREP + self.W_PREP * i), self.W_PREP)
            for i in range(S)
        ]
        pcount = sum((en != 0).astype(xp.uint32) for en in entries)
        fire = ~dec & (bal == bt) & (pcount == 2)
        best = u(0)
        for en in entries:
            best = xp.maximum(best, xp.where(en != 0, en - 1, u(0)))
        chosen = xp.where(best > 0, ((best - 1) % u(P)) + 1, prp)

        # accepted: accepts |= 1 << src, majority fire.
        acc2 = accepts | (u(1) << src)
        acount = sum(
            ((acc2 >> u(i)) & u(1)) for i in range(S)
        ).astype(xp.uint32)
        fire_acc = ~dec & (bal == bt) & (acount == 2)

        # get: value of the accepted proposal.
        val = xp.where(acc > 0, ((acc - 1) % u(P)) + 1, u(0))

        # Unified (slot, selector) tables: sends, poison, put's new
        # ballot. Gate: prepared/accepted send+poison only on fire.
        sel = xp.where(
            is_put, bal,
            xp.where(
                is_get, val,
                xp.where(
                    is_prepare, acc,
                    xp.where(
                        is_prepared, chosen,
                        xp.where(is_accepted, prp, u(0)),
                    ),
                ),
            ),
        )
        gate = xp.where(
            is_prepared, fire, xp.where(is_accepted, fire_acc, True)
        )
        trow = slot * u(SEL) + sel
        srow = xp.asarray(t["sendtab"])[trow]
        send_row = xp.where(gate, srow[: self.net_lanes], u(0))
        poison = gate & (srow[self.net_lanes] != 0)
        nb = srow[self.net_lanes + 1]

        # Per-kind server-lane updates (branchless, selected by kind).
        put_prep = (acc + 1) << (u(self.W_PREP) * dsrv)
        put_lane = (
            (nb << u(self.B_BALLOT))
            | (pt << u(self.B_PROP))
            | (acc << u(self.B_ACC))
        )
        prepare_lane = fset(lane, u(self.B_BALLOT), self.W_BALLOT, bt)
        acc_code_cb = u(1) + (bt - 1) * u(P) + (chosen - 1)
        fired_lane = fset(lane, u(self.B_PROP), self.W_PROP, chosen)
        fired_lane = fset(fired_lane, u(self.B_ACC), self.W_ACC,
                          acc_code_cb)
        fired_lane = fset(
            fired_lane, u(self.B_ACCEPTS), self.W_ACCEPTS, u(1) << dsrv
        )
        prepared_lane = xp.where(fire, fired_lane, lane)
        acc_code_bp = u(1) + (bt - 1) * u(P) + (pt - 1)
        accept_lane = fset(lane, u(self.B_BALLOT), self.W_BALLOT, bt)
        accept_lane = fset(accept_lane, u(self.B_ACC), self.W_ACC,
                           acc_code_bp)
        accepted_lane = fset(
            lane, u(self.B_ACCEPTS), self.W_ACCEPTS, acc2
        )
        accepted_lane = xp.where(
            fire_acc, accepted_lane | u(1 << self.B_DEC), accepted_lane
        )
        decided_lane = fset(lane, u(self.B_BALLOT), self.W_BALLOT, bt)
        decided_lane = fset(decided_lane, u(self.B_ACC), self.W_ACC,
                            acc_code_bp)
        decided_lane = decided_lane | u(1 << self.B_DEC)

        srv_lane = lane
        if not self.two_lane:
            # Prepares share the main lane: merge field updates.
            pmask = u(((1 << (S * self.W_PREP)) - 1) << self.B_PREP)
            put_lane = put_lane | (put_prep << u(self.B_PREP))
            prepared_lane = (prepared_lane & ~pmask) | (new_plane & pmask)
        srv_lane = xp.where(is_put, put_lane, srv_lane)
        srv_lane = xp.where(is_prepare, prepare_lane, srv_lane)
        srv_lane = xp.where(is_prepared, prepared_lane, srv_lane)
        srv_lane = xp.where(is_accept, accept_lane, srv_lane)
        srv_lane = xp.where(is_accepted, accepted_lane, srv_lane)
        srv_lane = xp.where(is_decided, decided_lane, srv_lane)

        # Compose the output with STATIC per-lane selects, never a
        # dynamic-index write: ``vec.at[dsrv].set(...)`` vmapped over
        # multi-million-row pair batches was observed to DROP the
        # scatter on TPU (XLA lowering hazard; correct on CPU) — the
        # repro is a padded 2M-pair wave where the server lane kept its
        # old value while the net lanes updated. W is tiny (~13), so
        # W selects are cheap and fusion-friendly anyway.
        lanes_out = []
        for j in range(self.n_state_lanes):
            lane_j = vec[j]
            if j < self.S:
                lane_j = xp.where(dsrv == j, srv_lane, lane_j)
            if self.two_lane and self.S <= j < 2 * self.S:
                plane_new = xp.where(
                    is_put, put_prep,
                    xp.where(is_prepared, new_plane, plane),
                )
                lane_j = xp.where(pl_idx == j, plane_new, lane_j)
            lanes_out.append(lane_j)
        out = vec
        for j, lane_j in enumerate(lanes_out):
            out = out.at[j].set(lane_j)

        # Client-lane updates (putok/getok) + the poison bit (which
        # always lives on client lane 0).
        cst = u(self.CST) * (dcli % u(self.CPL))
        putok_clane = fset(clane, cst, 2, u(1))
        putok_clane = fset(putok_clane, cst + u(2), 2, u(2))
        getok_clane = fset(clane, cst, 2, u(2))
        getok_clane = fset(getok_clane, cst + u(2), 2, u(3))
        getok_clane = fset(getok_clane, cst + u(4), self.W_RV, vt)
        clane_upd = xp.where(
            is_putok, putok_clane, xp.where(is_getok, getok_clane, clane)
        )
        upd = is_putok | is_getok
        for q in range(self.n_client_lanes):
            lane_q = xp.where(upd & (cl_rel == q), clane_upd, out[cl0 + q])
            if q == 0:
                lane_q = xp.where(
                    poison, lane_q | u(1 << _B_POISON), lane_q
                )
            out = out.at[cl0 + q].set(lane_q)

        # Network: clear the delivered bit, OR the (gated) sends in.
        for ln in range(self.net_lanes):
            idx = self.n_state_lanes + ln
            lane_v = out[idx]
            clear = xp.where(
                (slot >> u(5)) == u(ln),
                u(1) << (slot & u(31)),
                u(0),
            )
            out = out.at[idx].set((lane_v & ~clear) | send_row[ln])
        return out

    # -- properties --------------------------------------------------------

    def property_conditions_vec(self, vec):
        import jax.numpy as jnp

        idx = jnp.uint32(0)
        for j in range(self.C):
            clane = vec[self._clane_index(j)]
            off = self._coff(j)
            ph = _field(clane, off + 2, 2, jnp)
            rv = _field(clane, off + 4, self.W_RV, jnp)
            idx = idx * self.TB + ph * self.TBV + rv
        table = jnp.asarray(self._lin_table)
        linearizable = table[idx] & (
            _field(vec[self._clane_index(0)], _B_POISON, 1, jnp) == 0
        )
        # "value chosen": a deliverable GetOk with a non-default value.
        masks = self._const_mask(
            [
                self._env_key(e)
                for e in self.universe
                if e.kind == "getok" and e.value != 0
            ]
        )
        chosen = jnp.bool_(False)
        for ln, m in masks.items():
            chosen = chosen | (
                (vec[self.n_state_lanes + ln] & jnp.uint32(m)) != 0
            )
        return jnp.stack([linearizable, chosen])


# Round 6: the TUNED_ENGINE_CAPS budget table is retired (VERDICT r5
# item 6) — per-wave budgets auto-size from measured peaks
# (``cand_capacity="auto"``, checkers/tpu_sortmerge.py) and the
# pair-width default comes from ``pair_width_hint`` above. The
# round-5 measured reference points the table carried — enabled-pair
# peaks 3c 343,235 / 4c 686,045 / 5c 1,371,240, max 8-9 enabled slots
# per row at every client count — now live in the auto-budget store
# after one run, and in PERF.md for the record.

#: STRUCTURAL engine sizes per client count — NOT tuning: capacity
#: holds the pinned unique-state counts (265 / 16,668 / 1,194,428 /
#: 2,372,188 / 4,711,569), frontier the measured wave peaks, and the
#: 4c/5c memory knobs the padded-HBM sizing rules (PERF.md). Shared
#: by bench.py, cli.py, and tools/profile_stages.py so a resize lands
#: in exactly one place (the retune-drift property the retired budget
#: table also served).
STRUCTURAL_SIZES = {
    1: dict(capacity=1 << 10, frontier_capacity=1 << 8),
    2: dict(capacity=1 << 15, frontier_capacity=1 << 12),
    3: dict(capacity=5 << 18, frontier_capacity=1 << 18),
    4: dict(capacity=5 << 19, frontier_capacity=1 << 19,
            tile_rows=1 << 17),
    5: dict(capacity=3 << 21, frontier_capacity=3 << 19,
            tile_rows=1 << 17, f_min=1 << 16,
            flat_budget_bytes=2 << 30, mask_budget_cells=1 << 26),
}

#: PER-SHARD engine sizes of the sharded sort-merge engine
#: (``spawn_tpu_sharded_sortmerge``, ``paxos check-tpu N --shards S``),
#: which takes no ``cand_capacity="auto"``. Only sizes a run has proven:
#:
#: * 2 clients: the whole 16,668-state space fits one shard, so the
#:   same budgets serve every shard count; proven on the virtual CPU
#:   mesh at S = 1, 2, 4 and 8 (tests/test_cli_shards.py).
#: * 4 clients: proven on a four-chip v5e host at S = 4 (chip_smoke.py
#:   --chips 4; the benchmark cell paxos-4c3s-mesh4.verify): capacity
#:   holds a quarter of the 2,372,188 states with 1.3x room for routing
#:   skew; frontier and candidates are a quarter of the one-chip wave
#:   peaks (~524K frontier rows, 686,045 candidates) with 2-3x room.
#:   Fewer shards overflow these budgets.
SHARDED_SIZES = {
    2: dict(capacity=1 << 15, frontier_capacity=1 << 12,
            cand_capacity=1 << 14),
    4: dict(capacity=3 << 18, frontier_capacity=1 << 18,
            cand_capacity=1 << 19, tile_rows=1 << 17),
}


def paxos_encoded(
    client_count: int = 2, server_count: int = 3, put_count: int = 1
) -> PaxosEncoded:
    return PaxosEncoded(
        PaxosModelCfg(
            client_count=client_count,
            server_count=server_count,
            put_count=put_count,
        )
    )
