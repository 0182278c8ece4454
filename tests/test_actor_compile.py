"""The actor→encoding compiler (actor/compile.py), proven by
REGENERATING workloads that have hand encodings or reference-pinned
counts and diffing results (VERDICT r2 item 2 / SURVEY §7 step 5):

* ping-pong: 14 (lossy dup, max 1), 4,094 (lossy dup, max 5, boundary),
  11 (lossless nondup, max 5) — reference actor/model.rs:688, 847, 887
* single-copy register 2c/1s: 93 — examples/single-copy-register.rs:110,
  diffed against the hand encoding models/single_copy_register_tpu.py
* ABD linearizable register 2c/2s: 544 —
  examples/linearizable-register.rs:286 (no hand encoding exists: this
  is "a new actor workload gets check-tpu with zero hand-written
  device code")

All device runs go through spawn_tpu_sortmerge on the CPU mesh and are
compared engine-to-host on unique counts AND discovered property sets.
"""

import pytest

from stateright_tpu.actor import Network
from stateright_tpu.actor.compile import compile_actor_model
from stateright_tpu.actor.register import Get, GetOk, Put, PutOk
from stateright_tpu.models.ping_pong import (
    PingPongCfg,
    ping_pong_device_specs as ping_pong_specs,  # noqa: F401 — re-export
    ping_pong_model,
)


def spawn_compiled(model, enc, **kw):
    kw.setdefault("capacity", 1 << 13)
    kw.setdefault("frontier_capacity", 1 << 10)
    # Sparse dispatch budgets ENABLED pairs, which (unlike the dense
    # valid count) includes successors the boundary later prunes —
    # size for the larger of the two.
    kw.setdefault("cand_capacity", 1 << 14)
    return model.checker().spawn_tpu_sortmerge(encoded=enc, **kw)


def assert_matches_host(model, enc, expected_unique):
    host = model.checker().spawn_bfs().join()
    assert host.unique_state_count() == expected_unique
    tpu = spawn_compiled(model, enc).join()
    assert tpu.unique_state_count() == expected_unique
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    # Discovery paths replay through the host model (materializing a
    # Path already replays the trace — the differential check that the
    # compiled step_vec agrees with the actor handlers). The last state
    # must witness the discovery: satisfy a sometimes, violate an
    # always; an eventually counterexample is a terminal path on which
    # the condition never held.
    from stateright_tpu.model import Expectation

    for name, path in tpu.discoveries().items():
        prop = model.property_by_name(name)
        if prop.expectation == Expectation.SOMETIMES:
            assert prop.condition(model, path.last_state())
        elif prop.expectation == Expectation.ALWAYS:
            assert not prop.condition(model, path.last_state())
        else:
            assert all(
                not prop.condition(model, s) for s, _ in path.steps
            )
    return host, tpu


@pytest.mark.parametrize(
    "cfg_kw,lossy,network,expected",
    [
        (dict(max_nat=1, maintains_history=True), True, None, 14),
        (dict(max_nat=5, maintains_history=True), True, None, 4094),
        (
            dict(max_nat=5, maintains_history=True),
            False,
            Network.new_unordered_nonduplicating(),
            11,
        ),
    ],
)
def test_ping_pong_regenerated(cfg_kw, lossy, network, expected):
    cfg = PingPongCfg(**cfg_kw)
    model = ping_pong_model(cfg)
    if network is not None:
        model.init_network(network)
    model.set_lossy_network(lossy)
    enc = compile_actor_model(model, **ping_pong_specs(cfg))
    assert_matches_host(model, enc, expected)


def test_ping_pong_crashes_regenerated():
    """Crash slots: lossless nondup max 2 with one allowed crash."""
    cfg = PingPongCfg(max_nat=2, maintains_history=True)
    model = (
        ping_pong_model(cfg)
        .init_network(Network.new_unordered_nonduplicating())
        .set_max_crashes(1)
    )
    enc = compile_actor_model(model, **ping_pong_specs(cfg))
    host = model.checker().spawn_bfs().join()
    tpu = spawn_compiled(model, enc).join()
    assert tpu.unique_state_count() == host.unique_state_count()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())


# The register-family device specs now live in the library
# (actor/register.py) so models can compile themselves; re-exported
# here for the existing test call sites.
from stateright_tpu.actor.register import register_specs  # noqa: E402


def test_single_copy_regenerated_matches_hand_encoding():
    from stateright_tpu.actor.register import DEFAULT_VALUE
    from stateright_tpu.models.single_copy_register import (
        SingleCopyRegisterCfg,
        single_copy_register_model,
    )
    from stateright_tpu.models.single_copy_register_tpu import (
        SingleCopyEncoded,
    )

    cfg = SingleCopyRegisterCfg(client_count=2)
    model = single_copy_register_model(cfg)
    enc = compile_actor_model(
        model,
        properties=register_specs(DEFAULT_VALUE),
        # Each client performs at most put_count+1 operations.
        closure_history_bound=lambda h: len(h)
        <= cfg.client_count * (cfg.put_count + 1),
    )
    host, tpu = assert_matches_host(model, enc, 93)

    # Diff against the HAND encoding: same counts, same discoveries.
    hand = (
        single_copy_register_model(cfg)
        .checker()
        .spawn_tpu_sortmerge(
            encoded=SingleCopyEncoded(cfg),
            capacity=1 << 10,
            frontier_capacity=256,
            cand_capacity=1 << 11,
        )
        .join()
    )
    assert hand.unique_state_count() == tpu.unique_state_count() == 93
    assert sorted(hand.discoveries()) == sorted(tpu.discoveries())


def test_abd_regenerated_544():
    """ABD gets check-tpu with zero hand-written device code
    (examples/linearizable-register.rs:286 pins 544 states)."""
    from stateright_tpu.actor.register import DEFAULT_VALUE
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    cfg = AbdModelCfg(client_count=2, server_count=2)
    model = abd_model(cfg)
    # ABD's logical clocks are bounded only by system reachability, so
    # the overapprox closure diverges (like paxos ballots) — harvest
    # from host exploration instead.
    enc = compile_actor_model(
        model,
        properties=register_specs(DEFAULT_VALUE),
        closure="reachable",
    )
    assert_matches_host(model, enc, 544)


def test_compiler_ordered_requires_reachable():
    """Ordered networks need the harvested queue bounds of reachable
    mode; overapprox mode fails loudly (see the Limits docstring)."""
    cfg = PingPongCfg(max_nat=1)
    model = ping_pong_model(cfg).init_network(Network.new_ordered())
    with pytest.raises(ValueError, match="reachable"):
        compile_actor_model(model, **ping_pong_specs(cfg))


def test_compiler_requires_specs_for_all_properties():
    cfg = PingPongCfg(max_nat=1)
    model = ping_pong_model(cfg)
    with pytest.raises(ValueError, match="no device spec"):
        compile_actor_model(model, properties={})


def test_closure_divergence_detected():
    cfg = PingPongCfg(max_nat=5)
    model = ping_pong_model(cfg)
    specs = ping_pong_specs(cfg)
    specs.pop("closure_actor_bound")  # counters now unbounded
    with pytest.raises(RuntimeError, match="closure"):
        compile_actor_model(model, max_domain=64, **specs)


def test_count_bound_overflow_raises():
    """A model with finite component domains but unbounded envelope
    multiplicity must fail loudly when the device prunes a successor at
    the implicit 128-count bound (ADVICE r3, medium) — not report a
    clean, silently truncated 'verified' space."""
    from stateright_tpu.actor import Actor, ActorModel, Network

    class Flooder(Actor):
        def on_start(self, id, out):
            out.send(id, "go")
            return 0

        def on_msg(self, id, state, src, msg, out):
            # Consume one "go", emit two: multiplicity diverges while
            # the local state and envelope universe stay singletons.
            out.send(id, "go")
            out.send(id, "go")

    model = (
        ActorModel(cfg=None)
        .actor(Flooder())
        .init_network(Network.new_unordered_nonduplicating())
    )
    enc = compile_actor_model(model, properties={})
    checker = spawn_compiled(
        model, enc,
        capacity=1 << 9, frontier_capacity=1 << 5,
        cand_capacity=1 << 7, waves_per_sync=32,
    )
    with pytest.raises(RuntimeError, match="encoding-bound overflow"):
        checker.join()


def test_reachable_mode_propagates_handler_errors():
    """closure='reachable' harvests only reachable (state, envelope)
    pairs, so a raising handler is a genuine model bug and must fail
    the compile (ADVICE r3) — overapprox mode still records a no-op."""
    from stateright_tpu.actor import Actor, ActorModel

    class Boom(Actor):
        def on_start(self, id, out):
            out.send(id, "go")
            return 0

        def on_msg(self, id, state, src, msg, out):
            raise KeyError("handler bug")

    model = ActorModel(cfg=None).actor(Boom())
    with pytest.raises(RuntimeError, match="on_msg raised on a reachable"):
        compile_actor_model(model, properties={}, closure="reachable")
    # Overapprox mode keeps the lenient no-op treatment.
    enc = compile_actor_model(model, properties={})
    assert enc.width >= 1


def _sparse_contract_check(enc, max_states=20000):
    """Pin the SparseEncodedModel contract for a compiled encoding over
    every reachable state: ``enabled & ~trunc`` equals the dense
    validity, and ``step_slot_vec`` reproduces ``step_vec``'s successor
    on every enabled, non-truncated pair."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from collections import deque

    model = enc.host_model
    seen = {}
    q = deque()
    for s in model.init_states():
        seen[tuple(enc.encode(s).tolist())] = s
        q.append(s)
    while q:
        s = q.popleft()
        for a in model.actions(s):
            n = model.next_state(s, a)
            if n is None or not model.within_boundary(n):
                continue
            key = tuple(enc.encode(n).tolist())
            if key not in seen:
                assert len(seen) < max_states
                seen[key] = n
                q.append(n)
    vecs = jnp.asarray(np.array(sorted(seen), dtype=np.uint32))
    succs, valid, _ = (
        np.asarray(a) for a in jax.jit(jax.vmap(enc.step_vec))(vecs)
    )
    mask = np.asarray(jax.jit(jax.vmap(enc.enabled_mask_vec))(vecs))
    rows, slots = np.nonzero(mask)
    sp, ptr, hard = (
        np.asarray(a)
        for a in jax.jit(jax.vmap(enc.step_slot_vec))(
            vecs[jnp.asarray(rows)],
            jnp.asarray(slots.astype(np.uint32)),
        )
    )
    bad = ptr | hard
    eff = mask.copy()
    eff[rows[bad], slots[bad]] = False
    assert (eff == valid).all(), "enabled & ~trunc diverges from dense"
    ok = ~bad
    assert (sp[ok] == succs[rows[ok], slots[ok]]).all(), (
        "step_slot_vec diverges from step_vec"
    )
    return len(seen)


@pytest.mark.parametrize(
    "cfg_kw,lossy,network,expected",
    [
        (dict(max_nat=1), True, None, 14),           # deliver+drop, dup
        (dict(max_nat=5), False, "nondup", 11),      # deliver, nondup
        (dict(max_nat=2), True, "nondup", None),     # drop, NONDUP dec
    ],
)
def test_compiled_sparse_contract_ping_pong(cfg_kw, lossy, network,
                                            expected):
    cfg = PingPongCfg(maintains_history=True, **cfg_kw)
    model = ping_pong_model(cfg).set_lossy_network(lossy)
    if network == "nondup":
        model = model.init_network(Network.new_unordered_nonduplicating())
    enc = compile_actor_model(model, **ping_pong_specs(cfg))
    if expected is None:
        expected = (
            model.checker().spawn_bfs().join().unique_state_count()
        )
    assert _sparse_contract_check(enc) == expected


def test_compiled_sparse_contract_crashes_and_timers():
    """Crash and timeout slots through the sparse tables: a one-actor
    timer loop with crashes."""
    from stateright_tpu.actor import Actor, ActorModel
    from stateright_tpu.model import Expectation

    class Ticker(Actor):
        def on_start(self, id, out):
            out.set_timer("tick", (1.0, 2.0))
            return 0

        def on_msg(self, id, state, src, msg, out):
            pass

        def on_timeout(self, id, state, timer, out):
            if state.value < 3:
                state.set(state.value + 1)
                out.set_timer("tick", (1.0, 2.0))

    model = (
        ActorModel(cfg=None)
        .actor(Ticker())
        .actor(Ticker())
        .set_max_crashes(1)
        .property(
            Expectation.ALWAYS, "counts bounded",
            lambda cfg, s: all(a <= 3 for a in s.actor_states),
        )
    )
    enc = compile_actor_model(
        model,
        properties={
            "counts bounded": lambda ctx, jnp: jnp.all(
                ctx.actor_values(lambda i, s: s) <= 3
            ),
        },
    )
    n = _sparse_contract_check(enc)
    host = model.checker().spawn_bfs().join()
    assert n == host.unique_state_count()
    sp = spawn_compiled(model, enc, sparse=True, pair_width=8).join()
    assert sp.unique_state_count() == n
    assert sorted(sp.discoveries()) == sorted(host.discoveries())


def test_compiled_sparse_engine_matches_dense():
    """Ping-pong 4,094 (lossy dup, boundary) through the sparse engine:
    identical count and property set as dense — exercises the
    boundary-aware sparse path (terminal scatter-back)."""
    cfg = PingPongCfg(maintains_history=True, max_nat=5)
    model = ping_pong_model(cfg).set_lossy_network(True)
    enc = compile_actor_model(model, **ping_pong_specs(cfg))
    dense = spawn_compiled(model, enc, sparse=False).join()
    sp = spawn_compiled(model, enc, sparse=True, pair_width=16).join()
    assert sp.unique_state_count() == dense.unique_state_count() == 4094
    assert sorted(sp.discoveries()) == sorted(dense.discoveries())


def test_abd_sharded_sortmerge_fingerprint_only():
    """Compiler × sharding: the compiled ABD encoding through the
    sharded sort-merge engine (2 CPU-mesh shards) — the product's core
    composition (VERDICT r3 weak #7). The 544 count and the property
    set must match the host.

    Fingerprint-only (track_paths=False): the engine's no-parent-log
    program. The compiled × sharded × paths composition is
    test_abd_sharded_paths_on_tpu below."""
    from stateright_tpu.actor.register import DEFAULT_VALUE
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    cfg = AbdModelCfg(client_count=2, server_count=2)
    model = abd_model(cfg)
    enc = compile_actor_model(
        model,
        properties=register_specs(DEFAULT_VALUE),
        closure="reachable",
    )
    host = model.checker().spawn_bfs().join()
    sharded = (
        model.checker()
        .spawn_tpu_sharded_sortmerge(
            encoded=enc,
            n_shards=2,
            capacity=1 << 10,
            frontier_capacity=1 << 9,
            cand_capacity=1 << 11,
            track_paths=False,
        )
        .join()
    )
    assert sharded.unique_state_count() == 544
    assert sharded.discovered_property_names() == set(host.discoveries())


def test_abd_sharded_paths_on_tpu():
    """Compiler × sharding × PATHS (VERDICT r4 weak #4 / item 6): the
    compiled ABD encoding through spawn_tpu_sharded_sortmerge with
    track_paths=True, a replayed discovery path included, on a
    single-device mesh (the first CPU device, or the chip). Executed
    on TPU v5 lite 2026-07-31: 544 states, 14s end-to-end including
    compile, 11-action 'value chosen' path."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    mesh = Mesh(np.array(jax.devices()[:1]), ("shard",))
    model = abd_model(AbdModelCfg(client_count=2, server_count=2))
    enc = model.to_encoded()
    host = (
        abd_model(AbdModelCfg(client_count=2, server_count=2))
        .checker()
        .spawn_bfs()
        .join()
    )
    c = (
        model.checker()
        .spawn_tpu_sharded_sortmerge(
            encoded=enc,
            mesh=mesh,
            capacity=1 << 10,
            frontier_capacity=1 << 9,
            cand_capacity=1 << 11,
            track_paths=True,
        )
        .join()
    )
    assert c.unique_state_count() == 544 == host.unique_state_count()
    assert sorted(c.discoveries()) == sorted(host.discoveries())
    p = c.discovery("value chosen")
    assert p is not None and len(p.actions()) >= 1


def test_compiled_ordered_ping_pong():
    """Ordered (FIFO) networks compile (VERDICT r3 missing #3):
    integer-queue channels, head-only delivery, the no-op-pop
    exception, and FIFO send appends — regenerated ping-pong matches
    host BFS state-for-state with replayed discovery paths, and the
    sparse contract holds exhaustively."""
    cfg = PingPongCfg(maintains_history=True, max_nat=3)
    model = ping_pong_model(cfg).init_network(Network.new_ordered())
    enc = compile_actor_model(
        model, closure="reachable", **ping_pong_specs(cfg)
    )
    host = model.checker().spawn_bfs().join()
    assert_matches_host(model, enc, host.unique_state_count())
    assert _sparse_contract_check(enc) == host.unique_state_count()


def test_compiled_ordered_abd():
    """`linearizable-register check-tpu 2 ordered` (BASELINE.md:32,
    bench.sh:33): the compiled ABD encoding over FIFO channels matches
    host DFS count and property set, with a replayed path."""
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    cfg = AbdModelCfg(client_count=2, server_count=2)
    model = abd_model(cfg, Network.new_ordered())
    host = model.checker().spawn_dfs().join()
    enc = model.to_encoded()
    tpu = spawn_compiled(model, enc, capacity=1 << 14,
                         frontier_capacity=1 << 11).join()
    assert tpu.unique_state_count() == host.unique_state_count()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    p = tpu.discovery("value chosen")
    assert p is not None and len(p.actions()) >= 1


def test_compiled_ordered_overapprox_declared_bounds():
    """Ordered networks under bounded overapproximation (VERDICT r4
    item 4): a DECLARED per-channel queue bound replaces the
    reachable-mode host exploration entirely — same count, property
    set, and replayable paths as the harvested-bounds compile."""
    cfg = PingPongCfg(maintains_history=True, max_nat=3)
    model = ping_pong_model(cfg).init_network(Network.new_ordered())
    harvested = compile_actor_model(
        model, closure="reachable", **ping_pong_specs(cfg)
    )
    bounds = {
        (int(ch[0]), int(ch[1])): harvested.ch_q[ch]
        for ch in harvested.channels
    }
    enc = compile_actor_model(
        model,
        closure="overapprox",
        closure_queue_bound=bounds,
        **ping_pong_specs(cfg),
    )
    assert enc.closure_mode == "overapprox"
    host = model.checker().spawn_bfs().join()
    assert_matches_host(model, enc, host.unique_state_count())
    # A uniform int bound works too (max(harvested, declared) rule
    # keeps the layout sound even when generous).
    enc2 = compile_actor_model(
        model,
        closure="overapprox",
        closure_queue_bound=max(bounds.values()),
        **ping_pong_specs(cfg),
    )
    tpu = spawn_compiled(model, enc2, sparse=True).join()
    assert tpu.unique_state_count() == host.unique_state_count()


def test_compiled_ordered_overapprox_underdeclared_bound_is_loud():
    """An under-declared queue bound must raise the truncation flag,
    never silently verify a truncated space."""
    cfg = PingPongCfg(maintains_history=True, max_nat=3)
    model = ping_pong_model(cfg).init_network(Network.new_ordered())
    enc = compile_actor_model(
        model,
        closure="overapprox",
        closure_queue_bound=1,
        **ping_pong_specs(cfg),
    )
    host = model.checker().spawn_bfs().join()
    try:
        c = spawn_compiled(model, enc).join()
    except RuntimeError as exc:
        assert "truncat" in str(exc) or "encoding-bound" in str(exc)
    else:
        # A bound of 1 may genuinely suffice for this protocol; the
        # test then degenerates to the agreement check.
        assert c.unique_state_count() == host.unique_state_count()


def test_compiled_ordered_rejects_unsupported_modes():
    cfg = PingPongCfg(max_nat=1)
    model = ping_pong_model(cfg).init_network(Network.new_ordered())
    with pytest.raises(ValueError, match="reachable"):
        compile_actor_model(model, **ping_pong_specs(cfg))
    lossy = (
        ping_pong_model(cfg)
        .init_network(Network.new_ordered())
        .set_lossy_network(True)
    )
    with pytest.raises(ValueError, match="lossy ordered"):
        compile_actor_model(
            lossy, closure="reachable", **ping_pong_specs(cfg)
        )


def test_abd_bounded_overapprox_default():
    """VERDICT r3 #5: ABD's default encoding mode is now bounded
    overapproximation — protocol bounds (clock <= writes, ops <=
    put_count+1, linearizable-expansion) close the component fixpoint
    WITHOUT any host exploration — and still reproduces the
    reference-pinned 544 with the host property set and a replayable
    path."""
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    cfg = AbdModelCfg(client_count=2, server_count=2)
    model = abd_model(cfg)
    enc = model.to_encoded()
    assert enc.closure_mode == "overapprox"
    host = model.checker().spawn_bfs().join()
    tpu = spawn_compiled(model, enc).join()
    assert tpu.unique_state_count() == 544
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    p = tpu.discovery("value chosen")
    assert p is not None and len(p.actions()) >= 1


def test_abd_3clients_bounded_overapprox_compiles_and_agrees():
    """The scale story for bounded overapproximation (VERDICT r3 #5):
    at 3 clients the closure converges from protocol bounds alone (no
    host exploration — round 3's "reachable" mode would have explored
    all 35,009 system states at compile time), and the encoding agrees
    with the host on every successor of the shallow prefix. The FULL
    device run was executed on real TPU (round 4): 35,009 states,
    matching an independently-run host BFS."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from collections import deque

    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    cfg = AbdModelCfg(client_count=3, server_count=2)
    model = abd_model(cfg)
    enc = model.to_encoded()
    assert enc.closure_mode == "overapprox"
    # Shallow differential: device successors == host successors.
    seen = {}
    q = deque()
    for s in model.init_states():
        seen[tuple(enc.encode(s).tolist())] = s
        q.append((s, 0))
    while q:
        s, d = q.popleft()
        if d >= 3:
            continue
        for n in model.next_states(s):
            k = tuple(enc.encode(n).tolist())
            if k not in seen:
                seen[k] = n
                q.append((n, d + 1))
    vecs = jnp.asarray(np.array(sorted(seen), dtype=np.uint32))
    mask = np.asarray(jax.jit(jax.vmap(enc.enabled_mask_vec))(vecs))
    rows, slots = np.nonzero(mask)
    sp, ptr, hard = (
        np.asarray(a)
        for a in jax.jit(jax.vmap(enc.step_slot_vec))(
            vecs[jnp.asarray(rows)],
            jnp.asarray(slots.astype(np.uint32)),
        )
    )
    assert not ptr.any() and not hard.any()
    got = {}
    for j in range(len(rows)):
        got.setdefault(int(rows[j]), set()).add(tuple(sp[j].tolist()))
    keys = sorted(seen)
    for i, k in enumerate(keys):
        host_succ = {
            tuple(enc.encode(n).tolist())
            for n in model.next_states(seen[k])
        }
        assert got.get(i, set()) == host_succ


def test_compiled_ordered_abd_3s_depth_differential():
    """The bench lane `abd 2c/3s ordered` (driver family
    `linearizable-register check N ordered`, BASELINE.md:32): the
    overapprox-compiled FIFO encoding matches host BFS state-for-state
    at a bounded depth, pinning the encoding semantics the full
    1,212,979-state device run (bench.py; reproduced across runs on
    real TPU, round 5) builds on. Depth 10 (1,066 states; was 7/171,
    ADVICE r5): encoding bugs that first manifest past the shallow
    prefix — queue-depth interleavings, second-round timestamps —
    fail here instead of moving the bench expectation."""
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    def mk():
        return abd_model(
            AbdModelCfg(client_count=2, server_count=3),
            Network.new_ordered(),
        )

    host = mk().checker().target_max_depth(10).spawn_bfs().join()
    assert host.unique_state_count() == 1066
    m = mk()
    tpu = (
        m.checker()
        .target_max_depth(10)
        .spawn_tpu_sortmerge(
            encoded=m.to_encoded(),
            capacity=1 << 13,
            frontier_capacity=1 << 11,
            cand_capacity=1 << 13,
            track_paths=False,
        )
        .join()
    )
    assert tpu.unique_state_count() == host.unique_state_count()
    assert tpu.discovered_property_names() == set(host.discoveries())


@pytest.mark.slow
@pytest.mark.skipif(
    "STPU_EXHAUSTIVE" not in __import__("os").environ,
    reason="~hour-scale host DFS (~1.2M states at host rates); "
    "run with STPU_EXHAUSTIVE=1 (verified 2026-08-03: 1,212,979, "
    "only 'value chosen' — PERF.md §counts)",
)
def test_abd_ordered_2c3s_exhaustive_host_pin():
    """Independent exhaustive verification of the ordered BENCH lane's
    headline count (VERDICT r5 item 5): host DFS explores the full
    `abd 2c/3s ordered` space with no device involvement and must
    report exactly 1,212,979 unique states with only 'value chosen'
    discovered — so the count no longer rests on a single engine
    configuration plus depth-prefix differentials."""
    from stateright_tpu.models.linearizable_register import (
        AbdModelCfg,
        abd_model,
    )

    ck = (
        abd_model(
            AbdModelCfg(client_count=2, server_count=3),
            Network.new_ordered(),
        )
        .checker()
        .spawn_dfs()
        .join()
    )
    assert ck.unique_state_count() == 1212979
    assert sorted(ck.discoveries()) == ["value chosen"]


def test_compiled_2pc_actors_matches_host():
    """The actor-model 2pc (models/two_phase_commit_actors.py — the
    registry's compiled-2pc fixture, ROADMAP direction 5) through the
    compiler: count + discovery parity with host BFS, and the
    consistency property holds. Doubles as the regression test for
    the history-table sentinel fix: this model is history-FREE
    (init_history=None), and the old `.get(key) is not None` lookup
    read the legitimate None history value as "un-harvested",
    hard-truncating every delivery on the first wave."""
    from stateright_tpu.models.two_phase_commit_actors import (
        two_phase_actor_device_specs,
        two_phase_actor_model,
    )

    model = two_phase_actor_model(2)
    enc = compile_actor_model(
        model, **two_phase_actor_device_specs(2)
    )
    assert_matches_host(model, enc, 306)


def test_compiled_paxos_matches_host():
    """The compiled paxos encoding (models/paxos.py
    paxos_compiled_encoded — the registry's compiled-paxos fixture):
    the actor paxos model through the compiler in reachable mode,
    count + discovery parity with host BFS at the registry config."""
    from stateright_tpu.models.paxos import (
        PaxosModelCfg,
        paxos_compiled_encoded,
        paxos_model,
    )

    cfg = PaxosModelCfg(client_count=1, server_count=2, put_count=1)
    model = paxos_model(cfg)
    enc = paxos_compiled_encoded(cfg)
    host = model.checker().spawn_bfs().join()
    tpu = spawn_compiled(model, enc).join()
    assert tpu.unique_state_count() == host.unique_state_count()
    assert sorted(tpu.discoveries()) == sorted(host.discoveries())
    tpu.assert_properties()
