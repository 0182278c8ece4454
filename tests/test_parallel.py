"""The multi-chip sharded wave engine on the virtual 8-device CPU mesh.

The sharding contract (VERDICT round-1 item 1): identical results —
unique counts, discovered-property sets, replayable counterexamples —
for shard counts 1/2/8, matching the host oracle and the reference's
pinned state counts (2pc rm=3 = 288, rm=5 = 8,832,
examples/2pc.rs:153-168).
"""

import numpy as np
import pytest

from stateright_tpu.fixtures import DGraph
from stateright_tpu.model import Property
from stateright_tpu.models.two_phase_commit import TwoPhaseSys


@pytest.fixture(scope="module")
def host_2pc3():
    return TwoPhaseSys(rm_count=3).checker().spawn_bfs().join()


@pytest.mark.parametrize("layout", ["n_shards", "mesh"])
@pytest.mark.parametrize("shards", [1, 2, 8])
def test_sharded_2pc_matches_host(shards, layout, host_2pc3):
    """State-identical across shard counts, built from a shard count
    or from an explicit mesh, WITH path tracking: discovery paths
    replay through the host model."""
    if layout == "mesh":
        import jax
        from jax.sharding import Mesh

        where = dict(
            mesh=Mesh(np.array(jax.devices()[:shards]), ("shard",))
        )
    else:
        where = dict(n_shards=shards)
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            capacity=512,
            frontier_capacity=128,
            cand_capacity=1024,
            bucket_capacity=512,
            **where,
        )
        .join()
    )
    assert c.n_shards == shards
    assert c.unique_state_count() == 288
    assert c.unique_state_count() == host_2pc3.unique_state_count()
    assert sorted(c.discoveries()) == sorted(host_2pc3.discoveries())
    c.assert_properties()
    # Counterexample paths replay through the host model.
    for name, path in c.discoveries().items():
        prop = c.model.property_by_name(name)
        assert prop.condition(c.model, path.last_state())


@pytest.mark.slow
def test_sharded_2pc_5rms_8832():
    c = (
        TwoPhaseSys(rm_count=5)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=8,
            capacity=1 << 12,
            frontier_capacity=512,
            cand_capacity=2048,
            bucket_capacity=1024,
            waves_per_sync=32,
            track_paths=False,
        )
        .join()
    )
    assert c.unique_state_count() == 8832
    c.assert_properties()
    assert c.metrics["shuffle_volume"] > 0


def test_sharded_single_shard_no_shuffle():
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=1, capacity=1 << 10, frontier_capacity=128,
            cand_capacity=512,
        )
        .join()
    )
    assert c.unique_state_count() == 288
    assert c.metrics["shuffle_volume"] == 0


def test_sharded_agrees_with_single_chip_engine():
    single = (
        TwoPhaseSys(rm_count=4)
        .checker()
        .spawn_tpu(
            capacity=1 << 12, frontier_capacity=512, cand_capacity=2048
        )
        .join()
    )
    sharded = (
        TwoPhaseSys(rm_count=4)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=8,
            capacity=1 << 10,
            frontier_capacity=256,
            cand_capacity=512,
            bucket_capacity=256,
        )
        .join()
    )
    assert sharded.unique_state_count() == single.unique_state_count()
    assert sharded.state_count() == single.state_count()
    assert sharded.max_depth() == single.max_depth()
    assert sorted(sharded.discoveries()) == sorted(single.discoveries())


def test_sharded_eventually_property():
    class DGraphEncoded:
        width = 1
        max_actions = 2

        def __init__(self, model):
            self.host_model = model

        def init_vecs(self):
            return np.array([[1]], dtype=np.uint32)

        def encode(self, state):
            return np.array([state], dtype=np.uint32)

        def step_vec(self, vec):
            import jax.numpy as jnp

            node = vec[0]
            s1 = jnp.where(node == 1, jnp.uint32(2), jnp.uint32(3))
            v1 = (node == 1) | (node == 2)
            s2 = jnp.uint32(4)
            v2 = node == 1
            return (
                jnp.stack([vec.at[0].set(s1), vec.at[0].set(s2)]),
                jnp.stack([v1, v2]),
            )

        def property_conditions_vec(self, vec):
            import jax.numpy as jnp

            return jnp.stack([vec[0] == 3])

        def within_boundary_vec(self, vec):
            return True

    model = (
        DGraph.with_path([1, 2, 3])
        .path([1, 4])
        .property(Property.eventually("reaches 3", lambda m, s: s == 3))
    )
    checker = (
        model.checker()
        .spawn_tpu_sharded_sortmerge(
            encoded=DGraphEncoded(model),
            n_shards=4,
            capacity=64,
            frontier_capacity=8,
        )
        .join()
    )
    path = checker.assert_any_discovery("reaches 3")
    assert path.states() == [1, 4]


def test_sharded_target_max_depth():
    single = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .target_max_depth(5)
        .spawn_tpu(capacity=1 << 10)
        .join()
    )
    sharded = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .target_max_depth(5)
        .spawn_tpu_sharded_sortmerge(
            n_shards=4,
            capacity=1 << 10,
            frontier_capacity=128,
            cand_capacity=512,
            bucket_capacity=256
        )
        .join()
    )
    assert sharded.unique_state_count() == single.unique_state_count()
    assert sharded.max_depth() == 5


def test_sharded_fast_mode_discovery_fingerprints():
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=2,
            capacity=1 << 10,
            frontier_capacity=128,
            cand_capacity=512,
            bucket_capacity=256,
            track_paths=False,
        )
        .join()
    )
    assert c.unique_state_count() == 288
    names = c.discovered_property_names()
    assert names == {"abort agreement", "commit agreement"}
    with pytest.raises(RuntimeError):
        c.discoveries()


def test_sharded_sparse_paxos_with_paths():
    """Sparse action dispatch through the SHARDED engine (round 4):
    the pair pipeline runs shard-locally and only real candidates
    enter the routing sort and the all_to_all. Counts, property set,
    and replayed paths match the host across shard counts, and the
    class ladders engage (f_min below the frontier capacity)."""
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

    model = paxos_model(PaxosModelCfg(client_count=1, server_count=3))
    host = model.checker().spawn_bfs().join()
    for shards in (1, 2):
        ck = (
            paxos_model(PaxosModelCfg(client_count=1, server_count=3))
            .checker()
            .spawn_tpu_sharded_sortmerge(
                n_shards=shards,
                capacity=1 << 10,
                frontier_capacity=1 << 7,
                cand_capacity=1 << 9,
                pair_width=16,
                f_min=32,       # exercise the frontier ladder
                v_min=128,      # exercise the visited ladder
                ladder_step=2,
                v_ladder_step=4,
            )
            .join()
        )
        assert ck.unique_state_count() == 265
        assert sorted(ck.discoveries()) == sorted(host.discoveries())
        p = ck.discovery("value chosen")
        assert p is not None and len(p.actions()) >= 1


def test_sharded_sparse_chunked_mode_matches():
    """The sharded memory-lean chunked sparse path (successors
    fingerprinted in chunks, routed tiles recomputed in dest_tile) —
    forced via a tiny flat budget — matches the host with replayable
    paths across 2 shards."""
    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model

    model = paxos_model(PaxosModelCfg(client_count=1, server_count=3))
    host = model.checker().spawn_bfs().join()
    ck = (
        paxos_model(PaxosModelCfg(client_count=1, server_count=3))
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=2,
            capacity=1 << 10,
            frontier_capacity=1 << 7,
            cand_capacity=1 << 9,
            pair_width=16,
            flat_budget_bytes=1 << 10,
        )
        .join()
    )
    assert ck.unique_state_count() == 265
    assert sorted(ck.discoveries()) == sorted(host.discoveries())
    p = ck.discovery("value chosen")
    assert p is not None and len(p.actions()) >= 1


# -- the receive-tile class (parallel/engine_sortmerge.py) -----------------

#: the paxos-4 mesh cell's lossless per-destination cap, 2^19 rows
_RECV_LADDER = [1 << 13, 1 << 15, 1 << 17, 1 << 19]


@pytest.mark.parametrize(
    "count, want",
    [
        (1 << 13, 0), (1 << 15, 1), (1 << 17, 2), (1 << 19, 3),
        ((1 << 13) + 1, 1), ((1 << 15) + 1, 2), ((1 << 17) + 1, 3),
        (0, 0),
        ((1 << 19) + 1, "overflow"),
    ],
)
def test_recv_tile_class(count, want):
    """The receive class holds the wave's largest destination run: a
    count equal to a rung takes that rung, one more takes the next,
    nothing at all takes the floor. A run above the top rung takes the
    top, and the routing stage's overflow check refuses the wave with
    the existing candidate-overflow error."""
    import jax.numpy as jnp

    from stateright_tpu.parallel.engine_sortmerge import (
        class_ladder,
        class_of,
    )

    assert class_ladder(1 << 19) == _RECV_LADDER
    if want == "overflow":
        assert class_of(count, _RECV_LADDER) == 3
        # the same past the top of a real engine's ladder: per-shard
        # destination runs of 2pc rm=3 outgrow an 8-row cap
        ck = (
            TwoPhaseSys(rm_count=3)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                n_shards=2,
                capacity=512,
                frontier_capacity=128,
                cand_capacity=1024,
                bucket_capacity=8,
                track_paths=False,
            )
        )
        with pytest.raises(RuntimeError, match="candidate/bucket overflow"):
            ck.join()
        assert ck._build_info["recv_classes"] == [8]
        return
    assert class_of(count, _RECV_LADDER) == want
    assert int(class_of(jnp.uint32(count), _RECV_LADDER)) == want


@pytest.fixture(scope="module")
def host_2pc4():
    return TwoPhaseSys(rm_count=4).checker().spawn_bfs().join()


@pytest.mark.parametrize(
    "shards, track_paths, tier_hot_rows",
    [
        (2, True, None),
        (4, True, None),
        (4, False, None),
        (2, True, 64),
        (4, False, 64),
    ],
)
def test_sharded_recv_classes_match_host(shards, track_paths,
                                         tier_hot_rows, host_2pc4):
    """Waves that run below the lossless receive cap explore exactly
    the host's space, with paths and in tiered mode alike, and every
    wave lands in one receive class."""
    import jax

    if len(jax.devices()) < shards:
        pytest.skip(f"need {shards} devices")
    kw = {} if tier_hot_rows is None else dict(tier_hot_rows=tier_hot_rows)
    c = (
        TwoPhaseSys(rm_count=4)
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=shards,
            capacity=1 << 11,
            frontier_capacity=256,
            cand_capacity=2048,
            waves_per_sync=8,
            track_paths=track_paths,
            **kw,
        )
        .join()
    )
    assert c.unique_state_count() == host_2pc4.unique_state_count()
    assert c.discovered_property_names() == set(host_2pc4.discoveries())
    if track_paths:
        for name, path in c.discoveries().items():
            prop = c.model.property_by_name(name)
            assert prop.condition(c.model, path.last_state()), name
    waves = c.metrics["recv_class_waves"]
    assert len(waves) == len(c._build_info["recv_classes"])
    if tier_hot_rows is None:
        assert sum(waves) == c.metrics["waves"]
    else:
        assert c.metrics["tier_spills"] >= 1


def test_sharded_recv_class_waves_2pc_rm5():
    """2pc rm=5 over four shards: wave 1 (one initial state) travels in
    the floor class, the run uses more than one class, and the traced
    and untraced runs report the same per-class wave counts — the
    traced one in ``run_end`` and in each wave's ``dest_cap``."""
    import jax

    from stateright_tpu.telemetry import RunTracer

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh")

    def spawn():
        return (
            TwoPhaseSys(rm_count=5)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                n_shards=4,
                capacity=1 << 12,
                frontier_capacity=1 << 10,
                cand_capacity=1 << 12,
                waves_per_sync=32,
                track_paths=False,
            )
        )

    c0 = spawn().join()
    tr = RunTracer()
    with tr.activate():
        c1 = spawn().join()
    assert c0.unique_state_count() == c1.unique_state_count() == 8832
    ladder = c1._build_info["recv_classes"]
    waves = c1.metrics["recv_class_waves"]
    assert waves == c0.metrics["recv_class_waves"]
    assert sum(waves) == c1.metrics["waves"]
    assert sum(1 for n in waves if n) > 1
    end = next(e for e in tr.events if e["ev"] == "run_end")
    assert end["recv_class_waves"] == waves
    caps = {}
    for e in tr.events:
        if e["ev"] == "shard_wave":
            caps.setdefault(e["wave"], set()).add(e["dest_cap"])
            assert e["dest_fill_peak"] <= e["dest_cap"]
    assert caps[min(caps)] == {ladder[0]}
    assert [
        sum(1 for cap in caps.values() if cap == {rung})
        for rung in ladder
    ] == waves


# -- the pair class (parallel/engine_sortmerge.py) -------------------------


@pytest.fixture(scope="module")
def mesh_cell_pair_ladder():
    """The pair classes of the paxos-4 mesh cell's engine, as the CLI's
    ``check-tpu 4 --shards 4`` builds it (no program is built)."""
    import jax

    from stateright_tpu.models.paxos import PaxosModelCfg, paxos_model
    from stateright_tpu.models.paxos_tpu import SHARDED_SIZES

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh")
    ck = (
        paxos_model(PaxosModelCfg(client_count=4, server_count=3))
        .checker()
        .spawn_tpu_sharded_sortmerge(
            n_shards=4, track_paths=False, **SHARDED_SIZES[4]
        )
    )
    return ck._pair_ladder()


@pytest.mark.parametrize(
    "n_pairs, want",
    [
        (0, 0),
        (1 << 13, 0), ((1 << 13) + 1, 1),
        (1 << 15, 1), ((1 << 15) + 1, 2),
        (1 << 17, 2), ((1 << 17) + 1, 3),
        (1 << 19, 3),
    ],
)
def test_pair_class_boundaries(n_pairs, want, mesh_cell_pair_ladder):
    """The mesh cell's pair classes are the class ladder over its
    per-shard candidate budget, 2^19: a wave with no pairs takes the
    floor, a count equal to a rung takes that rung, one more the next,
    and the budget itself the top."""
    import jax.numpy as jnp

    from stateright_tpu.parallel.engine_sortmerge import class_of

    ladder = mesh_cell_pair_ladder
    assert ladder == _RECV_LADDER
    assert class_of(n_pairs, ladder) == want
    assert int(class_of(jnp.uint32(n_pairs), ladder)) == want


@pytest.fixture(scope="module")
def one_chip_2pc5():
    return (
        TwoPhaseSys(rm_count=5)
        .checker()
        .spawn_tpu_sortmerge(
            capacity=1 << 15, frontier_capacity=1 << 13,
            track_paths=False,
        )
        .join()
    )


@pytest.mark.parametrize(
    "mode, kw",
    [
        ("untiered", {}),
        ("tiered", dict(tier_hot_rows=256)),
        ("chunked", dict(flat_budget_bytes=1 << 16)),
    ],
)
def test_sharded_pair_classes_2pc_rm5(mode, kw, one_chip_2pc5):
    """2pc rm=5 over four shards with a pair ladder of four rungs
    (128 to 4,096 rows): untiered, tiered (the hot tier spills) and
    chunked (the flat budget forces the recompute payload in every
    class above the floor) each explore exactly the one-chip
    engine's space, every wave runs in one pair class, more than one
    class engages, and the traced run reports the untraced run's
    per-class wave counts — in ``metrics``, in ``run_end`` and in
    each shard's ``pair_cap`` lane."""
    import jax

    from stateright_tpu.telemetry import RunTracer

    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU mesh")

    def spawn():
        return (
            TwoPhaseSys(rm_count=5)
            .checker()
            .spawn_tpu_sharded_sortmerge(
                n_shards=4,
                capacity=1 << 12,
                frontier_capacity=1 << 10,
                cand_capacity=1 << 12,
                waves_per_sync=4,
                track_paths=False,
                **kw,
            )
        )

    c0 = spawn().join()
    tr = RunTracer()
    with tr.activate():
        c1 = spawn().join()
    ref = one_chip_2pc5
    for c in (c0, c1):
        assert c.unique_state_count() == ref.unique_state_count() == 8832
        assert c.max_depth() == ref.max_depth()
        assert (c.discovered_property_names()
                == ref.discovered_property_names())
    ladder = c1._build_info["pair_classes"]
    assert ladder == [128, 256, 1024, 4096]
    waves = c0.metrics["pair_class_waves"]
    assert len(waves) == len(ladder)
    assert sum(waves) == c0.metrics["waves"]
    assert sum(1 for n in waves if n) > 1
    assert c1.metrics["pair_class_waves"] == waves
    end = next(e for e in tr.events if e["ev"] == "run_end")
    assert end["pair_class_waves"] == waves
    caps = {}
    for e in tr.events:
        if e["ev"] == "shard_wave":
            caps.setdefault(e["wave"], set()).add(e["pair_cap"])
            assert e["enabled_pairs"] <= e["pair_cap"]
    assert [
        sum(1 for cap in caps.values() if cap == {rung})
        for rung in ladder
    ] == waves
    if mode == "tiered":
        assert c0.metrics["tier_spills"] >= 1
    if mode == "chunked":
        assert {m["mode"] for m in c0._build_info["engine_modes"]} == {
            "chunked"
        }
