"""The Pallas dedup kernels compile for a TPU v5e chip.

The TPU compiler ships with the installed jaxlib and compiles for a chip
that is described, not attached, so these tests run on the CPU-only
test machines. They lower and compile ``merge_sorted`` and
``member_sorted`` with ``impl="pallas"`` (no interpreter) at one small
shape and at the paxos-4 main-path shape: the visited prefix at
``STRUCTURAL_SIZES[4]`` capacity (5 << 19 rows) against a frontier-sized
winner/query block (1 << 19 rows). Interpret mode never checked the
layout rules these compiles enforce (PERF.md §bring-up).

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library, and the suite
runs under several xdist workers.
"""

import os

import pytest

SHAPES = {
    "small": (4096, 1024),
    "paxos4": (5 << 19, 1 << 19),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("op", ["merge_sorted", "member_sorted"])
def test_pallas_kernel_compiles_for_v5e(op, shape, one_chip,
                                         no_compile_cache):
    import jax
    import jax.numpy as jnp

    from stateright_tpu.ops import merge

    fn = getattr(merge, op)
    na, nb = SHAPES[shape]

    def spec(n):
        return jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)

    compiled = jax.jit(
        lambda a_lo, a_hi, b_lo, b_hi: fn(
            a_lo, a_hi, b_lo, b_hi, impl="pallas"
        )
    ).lower(spec(na), spec(na), spec(nb), spec(nb)).compile()
    assert "tpu_custom_call" in compiled.as_text()
