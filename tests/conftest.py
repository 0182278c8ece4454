"""Test configuration.

Tests run on a virtual 8-device CPU mesh (fast, deterministic, no TPU
required); the sharded-engine tests in test_parallel.py exercise the
multi-chip path (shard_map + all_to_all frontier shuffles) on that
mesh. The chip is driven by ``chip_smoke.py`` (one process, through
the chip tool); ``STPU_TPU_TESTS=1`` runs this suite on an attached
device instead.

Forcing the CPU takes both (a) the XLA flag before any backend
initializes and (b) an explicit ``jax_platforms`` config update, so a
machine with a TPU attached still runs the suite on the CPU.
"""

import os

if os.environ.get("STPU_TPU_TESTS"):
    # Run the suite against the real device (the TPU-gated tests stop
    # skipping).
    import jax  # noqa: E402
else:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")
