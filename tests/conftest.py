"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's CI strategy of simulating multi-node setups locally
(tests/nightly via `launch.py --launcher local`, SURVEY.md §4): multi-chip
sharding is validated with XLA's forced host-device count; the real TPU is
exercised by chip_smoke.py instead.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

import jax


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: soak/stress tests excluded from tier-1 (-m 'not slow')")

# The unit suite runs on the CPU whatever the machine holds (a chip
# belongs to one process at a time, and the suite runs several).
# Backends are created lazily, so setting the config here keeps the TPU
# client from ever being created.
jax.config.update("jax_platforms", "cpu")

# CPU/TPU XLA default matmul precision is allowed to drop to bf16; numeric
# parity tests need true f32 (off the tests the MXU keeps the fast default).
jax.config.update("jax_default_matmul_precision", "float32")


# The package turns the persistent compile cache on at import
# (<checkout>/.jax_cache).  The suite's own processes run without it:
# several xdist workers writing one directory race on half-written
# entries, and tests of the cache enable it on a tmp_path themselves.
import mxnet_tpu  # noqa: E402

mxnet_tpu.aot.disable_persistent_cache()


@pytest.fixture(autouse=True)
def _seed_rngs():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
