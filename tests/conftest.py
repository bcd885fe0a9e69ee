"""Test harness configuration.

The suite runs on the CPU backend (the driver sets ``JAX_PLATFORMS=cpu``;
this sandbox has no accelerator), with 8 virtual CPU devices for the
multi-device sharding tests: ``xla_force_host_platform_device_count`` is set
here, before anything initializes the backend (the TPU analogue of the
reference's multi-actor-in-one-JVM TestKit strategy, SURVEY.md §4). Pallas
kernels run interpreted here; what the TPU's compiler makes of them is
checked, without a chip, by tests/test_chip_compile.py, and on the chip by
``chip_smoke.py``.

Numeric parity assertions need exact f32 matmuls, so matmul precision is
pinned to "highest" suite-wide (unit tests check correctness, not
throughput).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from sharetrade_tpu.utils.runtime_env import configure_compile_cache  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compile cache, placed by the one rule every entry point follows
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache): repeat runs
# and the six xdist workers share compiles of >= 1 s.
configure_compile_cache()


@pytest.fixture
def tmp_journal_path(tmp_path):
    return str(tmp_path / "events.journal")


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices("cpu")
    assert len(devices) >= 8, (
        "expected 8 virtual CPU devices (xla_force_host_platform_device_count)")
    return devices[:8]


@pytest.fixture(scope="session")
def cpu_mesh(cpu_devices):
    """8-device dp mesh on the virtual CPU client for sharding tests."""
    from jax.sharding import Mesh
    return Mesh(np.array(cpu_devices).reshape(8), ("dp",))
