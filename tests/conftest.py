"""Test fixture: simulate an 8-device TPU slice on CPU.

The CPU analogue of the reference's Gloo/CPU cluster simulation
(reference: src/distributed_trainer.py:55-61, src/playground/ddp_script.py:
230-234): all sharding/collective tests run on 8 fake CPU devices so the
full multi-chip path is exercised without TPU hardware. Must run before
jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Keep test compiles fast & deterministic.
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Entry points point JAX's persistent compile cache at the checkout's
# .jax_cache/ (runtime.enable_compile_cache). Tests stay hermetic: no
# executable written by one run may be loaded by the next.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# The device-less TPU-topology tests initialize libtpu, which on a
# non-GCP host (or one whose metadata server answers 403) retries the
# instance-metadata fetch 30x per variable — minutes of wall-clock at
# 0% CPU before the init even fails. Skip the metadata query outright:
# topology descriptors don't need it, and the suite must not stall on
# a dead metadata endpoint.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu8():
    """Session-wide 8-device CPU runtime with a pure-DP mesh."""
    from distributed_training_tpu.runtime import fake_cpu_runtime
    return fake_cpu_runtime(8)


@pytest.fixture(scope="session", autouse=True)
def _check_devices():
    assert jax.device_count() >= 8, (
        "conftest failed to fake 8 cpu devices; got "
        f"{jax.device_count()}")
