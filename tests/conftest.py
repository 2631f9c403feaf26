"""Test bootstrap: force an 8-device virtual CPU platform.

The reference tests distributed behavior single-node with
``mpirun --oversubscribe`` over loopback BTLs (SURVEY.md §4); the
TPU-native analog is an N-device virtual CPU mesh via
``--xla_force_host_platform_device_count``. This must be configured
before jax initializes a backend.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# MPI_DOUBLE / MPI_INT64_T are first-class; without x64 JAX silently
# truncates them to 32-bit, which breaks datatype/op bit-parity.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with -m 'not slow': timing-sensitive acceptance
    # tests (the streaming-engine bandwidth shape) opt out of CI noise
    config.addinivalue_line(
        "markers", "slow: timing-sensitive; excluded from tier-1")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


# Opt-in runtime lockdep (tpucheck's dynamic witness): under
# OMPI_TPU_LOCKDEP=1 every lock allocated DURING the test session is
# order-witnessed, and an observed AB/BA inversion fails the session
# at teardown.  Off by default — the witness costs a dict update per
# acquire and belongs in targeted runs, not every tier-1 pass.
from ompi_tpu.core.var import _TRUE_STRINGS  # noqa: E402

if os.environ.get("OMPI_TPU_LOCKDEP", "").strip().lower() in _TRUE_STRINGS:

    @pytest.fixture(scope="session", autouse=True)
    def _lockdep_witness():
        from ompi_tpu.analysis import lockdep

        lockdep.enable()
        lockdep.reset()
        yield
        try:
            lockdep.assert_clean()
        finally:
            lockdep.disable()
            lockdep.reset()
