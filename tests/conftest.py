"""Test bootstrap: force an 8-device virtual CPU platform.

The analogue of the reference's ``@distributed_test`` process-forking
fixture (tests/unit/common.py:57): instead of forking NCCL workers we give
JAX eight virtual CPU devices so every mesh/collective test runs
single-process. Must run before jax is imported anywhere.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_groups():
    """Each test gets a fresh (uninitialised) global mesh."""
    yield
    from deepspeed_tpu.utils import groups
    groups.destroy()


@pytest.fixture
def mesh8():
    """A pipe=1 data=8 expert=1 model=1 mesh over the virtual devices."""
    from deepspeed_tpu.utils import groups
    return groups.initialize()


def require_devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n, reason=f"requires {n} devices")
