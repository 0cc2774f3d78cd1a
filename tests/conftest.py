"""Test bootstrap: fake an 8-device TPU pod with virtual CPU devices.

The reference tests multi-rank behaviour single-process by mocking
torch.distributed (reference tests/conftest.py:24-42). The JAX-native
equivalent is better: run the *real* collectives on 8 virtual CPU devices
via ``--xla_force_host_platform_device_count=8`` (SURVEY.md §4), so every
shard_map/ppermute/psum path is executed, not mocked.

Env vars must be set before jax initialises its backends, hence the
module-level block ahead of any jax import.
"""

import os

# The tests run on the CPU platform whatever the machine holds: the chip
# is reached only through chip_smoke.py and the benchmark, never pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# jax latches JAX_PLATFORMS when it is first imported; if something
# imported it before this file ran, update the live config too.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from scaletorch_tpu.parallel import mesh as mesh_mod  # noqa: E402


@pytest.fixture(autouse=True)
def reset_mesh_manager():
    """Restore the global mesh singleton per test (parity: reference
    tests/conftest.py:14-21 reset_pgm)."""
    yield
    mesh_mod.reset_mesh_manager()


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


def make_mesh_manager(**kwargs):
    return mesh_mod.setup_mesh_manager(**kwargs)


@pytest.fixture
def mm_factory(devices8):
    """Factory fixture: build a MeshManager with arbitrary 5D geometry
    (parity: reference mock_pgm factory, tests/conftest.py:78-102)."""
    return make_mesh_manager
