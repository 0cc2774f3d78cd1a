"""Test bootstrap: fake an 8-device TPU pod with virtual CPU devices.

The reference tests multi-rank behaviour single-process by mocking
torch.distributed (reference tests/conftest.py:24-42). The JAX-native
equivalent is better: run the *real* collectives on 8 virtual CPU devices
via ``--xla_force_host_platform_device_count=8`` (SURVEY.md §4), so every
shard_map/ppermute/psum path is executed, not mocked.

Env vars must be set before jax initialises its backends, hence the
module-level block ahead of any jax import.

A test that needs a compiled program finds it in the run's compile cache
(ROADMAP D1; the other half of the rule is ``tests/inference/compiled.py``).
Every entry point keeps one (``env.configure_compile_cache``), and so does
a run of the tests: where the caller set ``JAX_COMPILATION_CACHE_DIR`` the
run uses that directory and removes nothing; otherwise the first process
to import this file makes a temporary directory, names it in the variable
and removes it when its session ends. The xdist controller imports this
file before it starts its workers, and they and every subprocess a test
starts with the inherited environment (``scripts/serve.py``,
``benchmarks/run.py``) find the variable set: one run, one directory, and
the same engine is compiled once in it, not once a case, a file, a worker
and a subprocess. Never ``env.COMPILE_CACHE_DEFAULT``: a run leaves
nothing in the tree and nothing behind it.
"""

import contextlib
import os
import shutil
import tempfile

# The tests run on the CPU platform whatever the machine holds: the chip
# is reached only through chip_smoke.py and the benchmark, never pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_made_cache_dir = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _made_cache_dir = tempfile.mkdtemp(prefix="scaletorch_tpu-tests-jax-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _made_cache_dir
# What jax keeps: every program, however short its compile (jax's own
# floor is 1 s). A run starts ~60 processes that each compile the same
# few hundred one-operation programs first; measured on three files
# (CHANGES.md, PR 63) 0 costs least CPU, 0.3 and 1 s more, no cache most.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

# jax latches JAX_PLATFORMS when it is first imported; if something
# imported it before this file ran, update the live config too.
jax.config.update("jax_platforms", "cpu")
# The tests' programs are toys that run for milliseconds and compile for
# seconds: LLVM's optimisations are most of a compile here and buy the
# run nothing (tier-1 whole: 1,269 -> 1,019 s, CHANGES.md, PR 63). In this
# process only: a subprocess is an entry point as an operator starts it.
# The compiler for a described TPU compiles the same program either way
# (``tests/aot/``: the two texts hash alike).
jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402

from scaletorch_tpu.parallel import mesh as mesh_mod  # noqa: E402


def pytest_unconfigure(config):
    """The process that made the run's compile cache removes it (an
    xdist worker inherited the variable and made none)."""
    if _made_cache_dir is not None:
        shutil.rmtree(_made_cache_dir, ignore_errors=True)


@contextlib.contextmanager
def compile_cache_at(directory):
    """jax's compile cache kept in ``directory`` (None: nowhere) while
    the block lasts, then where the run keeps it again: for a test that
    counts what a process with no cache, or with a cache of its own,
    keeps."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", directory)
    compilation_cache.reset_cache()     # forget the directory it had
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


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
