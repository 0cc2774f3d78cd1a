"""The chip the ``tests/aot/`` files compile for, and the configurations
each of them compiles.
"""

import pytest
from jax.sharding import SingleDeviceSharding

from tests.aot.programs import _programs_of
from tests.conftest import compile_cache_at


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, for the module that asks: only a
    worker that runs one of these files loads the TPU compiler, and a
    second worker that does skips unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
    (the driver's command sets it). Module-scoped because of what it
    sets while it lasts: the environment, and no compile cache, because
    the client that compiles for a described chip keeps each program
    and can load none back ("DeserializeLoadedExecutable not
    implemented": a warning and the compile again at every hit)."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env, compile_cache_at(None):
        env.setenv("TPU_SKIP_MDS_QUERY", "1")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        env.setenv("TPU_LOG_DIR", "disabled")
        # the target is a topology, so the platform test cannot see it:
        # the step programs pick their pair as they would on the chip
        env.setenv("SCALETORCH_TPU_FORCE_PALLAS", "1")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def pytest_generate_tests(metafunc):
    """A test that takes ``configuration`` runs once for each name its
    file lists in ``CONFIGURATIONS``, so the cases every configuration
    is held to (``step_program_cases.py``) are imported by the file that
    compiles the configuration's programs and by no other."""
    if "configuration" in metafunc.fixturenames:
        metafunc.parametrize("configuration", metafunc.module.CONFIGURATIONS,
                             scope="module")


@pytest.fixture(scope="module")
def serving_programs(one_chip, configuration):
    return _programs_of(one_chip, configuration)
