"""Test bootstrap: fake 8-device CPU mesh.

The analog of the reference's in-process fake cluster
(``multi_worker_test_base.create_in_process_cluster`` — SURVEY.md section 4):
all sharding/collective tests run on 8 virtual CPU devices so multi-chip SPMD
programs compile and execute without TPU hardware.  Must run before JAX
initialises its backends; pytest imports conftest before test modules, so
setting the env + config here is safe.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Tests run on the virtual CPU mesh whatever the host offers.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from distributed_tensorflow_examples_tpu.parallel import local_mesh_for_testing

    return local_mesh_for_testing({"data": 8})


@pytest.fixture(scope="session")
def mesh_4x2():
    from distributed_tensorflow_examples_tpu.parallel import local_mesh_for_testing

    return local_mesh_for_testing({"data": 4, "model": 2})


@pytest.fixture()
def rng():
    return jax.random.key(0)


@pytest.fixture(autouse=True)
def _np_seed():
    np.random.seed(0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-process / fault-injection tests"
    )
