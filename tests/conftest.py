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
# XLA's CPU client runs every virtual device's share of a step on one thread
# pool, sized by NPROC where it is set and else by the cores it may use - 8
# here, as many as the devices.  A step with a collective blocks 8 threads
# until all 8 have joined; steps are dispatched back to back, the pool steals
# work out of order, and under six busy workers a later step's shares take
# threads the earlier step still needs: a deadlock, which XLA ends after 40 s
# by aborting the process ("Termination timeout ... only 5 of them arrived";
# it took a whole worker, and xdist then never finished the run).  With room
# for four steps in the pool the stress that aborted 5 of 6 processes in two
# minutes ran 240,000 steps clean.  Children of the tests inherit it.
os.environ.setdefault("NPROC", "32")

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


@pytest.fixture()
def engine_chunks(monkeypatch):
    """``plan(owed, chunk, floor) -> [(offset, n_valid, width), ...]``: the
    chunks the serve engine dispatches for a prompt that owes its cache
    ``owed`` tokens, with ``PREFILL_CHUNK`` at ``chunk`` and the floor of
    its widths at ``floor`` (both far under the served sizes: a rehearsal) -
    whole chunks of ``chunk``, then the narrowest of
    ``model_server.chunk_widths`` that holds the rest.  A model's chunk is
    held to its reference through the widths the engine would hand it."""
    from distributed_tensorflow_examples_tpu.serve import model_server

    def plan(owed: int, chunk: int, floor: int) -> list:
        monkeypatch.setattr(model_server, "PREFILL_FLOOR", floor)
        widths = model_server.chunk_widths(chunk)
        chunks = []
        for offset in range(0, owed, chunk):
            n = min(chunk, owed - offset)
            chunks.append((offset, n, next(w for w in widths if w >= n)))
        return chunks

    return plan


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-process / fault-injection tests"
    )
