"""Hermetic multi-device test rig.

The reference's trick — run the *real* framework on a *local* multi-worker
topology (Spark ``local[4]``, single-node Ray; SURVEY §4.1/§4.3) — ports to
JAX as an 8-device virtual CPU mesh: every DP/FSDP/TP sharding test runs the
actual pjit/collective path in CI without TPUs.

Must set the env vars before jax is imported anywhere.
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("ZOO_NUM_CORES", "4")

# One persistent compile cache for the run and for every process it
# spawns (the subprocess rigs inherit the environment): the suite is
# compile-bound, and most of what it compiles is the same few tiny
# executables — once per test, per model instance, per replica process.
# Placed by the program's own rule; thresholds 0 because these compiles
# are all far below jax's 1 s default.
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from zoo_tpu.common.compile_cache import ensure_compile_cache  # noqa: E402

ensure_compile_cache()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    """The end-to-end smoke rehearsal runs last: it is the longest
    single test and the one that says least about where a fault is, so
    every narrower test reports first."""
    items.sort(key=lambda item: item.fspath.basename
               == "test_chip_smoke.py")


@pytest.fixture()
def orca_ctx():
    """Function-scoped orca context over the 8-device CPU mesh (mirrors the
    reference's package-scoped ``init_orca_context(cores=4)`` conftest,
    ``test/zoo/orca/learn/spark/conftest.py:20-25``)."""
    from zoo_tpu.orca import init_orca_context, stop_orca_context
    ctx = init_orca_context(cluster_mode="local", cores=4)
    yield ctx
    stop_orca_context()


@pytest.fixture()
def tmp_model_dir(tmp_path):
    return str(tmp_path / "model")
