"""Force an 8-device virtual CPU mesh for all tests.

The TPU-world answer to the reference's "multi-node without a cluster"
(`cluster4` = localhost slots=4, mpirun --oversubscribe — SURVEY.md §4): run
the real sharded programs on N virtual CPU devices. Must run before jax
initializes its backends, hence the env mutation at conftest import time.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
# tools/ scripts (telemetry_report, an4_report) are imported by tests;
# one insert here replaces per-test sys.path mutation
sys.path.insert(0, os.path.join(_ROOT, "tools"))

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an attached chip
# The persistent compile cache (utils.platform.enable_compile_cache) exists
# for the chip's minute-long compiles. Entry points called by tests still
# choose its directory, but nothing is written or read: XLA:CPU warns on
# every entry it loads back, and child processes inherit this too.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture(scope="session")
def mesh8():
    from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=8, seq=1))


@pytest.fixture(scope="module")
def topo():
    """A TPU v5e:2x2 that is described, not attached: what the files that ask
    the chip's compiler without a chip compile for
    (tests/test_tpu_compile*.py, one file a kind of program). Made
    inside the fixture, so that only a worker that runs such a test loads
    the TPU's library; skipped where it cannot be described."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture
def _compile_cache_off():
    """The persistent compile cache off round a test that compiles for the
    described chip (`pytestmark = pytest.mark.usefixtures(...)` in those
    files): an entry written for a described chip cannot be read back
    without one, and the next compile would only warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
