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
