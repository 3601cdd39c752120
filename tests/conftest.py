"""Test configuration: force a virtual 8-device CPU mesh before jax imports.

This is the exact analog of the reference's Spark ``local[n]`` test contexts
(SURVEY.md Section 4): multi-device sharding logic is exercised with no TPU
attached by forcing the host platform to expose 8 XLA CPU devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Model-fitting numerics are validated against float64 oracles.
jax.config.update("jax_enable_x64", True)

# Persistent compile cache: scan-heavy kernels (spline, CSS recursions) are
# slow to compile; cache across pytest runs, where the environment or the
# package's one resolver says.
from spark_timeseries_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled executable when a test module ends.

    Each loaded XLA:CPU executable holds several memory mappings, and jax
    keeps every program a process ever compiled.  One pytest process over
    the whole suite reached the kernel's ``vm.max_map_count`` (65,530)
    about 237 tests in, and the next compile or cache read segfaulted
    (rc=139, whether or not the persistent cache was on).  Modules rarely
    share program shapes, and the persistent cache above makes the few
    re-compiles a disk read.
    """
    yield
    jax.clear_caches()


@pytest.fixture()
def plane_off():
    """The telemetry plane disabled before and after a test (enable() builds
    a fresh registry, so state cannot bleed between tests either way); the
    telemetry files ask for it module-wide (``pytestmark``)."""
    from spark_timeseries_tpu import obs

    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 forced CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def lane_mesh(cpu_devices):
    """1-D ``(series,)`` mesh over all 8 forced CPU devices — the sharded
    chunk-walk fixture (ISSUE 6).  Because the forced-device env above runs
    before any jax import, sharded-walk tests execute in tier-1 directly
    (no subprocess, no skip): every lane dispatches to its own XLA CPU
    device exactly as it would to a TPU chip."""
    from spark_timeseries_tpu.parallel import mesh as meshlib

    return meshlib.default_mesh()
