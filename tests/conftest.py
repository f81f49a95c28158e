"""Test harness: run all tests on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is validated
on XLA's host platform with 8 virtual devices (the same XLA partitioner runs on
TPU). Mirrors the reference's embedded single-process cluster test pattern
(query/query_test.go TestMain runs zero+worker in-process, SURVEY.md §4).
"""

import os

# Must be set before jax is imported anywhere in the test process. Forced (not
# setdefault): a machine with a chip defaults JAX to it, and tests must run on
# the virtual 8-device CPU mesh (the chip is reached only through
# chip_smoke.py, one process per chip).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402

from dgraph_tpu.utils import runtime  # noqa: E402

# Persistent compilation cache (the same place serve and worker use):
# makes repeated test runs cheap.
runtime.configure_compile_cache()
assert len(jax.devices()) >= 8, (
    "tests need the 8-virtual-device CPU mesh; got "
    f"{jax.devices()} — check XLA_FLAGS/JAX_PLATFORMS handling in conftest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _restore_query_edge_limit():
    """The edge budget default is a module global (engine.MAX_QUERY_EDGES);
    tests that shrink it via set_query_edge_limit must not leak the budget
    into later tests — restore it unconditionally around every test."""
    from dgraph_tpu.query import engine

    old = engine.MAX_QUERY_EDGES
    yield
    engine.MAX_QUERY_EDGES = old


@pytest.fixture(scope="module")
def one_v5e():
    """A described (not attached) v5e chip to compile for: the TPU's own
    compiler and its memory analysis, with no chip. Skips where this
    installation cannot describe one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # or the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache and never read back (the next one would warn); the tests
        # that take it are their files' last, so no other compile goes
        # uncached
        cached = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cached)
            compilation_cache.reset_cache()
