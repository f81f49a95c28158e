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
