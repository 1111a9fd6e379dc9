"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the reference's analogue: running every test
under oversubscribed localhost MPI with 2-4 ranks, tests/CMakeLists.txt:1032).
Must set the env before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the tests' CPU pin, honoured as is
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: wall-clock legs that need a host of their own; "
        "tier-1 runs with -m 'not slow'")


@pytest.fixture(autouse=True)
def _fresh_cost_model():
    """The online cost model (ISSUE 18) is process-global by design —
    it must survive context fini to feed warm instantiations. Under
    pytest that globality would leak measurements between unrelated
    tests (a class measured slow in one test steers placement/fusion in
    the next), so every test starts from a cold model, mirroring how
    LaneStats snapshots isolate the engagement counters."""
    yield
    from parsec_tpu.core import costmodel
    costmodel.model.reset()


@pytest.fixture()
def context():
    """A fresh single-rank runtime context per test."""
    from parsec_tpu.core.context import Context
    ctx = Context(nb_cores=1)
    yield ctx
    ctx.fini()
