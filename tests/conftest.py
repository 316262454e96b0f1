"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding is exercised without TPU hardware (SURVEY.md §7 / driver dryrun
contract).
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _close_leaked_worker_servers():
    """Sweep worker/coordinator HTTP servers a module leaves open.

    Autouse module fixtures are set up before a module's own fixtures, so
    this teardown runs AFTER theirs (LIFO): properly closed clusters are
    unaffected, while leaked serve_forever threads — which accumulated
    into the hundreds over a full run and starved later tests — are
    closed at each module boundary (reference test pattern:
    DistributedQueryRunner.java:108 is closeable)."""
    yield
    from presto_tpu.worker.server import WorkerServer
    WorkerServer.close_all_live()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy end-to-end cases excluded from the tier-1 budget "
        "(run with -m slow)")
