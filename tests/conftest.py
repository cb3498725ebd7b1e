import os

# Must be set before jax initializes: tests run on a virtual 8-device CPU
# mesh so multi-chip sharding paths are exercised without TPU hardware.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Force CPU as the default backend: tests need the 8-device virtual mesh
# and must never take the chip from the process that owns it.
if os.environ.get("PATHWAY_TPU_TEST_REAL") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest

from pathway_tpu.internals.parse_graph import G


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process batteries excluded from the tier-1 "
        "sweep (-m 'not slow'); run by scripts/ci_lanes.sh and the "
        "fault-matrix CLI",
    )


@pytest.fixture(autouse=True)
def _clear_graph():
    G.clear()
    yield
    G.clear()
