"""Test configuration.

Tests run on the CPU (``JAX_PLATFORMS=cpu``, forced here) with 8
virtual XLA devices, so multi-chip sharding logic is exercised without
TPU hardware.  The chip is reached only through ``chip_smoke.py``
(see README.md).  These env vars must be set before jax is imported
anywhere, hence the top-of-conftest placement.
"""

import os
import sys

# FORCE cpu (not setdefault): a test run must never reach for a chip,
# whatever the calling environment selects
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE_ROOT = "/root/reference"


def reference_path(*parts: str) -> str:
    """Path into the read-only reference checkout (tests skip if absent)."""
    return os.path.join(REFERENCE_ROOT, *parts)


import pytest


@pytest.fixture(autouse=True)
def _isolate_autopilot():
    """The autopilot singleton (cost model + tuner knob overrides) is
    process-wide and fed by every ledger batch any test closes; without
    a per-test reset, a tuner step taken during one module's funnel
    tests changes another module's sweep counts (order-dependence).
    Tests that need accumulation build it within themselves."""
    yield
    from mythril_tpu.autopilot import reset_for_tests

    reset_for_tests()
