"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware; the driver separately dry-runs the
multi-chip path (see __graft_entry__.py).
"""

import os

# Tests run on the CPU whatever the machine holds: set before the first
# `import jax`, this is all the CPU path needs.
os.environ["JAX_PLATFORMS"] = "cpu"
# Solver-interior telemetry defaults OFF under the tier-1 wall: with it
# on, every solver test would compile the (larger) telemetry variant of
# its executable, and the suite's compile budget is the binding
# constraint. Telemetry behavior is exercised by tests/test_soltel.py
# (explicit per-solver caps, which ignore this default) and the
# chaos/obs smokes run with it ON outside the wall (`make obs-smoke`).
os.environ.setdefault("KSCHED_SOLTEL", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from ksched_tpu.utils import seed_rng  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests excluded from the budgeted tier-1 "
        "selection (-m 'not slow'); run them with a plain `pytest tests/`",
    )


@pytest.fixture(autouse=True)
def _seeded_rng():
    seed_rng(42)
    yield
