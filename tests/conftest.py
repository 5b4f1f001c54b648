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


#: Two accepted tests of tests/benchmark/test_benchmark_seams.py (PR 37) state
#: that every cell's pods are `class_only` and that `benchmarks/pods/` holds
#: that module alone. `k8s-5000-preemption` (PR 38) brings `pods/by_role.py`,
#: so both sentences are false for it, and a PR that adds a configuration may
#: not edit a file the benchmark has (nor `tests/benchmark/conftest.py`, where
#: PR 33 kept such a mark). The tests are NOT taken out of the collection:
#: they run and are reported `xfailed`, only their `AssertionError` is
#: expected, and `strict` turns the run red once a `benchmark` PR has
#: rewritten them (parametrize the first over `PLAN_DIGESTS`, list both
#: modules in the second), which deletes this hook with that edit (PERF.md
#: section 7). `test_benchmark_preemption.py` holds what stays true of both.
_STALE_SINCE_PR38 = (
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[k8s-5000-preemption.rollout-",
    "test_benchmark_seams.py::test_class_only_stamps_each_event_as_it_is_made_and_draws_"
    "nothing_from_the_frameworks_rng",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if any(stale in item.nodeid for stale in _STALE_SINCE_PR38):
            item.add_marker(pytest.mark.xfail(
                reason="PR 37's pin of every cell's pods to class_only; PR 38 brings "
                "pods/by_role.py and may not edit the file: the next benchmark PR does",
                raises=AssertionError, strict=True,
            ))
