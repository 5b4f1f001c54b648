"""One accepted test of this directory states the set of guarantees as it
stood at PR 32 by equality (`test_benchmark_checks.py`: "the guarantees
stated today are these five"). `k8s-5000-zonespread` states a sixth,
`topology_spread`, so that sentence is false from PR 33 on, and a PR that
adds a configuration may not edit a file the benchmark has.

The test is NOT taken out of the collection: it runs in every run and is
reported as an expected failure (`xfailed`, with the reason below), and
only its `AssertionError` is expected. `strict`: once a `benchmark` PR has
rewritten the pin (`==` to `>=` at its first assertion, or the form of
`test_benchmark_zonespread.py`'s test of the same name), it passes, the
run turns red, and this file is deleted with that edit. Until then
`test_benchmark_zonespread.py` holds both halves of the pinned test, the
first by an equality that does not go stale (stated == the modules under
`checks/`)."""

import pytest

PINNED = (
    "test_benchmark_checks.py::"
    "test_the_guarantees_stated_today_are_these_five_and_each_cell_loads"
)
REASON = (
    "PR 32's pin of the stated guarantees to five, by equality; PR 33 states a "
    "sixth (topology_spread) and may not edit the file: the next benchmark PR "
    "rewrites the pin and deletes tests/benchmark/conftest.py"
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED):
            item.add_marker(pytest.mark.xfail(reason=REASON, raises=AssertionError, strict=True))
