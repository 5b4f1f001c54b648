"""The traceable device cost matrices (costmodels/device_costs.py) must
agree elementwise with the numpy policy implementations the host path
uses (coco_cost_matrix / whare_cost_matrix)."""

import numpy as np
import jax.numpy as jnp

from ksched_tpu.costmodels.coco import coco_cost_matrix
from ksched_tpu.costmodels.device_costs import coco_device_cost_fn, whare_device_cost_fn
from ksched_tpu.costmodels.whare import whare_cost_matrix


def test_coco_device_matches_numpy():
    rng = np.random.default_rng(0)
    for seed in range(5):
        rng = np.random.default_rng(seed)
        M = int(rng.integers(3, 50))
        census = rng.integers(0, 10, (M, 4)).astype(np.int64)
        penalties = rng.integers(0, 50, (M, 4)).astype(np.int64)
        want = coco_cost_matrix(census, penalties)
        got = np.asarray(coco_device_cost_fn(penalties)(jnp.asarray(census)))
        np.testing.assert_array_equal(got, want)
        # and the no-penalty form
        want0 = coco_cost_matrix(census)
        got0 = np.asarray(coco_device_cost_fn()(jnp.asarray(census)))
        np.testing.assert_array_equal(got0, want0)


def test_whare_device_matches_numpy_homogeneous():
    for seed in range(5):
        rng = np.random.default_rng(seed)
        M = int(rng.integers(3, 50))
        slots = 16
        census = rng.integers(0, 5, (M, 4)).astype(np.int64)
        census = np.minimum(census, slots)  # can't run more than slots
        idle = np.maximum(0, slots - census.sum(axis=1))
        want = whare_cost_matrix(census, idle, np.full(M, slots, np.int64))
        got = np.asarray(
            whare_device_cost_fn(slots)(jnp.asarray(census))
        )
        np.testing.assert_array_equal(got, want)


def test_whare_platform_scales_expected_slowdown():
    """Heterogeneity: the oldest platform must never be cheaper than the
    newest with the same census, and the device form is the numpy form."""
    census = np.full((3, 4), 2, np.int64)
    platform = np.asarray([2, 0, 1], np.int64)  # C, A, B
    cost = np.asarray(
        whare_device_cost_fn(16, platform=platform)(jnp.asarray(census))
    )
    assert (cost[:, 1] >= cost[:, 2]).all() and (cost[:, 2] >= cost[:, 0]).all()
    assert (cost[:, 1] > cost[:, 0]).any()
    idle = np.full(3, 8, np.int64)
    want = whare_cost_matrix(census, idle, np.full(3, 16, np.int64), platform=platform)
    np.testing.assert_array_equal(cost, want)
    # a machine of the neutral platform costs what it costs with no platform given
    np.testing.assert_array_equal(
        want[:, 2], whare_cost_matrix(census, idle, np.full(3, 16, np.int64))[:, 2]
    )
