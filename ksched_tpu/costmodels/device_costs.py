"""Traceable (jnp) twins of the vectorized cost matrices, for the
device-resident scheduling round.

DeviceBulkCluster's `class_cost_fn` runs inside the jitted round and
receives the on-device running-class census [M, C]; these functions turn
it into the [C, M] arc-cost matrix the transport solve consumes — the
same policies as the numpy forms (costmodels/coco.py `coco_cost_matrix`,
costmodels/whare.py `whare_cost_matrix`; tests assert elementwise
equality), expressed in jnp so the whole round stays one compiled
program.

The reference plans these models but never implements them
(costmodel/interface.go:33-43); the policy inputs exist as protos
(coco_interference_scores.proto:11-16, whare_map_stats.proto:12-18).
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from .coco import INTERFERENCE, MAX_COST as COCO_MAX_COST
from .whare import DEFAULT_PLATFORM, IDLE_BONUS, MAX_COST as WHARE_MAX_COST, psi_prior


def coco_device_cost_fn(penalties: Optional[np.ndarray] = None):
    """class_cost_fn for CoCo: census [M, 4] -> cost [4, M] int32.

    penalties: optional [M, 4] static per-machine per-incoming-class
    penalty matrix (CoCoInterferenceScores), closed over as a constant.
    """
    W = jnp.asarray(INTERFERENCE, jnp.int32)
    pen = None if penalties is None else jnp.asarray(penalties.T, jnp.int32)

    def fn(census):
        cost = W @ census.T.astype(jnp.int32)  # [4, M]
        if pen is not None:
            cost = cost + pen
        return jnp.minimum(cost, COCO_MAX_COST).astype(jnp.int32)

    return fn


def whare_device_cost_fn(
    slots,
    platform: Optional[np.ndarray] = None,
    psi: Optional[np.ndarray] = None,
):
    """class_cost_fn for Whare-Map: census [M, 4] -> cost [4, M] int32.

    slots: [M] total slots of each machine, its own (machines may differ
    in size; one number where they are alike). The device round has no
    separate idle input: idle(m) = slots(m) - census row sum.
    platform: optional [M] indices into costmodels.whare.PLATFORMS, the
    platform of each machine (the "heterogeneity in homogeneous WSCs"
    axis of Whare-Map: what its `ksched.io/platform` label names);
    default: one platform, the neutral one.
    psi: optional [4, P, 5] slowdown map (default: the learning prior).
    """
    psi_np = np.asarray(psi_prior() if psi is None else psi, np.int32)
    # each machine against the map of its platform: [4, M, 5], or [4, 1, 5]
    # for every machine alike
    where = [DEFAULT_PLATFORM] if platform is None else np.asarray(platform, np.int32)
    psi_m = jnp.asarray(psi_np[:, where, :])
    slots_m = jnp.asarray(np.maximum(1, np.asarray(slots, np.int32)))  # [M], or a scalar

    def fn(census):
        c32 = census.astype(jnp.int32)
        running = jnp.sum(c32, axis=1)  # [M]
        # an empty machine's one co-runner is ALONE: the fifth count
        beside = jnp.concatenate([c32, (running == 0).astype(jnp.int32)[:, None]], axis=1)
        expected = jnp.sum(psi_m * beside[None, :, :], axis=2) // jnp.sum(beside, axis=1)[None, :]  # [4, M]
        idle = jnp.maximum(0, slots_m - running)
        bonus = (IDLE_BONUS * idle) // slots_m
        cost = expected - bonus[None, :]
        return jnp.clip(cost, 0, WHARE_MAX_COST).astype(jnp.int32)

    return fn
