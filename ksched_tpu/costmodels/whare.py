"""Whare-Map: heterogeneity- and co-runner-aware cost model.

The reference declares WHARE (costmodel/interface.go:37) and carries its
input — the per-machine `WhareMapStats` census (whare_map_stats.proto:
12-18) — without implementing the model. This implements the Whare-MCs
idea (Mars et al., "Whare-Map: heterogeneity in 'homogeneous' warehouse-
scale computers", ISCA'13): score each (task class, machine) pair by the
*observed* slowdown of that class when running on that machine with its
current co-runner mix, and prefer placements with low expected slowdown.

The "map" is a 4×4 matrix psi[c, k]: EWMA-learned normalized slowdown
(scaled ×100) of class c co-located with class k. It starts from a
neutral prior and is refined online via `record_runtime` as task final
reports arrive (TaskFinalReport, task_final_report.proto:10-19, carries
the runtimes the reference would feed this with).

EC(c) → machine cost = expected slowdown of class c against the
machine's census, census-weighted:

    cost(c, m) = Σ_k census_k(m) · psi[c, k] / max(1, Σ_k census_k(m))
                 − IDLE_BONUS · idle(m)/slots(m)

so an idle machine costs its prior, a crowded noisy machine costs its
measured co-runner slowdown. Capacity = free slots below, as in the
trivial model (trivial_cost_modeler.go:76-83).

Vectorized form for the array fast path: `whare_cost_matrix(census,
idle, psi)` returns the [4, M] matrix in one shot.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..data import ResourceDescriptor, ResourceTopologyNodeDescriptor
from ..graph.flowgraph import Node
from ..utils import ResourceMap, TaskMap
from .base import Cost, CostModeler
from .census import CLASS_ECS, ClassCensusKeeper, ec_class

# Prior psi[c, k] ×100: neutral 100 = no slowdown; devils degrade
# co-runners, rabbits are the most sensitive.
PSI_PRIOR = np.array(
    [
        # co-runner: S    R    D    T
        [105, 103, 140, 100],  # sheep
        [115, 110, 200, 101],  # rabbit
        [120, 130, 150, 105],  # devil
        [100, 100, 102, 100],  # turtle
    ],
    # int32: psi values stay O(10^4) (slowdown x100), census counts
    # O(slots), so products sit far below 2^31 — and the matrix feeds
    # device-bound int32 cost arrays anyway
    dtype=np.int32,
)

IDLE_BONUS = 20
MAX_COST = 2_000
UNSCHEDULED_COST = MAX_COST + 500
EWMA_WEIGHT = 0.25  # weight of a new observation


def whare_cost_matrix(
    census: np.ndarray, idle: np.ndarray, slots: np.ndarray, psi: Optional[np.ndarray] = None
) -> np.ndarray:
    """Vectorized Whare-MCs costs.

    census: [M, 4] running-class counts; idle: [M] idle slots;
    slots: [M] total slots; psi: [4, 4] slowdown map (default prior).
    Returns [4, M] int32.
    """
    if psi is None:
        psi = PSI_PRIOR
    tot = np.maximum(1, census.sum(axis=1))  # [M]
    expected = (psi @ census.T.astype(np.int64)) // tot  # [4, M]
    bonus = (IDLE_BONUS * idle.astype(np.int64)) // np.maximum(1, slots.astype(np.int64))
    cost = expected - bonus[None, :]
    return np.clip(cost, 0, MAX_COST).astype(np.int32)


class WhareMapCostModel(CostModeler):
    """Observed-slowdown placement (TPU-rebuild implementation of the
    reference's planned WHARE model, costmodel/interface.go:37)."""

    # continuation cost is the constant 0 and the census ignores a
    # non-resource accumulator (base.py)
    pinned_tasks_are_inert = True
    # resource -> resource and PU -> sink arcs cost the constant 0 (base.py)
    resource_arc_costs_are_fixed = True

    def __init__(
        self,
        resource_map: ResourceMap,
        task_map: TaskMap,
        leaf_resource_ids,
        max_tasks_per_pu: int,
    ) -> None:
        self.resource_map = resource_map
        self.task_map = task_map
        self.leaf_resource_ids = leaf_resource_ids
        self.census = ClassCensusKeeper(resource_map, task_map, max_tasks_per_pu)
        # float32 is ample for an EWMA over x100 slowdowns (24-bit
        # mantissa vs values O(10^4)); 64-bit buys nothing here
        self.psi = PSI_PRIOR.astype(np.float32).copy()

    # -- the map (online learning) ----------------------------------------

    def record_runtime(self, task_class: int, corunner_class: int, slowdown_x100: float) -> None:
        """Fold an observed slowdown sample (×100; 100 = baseline) into
        the map — fed from TaskFinalReport runtimes in the reference's
        intended pipeline."""
        old = self.psi[task_class, corunner_class]
        self.psi[task_class, corunner_class] = (
            (1.0 - EWMA_WEIGHT) * old + EWMA_WEIGHT * slowdown_x100
        )

    def psi_int(self) -> np.ndarray:
        return np.rint(self.psi).astype(np.int32)

    # -- arc costs --------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return UNSCHEDULED_COST

    def unscheduled_agg_to_sink_cost(self, job_id: int) -> Cost:
        return 0

    def task_to_resource_node_cost(self, task_id: int, resource_id: int) -> Cost:
        return int(self._machine_cost(self.census.task_class(task_id), resource_id))

    def resource_node_to_resource_node_cost(
        self, source: Optional[ResourceDescriptor], destination: ResourceDescriptor
    ) -> Cost:
        return 0

    def leaf_resource_node_to_sink_cost(self, resource_id: int) -> Cost:
        return 0

    def task_continuation_cost(self, task_id: int) -> Cost:
        return 0

    def task_preemption_cost(self, task_id: int) -> Cost:
        return MAX_COST // 2

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return 0

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        c = ec_class(ec)
        if c is None:
            return 0, 0
        return int(self._machine_cost(c, resource_id)), self.census.free_slots(resource_id)

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        return 0, 0

    def _machine_cost(self, task_class: int, resource_id: int) -> int:
        rs = self.resource_map.find(resource_id)
        if rs is None:
            raise KeyError(f"no resource status for {resource_id}")
        rd = rs.descriptor
        census = self.census.machine_census(resource_id)
        tot = max(1, int(census.sum()))
        expected = int(self.psi_int()[task_class] @ census) // tot
        slots = max(1, rd.num_slots_below)
        idle = rd.whare_map_stats.num_idle
        cost = expected - (IDLE_BONUS * idle) // slots
        return int(np.clip(cost, 0, MAX_COST))

    # -- preference enumeration -------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        return [CLASS_ECS[self.census.task_class(task_id)]]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        if ec_class(ec) is None:
            return []
        return list(self.census.machines.keys())

    def get_task_preference_arcs(self, task_id: int) -> List[int]:
        return []

    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
        return []

    # -- lifecycle --------------------------------------------------------

    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        self.census.add_machine(rtnd)

    def add_task(self, task_id: int) -> None:
        pass

    def remove_machine(self, resource_id: int) -> None:
        self.census.remove_machine(resource_id)

    def remove_task(self, task_id: int) -> None:
        pass

    # -- stats traversal --------------------------------------------------

    def gather_stats(self, accumulator: Node, other: Node) -> Node:
        return self.census.gather(accumulator, other)

    def prepare_stats(self, accumulator: Node) -> None:
        self.census.prepare(accumulator)

    def update_stats(self, accumulator: Node, other: Node) -> Node:
        return accumulator
