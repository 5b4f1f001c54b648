"""CoCo: co-location interference cost model.

The reference declares CoCo (costmodel/interface.go:33-43, enum value
COCO=5) and carries its inputs — per-task CoCo classes
(task_desc.proto:25-30: Sheep/Rabbit/Devil/Turtle) and per-machine
`CoCoInterferenceScores` penalties (coco_interference_scores.proto:
11-16) — but never implements the model. This is a from-scratch
implementation of the policy those inputs describe: the cost of placing
a task on a machine is the expected co-location interference, i.e. how
badly the machine's current residents and the incoming task hurt each
other.

Policy:

- Per-class equivalence classes (census.CLASS_ECS) keep arc fan-out at
  O(T + 4·M): task → class-EC → machine.
- EC(c) → machine cost = Σ_k census_k(machine) · W[c, k] + penalty(c,
  machine), where census is the running-class census maintained by the
  stats traversal, W is the 4×4 class-interaction matrix (devils hurt
  everyone; rabbits are sensitive; turtles barely interact — the
  qualitative CoCo taxonomy), and penalty(c, m) is the machine's own
  per-class score from `CoCoInterferenceScores`.
- Costs are clamped to MAX_COST so the unscheduled escape cost can be
  set above the worst placement: a task is left waiting only when every
  machine is full or pathologically noisy.
- Capacity on EC→machine arcs = free slots below, the same rule the
  trivial model uses (trivial_cost_modeler.go:76-83).

The vectorized form is `coco_cost_matrix(census, penalties)`: a [4, M]
int32 matrix from an [M, 4] census — pure numpy, no per-arc callbacks:
the program's one copy of the arithmetic over many machines. The array
fast path makes one per round; on the served path the batch hook
(`ClassCensusCostModel.ec_to_resource_batch`, census.py) takes a class
EC's row of it over the census keeper's arrays, for every machine where
the EC lists its arcs (the fill, a round whose statistics pass walked
every node) and otherwise for the machines the census gathered again
since the EC last listed them: the few a round's Bindings and
completions touched. The scalar hooks (`_machine_cost`) are the
definition the batch is tested against.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..data import ResourceDescriptor
from .base import Cost
from .census import NUM_TASK_CLASSES, ClassCensusCostModel

# Class-interaction weights W[c, k]: marginal cost of placing a class-c
# task next to one resident class-k task. Order: Sheep, Rabbit, Devil,
# Turtle. Devils (antagonists) hurt everyone and everyone hurts the
# cache-sensitive rabbits; turtles neither give nor take.
INTERFERENCE = np.array(
    [
        # resident:  S   R   D   T
        [2, 1, 8, 0],  # incoming sheep
        [4, 3, 16, 0],  # incoming rabbit
        [8, 12, 10, 1],  # incoming devil
        [0, 0, 1, 0],  # incoming turtle
    ],
    dtype=np.int64,
)

MAX_COST = 2_000  # clamp so unsched cost can dominate
UNSCHEDULED_COST = MAX_COST + 500


def machine_penalty_matrix(rd: ResourceDescriptor) -> np.ndarray:
    """Per-machine additive penalty vector p[c] for incoming class c,
    from the machine's CoCoInterferenceScores."""
    s = rd.coco_interference_scores
    return np.array(
        [s.sheep_penalty, s.rabbit_penalty, s.devil_penalty, s.turtle_penalty],
        dtype=np.int64,
    )


def coco_cost_matrix(census: np.ndarray, penalties: Optional[np.ndarray] = None) -> np.ndarray:
    """Vectorized CoCo costs.

    census: [M, 4] running-class counts per machine.
    penalties: optional [M, 4] per-machine per-incoming-class penalties.
    Returns [4, M] int32 cost of placing each class on each machine.
    """
    cost = INTERFERENCE @ census.T.astype(np.int64)  # [4, M]
    if penalties is not None:
        cost = cost + penalties.T.astype(np.int64)
    return np.minimum(cost, MAX_COST).astype(np.int32)


class CocoCostModel(ClassCensusCostModel):
    """Interference-aware placement (TPU-rebuild implementation of the
    reference's planned COCO model, costmodel/interface.go:39). The
    class ECs, their arcs and the census: ClassCensusCostModel."""

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return UNSCHEDULED_COST

    def task_preemption_cost(self, task_id: int) -> Cost:
        return MAX_COST // 2

    def _machine_cost(self, task_class: int, resource_id: int) -> int:
        census = self.census.machine_census(resource_id)
        rs = self.resource_map.find(resource_id)
        pen = machine_penalty_matrix(rs.descriptor)[task_class]
        raw = int(INTERFERENCE[task_class] @ census) + int(pen)
        return min(raw, MAX_COST)

    def _machine_constants(self, machines: List[ResourceDescriptor]) -> np.ndarray:
        """Each machine's penalty vector, [M, 4]."""
        return np.array(
            [machine_penalty_matrix(rd) for rd in machines], np.int64
        ).reshape(len(machines), NUM_TASK_CLASSES)

    def _class_cost_row(
        self, task_class: int, census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
        constants: np.ndarray,
    ) -> np.ndarray:
        return coco_cost_matrix(census, constants)[task_class]
