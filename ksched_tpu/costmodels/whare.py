"""Whare-Map: heterogeneity- and co-runner-aware cost model.

The reference declares WHARE (costmodel/interface.go:37) and carries its
input — the per-machine `WhareMapStats` census (whare_map_stats.proto:
12-18) — without implementing the model. This implements the Whare-MCs
idea (Mars and Tang, "Whare-Map: heterogeneity in 'homogeneous'
warehouse-scale computers", ISCA'13): score each (task class, machine)
pair by the slowdown of that class on that machine's platform
(microarchitecture) next to its current co-runners, and prefer
placements with a low expected slowdown.

The map is psi[c, p, k] (x100, 100 = no slowdown): class c on a machine
of platform p beside a co-runner k, k one of the four census classes or
ALONE (the last index: the task has the machine to itself). It starts
from the prior

    psi[c, p, k] = PSI_PRIOR[c, k] * PLATFORM_PRIOR[c, p] // 100

(co-runner interference, 100 for ALONE, times how much the class gains
or loses on the platform; platform B is neutral) and is refined online
by `record_runtime(c, p, k, slowdown)`, an EWMA over the cell of the
machine the task ran on (TaskFinalReport, task_final_report.proto:10-19,
carries the runtimes the reference would feed this with; nothing on the
served path reports runtimes yet, so there the map stays at its prior).
A machine's platform is the value of its `ksched.io/platform` label
(`data.PLATFORM_LABEL`), one of PLATFORMS; a machine without the label,
or with another value, is platform B.

EC(c) -> machine m, of platform p(m), census n_k(m) over the four
classes, idle(m) free slots of slots(m); an EMPTY machine's one
co-runner is ALONE (n_ALONE(m) = 1 where no task runs on m, else 0). In
integer arithmetic:

    cost(c, m) = clip( sum_k n_k(m) * psi[c, p(m), k] // sum_k n_k(m)
                       - IDLE_BONUS * idle(m) // max(1, slots(m)), 0, MAX_COST )

so an empty machine costs what its PLATFORM does to a lone task of the
class, psi[c, p, ALONE] less the whole bonus (Whare-Map's premise: the
machines of a "homogeneous" cluster differ before any co-runner does;
on platform B, 100 - 20 = 80 for every class), and a machine that runs
one task or more costs its census-weighted slowdown less the bonus for
the share of it that is idle. Capacity = free slots below, as in the
trivial model (trivial_cost_modeler.go:76-83). Leaving a task
unscheduled costs UNSCHEDULED_COST, more than any machine.

`whare_cost_matrix(census, idle, slots, psi, platform)` is the equation,
over many machines at once, [4, M]: the program's one copy of it. The
batch hook (`ClassCensusCostModel.ec_to_resource_batch`, census.py; one
call an EC a round, inside a `platform_costs` span whose `machines` is
the number priced) computes its class's row with it over the census
keeper's arrays: for every machine where the EC lists its arcs (the
fill, a round whose statistics pass walked every node, the round after
`record_runtime` moved the map or a machine joined or left), otherwise
for the machines the census gathered again since the EC last listed
them: the few a round's Bindings and completions touched. The scalar
hooks ask the equation for one machine.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..data import PLATFORM_LABEL, ResourceDescriptor
from ..obs.spans import span
from ..utils import ResourceMap, TaskMap
from .base import Cost
from .census import ClassCensusCostModel, ec_class

# Prior psi[c, k] ×100: neutral 100 = no slowdown; devils degrade
# co-runners, rabbits are the most sensitive; alone, nothing slows a task.
PSI_PRIOR = np.array(
    [
        # co-runner: S    R    D    T  alone
        [105, 103, 140, 100, 100],  # sheep
        [115, 110, 200, 101, 100],  # rabbit
        [120, 130, 150, 105, 100],  # devil
        [100, 100, 102, 100, 100],  # turtle
    ],
    # int32: psi values stay O(10^4) (slowdown x100), census counts
    # O(slots), so products sit far below 2^31 — and the matrix feeds
    # device-bound int32 cost arrays anyway
    dtype=np.int32,
)

#: the co-runner index of a task that has its machine to itself
ALONE = 4

#: the platforms (microarchitecture generations) the map knows, oldest
#: first: the values of a machine's PLATFORM_LABEL
PLATFORMS = ("A", "B", "C")
#: the platform of a machine that carries no such label
DEFAULT_PLATFORM = PLATFORMS.index("B")

# Prior PLATFORM_PRIOR[c, p] x100: what a class loses on the oldest
# platform and gains on the newest, B neutral; most for the class most
# sensitive to its machine (rabbit), least for the turtle.
PLATFORM_PRIOR = np.array(
    [
        # platform: A    B    C
        [110, 100, 95],  # sheep
        [130, 100, 85],  # rabbit
        [115, 100, 90],  # devil
        [102, 100, 99],  # turtle
    ],
    dtype=np.int32,
)

IDLE_BONUS = 20
MAX_COST = 2_000
UNSCHEDULED_COST = MAX_COST + 500
EWMA_WEIGHT = 0.25  # weight of a new observation


def psi_prior() -> np.ndarray:
    """The map before any runtime was recorded: int32 [4, P, 5]."""
    return (PSI_PRIOR[:, None, :] * PLATFORM_PRIOR[:, :, None] // 100).astype(np.int32)


def platform_index(labels) -> int:
    """The platform a machine's labels name, as an index into PLATFORMS."""
    name = labels.get(PLATFORM_LABEL)
    return PLATFORMS.index(name) if name in PLATFORMS else DEFAULT_PLATFORM


def whare_cost_matrix(
    census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
    psi: Optional[np.ndarray] = None, platform: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Vectorized Whare-MCs costs.

    census: [M, 4] running-class counts; idle: [M] idle slots;
    slots: [M] total slots; psi: [C, P, 5] slowdown map (default: the
    prior, C = 4); platform: [M] indices into PLATFORMS (default: every
    machine DEFAULT_PLATFORM). Returns [C, M] int32.
    """
    if psi is None:
        psi = psi_prior()
    census = census.astype(np.int64)
    if platform is None:
        platform = np.full(len(census), DEFAULT_PLATFORM)
    # an empty machine's one co-runner is ALONE: the fifth count
    beside = np.concatenate([census, (census.sum(axis=1) == 0)[:, None]], axis=1)
    # psi[:, platform, :] is [C, M, 5]: each machine against the map of its platform
    weighted = (psi[:, platform, :].astype(np.int64) * beside[None, :, :]).sum(axis=2)
    expected = weighted // beside.sum(axis=1)
    bonus = (IDLE_BONUS * idle.astype(np.int64)) // np.maximum(1, slots.astype(np.int64))
    cost = expected - bonus[None, :]
    return np.clip(cost, 0, MAX_COST).astype(np.int32)


class WhareMapCostModel(ClassCensusCostModel):
    """Observed-slowdown placement (TPU-rebuild implementation of the
    reference's planned WHARE model, costmodel/interface.go:37). The
    class ECs, their arcs and the census: ClassCensusCostModel."""

    def __init__(
        self,
        resource_map: ResourceMap,
        task_map: TaskMap,
        leaf_resource_ids,
        max_tasks_per_pu: int,
    ) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        # float32 is ample for an EWMA over x100 slowdowns (24-bit
        # mantissa vs values O(10^4)); 64-bit buys nothing here
        self.psi = psi_prior().astype(np.float32)
        self._psi_int = psi_prior()

    # -- the map (online learning) ----------------------------------------

    def record_runtime(
        self, task_class: int, platform: int, corunner_class: int, slowdown_x100: float
    ) -> None:
        """Fold an observed slowdown sample (×100; 100 = baseline) of a
        task of ``task_class`` that ran on a machine of ``platform``
        (an index into PLATFORMS) beside ``corunner_class`` (a census
        class, or ALONE) into that cell of the map — fed from TaskFinalReport runtimes in the
        reference's intended pipeline. Every cost of the class may have
        moved, on machines no census gathered again: its EC lists anew
        (and so do the others: one record of listings serves all)."""
        old = self.psi[task_class, platform, corunner_class]
        self.psi[task_class, platform, corunner_class] = (
            (1.0 - EWMA_WEIGHT) * old + EWMA_WEIGHT * slowdown_x100
        )
        self._psi_int = np.rint(self.psi).astype(np.int32)
        self.census.forget_listings()

    def psi_int(self) -> np.ndarray:
        """The map as the costs read it: int32 [4, P, 5]."""
        return self._psi_int

    # -- arc costs --------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return UNSCHEDULED_COST

    def task_preemption_cost(self, task_id: int) -> Cost:
        return MAX_COST // 2

    def _machine_cost(self, task_class: int, resource_id: int) -> int:
        """One cell of `whare_cost_matrix`: the equation has one copy."""
        rs = self.resource_map.find(resource_id)
        if rs is None:
            raise KeyError(f"no resource status for {resource_id}")
        rd = rs.descriptor
        return int(whare_cost_matrix(
            self.census.machine_census(resource_id)[None, :],
            np.array([rd.whare_map_stats.num_idle]), np.array([rd.num_slots_below]),
            self._psi_int[task_class : task_class + 1],
            np.array([platform_index(rd.labels)]),
        )[0, 0])

    def _machine_constants(self, machines: List[ResourceDescriptor]) -> np.ndarray:
        """Each machine's platform, [M]."""
        return np.array([platform_index(rd.labels) for rd in machines], np.int64)

    def _class_cost_row(
        self, task_class: int, census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
        constants: np.ndarray,
    ) -> np.ndarray:
        return whare_cost_matrix(
            census, idle, slots, self._psi_int[task_class : task_class + 1], constants
        )[0]

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        """The shared batch inside a `platform_costs` span: the keeper's
        arrays read again for the machines `stats` gathered, and the
        class's row of the matrix over the ``machines`` priced."""
        if ec_class(ec) is None:
            return super().ec_to_resource_batch(ec, resource_ids)
        with span("platform_costs", machines=len(resource_ids)):
            return super().ec_to_resource_batch(ec, resource_ids)
