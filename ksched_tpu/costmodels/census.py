"""Shared class-census machinery for interference-aware cost models.

The reference carries a per-machine co-location census in
`WhareMapStats` (proto/whare_map_stats.proto:12-18) and per-class
penalties in `CoCoInterferenceScores` (proto/coco_interference_scores.
proto:11-16), but implements neither model (costmodel/interface.go:33-43
lists them as planned). Both models need the same input: for every
machine, how many running tasks of each CoCo class (Sheep/Rabbit/Devil/
Turtle, task_desc.proto:25-30) live below it, plus idle slots.

This module provides that census as part of the stats traversal the
graph manager already drives (ComputeTopologyStatistics, reference
graph_manager.go:480-511): `prepare` zeroes counts, `gather` re-seeds PU
leaves from their `current_running_tasks` and sums child counts upward —
exactly the aggregation discipline the trivial model uses for
slots/running counts (trivial_cost_modeler.go:147-176), extended with
the 4-class census.

Equivalence classes: one EC per task class (`class_ec(c)`), so the
flow-graph fan-out stays O(T + C·M) instead of O(T·M) — the same
aggregator trick the trivial model's single wildcard EC plays
(interface.go:46), refined per class so EC→machine arcs can carry
class-dependent interference costs.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..data import (
    ResourceTopologyNodeDescriptor,
    TaskType,
    WhareMapStats,
)
from ..graph.flowgraph import Node, NodeType
from ..utils import ResourceMap, TaskMap, equiv_class_from_bytes, resource_id_from_string

NUM_TASK_CLASSES = 4  # Sheep, Rabbit, Devil, Turtle (task_desc.proto:25-30)

#: equivalence-class id per task class
CLASS_ECS = [
    equiv_class_from_bytes(b"TASK_CLASS_SHEEP"),
    equiv_class_from_bytes(b"TASK_CLASS_RABBIT"),
    equiv_class_from_bytes(b"TASK_CLASS_DEVIL"),
    equiv_class_from_bytes(b"TASK_CLASS_TURTLE"),
]
_EC_TO_CLASS = {ec: c for c, ec in enumerate(CLASS_ECS)}


def class_ec(task_type: TaskType) -> int:
    return CLASS_ECS[int(task_type)]


def ec_class(ec: int) -> Optional[int]:
    """Inverse of class_ec; None if the EC is not a class EC."""
    return _EC_TO_CLASS.get(ec)


def census_vector(w: WhareMapStats) -> np.ndarray:
    """WhareMapStats -> [4] counts in TaskType order."""
    return np.array(
        [w.num_sheep, w.num_rabbits, w.num_devils, w.num_turtles], dtype=np.int64
    )


class ClassCensusKeeper:
    """Maintains per-resource slot/running aggregates plus the 4-class
    census in each descriptor's `whare_map_stats`, via the stats
    traversal hooks (CostModeler.prepare_stats/gather_stats)."""

    def __init__(
        self,
        resource_map: ResourceMap,
        task_map: TaskMap,
        max_tasks_per_pu: int,
    ) -> None:
        self.resource_map = resource_map
        self.task_map = task_map
        self.max_tasks_per_pu = max_tasks_per_pu
        self.machines: Dict[int, ResourceTopologyNodeDescriptor] = {}
        #: bumped when a machine joins or leaves: what is kept in the
        #: order of `machines` (machine_arrays; a model's own vectors)
        #: is made again
        self.machines_version = 0
        #: machines the statistics pass prepared, so gathered again,
        #: since take_machines_dirty was last called
        self._prepared = 0
        #: those of them machine_arrays has not read again yet
        self._dirty: Set[int] = set()
        self._arrays_version = -1
        self._rids: List[int] = []
        self._row: Dict[int, int] = {}
        self._census = np.zeros((0, NUM_TASK_CLASSES), np.int64)
        self._idle = np.zeros(0, np.int64)
        self._slots = np.zeros(0, np.int64)
        self._free = np.zeros(0, np.int64)

    # -- machine registry (cost models' add/remove_machine hooks) ---------

    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        rid = resource_id_from_string(rtnd.resource_desc.uuid)
        if rid not in self.machines:
            self.machines[rid] = rtnd
            self.machines_version += 1

    def remove_machine(self, resource_id: int) -> None:
        if self.machines.pop(resource_id, None) is not None:
            self.machines_version += 1

    # -- stats traversal ---------------------------------------------------

    def prepare(self, accumulator: Node) -> None:
        if not accumulator.is_resource_node:
            return
        rd = accumulator.resource_descriptor
        if rd is None:
            raise ValueError(f"node {accumulator.id} has no resource descriptor")
        rd.num_running_tasks_below = 0
        rd.num_slots_below = 0
        rd.whare_map_stats = WhareMapStats()
        if accumulator.type == NodeType.MACHINE:
            self._prepared += 1
            self._dirty.add(accumulator.resource_id)

    def gather(self, accumulator: Node, other: Node) -> Node:
        if not accumulator.is_resource_node:
            return accumulator
        acc_rd = accumulator.resource_descriptor
        if not other.is_resource_node:
            if other.type == NodeType.SINK:
                # PU leaf: re-seed from its running-task list, counting
                # classes from the task descriptors.
                acc_rd.num_running_tasks_below = len(acc_rd.current_running_tasks)
                acc_rd.num_slots_below = self.max_tasks_per_pu
                w = acc_rd.whare_map_stats
                w.num_idle = max(
                    0, self.max_tasks_per_pu - len(acc_rd.current_running_tasks)
                )
                for tid in acc_rd.current_running_tasks:
                    td = self.task_map.find(tid)
                    ttype = td.task_type if td is not None else TaskType.SHEEP
                    if ttype == TaskType.SHEEP:
                        w.num_sheep += 1
                    elif ttype == TaskType.RABBIT:
                        w.num_rabbits += 1
                    elif ttype == TaskType.DEVIL:
                        w.num_devils += 1
                    else:
                        w.num_turtles += 1
            return accumulator
        o_rd = other.resource_descriptor
        if o_rd is None:
            raise ValueError(f"node {other.id} has no resource descriptor")
        acc_rd.num_running_tasks_below += o_rd.num_running_tasks_below
        acc_rd.num_slots_below += o_rd.num_slots_below
        aw, ow = acc_rd.whare_map_stats, o_rd.whare_map_stats
        aw.num_idle += ow.num_idle
        aw.num_sheep += ow.num_sheep
        aw.num_rabbits += ow.num_rabbits
        aw.num_devils += ow.num_devils
        aw.num_turtles += ow.num_turtles
        return accumulator

    # -- the census as arrays ------------------------------------------------

    def take_machines_dirty(self) -> int:
        """Machines whose census the statistics pass gathered again since
        the last call (every machine, in a pass that walked every node)."""
        n, self._prepared = self._prepared, 0
        return n

    def machine_arrays(self) -> Tuple[List[int], np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(machine ids in the order of `machines`, census [M, 4], idle
        slots [M], slots [M], free slots [M]) as the descriptors have
        them: read again for the machines the statistics pass prepared
        since the last call, for every machine after one joined or left.
        The arrays are the keeper's: a caller does not write them."""
        if self._arrays_version != self.machines_version:
            self._arrays_version = self.machines_version
            self._rids = list(self.machines)
            self._row = {rid: i for i, rid in enumerate(self._rids)}
            m = len(self._rids)
            self._census = np.zeros((m, NUM_TASK_CLASSES), np.int64)
            self._idle, self._slots, self._free = (np.zeros(m, np.int64) for _ in range(3))
            dirty = self._rids
        else:
            dirty = [rid for rid in self._dirty if rid in self._row]
        self._dirty.clear()
        for rid in dirty:
            rd = self.machines[rid].resource_desc
            w = rd.whare_map_stats
            i = self._row[rid]
            self._census[i] = (w.num_sheep, w.num_rabbits, w.num_devils, w.num_turtles)
            self._idle[i] = w.num_idle
            self._slots[i] = rd.num_slots_below
            self._free[i] = rd.num_slots_below - rd.num_running_tasks_below
        return self._rids, self._census, self._idle, self._slots, self._free

    # -- convenience -------------------------------------------------------

    def free_slots(self, resource_id: int) -> int:
        rs = self.resource_map.find(resource_id)
        if rs is None:
            raise KeyError(f"no resource status for {resource_id}")
        rd = rs.descriptor
        return rd.num_slots_below - rd.num_running_tasks_below

    def machine_census(self, resource_id: int) -> np.ndarray:
        rs = self.resource_map.find(resource_id)
        if rs is None:
            raise KeyError(f"no resource status for {resource_id}")
        return census_vector(rs.descriptor.whare_map_stats)

    def task_class(self, task_id: int) -> int:
        td = self.task_map.find(task_id)
        return int(td.task_type) if td is not None else int(TaskType.SHEEP)
