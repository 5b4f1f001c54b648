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

What a class EC's arc to a machine costs and carries is a function of
that machine's census, so it moves only where the stats traversal
gathered the machine again. The keeper records those machines for each
class EC since the EC last listed its arcs (`start_listing`,
`take_listing_changes`), and `ClassCensusCostModel`, the base of both
models, answers `equiv_class_pref_arc_changes` from that record and
prices any list of the keeper's machines from its arrays by row: a round
re-prices the machines the census gathered again, not every machine.
"""

from __future__ import annotations

import abc
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

from ..data import (
    ResourceDescriptor,
    ResourceTopologyNodeDescriptor,
    TaskType,
    WhareMapStats,
)
from ..graph.flowgraph import Node, NodeType
from ..utils import ResourceMap, TaskMap, equiv_class_from_bytes, resource_id_from_string
from .base import Cost, CostModeler

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
        #: class EC -> the machines prepared since the EC last listed
        #: its arcs to every machine (start_listing). An EC that takes
        #: no turn for some rounds owes the machines of all of them. No
        #: entry: the EC never listed, or its listing cannot be trusted
        #: any more (forget_listings)
        self._owed: Dict[int, Set[int]] = {}
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
            self.forget_listings()  # no EC has an arc to the newcomer

    def remove_machine(self, resource_id: int) -> None:
        if self.machines.pop(resource_id, None) is not None:
            self.machines_version += 1
            self.forget_listings()

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
            for owed in self._owed.values():
                owed.add(accumulator.resource_id)

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

    def rows(self, resource_ids: Sequence[int]) -> Union[slice, np.ndarray, None]:
        """The rows of the arrays machine_arrays last returned that hold
        ``resource_ids``: the whole of each array, no copy, for the
        machines as `machines` lists them; None where one of them is no
        machine of the keeper."""
        if resource_ids == self._rids:
            return slice(None)
        row = self._row
        try:
            return np.fromiter((row[rid] for rid in resource_ids), np.int64, len(resource_ids))
        except KeyError:
            return None

    # -- what changed since a class EC listed its arcs -----------------------

    def start_listing(self, ec: int) -> None:
        """``ec`` lists an arc to every machine, priced from the census as
        it stands: from here on its record holds the machines prepared."""
        self._owed[ec] = set()

    def forget_listings(self) -> None:
        """No EC's arcs are what a listing would make them, for a reason
        the census does not see (a machine joined or left; the model's
        prices moved): every EC lists again."""
        self._owed.clear()

    def take_listing_changes(self, ec: int) -> Optional[List[int]]:
        """The machines whose census the statistics pass gathered again
        since ``ec`` listed its arcs or was last answered here, in the
        order a listing has them (so the journal's records come in a
        sweep's order), and the record starts again. None where ``ec``
        has no listing to go by, and where the record holds every
        machine (a pass that walked every node): listing them is then
        no more work than patching them, and needs no row looked up."""
        owed = self._owed.get(ec)
        if (
            owed is None
            or len(owed) >= len(self.machines)
            # a listing that was never priced through machine_arrays
            or self._arrays_version != self.machines_version
        ):
            return None
        self.start_listing(ec)
        return sorted(owed, key=self._row.__getitem__)

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


class ClassCensusCostModel(CostModeler):
    """What the two census-priced models (CoCo, Whare-Map) share: one EC
    a task class with an arc to every machine, capacity the machine's
    free slots; constants on every other arc; the keeper fed by the
    lifecycle and stats hooks; and the EC -> machine arcs re-priced from
    the keeper's record and arrays. A model brings its constants, the
    price of one machine (``_machine_cost``, the definition) and of
    many (``_class_cost_row``, tested against it)."""

    # continuation cost is the constant 0 and the census ignores a
    # non-resource accumulator (base.py)
    pinned_tasks_are_inert = True
    # resource -> resource and PU -> sink arcs cost the constant 0 (base.py)
    resource_arc_costs_are_fixed = True
    # a class EC lists every machine, a full one at capacity 0 (base.py)
    full_resources_stay_listed = True

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
        #: what `_machine_constants` made of every machine, in the order
        #: of the keeper's machines, made again when one joins or leaves
        self._constants = np.zeros(0, np.int64)
        self._constants_version = -1

    def take_census_machines_dirty(self) -> int:
        return self.census.take_machines_dirty()

    # -- what a model brings ------------------------------------------------

    @abc.abstractmethod
    def _machine_cost(self, task_class: int, resource_id: int) -> int:
        """EC(task_class) -> one machine, from its descriptor."""

    @abc.abstractmethod
    def _machine_constants(self, machines: List[ResourceDescriptor]) -> np.ndarray:
        """What the price reads of each machine besides its census (a
        platform, a penalty vector): one row a machine."""

    @abc.abstractmethod
    def _class_cost_row(
        self, task_class: int, census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
        constants: np.ndarray,
    ) -> np.ndarray:
        """EC(task_class) -> each of the machines the arrays hold, [M]."""

    # -- arc costs --------------------------------------------------------

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
        # continuing in place is free of *new* interference
        return 0

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return 0

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        c = ec_class(ec)
        if c is None:
            return 0, 0
        return int(self._machine_cost(c, resource_id)), self.census.free_slots(resource_id)

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        return 0, 0

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        """A class EC's arcs to any of the keeper's machines in one call:
        the class's row of the model's matrix over those rows of the
        keeper's arrays. Other resources are asked one by one (the base
        class's loop)."""
        c = ec_class(ec)
        if c is None:
            return super().ec_to_resource_batch(ec, resource_ids)
        rids, census, idle, slots, free = self.census.machine_arrays()
        rows = self.census.rows(resource_ids)
        if rows is None:
            return super().ec_to_resource_batch(ec, resource_ids)
        if self._constants_version != self.census.machines_version:
            self._constants_version = self.census.machines_version
            self._constants = self._machine_constants(
                [self.census.machines[r].resource_desc for r in rids]
            )
        row = self._class_cost_row(c, census[rows], idle[rows], slots[rows], self._constants[rows])
        return row.tolist(), free[rows].tolist()

    # -- preference enumeration -------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        return [CLASS_ECS[self.census.task_class(task_id)]]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        if ec_class(ec) is None:
            return []
        self.census.start_listing(ec)
        return list(self.census.machines.keys())

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        return self.census.take_listing_changes(ec)

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
