"""The trivial cost model: one wildcard aggregator, constant costs.

Reference: scheduling/flow/costmodel/trivial_cost_modeler.go. Policy:
leaving a task unscheduled costs 5, routing through the cluster
aggregator EC costs 2, everything else costs 0; the EC fans out to every
machine with capacity = free slots below (slots − running).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from ..data import ResourceDescriptor, ResourceTopologyNodeDescriptor
from ..graph.flowgraph import Node, NodeType
from ..utils import ResourceMap, TaskMap, resource_id_from_string
from .base import CLUSTER_AGGREGATOR_EC, Cost, CostModeler


class TrivialCostModel(CostModeler):
    # continuation cost is the constant 0 and the three stats hooks
    # return at once for a non-resource accumulator (base.py)
    pinned_tasks_are_inert = True
    # resource -> resource and PU -> sink arcs cost the constant 0 (base.py)
    resource_arc_costs_are_fixed = True

    UNSCHEDULED_COST = 5  # reference: trivial_cost_modeler.go:41-43
    CLUSTER_AGG_COST = 2  # reference: trivial_cost_modeler.go:69-74

    def __init__(
        self,
        resource_map: ResourceMap,
        task_map: TaskMap,
        leaf_resource_ids: Set[int],
        max_tasks_per_pu: int,
    ) -> None:
        self.resource_map = resource_map
        self.task_map = task_map
        self.leaf_resource_ids = leaf_resource_ids
        self.max_tasks_per_pu = max_tasks_per_pu
        # machine resource id -> topology node (reference:
        # trivial_cost_modeler.go:23-25,129-143)
        self._machines: Dict[int, ResourceTopologyNodeDescriptor] = {}

    # -- arc costs --------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return self.UNSCHEDULED_COST

    def unscheduled_agg_to_sink_cost(self, job_id: int) -> Cost:
        return 0

    def task_to_resource_node_cost(self, task_id: int, resource_id: int) -> Cost:
        return 0

    def resource_node_to_resource_node_cost(
        self, source: Optional[ResourceDescriptor], destination: ResourceDescriptor
    ) -> Cost:
        return 0

    def leaf_resource_node_to_sink_cost(self, resource_id: int) -> Cost:
        return 0

    def task_continuation_cost(self, task_id: int) -> Cost:
        return 0

    def task_preemption_cost(self, task_id: int) -> Cost:
        return 0

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return self.CLUSTER_AGG_COST if ec == CLUSTER_AGGREGATOR_EC else 0

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        rs = self.resource_map.find(resource_id)
        if rs is None:
            raise KeyError(f"no resource status for {resource_id}")
        free = rs.descriptor.num_slots_below - rs.descriptor.num_running_tasks_below
        return 0, free

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        return 0, 0

    # -- preference enumeration -------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        if self.task_map.find(task_id) is None:
            raise KeyError(f"no task descriptor for {task_id}")
        return [CLUSTER_AGGREGATOR_EC]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        if ec != CLUSTER_AGGREGATOR_EC:
            return []
        return list(self._machines.keys())

    def get_task_preference_arcs(self, task_id: int) -> List[int]:
        return []

    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
        return []

    # -- lifecycle --------------------------------------------------------

    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        rid = resource_id_from_string(rtnd.resource_desc.uuid)
        self._machines.setdefault(rid, rtnd)

    def add_task(self, task_id: int) -> None:
        pass

    def remove_machine(self, resource_id: int) -> None:
        self._machines.pop(resource_id, None)

    def remove_task(self, task_id: int) -> None:
        pass

    # -- stats traversal --------------------------------------------------

    def gather_stats(self, accumulator: Node, other: Node) -> Node:
        """Accumulate running-task/slot counts up the resource tree;
        PU leaves re-seed from their running-task lists (reference:
        trivial_cost_modeler.go:147-165)."""
        if not accumulator.is_resource_node:
            return accumulator
        if not other.is_resource_node:
            if other.type == NodeType.SINK:
                rd = accumulator.resource_descriptor
                rd.num_running_tasks_below = len(rd.current_running_tasks)
                rd.num_slots_below = self.max_tasks_per_pu
            return accumulator
        if other.resource_descriptor is None:
            raise ValueError(f"node {other.id} has no resource descriptor")
        acc_rd = accumulator.resource_descriptor
        acc_rd.num_running_tasks_below += other.resource_descriptor.num_running_tasks_below
        acc_rd.num_slots_below += other.resource_descriptor.num_slots_below
        return accumulator

    def prepare_stats(self, accumulator: Node) -> None:
        if not accumulator.is_resource_node:
            return
        rd = accumulator.resource_descriptor
        if rd is None:
            raise ValueError(f"node {accumulator.id} has no resource descriptor")
        rd.num_running_tasks_below = 0
        rd.num_slots_below = 0

    def update_stats(self, accumulator: Node, other: Node) -> Node:
        return accumulator
