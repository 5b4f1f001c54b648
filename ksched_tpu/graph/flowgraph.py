"""L2: the mutable flow network.

Reference: scheduling/flow/flowgraph/{graph.go,node.go,arc.go}. Same
capability surface — add/change/delete nodes and arcs, id recycling,
13 node kinds, running-vs-other arc types — with one structural change
for the TPU build: node ids are dense, recycled ints handed out by an
IDGenerator so they double as row indices into the flat device arrays
that the solver consumes (no DIMACS text in between).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, Iterator, Optional

from ..data import ResourceDescriptor, ResourceType, TaskDescriptor, TaskState
from ..utils import IDGenerator


class NodeType(enum.IntEnum):
    """Flow node kinds (reference: flowgraph/node.go:27-41)."""

    ROOT_TASK = 0
    SCHEDULED_TASK = 1
    UNSCHEDULED_TASK = 2
    JOB_AGGREGATOR = 3
    SINK = 4
    EQUIV_CLASS = 5
    COORDINATOR = 6
    MACHINE = 7
    NUMA = 8
    SOCKET = 9
    CACHE = 10
    CORE = 11
    PU = 12


_TASK_NODE_TYPES = frozenset(
    {NodeType.ROOT_TASK, NodeType.SCHEDULED_TASK, NodeType.UNSCHEDULED_TASK}
)
_RESOURCE_NODE_TYPES = frozenset(
    {
        NodeType.COORDINATOR,
        NodeType.MACHINE,
        NodeType.NUMA,
        NodeType.SOCKET,
        NodeType.CACHE,
        NodeType.CORE,
        NodeType.PU,
    }
)

_RESOURCE_TO_NODE_TYPE = {
    ResourceType.PU: NodeType.PU,
    ResourceType.CORE: NodeType.CORE,
    ResourceType.CACHE: NodeType.CACHE,
    ResourceType.MACHINE: NodeType.MACHINE,
    ResourceType.NUMA_NODE: NodeType.NUMA,
    ResourceType.SOCKET: NodeType.SOCKET,
    ResourceType.COORDINATOR: NodeType.COORDINATOR,
}


def resource_node_type(rd: ResourceDescriptor) -> NodeType:
    """Map a resource descriptor's type to a flow node type (reference:
    flowgraph/node.go:161-191; NIC/DISK/SSD/LOGICAL unsupported there too)."""
    try:
        return _RESOURCE_TO_NODE_TYPE[rd.type]
    except KeyError:
        raise ValueError(f"resource type not supported as a flow node: {rd.type!r}")


class ArcType(enum.IntEnum):
    """Reference: flowgraph/arc.go:20-23."""

    OTHER = 0
    RUNNING = 1


@dataclass
class Arc:
    """A directed arc with capacity bounds and cost (reference:
    flowgraph/arc.go:26-47)."""

    src: int
    dst: int
    src_node: "Node"
    dst_node: "Node"
    cap_lower: int = 0
    cap_upper: int = 0
    cost: int = 0
    type: ArcType = ArcType.OTHER


@dataclass
class Node:
    """A flow-graph node (reference: flowgraph/node.go:76-106)."""

    id: int
    excess: int = 0
    type: NodeType = NodeType.ROOT_TASK
    comment: str = ""
    task: Optional[TaskDescriptor] = None
    job_id: int = 0
    resource_id: int = 0
    resource_descriptor: Optional[ResourceDescriptor] = None
    equiv_class: Optional[int] = None
    outgoing: Dict[int, Arc] = field(default_factory=dict)
    incoming: Dict[int, Arc] = field(default_factory=dict)
    visited: int = 0
    #: a task node's place in its job's tree (graph_manager.task_runnable)
    tree_path: tuple = ()

    @property
    def is_task_node(self) -> bool:
        return self.type in _TASK_NODE_TYPES

    @property
    def is_resource_node(self) -> bool:
        return self.type in _RESOURCE_NODE_TYPES

    @property
    def is_equiv_class_node(self) -> bool:
        return self.type == NodeType.EQUIV_CLASS

    @property
    def is_task_assigned_or_running(self) -> bool:
        assert self.task is not None, f"node {self.id} has no task descriptor"
        return self.task.state in (TaskState.ASSIGNED, TaskState.RUNNING)


class FlowGraph:
    """Mutable directed flow network with recycled dense integer node ids
    (reference: flowgraph/graph.go:27-201). The id free-list keeps the id
    space compact so ids can serve as device-array row indices."""

    def __init__(self) -> None:
        self._nodes: Dict[int, Node] = {}
        self._arcs: Dict[tuple, Arc] = {}  # (src, dst) -> Arc; capacity>0 arcs
        self._ids = IDGenerator(start=1)

    # -- nodes ------------------------------------------------------------

    def add_node(self) -> Node:
        nid = self._ids.take()
        if nid in self._nodes:
            raise RuntimeError(f"node id {nid} already present")
        node = Node(id=nid)
        self._nodes[nid] = node
        return node

    def delete_node(self, node: Node) -> None:
        """Remove a node and all its arcs; recycle the id (reference:
        flowgraph/graph.go:131-161)."""
        for arc in list(node.outgoing.values()):
            self.delete_arc(arc)
        for arc in list(node.incoming.values()):
            self.delete_arc(arc)
        del self._nodes[node.id]
        self._ids.give_back(node.id)

    def node(self, nid: int) -> Optional[Node]:
        return self._nodes.get(nid)

    def nodes(self) -> Iterator[Node]:
        return iter(self._nodes.values())

    @property
    def num_nodes(self) -> int:
        return len(self._nodes)

    @property
    def max_node_id(self) -> int:
        """One past the largest id ever allocated — the dense array extent."""
        return self._ids.high_water_mark

    # -- arcs -------------------------------------------------------------

    def add_arc(self, src: Node, dst: Node) -> Arc:
        if src.id not in self._nodes or dst.id not in self._nodes:
            raise RuntimeError(f"add_arc: unknown endpoint {src.id}->{dst.id}")
        arc = Arc(src=src.id, dst=dst.id, src_node=src, dst_node=dst)
        if dst.id in src.outgoing:
            raise RuntimeError(f"arc {src.id}->{dst.id} already present")
        src.outgoing[dst.id] = arc
        dst.incoming[src.id] = arc
        self._arcs[(src.id, dst.id)] = arc
        return arc

    def change_arc(self, arc: Arc, cap_lower: int, cap_upper: int, cost: int) -> None:
        """Update an arc in place; zero capacity removes it from the live
        arc set but keeps it attached to its endpoints (reference:
        flowgraph/graph.go:77-84 — delete = capacity→0 is the trick that
        keeps incremental re-solves sound)."""
        if cap_lower == 0 and cap_upper == 0:
            self._arcs.pop((arc.src, arc.dst), None)
        elif (arc.src, arc.dst) not in self._arcs and arc.dst in arc.src_node.outgoing:
            # Re-register an arc that was previously zeroed out (the
            # reference never re-adds these to its arc set — graph.go:77-84 —
            # which silently drops them from full re-exports; we fix that).
            self._arcs[(arc.src, arc.dst)] = arc
        arc.cap_lower = cap_lower
        arc.cap_upper = cap_upper
        arc.cost = cost

    def delete_arc(self, arc: Arc) -> None:
        arc.src_node.outgoing.pop(arc.dst, None)
        arc.dst_node.incoming.pop(arc.src, None)
        self._arcs.pop((arc.src, arc.dst), None)

    def get_arc(self, src: Node, dst: Node) -> Optional[Arc]:
        return src.outgoing.get(dst.id)

    def arcs(self) -> Iterator[Arc]:
        return iter(self._arcs.values())

    @property
    def num_arcs(self) -> int:
        return len(self._arcs)
