"""L3: the graph-change journal.

Reference: scheduling/flow/dimacs/{change.go,change_stats.go,*_change.go}
and scheduling/flow/flowmanager/graph_change_manager.go. Every graph
mutation flows through the ChangeManager, which journals it as a typed
change record. In the reference the journal is serialized to DIMACS text
for the solver subprocess; here the journal is scattered into flat device
arrays by the exporter (graph/device_export.py) — the wire format became
array indices. A DIMACS text codec is kept in graph/dimacs.py for
debugging and golden-file parity.

The four structural change kinds mirror the reference's incremental
DIMACS lines (add node / remove node / new arc / change arc), and the
36-bucket ChangeType taxonomy mirrors dimacs/change_stats.go:19-58 —
including per-type accumulation, which the reference left as a TODO stub
(change_stats.go:96-98).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Union

from .flowgraph import Arc, ArcType, FlowGraph, Node, NodeType


class ChangeType(enum.IntEnum):
    """Reference: dimacs/change_stats.go:19-58."""

    ADD_TASK_NODE = 0
    ADD_RESOURCE_NODE = 1
    ADD_EQUIV_CLASS_NODE = 2
    ADD_UNSCHED_JOB_NODE = 3
    ADD_SINK_NODE = 4
    ADD_ARC_TASK_TO_EQUIV_CLASS = 5
    ADD_ARC_TASK_TO_RES = 6
    ADD_ARC_EQUIV_CLASS_TO_RES = 7
    ADD_ARC_BETWEEN_EQUIV_CLASS = 8
    ADD_ARC_BETWEEN_RES = 9
    ADD_ARC_TO_UNSCHED = 10
    ADD_ARC_FROM_UNSCHED = 11
    ADD_ARC_RUNNING_TASK = 12
    ADD_ARC_RES_TO_SINK = 13
    DEL_UNSCHED_JOB_NODE = 14
    DEL_TASK_NODE = 15
    DEL_RESOURCE_NODE = 16
    DEL_EQUIV_CLASS_NODE = 17
    DEL_ARC_EQUIV_CLASS_TO_RES = 18
    DEL_ARC_RUNNING_TASK = 19
    DEL_ARC_EVICTED_TASK = 20
    DEL_ARC_BETWEEN_EQUIV_CLASS = 21
    DEL_ARC_BETWEEN_RES = 22
    DEL_ARC_TASK_TO_EQUIV_CLASS = 23
    DEL_ARC_TASK_TO_RES = 24
    CHG_ARC_EVICTED_TASK = 25
    CHG_ARC_TO_UNSCHED = 26
    CHG_ARC_FROM_UNSCHED = 27
    CHG_ARC_TASK_TO_EQUIV_CLASS = 28
    CHG_ARC_TASK_TO_RES = 29
    CHG_ARC_EQUIV_CLASS_TO_RES = 30
    CHG_ARC_BETWEEN_EQUIV_CLASS = 31
    CHG_ARC_BETWEEN_RES = 32
    CHG_ARC_RES_TO_SINK = 33
    CHG_ARC_RUNNING_TASK = 34
    CHG_ARC_TASK_TO_UNSCHED = 35


@dataclass(frozen=True)
class AddNodeChange:
    """Incremental 'add node' record (reference: dimacs/add_node_change.go)."""

    node_id: int
    excess: int
    node_type: NodeType
    comment: str = ""


@dataclass(frozen=True)
class RemoveNodeChange:
    """Reference: dimacs/remove_node_change.go."""

    node_id: int
    comment: str = ""


@dataclass(frozen=True)
class NewArcChange:
    """Reference: dimacs/create_arc_change.go."""

    src: int
    dst: int
    cap_lower: int
    cap_upper: int
    cost: int
    arc_type: ArcType
    comment: str = ""


@dataclass(frozen=True)
class ChangeArcChange:
    """Reference: dimacs/update_arc_change.go (carries old_cost so a
    solver can cheaply detect pure capacity changes)."""

    src: int
    dst: int
    cap_lower: int
    cap_upper: int
    cost: int
    arc_type: ArcType
    old_cost: int
    comment: str = ""


Change = Union[AddNodeChange, RemoveNodeChange, NewArcChange, ChangeArcChange]


class ChangeStats:
    """Per-round mutation counters (reference: dimacs/change_stats.go:62-98;
    per-type accumulation implemented here rather than stubbed)."""

    def __init__(self) -> None:
        self.nodes_added = 0
        self.nodes_removed = 0
        self.arcs_added = 0
        self.arcs_changed = 0
        self.arcs_removed = 0
        self.by_type: Dict[ChangeType, int] = {t: 0 for t in ChangeType}

    def update(self, change_type: ChangeType, change: Change) -> None:
        self.by_type[change_type] += 1
        if isinstance(change, AddNodeChange):
            self.nodes_added += 1
        elif isinstance(change, RemoveNodeChange):
            self.nodes_removed += 1
        elif isinstance(change, NewArcChange):
            self.arcs_added += 1
        elif isinstance(change, ChangeArcChange):
            if change.cap_lower == 0 and change.cap_upper == 0:
                self.arcs_removed += 1
            else:
                self.arcs_changed += 1

    def reset(self) -> None:
        self.__init__()

    @property
    def arc_records(self) -> int:
        """Arc records taken since the last reset, those merged into
        an earlier record of the same arc included."""
        return self.arcs_added + self.arcs_changed + self.arcs_removed

    def to_csv(self) -> str:
        """Reference: dimacs/change_stats.go:70-82."""
        totals = [
            self.nodes_added,
            self.nodes_removed,
            self.arcs_added,
            self.arcs_changed,
            self.arcs_removed,
        ]
        per_type = [self.by_type[t] for t in ChangeType]
        return ",".join(str(v) for v in totals + per_type)


class ChangeManager:
    """The sole mutation path for the flow graph; journals every change
    for the next incremental solve (reference:
    flowmanager/graph_change_manager.go:71-218).

    Keeps the reference's no-op short-circuits (idempotent ChangeArc calls
    journal nothing) and its delete-is-capacity-zero convention, which is
    what makes warm-started incremental re-solves sound.
    """

    def __init__(self, stats: Optional[ChangeStats] = None) -> None:
        self.graph = FlowGraph()
        self.stats = stats if stats is not None else ChangeStats()
        self._journal: List[Change] = []
        # (src, dst) -> index in _journal of the latest arc record, for O(1)
        # merge-to-same-arc. Safe because an arc record for (src, dst) always
        # postdates any structural change to its endpoints (arcs are detached
        # before node removal and re-journaled on re-add).
        self._arc_index: Dict[tuple, int] = {}
        # Optimization passes over the journal (reference declares these
        # flags at graph_change_manager.go:72-76 but panics in the passes;
        # we implement merge-to-same-arc for real).
        self.remove_duplicate = True

    # -- journal ----------------------------------------------------------

    def _record(self, change_type: ChangeType, change: Change) -> None:
        self.stats.update(change_type, change)
        if self.remove_duplicate and self._merge(change):
            return
        if isinstance(change, (NewArcChange, ChangeArcChange)):
            self._arc_index[(change.src, change.dst)] = len(self._journal)
        self._journal.append(change)

    def _merge(self, change: Change) -> bool:
        """Collapse repeated updates to the same arc into one journal entry
        (the reference's unimplemented MergeChangesToSameArc,
        graph_change_manager.go:243-261)."""
        if not isinstance(change, ChangeArcChange):
            return False
        idx = self._arc_index.get((change.src, change.dst))
        if idx is None:
            return False
        prev = self._journal[idx]
        if isinstance(prev, NewArcChange):
            self._journal[idx] = NewArcChange(
                src=prev.src,
                dst=prev.dst,
                cap_lower=change.cap_lower,
                cap_upper=change.cap_upper,
                cost=change.cost,
                arc_type=prev.arc_type,
                comment=prev.comment,
            )
        else:
            self._journal[idx] = ChangeArcChange(
                src=prev.src,
                dst=prev.dst,
                cap_lower=change.cap_lower,
                cap_upper=change.cap_upper,
                cost=change.cost,
                arc_type=change.arc_type,
                old_cost=prev.old_cost,
                comment=prev.comment,
            )
        return True

    def get_graph_changes(self) -> List[Change]:
        return list(self._journal)

    def get_optimized_graph_changes(self) -> List[Change]:
        return list(self._journal)

    def reset_changes(self) -> None:
        self._journal.clear()
        self._arc_index.clear()

    @property
    def has_changes(self) -> bool:
        return bool(self._journal)

    # -- mutations (reference: graph_change_manager.go:93-193) ------------

    def add_node(
        self,
        node_type: NodeType,
        excess: int,
        change_type: ChangeType,
        comment: str = "",
    ) -> Node:
        node = self.graph.add_node()
        node.type = node_type
        node.excess = excess
        node.comment = comment
        self._record(change_type, AddNodeChange(node.id, excess, node_type, comment))
        return node

    def delete_node(self, node: Node, change_type: ChangeType, comment: str = "") -> None:
        # Journal arc removals implied by the node removal so the device
        # exporter can invalidate their slots.
        for arc in list(node.outgoing.values()):
            self._record(
                change_type,
                ChangeArcChange(arc.src, arc.dst, 0, 0, arc.cost, arc.type, arc.cost, "DeleteNode: implied arc removal"),
            )
        for arc in list(node.incoming.values()):
            self._record(
                change_type,
                ChangeArcChange(arc.src, arc.dst, 0, 0, arc.cost, arc.type, arc.cost, "DeleteNode: implied arc removal"),
            )
        self.graph.delete_node(node)
        self._record(change_type, RemoveNodeChange(node.id, comment))

    def add_arc(
        self,
        src: Node,
        dst: Node,
        cap_lower: int,
        cap_upper: int,
        cost: int,
        arc_type: ArcType,
        change_type: ChangeType,
        comment: str = "",
    ) -> Arc:
        arc = self.graph.add_arc(src, dst)
        arc.cap_lower = cap_lower
        arc.cap_upper = cap_upper
        arc.cost = cost
        arc.type = arc_type
        self._record(
            change_type,
            NewArcChange(src.id, dst.id, cap_lower, cap_upper, cost, arc_type, comment),
        )
        return arc

    def change_arc(
        self,
        arc: Arc,
        cap_lower: int,
        cap_upper: int,
        cost: int,
        change_type: ChangeType,
        comment: str = "",
    ) -> None:
        """No-op short-circuit when nothing changes (reference:
        graph_change_manager.go:142-156)."""
        if arc.cap_lower == cap_lower and arc.cap_upper == cap_upper and arc.cost == cost:
            return
        old_cost = arc.cost
        self.graph.change_arc(arc, cap_lower, cap_upper, cost)
        self._record(
            change_type,
            ChangeArcChange(arc.src, arc.dst, cap_lower, cap_upper, cost, arc.type, old_cost, comment),
        )

    def change_arc_capacity(self, arc: Arc, cap_upper: int, change_type: ChangeType, comment: str = "") -> None:
        self.change_arc(arc, arc.cap_lower, cap_upper, arc.cost, change_type, comment)

    def change_arc_cost(self, arc: Arc, cost: int, change_type: ChangeType, comment: str = "") -> None:
        self.change_arc(arc, arc.cap_lower, arc.cap_upper, cost, change_type, comment)

    def delete_arc(self, arc: Arc, change_type: ChangeType, comment: str = "") -> None:
        """Delete = capacity→0 journal entry, then detach (reference:
        graph_change_manager.go:184-193)."""
        old_cost = arc.cost
        self.graph.change_arc(arc, 0, 0, arc.cost)
        self._record(
            change_type,
            ChangeArcChange(arc.src, arc.dst, 0, 0, arc.cost, arc.type, old_cost, comment),
        )
        self.graph.delete_arc(arc)
