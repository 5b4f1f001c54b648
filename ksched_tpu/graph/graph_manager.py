"""L5: the graph manager — job/task/resource lifecycle → graph mutations.

Reference: scheduling/flow/flowmanager/graph_manager.go (the heart of the
system, 1338 lines). Behavior parity notes:

- every mutation goes through the journaled ChangeManager (the invariant
  that makes incremental solving possible, SURVEY §3.5);
- task nodes carry supply 1 and the sink absorbs it (addTaskNode
  graph_manager.go:632-648, removeTaskNode :803-813);
- each job gets an unscheduled-aggregator escape node so infeasibility is
  impossible (updateUnscheduledAggNode :1287-1305);
- the preemption flag flips both the capacity rule on resource arcs
  (:662-667) and scheduled-task arc handling (pin vs keep, :675-720,
  :855-888);
- AddOrUpdateJobNodes drives a worklist BFS (updateFlowGraph :1012-1033)
  that touches task, EC, and resource nodes exactly once per round.
  Where the reference finds the tasks by walking every job's tree from
  its root each round, the tasks here come from a work list the events
  keep (task_runnable, task_evicted, pin, completion): the tasks that
  have or need a node and whose arcs can change. Their order, and the
  place of the EC and resource nodes among them, is the reference
  walk's, so node ids and the change journal are the same.
"""

from __future__ import annotations

from collections import deque
from typing import Deque, Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from ..costmodels.base import CLUSTER_AGGREGATOR_EC, CostModeler
from ..data import (
    DeltaType,
    JobDescriptor,
    ResourceDescriptor,
    ResourceTopologyNodeDescriptor,
    ResourceType,
    SchedulingDelta,
    TaskDescriptor,
    TaskState,
)
from ..obs.spans import Span, span, start_span
from ..utils import ResourceMap, job_id_from_string, resource_id_from_string
from .changes import ChangeManager, ChangeStats, ChangeType
from .flowgraph import Arc, ArcType, Node, NodeType, resource_node_type

TaskMapping = Dict[int, int]  # task node id -> PU node id (flowmanager/types.go:6)


def task_needs_node(td: TaskDescriptor) -> bool:
    """Reference: graph_manager.go:1333-1338."""
    return td.state in (TaskState.RUNNABLE, TaskState.RUNNING, TaskState.ASSIGNED)


def _is_pref_arc(arc: Arc) -> bool:
    """An arc of a task's own choosing: from the task to a resource, or
    to an equivalence class other than the cluster aggregator; not its
    running arc (the arc it is placed by), nor the two every task has."""
    if arc.type == ArcType.RUNNING:
        return False
    dst = arc.dst_node
    if dst.resource_id != 0:
        return True
    return dst.equiv_class is not None and dst.equiv_class != CLUSTER_AGGREGATOR_EC


class _TurnRuns:
    """The spans inside one update of the job nodes: one for each run
    of consecutive turns of one kind in its FIFO, `task_refresh` for
    task turns and `res_refresh` for resource-node turns, never one a
    node. The clock is read where the kind of turn changes. The first
    run is a task run and takes in the listing of the task turns. An
    EC node's turn has spans of its own (`ec_chain_refresh`,
    `ec_refresh`) and only ends a run. An update that queues no
    resource node (GraphManager._queue_res_turn) opens no
    `res_refresh`."""

    TASK, RES = "task_refresh", "res_refresh"
    __slots__ = ("kind", "res_nodes", "res_arcs", "_stats", "_span", "_turn0", "_arcs0")

    def __init__(self, stats: ChangeStats) -> None:
        self.kind: Optional[str] = None  # of the open run; None: no run is open
        self.res_nodes = 0  # resource-node turns of the runs closed so far
        self.res_arcs = 0  # arc records those runs sent to the journal
        self._stats = stats
        self._span: Optional[Span] = None
        self._turn0 = self._arcs0 = 0

    def switch(self, kind: Optional[str], turn: int) -> None:
        """After ``turn`` turns the next one is of another kind: close
        the open run and open one of ``kind`` (None: none)."""
        sp = self._span
        if sp is not None:
            turns = turn - self._turn0
            if self.kind == self.RES:
                arcs = self._stats.arc_records - self._arcs0
                self.res_nodes += turns
                self.res_arcs += arcs
                sp.set("nodes", turns)
                sp.set("arcs_changed", arcs)
            else:
                sp.set("tasks", turns)
            sp.finish()
        self.kind = kind
        self._span = start_span(kind) if kind is not None else None
        self._turn0 = turn
        self._arcs0 = self._stats.arc_records


class GraphManager:
    def __init__(
        self,
        cost_model: CostModeler,
        leaf_resource_ids: Set[int],
        stats: Optional[ChangeStats] = None,
        max_tasks_per_pu: int = 1,
        preemption: bool = False,
        update_preferences_running_task: bool = False,
    ) -> None:
        self.preemption = preemption
        self.update_preferences_running_task = update_preferences_running_task
        self.max_tasks_per_pu = max_tasks_per_pu
        self.cm = ChangeManager(stats)
        self.cost_model = cost_model
        self.sink_node = self.cm.add_node(NodeType.SINK, 0, ChangeType.ADD_SINK_NODE, "SINK")

        self.resource_to_node: Dict[int, Node] = {}
        self.task_to_node: Dict[int, Node] = {}
        self.task_ec_to_node: Dict[int, Node] = {}
        self.job_unsched_to_node: Dict[int, Node] = {}
        self.task_to_running_arc: Dict[int, Arc] = {}
        self.node_to_parent_node: Dict[int, Node] = {}  # keyed by node id
        self.leaf_resource_ids = leaf_resource_ids  # shared with the cost model
        self.leaf_node_ids: Set[int] = set()
        self._cur_traversal_counter = 0
        self._ec_purge_candidates: Set[int] = set()  # idle at the last purge
        self._ec_pointed_at: Set[int] = set()  # by a task's update since the last purge
        #: EC -> the living ECs whose update ever listed it as a
        #: preference (an EC -> EC arc): while one lives, the purge
        #: leaves the listed EC alone though no arc enters it
        self._ec_listed_by: Dict[int, Set[int]] = {}
        #: the ECs among those listers: the ones whose arcs to other ECs
        #: an update may have to prune
        self._ec_listers: Set[int] = set()
        #: the cost model can neither re-price a pinned task's one arc
        #: nor learn anything from a task node in the statistics walk
        self._tasks_inert = cost_model.pinned_tasks_are_inert
        #: a resource node an update meets gets a turn of its own: not
        #: where the model could only re-price its arcs at the price
        #: they have (_queue_res_turn)
        self._res_turns = not cost_model.resource_arc_costs_are_fixed
        #: the model bounds what a machine takes in a round: the arcs
        #: from a machine node to its children carry, together, no more
        #: than the bound (_write_machine_intake)
        self._intake_bounded = cost_model.bounds_machine_intake
        #: the model lists arcs of a task's own: that half of a task's
        #: turn gets a span (`pref_refresh`)
        self._pref_spans = cost_model.lists_task_preferences
        #: job id -> task uid -> (tree path, descriptor): the tasks the
        #: per-round update visits. A task is listed while it has or
        #: needs a node, unless it is pinned and the model calls pinned
        #: tasks inert. The path (child indices from the job's root)
        #: orders the list as the root-down walk met the tasks.
        self._worklist: Dict[int, Dict[int, Tuple[tuple, TaskDescriptor]]] = {}
        #: job id -> pinned tasks that hold a node and are off the list
        self._pinned_unlisted: Dict[int, int] = {}
        #: the last add_or_update_job_nodes: task nodes updated, and
        #: pinned tasks of the same jobs that were left alone
        self.tasks_visited = 0
        self.tasks_skipped = 0
        #: the last add_or_update_job_nodes: arcs from an EC node to a
        #: resource that it added, removed or re-priced; and arcs from
        #: an EC node to an EC node that it added, removed or gave
        #: another capacity or cost
        self.ec_arcs_changed = 0
        self.ec_chain_arcs_changed = 0
        #: the last add_or_update_job_nodes: arcs from an EC node to a
        #: resource whose cost and capacity it wrote, changed or not
        #: (what a sweep of every preferred resource works through)
        self.ec_arcs_repriced = 0
        #: the last add_or_update_job_nodes: resource nodes that took a
        #: turn (_queue_res_turn), and arcs out of them that it added or
        #: whose price really changed (a record in the journal, new or
        #: merged into one that was there; ChangeManager.change_arc drops
        #: a no-op)
        self.res_nodes_visited = 0
        self.res_arcs_changed = 0
        #: a task's own preference arcs (_is_pref_arc: to a resource, or
        #: to an EC other than the cluster aggregator; never its running
        #: arc): how many the graph holds, and how many came or went
        #: since take_pref_arcs_moved was last called (an update's adds
        #: and prunes, the pins and removals after it)
        self.pref_arcs_live = 0
        self._pref_arcs_moved = 0
        #: the last purge_unconnected_equiv_class_nodes: EC nodes it
        #: removed, and the arcs that went with them
        self.ec_purged = 0
        self.ec_arcs_dropped = 0
        #: node id -> the task node is pinned: _pin_task_to_node left it
        #: one arc, to its PU, with lower bound 1, so every feasible
        #: flow carries its unit there and no solve can change its
        #: binding. Set and cleared by events (pin; eviction, removal),
        #: for any cost model; never set under preemption.
        self._pinned = np.zeros(1024, dtype=bool)
        self.num_pinned = 0
        #: node ids of the other task nodes: what a solve can place,
        #: migrate or preempt, and all the decode has to map
        self.unpinned_task_nodes: Set[int] = set()
        #: resource ids of the PUs whose current_running_tasks changed
        #: since the last statistics pass: fed by running_tasks_changed,
        #: drained by compute_topology_statistics
        self._stats_dirty_pus: Set[int] = set()
        #: the resource topology changed since the last statistics pass
        self._stats_topology_changed = False
        #: the `deltas` phase since the last statistics pass vouched for
        #: the set (running_tasks_kept_by_events): it left the lists to
        #: the events instead of rebuilding them. Never before a first
        #: round, nor after one that raised or rebuilt the lists.
        self._stats_lists_kept = False
        #: the last compute_topology_statistics: PUs on the dirty set,
        #: resource nodes it prepared, whether it walked every node, and
        #: the children its nodes iterated (counted as arcs a node, so
        #: the count costs one add a node)
        self.stats_pus_dirty = 0
        self.stats_nodes_visited = 0
        self.stats_full_walk = 0
        self.stats_children_gathered = 0
        #: the post-solve refresh of the tree (refresh_resource_topology)
        #: has a baseline of its own: the PUs whose lists changed since
        #: the last refresh, fed by the same call, drained by the refresh
        self._apply_dirty_pus: Set[int] = set()
        #: the next refresh owes a walk of every node: the topology
        #: changed since the last one, or that one did not reach its end
        self._apply_walk_owed = True
        #: this round's `deltas` phase vouched for that set
        self._apply_lists_kept = False
        #: the last refresh_resource_topology: PUs on its dirty set,
        #: resource nodes it refreshed, and whether it walked every node
        self.apply_pus_dirty = 0
        self.apply_nodes_visited = 0
        self.apply_full_walk = 0

    def _pref_arcs(self, delta: int) -> None:
        """``delta`` preference arcs came (> 0) or went (< 0)."""
        self.pref_arcs_live += delta
        self._pref_arcs_moved += abs(delta)

    def take_pref_arcs_moved(self) -> int:
        """Preference arcs that came or went since the last call."""
        moved, self._pref_arcs_moved = self._pref_arcs_moved, 0
        return moved

    def _set_pinned(self, task_node: Node, pinned: bool) -> None:
        node_id = task_node.id
        if node_id >= len(self._pinned):
            grown = np.zeros(max(2 * len(self._pinned), node_id + 1), dtype=bool)
            grown[: len(self._pinned)] = self._pinned
            self._pinned = grown
        if bool(self._pinned[node_id]) != pinned:
            self._pinned[node_id] = pinned
            self.num_pinned += 1 if pinned else -1

    def pinned_mask(self, num_nodes: int) -> np.ndarray:
        """A copy of the pinned mask over node ids [0, num_nodes): a
        dispatched solve's decode keeps it while events move on."""
        mask = np.zeros(num_nodes, dtype=bool)
        n = min(num_nodes, len(self._pinned))
        mask[:n] = self._pinned[:n]
        return mask

    @property
    def unpinned_running_tasks(self) -> int:
        """Running tasks a solve may preempt or migrate: those with a
        running arc that is not a pin (all of them under preemption,
        none without)."""
        return len(self.task_to_running_arc) - self.num_pinned

    # ------------------------------------------------------------------
    # Public lifecycle API (reference interface graph_manager.go:32-86)
    # ------------------------------------------------------------------

    def task_runnable(self, td: TaskDescriptor, path: tuple) -> None:
        """The scheduler promoted ``td`` to RUNNABLE: it needs a node
        from the next update of its job on. ``path`` is the task's place
        in its job's tree, the index in each ``spawned`` list from the
        root task down (() for the root). No reference counterpart: its
        walk finds such a task by itself."""
        self._worklist.setdefault(job_id_from_string(td.job_id), {})[td.uid] = (path, td)

    def add_or_update_job_nodes(self, jobs: List[JobDescriptor]) -> None:
        """Reference: graph_manager.go:166-208, over the work list
        instead of every task under each job's root.

        The reference walks one FIFO breadth-first: tasks level by
        level, a new child's node added in its parent's turn, and an EC
        or resource node queued where it is first met (a resource node
        only for a model that may re-price its arcs, _queue_res_turn:
        its `due` key is its own and its turn queues no task and no EC,
        so the other turns keep their order without it). An event here is
        a listed task's turn (phase 0: update its arcs) or its parent's
        (phase 1: add its node), keyed (depth, job position, path), and
        the sorted events give the tasks' order. A node queued during
        the turn at key (d, j, p) entered the FIFO behind every child of
        the earlier turns and ahead of this turn's children, so it is
        due before the first event at or beyond (d + 1, j, p)."""
        node_queue: Deque[Tuple[Node, Optional[TaskDescriptor]]] = deque()
        due: Deque[tuple] = deque()  # node_queue[i] comes before events at or beyond due[i]
        marked: Set[int] = set()
        visited = 0
        self.ec_arcs_changed = 0
        self.ec_chain_arcs_changed = 0
        self.ec_arcs_repriced = 0
        runs = _TurnRuns(self.cm.stats)
        task_run, res_run = runs.TASK, runs.RES
        turn = 0  # turns taken so far, of any kind
        try:
            # the first turn is a task's: its run takes in the listing
            runs.switch(task_run, turn)
            events, skipped = self._listed_task_turns(jobs)
            i, n = 0, len(events)
            while i < n or node_queue:
                if node_queue and (i == n or due[0] <= events[i][0]):
                    depth, jpos, path = due.popleft()
                    node, _ = node_queue.popleft()
                    if node.is_equiv_class_node:
                        if runs.kind is not None:
                            runs.switch(None, turn)
                        self._update_equiv_class_node(node, node_queue, marked)
                    elif node.is_resource_node:
                        if runs.kind != res_run:
                            runs.switch(res_run, turn)
                        self._update_res_outgoing_arcs(node, node_queue, marked)
                    else:
                        raise ValueError(f"unexpected node type in worklist: {node.type}")
                else:
                    if runs.kind != task_run:
                        runs.switch(task_run, turn)
                    (depth, jpos, path), phase, _, _, td = events[i]
                    i += 1
                    if phase:
                        self._add_listed_task_node(job_id_from_string(td.job_id), td)
                    else:
                        task_node = self.task_to_node.get(td.uid)
                        if task_node is not None:
                            self._update_task_node(task_node, node_queue, marked)
                            visited += 1
                if len(node_queue) > len(due):
                    due.extend([(depth + 1, jpos, path)] * (len(node_queue) - len(due)))
                turn += 1
        finally:
            runs.switch(None, turn)
        if self._intake_bounded:
            # the bounds that moved since the last update, whatever moved
            # them (a larger pod than any before, a placement or an
            # eviction outside a round): none is stale at the solve
            for rid in self.cost_model.take_machine_intake_changes():
                node = self.resource_to_node.get(rid)
                if node is not None:
                    self._write_machine_intake(node)
        self.tasks_visited = visited
        self.tasks_skipped = skipped
        self.res_nodes_visited = runs.res_nodes
        self.res_arcs_changed = runs.res_arcs

    def _listed_task_turns(self, jobs: List[JobDescriptor]) -> Tuple[list, int]:
        """The task turns of an update of ``jobs`` in the order they
        are taken, each (key, phase, index among its siblings, uid,
        descriptor), where the uid only keeps the sort from ever
        comparing descriptors; and the pinned tasks of those jobs that
        are off the list."""
        events: List[Tuple[tuple, int, int, int, TaskDescriptor]] = []
        skipped = 0
        for jpos, job in enumerate(jobs):
            jid = job_id_from_string(job.uuid)
            if jid not in self.job_unsched_to_node:
                self._add_unscheduled_agg_node(jid)
            skipped += self._pinned_unlisted.get(jid, 0)
            listed = self._worklist.get(jid)
            if not listed:
                continue
            root_td = job.root_task
            assert root_td is not None, f"job {job.uuid} has no root task"
            if root_td.uid in listed and root_td.uid not in self.task_to_node:
                # a root's node is added before the walk starts
                self._add_listed_task_node(jid, root_td)
            for path, td in listed.values():
                depth = len(path)
                events.append(((depth, jpos, path), 0, 0, td.uid, td))
                if depth and td.uid not in self.task_to_node:
                    events.append(((depth - 1, jpos, path[:-1]), 1, path[-1], td.uid, td))
        events.sort()
        return events, skipped

    def _add_listed_task_node(self, job_id: int, td: TaskDescriptor) -> None:
        """Reference: graph_manager.go:895-929, one child. A listed task
        whose state moved on before it ever got a node leaves the list."""
        if not task_needs_node(td):
            self._worklist[job_id].pop(td.uid, None)
            return
        node = self._add_task_node(job_id, td)
        node.tree_path = self._worklist[job_id][td.uid][0]
        self._update_unscheduled_agg_node(self.job_unsched_to_node[job_id], 1)

    def update_time_dependent_costs(self, jobs: List[JobDescriptor]) -> None:
        self.add_or_update_job_nodes(jobs)

    def add_resource_topology(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: graph_manager.go:238-251."""
        rd = rtnd.resource_desc
        self._stats_topology_changed = self._apply_walk_owed = True
        self._add_resource_topology_dfs(rtnd)
        if rtnd.parent_id:
            curr = self.resource_to_node[resource_id_from_string(rtnd.parent_id)]
            self._update_resource_stats_up_to_root(
                curr,
                self._capacity_to_parent(rd),
                rd.num_slots_below,
                rd.num_running_tasks_below,
            )

    def update_resource_topology(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: graph_manager.go:217-236."""
        rd = rtnd.resource_desc
        old_capacity = self._capacity_to_parent(rd)
        old_slots = rd.num_slots_below
        old_running = rd.num_running_tasks_below
        self._update_resource_topology_dfs(rtnd)
        if rtnd.parent_id:
            curr = self.resource_to_node[resource_id_from_string(rtnd.parent_id)]
            self._update_resource_stats_up_to_root(
                curr,
                self._capacity_to_parent(rd) - old_capacity,
                rd.num_slots_below - old_slots,
                rd.num_running_tasks_below - old_running,
            )

    def refresh_resource_topology(self, roots: Iterable[ResourceTopologyNodeDescriptor]) -> None:
        """The post-solve refresh of the tree under ``roots`` (every
        registered root): what update_resource_topology leaves on each
        of them, by a visit of the PUs whose ``current_running_tasks``
        changed since the last refresh and of their ancestors. The two
        counts of a node and the capacity of the arc from its parent
        are a function of the lists' lengths below it, so every other
        node keeps what the last refresh left. Every node is walked
        when that cannot be trusted (a walk is owed: the topology
        changed since the last refresh, which covers the first one and
        a cold restore's, or the last one did not reach its end; this
        round's `deltas` phase did not vouch for the set; preemption,
        whose delta walk rebuilds every list) and when the dirty PUs'
        paths to the root would visit as many nodes as the tree has.
        No cost-model hook is called, so no model is excepted."""
        dirty = self._apply_dirty_pus
        self.apply_pus_dirty = len(dirty)
        walk_all = (
            self._apply_walk_owed
            or not self._apply_lists_kept
            or self.preemption
            or self._paths_span_the_tree(dirty)
        )
        self._apply_lists_kept = False
        self._apply_walk_owed = True  # until this one has reached its end
        self.apply_full_walk = int(walk_all)
        if walk_all:
            for rtnd in roots:
                self.update_resource_topology(rtnd)
            self.apply_nodes_visited = len(self.resource_to_node)
        else:
            self.apply_nodes_visited = self._refresh_dirty_topology(roots, dirty)
        self._apply_walk_owed = False
        dirty.clear()

    def _refresh_dirty_topology(
        self, roots: Iterable[ResourceTopologyNodeDescriptor], dirty_pus: Set[int]
    ) -> int:
        """_update_resource_topology_dfs, descending only into the
        nodes on a path from a PU of ``dirty_pus`` (resource ids) to its
        root: the same counts, and the same arc changes in the same
        order, so the journal is the walk's. Returns the nodes visited."""
        on_path: Set[str] = set()
        parent_of = self.node_to_parent_node
        for rid in dirty_pus:
            node: Optional[Node] = self.resource_to_node[rid]
            while node is not None:
                uuid = node.resource_descriptor.uuid
                if uuid in on_path:
                    break  # the rest of the way up is on another PU's path
                on_path.add(uuid)
                node = parent_of.get(node.id)
        for rtnd in roots:
            if rtnd.resource_desc.uuid in on_path:
                self._refresh_dirty_topology_dfs(rtnd, on_path)
        return len(on_path)

    def _refresh_dirty_topology_dfs(
        self, rtnd: ResourceTopologyNodeDescriptor, on_path: Set[str]
    ) -> None:
        rd = rtnd.resource_desc
        if rd.type == ResourceType.PU:
            slots = self.max_tasks_per_pu
            running = len(rd.current_running_tasks)
        else:
            slots = running = 0
        for child in rtnd.children:
            child_rd = child.resource_desc
            if child_rd.uuid in on_path:
                self._refresh_dirty_topology_dfs(child, on_path)
            slots += child_rd.num_slots_below
            running += child_rd.num_running_tasks_below
        rd.num_slots_below = slots
        rd.num_running_tasks_below = running
        if rtnd.parent_id:
            curr = self.resource_to_node[resource_id_from_string(rd.uuid)]
            self._write_capacity_from_parent(curr, rd)

    def remove_resource_topology(self, rd: ResourceDescriptor) -> List[int]:
        """Reference: graph_manager.go:362-387. Returns removed PU node ids."""
        r_node = self.resource_to_node.get(resource_id_from_string(rd.uuid))
        if r_node is None:
            raise KeyError(f"no node for resource {rd.uuid}")
        self._stats_topology_changed = self._apply_walk_owed = True
        removed_pus: List[int] = []
        cap_delta = 0
        for arc in list(r_node.outgoing.values()):
            cap_delta -= arc.cap_upper
            if arc.dst_node.resource_id != 0:
                removed_pus.extend(self._traverse_and_remove_topology(arc.dst_node))
        self._update_resource_stats_up_to_root(
            r_node,
            cap_delta,
            -r_node.resource_descriptor.num_slots_below,
            -r_node.resource_descriptor.num_running_tasks_below,
        )
        if r_node.type == NodeType.PU:
            removed_pus.append(r_node.id)
        elif r_node.type == NodeType.MACHINE:
            self.cost_model.remove_machine(r_node.resource_id)
        self._remove_resource_node(r_node)
        return removed_pus

    def job_completed(self, job_id: int) -> None:
        """Reference: graph_manager.go:341-345."""
        node = self.job_unsched_to_node.pop(job_id)
        self.cm.delete_node(node, ChangeType.DEL_UNSCHED_JOB_NODE, "JobCompleted")
        if not self._worklist.get(job_id) and not self._pinned_unlisted.get(job_id):
            self._worklist.pop(job_id, None)
            self._pinned_unlisted.pop(job_id, None)

    def purge_unconnected_equiv_class_nodes(self) -> None:
        """Remove equivalence-class nodes nothing points at (reference
        declares this, graph_manager.go:347-357, but never calls it;
        the scheduler here runs it per round).

        Debounced: an EC must be idle at two consecutive calls before
        removal: unconnected, and no task's update pointed at it since
        the call before (with preemption off a placed task is pinned
        and its EC arc gone by the time of the purge, so an EC in use
        every round is unconnected at every purge). ECs that are merely
        transiently idle (e.g. every task pinned this round, new
        arrivals next round) then don't churn their wide EC->machine
        fan-outs through the change journal each cycle. ECs orphaned by
        a removal within this call (their only in-arcs came from a
        purged EC) are dead for certain and cascade immediately — the
        reference's note about multi-call subgraph cleanup
        (graph_manager.go:348-351) without leaving chains behind if the
        cluster quiesces. An EC that another EC's update listed (the
        far end of an EC -> EC arc: no task ever points at it, and in a
        round whose allotment passes it by no arc enters it) is idle
        only once every EC that listed it is gone; then it is one of
        the orphans and goes in the same call."""
        listed_by = self._ec_listed_by

        def unconnected() -> set:
            return {
                ec for ec, node in self.task_ec_to_node.items()
                if not node.incoming and not listed_by.get(ec)
            }

        seen = unconnected()
        doomed = (seen & self._ec_purge_candidates) - self._ec_pointed_at
        self.ec_purged = self.ec_arcs_dropped = 0
        while doomed:
            for ec in doomed:
                node = self.task_ec_to_node[ec]
                self.ec_purged += 1
                self.ec_arcs_dropped += len(node.outgoing) + len(node.incoming)
                self._remove_equiv_class_node(node)
            now = unconnected()
            doomed = now - seen  # newly orphaned by this wave: cascade
            seen |= now
        self._ec_purge_candidates = unconnected() - self._ec_pointed_at
        self._ec_pointed_at.clear()

    def task_completed(self, task_id: int) -> int:
        """Reference: graph_manager.go:389-405."""
        task_node = self.task_to_node[task_id]
        if self.preemption:
            self._update_unscheduled_agg_node(self.job_unsched_to_node[task_node.job_id], -1)
        self.task_to_running_arc.pop(task_id, None)
        return self._remove_task_node(task_node)
        # The task stays in the cost model: final-report handling still
        # needs its equivalence classes (reference note at :402-404).

    def task_evicted(self, task_id: int, resource_id: int) -> None:
        """Reference: graph_manager.go:412-433."""
        task_node = self.task_to_node[task_id]
        task_node.type = NodeType.UNSCHEDULED_TASK
        arc = self.task_to_running_arc.pop(task_id)
        self.cm.delete_arc(arc, ChangeType.DEL_ARC_EVICTED_TASK, "TaskEvicted: delete running arc")
        self._set_pinned(task_node, False)
        self.unpinned_task_nodes.add(task_node.id)
        if not self.preemption:
            jid = job_id_from_string(task_node.task.job_id)
            self._update_unscheduled_agg_node(self.job_unsched_to_node[jid], 1)
            listed = self._worklist[task_node.job_id]
            if task_id not in listed:  # it was pinned and left alone
                listed[task_id] = (task_node.tree_path, task_node.task)
                self._pinned_unlisted[task_node.job_id] -= 1

    def task_failed(self, task_id: int) -> None:
        """Reference: graph_manager.go:435-448."""
        task_node = self.task_to_node[task_id]
        if self.preemption:
            self._update_unscheduled_agg_node(self.job_unsched_to_node[task_node.job_id], -1)
        self.task_to_running_arc.pop(task_id, None)
        self._remove_task_node(task_node)
        self.cost_model.remove_task(task_id)

    def task_killed(self, task_id: int) -> None:
        self.task_failed(task_id)

    def task_migrated(self, task_id: int, from_rid: int, to_rid: int) -> None:
        self.task_evicted(task_id, from_rid)
        self.task_scheduled(task_id, to_rid)

    def task_scheduled(self, task_id: int, resource_id: int) -> None:
        """Reference: graph_manager.go:454-460."""
        task_node = self.task_to_node[task_id]
        task_node.type = NodeType.SCHEDULED_TASK
        res_node = self.resource_to_node[resource_id]
        self._update_arcs_for_scheduled_task(task_node, res_node)

    def update_all_costs_to_unscheduled_aggs(self) -> None:
        """Reference: graph_manager.go:462-475."""
        for job_node in self.job_unsched_to_node.values():
            for arc in list(job_node.incoming.values()):
                if arc.src_node.is_task_assigned_or_running:
                    self._update_running_task_node(arc.src_node, False, None, None)
                else:
                    self._update_task_to_unscheduled_agg_arc(arc.src_node)

    def running_tasks_changed(self, resource_id: int) -> None:
        """The scheduler appended to or took from the
        ``current_running_tasks`` of the PU ``resource_id``: the next
        statistics pass has to gather it, and what lies above it, again,
        and so has the next refresh of the tree. Each drains a set of its
        own: an eviction between two rounds is gathered by the pass that
        opens the next round, and the capacities on its path, which only
        the refresh writes, are still to come."""
        self._stats_dirty_pus.add(resource_id)
        self._apply_dirty_pus.add(resource_id)

    def running_tasks_kept_by_events(self) -> None:
        """The scheduler's `deltas` phase left every PU's list as the
        events made it (each told through running_tasks_changed), where
        the reference's rebuilds them all: this round's refresh of the
        tree and the next statistics pass may trust their dirty sets.
        Without this word each walks every node, so a round that rebuilt
        the lists, raised half-way or came from other code than
        FlowScheduler's costs a full walk and no stale count."""
        self._stats_lists_kept = self._apply_lists_kept = True

    def compute_topology_statistics(self, start: Node) -> None:
        """Usage statistics of the resource tree, gathered from the PUs
        up (reference: graph_manager.go:478-511, which walks every node
        every round). Where the model calls tasks inert, what the three
        hooks leave on a resource node is a function of the PUs'
        ``current_running_tasks`` below it, so only the PUs whose lists
        changed since the last pass and their ancestors are gathered
        again; every other node keeps what the last pass left. Every
        node is walked when the set cannot be trusted (no `deltas`
        phase vouched for it since the last pass, which covers the
        first pass and a restore's; the topology changed; preemption,
        whose delta walk rebuilds every list; a model that does not
        make the claim) and when the dirty PUs' paths to the root
        would visit as many nodes as the tree has."""
        dirty, self._stats_dirty_pus = self._stats_dirty_pus, set()
        self.stats_pus_dirty = len(dirty)
        walk_all = (
            not self._stats_lists_kept
            or self._stats_topology_changed
            or self.preemption
            or not self._tasks_inert
            or start is not self.sink_node
        )
        self._stats_lists_kept = self._stats_topology_changed = False
        walk_all = walk_all or self._paths_span_the_tree(dirty)
        self.stats_full_walk = int(walk_all)
        if walk_all:
            self.stats_children_gathered = self._walk_topology_statistics(start)
            self.stats_nodes_visited = len(self.resource_to_node)
        else:
            self.stats_nodes_visited, self.stats_children_gathered = (
                self._gather_dirty_statistics(dirty)
            )

    def _paths_span_the_tree(self, dirty_pus: Set[int]) -> bool:
        """The paths from ``dirty_pus`` (resource ids) up to the root
        would visit as many nodes as the tree has, reckoned as PUs times
        the depth of one of them: a wave that touched most PUs is then
        never slower than the walk of every node."""
        if not dirty_pus:
            return False
        depth = 1
        node = self.resource_to_node[next(iter(dirty_pus))]
        while (node := self.node_to_parent_node.get(node.id)) is not None:
            depth += 1
        return len(dirty_pus) * depth >= len(self.resource_to_node)

    def _walk_topology_statistics(self, start: Node) -> int:
        """Reverse BFS from the sink over every node; correct only for
        tree topologies (reference: graph_manager.go:478-511). Where the
        model calls tasks inert, a task node is passed over: the three
        hooks would return at once for it, and it has no incoming arc,
        so nothing lies behind it. Returns the arcs it went over: the
        incoming arcs of every node it visited."""
        self._cur_traversal_counter += 1
        counter = self._cur_traversal_counter
        skip_tasks = self._tasks_inert
        to_visit: Deque[Node] = deque([start])
        start.visited = counter
        iterated = 0
        while to_visit:
            cur = to_visit.popleft()
            iterated += len(cur.incoming)
            for arc in cur.incoming.values():
                src = arc.src_node
                if skip_tasks and src.task is not None:
                    continue
                if src.visited != counter:
                    self.cost_model.prepare_stats(src)
                    to_visit.append(src)
                    src.visited = counter
                self.cost_model.gather_stats(src, cur)
                self.cost_model.update_stats(src, cur)
        return iterated

    def _gather_dirty_statistics(self, dirty_pus: Set[int]) -> Tuple[int, int]:
        """The walk's three hooks for the PUs of ``dirty_pus`` (resource
        ids) and, level by level up the tree, for each of their
        ancestors once all its dirty children are done: an ancestor is
        prepared and gathers from every resource child (the tree's
        children, so a machine's arcs from EC nodes are not looked at).
        Returns the resource nodes prepared and the children iterated:
        one for each dirty PU (the sink), and every outgoing arc of each
        ancestor, dirty below or not: the coordinator of 12,500 machines
        re-reads them all for one dirty path."""
        prepare = self.cost_model.prepare_stats
        gather = self.cost_model.gather_stats
        update = self.cost_model.update_stats
        sink = self.sink_node
        level = [self.resource_to_node[rid] for rid in dirty_pus]
        for pu in level:
            prepare(pu)
            gather(pu, sink)
            update(pu, sink)
        visited = iterated = len(level)
        parent_of = self.node_to_parent_node
        while level:
            parents: Dict[int, Node] = {}
            for node in level:
                parent = parent_of.get(node.id)
                if parent is not None:
                    parents[parent.id] = parent
            level = list(parents.values())
            for parent in level:
                prepare(parent)
                iterated += len(parent.outgoing)
                for arc in parent.outgoing.values():
                    child = arc.dst_node
                    if child.resource_id != 0:
                        gather(parent, child)
                        update(parent, child)
            visited += len(level)
        return visited, iterated

    # ------------------------------------------------------------------
    # Delta generation (reference: graph_manager.go:253-339)
    # ------------------------------------------------------------------

    def node_binding_to_scheduling_delta(
        self, task_node_id: int, res_node_id: int, task_bindings: Dict[int, int]
    ) -> Optional[SchedulingDelta]:
        task_node = self.cm.graph.node(task_node_id)
        assert task_node is not None and task_node.is_task_node, f"non-task node {task_node_id}"
        res_node = self.cm.graph.node(res_node_id)
        assert res_node is not None and res_node.type == NodeType.PU, f"non-PU node {res_node_id}"
        task = task_node.task
        rd = res_node.resource_descriptor
        bound = task_bindings.get(task.uid)
        if bound is None:
            return SchedulingDelta(DeltaType.PLACE, task.uid, rd.uuid)
        if bound != resource_id_from_string(rd.uuid):
            return SchedulingDelta(DeltaType.MIGRATE, task.uid, rd.uuid)
        # Already scheduled here; repopulate the running-task list that
        # SchedulingDeltasForPreemptedTasks cleared.
        rd.current_running_tasks.append(task.uid)
        return None

    def scheduling_deltas_for_preempted_tasks(
        self, task_mapping: TaskMapping, resource_map: ResourceMap
    ) -> List[SchedulingDelta]:
        deltas: List[SchedulingDelta] = []
        for rs in resource_map.unsafe_get().values():
            rd = rs.descriptor
            for task_id in rd.current_running_tasks:
                task_node = self.task_to_node.get(task_id)
                if task_node is None:
                    continue  # task finished; no PREEMPT needed
                if task_node.id not in task_mapping:
                    deltas.append(SchedulingDelta(DeltaType.PREEMPT, task_id, rd.uuid))
            # Cleared wholesale; NodeBindingToSchedulingDelta repopulates
            # (reference: graph_manager.go:327-337).
            rd.current_running_tasks = []
        return deltas

    # ------------------------------------------------------------------
    # Private: node add/remove helpers
    # ------------------------------------------------------------------

    def _add_equiv_class_node(self, ec: int) -> Node:
        node = self.cm.add_node(NodeType.EQUIV_CLASS, 0, ChangeType.ADD_EQUIV_CLASS_NODE, f"EC_{ec}")
        node.equiv_class = ec
        assert ec not in self.task_ec_to_node
        self.task_ec_to_node[ec] = node
        return node

    def _add_resource_node(self, rd: ResourceDescriptor) -> Node:
        comment = rd.friendly_name or "AddResourceNode"
        node = self.cm.add_node(resource_node_type(rd), 0, ChangeType.ADD_RESOURCE_NODE, comment)
        rid = resource_id_from_string(rd.uuid)
        node.resource_id = rid
        node.resource_descriptor = rd
        assert rid not in self.resource_to_node
        self.resource_to_node[rid] = node
        if node.type == NodeType.PU:
            self.leaf_node_ids.add(node.id)
            self.leaf_resource_ids.add(rid)
        return node

    def _add_task_node(self, job_id: int, td: TaskDescriptor) -> Node:
        self.cost_model.add_task(td.uid)
        node = self.cm.add_node(NodeType.UNSCHEDULED_TASK, 1, ChangeType.ADD_TASK_NODE, td.name or "AddTaskNode")
        node.task = td
        node.job_id = job_id
        self.sink_node.excess -= 1
        assert td.uid not in self.task_to_node
        self.task_to_node[td.uid] = node
        self.unpinned_task_nodes.add(node.id)
        return node

    def _add_unscheduled_agg_node(self, job_id: int) -> Node:
        node = self.cm.add_node(
            NodeType.JOB_AGGREGATOR, 0, ChangeType.ADD_UNSCHED_JOB_NODE, f"UNSCHED_AGG_for_{job_id}"
        )
        node.job_id = job_id
        assert job_id not in self.job_unsched_to_node
        self.job_unsched_to_node[job_id] = node
        return node

    def _remove_equiv_class_node(self, node: Node) -> None:
        ec = node.equiv_class
        del self.task_ec_to_node[ec]
        self._ec_listed_by.pop(ec, None)
        if ec in self._ec_listers:
            self._ec_listers.discard(ec)
            for listers in self._ec_listed_by.values():
                listers.discard(ec)
        self.cm.delete_node(node, ChangeType.DEL_EQUIV_CLASS_NODE, "RemoveEquivClassNode")

    def _remove_resource_node(self, node: Node) -> None:
        self.node_to_parent_node.pop(node.id, None)
        self.leaf_node_ids.discard(node.id)
        self.leaf_resource_ids.discard(node.resource_id)
        self.resource_to_node.pop(node.resource_id, None)
        self.cm.delete_node(node, ChangeType.DEL_RESOURCE_NODE, "RemoveResourceNode")

    def _remove_task_node(self, node: Node) -> int:
        node_id = node.id
        node.excess = 0
        self.sink_node.excess += 1
        if self.pref_arcs_live:
            self._pref_arcs(-sum(1 for arc in node.outgoing.values() if _is_pref_arc(arc)))
        del self.task_to_node[node.task.uid]
        # node ids are reused: the next holder of this one starts unpinned
        self._set_pinned(node, False)
        self.unpinned_task_nodes.discard(node_id)
        if self._worklist[node.job_id].pop(node.task.uid, None) is None:
            self._pinned_unlisted[node.job_id] -= 1
        self.cm.delete_node(node, ChangeType.DEL_TASK_NODE, "RemoveTaskNode")
        return node_id

    # ------------------------------------------------------------------
    # Private: resource topology
    # ------------------------------------------------------------------

    def _capacity_to_parent(self, rd: ResourceDescriptor) -> int:
        """Reference: graph_manager.go:662-667 — slots below, minus running
        tasks below when preemption is off (a running task's slot must not
        be handed out again if it cannot be preempted)."""
        if self.preemption:
            return rd.num_slots_below
        return rd.num_slots_below - rd.num_running_tasks_below

    def _write_capacity_from_parent(self, curr: Node, rd: ResourceDescriptor) -> None:
        """The refresh's write of the arc into ``curr`` (the node of
        ``rd``), and, where ``curr`` is a machine whose intake the model
        bounds, of the arcs out of it, once its children are done."""
        parent = self.node_to_parent_node[curr.id]
        if not (self._intake_bounded and parent.type == NodeType.MACHINE):
            # (the arcs below a bounded machine are written in its own turn)
            self.cm.change_arc_capacity(
                self.cm.graph.get_arc(parent, curr), self._capacity_to_parent(rd),
                ChangeType.CHG_ARC_BETWEEN_RES, "UpdateResourceTopologyDFS",
            )
        if self._intake_bounded and curr.type == NodeType.MACHINE:
            self._write_machine_intake(curr)

    def _write_machine_intake(self, machine: Node) -> None:
        """The arcs from ``machine`` to its children under a model that
        bounds what the machine takes in a round
        (CostModeler.bounds_machine_intake): each child's free slots, and
        over all of them no more than the bound, dealt to the children in
        the order they were added. They are the machine's own path to
        the sink: every arc INTO the machine shares them, so the ECs
        together cannot send more than the bound."""
        left = self.cost_model.machine_intake(machine.resource_id)
        for arc in machine.outgoing.values():
            child = arc.dst_node
            if child.resource_id != 0:
                capacity = min(self._capacity_to_parent(child.resource_descriptor), left)
                left -= capacity
                self.cm.change_arc_capacity(
                    arc, capacity, ChangeType.CHG_ARC_BETWEEN_RES, "MachineIntake"
                )

    def _add_resource_topology_dfs(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: graph_manager.go:557-630."""
        rd = rtnd.resource_desc
        rid = resource_id_from_string(rd.uuid)
        node = self.resource_to_node.get(rid)
        added_new = False
        if node is None:
            added_new = True
            node = self._add_resource_node(rd)
            if node.type == NodeType.PU:
                self._update_res_to_sink_arc(node)
                if rd.num_slots_below == 0:
                    rd.num_slots_below = self.max_tasks_per_pu
                    if rd.num_running_tasks_below == 0:
                        rd.num_running_tasks_below = len(rd.current_running_tasks)
            else:
                if node.type == NodeType.MACHINE:
                    self.cost_model.add_machine(rtnd)
                rd.num_slots_below = 0
                rd.num_running_tasks_below = 0
        else:
            rd.num_slots_below = 0
            rd.num_running_tasks_below = 0

        for child in rtnd.children:
            self._add_resource_topology_dfs(child)
            rd.num_slots_below += child.resource_desc.num_slots_below
            rd.num_running_tasks_below += child.resource_desc.num_running_tasks_below
        if self._intake_bounded and node.type == NodeType.MACHINE:
            self._write_machine_intake(node)

        if not rtnd.parent_id:
            if rd.type != ResourceType.COORDINATOR:
                raise ValueError("a non-coordinator resource must have a parent")
            return
        if added_new:
            parent = self.resource_to_node[resource_id_from_string(rtnd.parent_id)]
            assert node.id not in self.node_to_parent_node
            self.node_to_parent_node[node.id] = parent
            self.cm.add_arc(
                parent,
                node,
                0,
                self._capacity_to_parent(rd),
                self.cost_model.resource_node_to_resource_node_cost(parent.resource_descriptor, rd),
                ArcType.OTHER,
                ChangeType.ADD_ARC_BETWEEN_RES,
                "AddResourceTopologyDFS",
            )

    def _update_resource_topology_dfs(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: graph_manager.go:1063-1092."""
        rd = rtnd.resource_desc
        rd.num_slots_below = 0
        rd.num_running_tasks_below = 0
        if rd.type == ResourceType.PU:
            rd.num_slots_below = self.max_tasks_per_pu
            rd.num_running_tasks_below = len(rd.current_running_tasks)
        for child in rtnd.children:
            self._update_resource_topology_dfs(child)
            rd.num_slots_below += child.resource_desc.num_slots_below
            rd.num_running_tasks_below += child.resource_desc.num_running_tasks_below
        if rtnd.parent_id:
            curr = self.resource_to_node[resource_id_from_string(rd.uuid)]
            self._write_capacity_from_parent(curr, rd)

    def _update_resource_stats_up_to_root(
        self, curr: Node, cap_delta: int, slots_delta: int, running_delta: int
    ) -> None:
        """Reference: graph_manager.go:1041-1061."""
        while True:
            parent = self.node_to_parent_node.get(curr.id)
            if parent is None:
                return
            parent_arc = self.cm.graph.get_arc(parent, curr)
            assert parent_arc is not None, f"missing arc {parent.id}->{curr.id}"
            self.cm.change_arc_capacity(
                parent_arc, parent_arc.cap_upper + cap_delta, ChangeType.CHG_ARC_BETWEEN_RES, "UpdateCapacityUpToRoot"
            )
            prd = parent.resource_descriptor
            prd.num_slots_below += slots_delta
            prd.num_running_tasks_below += running_delta
            curr = parent

    def _traverse_and_remove_topology(self, node: Node) -> List[int]:
        """Reference: graph_manager.go:829-844."""
        removed: List[int] = []
        for arc in list(node.outgoing.values()):
            if arc.dst_node.resource_id != 0:
                removed.extend(self._traverse_and_remove_topology(arc.dst_node))
        if node.type == NodeType.PU:
            removed.append(node.id)
        elif node.type == NodeType.MACHINE:
            self.cost_model.remove_machine(node.resource_id)
        self._remove_resource_node(node)
        return removed

    # ------------------------------------------------------------------
    # Private: worklist update (the per-round hot path)
    # ------------------------------------------------------------------

    def _update_task_node(self, task_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:1183-1192."""
        if task_node.is_task_assigned_or_running:
            self._update_running_task_node(
                task_node, self.update_preferences_running_task, node_queue, marked
            )
            return
        self._update_task_to_unscheduled_agg_arc(task_node)
        if self._pref_spans:
            # the task's own arcs, with the model's arithmetic over its
            # input (made at the first of these questions)
            with span("pref_refresh"):
                self._update_task_to_equiv_arcs(task_node, node_queue, marked)
                self._update_task_to_res_arcs(task_node, node_queue, marked)
            return
        self._update_task_to_equiv_arcs(task_node, node_queue, marked)
        self._update_task_to_res_arcs(task_node, node_queue, marked)

    def _update_equiv_class_node(self, ec_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        with span("ec_chain_refresh"):
            self._update_equiv_to_equiv_arcs(ec_node, node_queue, marked)
        self._update_equiv_to_res_arcs(ec_node, node_queue, marked)

    def _update_equiv_to_equiv_arcs(self, ec_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:939-970."""
        ec = ec_node.equiv_class
        pref_ecs = self.cost_model.get_equiv_class_to_equiv_classes_arcs(ec)
        for pref_ec in pref_ecs:
            pref_node = self.task_ec_to_node.get(pref_ec)
            if pref_node is None:
                pref_node = self._add_equiv_class_node(pref_ec)
            self._ec_listed_by.setdefault(pref_ec, set()).add(ec)
            self._ec_listers.add(ec)
            cost, cap_upper = self.cost_model.equiv_class_to_equiv_class(ec, pref_ec)
            arc = self.cm.graph.get_arc(ec_node, pref_node)
            if arc is None:
                self.cm.add_arc(
                    ec_node, pref_node, 0, cap_upper, cost, ArcType.OTHER,
                    ChangeType.ADD_ARC_BETWEEN_EQUIV_CLASS, "UpdateEquivClassNode",
                )
                self.ec_chain_arcs_changed += 1
            else:
                if (arc.cap_upper, arc.cost) != (cap_upper, cost):
                    self.ec_chain_arcs_changed += 1
                self.cm.change_arc(
                    arc, arc.cap_lower, cap_upper, cost,
                    ChangeType.CHG_ARC_BETWEEN_EQUIV_CLASS, "UpdateEquivClassNode",
                )
            if pref_node.id not in marked:
                marked.add(pref_node.id)
                node_queue.append((pref_node, pref_node.task))
        # an EC that never listed another has no arc to one: its arcs to
        # resources (a zone's 1,667 machines) are not looked through
        if pref_ecs or ec in self._ec_listers:
            self.ec_chain_arcs_changed += self._remove_invalid_ec_pref_arcs(
                ec_node, pref_ecs, ChangeType.DEL_ARC_BETWEEN_EQUIV_CLASS
            )

    def _update_equiv_to_res_arcs(self, ec_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:974-1010, vectorized through the
        batch cost-model hook so wide fan-outs (EC → every machine) cost
        one call. Where the EC has arcs and the model kept a record of
        what changed since it listed them, only those resources are
        looked at; the span says which it was (`swept`)."""
        with span("ec_refresh") as sp:
            ec = ec_node.equiv_class
            changed = self.cost_model.equiv_class_pref_arc_changes(ec) if ec_node.outgoing else None
            sp.set("swept", int(changed is None))
            if changed is None:
                self._sweep_equiv_to_res_arcs(ec_node, node_queue, marked)
            else:
                self._patch_equiv_to_res_arcs(ec_node, changed)

    def _set_equiv_to_res_arc(self, ec_node: Node, res_node: Node, cost: int, cap_upper: int) -> None:
        self.ec_arcs_repriced += 1
        arc = self.cm.graph.get_arc(ec_node, res_node)
        if arc is None:
            self.cm.add_arc(
                ec_node, res_node, 0, cap_upper, cost, ArcType.OTHER,
                ChangeType.ADD_ARC_EQUIV_CLASS_TO_RES, "UpdateEquivToResArcs",
            )
            self.ec_arcs_changed += 1
        else:
            if (arc.cap_upper, arc.cost) != (cap_upper, cost):
                self.ec_arcs_changed += 1
            self.cm.change_arc(
                arc, arc.cap_lower, cap_upper, cost,
                ChangeType.CHG_ARC_EQUIV_CLASS_TO_RES, "UpdateEquivToResArcs",
            )

    def _sweep_equiv_to_res_arcs(self, ec_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        pref_rids = self.cost_model.get_outgoing_equiv_class_pref_arcs(ec_node.equiv_class)
        if pref_rids:
            costs, caps = self.cost_model.ec_to_resource_batch(ec_node.equiv_class, pref_rids)
            for pref_rid, cost, cap_upper in zip(pref_rids, costs, caps):
                pref_node = self.resource_to_node.get(pref_rid)
                assert pref_node is not None, "cost model preferred an unknown resource"
                self._set_equiv_to_res_arc(ec_node, pref_node, cost, cap_upper)
                self._queue_res_turn(pref_node, node_queue, marked)
        self.ec_arcs_changed += self._remove_invalid_pref_res_arcs(
            ec_node, pref_rids, ChangeType.DEL_ARC_EQUIV_CLASS_TO_RES
        )

    def _patch_equiv_to_res_arcs(self, ec_node: Node, changed: List[int]) -> None:
        """The arcs from ``ec_node`` to the resources of ``changed``,
        each as the model now has it, priced in one call. Capacity 0:
        no arc, or an arc of capacity 0 where the model's listing keeps
        a full resource (CostModeler.full_resources_stay_listed): what a
        sweep would leave. The resources are not queued for a visit:
        nothing about them is known to have changed but this arc."""
        # a resource that left took its node, and the arc, with it
        live = [rid for rid in changed if rid in self.resource_to_node]
        if not live:
            return
        costs, caps = self.cost_model.ec_to_resource_batch(ec_node.equiv_class, live)
        keep_full = self.cost_model.full_resources_stay_listed
        for rid, cost, cap_upper in zip(live, costs, caps):
            res_node = self.resource_to_node[rid]
            if cap_upper > 0 or keep_full:
                self._set_equiv_to_res_arc(ec_node, res_node, cost, cap_upper)
                continue
            arc = self.cm.graph.get_arc(ec_node, res_node)
            if arc is not None:
                self.cm.delete_arc(arc, ChangeType.DEL_ARC_EQUIV_CLASS_TO_RES, "UpdateEquivToResArcs")
                self.ec_arcs_changed += 1

    def _queue_res_turn(self, res_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """An EC's sweep, a task's preference arcs or its parent's turn
        met ``res_node``: queue it for a turn of its own, once an
        update. Not where the model prices its resource arcs at
        constants (CostModeler.resource_arc_costs_are_fixed): the turn
        would re-price the arcs out of the node at the price they have,
        which the journal drops, so nothing is queued and nothing
        marked, and the update takes task and EC turns alone."""
        if self._res_turns and res_node.id not in marked:
            marked.add(res_node.id)
            node_queue.append((res_node, res_node.task))

    def _update_res_outgoing_arcs(self, res_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:1094-1111."""
        for arc in list(res_node.outgoing.values()):
            if arc.dst_node.resource_id == 0:
                self._update_res_to_sink_arc(res_node)
                continue
            cost = self.cost_model.resource_node_to_resource_node_cost(
                res_node.resource_descriptor, arc.dst_node.resource_descriptor
            )
            self.cm.change_arc_cost(arc, cost, ChangeType.CHG_ARC_BETWEEN_RES, "UpdateResOutgoingArcs")
            self._queue_res_turn(arc.dst_node, node_queue, marked)

    def _update_res_to_sink_arc(self, res_node: Node) -> None:
        """Reference: graph_manager.go:1116-1129."""
        if res_node.type != NodeType.PU:
            raise ValueError("only PU nodes connect to the sink")
        arc = self.cm.graph.get_arc(res_node, self.sink_node)
        cost = self.cost_model.leaf_resource_node_to_sink_cost(res_node.resource_id)
        if arc is None:
            self.cm.add_arc(
                res_node, self.sink_node, 0, self.max_tasks_per_pu, cost, ArcType.OTHER,
                ChangeType.ADD_ARC_RES_TO_SINK, "UpdateResToSinkArc",
            )
        else:
            self.cm.change_arc_cost(arc, cost, ChangeType.CHG_ARC_RES_TO_SINK, "UpdateResToSinkArc")

    # -- task arcs ---------------------------------------------------------

    def _update_running_task_node(
        self,
        task_node: Node,
        update_preferences: bool,
        node_queue: Optional[Deque],
        marked: Optional[Set[int]],
    ) -> None:
        """Reference: graph_manager.go:1140-1158."""
        task_id = task_node.task.uid
        running_arc = self.task_to_running_arc.get(task_id)
        assert running_arc is not None, f"no running arc for task {task_id}"
        new_cost = self.cost_model.task_continuation_cost(task_id)
        self.cm.change_arc_cost(
            running_arc, new_cost, ChangeType.CHG_ARC_RUNNING_TASK, "UpdateRunningTaskNode: continuation cost"
        )
        if not self.preemption:
            return
        self._update_running_task_to_unscheduled_agg_arc(task_node)
        if update_preferences:
            self._update_task_to_res_arcs(task_node, node_queue, marked)
            self._update_task_to_equiv_arcs(task_node, node_queue, marked)

    def _update_running_task_to_unscheduled_agg_arc(self, task_node: Node) -> None:
        """Reference: graph_manager.go:1164-1181 (preemption-only)."""
        assert self.preemption, "running task has no unsched arc without preemption"
        unsched = self.job_unsched_to_node[task_node.job_id]
        arc = self.cm.graph.get_arc(task_node, unsched)
        assert arc is not None, "running task must keep its unsched arc under preemption"
        cost = self.cost_model.task_preemption_cost(task_node.task.uid)
        self.cm.change_arc_cost(arc, cost, ChangeType.CHG_ARC_TO_UNSCHED, "UpdateRunningTaskToUnscheduledAggArc")

    def _update_task_to_equiv_arcs(self, task_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:1197-1226."""
        pref_ecs = self.cost_model.get_task_equiv_classes(task_node.task.uid)
        if not pref_ecs:
            self._remove_invalid_ec_pref_arcs(task_node, pref_ecs, ChangeType.DEL_ARC_TASK_TO_EQUIV_CLASS)
            return
        for pref_ec in pref_ecs:
            pref_node = self.task_ec_to_node.get(pref_ec)
            if pref_node is None:
                pref_node = self._add_equiv_class_node(pref_ec)
            self._ec_pointed_at.add(pref_ec)
            cost = self.cost_model.task_to_equiv_class_aggregator(task_node.task.uid, pref_ec)
            arc = self.cm.graph.get_arc(task_node, pref_node)
            if arc is None:
                self.cm.add_arc(
                    task_node, pref_node, 0, 1, cost, ArcType.OTHER,
                    ChangeType.ADD_ARC_TASK_TO_EQUIV_CLASS, "UpdateTaskToEquivArcs",
                )
                if pref_ec != CLUSTER_AGGREGATOR_EC:
                    self._pref_arcs(1)
            else:
                self.cm.change_arc(
                    arc, arc.cap_lower, arc.cap_upper, cost,
                    ChangeType.CHG_ARC_TASK_TO_EQUIV_CLASS, "UpdateTaskToEquivArcs",
                )
            if pref_node.id not in marked:
                marked.add(pref_node.id)
                node_queue.append((pref_node, pref_node.task))
        self._remove_invalid_ec_pref_arcs(task_node, pref_ecs, ChangeType.DEL_ARC_TASK_TO_EQUIV_CLASS)

    def _update_task_to_res_arcs(self, task_node: Node, node_queue: Deque, marked: Set[int]) -> None:
        """Reference: graph_manager.go:1229-1264."""
        pref_rids = self.cost_model.get_task_preference_arcs(task_node.task.uid)
        if not pref_rids:
            self._remove_invalid_pref_res_arcs(task_node, pref_rids, ChangeType.DEL_ARC_TASK_TO_RES)
            return
        for pref_rid in pref_rids:
            pref_node = self.resource_to_node.get(pref_rid)
            assert pref_node is not None, "cost model preferred an unknown resource"
            cost = self.cost_model.task_to_resource_node_cost(task_node.task.uid, pref_rid)
            arc = self.cm.graph.get_arc(task_node, pref_node)
            if arc is None:
                self.cm.add_arc(
                    task_node, pref_node, 0, 1, cost, ArcType.OTHER,
                    ChangeType.ADD_ARC_TASK_TO_RES, "UpdateTaskToResArcs",
                )
                self._pref_arcs(1)
            elif arc.type != ArcType.RUNNING:
                # Running arcs are priced by TaskContinuationCost elsewhere.
                self.cm.change_arc_cost(arc, cost, ChangeType.CHG_ARC_TASK_TO_RES, "UpdateTaskToResArcs")
            self._queue_res_turn(pref_node, node_queue, marked)
        self._remove_invalid_pref_res_arcs(task_node, pref_rids, ChangeType.DEL_ARC_TASK_TO_RES)

    def _update_task_to_unscheduled_agg_arc(self, task_node: Node) -> Node:
        """Reference: graph_manager.go:1270-1285."""
        unsched = self.job_unsched_to_node.get(task_node.job_id)
        if unsched is None:
            unsched = self._add_unscheduled_agg_node(task_node.job_id)
        cost = self.cost_model.task_to_unscheduled_agg_cost(task_node.task.uid)
        arc = self.cm.graph.get_arc(task_node, unsched)
        if arc is None:
            self.cm.add_arc(
                task_node, unsched, 0, 1, cost, ArcType.OTHER,
                ChangeType.ADD_ARC_TO_UNSCHED, "UpdateTaskToUnscheduledAggArc",
            )
        else:
            self.cm.change_arc_cost(arc, cost, ChangeType.CHG_ARC_TO_UNSCHED, "UpdateTaskToUnscheduledAggArc")
        return unsched

    def _update_unscheduled_agg_node(self, unsched: Node, cap_delta: int) -> None:
        """Reference: graph_manager.go:1291-1305."""
        arc = self.cm.graph.get_arc(unsched, self.sink_node)
        cost = self.cost_model.unscheduled_agg_to_sink_cost(unsched.job_id)
        if arc is not None:
            self.cm.change_arc(
                arc, arc.cap_lower, arc.cap_upper + cap_delta, cost,
                ChangeType.CHG_ARC_FROM_UNSCHED, "UpdateUnscheduledAggNode",
            )
            return
        assert cap_delta >= 1, f"first capacity delta must be >=1, got {cap_delta}"
        self.cm.add_arc(
            unsched, self.sink_node, 0, cap_delta, cost, ArcType.OTHER,
            ChangeType.ADD_ARC_FROM_UNSCHED, "UpdateUnscheduledAggNode",
        )

    # -- preference pruning ------------------------------------------------

    def _remove_invalid_ec_pref_arcs(self, node: Node, pref_ecs: List[int], change_type: ChangeType) -> int:
        """Reference: graph_manager.go:732-760. Returns how many it pruned."""
        pref = set(pref_ecs)
        to_delete = [
            arc
            for arc in node.outgoing.values()
            if arc.dst_node.equiv_class is not None and arc.dst_node.equiv_class not in pref
        ]
        if to_delete and node.is_task_node:
            self._pref_arcs(-sum(1 for arc in to_delete if _is_pref_arc(arc)))
        for arc in to_delete:
            self.cm.delete_arc(arc, change_type, "RemoveInvalidECPrefArcs")
        return len(to_delete)

    def _remove_invalid_pref_res_arcs(self, node: Node, pref_rids: List[int], change_type: ChangeType) -> int:
        """Reference: graph_manager.go:766-790 — prunes arcs to resources
        no longer preferred, skipping running arcs is NOT done there; the
        running arc always points at the bound resource which the cost
        model keeps in its preference lists when relevant. Returns how
        many it pruned."""
        pref = set(pref_rids)
        to_delete = [
            arc
            for arc in node.outgoing.values()
            if arc.dst_node.resource_id != 0 and arc.dst_node.resource_id not in pref
        ]
        if to_delete and node.is_task_node:
            self._pref_arcs(-sum(1 for arc in to_delete if _is_pref_arc(arc)))
        for arc in to_delete:
            self.cm.delete_arc(arc, change_type, "RemoveInvalidPrefResArcs")
        return len(to_delete)

    # -- scheduled-task arc handling ---------------------------------------

    def _update_arcs_for_scheduled_task(self, task_node: Node, res_node: Node) -> None:
        """Reference: graph_manager.go:855-888."""
        if not self.preemption:
            self._pin_task_to_node(task_node, res_node)
            return
        task_id = task_node.task.uid
        new_cost = self.cost_model.task_continuation_cost(task_id)
        running_arc = self.task_to_running_arc.get(task_id)
        if running_arc is None:
            # A preference arc to the chosen resource doubles as the
            # running arc (the graph doesn't support multi-arcs; reference
            # note at graph_manager.go:869-872).
            running_arc = self.cm.graph.get_arc(task_node, res_node)
            if running_arc is not None and _is_pref_arc(running_arc):
                self._pref_arcs(-1)
        if running_arc is not None:
            running_arc.type = ArcType.RUNNING
            self.cm.change_arc(running_arc, 0, 1, new_cost, ChangeType.CHG_ARC_RUNNING_TASK,
                               "UpdateArcsForScheduledTask: transform to running arc")
            self.task_to_running_arc[task_id] = running_arc
            self._update_running_task_to_unscheduled_agg_arc(task_node)
            return
        running_arc = self.cm.add_arc(
            task_node, res_node, 0, 1, new_cost, ArcType.RUNNING,
            ChangeType.ADD_ARC_RUNNING_TASK, "UpdateArcsForScheduledTask: add running arc",
        )
        assert task_id not in self.task_to_running_arc
        self.task_to_running_arc[task_id] = running_arc
        self._update_running_task_to_unscheduled_agg_arc(task_node)

    def _pin_task_to_node(self, task_node: Node, res_node: Node) -> None:
        """Preemption-off path: delete all non-chosen arcs, keep/create one
        running arc with lower bound 1 (reference: graph_manager.go:675-720)."""
        added_running_arc = False
        task_id = task_node.task.uid
        # every arc of the task's own goes, or becomes its running arc
        if self.pref_arcs_live:
            self._pref_arcs(-sum(1 for arc in task_node.outgoing.values() if _is_pref_arc(arc)))
        for arc in list(task_node.outgoing.values()):
            if arc.dst != res_node.id:
                self.cm.delete_arc(arc, ChangeType.DEL_ARC_TASK_TO_EQUIV_CLASS, "PinTaskToNode")
                continue
            added_running_arc = True
            new_cost = self.cost_model.task_continuation_cost(task_id)
            arc.type = ArcType.RUNNING
            self.cm.change_arc(arc, 1, 1, new_cost, ChangeType.CHG_ARC_RUNNING_TASK,
                               "PinTaskToNode: transform to running arc")
            assert task_id not in self.task_to_running_arc
            self.task_to_running_arc[task_id] = arc
        self._update_unscheduled_agg_node(self.job_unsched_to_node[task_node.job_id], -1)
        if not added_running_arc:
            new_cost = self.cost_model.task_continuation_cost(task_id)
            arc = self.cm.add_arc(
                task_node, res_node, 1, 1, new_cost, ArcType.RUNNING,
                ChangeType.ADD_ARC_RUNNING_TASK, "PinTaskToNode: add running arc",
            )
            assert task_id not in self.task_to_running_arc
            self.task_to_running_arc[task_id] = arc
        self._set_pinned(task_node, True)
        self.unpinned_task_nodes.discard(task_node.id)
        if self._tasks_inert:
            # one arc that nothing re-prices: off the work list until
            # the task is evicted
            jid = task_node.job_id
            if self._worklist[jid].pop(task_id, None) is not None:
                self._pinned_unlisted[jid] = self._pinned_unlisted.get(jid, 0) + 1
