"""L6: the flow scheduler service — the event-driven round loop.

Reference: scheduling/flow/flowscheduler/{interface.go,scheduler.go}.
Same event surface: AddJob, Register/DeregisterResource, ScheduleAllJobs/
ScheduleJobs, HandleTask{Completion,Placement,Eviction,Migration,Failure},
HandleJobCompletion, KillRunningTask, GetTaskBindings. A scheduling round
is: compute topology statistics → add/update job nodes → solve → deltas
(PREEMPT first, then PLACE/MIGRATE) → apply → refresh topology.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from ..costmodels.base import CostModeler
from ..costmodels.trivial import TrivialCostModel
from ..data import (
    DeltaType,
    JobDescriptor,
    JobState,
    ResourceDescriptor,
    ResourceState,
    ResourceTopologyNodeDescriptor,
    ResourceType,
    SchedulingDelta,
    TaskDescriptor,
    TaskState,
)
from ..graph.changes import ChangeStats
from ..graph.graph_manager import GraphManager, TaskMapping
from ..obs.spans import span, start_span
from ..solver.base import FlowSolver
from ..solver.cpu_ref import ReferenceSolver
from ..solver.placement import PlacementSolver
from ..utils import JobMap, ResourceMap, TaskMap, job_id_from_string, resource_id_from_string


#: the states _compute_runnable_tasks_for_job promotes to RUNNABLE
_PROMOTED_STATES = (TaskState.CREATED, TaskState.BLOCKING)
#: the states of a job's root under which its tree is looked at at all
_WALKED_ROOT_STATES = (
    TaskState.CREATED,
    TaskState.RUNNING,
    TaskState.RUNNABLE,
    TaskState.COMPLETED,
)


@dataclass
class _MetJob:
    """What the scheduler keeps of a job whose tree it has walked."""

    #: uid -> place in the tree (the index in each `spawned` list from
    #: the root down, () for the root) of every descriptor seen there:
    #: a new child's path is its parent's and its own index
    paths: Dict[int, tuple]
    #: the root's children the walk and the events account for
    root_children: int
    #: (path, descriptor) of the children added since the last scan
    arrived: List[tuple] = field(default_factory=list)
    #: `arrived` stands in the order the walk would meet its tasks: the
    #: order of arrival, while every one is a child of the root
    in_walk_order: bool = True


def _walk_order(arrival: tuple) -> tuple:
    """Sorts (path, descriptor) as _walk_job_tree meets the tasks: a
    parent's children by index, the parents root first and then, among
    siblings, the last with its whole subtree before the one ahead of
    it (the walk keeps its parents on a stack)."""
    path = arrival[0]
    return tuple(-i for i in path[:-1]), path[-1]


@dataclass
class RoundTiming:
    """Per-phase wall-clock breakdown of one scheduling round (the
    reference only times the whole round ad hoc in its CLI,
    cmd/k8sscheduler/scheduler.go:146-150; we make phases first-class).

    Every `_s` field is the duration of an obs span (`round` → `stats`,
    `graph_update`, `solve`, `deltas`, `apply`), so the RoundRecord
    JSONL (runtime/trace.py) and a captured Perfetto trace are two
    views of the same measurement and can never disagree."""

    stats_s: float = 0.0
    graph_update_s: float = 0.0
    solve_s: float = 0.0
    deltas_s: float = 0.0
    apply_s: float = 0.0
    total_s: float = 0.0
    #: the min-cost objective of the round's solve. In a round that
    #: re-fits the slot plan `solver.last_result` is the later solve's
    #: (it pairs with `solver.state.problem()`); this stays the round's
    objective: int = 0
    #: what `graph_update` did: task nodes updated, and pinned tasks of
    #: the same jobs left alone (GraphManager.add_or_update_job_nodes)
    graph_tasks_visited: int = 0
    graph_tasks_skipped: int = 0
    #: descriptors the scan for runnable tasks looked at since the last
    #: round said so (`runnable_scan`, and a restore's walk before it):
    #: the children added to trees the scheduler had walked, and every
    #: descriptor of a tree it walked (_compute_runnable_tasks_for_job)
    runnable_tasks_scanned: int = 0
    #: the resource half of the same update: resource nodes that took a
    #: turn, and arcs out of them it added or whose price really
    #: changed (GraphManager.res_nodes_visited, res_arcs_changed)
    res_nodes_visited: int = 0
    res_arcs_changed: int = 0
    #: what `stats` did: PUs whose running-task lists changed since the
    #: last pass, resource nodes it prepared, 1 if it walked every
    #: node, and the children its nodes iterated to gather from (a
    #: patched pass: one for each dirty PU and every outgoing arc of
    #: each ancestor, so a coordinator re-reads all its machines for one
    #: dirty path; GraphManager.compute_topology_statistics)
    stats_pus_dirty: int = 0
    stats_nodes_visited: int = 0
    stats_full_walk: int = 0
    stats_children_gathered: int = 0
    #: what `apply`'s refresh of the resource tree did: PUs whose lists
    #: changed since the last refresh, resource nodes it visited, and 1
    #: if it walked every node (GraphManager.refresh_resource_topology)
    apply_pus_dirty: int = 0
    apply_nodes_visited: int = 0
    apply_full_walk: int = 0
    #: what the post-solve half worked on: unpinned task nodes handed
    #: to `decode`, and pinned tasks it left alone (their arcs dropped
    #: by a mask)
    decode_tasks: int = 0
    decode_pinned_skipped: int = 0
    #: what the device-resident export shipped (0 without a mirror):
    #: exact host-to-device bytes of the round (problem plus plan),
    #: 1 if the arrays or the plan went up whole, and the plan regions
    #: relocated since the previous round (DeviceResidentState)
    upload_bytes: int = 0
    upload_full: int = 0
    plan_relocations: int = 0
    #: exact bytes the round's solves moved, where the scan-CSR solver
    #: is the configured rung (zeros elsewhere): up, the export's
    #: `upload_bytes` plus what the solver itself handed to
    #: `jnp.asarray` (problem arrays, warm flow, eps, the plan's
    #: re-ship; the eps scalar alone over a device-resident problem);
    #: down, the attempts' scalars, the telemetry ring and the flow
    #: (JaxSolver.last_h2d_bytes / last_d2h_bytes)
    solve_h2d_bytes: int = 0
    solve_d2h_bytes: int = 0
    #: the slot plan of a scan-CSR service (graph/slot_plan.py; zeros
    #: without one): its rows at the solve (`entry_cap`: what a
    #: superstep pays for) and those in use; 1 if the round re-fitted
    #: it to a smaller bucket, 1 if a rebuild of the round raised
    #: `entry_cap`, and every re-layout of the round, whatever asked
    #: for it (overflow, growth, re-fit)
    plan_rows: int = 0
    plan_rows_live: int = 0
    plan_refits: int = 0
    plan_regrowths: int = 0
    plan_relayouts: int = 0
    #: records of the change journal the round's export applied to the
    #: flat arrays (0 when it built them whole; PlacementSolver)
    journal_changes: int = 0
    #: units of folded supply the export held routed leaf -> sink when
    #: it made the round's problem: the pinned pods whose PU has no
    #: other way out (DeviceGraphState.supply_prerouted; 0 under
    #: preemption, which pins nothing)
    supply_prerouted: int = 0
    #: EC nodes the round's purge removed, after `apply`
    #: (GraphManager.purge_unconnected_equiv_class_nodes)
    ec_purged: int = 0
    #: equivalence classes in the graph after `graph_update`: EC nodes
    #: live, their arcs to resources live, and those arcs added, removed
    #: or re-priced this round (GraphManager.ec_arcs_changed)
    ec_nodes: int = 0
    ec_arcs: int = 0
    ec_arcs_changed: int = 0
    #: EC -> resource arcs whose cost and capacity `graph_update` wrote,
    #: changed or not (GraphManager.ec_arcs_repriced: what a model whose
    #: ECs sweep every machine every round pays for), and machines whose
    #: class census `stats` gathered again
    #: (CostModeler.take_census_machines_dirty; 0 for a model without one)
    ec_arcs_repriced: int = 0
    census_machines_dirty: int = 0
    #: under a model that keeps books of what its machines have reserved
    #: (CostModeler.round_books; zeros elsewhere), at the solve: machines
    #: whose books a bind or an unbind moved since the last round read
    #: them, machines that some size class cannot use (holes in the dense
    #: problem's rows), and the sum over machines of what each takes this
    #: round
    books_machines_dirty: int = 0
    machines_gated: int = 0
    columns_offered: int = 0
    #: the dense problem of a round the collapse answered (zeros on any
    #: other rung; PlacementSolver.collapse_shape): the tasks its rows
    #: pass grouped, the rows they made, and the transport's padded
    #: columns
    audit_tasks_grouped: int = 0
    collapse_rows: int = 0
    collapse_cols: int = 0
    #: EC -> EC arcs `graph_update` added, removed or gave another
    #: capacity or cost this round (GraphManager.ec_chain_arcs_changed);
    #: 1 if the cost model left its allotment for the per-pod predicate
    #: in this round (CostModeler.spread_fallback: a zone short of room)
    ec_chain_arcs_changed: int = 0
    spread_fallback: int = 0
    #: runnable tasks the round left unplaced while the cluster had a
    #: free slot for each: what a placement rule (or a cost above the
    #: unscheduled cost) kept out, not a full cluster
    unscheduled_by_rule: int = 0
    #: running tasks the round's solve could preempt or migrate: those
    #: whose running arc is no pin (GraphManager.unpinned_running_tasks
    #: at the solve: every running task under preemption, none without)
    tasks_unpinned: int = 0
    #: tasks' own preference arcs (to a machine, to an EC other than the
    #: cluster aggregator): in the graph at the solve, and those the
    #: round added or removed (its update's adds and prunes, its pins)
    pref_arcs_live: int = 0
    pref_arcs_changed: int = 0
    #: under a model that places by data locality
    #: (CostModeler.round_locality; zeros elsewhere): the tasks the
    #: round bound whose cheapest route to their machine was a machine
    #: arc, a rack arc, the cluster aggregator; of those tasks the share
    #: bound through an arc of their own, and the share of their input
    #: bytes that lies on another machine than the one they were bound
    #: to (the policy's own objective), both in percent
    bound_via_machine: int = 0
    bound_via_rack: int = 0
    bound_via_cluster: int = 0
    bound_on_preferred_share: float = 0.0
    remote_bytes_share: float = 0.0


class FlowScheduler:
    def __init__(
        self,
        resource_map: ResourceMap,
        job_map: JobMap,
        task_map: TaskMap,
        root: ResourceTopologyNodeDescriptor,
        max_tasks_per_pu: int = 1,
        cost_model: Optional[CostModeler] = None,
        cost_model_factory=None,
        backend: Optional[FlowSolver] = None,
        preemption: bool = False,
        device_resident: bool = False,
    ) -> None:
        self.resource_map = resource_map
        self.job_map = job_map
        self.task_map = task_map
        self.resource_topology = root

        leaf_resource_ids: Set[int] = set()
        self.dimacs_stats = ChangeStats()
        if cost_model is None and cost_model_factory is not None:
            # Every model shares the Trivial constructor signature; the
            # factory form exists because leaf_resource_ids is owned here.
            cost_model = cost_model_factory(
                resource_map, task_map, leaf_resource_ids, max_tasks_per_pu
            )
        self.cost_model = cost_model or TrivialCostModel(
            resource_map, task_map, leaf_resource_ids, max_tasks_per_pu
        )
        if self.cost_model.needs_preemption and not preemption:
            raise ValueError(
                f"{type(self.cost_model).__name__} prices preemption: it is served "
                "only with preemption on (--preemption)"
            )
        self.gm = GraphManager(
            self.cost_model,
            leaf_resource_ids,
            self.dimacs_stats,
            max_tasks_per_pu,
            preemption=preemption,
        )
        self.gm.add_resource_topology(root)
        self.solver = PlacementSolver(
            self.gm,
            backend or ReferenceSolver(),
            device_resident=device_resident,
        )

        self.resource_roots: Set[int] = set()  # ids of registered topology roots
        self._root_rtnds: Dict[int, ResourceTopologyNodeDescriptor] = {}
        # The coordinator root registered above IS a topology root: the
        # per-iteration UpdateResourceTopology pass (reference
        # flowscheduler/scheduler.go:371-375) walks the roots to refresh
        # num_slots_below/num_running_tasks_below — without this entry
        # the refresh walks nothing and running-task stats never update.
        root_rid = resource_id_from_string(root.resource_desc.uuid)
        self.resource_roots.add(root_rid)
        self._root_rtnds[root_rid] = root
        self.task_bindings: Dict[int, int] = {}
        self.resource_bindings: Dict[int, Set[int]] = {}
        #: ids of the tasks whose entry in task_bindings was set or
        #: deleted since take_changed_bindings last emptied this, oldest
        #: change first (a dict as an ordered set: a task touched again
        #: moves to the end, as its re-insertion does in task_bindings)
        self._bindings_changed: Dict[int, None] = {}
        self.jobs_to_schedule: Dict[int, JobDescriptor] = {}
        self.runnable_tasks: Dict[int, Set[int]] = {}
        #: job id -> the jobs whose trees the scheduler has walked; what
        #: is added to one after that comes through add_task
        self._met_jobs: Dict[int, _MetJob] = {}
        #: descriptors looked at for promotion since a round last took
        #: the count (RoundTiming.runnable_tasks_scanned)
        self._tasks_scanned = 0
        self.last_timing = RoundTiming()
        #: the slot plan's (refits, regrowths, layout_rebuilds) as the
        #: last round's record saw them (_note_plan)
        self._plan_seen = (0, 0, 0)
        #: PU resource id -> tasks that finished, failed or were killed
        #: there since the last `deltas` phase. They stay in the PU's
        #: current_running_tasks until that phase drops them, so the
        #: round in between still counts their slots as taken (`stats`
        #: and the capacity arcs read the list's length): the reference
        #: rebuilds the list once a round, at that point.
        self._departed: Dict[int, Set[int]] = {}
        #: free slots under the root as the round's `stats` phase counted
        #: them: what the solve could hand out
        self._free_slots_at_solve = 0
        #: pipelined-round state: (solver token, timing, round span)
        #: while a dispatched solve is in flight, else None
        self._round_in_flight = None

    # ------------------------------------------------------------------
    # Event API
    # ------------------------------------------------------------------

    def get_task_bindings(self) -> Dict[int, int]:
        return self.task_bindings

    def take_changed_bindings(self) -> Dict[int, None]:
        """Hand over, and forget, the ids of the tasks whose binding was
        set or deleted since the last call, in the order of each task's
        last change: the bound ones among them stand in task_bindings in
        this order. A task bound back where it was is in it too. For ONE
        consumer (cli.SchedulerService._collect_bindings), which emits
        from it what a diff of all of task_bindings would; a scheduler
        nobody collects from keeps one key for every task it ever bound."""
        changed, self._bindings_changed = self._bindings_changed, {}
        return changed

    def _note_binding_changed(self, task_id: int) -> None:
        changed = self._bindings_changed
        changed.pop(task_id, None)  # a task touched again moves to the end
        changed[task_id] = None

    def add_job(self, jd: JobDescriptor) -> None:
        """Offer a job. One the scheduler has not met is walked from
        its root by the next scan; a tree it has walked grows through
        add_task."""
        self.jobs_to_schedule[job_id_from_string(jd.uuid)] = jd

    def add_task(self, parent: TaskDescriptor, td: TaskDescriptor) -> None:
        """``td`` becomes the last child of ``parent``. Whoever grows
        the tree of a job that was offered does it here: under a tree
        the scheduler has walked the child is recorded with its path,
        and the next scan looks at what was recorded, not at the tree.
        (A job it has not met yet needs no record: its walk is still to
        come.) A parent that walk never saw means the tree grew behind
        the scheduler's back: the job is walked again."""
        parent.spawned.append(td)
        job_id = job_id_from_string(td.job_id)
        met = self._met_jobs.get(job_id)
        if met is None:
            return
        path = met.paths.get(parent.uid)
        if path is None:
            del self._met_jobs[job_id]
            return
        if path:
            met.in_walk_order = False
        else:
            met.root_children += 1
        path += (len(parent.spawned) - 1,)
        met.paths[td.uid] = path
        met.arrived.append((path, td))

    def handle_job_completion(self, job_id: int) -> None:
        """Reference: flowscheduler/scheduler.go:93-104."""
        self._check_not_in_flight("handle_job_completion")
        self.gm.job_completed(job_id)
        jd = self.job_map.find(job_id)
        assert jd is not None, f"job {job_id} must exist"
        self.jobs_to_schedule.pop(job_id, None)
        self.runnable_tasks.pop(job_id, None)
        self._met_jobs.pop(job_id, None)
        jd.state = JobState.COMPLETED

    def handle_task_completion(self, td: TaskDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:106-132."""
        self._check_not_in_flight("handle_task_completion")
        rid = self.task_bindings.get(td.uid)
        assert rid is not None, f"task {td.uid} must be bound to a resource"
        if not self._unbind_task_from_resource(td, rid, departed=True):
            raise RuntimeError(f"could not unbind task {td.uid} from resource {rid}")
        td.state = TaskState.COMPLETED
        self.cost_model.record_task_completion(td)
        self.gm.task_completed(td.uid)

    def register_resource(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:134-160."""
        stack = [rtnd]
        while stack:
            cur = stack.pop()
            rd = cur.resource_desc
            if rd.type == ResourceType.PU:
                rd.schedulable = True
                if rd.state == ResourceState.UNKNOWN:
                    rd.state = ResourceState.IDLE
            stack.extend(cur.children)
        self.gm.add_resource_topology(rtnd)
        rid = resource_id_from_string(rtnd.resource_desc.uuid)
        if rtnd.parent_id == "":
            self.resource_roots.add(rid)
            self._root_rtnds[rid] = rtnd

    def deregister_resource(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:162-210."""
        self._check_not_in_flight("deregister_resource")
        self._dfs_evict_tasks(rtnd)
        self.gm.remove_resource_topology(rtnd.resource_desc)
        rid = resource_id_from_string(rtnd.resource_desc.uuid)
        self.resource_roots.discard(rid)
        self._root_rtnds.pop(rid, None)
        self._dfs_clean_up_resource(rtnd)
        if rtnd.parent_id:
            parent_rs = self.resource_map.find(resource_id_from_string(rtnd.parent_id))
            assert parent_rs is not None, f"parent of {rtnd.resource_desc.uuid} must exist"
            parent_node = parent_rs.topology_node
            parent_node.children = [
                c for c in parent_node.children if c.resource_desc.uuid != rtnd.resource_desc.uuid
            ]

    def handle_task_placement(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:212-229.

        Fenced like the other placement-mutating events: an external
        placement while a pipelined round is in flight would bind a
        task the dispatched snapshot still maps as schedulable. The
        internal caller (delta application) runs after the latch
        clears."""
        self._check_not_in_flight("handle_task_placement")
        self._handle_task_placement(td, rd)

    def _handle_task_placement(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        td.scheduled_to_resource = rd.uuid
        self.gm.task_scheduled(td.uid, resource_id_from_string(rd.uuid))
        self._bind_task_to_resource(td, rd)
        runnables = self.runnable_tasks.get(job_id_from_string(td.job_id))
        if runnables is not None:
            runnables.discard(td.uid)
        self._execute_task(td, rd)

    def handle_task_eviction(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:231-246.

        Externally driven evictions are fenced like the other
        placement-mutating events: an eviction during an in-flight
        pipelined round would unbind a task the dispatched snapshot
        still maps, letting _finish_round decode a stale PLACE for it.
        Internal callers (delta application, deregister's evict-DFS)
        run after the latch clears and use _evict_task directly."""
        self._check_not_in_flight("handle_task_eviction")
        self._evict_task(td, rd)

    def _evict_task(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        rid = resource_id_from_string(rd.uuid)
        self.gm.task_evicted(td.uid, rid)
        if not self._unbind_task_from_resource(td, rid):
            raise RuntimeError(f"could not unbind task {td.uid} from resource {rid}")
        td.state = TaskState.RUNNABLE
        self._insert_task_into_runnables(job_id_from_string(td.job_id), td.uid)

    def handle_task_migration(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:248-270. Fenced while
        a pipelined round is in flight (see handle_task_placement);
        delta application uses _handle_task_migration after the latch
        clears."""
        self._check_not_in_flight("handle_task_migration")
        self._handle_task_migration(td, rd)

    def _handle_task_migration(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        old_rid = self.task_bindings[td.uid]
        new_rid = resource_id_from_string(rd.uuid)
        # scheduledToResource must be up to date before TaskMigrated
        # (reference hack note at :254-259).
        td.scheduled_to_resource = rd.uuid
        self.gm.task_migrated(td.uid, old_rid, new_rid)
        rd.state = ResourceState.BUSY
        td.state = TaskState.RUNNING
        if not self._unbind_task_from_resource(td, old_rid):
            raise RuntimeError(f"binding {td.uid}->{old_rid} must exist")
        self._bind_task_to_resource(td, rd)

    def handle_task_failure(self, td: TaskDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:272-287."""
        self._check_not_in_flight("handle_task_failure")
        self.gm.task_failed(td.uid)
        rid = self.task_bindings.get(td.uid)
        assert rid is not None, f"failed task {td.uid} should have been bound"
        self._unbind_task_from_resource(td, rid, departed=True)
        td.state = TaskState.FAILED

    def kill_running_task(self, task_id: int) -> None:
        """Reference: flowscheduler/scheduler.go:289-306."""
        self._check_not_in_flight("kill_running_task")
        self.gm.task_killed(task_id)
        td = self.task_map.find(task_id)
        assert td is not None, f"unknown task {task_id}"
        if td.state != TaskState.RUNNING or task_id not in self.task_bindings:
            raise RuntimeError(f"task {task_id} not bound or not running")
        # it keeps its binding (reference: scheduler.go:289-306) and
        # leaves its PU's list like a finished task
        self._departed.setdefault(self.task_bindings[task_id], set()).add(task_id)
        td.state = TaskState.ABORTED

    # ------------------------------------------------------------------
    # The scheduling round
    # ------------------------------------------------------------------

    def schedule_all_jobs(self):
        """Reference: flowscheduler/scheduler.go:309-318."""
        return self.schedule_jobs(self._runnable_jobs())

    def _runnable_jobs(self):
        """The jobs with at least one runnable task, before `round`
        opens: what was added to each job since the last scan is
        promoted, and a job met for the first time is walked whole."""
        with span("runnable_scan") as sp:
            jds = [
                jd for jd in self.jobs_to_schedule.values()
                if len(self._compute_runnable_tasks_for_job(jd)) > 0
            ]
            sp.set("jobs", len(jds))
            sp.set("runnable_tasks_scanned", self._tasks_scanned)
        return jds

    def _take_tasks_scanned(self) -> int:
        scanned, self._tasks_scanned = self._tasks_scanned, 0
        return scanned

    # ------------------------------------------------------------------
    # Pipelined rounds: dispatch the solve, overlap host work, finish
    # ------------------------------------------------------------------

    def schedule_all_jobs_async(self):
        """Phase 1 of a pipelined round: stats refresh + graph update +
        solve DISPATCH; returns before the solve completes. While the
        round is in flight the caller may keep ADDING jobs and tasks —
        their graph mutations journal for the next round, mirroring the
        reference's pod batching (k8sclient/client.go:153-193) which
        accumulates arrivals while the solver subprocess crunches.
        Events that mutate existing placements (completion, failure,
        kill, deregister) raise until finish_scheduling() applies the
        in-flight round's deltas. Returns None when no job has runnable
        tasks (nothing dispatched; finish_scheduling must not be
        called)."""
        if self._round_in_flight is not None:
            raise RuntimeError("a scheduling round is already in flight")
        jds = self._runnable_jobs()
        if not jds:
            return None
        timing, round_span = self._begin_round(jds)
        try:
            with span("solve_dispatch") as sp:
                token = self.solver.solve_async()
            timing.solve_s = sp.dur_s  # dispatch only
            self._note_export(timing)
        except BaseException:
            round_span.__exit__(*sys.exc_info())
            raise
        self._round_in_flight = (token, timing, round_span)
        return token

    def finish_scheduling(self):
        """Phase 2: synchronize the solve, apply deltas, close the
        round. Returns (num_scheduled, deltas) like schedule_jobs."""
        if self._round_in_flight is None:
            raise RuntimeError("no scheduling round in flight")
        token, timing, round_span = self._round_in_flight
        try:
            try:
                with span("solve_sync") as sp:
                    task_mappings = self.solver.complete(token)
            finally:
                # the latch must clear even when the solver raises
                # (overflow / non-convergence), or every later event
                # handler would refuse with "in flight" forever — and it
                # must be off before delta application anyway, for the
                # internal placement/eviction handlers
                self._round_in_flight = None
            timing.solve_s += sp.dur_s  # + synchronize
            return self._finish_round(task_mappings, timing, round_span)
        except BaseException:
            round_span.__exit__(*sys.exc_info())
            raise

    def _begin_round(self, jds):
        """The pre-solve half of a round, shared by the synchronous
        and pipelined paths: mutation-counter reset, topology stats
        refresh, and the job/task graph update. Opens the `round` span
        (closed by _finish_round — or here, on an exception)."""
        timing = RoundTiming(runnable_tasks_scanned=self._take_tasks_scanned())
        round_span = start_span("round", jobs=len(jds))
        try:
            # Reset the mutation counters at round START (the reference
            # resets after the round, flowscheduler/scheduler.go:332,
            # which zeroes them before any post-round reader — e.g. the
            # round tracer — can observe the round's mutation counts).
            self.dimacs_stats.reset()
            with span("stats") as sp:
                self.gm.compute_topology_statistics(self.gm.sink_node)
                timing.stats_pus_dirty = self.gm.stats_pus_dirty
                timing.stats_nodes_visited = self.gm.stats_nodes_visited
                timing.stats_full_walk = self.gm.stats_full_walk
                timing.stats_children_gathered = self.gm.stats_children_gathered
                timing.census_machines_dirty = self.cost_model.take_census_machines_dirty()
                sp.set("stats_pus_dirty", timing.stats_pus_dirty)
                sp.set("stats_nodes_visited", timing.stats_nodes_visited)
                sp.set("stats_full_walk", timing.stats_full_walk)
                sp.set("stats_children_gathered", timing.stats_children_gathered)
                sp.set("census_machines_dirty", timing.census_machines_dirty)
            timing.stats_s = sp.dur_s
            self._free_slots_at_solve = self._free_slots()
            with span("graph_update") as sp:
                self.gm.add_or_update_job_nodes(jds)
                timing.graph_tasks_visited = self.gm.tasks_visited
                timing.graph_tasks_skipped = self.gm.tasks_skipped
                timing.res_nodes_visited = self.gm.res_nodes_visited
                timing.res_arcs_changed = self.gm.res_arcs_changed
                sp.set("graph_tasks_visited", timing.graph_tasks_visited)
                sp.set("graph_tasks_skipped", timing.graph_tasks_skipped)
                sp.set("res_nodes_visited", timing.res_nodes_visited)
                sp.set("res_arcs_changed", timing.res_arcs_changed)
                ec_nodes = self.gm.task_ec_to_node.values()
                timing.ec_nodes = len(ec_nodes)
                timing.ec_arcs = sum(len(node.outgoing) for node in ec_nodes)
                timing.ec_arcs_changed = self.gm.ec_arcs_changed
                timing.ec_arcs_repriced = self.gm.ec_arcs_repriced
                timing.ec_chain_arcs_changed = self.gm.ec_chain_arcs_changed
                timing.spread_fallback = self.cost_model.spread_fallback
                sp.set("ec_nodes", timing.ec_nodes)
                sp.set("ec_arcs", timing.ec_arcs)
                sp.set("ec_arcs_changed", timing.ec_arcs_changed)
                sp.set("ec_arcs_repriced", timing.ec_arcs_repriced)
                sp.set("ec_chain_arcs_changed", timing.ec_chain_arcs_changed)
                books = self.cost_model.round_books()
                if books is not None:
                    (
                        timing.books_machines_dirty, timing.machines_gated,
                        timing.columns_offered,
                    ) = books
                    sp.set("books_machines_dirty", timing.books_machines_dirty)
            timing.graph_update_s = sp.dur_s
            timing.tasks_unpinned = self.gm.unpinned_running_tasks
            timing.pref_arcs_live = self.gm.pref_arcs_live
        except BaseException:
            round_span.__exit__(*sys.exc_info())
            raise
        return timing, round_span

    def _free_slots(self) -> int:
        rd = self.resource_topology.resource_desc
        return rd.num_slots_below - rd.num_running_tasks_below

    def _note_export(self, timing: RoundTiming) -> None:
        """What the round's export applied, and what the device-resident
        mirror shipped for it."""
        timing.journal_changes = self.solver.journal_changes
        timing.supply_prerouted = self.solver.state.supply_prerouted
        res = self.solver.resident
        if res is not None:
            timing.upload_bytes = res.last_upload_bytes
            timing.upload_full = int(
                res.last_upload_kind == "full_build"
                or res.last_plan_kind == "rebuild"
            )
            timing.plan_relocations = res.last_plan_relocations
        plan = self.solver.state.plan
        if plan.enabled:
            timing.plan_rows = plan.entry_cap
            timing.plan_rows_live = plan.rows_live

    def _note_solve_bytes(self, timing: RoundTiming, export_bytes: int) -> None:
        """Adds a completed solve's transfers, and the device-resident
        export's that fed it, to the round's."""
        up, down = self.solver.solve_bytes
        timing.solve_h2d_bytes += export_bytes + up
        timing.solve_d2h_bytes += down

    def _refit_plan(self, timing: RoundTiming) -> None:
        """The slot plan re-fits to the graph the round left
        (graph/slot_plan.py): when it would land in a smaller bucket it
        is re-laid out there, and the round's own path runs once on it
        (PlacementSolver.rehearse), because a new `entry_cap` is a new
        solve program and new upload shapes: the round that re-fits
        compiles them, not the next one. No runnable pod waits on that
        graph; what the solve would bind (`would_place`) is dropped."""
        plan = self.solver.state.plan
        graph = self.gm.cm.graph
        if not plan.refit_due(2 * graph.num_arcs):
            return
        with span("plan_refit", rows=plan.entry_cap) as sp:
            plan.refit()
            mapping = self.solver.rehearse() or {}
            sp.set("rows_after", plan.entry_cap)
            sp.set("rows_live", plan.rows_live)
            sp.set("would_place", sum(
                1 for task_node_id in mapping
                if graph.node(task_node_id).task.uid not in self.task_bindings
            ))
        res = self.solver.resident
        if res is not None:
            # the new layout went up whole
            timing.upload_bytes += res.last_upload_bytes
            timing.upload_full = 1
        self._note_solve_bytes(timing, res.last_upload_bytes if res is not None else 0)

    def _note_plan(self, timing: RoundTiming) -> None:
        """What happened to the slot plan's layout since the last round
        said so: this round's export and re-fit, and whatever an event
        between two rounds forced."""
        plan = self.solver.state.plan
        seen = (plan.refits, plan.regrowths, plan.layout_rebuilds)
        timing.plan_refits, timing.plan_regrowths, timing.plan_relayouts = (
            now - before for now, before in zip(seen, self._plan_seen)
        )
        self._plan_seen = seen

    def _finish_round(self, task_mappings, timing, round_span):
        """The post-solve half of a round, shared by the synchronous
        and pipelined paths (so delta decoding / feedback can never
        drift between them): preemption deltas + binding diffs, delta
        application, per-root topology refresh, EC purge, and the
        unscheduled-feedback hook. Closes the `round` span; its
        duration IS timing.total_s."""
        try:
            timing.objective = int(self.solver.last_result.objective)
            timing.decode_tasks = self.solver.decode_tasks
            timing.decode_pinned_skipped = self.solver.decode_pinned_skipped
            self._note_solve_bytes(timing, timing.upload_bytes)
            (
                timing.audit_tasks_grouped, timing.collapse_rows, timing.collapse_cols,
            ) = self.solver.collapse_shape
            with span("deltas") as sp:
                if self.gm.unpinned_running_tasks:
                    # Some running task is not pinned (preemption): the
                    # mapping holds every running task, any of them may
                    # have lost its place, and the reference's walk
                    # finds those: it empties every PU's list and the
                    # loop below refills it from the mapping.
                    with span("preempt_deltas") as psp:
                        deltas = self.gm.scheduling_deltas_for_preempted_tasks(
                            task_mappings, self.resource_map
                        )
                        psp.set("preempted", len(deltas))
                    self._drop_departed(lists_rebuilt=True)
                else:
                    # Every running task is pinned and off the mapping:
                    # none can be preempted, and the lists stand as the
                    # events left them, less what departed.
                    deltas = []
                    self._drop_departed()
                    self.gm.running_tasks_kept_by_events()
                for task_node_id, res_node_id in task_mappings.items():
                    delta = self.gm.node_binding_to_scheduling_delta(
                        task_node_id, res_node_id, self.task_bindings
                    )
                    if delta is not None:
                        deltas.append(delta)
            timing.deltas_s = sp.dur_s

            with span("apply") as sp:
                num_scheduled = self._apply_scheduling_deltas(deltas)
                self.gm.refresh_resource_topology(self._root_rtnds.values())
                timing.apply_pus_dirty = self.gm.apply_pus_dirty
                timing.apply_nodes_visited = self.gm.apply_nodes_visited
                timing.apply_full_walk = self.gm.apply_full_walk
                sp.set("apply_pus_dirty", timing.apply_pus_dirty)
                sp.set("apply_nodes_visited", timing.apply_nodes_visited)
                sp.set("apply_full_walk", timing.apply_full_walk)
            timing.apply_s = sp.dur_s
            with span("ec_purge") as sp:
                self.gm.purge_unconnected_equiv_class_nodes()
                timing.ec_purged = self.gm.ec_purged
                sp.set("ec_purged", timing.ec_purged)
                sp.set("ec_arcs_dropped", self.gm.ec_arcs_dropped)
            # the round's transient arcs are gone: the slot plan re-fits
            # to the graph it holds, and pays for the new program here,
            # before the round's Bindings go out
            self._refit_plan(timing)
            self._note_plan(timing)
            # Policy feedback: which runnable tasks stayed unscheduled
            # (drives e.g. Quincy's wait-cost starvation bound).
            unscheduled = [
                t
                for tasks in self.runnable_tasks.values()
                for t in tasks
                if t not in self.task_bindings
            ]
            timing.pref_arcs_changed = self.gm.take_pref_arcs_moved()
            locality = self.cost_model.round_locality()
            if locality is not None:
                via_machine, via_rack, via_cluster, read, remote = locality
                timing.bound_via_machine = via_machine
                timing.bound_via_rack = via_rack
                timing.bound_via_cluster = via_cluster
                with_input = via_machine + via_rack + via_cluster
                if with_input:
                    timing.bound_on_preferred_share = 100.0 * (via_machine + via_rack) / with_input
                if read:
                    timing.remote_bytes_share = 100.0 * remote / read
            self.cost_model.note_round(unscheduled)
            timing.unscheduled_by_rule = max(
                0, min(len(unscheduled), self._free_slots_at_solve - num_scheduled)
            )
        except BaseException:
            round_span.__exit__(*sys.exc_info())
            raise
        round_span.set("num_scheduled", num_scheduled)
        timing.total_s = round_span.finish()
        self.last_timing = timing
        return num_scheduled, deltas

    def _check_not_in_flight(self, what: str) -> None:
        if self._round_in_flight is not None:
            raise RuntimeError(
                f"{what} while a pipelined scheduling round is in flight; "
                "call finish_scheduling() first (only job/task ADDITIONS "
                "may overlap an in-flight round)"
            )

    def schedule_jobs(self, jds: List[JobDescriptor]):
        """Reference: flowscheduler/scheduler.go:321-338."""
        self._check_not_in_flight("schedule_jobs")
        if not jds:
            self.last_timing = RoundTiming(runnable_tasks_scanned=self._take_tasks_scanned())
            return 0, []
        timing, round_span = self._begin_round(jds)
        try:
            # Reference round body: flowscheduler/scheduler.go:340-375.
            with span("solve") as sp:
                task_mappings = self.solver.solve()
            timing.solve_s = sp.dur_s
            self._note_export(timing)
            return self._finish_round(task_mappings, timing, round_span)
        except BaseException:
            round_span.__exit__(*sys.exc_info())
            raise

    def _apply_scheduling_deltas(self, deltas: List[SchedulingDelta]) -> int:
        """Reference: flowscheduler/scheduler.go:377-412."""
        num_scheduled = 0
        for d in deltas:
            td = self.task_map.find(d.task_id)
            assert td is not None, f"no descriptor for task {d.task_id}"
            rs = self.resource_map.find(resource_id_from_string(d.resource_id))
            assert rs is not None, f"no status for resource {d.resource_id}"
            if d.type == DeltaType.PLACE:
                jd = self.job_map.find(job_id_from_string(td.job_id))
                if jd.state != JobState.RUNNING:
                    jd.state = JobState.RUNNING
                self._handle_task_placement(td, rs.descriptor)
                num_scheduled += 1
            elif d.type == DeltaType.PREEMPT:
                self._evict_task(td, rs.descriptor)
            elif d.type == DeltaType.MIGRATE:
                self._handle_task_migration(td, rs.descriptor)
            elif d.type == DeltaType.NOOP:
                pass
            else:
                raise ValueError(f"unknown delta type {d.type}")
        return num_scheduled

    # ------------------------------------------------------------------
    # Bindings bookkeeping
    # ------------------------------------------------------------------

    def _bind_task_to_resource(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        """Reference: flowscheduler/scheduler.go:421-437."""
        task_id = td.uid
        rid = resource_id_from_string(rd.uuid)
        rd.state = ResourceState.BUSY
        rd.current_running_tasks.append(task_id)
        assert task_id not in self.task_bindings, f"task {task_id} already bound"
        self.task_bindings[task_id] = rid
        self._note_binding_changed(task_id)
        self.resource_bindings.setdefault(rid, set()).add(task_id)
        self.gm.running_tasks_changed(rid)
        self.cost_model.task_bound(td, rid)

    def _unbind_task_from_resource(
        self, td: TaskDescriptor, rid: int, departed: bool = False
    ) -> bool:
        """Reference: flowscheduler/scheduler.go:443-464. The PU's
        current_running_tasks is kept here and in _bind_task_to_resource:
        an evicted or migrated task leaves it now; one that ``departed``
        (finished, failed) at the next `deltas` phase (_drop_departed)."""
        task_id = td.uid
        rs = self.resource_map.find(rid)
        rd = rs.descriptor
        if len(rd.current_running_tasks) == 0:
            rd.state = ResourceState.IDLE
        if task_id not in self.task_bindings:
            return False
        task_set = self.resource_bindings.get(rid, set())
        if task_id not in task_set:
            return False
        del self.task_bindings[task_id]
        self._note_binding_changed(task_id)
        task_set.discard(task_id)
        if departed:
            self._departed.setdefault(rid, set()).add(task_id)
            return True
        if task_id in rd.current_running_tasks:
            # absent where the preemption walk emptied the list and the
            # mapping moved the task elsewhere
            rd.current_running_tasks.remove(task_id)
            self.gm.running_tasks_changed(rid)
        self.cost_model.task_unbound(task_id, rid)
        return True

    def _drop_departed(self, lists_rebuilt: bool = False) -> None:
        """The `deltas` phase, for the PUs that had a completion, a
        failure or a kill since the last one: the point of the round at
        which the reference's rebuild of the lists lets go of them
        (``lists_rebuilt``: the preemption walk just did that, and only
        the cost model is still to be told)."""
        for rid, gone in self._departed.items():
            for task_id in gone:
                self.cost_model.task_unbound(task_id, rid)
            rs = self.resource_map.find(rid)
            if rs is None or lists_rebuilt:
                continue  # the PU left with its machine
            rd = rs.descriptor
            rd.current_running_tasks = [t for t in rd.current_running_tasks if t not in gone]
            self.gm.running_tasks_changed(rid)
        self._departed.clear()

    def _execute_task(self, td: TaskDescriptor, rd: ResourceDescriptor) -> None:
        """No real executor, as in the reference (scheduler.go:469-474)."""
        td.state = TaskState.RUNNING
        td.scheduled_to_resource = rd.uuid

    def _insert_task_into_runnables(self, job_id: int, task_id: int) -> None:
        self.runnable_tasks.setdefault(job_id, set()).add(task_id)

    def _compute_runnable_tasks_for_job(self, jd: JobDescriptor) -> Set[int]:
        """Dependency-free lazy graph reduction (reference:
        flowscheduler/scheduler.go:493-529), which walks the job's tree
        every round. Here the tree is walked when the scheduler meets
        the job (its first offer, a restore, a job offered again after
        it completed) and when its root holds other children than the
        scheduler was told of; after that a scan looks at the children
        add_task recorded since the last one, in the walk's order."""
        job_id = job_id_from_string(jd.uuid)
        met = self._met_jobs.get(job_id)
        if met is None or met.root_children != len(jd.root_task.spawned):
            self._tasks_scanned += self._walk_job_tree(jd)
        elif met.arrived and jd.root_task.state in _WALKED_ROOT_STATES:
            arrived, met.arrived = met.arrived, []
            if not met.in_walk_order:
                arrived.sort(key=_walk_order)
                met.in_walk_order = True
            for path, td in arrived:
                if td.state in _PROMOTED_STATES:
                    self._promote_task(td, path)
            self._tasks_scanned += len(arrived)
        return self.runnable_tasks.setdefault(job_id, set())

    def _walk_job_tree(self, jd: JobDescriptor) -> int:
        """The reference's walk of a job's whole tree: every CREATED or
        BLOCKING descriptor under the root is promoted. A promoted task
        is handed to the graph manager with its place in the tree, which
        is why a child is looked at from its parent: that is where its
        index is known. The scheduler has met the job from here on, and
        keeps every descriptor's path for the children to come. Returns
        the descriptors visited."""
        root = jd.root_task
        if root.state not in _WALKED_ROOT_STATES:
            return 0
        if root.state == TaskState.CREATED:
            self._promote_task(root, ())
        paths = {root.uid: ()}
        parents = [(root, ())]
        while parents:
            parent, path = parents.pop()
            for i, child in enumerate(parent.spawned):
                paths[child.uid] = child_path = path + (i,)
                if child.state in _PROMOTED_STATES:
                    self._promote_task(child, child_path)
                if child.spawned:
                    parents.append((child, child_path))
        self._met_jobs[job_id_from_string(jd.uuid)] = _MetJob(paths, len(root.spawned))
        return len(paths)

    def _promote_task(self, td: TaskDescriptor, path: tuple) -> None:
        td.state = TaskState.RUNNABLE
        self._insert_task_into_runnables(job_id_from_string(td.job_id), td.uid)
        self.gm.task_runnable(td, path)

    # ------------------------------------------------------------------
    # Resource removal helpers
    # ------------------------------------------------------------------

    def _dfs_evict_tasks(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        for child in rtnd.children:
            self._dfs_evict_tasks(child)
        self._evict_tasks_from_resource(rtnd)

    def _evict_tasks_from_resource(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        rd = rtnd.resource_desc
        rid = resource_id_from_string(rd.uuid)
        for task_id in list(self.resource_bindings.get(rid, ())):
            td = self.task_map.find(task_id)
            assert td is not None, f"descriptor for task {task_id} must exist"
            self._evict_task(td, rd)

    def _dfs_clean_up_resource(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        for child in rtnd.children:
            self._dfs_clean_up_resource(child)
        rid = resource_id_from_string(rtnd.resource_desc.uuid)
        self.resource_bindings.pop(rid, None)
        self.resource_map.remove(rid)
