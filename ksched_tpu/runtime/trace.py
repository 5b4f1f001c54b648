"""First-class round tracing.

The reference times rounds ad hoc in its CLI (`time.Since` around
ScheduleAllJobs, cmd/k8sscheduler/scheduler.go:146-150) and discards the
solver's own timing lines (placement/solver.go:169-170). Here every
round yields a structured record — per-phase wall clock (the RoundTiming
breakdown, itself derived from obs span durations), mutation counts
(ChangeStats), solver effort — exportable as JSON lines and
summarizable as percentiles.

The tracer is also the metrics publication point: every record it
appends is simultaneously published to the obs metrics registry
(rounds/faults/retries/degradations counters, per-phase latency
histograms), so the live `/metricsz` surface and the JSONL artifact
are two views of the same records and reconcile exactly at any
instant — the obs smoke asserts this over a chaos soak.
"""

from __future__ import annotations

import json
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..obs.metrics import get_registry, log_buckets


@dataclass
class RoundRecord:
    round_index: int
    wall_time: float  # epoch seconds at record time
    phases_ms: Dict[str, float]
    num_scheduled: int = 0
    solver_work: int = 0  # supersteps / iterations / augmentations
    #: of `solver_work`, the supersteps the scan-CSR rung ran over the
    #: rows of the nodes that held excess alone (JaxSolver.
    #: last_sparse_supersteps; 0 on any other rung)
    supersteps_sparse: int = 0
    #: the global price updates that fired in the round's solve
    #: (JaxSolver.last_price_updates: `steps // price_update_every` of
    #: the attempts at eps 1; 0 where the rung runs none)
    price_updates: int = 0
    nodes_added: int = 0
    arcs_added: int = 0
    arcs_changed: int = 0
    arcs_removed: int = 0
    # -- robustness observability (chaos harness / hardened loop): every
    # injected fault, retry, degradation, and heartbeat expiry is
    # attributable to the round it landed in --------------------------------
    faults_injected: Dict[str, int] = field(default_factory=dict)
    retries: int = 0  # control-plane retry/re-post attempts this round
    degradations: int = 0  # solver rungs stepped down this round
    solver_rung: int = 0  # ladder rung that produced the round; -1 = no solve (NOOP if noop_round, else an idle sweep)
    noop_round: bool = False  # ladder exhausted: previous assignments kept
    deadline_miss: bool = False  # round blew its watchdog deadline
    machines_lost: int = 0  # heartbeat-expired machines this sweep
    tasks_failed: int = 0  # heartbeat-expired tasks this sweep
    #: owning cell in a multi-tenant service ("" = single-tenant): the
    #: per-tenant soak/obs_report group round records on this, and the
    #: zero-cross-tenant-interference check relies on fault/degradation
    #: counters landing ONLY in the chaos tenant's records
    tenant: str = ""
    #: how long the pods this round admitted sat in the channel before
    #: it opened (PodEvent.received_s to the service_round span's
    #: start): mean and max over the batch, 0.0 for a round without pods
    queue_wait_ms: float = 0.0
    queue_wait_max_ms: float = 0.0
    #: the round's graph update (GraphManager.add_or_update_job_nodes):
    #: task nodes it updated, and pinned tasks of the same jobs that it
    #: left alone because nothing can change their one arc (from the
    #: round's RoundTiming, so 0 wherever the phase timings are)
    graph_tasks_visited: int = 0
    graph_tasks_skipped: int = 0
    #: descriptors the scheduler looked at to find the round's runnable
    #: tasks (from its RoundTiming; FlowScheduler._runnable_jobs): the
    #: pods admitted since the last round, or every descriptor of a job
    #: it met for the first time (a first offer, the round after a
    #: restore)
    runnable_tasks_scanned: int = 0
    #: the resource half of the same update (from its RoundTiming):
    #: resource nodes that took a turn in its FIFO, and arcs out of
    #: them it added or whose price really changed
    res_nodes_visited: int = 0
    res_arcs_changed: int = 0
    #: the round's statistics pass (from its RoundTiming;
    #: GraphManager.compute_topology_statistics): PUs whose
    #: current_running_tasks changed since the pass before, resource
    #: nodes it prepared (those PUs and their ancestors, or every
    #: resource node), 1 if it walked every node (that method's
    #: docstring says when), and the children those nodes iterated to
    #: gather from: a patched pass counts one for each dirty PU and
    #: every outgoing arc of each ancestor (a coordinator re-reads all
    #: its machines for one dirty path), a full walk the arcs it went
    #: over
    stats_pus_dirty: int = 0
    stats_nodes_visited: int = 0
    stats_full_walk: int = 0
    stats_children_gathered: int = 0
    #: the round's refresh of the resource tree, in `apply` (from its
    #: RoundTiming; GraphManager.refresh_resource_topology): PUs whose
    #: current_running_tasks changed since the refresh before, resource
    #: nodes it visited (those PUs and their ancestors, or every
    #: resource node), and 1 if it walked every node
    apply_pus_dirty: int = 0
    apply_nodes_visited: int = 0
    apply_full_walk: int = 0
    #: the round's post-solve half (from its RoundTiming): unpinned task
    #: nodes handed to `decode` (the batch and the unscheduled backlog;
    #: every task under preemption), and pinned tasks it left alone
    decode_tasks: int = 0
    decode_pinned_skipped: int = 0
    #: the device-resident export of the round (from its RoundTiming; 0
    #: on a service without --device-resident): exact host-to-device
    #: bytes (problem records or arrays, plus plan records or plan), 1
    #: if the arrays or the plan went up whole (first build, pow2
    #: growth, layout rebuild, or a delta past the largest compiled
    #: record bucket), and plan regions relocated since the last round
    upload_bytes: int = 0
    upload_full: int = 0
    plan_relocations: int = 0
    #: exact bytes the round's solves moved where the scan-CSR solver is
    #: the configured rung (from its RoundTiming; zeros elsewhere): up,
    #: `upload_bytes` plus what the solver itself uploaded (problem
    #: arrays, warm flow, eps, the plan's re-ship; next to nothing over
    #: a device-resident problem); down, the attempts' scalars, the
    #: telemetry ring and the flow (prices stay on the device)
    solve_h2d_bytes: int = 0
    solve_d2h_bytes: int = 0
    #: time the Python collector held the process during the round, all
    #: generations and threads (obs/spans.py `gc_pause`); counted while
    #: a SpanTracer is installed, 0.0 otherwise
    gc_pause_ms: float = 0.0
    #: the slot plan (graph/slot_plan.py): rows at the solve and in
    #: use, and the round's re-fits, regrowths and re-layouts
    plan_rows: int = 0
    plan_rows_live: int = 0
    plan_refits: int = 0
    plan_regrowths: int = 0
    plan_relayouts: int = 0
    #: records of the change journal the round's export applied (from
    #: its RoundTiming; 0 when it built the arrays whole), units of
    #: folded supply the export held routed leaf -> sink when it made
    #: the round's problem (the pinned pods, whose PU has one way out;
    #: 0 under preemption), and EC nodes the purge removed after `apply`
    journal_changes: int = 0
    supply_prerouted: int = 0
    ec_purged: int = 0
    #: the round's equivalence classes (from its RoundTiming): EC nodes
    #: and EC -> resource arcs live after `graph_update`, those arcs
    #: added, removed or re-priced by it, and runnable tasks the round
    #: left unplaced while the cluster had a free slot for each (what a
    #: placement rule kept out, not a full cluster)
    ec_nodes: int = 0
    ec_arcs: int = 0
    ec_arcs_changed: int = 0
    unscheduled_by_rule: int = 0
    #: EC -> resource arcs whose cost and capacity `graph_update` wrote,
    #: changed or not, and machines whose class census `stats` gathered
    #: again (0 under a model that keeps none); where the dense collapse
    #: answered the round (zeros elsewhere): the tasks its rows pass
    #: grouped, the rows of the dense problem and its padded columns
    ec_arcs_repriced: int = 0
    census_machines_dirty: int = 0
    #: under a model that keeps books of reserved CPU and memory (zeros
    #: elsewhere), at the solve: machines whose books moved since the
    #: round before, machines some size class cannot use, and the sum of
    #: what each machine takes this round
    books_machines_dirty: int = 0
    machines_gated: int = 0
    columns_offered: int = 0
    audit_tasks_grouped: int = 0
    collapse_rows: int = 0
    collapse_cols: int = 0
    #: EC -> EC arcs `graph_update` added, removed or gave another
    #: capacity or cost, and 1 if the cost model left its allotment for
    #: the per-pod predicate (a zone short of room)
    ec_chain_arcs_changed: int = 0
    spread_fallback: int = 0
    #: --pipeline: how long the PREVIOUS round's Bindings waited from
    #: their `bindings_collect` to the flush that POSTed them (this
    #: round's dispatch window, or an idle sweep in between); stamped on
    #: solved rounds, 0.0 on the synchronous path
    post_defer_ms: float = 0.0
    #: --preemption: running tasks the round's solve could preempt or
    #: migrate (from its RoundTiming; 0 without preemption); pods the
    #: round took off their nodes and posted through `evict_pods`, those
    #: of them that it bound to another node in the same round (an
    #: eviction and a Binding), and pods evicted at some time and still
    #: without a new Binding once the round was posted
    tasks_unpinned: int = 0
    #: tasks' own preference arcs in the graph at the solve and those
    #: the round added or removed; under a model that places by data
    #: locality (--cost-model quincy) the tasks bound by their cheapest
    #: route (machine arc, rack arc, cluster aggregator), the share of
    #: them bound through an arc of their own and the share of their
    #: input bytes on another machine than theirs, in percent (from the
    #: round's RoundTiming)
    pref_arcs_live: int = 0
    pref_arcs_changed: int = 0
    bound_via_machine: int = 0
    bound_via_rack: int = 0
    bound_via_cluster: int = 0
    bound_on_preferred_share: float = 0.0
    remote_bytes_share: float = 0.0
    pods_evicted: int = 0
    pods_migrated: int = 0
    pods_pending_evicted: int = 0
    #: tasks the round's collection of Bindings looked at: those whose
    #: binding the scheduler changed since the last collection and the
    #: re-delivered pods' (cli.SchedulerService._collect_bindings)
    bindings_examined: int = 0
    #: --array-round (scheduler/array_service.py; zeros on the graph
    #: path): rows of the device's task table that hold a pod after the
    #: round, exact bytes the round shipped to the device (completed
    #: rows, the batch's classes, the counts) and read back from it (the
    #: round's scalars, and the rows it placed with their PUs where it
    #: placed any), pods that hold a row and no PU after the round, and
    #: 1 if the round's transport hit its superstep bound before it
    #: converged (its placements are then not an optimum's)
    array_rows_live: int = 0
    array_h2d_bytes: int = 0
    array_d2h_bytes: int = 0
    array_pods_waiting: int = 0
    array_unconverged: int = 0
    #: the decode window the round's program ran at (a bucket, 256 or
    #: 4,096, or the table's rows; 0: the round launched no program), and
    #: the machines with a free slot when the round began (after the
    #: completions it retired): the columns the transport chose among
    array_decode_width: int = 0
    array_machines_open: int = 0


class RoundTracer:
    def __init__(self, capacity: Optional[int] = None, registry=None) -> None:
        self.records: List[RoundRecord] = []
        self.capacity = capacity
        # metric handles resolve at construction time (scoped_registry
        # gives a soak run private per-run accounting); with obs
        # disabled these are inert null metrics
        reg = registry if registry is not None else get_registry()
        self._m_rounds = reg.counter(
            "ksched_rounds_total",
            "scheduling rounds by kind (sched = solved, idle = sweep-only, "
            "noop = ladder exhausted, previous assignments kept)",
            labelnames=("kind",),
        )
        self._m_phase = reg.histogram(
            "ksched_round_phase_ms",
            "per-phase round latency (solved rounds only; idle sweeps and "
            "NOOP rounds carry no phase timings)",
            labelnames=("phase",),
        )
        self._m_scheduled = reg.counter(
            "ksched_scheduled_tasks_total", "tasks placed across all rounds"
        )
        self._m_faults = reg.counter(
            "ksched_faults_attributed_total",
            "injected faults attributed to a round's record, by kind "
            "(reconciles against ksched_chaos_injected_total)",
            labelnames=("kind",),
        )
        self._m_retries = reg.counter(
            "ksched_retries_total", "control-plane retry/re-post attempts"
        )
        self._m_degr = reg.counter(
            "ksched_round_degradations_total",
            "solver rungs stepped down, attributed per round",
        )
        self._m_miss = reg.counter(
            "ksched_deadline_misses_total", "rounds that blew the watchdog deadline"
        )
        self._m_lost = reg.counter(
            "ksched_machines_lost_total", "heartbeat-expired machines"
        )
        self._m_failed = reg.counter(
            "ksched_tasks_failed_total", "heartbeat-expired tasks"
        )
        self._m_graph = reg.counter(
            "ksched_graph_changes_total",
            "graph-delta journal records by kind",
            labelnames=("kind",),
        )
        self._m_work = reg.histogram(
            "ksched_round_solver_work",
            "solver supersteps/iterations per solved round",
            buckets=log_buckets(1, 1 << 20, 2.0),
        )
        self._m_queue_wait = reg.histogram(
            "ksched_pod_queue_wait_ms",
            "longest wait in the pod channel among the pods a solved round "
            "admitted (rounds without pods are not observed)",
        )

    def _publish(self, rec: RoundRecord) -> None:
        """Mirror one record onto the metrics registry. Called for every
        appended record, so summed records == served counters, always."""
        kind = (
            "noop" if rec.noop_round
            else ("idle" if rec.solver_rung == -1 else "sched")
        )
        self._m_rounds.labels(kind=kind).inc()
        if kind == "sched":
            for phase, ms in rec.phases_ms.items():
                self._m_phase.labels(phase=phase).observe(ms)
            if rec.solver_work:
                self._m_work.observe(rec.solver_work)
            if rec.queue_wait_max_ms > 0:  # the round admitted pods
                self._m_queue_wait.observe(rec.queue_wait_max_ms)
        if rec.num_scheduled:
            self._m_scheduled.inc(rec.num_scheduled)
        for k, v in rec.faults_injected.items():
            if v:
                self._m_faults.labels(kind=k).inc(v)
        if rec.retries:
            self._m_retries.inc(rec.retries)
        if rec.degradations:
            self._m_degr.inc(rec.degradations)
        if rec.deadline_miss:
            self._m_miss.inc()
        if rec.machines_lost:
            self._m_lost.inc(rec.machines_lost)
        if rec.tasks_failed:
            self._m_failed.inc(rec.tasks_failed)
        for kind_, n in (
            ("nodes_added", rec.nodes_added),
            ("arcs_added", rec.arcs_added),
            ("arcs_changed", rec.arcs_changed),
            ("arcs_removed", rec.arcs_removed),
        ):
            if n:
                self._m_graph.labels(kind=kind_).inc(n)

    # -- recording --------------------------------------------------------

    def record_flow_round(
        self,
        scheduler,
        num_scheduled: int,
        extra: Optional[Dict] = None,
        solved: bool = True,
    ) -> RoundRecord:
        """Capture a FlowScheduler round from its last_timing + stats.
        ``extra`` carries the robustness counters (faults_injected,
        retries, degradations, …) the hardened service loop attributes
        to this round; unknown keys are rejected so counter names
        cannot silently drift from the RoundRecord schema.

        ``solved=False`` marks an idle sweep (no graph rebuild/solve
        ran): the scheduler's dimacs_stats and solver-work counters
        still hold the *previous* solved round's values and must not be
        re-reported, or trace aggregations would multi-count that round
        once per quiet poll."""
        t = scheduler.last_timing
        stats = scheduler.dimacs_stats if solved else None
        backend = getattr(scheduler.solver, "backend", None) if solved else None
        rec = RoundRecord(
            round_index=len(self.records),
            wall_time=time.time(),
            phases_ms={
                "stats": t.stats_s * 1e3,
                "graph_update": t.graph_update_s * 1e3,
                "solve": t.solve_s * 1e3,
                "deltas": t.deltas_s * 1e3,
                "apply": t.apply_s * 1e3,
                "total": t.total_s * 1e3,
            },
            num_scheduled=num_scheduled,
            solver_work=getattr(backend, "last_iterations", 0)
            or getattr(backend, "last_supersteps", 0),
            supersteps_sparse=getattr(backend, "last_sparse_supersteps", 0),
            price_updates=getattr(backend, "last_price_updates", 0),
            nodes_added=stats.nodes_added if stats else 0,
            arcs_added=stats.arcs_added if stats else 0,
            arcs_changed=stats.arcs_changed if stats else 0,
            arcs_removed=stats.arcs_removed if stats else 0,
            graph_tasks_visited=t.graph_tasks_visited,
            graph_tasks_skipped=t.graph_tasks_skipped,
            runnable_tasks_scanned=t.runnable_tasks_scanned,
            res_nodes_visited=t.res_nodes_visited,
            res_arcs_changed=t.res_arcs_changed,
            stats_pus_dirty=t.stats_pus_dirty,
            stats_nodes_visited=t.stats_nodes_visited,
            stats_full_walk=t.stats_full_walk,
            stats_children_gathered=t.stats_children_gathered,
            apply_pus_dirty=t.apply_pus_dirty,
            apply_nodes_visited=t.apply_nodes_visited,
            apply_full_walk=t.apply_full_walk,
            decode_tasks=t.decode_tasks,
            decode_pinned_skipped=t.decode_pinned_skipped,
            upload_bytes=t.upload_bytes,
            upload_full=t.upload_full,
            plan_relocations=t.plan_relocations,
            solve_h2d_bytes=t.solve_h2d_bytes,
            solve_d2h_bytes=t.solve_d2h_bytes,
            plan_rows=t.plan_rows,
            plan_rows_live=t.plan_rows_live,
            plan_refits=t.plan_refits,
            plan_regrowths=t.plan_regrowths,
            plan_relayouts=t.plan_relayouts,
            journal_changes=t.journal_changes,
            supply_prerouted=t.supply_prerouted,
            ec_purged=t.ec_purged,
            ec_nodes=t.ec_nodes,
            ec_arcs=t.ec_arcs,
            ec_arcs_changed=t.ec_arcs_changed,
            unscheduled_by_rule=t.unscheduled_by_rule,
            ec_arcs_repriced=t.ec_arcs_repriced,
            census_machines_dirty=t.census_machines_dirty,
            books_machines_dirty=t.books_machines_dirty,
            machines_gated=t.machines_gated,
            columns_offered=t.columns_offered,
            audit_tasks_grouped=t.audit_tasks_grouped,
            collapse_rows=t.collapse_rows,
            collapse_cols=t.collapse_cols,
            ec_chain_arcs_changed=t.ec_chain_arcs_changed,
            spread_fallback=t.spread_fallback,
            tasks_unpinned=t.tasks_unpinned,
            pref_arcs_live=t.pref_arcs_live,
            pref_arcs_changed=t.pref_arcs_changed,
            bound_via_machine=t.bound_via_machine,
            bound_via_rack=t.bound_via_rack,
            bound_via_cluster=t.bound_via_cluster,
            bound_on_preferred_share=t.bound_on_preferred_share,
            remote_bytes_share=t.remote_bytes_share,
        )
        self._set_extra(rec, extra)
        self._append(rec)
        return rec

    def record_bulk_round(self, cluster, result) -> RoundRecord:
        """Capture a BulkCluster round from its BulkRoundResult."""
        backend = cluster.backend
        return self.record_timed_round(
            result.timing,
            num_scheduled=len(result.placed_tasks),
            solver_work=getattr(backend, "last_supersteps", 0)
            or getattr(backend, "last_iterations", 0),
        )

    def record_timed_round(
        self,
        timing: Dict[str, float],
        total_ms: Optional[float] = None,
        num_scheduled: int = 0,
        solver_work: int = 0,
        extra: Optional[Dict] = None,
    ) -> RoundRecord:
        """Capture an externally timed round from a `{phase}_s` dict.
        `total_ms` overrides the summed-phases total with a measured
        wall time. This is the one place the timing-key → phase-name
        mapping lives, so a caller that times its own rounds records
        exactly the series the service publishes. ``extra`` sets further
        fields of the record, as in `record_flow_round`: an unknown key
        is rejected."""
        phases_ms = {k[:-2]: v * 1e3 for k, v in timing.items()}
        phases_ms["total"] = (
            total_ms if total_ms is not None else sum(phases_ms.values())
        )
        rec = RoundRecord(
            round_index=len(self.records),
            wall_time=time.time(),
            phases_ms=phases_ms,
            num_scheduled=num_scheduled,
            solver_work=solver_work,
        )
        self._set_extra(rec, extra)
        self._append(rec)
        return rec

    @staticmethod
    def _set_extra(rec: RoundRecord, extra: Optional[Dict]) -> None:
        for k, v in (extra or {}).items():
            if not hasattr(rec, k):
                raise ValueError(f"unknown RoundRecord field {k!r}")
            setattr(rec, k, v)

    def _append(self, rec: RoundRecord) -> None:
        self._publish(rec)
        self.records.append(rec)
        if self.capacity is not None and len(self.records) > self.capacity:
            del self.records[0]

    # -- export -----------------------------------------------------------

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(asdict(r)) for r in self.records)

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_jsonl() + ("\n" if self.records else ""))

    def summary(self, phase: str = "total") -> Dict[str, float]:
        """Phase percentiles over SOLVED rounds. Idle sweeps (sweep-only
        quiet polls: ``solver_rung == -1`` without ``noop_round``) carry
        zeroed phase timings by construction and would drag an
        idle-heavy soak's p50 toward zero, so they are excluded from
        the percentiles and reported as ``idle_rounds`` instead. NOOP
        rounds are different — a *failed* solve is part of the latency
        story, not a skipped one — so they stay in the population."""
        idle = sum(
            1 for r in self.records if r.solver_rung == -1 and not r.noop_round
        )
        vals = np.array(
            [
                r.phases_ms.get(phase, 0.0)
                for r in self.records
                if not (r.solver_rung == -1 and not r.noop_round)
            ],
            dtype=np.float64,
        )
        if not len(vals):
            return {"rounds": 0, "idle_rounds": idle}
        return {
            "rounds": len(vals),
            "idle_rounds": idle,
            "p50_ms": float(np.percentile(vals, 50)),
            "p90_ms": float(np.percentile(vals, 90)),
            "p99_ms": float(np.percentile(vals, 99)),
            "mean_ms": float(vals.mean()),
            "max_ms": float(vals.max()),
        }
