"""The array round, served: `--array-round`.

`cli.SchedulerService` keeps the cluster as a flow graph on the host and
ships a problem to a solver rung every round. `ArrayRoundService` keeps
it where `scheduler/device_bulk.py` keeps it: the task table, the
placements, the per-PU occupancy and the machine set are device arrays,
built once by `init_topology` with every shape fixed for the service's
life, and a scheduling round (capacity refresh -> class census -> the
cost model's `class_cost_fn` -> dense transport -> rank-match decode ->
apply) is one jitted program, `DeviceBulkCluster.serve_round`. The
machines may differ: every machine is padded to the widest one's PUs, a
PU a machine does not have holds 0 slots (`DeviceBulkCluster.pu_slots`),
and the cost function follows `--cost-model`: `coco`'s, or `whare`'s over
each machine's own slots and the platform its `ksched.io/platform` label
names. The host
keeps a mirror that costs O(batch) a round: which row of the table each
pod holds (`admit` occupies "the first `count` free rows", so a min-heap
of the free rows names them without asking the device), the PU of each
row, and the rows that wait.

A round, inside `ServiceLoop.run_round`'s `service_round` span:

  array_completions   the rows of the pods completed since the last
                      round go up (`complete_tasks`, a batch-sized bucket)
  pods_admit          the batch's pods take rows: `array_admit` inside it
                      is the mirror's rows and `add_tasks` (the classes,
                      a batch-sized bucket; nothing else is shipped)
  round               `array_launch` (`serve_round` over the smallest
                      decode window that holds every waiting row),
                      `array_wait` (the round's seven scalars and the
                      admitted count on the host), `array_readback` (the
                      rows it placed and their PUs, 8 bytes a window row)
  bindings_collect    rows newly placed -> `Binding(pod, node)`: O(placed),
                      no diff against the resident pods
  bindings_post, round_accounting

What differs from the graph path, on purpose:

- a completed pod's slot is free in the NEXT round (the graph path lets
  go of it in that round's `deltas` phase, after the solve: the round
  after the next);
- prices are of the round's start, as there; every pod is of ONE job;
  a waiting pod's escape cost does not age;
- a pod the round left waiting keeps its row and is in the next round;
  a quiet channel re-solves only if a completion freed capacity since
  and pods wait.

Nothing fails silently: a round whose transport hit its superstep bound
(`unconverged_rounds`), an admission the device made short of its count
(`admissions_short`, and the loop stops: the mirror no longer names the
rows), a batch the table cannot hold (`admissions_deferred`: the pods
wait on the host and go in first when rows free), a cost that overflows
the solver's scaling (`cost_overflows`) and a completion for a pod that
holds no PU (`completions_refused`; returns False) each count on the
service and warn. What is not served is refused where the service is
built (`cli.refuse_unserved_by_array_round`), with a sentence."""

from __future__ import annotations

import heapq
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..cli import ARRAY_ROUND_COST_MODELS, ServiceLoop, fake_node_events
from ..cluster import Binding, ClusterAPI, NodeEvent, PodEvent
from ..costmodels import coco, whare
from ..costmodels.census import NUM_TASK_CLASSES
from ..costmodels.device_costs import coco_device_cost_fn, whare_device_cost_fn
from ..obs import metrics as obs_metrics
from ..obs.spans import gc_pause_total_s, span
from ..runtime.failure import RoundWatchdog
from ..utils import next_pow2
from .device_bulk import SERVED_SUMMARY, DeviceBulkCluster

#: rows the table keeps beyond the cluster's slots, for pods that wait
WAITING_ROWS = 1024
#: how wide a round's uploads and its decode window may be, below the
#: table's own width: one compiled program each, all compiled by
#: `init_topology`, the smallest that holds the round's rows is taken
BUCKETS = (256, 4096)
#: the transport's superstep bound (what chip_smoke.py's array leg runs
#: under); a round that reaches it is counted, never hidden
SUPERSTEPS = 1 << 17


class ArrayRoundService(ServiceLoop):
    """`--array-round`: the service whose round is the device round of
    `DeviceBulkCluster`, behind `SchedulerService`'s cluster API contract
    (`init_topology`, `complete_pod`, `run`, `run_round`)."""

    def __init__(
        self,
        api: ClusterAPI,
        max_tasks_per_pu: int,
        cost_model: str = "coco",
        fake_machine_types=(),
        tracer=None,
        flight=None,
        span_tracer=None,
        round_deadline_s: float = 0.0,
    ) -> None:
        self.api = api
        self.tracer = tracer
        self.flight = flight
        self.span_tracer = span_tracer
        self.tenant = ""
        self.injector = None
        self.backlog_dirty = False
        self._gc_mark = 0.0
        self._api_stats_mark: Dict[str, int] = api.stats() if hasattr(api, "stats") else {}
        self.watchdog = RoundWatchdog(round_deadline_s)
        self.max_tasks_per_pu = max_tasks_per_pu
        if cost_model not in ARRAY_ROUND_COST_MODELS:
            raise ValueError(
                f"--array-round has no device cost function for --cost-model {cost_model}"
            )
        self.cost_model = cost_model
        self.fake_machine_types = tuple(fake_machine_types)
        #: read by `init_topology`, which builds the table: a test lowers it
        self.supersteps = SUPERSTEPS
        self.cluster: Optional[DeviceBulkCluster] = None
        #: machine m of the table is the node `nodes[m]`
        self.nodes: List[str] = []
        #: the buckets below the table's width, then the table's (init_topology)
        self.widths: Tuple[int, ...] = ()
        #: the nodes as they came, until the table is built from them
        self._node_events: List[NodeEvent] = []
        #: machine m's slots, and how many of them hold a pod (the mirror)
        self.machine_slots = np.zeros(0, np.int32)
        #: machine m's platform, an index into costmodels.whare.PLATFORMS
        self.machine_platform = np.zeros(0, np.int32)
        self._machine_load = np.zeros(0, np.int32)
        # -- the host mirror of the table -------------------------------
        self.row_of: Dict[str, int] = {}  # pod -> its row
        self.pod_at: List[Optional[str]] = []  # row -> its pod
        self.pu_of_row = np.zeros(0, np.int32)  # row -> its PU, -1 without one
        self._free_rows: List[int] = []  # a min-heap: `admit` takes the lowest
        self._waiting_rows: set = set()  # live rows without a PU
        self._done_rows: List[int] = []  # completed since the last round
        self._deferred: Deque[PodEvent] = deque()  # pods the table had no row for
        # -- what went wrong, by kind -----------------------------------
        self.unconverged_rounds = 0
        self.admissions_short = 0
        self.admissions_deferred = 0
        self.cost_overflows = 0
        self.completions_refused = 0
        #: the wall of every device round (launch to read-back), in order
        self.round_latencies_s: List[float] = []
        reg = obs_metrics.get_registry()
        self._g_pods = reg.gauge("ksched_live_pods", "pods the service tracks")
        self._g_bound = reg.gauge("ksched_bound_tasks", "tasks currently bound")
        self._g_machines = reg.gauge("ksched_machines", "machines in the topology")

    @property
    def rounds(self) -> int:
        """Device rounds run so far."""
        return len(self.round_latencies_s)

    # -- topology ---------------------------------------------------------

    def add_node(self, node: NodeEvent) -> None:
        """A node of the cluster the table is about to be built for, of
        any cores x PUs. The table's machines are fixed once built: a node
        that comes after `init_topology` is refused."""
        if self.cluster is not None:
            raise ValueError(
                f"node {node.node_id}: --array-round builds its table for the "
                f"{len(self.nodes)} machines init_topology saw; a node that joins "
                "later is not served yet (ROADMAP R1)"
            )
        if node.num_cores < 1 or node.pus_per_core < 1:
            raise ValueError(
                f"node {node.node_id}: {node.num_cores} cores x {node.pus_per_core} PUs: "
                "--array-round wants a PU or more a node"
            )
        self._node_events.append(node)
        self.nodes.append(node.node_id)

    def init_topology(
        self,
        fake_machines: int = 0,
        node_batch_timeout_s: float = 2.0,
        cores_per_machine: int = 1,
        pus_per_core: int = 1,
    ) -> int:
        """Fabricate the machines or poll the control plane for them, as
        `SchedulerService.init_topology` does (the same function deals the
        fake machines their types and labels), then build the ONE table
        the service keeps: a row for every slot and `WAITING_ROWS` more,
        rounded up to a power of two; every machine padded to the widest
        one's PUs, a PU a machine does not have holding no slot; each
        machine's PUs and platform read from its NodeEvent. Every program
        a round can run is compiled here (three a bucket), so that no
        served round compiles."""
        if fake_machines > 0:
            events = fake_node_events(
                fake_machines, cores_per_machine, pus_per_core, types=self.fake_machine_types
            )
        else:
            events = self.api.get_node_batch(node_batch_timeout_s)
        for node in events:
            self.add_node(node)
        if not self.nodes:
            raise ValueError("--array-round: no node to build the table for")
        pus = np.array([n.num_cores * n.pus_per_core for n in self._node_events], np.int32)
        widest = int(pus.max())
        pu_slots = np.where(
            np.arange(widest)[None, :] < pus[:, None], self.max_tasks_per_pu, 0
        ).astype(np.int32)  # [M, widest]
        self.machine_slots = pu_slots.sum(axis=1).astype(np.int32)
        self._machine_load = np.zeros(len(self.nodes), np.int32)
        self.machine_platform = np.array(
            [whare.platform_index(dict(n.labels)) for n in self._node_events], np.int32
        )
        if self.cost_model == "whare":
            cost_fn = whare_device_cost_fn(self.machine_slots, self.machine_platform)
            unsched_cost = whare.UNSCHEDULED_COST
        else:
            cost_fn, unsched_cost = coco_device_cost_fn(), coco.UNSCHEDULED_COST
        self.cluster = DeviceBulkCluster(
            num_machines=len(self.nodes), pus_per_machine=widest,
            slots_per_pu=self.max_tasks_per_pu, pu_slots=pu_slots.reshape(-1), num_jobs=1,
            num_task_classes=NUM_TASK_CLASSES,
            task_capacity=next_pow2(int(self.machine_slots.sum()) + WAITING_ROWS),
            class_cost_fn=cost_fn, unsched_cost=unsched_cost, ec_cost=0,
            supersteps=self.supersteps,
        )
        self._node_events = []
        rows = self.cluster.Tcap
        self.pod_at = [None] * rows
        self.pu_of_row = np.full(rows, -1, np.int32)
        self._free_rows = list(range(rows))  # ascending: a heap as it stands
        self.widths = tuple(w for w in BUCKETS if w < rows) + (rows,)
        for width in self.widths:
            # on the empty table each changes nothing
            self.cluster.complete_tasks([], width=width)
            self.cluster.add_tasks(0, classes=[], width=width)
            self._launch(width)
        self.cluster.fetch_state()
        self._g_machines.set(len(self.nodes))
        return len(self.nodes)

    def enable_heartbeats(self, *_a, **_k):
        raise ValueError("--array-round does not serve --machine-timeout yet (ROADMAP R1)")

    def save_checkpoint(self, path: str) -> None:
        raise NotImplementedError(
            "--array-round keeps its cluster on the device and has no checkpoint yet (ROADMAP R1)"
        )

    @classmethod
    def restore(cls, *_a, **_k):
        raise NotImplementedError(
            "--array-round keeps its cluster on the device and restores from no checkpoint "
            "yet (ROADMAP R1)"
        )

    # -- pods ---------------------------------------------------------------

    def complete_pod(self, pod_id: str) -> bool:
        """The pod finished: its row is retired by the next round, before
        that round admits and solves. False (and counted) if the pod holds
        no PU: unknown, or still waiting."""
        row = self.row_of.get(pod_id)
        if row is None or self.pu_of_row[row] < 0:
            self.completions_refused += 1
            return False
        del self.row_of[pod_id]
        self.pod_at[row] = None
        self._machine_load[self.pu_of_row[row] // self.cluster.P] -= 1
        self.pu_of_row[row] = -1
        self._done_rows.append(row)
        heapq.heappush(self._free_rows, row)
        # freed capacity may admit pods that wait: a quiet poll re-solves
        self.backlog_dirty = bool(self._waiting_rows or self._deferred)
        return True

    def _width(self, n: int) -> int:
        return next(w for w in self.widths if w >= n)

    def _launch(self, width: int):
        rows = self.cluster.Tcap
        return self.cluster.serve_round(decode_width=None if width == rows else width)

    def _retire_completed(self) -> int:
        """The rows completed since the last round, to the device; the
        bytes shipped."""
        rows, self._done_rows = self._done_rows, []
        if not rows:
            return 0
        with span("array_completions", rows=len(rows)):
            width = self._width(len(rows))
            self.cluster.complete_tasks(rows, width=width)
        return 4 * width + 4

    def _admit(self, pods) -> Tuple[int, List[Binding], int]:
        """The batch's pods take rows of the table, those the table had
        no row for before them. (bytes shipped, Bindings owed to pods
        that were delivered again, rows taken)."""
        again: List[Binding] = []
        fresh: List[PodEvent] = list(self._deferred)
        self._deferred.clear()
        for pod in pods:
            row = self.row_of.get(pod.pod_id)
            if row is None:
                if not 0 <= pod.task_class < NUM_TASK_CLASSES:
                    raise ValueError(
                        f"pod {pod.pod_id}: task class {pod.task_class}, the census has "
                        f"{NUM_TASK_CLASSES}"
                    )
                fresh.append(pod)
            elif self.pu_of_row[row] >= 0:
                # delivered again (its POST failed): it keeps its row and
                # PU, and its Binding goes out again
                again.append(Binding(pod.pod_id, self._node_of_pu(self.pu_of_row[row])))
        take = min(len(fresh), len(self._free_rows))
        if take < len(fresh):
            self.admissions_deferred += 1
            self._deferred.extend(fresh[take:])
            warnings.warn(
                f"the task table is full: {len(fresh) - take} pods wait on the host for a row "
                f"({self.cluster.Tcap} rows, {len(self.row_of)} live)",
                RuntimeWarning, stacklevel=3,
            )
        if not take:
            return 0, again, 0
        with span("array_admit", pods=take):
            classes = np.empty(take, np.int32)
            for i in range(take):
                pod = fresh[i]
                row = heapq.heappop(self._free_rows)
                self.row_of[pod.pod_id] = row
                self.pod_at[row] = pod.pod_id
                self._waiting_rows.add(row)
                classes[i] = pod.task_class
            width = self._width(take)
            self.cluster.add_tasks(take, classes=classes, width=width)
        return 4 * width + 4, again, take

    def _node_of_pu(self, pu: int) -> str:
        return self.nodes[int(pu) // self.cluster.P]

    # -- the round ------------------------------------------------------------

    def _run_round_body(self, pods, now, solve, queue_wait):
        # the columns a transport chooses among: the completions taken
        # before this round have left, its own placements have not come
        # (O(machines); counted for the RoundRecord alone)
        machines_open = 0
        if self.tracer is not None:
            machines_open = int(np.count_nonzero(self._machine_load < self.machine_slots))
        if not solve:
            rec = self._record(0, 0, False, queue_wait, solved=False, machines_open=machines_open)
            return rec, 0
        t0 = time.perf_counter()
        with self.watchdog as wd:
            h2d = self._retire_completed()
            with span("pods_admit", pods=len(pods)):
                shipped, out, took = self._admit(pods)
            admitted_dev = self.cluster.last_admitted if took else None
            h2d += shipped
            d2h = 0
            supersteps = 0
            unconverged = False
            width = 0
            if self._waiting_rows:
                with span("round"):
                    t_round = time.perf_counter()
                    width = self._width(len(self._waiting_rows))
                    with span("array_launch", width=width):
                        summary_dev, rows_dev, pus_dev = self._launch(width)
                    with span("array_wait"):
                        summary, admitted = jax.device_get((summary_dev, admitted_dev))
                    got = dict(zip(SERVED_SUMMARY, (int(v) for v in summary)))
                    d2h += 4 * len(SERVED_SUMMARY) + (4 if admitted is not None else 0)
                    placed = got["placed"]
                    if placed:
                        with span("array_readback", rows=placed):
                            rows, pus = jax.device_get((rows_dev, pus_dev))
                        d2h += 8 * width
                    self.round_latencies_s.append(time.perf_counter() - t_round)
                supersteps = got["supersteps"]
                unconverged = not got["converged"]
                self._note_faults(got, took, admitted, len(self._waiting_rows) - placed)
                if placed:
                    with span("bindings_collect") as sp:
                        moved = rows < self.cluster.Tcap
                        rows, pus = rows[moved], pus[moved]
                        self.pu_of_row[rows] = pus
                        self._waiting_rows.difference_update(rows.tolist())
                        machines = pus // self.cluster.P
                        np.add.at(self._machine_load, machines, 1)
                        nodes, pod_at = self.nodes, self.pod_at
                        out += [
                            Binding(pod_at[row], nodes[m])
                            for row, m in zip(rows.tolist(), machines.tolist())
                        ]
                        sp.set("resident", len(self.row_of))
                        sp.set("new", placed)
            self._post_bindings(out)
        # pods still wait: only a completion makes the next quiet poll a round
        self.backlog_dirty = False
        rec = self._record(
            len(out), supersteps, unconverged, queue_wait, deadline_miss=wd.fired,
            total_ms=(time.perf_counter() - t0) * 1e3, h2d=h2d, d2h=d2h,
            decode_width=width, machines_open=machines_open,
        )
        return rec, len(out)

    def _note_faults(self, got: Dict[str, int], took: int, admitted, waiting: int) -> None:
        """Count, and say, what a fetched round shows went wrong."""
        short = admitted is not None and int(admitted) != took
        if short or got["live"] != len(self.row_of) or got["unscheduled"] != waiting:
            self.admissions_short += short
            raise RuntimeError(
                f"the device's table holds {got['live']} pods, {got['unscheduled']} of them "
                f"waiting; the host's mirror {len(self.row_of)} and {waiting}"
                + (f" (the admission took {int(admitted)} rows of {took})" if short else "")
                + ": the mirror no longer names the rows"
            )
        if not got["converged"]:
            self.unconverged_rounds += 1
            warnings.warn(
                f"the round's transport reached its bound of {self.supersteps} supersteps "
                "before it converged: its placements are not an optimum's",
                RuntimeWarning, stacklevel=3,
            )
        if got["cost_overflow"]:
            self.cost_overflows += 1
            warnings.warn(
                "the round's scaled costs overflow int32: its placements are invalid",
                RuntimeWarning, stacklevel=3,
            )

    def _record(self, bound, supersteps, unconverged, queue_wait, solved=True,
                deadline_miss=False, total_ms=0.0, h2d=0, d2h=0, decode_width=0,
                machines_open=0):
        """The round's RoundRecord (None without a tracer), the gauges."""
        with span("round_accounting"):
            self._g_pods.set(len(self.row_of))
            self._g_bound.set(len(self.row_of) - len(self._waiting_rows))
            if self.tracer is None:
                return None
            return self.tracer.record_timed_round(
                {}, total_ms=total_ms, num_scheduled=bound, solver_work=supersteps,
                extra=dict(
                    solver_rung=0 if solved else -1,
                    retries=self._retries_since_last_round(),
                    deadline_miss=deadline_miss,
                    queue_wait_ms=queue_wait[0], queue_wait_max_ms=queue_wait[1],
                    gc_pause_ms=(gc_pause_total_s() - self._gc_mark) * 1e3,
                    array_rows_live=len(self.row_of), array_h2d_bytes=h2d,
                    array_d2h_bytes=d2h,
                    array_pods_waiting=len(self._waiting_rows) + len(self._deferred),
                    array_unconverged=int(unconverged),
                    array_decode_width=decode_width, array_machines_open=machines_open,
                ),
            )

    def flush_pending_bindings(self) -> int:
        """No Binding is ever deferred here. What the loop's end must not
        strand is the completions taken since the last round: they go to
        the device, so that the table the loop leaves is the cluster's."""
        self._retire_completed()
        return 0
