"""CPU and memory requests: kube-scheduler's `NodeResourcesFit` filter with
its `LeastAllocated` score and `NodeResourcesBalancedAllocation`, as a flow
network.

Every pod of a Kubernetes cluster carries CPU and memory requests, every
node an allocatable vector, and the one filter and the two scores every
kube-scheduler runs by default are these (Kubernetes documentation,
"Resource Management for Pods and Containers", "Resource Bin Packing").
Upstream's own Kubernetes integration (Poseidon over Firmament) ships a
`cpu_mem` cost model as its default for the same reason; Firmament's
`cpu_cost_model.cc` has the shape used here: one equivalence class for
each distinct request vector, arcs only to machines where the request
fits. It is the first model here under which pods differ in SIZE: a
node's capacity for a round comes from its books, not from a slot count.

Units: CPU in millicores, memory in MiB. A pod's request is
`TaskDescriptor.resource_request` (`cpu_cores` x 1000, rounded;
`ram_cap`), a node's allocatable `ResourceDescriptor.capacity` of its
machine (the same two fields) and its pod limit P(m) the slots below it.
The books of machine m are kept by the scheduler's bind and unbind events:
`reserved(m)`, the sum of the requests of the pods in its PUs'
`current_running_tasks`, and `running(m)`, their number. A pod that
completed, failed or was killed leaves both in the next round's `deltas`
phase, where it leaves its PU's list (flow_scheduler._drop_departed; the
reference's timing): the books move only between a round's solve and its
refresh of the resource tree, so what a round is priced on is what stood
at its start. `r_max` is the componentwise maximum of the requests of
every pod admitted so far (monotone; no table of sizes is held).

For machine m, at the start of a round:

    free(m)  = allocatable(m) - reserved(m)        slots(m) = P(m) - running(m)
    k(m)     = min(free_cpu(m) // r_max.cpu, free_mem(m) // r_max.mem, slots(m))
    cap(m)   = k(m) if k(m) > 0 else min(1, slots(m))

k(m) pods of ANY sizes fit m together, since each is at most `r_max`. An
arc EC(r) -> m exists iff r <= free(m) componentwise and cap(m) > 0, with
capacity cap(m), and **m takes at most cap(m) new pods in the round, over
all size classes**: `machine_intake` is that bound, and the graph manager
writes it on the arcs from the machine node to its children
(`bounds_machine_intake`: each child's free slots, no more than the bound
over all of them), the machine's own path to the sink, which every arc
into the machine shares, so no set of Bindings of one round can overcommit
a node: with k(m) > 0 any k(m) pods fit, with cap(m) = 1 the one pod's own
arc checked it. The price, from the books at the start of the round, in
integers:

    u_cpu = (reserved_cpu(m) + r.cpu) * 100 // allocatable_cpu(m),  u_mem likewise
    cost(r, m) = (u_cpu + u_mem) // 2 + abs(u_cpu - u_mem) // 2

which is 200 less the sum of the two default scores at weight 1 each
(`LeastAllocated` = mean over the resources of (allocatable - requested) x
100 / allocatable; `BalancedAllocation` = (1 - std of the used fractions)
x 100, and the standard deviation of two numbers is half their
distance): 0..150. Task -> EC(r) costs 0; leaving a pod unscheduled costs
the constant UNSCHEDULED_COST = 500. Two pods bound to one node in one
round pay the same price (Firmament without multi-arcs); prices stepped
inside a round are not modelled.

What a round pays for: an EC's arcs are brought up to date, when the
update reaches the EC, for the machines whose books a bind or an unbind
moved since the EC last listed its arcs (`equiv_class_pref_arc_changes`,
as the census keeper of costmodels/census.py does for `coco` and `whare`),
priced in one call over rows of the books (`requests_costs` span). A pod
larger than any before it moves `r_max`, so every cap(m): every EC lists
again and the graph manager re-writes every machine's bound before the
solve. The bound is written in two places: the refresh of the resource
tree after a round's Bindings reads it for the machines they touched, and
the end of every graph update re-writes it for every machine whose cap(m)
moved since the last one (`take_machine_intake_changes`), whatever moved
it: a restore's placements and an eviction between two rounds reach no
refresh before the next solve.

A machine is one bag of CPU and memory, whatever cores and PUs the
resource tree gives it: its pod limit is the slots below it. A machine
whose descriptor gives no allocatable CPU or no allocatable memory fits
only a task that asks for none (a library cluster in which nothing states
a size is priced by slots alone); the service, whose pods do ask, refuses
such a node by name (`reads_machine_allocatable`, cli.SchedulerService.add_node):
it would have no arc, and its pods would wait at 500 with no word.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..data import ResourceTopologyNodeDescriptor, ResourceType, TaskDescriptor
from ..obs.spans import span
from ..utils import equiv_class_from_bytes, resource_id_from_string
from .base import Cost
from .trivial import TrivialCostModel

Request = Tuple[int, int]  # (CPU millicores, memory MiB)


def request_ec(request: Request) -> int:
    """The equivalence class of the pods that ask for `request`."""
    return equiv_class_from_bytes(b"K8S_REQUEST_%d_%d" % request)


def request_of(td: TaskDescriptor) -> Request:
    """What the task asks for, in the model's units."""
    rr = td.resource_request
    return int(round(rr.cpu_cores * 1000)), int(rr.ram_cap)


def requests_cost(reserved_cpu, reserved_mem, alloc_cpu, alloc_mem, request: Request):
    """cost(r, m) over arrays of machines: the equation's one copy."""
    u_cpu = (reserved_cpu + request[0]) * 100 // np.maximum(1, alloc_cpu)
    u_mem = (reserved_mem + request[1]) * 100 // np.maximum(1, alloc_mem)
    return (u_cpu + u_mem) // 2 + np.abs(u_cpu - u_mem) // 2


def fit_count(free_cpu, free_mem, slots, r_max: Request):
    """k(m) over arrays of machines (below 1: some size seen so far does
    not fit m, or it has no slot left)."""
    k = slots
    if r_max[0]:
        k = np.minimum(k, free_cpu // r_max[0])
    if r_max[1]:
        k = np.minimum(k, free_mem // r_max[1])
    return k


def intake(k, slots):
    """cap(m) from k(m) and slots(m), over arrays of machines."""
    return np.where(k > 0, k, np.clip(slots, 0, 1))


class K8sRequestsCostModel(TrivialCostModel):
    # the trivial model's continuation cost and stats hooks, unchanged
    pinned_tasks_are_inert = True
    # cap(m) goes on the arcs below every machine node (base.py)
    bounds_machine_intake = True
    # requests are fitted into `capacity.cpu_cores` / `ram_cap` (base.py)
    reads_machine_allocatable = True
    # the machines an EC reaches differ in cost: the scan-CSR rung needs
    # its global price update where two pods contend for one node (base.py)
    routes_differ_in_cost = True

    UNSCHEDULED_COST = 500

    def __init__(self, resource_map, task_map, leaf_resource_ids, max_tasks_per_pu) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        #: machine -> its row of the books; a machine that left keeps its
        #: row, zeroed
        self._row: Dict[int, int] = {}
        self._rid_of_row: List[int] = []
        self._pu_machine: Dict[int, int] = {}
        # the books, one row a machine (int64: sums of MiB and millicores)
        self._alloc_cpu = np.zeros(0, np.int64)
        self._alloc_mem = np.zeros(0, np.int64)
        self._limit = np.zeros(0, np.int64)  # P(m): the slots below the machine
        self._res_cpu = np.zeros(0, np.int64)
        self._res_mem = np.zeros(0, np.int64)
        self._running = np.zeros(0, np.int64)
        #: cap(m) under the books and `r_max` as they stand
        self._cap = np.zeros(0, np.int64)
        self.r_max: Request = (0, 0)
        #: task -> (row, request) while the task counts on the books
        self._where: Dict[int, Tuple[int, Request]] = {}
        self._ec_request: Dict[int, Request] = {}
        #: EC -> the machines whose books moved since the EC last listed
        #: its arcs; no entry: it never listed, or `r_max` moved since
        self._owed: Dict[int, Set[int]] = {}
        #: machines whose books moved since round_books was last read
        self._dirty: Set[int] = set()
        #: machines whose cap(m) moved since take_machine_intake_changes
        #: was last called
        self._intake_changed: Set[int] = set()

    # -- the books ---------------------------------------------------------

    def _grow(self, rows: int) -> None:
        if rows <= len(self._cap):
            return
        size = max(rows, 2 * len(self._cap), 64)
        for name in ("_alloc_cpu", "_alloc_mem", "_limit", "_res_cpu", "_res_mem",
                     "_running", "_cap"):
            old = getattr(self, name)
            new = np.zeros(size, np.int64)
            new[: len(old)] = old
            setattr(self, name, new)

    def _fit_count(self, rows) -> np.ndarray:
        """k(m) of `rows` (an index, a slice or an array of them)."""
        return fit_count(
            self._alloc_cpu[rows] - self._res_cpu[rows],
            self._alloc_mem[rows] - self._res_mem[rows],
            self._limit[rows] - self._running[rows], self.r_max,
        )

    def _intake(self, rows) -> np.ndarray:
        """cap(m) of `rows`."""
        return intake(self._fit_count(rows), self._limit[rows] - self._running[rows])

    def _moved(self, row: int, request: Request, sign: int) -> None:
        """A pod that asks for `request` joined (+1) or left (-1) the
        books of the machine at `row`."""
        self._res_cpu[row] += sign * request[0]
        self._res_mem[row] += sign * request[1]
        self._running[row] += sign
        rid = self._rid_of_row[row]
        cap = self._intake(row)
        if cap != self._cap[row]:
            self._cap[row] = cap
            self._intake_changed.add(rid)
        self._dirty.add(rid)
        for owed in self._owed.values():
            owed.add(rid)

    def _note_request(self, request: Request) -> None:
        """`r_max` takes `request` in; where it grows, every cap(m) may
        have moved."""
        grown = (max(self.r_max[0], request[0]), max(self.r_max[1], request[1]))
        if grown == self.r_max:
            return
        self.r_max = grown
        n = len(self._rid_of_row)
        before = self._cap[:n].copy()
        self._cap[:n] = self._intake(slice(0, n))
        rids = self._rid_of_row
        self._intake_changed.update(rids[i] for i in np.nonzero(before != self._cap[:n])[0])
        self._owed.clear()  # every EC's capacities are stale: each lists again

    def books(self) -> Dict[int, Tuple[int, int, int]]:
        """machine -> (reserved CPU, reserved memory, pods) as the events
        keep them."""
        return {
            rid: (int(self._res_cpu[i]), int(self._res_mem[i]), int(self._running[i]))
            for rid, i in self._row.items()
        }

    # -- events (FlowScheduler's bindings bookkeeping) ----------------------

    def task_bound(self, td: TaskDescriptor, pu_rid: int) -> None:
        machine = self._pu_machine.get(pu_rid)
        if machine is None or td.uid in self._where:
            return
        request = request_of(td)
        self._note_request(request)
        row = self._row[machine]
        self._where[td.uid] = (row, request)
        self._moved(row, request, +1)

    def task_unbound(self, task_id: int, pu_rid: int) -> None:
        where = self._where.pop(task_id, None)
        if where is None:
            return
        row, request = where
        if self._rid_of_row[row] not in self._row:
            return  # the machine left, and its books with it
        self._moved(row, request, -1)

    def task_class_fields(self, task_class: int) -> Dict[str, object]:
        # a pod's size class rides its requests; the index is not read
        return {}

    # -- what the graph manager and the scheduler read ---------------------

    def machine_intake(self, resource_id: int) -> int:
        row = self._row.get(resource_id)
        return 0 if row is None else int(self._cap[row])

    def take_machine_intake_changes(self) -> List[int]:
        changed, self._intake_changed = self._intake_changed, set()
        return list(changed)

    def round_books(self) -> Tuple[int, int, int]:
        n = len(self._rid_of_row)
        live = slice(0, n)
        # a machine that left has no limit; one that is there has one
        gated = (self._limit[live] > 0) & (self._fit_count(live) <= 0)
        dirty, self._dirty = len(self._dirty), set()
        return dirty, int(gated.sum()), int(self._cap[live].sum())

    # -- arc costs ---------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return self.UNSCHEDULED_COST

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return 0

    def _price(self, request: Request, rows) -> Tuple[np.ndarray, np.ndarray]:
        """(cost(r, m), capacity of EC(r) -> m) for the machines at
        `rows`; capacity 0: no arc."""
        res_cpu, res_mem = self._res_cpu[rows], self._res_mem[rows]
        alloc_cpu, alloc_mem = self._alloc_cpu[rows], self._alloc_mem[rows]
        fits = (res_cpu + request[0] <= alloc_cpu) & (res_mem + request[1] <= alloc_mem)
        cost = requests_cost(res_cpu, res_mem, alloc_cpu, alloc_mem, request)
        return cost, np.where(fits, self._cap[rows], 0)

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        costs, caps = self.ec_to_resource_batch(ec, [resource_id])
        return costs[0], caps[0]

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        """An EC's arcs to any machines in one call, over their rows of
        the books, inside a `requests_costs` span whose `machines` is the
        number priced."""
        request = self._ec_request[ec]
        with span("requests_costs", machines=len(resource_ids)):
            row = self._row
            rows = np.fromiter((row[rid] for rid in resource_ids), np.int64, len(resource_ids))
            cost, cap = self._price(request, rows)
            return cost.tolist(), cap.tolist()

    # -- preference enumeration --------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        td = self.task_map.find(task_id)
        if td is None:
            raise KeyError(f"no task descriptor for {task_id}")
        request = request_of(td)
        self._note_request(request)  # a pod re-delivered with a larger request
        ec = request_ec(request)
        self._ec_request[ec] = request
        return [ec]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        request = self._ec_request.get(ec)
        if request is None:
            return []
        self._owed[ec] = set()
        n = len(self._rid_of_row)
        _cost, cap = self._price(request, slice(0, n))
        rids = self._rid_of_row  # (a machine that left has cap 0 on its zeroed row)
        return [rids[i] for i in np.nonzero(cap > 0)[0].tolist()]

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        owed = self._owed.get(ec)
        if owed is None or ec not in self._ec_request:
            return None
        self._owed[ec] = set()
        return sorted(owed, key=self._row.__getitem__)

    # -- lifecycle ---------------------------------------------------------

    def add_task(self, task_id: int) -> None:
        td = self.task_map.find(task_id)
        if td is not None:
            self._note_request(request_of(td))

    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        machine = resource_id_from_string(rtnd.resource_desc.uuid)
        if machine in self._row:
            return
        super().add_machine(rtnd)
        pus = 0
        stack = list(rtnd.children)
        while stack:
            cur = stack.pop()
            if cur.resource_desc.type == ResourceType.PU:
                pus += 1
                self._pu_machine[resource_id_from_string(cur.resource_desc.uuid)] = machine
            stack.extend(cur.children)
        row = len(self._rid_of_row)
        self._grow(row + 1)
        self._row[machine] = row
        self._rid_of_row.append(machine)
        capacity = rtnd.resource_desc.capacity
        self._alloc_cpu[row] = int(round(capacity.cpu_cores * 1000))
        self._alloc_mem[row] = int(capacity.ram_cap)
        self._limit[row] = pus * self.max_tasks_per_pu
        self._cap[row] = self._intake(row)
        for owed in self._owed.values():
            owed.add(machine)

    def remove_machine(self, resource_id: int) -> None:
        super().remove_machine(resource_id)
        row = self._row.pop(resource_id, None)
        if row is None:
            return
        for books in (self._alloc_cpu, self._alloc_mem, self._limit, self._res_cpu,
                      self._res_mem, self._running, self._cap):
            books[row] = 0
        for pu in [p for p, m in self._pu_machine.items() if m == resource_id]:
            del self._pu_machine[pu]
        self._dirty.discard(resource_id)
        self._intake_changed.discard(resource_id)
        # its node goes, and every arc into it with it
        for owed in self._owed.values():
            owed.discard(resource_id)
