"""Quincy: the data-locality policy the flow-scheduling architecture was
built for, with its rack tier.

Quincy (Isard et al., SOSP'09, section 4.2) as Firmament evaluates it
(Gog et al., OSDI'16, section 7). The reference enumerates MODEL_QUINCY
(costmodel/interface.go:38) without implementing it. The graph, over
the rebuild's node kinds:

    task t --> C_m     a preferred computer (a machine node)
    task t --> R_l     a preferred rack (an equivalence class)
    task t --> X       the cluster aggregator (CLUSTER_AGGREGATOR_EC)
    task t --> U_j     its job's unscheduled aggregator
    X --> R_l --> C_m  for every rack l and every machine m of l with a
                       free slot, cost 0, capacities that never bind:
                       free(m) on R_l -> C_m, their sum F(l) on X -> R_l
    C_m --> core --> PU --> sink as the trivial model has them

A task reads blocks b (`TaskDescriptor.dependencies`: id and size; the
registry `blocks` has the machines H(b) that hold a replica). For a
machine m in rack l (`ResourceDescriptor.labels[RACK_LABEL]`, "" where
a machine carries none: one rack):

    total     = sum of s_b
    local(m)  = sum of s_b with m in H(b)
    inrack(l) = sum of s_b with some replica in rack l

and, in units of the quantum Q = QUANTUM bytes, each cost the WHOLE
weighted byte sum divided by Q and rounded down (never term by term):

    d(t, m)   = (PSI * (inrack(l) - local(m)) + XI * (total - inrack(l))) // Q
                for m with local(m) >= DELTA * total
    rho(t, l) = (PSI * inrack(l) + XI * (total - inrack(l))) // Q
                for l with inrack(l) >= DELTA * total: the worst
                computer of the rack holds nothing
    alpha(t)  = (XI * total) // Q        on t -> X
    u(t)      = alpha(t) + 1 + OMEGA * rounds_waited      on t -> U_j

PSI prices a byte through a rack switch, XI one through the core
switch, DELTA = 14% (`DELTA_PCT`; compared in whole numbers,
100 * held >= DELTA_PCT * total). d <= rho <= alpha, since PSI <= XI,
so the cheapest route a task has to a machine is its machine arc, else
its rack arc, else X; waiting always costs more than X, so no pod waits
while a slot is free.

Departures, each stated because the plain reference
(benchmarks/reference_quincy.py) restates it:

- At most MAX_PREFS = 7 preferred machines and 7 preferred racks a
  task: with three replicas a block the 14% rule alone admits 21 and
  14. Kept: those holding most, ties to the one met first in the order
  of the task's blocks and, within a block, of its replicas as the
  registry was given them.
- Costs stop at a stated largest cost: routes at `largest_cost` - 1, u at
  `largest_cost`, so that waiting still costs more than any route. The
  scan-CSR rung scales costs by the node count and needs
  largest cost x node bucket < 2^30 (solver/jax_solver.py):
  `largest_cost` is what `cli.build_service` holds a cluster to. With
  Q = 16 MiB an input up to 8,176 MiB is priced exactly.
- A preference arc to a machine with no free slot is not listed (nor
  R_l -> C_m to one): without preemption C_m's arcs below have capacity
  0 and it could carry no flow; a round's optimum is the same, a
  push-relabel solve does not walk into it. R_l -> C_m has capacity
  free(m) and X -> R_l their sum over the rack, F(l): what lies below
  the arc can take no more, so neither binds, and a discharge of X does
  not pour a round's pods into one rack that has to send them back (a
  capacity of the cluster's slots did: 1,107 supersteps to fill 312
  machines). The dense collapse (solver/graph_collapse.py) reads a
  chain arc as non-binding only where it could carry every task above
  it, so it answers for a round of fewer pods than the emptiest rack
  has free slots and refuses, conservatively, above.
- rounds_waited stops where u has reached `largest_cost`.
- free(m) follows the PU lists' timing: a completed pod gives its slot
  back in the next round's `deltas` phase (flow_scheduler._drop_departed).
- A running task keeps its running arc only (no preemption: it is
  pinned and inert). Under `--preemption` this model lists no
  preference for a running task either
  (`update_preferences_running_task` is off).

The registry forgets: a block leaves it when the last task that read it
completes, fails or is removed, so a service under steady arrivals holds
the blocks of its live tasks and no more.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..data import (
    RACK_LABEL,
    ReferenceDescriptor,
    ReferenceType,
    ResourceTopologyNodeDescriptor,
    ResourceType,
    TaskDescriptor,
)
from ..utils import ResourceMap, TaskMap, equiv_class_from_bytes, resource_id_from_string
from .base import CLUSTER_AGGREGATOR_EC, Cost
from .trivial import TrivialCostModel

MB = 1 << 20

#: one input block as the cluster API hands it over: (block id, bytes,
#: the machines that hold a replica, as resource ids)
Block = Tuple[int, int, Sequence[int]]


def rack_ec(rack: str) -> int:
    """The equivalence class of the rack aggregator R_l of rack `rack`."""
    return equiv_class_from_bytes(b"QUINCY_RACK_" + rack.encode())


class BlockRegistry:
    """block id -> machines holding a replica (the GFS/TidyFS view Quincy
    reads; here a first-class registry fed by the cluster API through
    `QuincyCostModel.task_input_fields`, or by a driver), with the
    number of live tasks that read each block: the last one to go takes
    the block with it."""

    def __init__(self) -> None:
        self._locations: Dict[int, List[int]] = {}
        self._sizes: Dict[int, int] = {}
        self._readers: Dict[int, int] = {}

    def __len__(self) -> int:
        return len(self._locations)

    def __contains__(self, block_id: int) -> bool:
        return block_id in self._locations

    def register(self, block_id: int, size: int, machine_ids: Iterable[int]) -> None:
        holders = self._locations.setdefault(block_id, [])
        for m in machine_ids:
            if m not in holders:
                holders.append(m)
        self._sizes[block_id] = size

    def drop_machine(self, machine_id: int) -> None:
        for holders in self._locations.values():
            if machine_id in holders:
                holders.remove(machine_id)

    def holders(self, block_id: int) -> Sequence[int]:
        """The machines that hold a replica, in the order given."""
        return self._locations.get(block_id, ())

    def size(self, block_id: int) -> int:
        return self._sizes.get(block_id, 0)

    def acquire(self, block_id: int) -> None:
        self._readers[block_id] = self._readers.get(block_id, 0) + 1

    def release(self, block_id: int) -> None:
        """A task that read the block is gone; the last one takes it."""
        left = self._readers.get(block_id, 0) - 1
        if left > 0:
            self._readers[block_id] = left
            return
        self._readers.pop(block_id, None)
        self._locations.pop(block_id, None)
        self._sizes.pop(block_id, None)


class _Input:
    """What a task's input comes to, in the docstring's terms: fixed for
    the task's life (blocks do not move), but for a machine that leaves."""

    __slots__ = ("total", "local", "alpha", "machines", "racks")

    def __init__(self) -> None:
        self.total = 0
        self.local: Dict[int, int] = {}  # machine -> local(m), every holder
        self.alpha = 0
        self.machines: List[Tuple[int, Cost]] = []  # preferred (m, d(t, m))
        self.racks: List[Tuple[str, Cost]] = []  # preferred (l, rho(t, l))


_NO_INPUT = _Input()


class QuincyCostModel(TrivialCostModel):
    # the trivial model's continuation cost, stats hooks and resource
    # arc prices, unchanged: said again so that a reader of the walks of
    # PRs 25-36 finds the claims where the policy is
    pinned_tasks_are_inert = True
    resource_arc_costs_are_fixed = True
    #: the graph manager opens a `pref_refresh` span around the
    #: preference half of a task's turn (GraphManager._update_task_node)
    lists_task_preferences = True
    #: d < rho < alpha: contended slots are settled by price (base.py)
    routes_differ_in_cost = True

    QUANTUM = 16 * MB  # Q: bytes a cost unit
    PSI = 1  # a quantum read through a rack switch
    XI = 2  # a quantum read through the core switch
    DELTA_PCT = 14  # a machine or rack is preferred from this share of the input on
    MAX_PREFS = 7  # preferred machines, and preferred racks, a task
    OMEGA = 10  # added to u(t) for each round waited
    #: no arc of this model costs more (base.py): u(t) stops here, a
    #: route one below
    largest_cost = 1023

    def __init__(
        self,
        resource_map: ResourceMap,
        task_map: TaskMap,
        leaf_resource_ids,
        max_tasks_per_pu: int,
    ) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        self.blocks = BlockRegistry()
        self._wait_rounds: Dict[int, int] = {}
        self._wait_cap = self.largest_cost // self.OMEGA + 1
        #: task -> its input's sums, made at the first question about it
        #: and dropped when it is bound (a pinned task is asked nothing)
        self._inputs: Dict[int, _Input] = {}
        #: task -> the blocks it holds in the registry, while it lives
        self._reads: Dict[int, Tuple[int, ...]] = {}
        #: machine -> rack, slots, pods that hold a slot; PU -> machine
        self._machine_rack: Dict[int, str] = {}
        self._slots: Dict[int, int] = {}
        self._load: Dict[int, int] = {}
        self._pu_machine: Dict[int, int] = {}
        self._rack_free: Dict[str, int] = {}  # rack -> F(l)
        #: rack -> its machines in the order they joined; R_l's
        #: equivalence class and back; racks in the order they appeared
        self._rack_machines: Dict[str, Dict[int, None]] = {}
        self._rack_ec: Dict[str, int] = {}
        self._ec_rack: Dict[int, str] = {}
        #: rack -> machines whose arc from R_l may have changed since
        #: its arcs were last listed; a key exists from the first listing on
        self._changed: Dict[str, Set[int]] = {}
        #: task -> machine while it holds a slot there
        self._on_machine: Dict[int, int] = {}
        #: what the round in progress bound, by the cheapest route the
        #: task had to its machine, and the bytes those tasks read and
        #: read from another machine (round_locality)
        self._bound_via = [0, 0, 0]
        self._bytes_read = 0
        self._bytes_remote = 0

    # -- the cluster API's seam --------------------------------------------

    def task_input_fields(self, blocks: Sequence[Block]) -> Dict[str, object]:
        """`PodEvent.inputs`, nodes already resolved to machines: the
        blocks go into the registry, the task's descriptor gets one
        dependency a block."""
        deps = []
        for block_id, size, machines in blocks:
            self.blocks.register(block_id, size, machines)
            deps.append(ReferenceDescriptor(id=block_id, type=ReferenceType.CONCRETE, size=size))
        return {"dependencies": deps}

    # -- locality arithmetic ----------------------------------------------

    def _preferred(self, held: int, total: int) -> bool:
        return 100 * held >= self.DELTA_PCT * total

    def _input(self, task_id: int) -> _Input:
        got = self._inputs.get(task_id)
        if got is not None:
            return got
        td = self.task_map.find(task_id)
        if td is None or not td.dependencies:
            return _NO_INPUT
        inp = self._inputs[task_id] = _Input()
        inrack: Dict[str, int] = {}
        rack_of = self._machine_rack
        total = 0
        for dep in td.dependencies:
            size = dep.size or self.blocks.size(dep.id)
            total += size
            seen_racks = []
            for m in self.blocks.holders(dep.id):
                rack = rack_of.get(m)
                if rack is None:
                    continue  # a holder that is no machine of this cluster
                inp.local[m] = inp.local.get(m, 0) + size
                if rack not in seen_racks:
                    seen_racks.append(rack)
                    inrack[rack] = inrack.get(rack, 0) + size
        if task_id not in self._reads:
            reads = self._reads[task_id] = tuple(dep.id for dep in td.dependencies)
            for block_id in reads:
                self.blocks.acquire(block_id)
        inp.total = total
        q, psi, xi, top = self.QUANTUM, self.PSI, self.XI, self.largest_cost - 1
        inp.alpha = min(xi * total // q, top)
        # dicts keep insertion order (the order met); a stable sort by
        # bytes held, most first, leaves ties in it
        for m, held in sorted(inp.local.items(), key=lambda kv: -kv[1])[: self.MAX_PREFS]:
            if self._preferred(held, total):
                near = inrack[rack_of[m]]
                inp.machines.append((m, min((psi * (near - held) + xi * (total - near)) // q, top)))
        for rack, near in sorted(inrack.items(), key=lambda kv: -kv[1])[: self.MAX_PREFS]:
            if self._preferred(near, total):
                inp.racks.append((rack, min((psi * near + xi * (total - near)) // q, top)))
        return inp

    def _forget_input(self, task_id: int) -> None:
        """The task is gone: its sums, and its hold on its blocks."""
        self._inputs.pop(task_id, None)
        for block_id in self._reads.pop(task_id, ()):
            self.blocks.release(block_id)

    def preferred_machines(self, task_id: int) -> List[int]:
        """The machines the task has a preference arc to, full or not."""
        return [m for m, _cost in self._input(task_id).machines if m in self._slots]

    # -- arc costs --------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        waited = self._wait_rounds.get(task_id, 0)
        return min(self._input(task_id).alpha + 1 + self.OMEGA * waited, self.largest_cost)

    def task_to_resource_node_cost(self, task_id: int, resource_id: int) -> Cost:
        inp = self._input(task_id)
        for m, cost in inp.machines:
            if m == resource_id:
                return cost
        return inp.alpha

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        inp = self._input(task_id)
        if ec != CLUSTER_AGGREGATOR_EC:
            rack = self._ec_rack.get(ec)
            for l, cost in inp.racks:
                if l == rack:
                    return cost
        return inp.alpha

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        return 0, self._rack_free.get(self._ec_rack.get(ec2), 0)

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        # R_l -> C_m (X lists no machine); 0 for a machine that has left
        return 0, self._slots.get(resource_id, 0) - self._load.get(resource_id, 0)

    # -- preference enumeration -------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        if self.task_map.find(task_id) is None:
            raise KeyError(f"no task descriptor for {task_id}")
        ecs = [CLUSTER_AGGREGATOR_EC]
        ecs.extend(self._rack_ec[l] for l, _cost in self._input(task_id).racks if l in self._rack_ec)
        return ecs

    def get_task_preference_arcs(self, task_id: int) -> List[int]:
        load, slots = self._load, self._slots
        return [
            m for m, _cost in self._input(task_id).machines
            if m in slots and load[m] < slots[m]
        ]

    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
        if ec != CLUSTER_AGGREGATOR_EC:
            return []
        return list(self._ec_rack)

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        rack = self._ec_rack.get(ec)
        if rack is None:
            return []  # X reaches machines through the racks alone
        self._changed[rack] = set()
        load, slots = self._load, self._slots
        return [m for m in self._rack_machines[rack] if load[m] < slots[m]]

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        rack = self._ec_rack.get(ec)
        if rack is None:
            return []  # X: its arcs go to racks, none to a resource
        changed = self._changed.get(rack)
        if changed is None:
            return None
        out = sorted(changed)
        changed.clear()
        return out

    # -- events ------------------------------------------------------------

    def _touch(self, machine: int, load_delta: int) -> None:
        """A pod took or gave back a slot of `machine`."""
        self._load[machine] += load_delta
        rack = self._machine_rack[machine]
        self._rack_free[rack] -= load_delta
        changed = self._changed.get(rack)
        if changed is not None:
            changed.add(machine)

    def task_bound(self, td: TaskDescriptor, pu_rid: int) -> None:
        machine = self._pu_machine.get(pu_rid)
        if machine is None or td.uid in self._on_machine:
            return
        self._on_machine[td.uid] = machine
        self._touch(machine, +1)
        self._wait_rounds.pop(td.uid, None)
        inp = self._inputs.pop(td.uid, None)
        if inp is None:
            return  # it reads nothing: no route is better than another
        if any(m == machine for m, _cost in inp.machines):
            via = 0
        elif any(l == self._machine_rack[machine] for l, _cost in inp.racks):
            via = 1
        else:
            via = 2
        self._bound_via[via] += 1
        self._bytes_read += inp.total
        self._bytes_remote += inp.total - inp.local.get(machine, 0)

    def task_unbound(self, task_id: int, pu_rid: int) -> None:
        machine = self._on_machine.pop(task_id, None)
        if machine is not None and machine in self._slots:
            self._touch(machine, -1)

    def round_locality(self) -> Optional[Tuple[int, int, int, int, int]]:
        return (*self._bound_via, self._bytes_read, self._bytes_remote)

    def add_task(self, task_id: int) -> None:
        self._wait_rounds.setdefault(task_id, 0)

    def remove_task(self, task_id: int) -> None:
        self._wait_rounds.pop(task_id, None)
        self._forget_input(task_id)

    def record_task_completion(self, td: TaskDescriptor) -> None:
        # a completed task stays the graph manager's until its node goes;
        # nothing asks for its input again
        self._wait_rounds.pop(td.uid, None)
        self._forget_input(td.uid)

    def note_round(self, unscheduled_task_ids) -> None:
        """After a round: the tasks that stayed unscheduled have waited
        one more (Quincy's starvation bound; it stops where u(t) has
        reached the largest cost), and the round's counts start again."""
        cap = self._wait_cap
        waits = self._wait_rounds
        for t in unscheduled_task_ids:
            waited = waits.get(t)
            if waited is not None and waited < cap:
                waits[t] = waited + 1
        self._bound_via = [0, 0, 0]
        self._bytes_read = self._bytes_remote = 0

    # -- lifecycle ---------------------------------------------------------

    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None:
        super().add_machine(rtnd)
        machine = resource_id_from_string(rtnd.resource_desc.uuid)
        if machine in self._slots:
            return
        pus = 0
        stack = list(rtnd.children)
        while stack:
            cur = stack.pop()
            if cur.resource_desc.type == ResourceType.PU:
                pus += 1
                self._pu_machine[resource_id_from_string(cur.resource_desc.uuid)] = machine
            stack.extend(cur.children)
        rack = rtnd.resource_desc.labels.get(RACK_LABEL, "")
        if rack not in self._rack_machines:
            self._rack_machines[rack] = {}
            self._rack_free[rack] = 0
            self._rack_ec[rack] = rack_ec(rack)
            self._ec_rack[self._rack_ec[rack]] = rack
        self._rack_machines[rack][machine] = None
        self._machine_rack[machine] = rack
        self._slots[machine] = pus * self.max_tasks_per_pu
        self._load[machine] = 0
        self._rack_free[rack] += self._slots[machine]
        changed = self._changed.get(rack)
        if changed is not None:
            changed.add(machine)

    def remove_machine(self, resource_id: int) -> None:
        super().remove_machine(resource_id)
        self.blocks.drop_machine(resource_id)
        slots = self._slots.pop(resource_id, None)
        if slots is None:
            return
        rack = self._machine_rack.pop(resource_id)
        self._rack_free[rack] -= slots - self._load.pop(resource_id)
        del self._rack_machines[rack][resource_id]
        if not self._rack_machines[rack]:
            del self._rack_machines[rack], self._rack_free[rack]
            del self._ec_rack[self._rack_ec.pop(rack)]
            self._changed.pop(rack, None)
        for pu in [p for p, m in self._pu_machine.items() if m == resource_id]:
            del self._pu_machine[pu]
        changed = self._changed.get(rack)
        if changed is not None:
            changed.discard(resource_id)
        # sums that counted the machine are made again when next asked for
        for task_id in [t for t, inp in self._inputs.items() if resource_id in inp.local]:
            del self._inputs[task_id]
