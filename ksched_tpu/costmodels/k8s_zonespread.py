"""A hard zone topology-spread constraint: a workload's pods stay level
across the zones, within `maxSkew`.

Kubernetes' `topologySpreadConstraints` with `topologyKey:
topology.kubernetes.io/zone`, `whenUnsatisfiable: DoNotSchedule` and a
label selector on the pod's own label (scheduler_perf
`TopologySpreading`, `pod-with-topology-spreading.yaml`) as a flow
network. It is the first model here that lists an EC -> EC arc
(`get_equiv_class_to_equiv_classes_arcs` is not empty) and whose
`equiv_class_to_equiv_class` returns a capacity that BINDS: a chain of
two equivalence classes, task -> EC(g) -> ZONE(z) -> machine, which the
dense collapse refuses (docs/solver_coverage.md, item 5), so every round
that allots a workload to more than one zone is solved on the general
CSR rung.

Workloads g = `PodEvent.task_class` (rides `TaskDescriptor.workload`).
Zones z of Z = the values of the label ZONE_LABEL over the machines, in
the order of the values (as strings); a machine without the label is in
the zone "". s = MAX_SKEW. n(g, z) = the pods of g bound to a machine of
z whose completion the scheduler has not yet been handed: n falls AT the
completion event (`record_task_completion`; at once too for a failure,
a kill, an eviction), not at the drop one round later, because an
over-count of the lowest zone would let the others run ahead of it.
K(g) = the runnable pods of g this round; R = the sum of K over the
workloads; F(z) = the free slots of zone z. With the trivial model's
constants (e = CLUSTER_AGG_COST = 2, u = UNSCHEDULED_COST = 5), per
round:

- task t of workload g: one arc t -> EC(g), capacity 1, cost e; one arc
  t -> its job's unscheduled aggregator, capacity 1, cost u; NO arc to
  CLUSTER_AGGREGATOR_EC (it would route around the rule);
- EC(g) -> ZONE(z): cost 0, capacity a(g, z), no arc where a(g, z) = 0.
  The water level L(g) is the largest L with
  sum_z max(0, L - n(g, z)) <= K(g); b(g, z) = max(0, L(g) - n(g, z));
  r(g) = K(g) - sum_z b(g, z); a(g, z) = b(g, z) + 1 for the first r(g)
  zones, in zone order, with n(g, z) <= L(g), and b(g, z) for the
  rest. So sum_z a(g, z) = K(g);
- when room is short, F(z) < R for some z:
  a(g, z) = max(0, min_z' n(g, z') + s - n(g, z)), the per-pod predicate
  against the round's opening counts, which is safe whatever is left
  unplaced. Counted: `spread_fallback`;
- ZONE(z) -> machine m, for m of zone z with a free slot: capacity
  free(m), cost 0;
- the machine subtree, PU -> sink and the running arcs exactly as the
  trivial model has them (a pinned task is inert).

The guarantee `topology_spread` (the batch form of kube-scheduler's
per-pod filter): after every round's Bindings, for every workload g and
every zone z that received a pod of g in that round,
f(g, z) <= min_z' f(g, z') + s, f counted over Bindings less
completions. It holds iff some serial order of the round's placements
passes kube's filter pod by pod (a pod may join z iff
f(z) + 1 <= min_z' f(z') + s at that moment): place next into the
receiving zone that is lowest. Only if: z's last pod passed the filter,
so z's final count was at most the minimum of that moment plus s, and
counts only rise within a round. If: let the step into z fail,
f(z) + 1 > min + s; z's final count is at least f(z) + 1 and at most the
final minimum plus s, so the zone at the minimum is below its final
count, still to receive, and lower than z (s >= 1), against the choice.

Why the allotment keeps it. With room (F(z) >= R for every z) every
zone can take every pod it is allotted, e < u places all K(g) pods of
every workload, every chain arc is saturated, so zone z ends at
n(g, z) + a(g, z): at L(g) or above for every zone, at L(g) + 1 or
below for every receiver. The allotment is stricter than s asks
(receivers end within 1 of the lowest zone, whatever s is); with cost 0
on every chain arc nothing is lost by it, since every pod is placed
either way. s acts in the fallback and in the check. In the fallback a
receiver ends at most at min_z' n(g, z') + s, and the minimum does not
fall within a round.

Departures from the equations, none of which moves a round's objective:

- EC(g) outlives its last runnable task by two rounds, as every EC node
  does (the graph manager's purge); ZONE(z) lives while any EC(g) that
  once listed it does, so its ~|machines| / |Z| arcs are not re-listed
  when no workload happens to be allotted to it. No arc enters EC(g)
  without a runnable task, so neither carries flow then.
- EC(g)'s chain arcs are recomputed when the update reaches EC(g), that
  is in the rounds in which g has a runnable task, and stand stale in
  between. ZONE(z)'s arcs are brought up to date when the update
  reaches it (a chain arc was listed to it this round), from the
  machines events touched since (`equiv_class_pref_arc_changes`): a
  bind, an unbind, a drop of the departed, a machine joining or leaving.
- free(m) and F(z) follow the PU lists' timing, not n's: a completed
  pod gives its slot back in the next round's `deltas` phase
  (flow_scheduler._drop_departed; the reference's timing). That
  under-counts room, the safe side.
- K(g) counts the pods of g that hold a task node when the update
  reaches EC(g). In a job tree deeper than a root and its children (the
  service builds no such tree) a pod below may get its node later in the
  same update; it is then not allotted for and waits a round.
- A machine's slots are counted once, when it is added (PUs below it x
  max_tasks_per_pu), and its zone is read then.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..data import ZONE_LABEL, ResourceTopologyNodeDescriptor, ResourceType, TaskDescriptor
from ..utils import equiv_class_from_bytes, resource_id_from_string
from .base import Cost
from .trivial import TrivialCostModel


def workload_ec(group: int) -> int:
    """The equivalence class of workload `group`."""
    return equiv_class_from_bytes(b"K8S_SPREAD_WORKLOAD_%d" % group)


def zone_ec(zone: str) -> int:
    """The equivalence class of the zone whose label value is `zone`."""
    return equiv_class_from_bytes(b"K8S_SPREAD_ZONE_" + zone.encode())


def water_level(counts: Sequence[int], pods: int) -> Tuple[int, List[int]]:
    """(L, a): the water level of `pods` poured over zones that hold
    `counts`, and what each zone is allotted (the docstring's L(g) and
    a(g, .), zones in the order given)."""
    order = sorted(counts)
    level, left, at_level = order[0], pods, 1
    while at_level < len(order) and (order[at_level] - level) * at_level <= left:
        # the zones at the level rise to the next zone's count
        left -= (order[at_level] - level) * at_level
        level = order[at_level]
        at_level += 1
    level += left // at_level
    left %= at_level
    allot = [max(0, level - c) for c in counts]
    for z, c in enumerate(counts):
        if left and c <= level:
            allot[z] += 1
            left -= 1
    return level, allot


class K8sZoneSpreadCostModel(TrivialCostModel):
    # the trivial model's continuation cost and stats hooks, unchanged
    pinned_tasks_are_inert = True

    #: s, `maxSkew`: Kubernetes' built-in default constraint for the
    #: zone key has 5
    MAX_SKEW = 5

    def __init__(self, resource_map, task_map, leaf_resource_ids, max_tasks_per_pu) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        self._ec_group: Dict[int, int] = {}
        #: ZONE(z)'s equivalence class -> z, and back
        self._ec_zone: Dict[int, str] = {}
        self._zone_ec: Dict[str, int] = {}
        #: zone label values, sorted: "zone order"
        self._zones: List[str] = []
        self._machine_zone: Dict[int, str] = {}
        #: zone -> its machines, in the order they joined
        self._zone_machines: Dict[str, Dict[int, None]] = {}
        #: zone -> F(z)
        self._zone_free: Dict[str, int] = {}
        #: m -> pods that hold a slot of it, and its slots
        self._load: Dict[int, int] = {}
        self._slots: Dict[int, int] = {}
        self._pu_machine: Dict[int, int] = {}
        #: g -> zone -> n(g, z), and task -> (g, z) while it counts in n
        self._n: Dict[int, Dict[str, int]] = {}
        self._counted: Dict[int, Tuple[int, str]] = {}
        #: task -> machine while it holds a slot there
        self._on_machine: Dict[int, int] = {}
        #: the runnable tasks that hold a node, each with its workload,
        #: and g -> K(g), how many of them are of g
        self._waiting: Dict[int, int] = {}
        self._runnable: Dict[int, int] = {}
        #: g -> zone -> a(g, z) above zero, as the last update of EC(g) set it
        self._allot: Dict[int, Dict[str, int]] = {}
        #: zone -> machines whose arc from ZONE(z) may have changed since
        #: its arcs were last listed; a key exists from the first listing on
        self._changed: Dict[str, Set[int]] = {}
        self.spread_fallback = 0

    # -- the rule ----------------------------------------------------------

    def _ec(self, group: int) -> int:
        ec = workload_ec(group)
        self._ec_group[ec] = group
        return ec

    def allotment(self, group: int) -> Dict[str, int]:
        """zone -> a(group, zone), the entries above zero, for the round
        as the model's counts stand."""
        zones = self._zones
        pods = self._runnable.get(group, 0)
        if not zones or not pods:
            return {}
        held = self._n.get(group, {})
        counts = [held.get(z, 0) for z in zones]
        runnable = len(self._waiting)
        if any(self._zone_free[z] < runnable for z in zones):
            self.spread_fallback = 1
            ceiling = min(counts) + self.MAX_SKEW
            allot = [max(0, ceiling - c) for c in counts]
        else:
            _level, allot = water_level(counts, pods)
        return {z: a for z, a in zip(zones, allot) if a > 0}

    def _touch(self, machine: int, load_delta: int) -> None:
        """A pod took or gave back a slot of `machine`."""
        zone = self._machine_zone[machine]
        self._load[machine] += load_delta
        self._zone_free[zone] -= load_delta
        changed = self._changed.get(zone)
        if changed is not None:
            changed.add(machine)

    def _wait(self, task_id: int, group: int) -> None:
        """The task is runnable, holds a node and is of `group`."""
        old = self._waiting.get(task_id)
        if old == group:
            return
        if old is not None:
            self._runnable[old] -= 1
        self._waiting[task_id] = group
        self._runnable[group] = self._runnable.get(group, 0) + 1

    def _unwait(self, task_id: int) -> None:
        group = self._waiting.pop(task_id, None)
        if group is not None:
            self._runnable[group] -= 1

    def _uncount(self, task_id: int) -> bool:
        """The task no longer counts in n; whether it did."""
        where = self._counted.pop(task_id, None)
        if where is None:
            return False
        group, zone = where
        held = self._n[group]
        if held[zone] == 1:
            del held[zone]
        else:
            held[zone] -= 1
        return True

    # -- events ------------------------------------------------------------

    def add_task(self, task_id: int) -> None:
        td = self.task_map.find(task_id)
        if td is None:
            raise KeyError(f"no task descriptor for {task_id}")
        self._wait(task_id, td.workload)

    def remove_task(self, task_id: int) -> None:
        # failed or killed: it stops counting now; its slot comes back
        # with task_unbound, in the next `deltas` phase
        self._unwait(task_id)
        self._uncount(task_id)

    def record_task_completion(self, td: TaskDescriptor) -> None:
        self._uncount(td.uid)

    def task_bound(self, td: TaskDescriptor, pu_rid: int) -> None:
        machine = self._pu_machine.get(pu_rid)
        if machine is None or td.uid in self._on_machine:
            return
        group = td.workload
        zone = self._machine_zone[machine]
        self._unwait(td.uid)
        held = self._n.setdefault(group, {})
        held[zone] = held.get(zone, 0) + 1
        self._counted[td.uid] = (group, zone)
        self._on_machine[td.uid] = machine
        self._touch(machine, +1)

    def task_unbound(self, task_id: int, pu_rid: int) -> None:
        machine = self._on_machine.pop(task_id, None)
        if machine is None:
            return
        if self._uncount(task_id):
            # evicted or migrating, not departed: runnable again
            td = self.task_map.find(task_id)
            if td is not None:
                self._wait(task_id, td.workload)
        if machine in self._slots:  # else the machine left, and its counts with it
            self._touch(machine, -1)

    def task_class_fields(self, task_class: int) -> Dict[str, object]:
        if task_class < 0:
            raise ValueError(
                f"task_class {task_class} is not the index of a workload (a whole number from 0)"
            )
        return {"workload": task_class}

    # -- arc costs ---------------------------------------------------------

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return self.CLUSTER_AGG_COST

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        return 0, self._allot[self._ec_group[ec1]].get(self._ec_zone[ec2], 0)

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        return 0, self._slots[resource_id] - self._load[resource_id]

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        load, slots = self._load, self._slots
        return [0] * len(resource_ids), [slots[m] - load[m] for m in resource_ids]

    # -- preference enumeration --------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        td = self.task_map.find(task_id)
        if td is None:
            raise KeyError(f"no task descriptor for {task_id}")
        if self._waiting.get(task_id, td.workload) != td.workload:
            # a waiting pod delivered again as of another workload
            self._wait(task_id, td.workload)
        return [self._ec(td.workload)]

    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
        group = self._ec_group.get(ec)
        if group is None:
            return []
        allot = self._allot[group] = self.allotment(group)
        return [self._zone_ec[zone] for zone in allot]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        zone = self._ec_zone.get(ec)
        if zone is None:
            return []
        self._changed[zone] = set()
        load, slots = self._load, self._slots
        return [m for m in self._zone_machines[zone] if load[m] < slots[m]]

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        zone = self._ec_zone.get(ec)
        if zone is None:
            return []  # EC(g): its arcs go to zones, none to a resource
        changed = self._changed.get(zone)
        if changed is None:
            return None
        out = sorted(changed)
        changed.clear()
        return out

    def note_round(self, unscheduled_task_ids: Sequence[int]) -> None:
        self.spread_fallback = 0

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
        zone = rtnd.resource_desc.labels.get(ZONE_LABEL, "")
        if zone not in self._zone_machines:
            self._zone_machines[zone] = {}
            self._zone_free[zone] = 0
            self._zones = sorted(self._zone_machines)
            self._zone_ec[zone] = zone_ec(zone)
            self._ec_zone[self._zone_ec[zone]] = zone
        self._zone_machines[zone][machine] = None
        self._machine_zone[machine] = zone
        self._slots[machine] = pus * self.max_tasks_per_pu
        self._load[machine] = 0
        self._zone_free[zone] += self._slots[machine]
        changed = self._changed.get(zone)
        if changed is not None:
            changed.add(machine)

    def remove_machine(self, resource_id: int) -> None:
        super().remove_machine(resource_id)
        slots = self._slots.pop(resource_id, None)
        if slots is None:
            return
        zone = self._machine_zone.pop(resource_id)
        self._zone_free[zone] -= slots - self._load.pop(resource_id)
        del self._zone_machines[zone][resource_id]
        if not self._zone_machines[zone]:
            # a zone with no machine is no zone: it would hold the
            # water level at its count for ever
            del self._zone_machines[zone], self._zone_free[zone]
            del self._ec_zone[self._zone_ec.pop(zone)]
            self._changed.pop(zone, None)
            self._zones = sorted(self._zone_machines)
        for pu in [p for p, m in self._pu_machine.items() if m == resource_id]:
            del self._pu_machine[pu]
        # its node goes, and every arc into it with it
        changed = self._changed.get(zone)
        if changed is not None:
            changed.discard(resource_id)
