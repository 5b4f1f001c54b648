"""Required hostname anti-affinity: never two pods of one workload on a node.

Kubernetes' most used placement rule (a Deployment whose pod template
carries `podAntiAffinity.requiredDuringSchedulingIgnoredDuringExecution`
with `topologyKey: kubernetes.io/hostname` and a label selector on the
pod's own label; scheduler_perf `SchedulingPodAntiAffinity`) as a flow
network. It is the first model here whose equivalence-class arcs carry
a capacity that BINDS below the machine's free slots, which is the one
structure the dense collapse refuses (docs/solver_coverage.md): every
round of it is solved on the general CSR rung.

A pod's `PodEvent.task_class` is the index g of its workload and rides
the task descriptor as `TaskDescriptor.workload` (not the four-valued
CoCo enum `task_type`). n(g, m) is the number of pods of g bound to
machine m and not yet dropped: a pod that completed, failed or was
killed leaves n in the next round's `deltas` phase, where it also
leaves its PU's `current_running_tasks` (flow_scheduler._drop_departed;
the reference's timing), which errs on the safe side. Per round, with
the trivial model's constants (e = CLUSTER_AGG_COST = 2, u =
UNSCHEDULED_COST = 5), so that objectives stay comparable:

- task t of workload g: one arc t -> EC(g), capacity 1, cost e; one arc
  t -> its job's unscheduled aggregator, capacity 1, cost u; NO arc to
  CLUSTER_AGGREGATOR_EC (it would route around the rule);
- EC(g) exists while g has a runnable task; the arc EC(g) -> m exists
  iff n(g, m) = 0 and m has a free slot, capacity 1, cost 0;
- the machine subtree, PU -> sink and the running arcs exactly as the
  trivial model has them (a pinned task is inert).

Invariant (the guarantee `anti_affinity`): at every instant n(g, m) <= 1
for all g, m. Exactness: the integral flows of this network are exactly
the placements that add at most one pod of g to each machine holding
none, and since e < u a minimum-cost flow binds as many pods as that
allows.

Departures from the equations, none of which moves a round's objective:

- EC(g) outlives its last runnable task by two rounds, as every EC
  node does (the graph manager's purge removes an EC that was idle at
  two purges in a row). No arc enters an EC without a runnable task, so
  it carries no flow.
- EC(g)'s arcs are brought up to date when the update reaches EC(g),
  that is in the rounds in which g has a runnable task, from the (g, m)
  pairs events touched since (`equiv_class_pref_arc_changes`): a bind,
  an unbind, a drop of the departed, a machine filling up or getting a
  slot back, a machine leaving. An EC no task points at may hold stale
  arcs until then.
- A machine's slots are counted once, when it is added (PUs below it x
  max_tasks_per_pu): a PU registered under a known machine later is not
  counted. No caller does that.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..data import ResourceTopologyNodeDescriptor, ResourceType, TaskDescriptor
from ..utils import equiv_class_from_bytes, resource_id_from_string
from .base import Cost
from .trivial import TrivialCostModel


def workload_ec(group: int) -> int:
    """The equivalence class of workload `group`."""
    return equiv_class_from_bytes(b"K8S_WORKLOAD_%d" % group)


class K8sAntiAffinityCostModel(TrivialCostModel):
    # the trivial model's continuation cost and stats hooks, unchanged
    pinned_tasks_are_inert = True

    def __init__(self, resource_map, task_map, leaf_resource_ids, max_tasks_per_pu) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        self._ec_group: Dict[int, int] = {}
        #: g -> m -> n(g, m), the entries above zero
        self._held: Dict[int, Dict[int, int]] = {}
        #: m -> pods counted on it (the sum of n(., m)) and its slots
        self._load: Dict[int, int] = {}
        self._slots: Dict[int, int] = {}
        self._pu_machine: Dict[int, int] = {}
        #: task -> (g, m) while the task counts in n
        self._where: Dict[int, Tuple[int, int]] = {}
        #: g -> machines whose arc from EC(g) may have changed since the
        #: arcs of EC(g) were last listed; a key exists from the first
        #: listing on
        self._changed: Dict[int, Set[int]] = {}

    # -- the rule ----------------------------------------------------------

    def _ec(self, group: int) -> int:
        ec = workload_ec(group)
        self._ec_group[ec] = group
        return ec

    def _eligible(self, group: int, machine: int) -> bool:
        return (
            not self._held.get(group, {}).get(machine)
            and self._load[machine] < self._slots[machine]
        )

    def _touch(self, group: int, machine: int, load_delta: int) -> None:
        """n(group, machine) moved by `load_delta`."""
        changed = self._changed.get(group)
        if changed is not None:
            changed.add(machine)
        slots = self._slots[machine]
        before = self._load[machine]
        self._load[machine] = before + load_delta
        if (before < slots) != (before + load_delta < slots):
            # full, or no longer: every workload's arc to it changes
            for changed in self._changed.values():
                changed.add(machine)

    # -- events (FlowScheduler's bindings bookkeeping) ----------------------

    def task_bound(self, td: TaskDescriptor, pu_rid: int) -> None:
        machine = self._pu_machine.get(pu_rid)
        if machine is None or td.uid in self._where:
            return
        group = td.workload
        held = self._held.setdefault(group, {})
        held[machine] = held.get(machine, 0) + 1
        self._where[td.uid] = (group, machine)
        self._touch(group, machine, +1)

    def task_unbound(self, task_id: int, pu_rid: int) -> None:
        where = self._where.pop(task_id, None)
        if where is None:
            return
        group, machine = where
        if machine not in self._slots:
            return  # the machine left, and its counts with it
        held = self._held[group]
        if held[machine] == 1:
            del held[machine]
        else:
            held[machine] -= 1
        self._touch(group, machine, -1)

    def task_class_fields(self, task_class: int) -> Dict[str, object]:
        if task_class < 0:
            raise ValueError(
                f"task_class {task_class} is not the index of a workload (a whole number from 0)"
            )
        return {"workload": task_class}

    # -- arc costs ---------------------------------------------------------

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return self.CLUSTER_AGG_COST

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        return 0, int(self._eligible(self._ec_group[ec], resource_id))

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        group = self._ec_group[ec]
        return [0] * len(resource_ids), [int(self._eligible(group, m)) for m in resource_ids]

    # -- preference enumeration --------------------------------------------

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        td = self.task_map.find(task_id)
        if td is None:
            raise KeyError(f"no task descriptor for {task_id}")
        return [self._ec(td.workload)]

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        group = self._ec_group.get(ec)
        if group is None:
            return []
        self._changed[group] = set()
        held = self._held.get(group, {})
        load, slots = self._load, self._slots
        return [m for m in self._machines if m not in held and load[m] < slots[m]]

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        changed = self._changed.get(self._ec_group.get(ec))
        if changed is None:
            return None
        out = sorted(changed)
        changed.clear()
        return out

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
        self._slots[machine] = pus * self.max_tasks_per_pu
        self._load[machine] = 0
        for changed in self._changed.values():
            changed.add(machine)

    def remove_machine(self, resource_id: int) -> None:
        super().remove_machine(resource_id)
        if self._slots.pop(resource_id, None) is None:
            return
        del self._load[resource_id]
        for held in self._held.values():
            held.pop(resource_id, None)
        for pu in [p for p, m in self._pu_machine.items() if m == resource_id]:
            del self._pu_machine[pu]
        # its node goes, and every arc into it with it
        for changed in self._changed.values():
            changed.discard(resource_id)
