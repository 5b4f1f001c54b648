"""Pod priority and preemption: an arrival that fits nowhere evicts a pod
of strictly lower priority.

Kubernetes ("Pod Priority and Preemption"; scheduler_perf
`PreemptionBasic`): a pending pod that fits on no node may evict pods of
strictly lower priority from one; it never evicts a pod of equal or
higher priority; pending pods of higher priority are served first. Here
that is a flow network over slots, served with `--preemption` (no running
task is pinned: each keeps its running arc, its arc to the cluster
aggregator and an arc to its job's unscheduled aggregator).

A pod's `PodEvent.priority` is a small whole tier k in 0..K-1 (0 the
lowest; scheduler_perf's two templates are tiers 0 and 1) and rides the
task as `TaskDescriptor.priority`; a tier outside 0..K-1 is refused with
a `ValueError` where the pod is admitted (`task_priority_fields`). Per
round, with the trivial model's constants so that objectives stay
comparable (e = CLUSTER_AGG_COST = 2, u(0) = UNSCHEDULED_COST = 5):

- a task t of tier k that is not running: t -> cluster EC, capacity 1,
  cost e; t -> unscheduled aggregator, capacity 1, cost u(k) = 5 * 8^k;
- a running task t of tier k: its running arc t -> PU, capacity 1, cost 0
  (continuation); t -> unscheduled aggregator, cost c(k) = u(k) (what its
  eviction costs); its arc t -> cluster EC stays, cost e;
- cluster EC -> machine m: capacity slots(m), every slot and not the free
  ones, cost 0. Running flow rides the running arcs and never this arc;
  what bounds a machine is below it: machine -> PU and PU -> sink carry
  slots (GraphManager._capacity_to_parent under preemption), so running
  and new flow together never pass slots(m). The arc is a constant: it is
  listed once, when the machine joins, and `equiv_class_pref_arc_changes`
  names nothing until a machine comes or goes.

The two conditions on the constants, for every tier k:

  (1) e + u(k-1) < u(k): a pending pod of tier k takes the slot of a
      running pod of tier k-1 (cost e to place plus c(k-1) = u(k-1) to
      evict) rather than wait (cost u(k)). u rises with k, so (1) holds
      for every lower tier j < k too: e + u(j) <= e + u(k-1) < u(k).
      5 * 8^k: 2 + 5 * 8^(k-1) < 5 * 8^k since 2 < 35 * 8^(k-1).
  (2) u(k) < e + c(k): a pending pod of tier k waits (u(k)) rather than
      take the slot of a running pod of its own tier (e + c(k) = e +
      u(k)): holds as e > 0. So an equal tier never displaces an
      incumbent, a fortiori no higher one; and no pod migrates for
      nothing: moving a running pod to a free slot costs e and frees a
      slot the arrival could have had for the same e directly.

What follows, and what the plain reference
(benchmarks/reference_preemption.py) relies on: slots are units and every
exchange is one pod for one pod, so by (1) and (2) a minimum-cost flow
leaves the S slots of the cluster to the top S tasks by tier, incumbents
first within a tier, a free slot before an eviction (e < e + c). The
numbers bound, evicted and left pending PER TIER are therefore unique,
though which node and which victim of a tier are not.

How many tiers: the scan-CSR rung scales costs by the padded node count
and refuses max|cost| * nodes >= 2^30 on entry (solver/jax_solver.py).
u(3) = 2,560 passes at the 65,536 padded nodes of a 5,000-node cluster
(1.7e8), u(4) = 20,480 does not: K = 4.

Departures from Kubernetes, each by the slot model (SURVEY section 0: a
task is one unit of flow):

- ONE eviction for each preempting pod, where scheduler_perf's 3,000m
  high-priority pod evicts three 900m pods: requests are not summed, a
  pod takes a slot.
- The evicted pod stays the scheduler's: the same task, RUNNABLE again,
  placed again when a slot frees (ksched's PREEMPT delta). kube-scheduler
  deletes the victim and its owner creates another pod.
- No graceful termination and no nominated node: the eviction and the
  Binding that takes the slot are posted in the same round, eviction
  first (cli.SchedulerService.run_once).
- Victims are chosen by the flow's cost alone: lowest tier first; within
  a tier any (Kubernetes also weighs PodDisruptionBudgets, start times
  and the number of victims).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from ..data import ResourceTopologyNodeDescriptor, ResourceType
from ..utils import resource_id_from_string
from .base import CLUSTER_AGGREGATOR_EC, Cost
from .trivial import TrivialCostModel


class K8sPriorityCostModel(TrivialCostModel):
    # the trivial model's continuation cost and stats hooks, unchanged:
    # under preemption nothing is pinned, and the statistics walk still
    # learns nothing from a task node
    pinned_tasks_are_inert = True
    #: served only by a scheduler whose running tasks keep their arcs
    needs_preemption = True

    #: tiers 0..MAX_TIERS-1 (the module docstring says why four)
    MAX_TIERS = 4
    TIER_FACTOR = 8

    def __init__(self, resource_map, task_map, leaf_resource_ids, max_tasks_per_pu) -> None:
        super().__init__(resource_map, task_map, leaf_resource_ids, max_tasks_per_pu)
        #: machine -> its slots, counted once when it joins
        self._slots: Dict[int, int] = {}
        #: machines that joined since the cluster EC's arcs were last
        #: listed; None until the first listing
        self._joined: Optional[Set[int]] = None

    @classmethod
    def unscheduled_cost(cls, tier: int) -> Cost:
        """u(k), which is also c(k)."""
        return cls.UNSCHEDULED_COST * cls.TIER_FACTOR ** tier

    def task_priority_fields(self, priority: int) -> Dict[str, object]:
        if not 0 <= priority < self.MAX_TIERS:
            raise ValueError(
                f"priority {priority} is not one of the {self.MAX_TIERS} tiers "
                f"(0..{self.MAX_TIERS - 1}) that {type(self).__name__} prices"
            )
        return {"priority": priority}

    def _tier(self, task_id: int) -> int:
        td = self.task_map.find(task_id)
        if td is None:
            raise KeyError(f"no task descriptor for {task_id}")
        return td.priority

    # -- arc costs ---------------------------------------------------------

    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        return self.unscheduled_cost(self._tier(task_id))

    def task_preemption_cost(self, task_id: int) -> Cost:
        return self.unscheduled_cost(self._tier(task_id))

    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        return 0, self._slots.get(resource_id, 0)

    # -- preference enumeration --------------------------------------------

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        if ec != CLUSTER_AGGREGATOR_EC:
            return []
        self._joined = set()
        return list(self._machines.keys())

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        if ec != CLUSTER_AGGREGATOR_EC or self._joined is None:
            return None
        out = sorted(self._joined)
        self._joined.clear()
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
            pus += cur.resource_desc.type == ResourceType.PU
            stack.extend(cur.children)
        self._slots[machine] = pus * self.max_tasks_per_pu
        if self._joined is not None:
            self._joined.add(machine)

    def remove_machine(self, resource_id: int) -> None:
        super().remove_machine(resource_id)
        self._slots.pop(resource_id, None)
        if self._joined is not None:
            # its node goes, and the arc into it with it
            self._joined.discard(resource_id)
