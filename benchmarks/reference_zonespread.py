"""The plain reference of `k8s-5000-zonespread`: a hard zone
topology-spread constraint against the pod's own workload, as a flow
network built in straight numpy from the policy's equations, and the
replay that holds a served stream to the rule.

Independent of the code under test: no graph manager, no cost model
class, no change journal. The equations (ksched_tpu/costmodels/
k8s_zonespread.py states the same ones), with e = 2, u = 5 and
s = `max_skew`; n(g, z) = pods of workload g bound in zone z and not yet
completed; K(g) = runnable pods of g; R = sum of K; F(z) = free slots
of zone z (a completed pod holds its slot one round more, so the caller
lists it among `slot_holders`):

  task t of workload g   t -> EC(g), capacity 1, cost e
                         t -> U (the job's unscheduled aggregator),
                         capacity 1, cost u
  EC(g)                  EC(g) -> ZONE(z), capacity a(g, z), cost 0,
                         where a(g, z) > 0
  with room              L(g) = the largest L with
  (F(z) >= R, all z)     sum_z max(0, L - n(g, z)) <= K(g);
                         b = max(0, L(g) - n(g, z)); r = K(g) - sum b;
                         a = b + 1 for the first r zones, in zone order,
                         with n(g, z) <= L(g), else b
  room short             a(g, z) = max(0, min_z' n(g, z') + s - n(g, z))
  ZONE(z)                ZONE(z) -> m for m of z with a free slot,
                         capacity free(m), cost 0
  machine m              m -> sink, capacity = its free slots, cost 0
  U                      U -> sink, capacity = the runnable tasks, cost 0

The machine's core and PU are folded into one arc of their joint
capacity, and the pods that run (pinned: one arc, lower bound 1, cost 0)
into the free slots: neither carries a cost, so the objective is the
served round's.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ksched_tpu.graph.device_export import FlowProblem
from ksched_tpu.graph.flowgraph import NodeType
from ksched_tpu.solver.cpu_ref import ReferenceSolver

EC_COST = 2  # e: task -> EC(g)
UNSCHEDULED_COST = 5  # u: task -> unscheduled aggregator


def allotment(counts: Sequence[int], pods: int) -> List[int]:
    """a(g, .) with room: the water level, by counting up."""
    level = min(counts)
    while sum(max(0, level + 1 - c) for c in counts) <= pods:
        level += 1
    allot = [max(0, level - c) for c in counts]
    rest = pods - sum(allot)
    for z, c in enumerate(counts):
        if rest and c <= level:
            allot[z] += 1
            rest -= 1
    return allot


def chain_capacities(
    runnable: Sequence[Tuple[str, int]],
    machine_zone: Sequence[int],
    free: np.ndarray,
    counted: Iterable[Tuple[int, int]],
    max_skew: int,
) -> Tuple[Dict[Tuple[int, int], int], bool]:
    """({(g, z): a(g, z) > 0}, whether room was short). `counted`:
    (workload, machine) of every pod that counts in n."""
    zones = sorted(set(machine_zone))
    zone_free = {z: 0 for z in zones}
    for m, z in enumerate(machine_zone):
        zone_free[z] += int(free[m])
    n: Dict[Tuple[int, int], int] = {}
    for g, m in counted:
        key = (g, machine_zone[m])
        n[key] = n.get(key, 0) + 1
    pods: Dict[int, int] = {}
    for _pod, g in runnable:
        pods[g] = pods.get(g, 0) + 1
    short = any(zone_free[z] < len(runnable) for z in zones)
    caps: Dict[Tuple[int, int], int] = {}
    for g, k in pods.items():
        counts = [n.get((g, z), 0) for z in zones]
        if short:
            allot = [max(0, min(counts) + max_skew - c) for c in counts]
        else:
            allot = allotment(counts, k)
        caps.update({(g, z): a for z, a in zip(zones, allot) if a > 0})
    return caps, short


def build_problem(
    runnable: Sequence[Tuple[str, int]],
    machine_slots: Sequence[int],
    machine_zone: Sequence[int],
    counted: Iterable[Tuple[int, int]],
    slot_holders: Iterable[int],
    max_skew: int,
) -> FlowProblem:
    """One round's network. `runnable`: (pod, workload) of every pod the
    round may place; `machine_slots`, `machine_zone`: slots and zone of
    machine 0, 1, ...; `counted`: (workload, machine) of every pod that
    counts in n; `slot_holders`: the machine of every pod that holds a
    slot (those, and the pods completed since the last round)."""
    n_m = len(machine_slots)
    load = np.zeros(n_m, dtype=np.int64)
    for m in slot_holders:
        load[m] += 1
    free = np.asarray(machine_slots, dtype=np.int64) - load
    caps, _short = chain_capacities(runnable, machine_zone, free, counted, max_skew)
    groups = sorted({g for _pod, g in runnable})
    zones = sorted(set(machine_zone))
    # node ids: 0 padding, 1 sink, 2 U, machines, zone ECs, workload ECs, tasks
    sink, unsched = 1, 2
    machine0 = 3
    zone0 = machine0 + n_m
    ec0 = zone0 + len(zones)
    task0 = ec0 + len(groups)
    num_nodes = task0 + len(runnable)
    ec_of = {g: ec0 + i for i, g in enumerate(groups)}
    zone_of = {z: zone0 + i for i, z in enumerate(zones)}
    src: List[int] = []
    dst: List[int] = []
    cap: List[int] = []
    cost: List[int] = []

    def arc(s: int, d: int, c: int, w: int) -> None:
        src.append(s)
        dst.append(d)
        cap.append(c)
        cost.append(w)

    for i, (_pod, g) in enumerate(runnable):
        arc(task0 + i, ec_of[g], 1, EC_COST)
        arc(task0 + i, unsched, 1, UNSCHEDULED_COST)
    for (g, z), a in sorted(caps.items()):
        arc(ec_of[g], zone_of[z], a, 0)
    for m in range(n_m):
        if free[m] > 0:
            arc(zone_of[machine_zone[m]], machine0 + m, int(free[m]), 0)
            arc(machine0 + m, sink, int(free[m]), 0)
    arc(unsched, sink, len(runnable), 0)

    excess = np.zeros(num_nodes, dtype=np.int64)
    excess[task0:] = 1
    excess[sink] = -len(runnable)
    node_type = np.full(num_nodes, -1, dtype=np.int8)
    node_type[sink] = int(NodeType.SINK)
    node_type[unsched] = int(NodeType.JOB_AGGREGATOR)
    node_type[machine0:zone0] = int(NodeType.MACHINE)
    node_type[zone0:task0] = int(NodeType.EQUIV_CLASS)
    node_type[task0:] = int(NodeType.UNSCHEDULED_TASK)
    return FlowProblem(
        num_nodes=num_nodes, excess=excess, node_type=node_type,
        src=np.asarray(src, dtype=np.int32), dst=np.asarray(dst, dtype=np.int32),
        cap=np.asarray(cap, dtype=np.int32), cost=np.asarray(cost, dtype=np.int32),
        flow_offset=np.zeros(len(src), dtype=np.int32), num_arcs=len(src),
    )


def reference_round(
    runnable: Sequence[Tuple[str, int]],
    machine_slots: Sequence[int],
    machine_zone: Sequence[int],
    counted: Iterable[Tuple[int, int]],
    slot_holders: Iterable[int],
    max_skew: int,
) -> Tuple[int, int, Dict[Tuple[int, int], int]]:
    """(objective, pods placed, {(g, z): pods of g the round's
    minimum-cost flow sends into zone z})."""
    if not runnable:
        return 0, 0, {}
    problem = build_problem(
        runnable, machine_slots, machine_zone, list(counted), list(slot_holders), max_skew
    )
    result = ReferenceSolver().solve(problem)
    objective = int(result.objective)
    # placed * e + (runnable - placed) * u = objective
    placed = (len(runnable) * UNSCHEDULED_COST - objective) // (UNSCHEDULED_COST - EC_COST)
    groups = sorted({g for _pod, g in runnable})
    zones = sorted(set(machine_zone))
    zone0 = 3 + len(machine_slots)
    ec0 = zone0 + len(zones)
    flow = np.asarray(result.flow)[: problem.num_arcs]
    into: Dict[Tuple[int, int], int] = {}
    for a in np.nonzero((problem.src >= ec0) & (problem.src < ec0 + len(groups)) & (flow > 0))[0]:
        key = (groups[int(problem.src[a]) - ec0], zones[int(problem.dst[a]) - zone0])
        into[key] = int(flow[a])
    return objective, placed, into


def check_topology_spread(
    log: Iterable[Tuple[str, str, str, float]],
    group_of: Dict[str, int],
    zone_of: Dict[str, int],
    max_skew: int,
) -> Tuple[Optional[str], Dict[str, int]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    log with each pod's workload and each node's zone. The Bindings of
    one round share one stamp; after every round's Bindings, for every
    workload g and every zone z that received a pod of g in that round,
    f(g, z) <= min_z' f(g, z') + max_skew, f counted over Bindings less
    completions in the log's own order. Returns (the first fault or
    None, facts: entries replayed, rounds, the largest f - min seen)."""
    zones = sorted(set(zone_of.values()))
    where: Dict[str, str] = {}
    f: Dict[int, Dict[int, int]] = {}
    facts = {"replayed": 0, "rounds": 0, "largest_skew": 0}
    received: set = set()
    stamp = None

    def close_round() -> Optional[str]:
        facts["rounds"] += 1
        for g, z in sorted(received):
            counts = f[g]
            skew = counts[z] - min(counts[y] for y in zones)
            facts["largest_skew"] = max(facts["largest_skew"], skew)
            if skew > max_skew:
                return (
                    f"t={stamp:.6f}: zone {z} holds {counts[z]} pods of workload {g}, "
                    f"{skew} above the lowest zone's {counts[z] - skew} (maxSkew {max_skew})"
                )
        received.clear()
        return None

    for kind, pod, node, t in log:
        if received and (kind != "bind" or t != stamp):
            fault = close_round()
            if fault is not None:
                return fault, facts
        facts["replayed"] += 1
        g = group_of[pod]
        counts = f.setdefault(g, {z: 0 for z in zones})
        if kind == "bind":
            stamp = t
            old = where.get(pod)
            if old is not None:
                counts[zone_of[old]] -= 1
            where[pod] = node
            counts[zone_of[node]] += 1
            received.add((g, zone_of[node]))
        elif kind == "done":
            node = where.pop(pod, None)
            if node is not None:
                counts[zone_of[node]] -= 1
    if received:
        fault = close_round()
        if fault is not None:
            return fault, facts
    return None, facts
