"""The plain reference of `k8s-5000-antiaffinity`: required hostname
anti-affinity against the pod's own workload, as a flow network built in
straight numpy from the policy's equations, and the replay that holds a
served stream to the rule.

Independent of the code under test: no graph manager, no cost model
class, no change journal. The equations (ksched_tpu/costmodels/
k8s_antiaffinity.py states the same ones), with e = 2 and u = 5:

  task t of workload g   t -> EC(g), capacity 1, cost e
                         t -> U (the job's unscheduled aggregator),
                         capacity 1, cost u
  EC(g), while g has a   EC(g) -> m iff n(g, m) = 0 and m has a free
  runnable task          slot, capacity 1, cost 0
  machine m              m -> sink, capacity = its free slots, cost 0
  U                      U -> sink, capacity = the runnable tasks, cost 0

n(g, m) counts the pods of g that hold a slot of m: bound and not yet
dropped (a completed pod is dropped in the next round's `deltas` phase,
so the caller lists it among `held` for one round more). The machine's
core and PU are folded into one arc of their joint capacity, and the
pods that run (pinned: one arc, lower bound 1, cost 0) into the free
slots: neither carries a cost, so the objective is the served round's.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ksched_tpu.graph.device_export import FlowProblem
from ksched_tpu.graph.flowgraph import NodeType
from ksched_tpu.solver.cpu_ref import ReferenceSolver

EC_COST = 2  # e: task -> EC(g)
UNSCHEDULED_COST = 5  # u: task -> unscheduled aggregator


def build_problem(
    runnable: Sequence[Tuple[str, int]],
    machine_slots: Sequence[int],
    held: Iterable[Tuple[int, int]],
) -> FlowProblem:
    """One round's network. `runnable`: (pod, workload) of every pod the
    round may place; `machine_slots`: slots of machine 0, 1, ...;
    `held`: (workload, machine) of every pod that holds a slot."""
    n_m = len(machine_slots)
    load = np.zeros(n_m, dtype=np.int64)
    n_gm: Dict[Tuple[int, int], int] = {}
    for g, m in held:
        load[m] += 1
        n_gm[(g, m)] = n_gm.get((g, m), 0) + 1
    free = np.asarray(machine_slots, dtype=np.int64) - load
    groups = sorted({g for _pod, g in runnable})
    # node ids: 0 padding, 1 sink, 2 U, machines, ECs, tasks
    sink, unsched = 1, 2
    machine0 = 3
    ec0 = machine0 + n_m
    task0 = ec0 + len(groups)
    num_nodes = task0 + len(runnable)
    ec_of = {g: ec0 + i for i, g in enumerate(groups)}
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
    for g in groups:
        for m in range(n_m):
            if free[m] > 0 and not n_gm.get((g, m)):
                arc(ec_of[g], machine0 + m, 1, 0)
    for m in range(n_m):
        if free[m] > 0:
            arc(machine0 + m, sink, int(free[m]), 0)
    arc(unsched, sink, len(runnable), 0)

    excess = np.zeros(num_nodes, dtype=np.int64)
    excess[task0:] = 1
    excess[sink] = -len(runnable)
    node_type = np.full(num_nodes, -1, dtype=np.int8)
    node_type[sink] = int(NodeType.SINK)
    node_type[unsched] = int(NodeType.JOB_AGGREGATOR)
    node_type[machine0:ec0] = int(NodeType.MACHINE)
    node_type[ec0:task0] = int(NodeType.EQUIV_CLASS)
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
    held: Iterable[Tuple[int, int]],
) -> Tuple[int, int]:
    """(objective, pods placed) of the round's minimum-cost flow."""
    if not runnable:
        return 0, 0
    result = ReferenceSolver().solve(build_problem(runnable, machine_slots, held))
    objective = int(result.objective)
    # placed * e + (runnable - placed) * u = objective
    placed = (len(runnable) * UNSCHEDULED_COST - objective) // (UNSCHEDULED_COST - EC_COST)
    return objective, placed


def check_anti_affinity(
    log: Iterable[Tuple[str, str, str, float]], group_of: Dict[str, int]
) -> Optional[str]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    log with each pod's workload; the first instant at which a node
    holds two pods of one workload, or None."""
    where: Dict[str, str] = {}
    n: Dict[Tuple[int, str], int] = {}
    for kind, pod, node, t in log:
        g = group_of[pod]
        if kind == "bind":
            old = where.get(pod)
            if old is not None:
                n[(g, old)] -= 1
            where[pod] = node
            n[(g, node)] = n.get((g, node), 0) + 1
            if n[(g, node)] > 1:
                return (
                    f"t={t:.6f}: node {node} holds {n[(g, node)]} pods of "
                    f"workload {g} (pod {pod})"
                )
        elif kind == "done":
            node = where.pop(pod, None)
            if node is not None:
                n[(g, node)] -= 1
    return None
