"""Flow → task-placement decoding.

Reference: scheduling/flow/placement/solver.go:183-269 — start from leaf
(PU) nodes that send flow to the sink and push PU ids backwards up each
flow-carrying arc until task nodes are reached; asserts a 1:1 task→PU
mapping. Tasks whose unit drained through their job's unscheduled
aggregator never receive a PU and stay unplaced.

Divergence from the reference: its reverse *BFS* can pop a node before
all of that node's unit contributors have been processed when flow paths
skip levels, silently dropping units. We instead process nodes in strict
topological order of the positive-flow DAG (longest-distance-from-sink
strata), which is correct for any acyclic flow. Positive-flow cycles
cannot appear in a minimal-cost flow from our backends (SSP never creates
them; the push-relabel backend cancels zero-cost cycles before decode).
Within a stratum nodes take their turn by node id, so which of several
equal-cost PUs a task reads, and the order of the mapping, follow from
the problem and the flow alone, whatever else the graph holds.

The decode covers the tasks whose binding the solve could change. A
task pinned to its PU (preemption off: one arc, lower bound 1) is known
without a walk; the caller passes the mask of pinned node ids and the
set of the other task nodes, both kept by the graph manager's events.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Set

import numpy as np

from ..graph.device_export import FlowProblem

TaskMapping = Dict[int, int]


def flow_to_mapping(
    problem: FlowProblem,
    total_flow: np.ndarray,
    leaf_node_ids: Iterable[int],
    sink_node_id: int,
    task_node_ids: Iterable[int],
    pinned: Optional[np.ndarray] = None,
) -> TaskMapping:
    """Decode a solved flow into {task node id -> PU node id}.

    total_flow must include lower-bound offsets (FlowResult.total_flow).
    Any consistent decomposition of the flow is a valid assignment (flow
    conservation guarantees it); per-node units are matched to incoming
    arcs in arc order, and nodes take their turn by (stratum, node id),
    which is also the order of the returned dict: a pure function of
    the problem and the flow.

    ``pinned`` is a boolean mask over node ids (GraphManager.pinned_mask)
    of the task nodes whose one arc has lower bound 1: each carries its
    unit to the PU it is bound to in every feasible flow, so its arc is
    dropped before any loop and ``task_node_ids`` names the other tasks
    only. A pinned node has no incoming arc and a PU's units are all
    the PU's own id, so the result is the full decode's, less the
    pinned tasks, pair for pair.
    """
    src = problem.src
    dst = problem.dst
    live = np.flatnonzero(total_flow > 0)
    if pinned is not None:
        live = live[~pinned[src[live]]]
    # An arc into the sink passes units on only if flow enters its
    # source: a PU that holds nothing but pinned tasks needs none.
    to_sink = dst[live] == sink_node_id
    fed = np.zeros(problem.num_nodes, dtype=bool)
    fed[dst[live[~to_sink]]] = True
    live = live[~to_sink | fed[src[live]]]
    task_nodes: Set[int] = set(task_node_ids)

    # Per-node incoming positive-flow arcs: dst -> [(src, flow), ...].
    incoming: Dict[int, List[tuple]] = {}
    for s, d, f in zip(src[live].tolist(), dst[live].tolist(), total_flow[live].tolist()):
        incoming.setdefault(d, []).append((s, f))

    # Stratify the positive-flow DAG by longest distance from the sink,
    # walking backwards. level[v] = 1 + max(level[w] for flow arcs v->w).
    level: Dict[int, int] = {sink_node_id: 0}
    frontier = {sink_node_id}
    n_nodes = problem.num_nodes
    rounds = 0
    while frontier:
        rounds += 1
        if rounds > n_nodes:
            raise RuntimeError("positive-flow cycle detected during decode")
        nxt: Set[int] = set()
        for w in frontier:
            lw = level[w]
            for s, _f in incoming.get(w, ()):
                if level.get(s, -1) < lw + 1:
                    level[s] = lw + 1
                    nxt.add(s)
        frontier = nxt

    # pu_units[v] = PU ids of the flow units passing through v.
    leaf_set: Set[int] = set(leaf_node_ids)
    pu_units: Dict[int, List[int]] = {}
    for s, f in incoming.get(sink_node_id, ()):
        if s in leaf_set:
            pu_units[s] = [s] * f

    mapping: TaskMapping = {}
    del level[sink_node_id]
    for v in sorted(level, key=lambda v: (level[v], v)):
        units = pu_units.get(v)
        if units is None:
            continue  # e.g. unscheduled aggregators: no PU units flow through
        if v in task_nodes:
            if len(units) != 1:
                raise AssertionError(
                    f"task node {v} decoded {len(units)} units; task->PU must be 1:1"
                )
            mapping[v] = units[0]
            continue
        it = 0
        for s, f in incoming.get(v, ()):
            take = min(f, len(units) - it)
            if take > 0:
                pu_units.setdefault(s, []).extend(units[it : it + take])
                it += take
            if it >= len(units):
                break
    return mapping
