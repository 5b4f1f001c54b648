"""`capacity_by_type`: no node ever holds more pods than ITS OWN cores x
pus_per_core x max_tasks_per_pu.

`capacity` reads one node capacity from the argv, which a cluster of
several machine types does not have. Here a node's cores come from the
configuration's type table (`machine_types`: name, cores, share per mille)
and the node's index, by the dealing rule the plain reference states
(benchmarks/reference_wharemap.py `machine_type`: (619 * i) mod 1000 into
the shares), not from the service. The run's log of ("bind", pod, node, t)
/ ("done", pod, "", t) / ("evict", pod, node, t) is replayed in the order
the loop thread made them, as `capacity` replays it; the first instant a
node is over its own capacity, a completion of a pod with no Binding on
record, or an eviction from a node the pod is not on, is the fault.
"""

from typing import Dict, List

from benchmarks import reference_wharemap as ref


def node_capacity(ctx, node: str) -> int:
    """What `node` holds at most, from the file's table and the node's name."""
    types = [tuple(t) for t in ctx.config["machine_types"]]
    args = ctx.svc_args
    return ref.node_shape(node, types, args.pus_per_core, args.max_tasks_per_pu)[1]


def check(ctx) -> List[str]:
    capacity: Dict[str, int] = {}
    where: Dict[str, str] = {}
    load: Dict[str, int] = {}
    facts = ctx.facts["capacity_by_type"] = {
        "replayed": len(ctx.log), "peak_load_by_capacity": {},
    }
    peak = facts["peak_load_by_capacity"]
    for kind, pod, node, _t in ctx.log:
        if kind == "bind":
            old = where.get(pod)
            if old is not None:
                load[old] -= 1
            where[pod] = node
            load[node] = load.get(node, 0) + 1
            if node not in capacity:
                capacity[node] = node_capacity(ctx, node)
            key = str(capacity[node])
            peak[key] = max(peak.get(key, 0), load[node])
            if load[node] > capacity[node]:
                return [
                    f"node {node} held {load[node]} pods, its own capacity is "
                    f"{capacity[node]} (pod {pod})"
                ]
        elif kind == "done":
            node = where.pop(pod, None)
            if node is None:
                return [f"pod {pod} completed without a Binding on record"]
            load[node] -= 1
        elif kind == "evict":
            on = where.pop(pod, None)
            if on != node:
                return [f"pod {pod} evicted from node {node}, the record has it on {on}"]
            load[node] -= 1
    return []
