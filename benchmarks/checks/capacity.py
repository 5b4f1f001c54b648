"""`capacity`: no node ever holds more than cores x pus_per_core x
max_tasks_per_pu pods.

The run's log of ("bind", pod, node, t) / ("done", pod, "", t) /
("evict", pod, node, t), replayed in the order the loop thread made them;
the first instant a node is over its capacity, a completion of a pod with
no Binding on record, or an eviction from a node the pod is not on, is the
fault. An evicted pod leaves its node: a Binding that fills the node again
is none.
"""

from typing import Dict, List


def check(ctx) -> List[str]:
    args = ctx.svc_args
    capacity = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu
    where: Dict[str, str] = {}
    load: Dict[str, int] = {}
    facts = ctx.facts["capacity"] = {
        "node_capacity": capacity, "replayed": len(ctx.log), "peak_node_load": 0,
    }
    for kind, pod, node, _t in ctx.log:
        if kind == "bind":
            old = where.get(pod)
            if old is not None:
                load[old] -= 1
            where[pod] = node
            load[node] = load.get(node, 0) + 1
            facts["peak_node_load"] = max(facts["peak_node_load"], load[node])
            if load[node] > capacity:
                return [f"node {node} held {load[node]} pods, capacity {capacity} (pod {pod})"]
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
