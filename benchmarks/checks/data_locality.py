"""`data_locality`: every round places its pods where Quincy's policy, with
its rack tier, says their inputs are cheapest to read, and no pod waits
while a slot is free.

The whole run's record (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed in the loop's order by the plain
reference's `check_data_locality` (benchmarks/reference_quincy.py): for
every round, the sum over its Bindings of the cheapest route the policy
gives each pod to its node equals the optimum of the round's
transportation problem on the replay's own books, exactly; with the polls
the benchmark's ClusterAPI kept, a round leaves a pod waiting only if it
took every free slot. What each pod read is recomputed through
`ctx.make_pod`, the configuration's pods module over the run's seed. A
node's rack comes from the configuration's file: `fake_node_i` lies in rack
i mod `racks`. That reading is cross-checked once against the labels the
service holds: two nodes are in one rack here exactly when their machines
carry one value of the rack label there. Every limit is exact.
"""

from typing import Dict, List

from benchmarks import reference_quincy
from benchmarks.correct import pod_classes
from ksched_tpu.data import RACK_LABEL


def racks_of_nodes(ctx) -> Dict[str, int]:
    racks = int(ctx.config["racks"])
    return {node: int(node.rsplit("_", 1)[1]) % racks for node in ctx.svc.node_to_machine}


def labels_disagree(ctx, rack_of: Dict[str, int]) -> List[str]:
    """Where the service's labels are another partition of the nodes."""
    label_of_rack: Dict[int, str] = {}
    rack_of_label: Dict[str, int] = {}
    for node, machine in ctx.svc.node_to_machine.items():
        label = ctx.svc.resource_map.find(machine).descriptor.labels.get(RACK_LABEL)
        rack = rack_of[node]
        if label is None:
            return [f"node {node} carries no {RACK_LABEL} label"]
        if label_of_rack.setdefault(rack, label) != label or rack_of_label.setdefault(label, rack) != rack:
            return [f"node {node}: rack {rack} by its name, label {label!r} on the service"]
    return []


def check(ctx) -> List[str]:
    args = ctx.svc_args
    capacity = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu
    rack_of = racks_of_nodes(ctx)
    faults = labels_disagree(ctx, rack_of)
    classes = pod_classes(ctx.plan, ctx.log)
    inputs_of = {
        pod: ctx.make_pod(pod, classes[pod]).inputs
        for pod in {pod for kind, pod, _node, _t in ctx.log if kind == "bind"}
    }
    polls = getattr(getattr(ctx.svc, "api", None), "polls", ())
    found, facts = reference_quincy.check_data_locality(
        ctx.log, inputs_of, rack_of, capacity,
        admitted=[(t1, n) for _t0, t1, n in polls if n],
    )
    ctx.facts["data_locality"] = {
        **facts, "racks": len(set(rack_of.values())), "nodes": len(rack_of),
        "node_capacity": capacity, "polls": len(polls),
        "limit": "served cost == optimum of the round's transportation problem, every round",
    }
    return faults + [f"data locality broken: {f}" for f in found]
