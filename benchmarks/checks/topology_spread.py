"""`topology_spread`: after every round's Bindings no zone that received a
pod of a workload holds more than `max_skew` pods of it above the lowest
zone (the batch form of kube-scheduler's per-pod filter for a
`topologySpreadConstraints` entry with `whenUnsatisfiable: DoNotSchedule`
on topology.kubernetes.io/zone against the pod's own label).

The whole run's log (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed by the plain reference's
`check_topology_spread`, with each pod's workload (its class) from the
plan and each node's zone from the configuration's file: `fake_node_i`
lies in zone i mod `zones`. That reading is cross-checked once against
the labels the service holds: two nodes are in one zone here exactly when
their machines carry one value of the zone label there.
"""

from typing import Dict, List

from benchmarks import reference_zonespread
from benchmarks.correct import pod_classes
from ksched_tpu.data import ZONE_LABEL


def zones_of_nodes(ctx) -> Dict[str, int]:
    zones = int(ctx.config["zones"])
    return {node: int(node.rsplit("_", 1)[1]) % zones for node in ctx.svc.node_to_machine}


def labels_disagree(ctx, zone_of: Dict[str, int]) -> List[str]:
    """Where the service's labels are another partition of the nodes."""
    label_of_zone: Dict[int, str] = {}
    zone_of_label: Dict[str, int] = {}
    for node, machine in ctx.svc.node_to_machine.items():
        label = ctx.svc.resource_map.find(machine).descriptor.labels.get(ZONE_LABEL)
        zone = zone_of[node]
        if label is None:
            return [f"node {node} carries no {ZONE_LABEL} label"]
        if label_of_zone.setdefault(zone, label) != label or zone_of_label.setdefault(label, zone) != zone:
            return [f"node {node}: zone {zone} by its name, label {label!r} on the service"]
    return []


def check(ctx) -> List[str]:
    max_skew = int(ctx.config["max_skew"])
    zone_of = zones_of_nodes(ctx)
    faults = labels_disagree(ctx, zone_of)
    group_of = pod_classes(ctx.plan, ctx.log)
    fault, facts = reference_zonespread.check_topology_spread(
        ctx.log, group_of, zone_of, max_skew
    )
    ctx.facts["topology_spread"] = {
        **facts, "zones": len(set(zone_of.values())),
        "workloads": len(set(group_of.values())),
        "limit": f"f(g, z) <= min f(g, .) + {max_skew} for every zone a round bound a pod of g in",
    }
    if fault is not None:
        faults.append(f"topology spread broken at {fault}")
    return faults
