"""`interference_map`: every round places its pods where Whare-Map's class x
platform x co-runner map says they cost least, and no pod waits while a
slot is idle.

The whole run's record (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed in the loop's order by the plain
reference's `check_interference_map` (benchmarks/reference_wharemap.py):
for every round, the sum of cost(c, m) over its Bindings, on the census the
replay keeps from the Bindings and completions alone, equals the optimum of
the round's transportation problem, exactly; with the polls the benchmark's
ClusterAPI kept, a round leaves a pod waiting only if it took every idle
slot. A pod's class comes from the plan the seed drew. A node's platform and
slots come from the configuration's file: `fake_node_i` is of the type the
table `machine_types` deals index i. That reading is cross-checked once
against what the service holds: every machine carries its type's name under
the platform label and has its type's PUs. Every limit is exact.
"""

from typing import List

from benchmarks import reference_wharemap as ref
from benchmarks.correct import pod_classes

#: the node label the service reads a platform from (ksched_tpu.data.PLATFORM_LABEL)
PLATFORM_LABEL = "ksched.io/platform"


def service_disagrees(ctx, types) -> List[str]:
    """Where a machine of the service is not what the file's table deals
    its node: another platform label, another number of PUs."""
    args = ctx.svc_args
    for node, machine in ctx.svc.node_to_machine.items():
        name, cores, _share = ref.machine_type(ref.node_index(node), types)
        status = ctx.svc.resource_map.find(machine)
        label = status.descriptor.labels.get(PLATFORM_LABEL)
        if label != name:
            return [f"node {node}: type {name} by its index, label {label!r} on the service"]
        pus = sum(len(core.children) for core in status.topology_node.children)
        if pus != cores * args.pus_per_core:
            return [f"node {node}: {cores * args.pus_per_core} PUs by its type {name}, {pus} on the service"]
    return []


def check(ctx) -> List[str]:
    args = ctx.svc_args
    types = [tuple(t) for t in ctx.config["machine_types"]]
    nodes = [f"fake_node_{i}" for i in range(args.num_machines)]
    faults = service_disagrees(ctx, types)
    polls = getattr(getattr(ctx.svc, "api", None), "polls", ())
    found, facts = ref.check_interference_map(
        ctx.log, pod_classes(ctx.plan, ctx.log), nodes, types,
        args.pus_per_core, args.max_tasks_per_pu,
        admitted=[(t1, n) for _t0, t1, n in polls if n],
    )
    ctx.facts["interference_map"] = {
        **facts, "polls": len(polls),
        "limit": "served cost == optimum of the round's transportation problem, every round",
    }
    return faults + [f"interference map broken: {f}" for f in found]
