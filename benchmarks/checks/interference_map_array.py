"""`interference_map_array`: every round of the array round places its batch
where Whare-Map's class x platform x co-runner map says it costs least, on
machines that differ, and no pod waits while a slot is idle.

The whole run's record (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed in the loop's order by the plain
reference's `check_interference_map_array`
(benchmarks/reference_wharemap_array.py, over the equation and the optimum of
benchmarks/reference_wharemap.py): for EVERY round, the sum of cost(c, m) over
its Bindings, on the census the replay keeps from the Bindings and completions
alone as it stood when the round began (a completion the service took before
the round has left it: the array round's rule), plus 2,500 for each pod the
round left waiting, equals the optimum of the round's transportation problem,
exactly; and a round leaves a pod waiting only if it took every idle slot. No
tolerance: costs are integers. A pod's class comes from the plan the seed
drew; what a round had to place from the polls the benchmark's ClusterAPI
kept, as `checks/interference_coco.handed_over` reads them. A node's platform
and slots come from the configuration's file: `fake_node_i` is of the type
the table `machine_types` deals index i. That reading is cross-checked once
against what the service holds on the device's side: every machine of the
table has its type's PUs (the others of its padded row hold no slot) and is
priced by its type's platform.
"""

from typing import List

import numpy as np

from benchmarks import reference_wharemap_array as ref
from benchmarks.checks.interference_coco import handed_over
from benchmarks.correct import pod_classes
from benchmarks.reference_wharemap import PLATFORMS, machine_type, node_index


def service_disagrees(svc, types, pus_per_core: int, max_tasks_per_pu: int) -> List[str]:
    """Where a machine of the service's table is not what the file's table
    deals its node: another number of PUs, a PU of another size, another
    platform."""
    cluster = getattr(svc, "cluster", None)
    if cluster is None:
        return ["the service keeps no table on the device (--array-round did not take)"]
    pu_slots = np.asarray(cluster.pu_slots).reshape(len(svc.nodes), -1)
    platform = np.asarray(getattr(svc, "machine_platform", ()))
    if len(platform) != len(svc.nodes):
        return ["the service names no platform for its machines"]
    for m, node in enumerate(svc.nodes):
        name, cores, _share = machine_type(node_index(node), types)
        pus = int((pu_slots[m] > 0).sum())
        if pus != cores * pus_per_core or int(pu_slots[m].sum()) != pus * max_tasks_per_pu:
            return [
                f"node {node}: {cores * pus_per_core} PUs of {max_tasks_per_pu} slots by its type "
                f"{name}, {pus} PUs and {int(pu_slots[m].sum())} slots in the service's table"
            ]
        want = PLATFORMS.index(name) if name in PLATFORMS else PLATFORMS.index("B")
        if int(platform[m]) != want:
            return [f"node {node}: platform {name} by its index, {int(platform[m])} on the service"]
    return []


def check(ctx) -> List[str]:
    args = ctx.svc_args
    types = [tuple(t) for t in ctx.config["machine_types"]]
    nodes = [f"fake_node_{i}" for i in range(args.num_machines)]
    faults = service_disagrees(ctx.svc, types, args.pus_per_core, args.max_tasks_per_pu)
    polls = getattr(getattr(ctx.svc, "api", None), "polls", ())
    found, facts = ref.check_interference_map_array(
        ctx.log, pod_classes(ctx.plan, ctx.log), nodes, types,
        args.pus_per_core, args.max_tasks_per_pu, batches=handed_over(ctx.plan, polls),
    )
    ctx.facts["interference_map_array"] = {
        **facts, "polls": len(polls),
        "limit": "served cost + 2,500 a pod left waiting == optimum of the round's "
                 "transportation problem, every round",
    }
    return faults + [f"interference map broken: {f}" for f in found]
