"""`anti_affinity`: at every instant no node holds two pods of one
workload, n(g, m) <= 1 for every workload g and node m.

The whole run's log (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed by the plain reference's
`check_anti_affinity`, with each pod's workload (its class) from the
plan; the first instant a node holds two pods of one workload is the
fault.
"""

from typing import List

from benchmarks import reference_antiaffinity
from benchmarks.correct import pod_classes


def check(ctx) -> List[str]:
    group_of = pod_classes(ctx.plan, ctx.log)
    fault = reference_antiaffinity.check_anti_affinity(ctx.log, group_of)
    ctx.facts["anti_affinity"] = {
        "replayed": len(ctx.log), "workloads": len(set(group_of.values())),
        "limit": "n(g, m) <= 1",
    }
    return [] if fault is None else [f"anti-affinity broken at {fault}"]
