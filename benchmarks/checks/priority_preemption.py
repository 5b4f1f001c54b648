"""`priority_preemption`: a pod is evicted only for a pod of strictly
higher priority that needed its slot, and the pods of the highest
priorities hold the slots.

The whole run's log (the fill, warm-up, the window, the drain and the
closing round) replayed by the plain reference's
`check_priority_preemption` (benchmarks/reference_preemption.py: (a) every
evicted pod left a node onto which the same round bound a pod of strictly
higher tier; (b) every node that lost a pod is full after the round; (c)
per round, bound-by-tier and evicted-by-tier equal the greedy
`reference_round` on the replay's own books; (d) at the end no pod of a
higher tier is pending while one of a lower tier runs). Each pod's tier is
recomputed through `ctx.make_pod`, the configuration's pods module over
the run's seed, for the pods that were submitted: the fill, the closing
round, every pod the log names, and the arrivals up to the last one due in
the window (the open loop submits them in order).
"""

from typing import Dict, List

from benchmarks import reference_preemption
from benchmarks.correct import pod_classes


def submitted_pods(ctx) -> Dict[str, int]:
    """pod -> class of every pod the run submitted."""
    classes = pod_classes(ctx.plan, ctx.log)
    last_due = max((int(p[1:]) for p in ctx.due if p[0] == "p"), default=-1)
    logged = {pod for _kind, pod, _node, _t in ctx.log}
    return {
        pod: c for pod, c in classes.items()
        if pod[0] != "p" or pod in logged or int(pod[1:]) <= last_due
    }


def check(ctx) -> List[str]:
    args = ctx.svc_args
    capacity = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu
    tier_of = {pod: ctx.make_pod(pod, c).priority for pod, c in submitted_pods(ctx).items()}
    faults, facts = reference_preemption.check_priority_preemption(
        ctx.log, tier_of, capacity, num_nodes=len(ctx.svc.node_to_machine)
    )
    ctx.facts["priority_preemption"] = {
        **facts, "pods": len(tier_of), "node_capacity": capacity,
        "nodes": len(ctx.svc.node_to_machine),
    }
    return [f"priority preemption broken: {f}" for f in faults]
