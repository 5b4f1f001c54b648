"""`interference_coco`: every round places its batch where CoCo's equation
says it costs least, and no pod waits while a slot is free.

The whole run's record (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed in the loop's order by the plain
reference's `check_interference_coco` (benchmarks/reference_coco.py): for
every round, the sum of cost(c, m) over its Bindings, on the census the
replay keeps from the Bindings and completions alone as it stood when the
round began (completions the service took before the round are applied
before it), plus 2,500 for each pod the round left waiting, equals the
optimum of the round's transportation problem, exactly; and a round leaves
a pod waiting only if it took every free slot. No tolerance: costs are
integers. A pod's class comes from the plan the seed drew; a node's slots
from the configuration's argv.

What a round had to place is what the polls handed over and no round has
bound yet. The benchmark's ClusterAPI keeps when each poll ended and how
many pods it handed over; which pods follows from the plan, because the
channel is a FIFO and the driver submits in the plan's order (the fill, the
class sweep, the arrivals or the waves, the closing round): `handed_over`.
The replay refuses a Binding of a pod no poll had handed over, so a reading
of the polls that is off is a fault, not a pass.
"""

from typing import List, Sequence, Tuple

from benchmarks import reference_coco as ref
from benchmarks.correct import pod_classes


def handed_over(plan, polls: Sequence[Tuple[float, float, int]]) -> List[Tuple[float, List[str]]]:
    """(when the poll ended, the pods it handed over) for every poll that
    handed over any, from the plan's order of submission and the polls'
    counts."""
    total = sum(n for _t0, _t1, n in polls)
    order = [pod for pod, _c in plan.resident]
    order += [pod for burst in plan.class_sweep for pod, _c in burst]
    middle = total - len(order) - len(plan.closing)
    if plan.arrival_classes is not None:
        order += [plan.arrival(i)[0] for i in range(middle)]
    else:
        for k in range(-(-middle // plan.wave_pods) if plan.wave_pods else 0):
            order += [pod for pod, _c in plan.wave(k)]
    order += [pod for pod, _c in plan.closing]
    out, at = [], 0
    for _t0, t1, n in polls:
        if n:
            out.append((t1, order[at:at + n]))
            at += n
    return out


def check(ctx) -> List[str]:
    args = ctx.svc_args
    slots = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu
    nodes = [f"fake_node_{i}" for i in range(args.num_machines)]
    polls = getattr(getattr(ctx.svc, "api", None), "polls", ())
    found, facts = ref.check_interference_coco(
        ctx.log, pod_classes(ctx.plan, ctx.log), nodes, slots,
        batches=handed_over(ctx.plan, polls),
    )
    ctx.facts["interference_coco"] = {
        **facts, "polls": len(polls),
        "limit": "served cost + 2,500 a pod left waiting == optimum of the round's "
                 "transportation problem, every round",
    }
    return [f"CoCo's interference equation broken: {f}" for f in found]
