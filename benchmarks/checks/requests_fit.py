"""`requests_fit`: every round places its pods as `NodeResourcesFit` with
`LeastAllocated` and `BalancedAllocation` says, at the least total cost,
and no pod waits while a node it fits has a place.

The whole run's record (the fill, the class sweep, warm-up, the window, the
drain and the closing round) replayed in the loop's order by the plain
reference's `check_requests_fit` (benchmarks/reference_requests.py) on
books of its own: (a) per round no node receives more than cap(m) and every
Binding had its arc; (b) the sum of cost(r, m) over the round's Bindings
(and 500 for each pod it left waiting) equals the optimum of the round's
transportation problem, the sizes' rows onto columns of capacity cap(m)
with holes, by a textbook successive shortest path, exactly; (c) a round
leaves a pod waiting only if every column its size could use was taken.

A pod's request is recomputed through `ctx.make_pod` from the plan's class.
Which pods a round held follows from the order the harness submits them
(the fill, the class sweep's bursts, the arrivals, the closing round: the
plan's order) and the polls the benchmark's ClusterAPI kept (how many pods
each handed over). The node's vector and pod limit come from the file; the
`allocatable` check cross-checks them against the service. Every limit is
exact.
"""

from typing import List

from benchmarks import reference_requests as ref
from benchmarks.checks.allocatable import pod_request
from benchmarks.correct import pod_classes


def submission_order(ctx, handed_over: int) -> List[str]:
    """Every pod the harness submitted, in the order it did: the fill, the
    class sweep, as many arrivals (or waves) as the polls' total leaves
    room for, and the closing round last."""
    plan = ctx.plan
    head = [pod for pod, _c in plan.resident]
    for burst in plan.class_sweep:
        head += [pod for pod, _c in burst]
    tail = [pod for pod, _c in plan.closing]
    middle = handed_over - len(head) - len(tail)
    if plan.arrival_classes is not None:
        body = [plan.arrival(i)[0] for i in range(max(0, middle))]
    else:
        body, k = [], 0
        while len(body) < middle:
            body += [pod for pod, _c in plan.wave(k)]
            k += 1
    return head + body + tail


def check(ctx) -> List[str]:
    args = ctx.svc_args
    nodes = [f"fake_node_{i}" for i in range(args.num_machines)]
    polls = [(t1, n) for _t0, t1, n in getattr(getattr(ctx.svc, "api", None), "polls", ()) if n]
    submitted = submission_order(ctx, sum(n for _t, n in polls))
    classes = pod_classes(ctx.plan, ctx.log)
    requests = {pod: pod_request(ctx, pod, classes[pod]) for pod in submitted if pod in classes}
    found, facts = ref.check_requests_fit(
        ctx.log, requests, nodes, tuple(int(v) for v in ctx.config["node_allocatable"]),
        int(ctx.config["node_pod_limit"]), submitted, polls,
    )
    ctx.facts["requests_fit"] = {
        **facts, "polls": len(polls), "submitted": len(submitted),
        "limit": "served cost == optimum of the round's transportation problem, every round",
    }
    return [f"requests do not fit: {f}" for f in found]
