"""`binding_after_eviction`: every pod that arrives gets a Binding, and
another one only after the service took it off its node.

As far as a run can show it: every pod due in the window got a Binding
(within the drain); and in the whole run's log, pod by pod, Bindings and
evictions alternate, a Binding first: between two Bindings of one pod lies
exactly one `evict` of it, and no pod is evicted that holds no Binding.
`binding` (exactly one Binding a pod) is what a deployment states that
never evicts.
"""

from typing import Dict, List


def check(ctx) -> List[str]:
    faults = []
    missing = [p for p in ctx.due if p not in ctx.bind_stamps]
    if missing:
        faults.append(f"{len(missing)} pods due in the window got no Binding (first: {missing[0]})")
    bound: Dict[str, bool] = {}
    twice = unheld = evictions = again = 0
    first = {}
    for kind, pod, _node, t in ctx.log:
        if kind == "bind":
            if bound.get(pod):
                twice += 1
                first.setdefault("twice", (pod, t))
            again += pod in bound
            bound[pod] = True
        elif kind == "evict":
            evictions += 1
            if not bound.get(pod):
                unheld += 1
                first.setdefault("unheld", (pod, t))
            bound[pod] = False
    if twice:
        pod, t = first["twice"]
        faults.append(
            f"{twice} Bindings of a pod that held one, with no eviction between "
            f"(first: {pod} at t={t:.6f})"
        )
    if unheld:
        pod, t = first["unheld"]
        faults.append(
            f"{unheld} evictions of a pod that held no Binding (first: {pod} at t={t:.6f})"
        )
    ctx.facts["binding_after_eviction"] = {
        "due": len(ctx.due), "unbound": len(missing), "bound": len(ctx.bind_stamps),
        "evictions": evictions, "bound_again": again,
        "pending_evicted_at_end": sum(1 for held in bound.values() if not held),
        "second_binding_unevicted": twice, "evicted_unbound": unheld,
    }
    return faults
