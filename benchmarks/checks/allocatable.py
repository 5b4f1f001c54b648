"""`allocatable`: at no instant does a node hold more than its allocatable
CPU, its allocatable memory or its pod limit.

`capacity` counts pods against cores x PUs x slots; here pods differ in
size, and a node's limits are the three of a Kubernetes node: the sum of
its pods' CPU requests within `node_allocatable[0]` millicores, of their
memory requests within `node_allocatable[1]` MiB, and at most
`node_pod_limit` pods. The run's log of ("bind", pod, node, t) / ("done",
pod, "", t) is replayed in the order the loop thread made them, as
`capacity` replays it. A pod's vector is recomputed through `ctx.make_pod`
from the plan's class (millicores: the CPUs a PodEvent carries, times
1,000, rounded). The node's vector and limit come from the configuration's
file, cross-checked once against what the service holds: every machine's
descriptor says the same allocatable, and has the slots the limit says. The
first instant a node is over one of the three, a completion of a pod with
no Binding on record, or any other kind of entry, is the fault. A completed
pod frees its requests at its "done" entry: the instant the cluster knows
of, earlier than the scheduler's books let go of it, so what is held here
is the cluster's view and the strict one. Every limit is exact.
"""

from typing import Dict, List, Tuple

from benchmarks.correct import pod_classes


def pod_request(ctx, pod: str, task_class: int) -> Tuple[int, int]:
    """(CPU millicores, memory MiB) the pod carried, through the cell's
    pods module."""
    event = ctx.make_pod(pod, task_class)
    return int(round(event.cpu_request * 1000)), int(event.memory_request)


def move(held: List[int], request: Tuple[int, int], sign: int) -> None:
    """A pod that asks `request` joins (+1) or leaves (-1) a node's load
    [CPU millicores, memory MiB, pods]."""
    held[0] += sign * request[0]
    held[1] += sign * request[1]
    held[2] += sign


def service_disagrees(ctx) -> List[str]:
    """Where a machine of the service is not the node the file describes."""
    cpu, mem = ctx.config["node_allocatable"]
    limit = int(ctx.config["node_pod_limit"])
    args = ctx.svc_args
    for node, machine in ctx.svc.node_to_machine.items():
        status = ctx.svc.resource_map.find(machine)
        capacity = status.descriptor.capacity
        held = (int(round(capacity.cpu_cores * 1000)), int(capacity.ram_cap))
        if held != (cpu, mem):
            return [f"node {node}: allocatable {held} on the service, {(cpu, mem)} in the file"]
        pus = sum(len(core.children) for core in status.topology_node.children)
        if pus * args.max_tasks_per_pu != limit:
            return [f"node {node}: {pus * args.max_tasks_per_pu} slots on the service, "
                    f"a pod limit of {limit} in the file"]
    return []


def check(ctx) -> List[str]:
    cpu, mem = (int(v) for v in ctx.config["node_allocatable"])
    limit = int(ctx.config["node_pod_limit"])
    classes = pod_classes(ctx.plan, ctx.log)
    requests: Dict[str, Tuple[int, int]] = {}
    where: Dict[str, str] = {}
    load: Dict[str, List[int]] = {}
    peak = [0, 0, 0]
    ctx.facts["allocatable"] = facts = {
        "replayed": len(ctx.log), "limits": [cpu, mem, limit], "peak": peak,
    }
    faults = service_disagrees(ctx)
    for kind, pod, node, t in ctx.log:
        if kind == "bind":
            if pod not in classes:
                return faults + [f"pod {pod} is bound and the plan does not know it"]
            request = requests[pod] = pod_request(ctx, pod, classes[pod])
            old = where.get(pod)
            if old is not None:
                move(load[old], request, -1)
            where[pod] = node
            held = load.setdefault(node, [0, 0, 0])
            move(held, request, +1)
            for axis in range(3):
                peak[axis] = max(peak[axis], held[axis])
            if held[0] > cpu or held[1] > mem or held[2] > limit:
                return faults + [
                    f"t={t:.6f}: node {node} holds {held[0]}m, {held[1]} MiB in {held[2]} pods "
                    f"(pod {pod} asks {request}); it can give {cpu}m, {mem} MiB, {limit} pods"
                ]
        elif kind == "done":
            node = where.pop(pod, None)
            if node is None:
                return faults + [f"pod {pod} completed without a Binding on record"]
            move(load[node], requests.pop(pod), -1)
        else:
            return faults + [f"t={t:.6f}: a {kind!r} entry: this deployment is served without preemption"]
    facts["pods_on_record"] = len(where)
    return faults
