"""The plain reference of `k8s-5000-requests`: kube-scheduler's
`NodeResourcesFit` filter with its `LeastAllocated` score and
`NodeResourcesBalancedAllocation` over a node's books, the optimum of one
round by a textbook successive shortest path, and the replay that holds a
served run's record to both.

Independent of the code under test: no graph manager, no cost model class,
no solver; nothing of `ksched_tpu` is imported. CPU in millicores, memory
in MiB. Every node can give A = (A_cpu, A_mem) to pods and holds at most P
of them. The books of node m, kept from the Bindings and completions alone:
reserved(m), the sum of the requests of the pods on it, and running(m),
their number. `r_max` is the componentwise maximum of the requests of every
pod handed to the scheduler so far. For node m at the start of a round:

    free(m) = A - reserved(m)          slots(m) = P - running(m)
    k(m)    = min(free_cpu(m) // r_max.cpu, free_mem(m) // r_max.mem, slots(m))
    cap(m)  = k(m) if k(m) > 0 else min(1, slots(m))

A pod that asks for r may go to m iff r <= free(m) componentwise and
cap(m) > 0 (it "has its arc"), m takes at most cap(m) new pods in the round
whatever they ask for, and the pod pays, in integers,

    u_cpu = (reserved_cpu(m) + r.cpu) * 100 // A_cpu,   u_mem likewise
    cost(r, m) = (u_cpu + u_mem) // 2 + abs(u_cpu - u_mem) // 2

(200 less the sum of the two default scores; 0..150) on the books of the
round's start, whatever else the round binds there. A pod that stays
unscheduled costs UNSCHEDULED_COST = 500, more than any node.

`cost_rows` is the equation over every node for each size; `reference_round`
the least total cost with which a round's pods, by size, go onto columns of
capacity cap(m) with holes (or stay unscheduled); `check_requests_fit`
replays a record round by round on books of its own and compares.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

import numpy as np

UNSCHEDULED_COST = 500
#: a cell no pod may take
HOLE = np.iinfo(np.int64).max // 8

Request = Tuple[int, int]  # (CPU millicores, memory MiB)


def node_intake(reserved: np.ndarray, running: np.ndarray, allocatable: Request,
                pod_limit: int, r_max: Request) -> np.ndarray:
    """cap(m) for every node: int64 [M] from the reserved vectors [M, 2]
    and the pods running [M]."""
    slots = pod_limit - running
    k = slots.copy()
    for axis in (0, 1):
        if r_max[axis] > 0:
            k = np.minimum(k, (allocatable[axis] - reserved[:, axis]) // r_max[axis])
    return np.where(k > 0, k, np.minimum(1, np.maximum(slots, 0)))


def cost_rows(reserved: np.ndarray, allocatable: Request,
              sizes: Sequence[Request]) -> Tuple[np.ndarray, np.ndarray]:
    """(cost(r, m), whether r fits m) for every size of `sizes` and every
    node: int64 [S, M] and bool [S, M]."""
    cost = np.empty((len(sizes), len(reserved)), np.int64)
    fits = np.empty((len(sizes), len(reserved)), bool)
    for s, size in enumerate(sizes):
        used = [(reserved[:, axis] + size[axis]) * 100 // allocatable[axis] for axis in (0, 1)]
        cost[s] = (used[0] + used[1]) // 2 + np.abs(used[0] - used[1]) // 2
        fits[s] = (reserved[:, 0] + size[0] <= allocatable[0]) & (
            reserved[:, 1] + size[1] <= allocatable[1])
    return cost, fits


def transport(cost: np.ndarray, supply: np.ndarray, capacity: np.ndarray, escape: int) -> int:
    """The least total cost of sending supply[r] units from every row r to
    the columns, column k taking at most capacity[k] over all rows, a cell
    at HOLE taking none, a unit that is not sent costing `escape`:
    successive shortest paths. Each pass is one Bellman-Ford over the
    residual network from the rows that still hold units (no residual
    cycle is negative while the flow so far is optimal for what it
    carries) and pushes along the path it finds as many units as it takes."""
    rows = len(supply)
    # the escape is one more column, with room for everything
    cost = np.concatenate([cost.astype(np.int64), np.full((rows, 1), escape, np.int64)], axis=1)
    capacity = np.concatenate([capacity.astype(np.int64), [int(supply.sum())]])
    cols = len(capacity)
    inf = HOLE
    flow = np.zeros((rows, cols), np.int64)
    used = np.zeros(cols, np.int64)
    left = supply.astype(np.int64).copy()
    every_row, every_col = np.arange(rows), np.arange(cols)
    open_cell = cost < inf
    total = 0
    while left.any():
        dist_row = np.where(left > 0, 0, inf)
        dist_col = np.full(cols, inf, np.int64)
        row_of_col = np.full(cols, -1, np.int64)  # the row a column is reached from
        col_of_row = np.full(rows, -1, np.int64)  # the column a row is reached back from
        for _ in range(rows + 1):
            # a row reaches every column that is open to it, at its cost
            reach = np.where(open_cell & (dist_row[:, None] < inf), dist_row[:, None] + cost, inf)
            best = reach.argmin(axis=0)
            nearest = reach[best, every_col]
            nearer = nearest < dist_col
            dist_col[nearer] = nearest[nearer]
            row_of_col[nearer] = best[nearer]
            # a column gives back a unit a row sent to it, at minus its cost
            back = np.where((flow > 0) & (dist_col[None, :] < inf), dist_col[None, :] - cost, inf)
            via = back.argmin(axis=1)
            gain = back[every_row, via]
            better = gain < dist_row
            if not better.any():
                break
            dist_row[better] = gain[better]
            col_of_row[better] = via[better]
        end = int(np.where(used < capacity, dist_col, inf).argmin())
        # walk the path back to the row it starts from; the units it takes
        path = []
        units = int(capacity[end] - used[end])
        col = end
        while True:
            row = int(row_of_col[col])
            path.append((row, col, 1))
            col = int(col_of_row[row])
            if col < 0:
                break
            path.append((row, col, -1))
            units = min(units, int(flow[row, col]))
        units = min(units, int(left[row]))
        for r, k, sign in path:
            flow[r, k] += sign * units
        left[row] -= units
        used[end] += units
        total += units * int(dist_col[end])
    return total


def reference_round(cost: np.ndarray, open_cell: np.ndarray, capacity: np.ndarray,
                    pods_by_size: Sequence[int]) -> int:
    """The optimum of one round's transportation problem: `pods_by_size`
    pods of each size onto the nodes, node m taking capacity[m] of them
    over all sizes and size s only where open_cell[s, m], at cost[s, m], or
    unscheduled. Two reductions, both exact, keep the textbook solver's
    problem small: of a round of n pods a size never needs more than its n
    cheapest open columns (n columns cannot all be taken by the n - 1 other
    pods), and nodes that cost every size alike, holes included, are one
    column with their capacities summed."""
    supply = np.asarray(pods_by_size, np.int64)
    n = int(supply.sum())
    if n == 0:
        return 0
    priced = np.where(open_cell & (capacity > 0)[None, :], cost, HOLE)
    keep = (priced < HOLE).any(axis=0)
    if n < keep.sum() // 8:
        nearest = np.zeros(len(keep), bool)
        for s in np.nonzero(supply)[0]:
            nearest[np.argpartition(priced[s], n - 1)[:n]] = True
        keep &= nearest
    priced, room = priced[:, keep], capacity[keep]
    alike, which = np.unique(priced.T, axis=0, return_inverse=True)
    room = np.bincount(which.reshape(-1), weights=room, minlength=len(alike)).astype(np.int64)
    return transport(alike.T, supply, room, UNSCHEDULED_COST)


def check_requests_fit(
    log: Iterable[Tuple[str, str, str, float]], request_of: Mapping[str, Request],
    nodes: Sequence[str], allocatable: Request, pod_limit: int,
    submitted: Sequence[str], admitted: Sequence[Tuple[float, int]],
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    record in the loop's order on books of its own: each node's reserved
    vector and pod count, from the Bindings and completions alone. The
    entries of one `assign_bindings` call share one stamp and are one
    round. `submitted` is every pod of the run in the order the scheduler's
    channel got them and `admitted` the polls that handed pods over ((when
    the poll ended, how many), in order): together they say which pods a
    round held, so which waited after it and what `r_max` was. A round is
    priced on the books as they stood when its batch was taken. A pod whose
    completion the service took since the round before still counts on this
    round's books and holds its place through this round's solve: the
    scheduler lets go of it in the `deltas` phase of this round, after the
    solve, and the model's books move with the same event. It leaves the
    books after the round. (A round that solved for pods and bound none of
    them is not in the record and lets completed pods go unseen until the
    next round with a Binding, as in `reference_wharemap`.)

    Held, round by round:

    (a) every Binding had its arc (its request fitted the node's free
        vector at the round's start and the node had a place), no node
        received more pods than cap(m), and after the round no node is
        over its allocatable vector or its pod limit;
    (b) the sum of cost(r, m) over the round's Bindings equals
        `reference_round` on those books for the pods the round held,
        exactly;
    (c) a pod waits after the round only if every node its size could use
        was taken up to cap(m).

    Returns (the faults, at most one of each kind; facts)."""
    index = {node: i for i, node in enumerate(nodes)}
    reserved = np.zeros((len(nodes), 2), np.int64)
    running = np.zeros(len(nodes), np.int64)
    where: Dict[str, int] = {}
    leaving: List[str] = []  # completed since the last round: still on the books
    waiting: List[str] = []  # handed over and not bound yet, in the channel's order
    r_max = [0, 0]
    polls = list(admitted)
    polled = handed = 0
    faults: Dict[str, str] = {}
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_compared": 0, "rounds_that_left_pods": 0,
        "pods_bound": 0, "served_cost": 0, "optimum_cost": 0, "largest_round": 0,
        "served_cost_after_fill": 0, "rounds_costing_zero": 0,
        "nodes": len(nodes), "peak_cpu": 0, "peak_mem": 0, "peak_pods": 0,
        "most_gated": 0, "bound_at_intake_one": 0, "r_max": [0, 0],
        # when the bound acts: nodes that received exactly cap(m) pods in a round
        "columns_saturated": 0, "columns_saturated_after_fill": 0,
        "rounds_saturating_after_fill": 0,
        # what the cluster holds after the last round, percent of its allocatable
        "reserved_cpu_percent": 0.0, "reserved_mem_percent": 0.0,
    }

    def close_round(binds: List[Tuple[str, str]], t: float) -> None:
        nonlocal polled, handed
        facts["rounds"] += 1
        # the pods handed over before this round's Bindings went out
        while polled < len(polls) and polls[polled][0] <= t:
            fresh = submitted[handed: handed + polls[polled][1]]
            handed += len(fresh)
            polled += 1
            waiting.extend(fresh)
            for pod in fresh:
                request = request_of.get(pod, (0, 0))
                r_max[0], r_max[1] = max(r_max[0], request[0]), max(r_max[1], request[1])
        facts["r_max"] = list(r_max)
        held = set(waiting)
        for pod, node in binds:
            if node not in index or pod not in request_of or pod not in held:
                faults.setdefault("a", (
                    f"t={t:.6f}: pod {pod} bound to {node}: no pod the round held, "
                    "or no node of the cluster"
                ))
                return
        sizes = sorted({request_of[pod] for pod in waiting})
        size_row = {size: s for s, size in enumerate(sizes)}
        cap = node_intake(reserved, running, allocatable, pod_limit, tuple(r_max))
        cost, fits = cost_rows(reserved, allocatable, sizes)
        open_cell = fits & (cap > 0)[None, :]
        facts["most_gated"] = max(facts["most_gated"], int((~open_cell.all(axis=0)).sum()))
        took = np.zeros(len(nodes), np.int64)
        served = 0
        for pod, node in binds:
            m, s = index[node], size_row[request_of[pod]]
            took[m] += 1
            served += int(cost[s, m])
            if not open_cell[s, m]:
                faults.setdefault("a", (
                    f"t={t:.6f}: pod {pod} asking {request_of[pod]} bound to {node}, which "
                    f"had {tuple(int(allocatable[x] - reserved[m, x]) for x in (0, 1))} free "
                    f"and took up to {int(cap[m])}: no arc"
                ))
        over = np.nonzero(took > cap)[0]
        if len(over):
            m = int(over[0])
            faults.setdefault("a", (
                f"t={t:.6f}: node {nodes[m]} received {int(took[m])} pods in one round, "
                f"cap(m) was {int(cap[m])}"
            ))
        facts["bound_at_intake_one"] += int(((took > 0) & (cap == 1)).sum())
        saturated = int(((took > 0) & (took == cap)).sum())
        facts["columns_saturated"] += saturated
        if facts["rounds"] > 1:
            facts["columns_saturated_after_fill"] += saturated
            facts["rounds_saturating_after_fill"] += saturated > 0
        # (b) the round's pods, by size, at their optimum
        by_size = np.bincount([size_row[request_of[pod]] for pod in waiting], minlength=len(sizes))
        want = reference_round(cost, open_cell, cap, by_size)
        bound_now = {pod for pod, _n in binds}
        left = [pod for pod in waiting if pod not in bound_now]
        paid = served + UNSCHEDULED_COST * len(left)
        facts["rounds_compared"] += 1
        facts["served_cost"] += paid
        facts["optimum_cost"] += want
        facts["rounds_costing_zero"] += want == 0
        if len(binds) > facts["largest_round"]:
            facts["largest_round"] = len(binds)
        if facts["rounds"] > 1:
            facts["served_cost_after_fill"] += served
        if paid != want:
            faults.setdefault("b", (
                f"t={t:.6f}: the round's {len(binds)} Bindings cost {served} and {len(left)} "
                f"pods waited at {UNSCHEDULED_COST}: {paid}; the optimum of the round is {want}"
            ))
        # (c) whoever waits could go nowhere
        if left:
            facts["rounds_that_left_pods"] += 1
            room = cap - took
            for pod in left:
                usable = open_cell[size_row[request_of[pod]]] & (room > 0)
                if usable.any():
                    faults.setdefault("c", (
                        f"t={t:.6f}: pod {pod} asking {request_of[pod]} waited while node "
                        f"{nodes[int(np.nonzero(usable)[0][0])]} had an arc for it and room"
                    ))
                    break
        waiting[:] = left
        for pod, node in binds:
            m = index[node]
            where[pod] = m
            reserved[m] += request_of[pod]
            running[m] += 1
        facts["pods_bound"] += len(binds)
        # the strict limits, at every instant the record shows
        facts["peak_cpu"] = max(facts["peak_cpu"], int(reserved[:, 0].max()))
        facts["peak_mem"] = max(facts["peak_mem"], int(reserved[:, 1].max()))
        facts["peak_pods"] = max(facts["peak_pods"], int(running.max()))
        for axis, name in enumerate(("reserved_cpu_percent", "reserved_mem_percent")):
            facts[name] = 100.0 * int(reserved[:, axis].sum()) / (len(nodes) * allocatable[axis])
        full = np.nonzero(
            (reserved[:, 0] > allocatable[0]) | (reserved[:, 1] > allocatable[1])
            | (running > pod_limit)
        )[0]
        if len(full):
            m = int(full[0])
            faults.setdefault("a", (
                f"t={t:.6f}: node {nodes[m]} holds {tuple(reserved[m].tolist())} in "
                f"{int(running[m])} pods, over {tuple(allocatable)} or {pod_limit} pods"
            ))
        # the round's `deltas` phase let go of what completed before it
        for pod in leaving:
            m = where.pop(pod, None)
            if m is not None:
                reserved[m] -= request_of[pod]
                running[m] -= 1
        leaving.clear()

    group: List[Tuple[str, str]] = []
    group_t = 0.0
    for kind, pod, node, t in log:
        facts["replayed"] += 1
        if group and (kind != "bind" or t != group_t):
            close_round(group, group_t)
            group = []
        if kind == "bind":
            group.append((pod, node))
            group_t = t
        elif kind == "done":
            leaving.append(pod)
        else:
            faults.setdefault("e", f"t={t:.6f}: a {kind!r} entry: this policy is served without preemption")
    if group:
        close_round(group, group_t)
    return [faults[k] for k in sorted(faults)], facts
