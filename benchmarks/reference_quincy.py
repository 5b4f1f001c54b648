"""The plain reference of `gtrace-12500-quincy`: Quincy's data-locality
policy with its rack tier, as equations over a pod's input blocks, the
optimum of one round by a textbook successive shortest path, and the
replay that holds a served run's record to both.

Independent of the code under test: no graph manager, no cost model
class, no solver; nothing of `ksched_tpu` is imported. Quincy (Isard et
al., SOSP'09, section 4.2) as Firmament evaluates it (Gog et al.,
OSDI'16, section 7). A pod t reads blocks b of s_b bytes, each held by
the nodes H(b); a node m lies in the rack `rack_of[m]`:

    total     = sum of s_b
    local(m)  = sum of s_b with m in H(b)
    inrack(l) = sum of s_b with some replica in rack l

The policy gives the pod three kinds of route to a node m of rack l, each
priced in quanta of Q = QUANTUM bytes, the whole weighted byte sum
divided by Q and rounded down:

    its machine arc, if local(m) >= 14% of total:
        d(t, m)   = (PSI * (inrack(l) - local(m)) + XI * (total - inrack(l))) // Q
    its rack arc, if inrack(l) >= 14% of total:
        rho(t, l) = (PSI * inrack(l) + XI * (total - inrack(l))) // Q
    the cluster aggregator, always:
        alpha(t)  = (XI * total) // Q

PSI = 1 a quantum through a rack switch, XI = 2 through the core switch.
At most MAX_PREFS = 7 machine arcs and 7 rack arcs a pod: those holding
most, ties to the one met first in the order of the pod's blocks and,
within a block, of its replicas. Every route stops at LARGEST_COST - 1.
Leaving the pod unscheduled costs alpha(t) + 1 + OMEGA * rounds waited,
at most LARGEST_COST: more than any route, so a round with room places
every pod, and what it minimises is the sum of the route costs.

`route_cost` is the cheapest of the routes the policy gives a pod to a
node; `reference_round` the least sum of route costs with which the
round's pods fit the free slots; `check_data_locality` replays a record
round by round and compares. A pod that reads nothing (the resident pods
of the fill) costs 0 wherever it lands.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

MB = 1 << 20
QUANTUM = 16 * MB
PSI = 1
XI = 2
DELTA_PCT = 14
MAX_PREFS = 7
OMEGA = 10
LARGEST_COST = 1023

#: one input block: (block id, bytes, the nodes that hold a replica)
Block = Tuple[int, int, Sequence[str]]


class Routes:
    """What the policy gives one pod: alpha, its machine arcs node ->
    d(t, m), its rack arcs rack -> rho(t, l); and the sums behind them."""

    __slots__ = ("total", "local", "alpha", "machines", "racks")

    def __init__(self, blocks: Sequence[Block], rack_of: Mapping[str, object]) -> None:
        local: Dict[str, int] = {}
        inrack: Dict[object, int] = {}
        total = 0
        for _block, size, nodes in blocks:
            total += size
            racks_of_block = []
            for node in nodes:
                if node not in rack_of:
                    continue  # a replica outside the cluster is none
                local[node] = local.get(node, 0) + size
                rack = rack_of[node]
                if rack not in racks_of_block:
                    racks_of_block.append(rack)
                    inrack[rack] = inrack.get(rack, 0) + size
        top = LARGEST_COST - 1
        self.total = total
        self.local = local
        self.alpha = min(XI * total // QUANTUM, top)
        self.machines: Dict[str, int] = {}
        self.racks: Dict[object, int] = {}
        # sorted() is stable: ties stay in the order met
        for node, held in sorted(local.items(), key=lambda kv: -kv[1])[:MAX_PREFS]:
            if 100 * held >= DELTA_PCT * total:
                near = inrack[rack_of[node]]
                self.machines[node] = min((PSI * (near - held) + XI * (total - near)) // QUANTUM, top)
        for rack, near in sorted(inrack.items(), key=lambda kv: -kv[1])[:MAX_PREFS]:
            if 100 * near >= DELTA_PCT * total:
                self.racks[rack] = min((PSI * near + XI * (total - near)) // QUANTUM, top)

    def to_node(self, node: str, rack: object) -> int:
        """The cheapest route to `node`, which lies in `rack`."""
        return min(self.machines.get(node, self.alpha), self.racks.get(rack, self.alpha))

    def via(self, node: str, rack: object) -> int:
        """0 / 1 / 2: the pod has a machine arc to `node`, else a rack arc
        to its rack, else only the cluster aggregator."""
        return 0 if node in self.machines else 1 if rack in self.racks else 2


def route_cost(blocks: Sequence[Block], node: str, rack_of: Mapping[str, object]) -> int:
    """The cheapest of the routes the policy gives a pod that reads
    `blocks` to `node`."""
    return Routes(blocks, rack_of).to_node(node, rack_of[node])


def unscheduled_cost(blocks: Sequence[Block], rack_of: Mapping[str, object], waited: int = 0) -> int:
    return min(Routes(blocks, rack_of).alpha + 1 + OMEGA * waited, LARGEST_COST)


def transport(cost: np.ndarray, capacity: np.ndarray) -> int:
    """The least total cost of sending one unit from every row to the
    columns, column k taking at most capacity[k]: successive shortest
    paths (one Bellman-Ford over the residual network a row; no residual
    cycle is negative while every flow so far is optimal). `capacity`
    sums to at least the number of rows."""
    rows, cols = cost.shape
    inf = np.iinfo(np.int64).max // 4
    cost = cost.astype(np.int64)
    assigned = np.full(rows, -1, np.int64)  # row -> the column it is sent to
    used = np.zeros(cols, np.int64)
    total = 0
    for start in range(rows):
        dist_row = np.full(rows, inf, np.int64)
        dist_col = np.full(cols, inf, np.int64)
        from_row = np.full(cols, -1, np.int64)  # the row a column is reached from
        dist_row[start] = 0
        frontier = np.array([start])
        while len(frontier):
            # a row reaches every column at its cost
            reach = dist_row[frontier, None] + cost[frontier]
            best = reach.argmin(axis=0)
            nearer = reach[best, np.arange(cols)] < dist_col
            dist_col[nearer] = reach[best, np.arange(cols)][nearer]
            from_row[nearer] = frontier[best][nearer]
            # a column gives back a row that was sent to it, at minus its cost
            back = np.nonzero((assigned >= 0) & nearer[np.maximum(assigned, 0)])[0]
            if not len(back):
                break
            gain = dist_col[assigned[back]] - cost[back, assigned[back]]
            better = gain < dist_row[back]
            dist_row[back[better]] = gain[better]
            frontier = back[better]
        end = int(np.where(used < capacity, dist_col, inf).argmin())
        if dist_col[end] >= inf:
            raise ValueError("the columns have no room for every row")
        total += int(dist_col[end])
        used[end] += 1
        col = end
        while True:
            row = int(from_row[col])
            col, assigned[row] = int(assigned[row]), col
            if row == start:
                break
    return total


def reference_round(
    free: Mapping[str, int], pods: Sequence[Routes], rack_of: Mapping[str, object],
    rack_free: Optional[Mapping[object, int]] = None,
) -> Optional[int]:
    """The least sum of route costs with which `pods` (the round's) fit
    `free` (node -> free slots, as the books stood when the round was
    solved); None where they do not all fit, which this reference does
    not price. The round's transportation problem has a column for each
    node some pod of the round has a machine arc to, one for the rest of
    each rack some pod has a rack arc to (or a named node lies in), and
    one for everything else; a pod that reads nothing takes whatever slot
    is left, at 0."""
    if rack_free is None:
        summed: Dict[object, int] = {}
        for node, slots in free.items():
            summed[rack_of[node]] = summed.get(rack_of[node], 0) + slots
        rack_free = summed
    if sum(rack_free.values()) < len(pods):
        return None
    reading = [p for p in pods if p.total]
    if not reading:
        return 0
    named = list(dict.fromkeys(node for p in reading for node in p.machines))
    racks = list(dict.fromkeys(
        [rack for p in reading for rack in p.racks] + [rack_of[node] for node in named]
    ))
    rest = {rack: rack_free.get(rack, 0) for rack in racks}
    for node in named:
        rest[rack_of[node]] -= free.get(node, 0)
    elsewhere = sum(rack_free.values()) - sum(rack_free.get(rack, 0) for rack in racks)
    capacity = np.array(
        [free.get(node, 0) for node in named] + [rest[rack] for rack in racks] + [elsewhere], np.int64
    )
    cost = np.empty((len(reading), len(capacity)), np.int64)
    for i, p in enumerate(reading):
        cost[i, : len(named)] = [p.to_node(node, rack_of[node]) for node in named]
        cost[i, len(named): -1] = [p.racks.get(rack, p.alpha) for rack in racks]
        cost[i, -1] = p.alpha
    return transport(cost, capacity)


def check_data_locality(
    log: Iterable[Tuple[str, str, str, float]], inputs_of: Mapping[str, Sequence[Block]],
    rack_of: Mapping[str, object], node_capacity: int,
    admitted: Sequence[Tuple[float, int]] = (),
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    record in the loop's order, as `capacity` does, on books of its own.
    The entries of one `assign_bindings` call share one stamp and are one
    round. A completed pod gives its slot back to the round AFTER the one
    that follows its completion: the scheduler counts a slot as taken
    until the `deltas` phase of the next round, which comes after that
    round's solve (the reference's own timing). Held, round by round:

    (a) the sum of `route_cost` over the round's Bindings equals
        `reference_round` on the books as they stood at its solve;
    (b) with `admitted` ((when a poll ended, the pods it handed over), in
        order): a round leaves a pod waiting only if it took every free
        slot.

    Returns (the faults, at most one of each kind; facts)."""
    where: Dict[str, str] = {}
    load: Dict[str, int] = {}
    rack_load: Dict[object, int] = {}
    rack_slots: Dict[object, int] = {}
    for node, rack in rack_of.items():
        rack_slots[rack] = rack_slots.get(rack, 0) + node_capacity
    total_slots = node_capacity * len(rack_of)
    routes: Dict[str, Routes] = {}
    reads_nothing = Routes((), rack_of)
    leaving: List[str] = []  # completed since the last round: slots still counted
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_compared": 0, "rounds_short_of_room": 0,
        "pods_bound": 0, "pods_reading": 0, "bound_via": [0, 0, 0],
        "served_cost": 0, "optimum_cost": 0, "bytes_read": 0, "bytes_remote": 0,
        "largest_round": 0, "most_arcs_a_pod": 0, "pods_left_waiting_at_most": 0,
    }
    faults: Dict[str, str] = {}
    polls = list(admitted)
    polled = 0  # polls counted so far
    admitted_so_far = bound_so_far = 0

    def take(node: str, delta: int) -> None:
        load[node] = load.get(node, 0) + delta
        rack = rack_of[node]
        rack_load[rack] = rack_load.get(rack, 0) + delta

    def close_round(binds: List[Tuple[str, str]], t: float) -> None:
        nonlocal polled, admitted_so_far, bound_so_far
        facts["rounds"] += 1
        facts["largest_round"] = max(facts["largest_round"], len(binds))
        pods = []
        for pod, _node in binds:
            blocks = inputs_of.get(pod, ())
            if not blocks:
                pods.append(reads_nothing)
                continue
            if pod not in routes:
                routes[pod] = Routes(blocks, rack_of)
                facts["most_arcs_a_pod"] = max(
                    facts["most_arcs_a_pod"], len(routes[pod].machines) + len(routes[pod].racks)
                )
            pods.append(routes[pod])
        served = 0
        for (pod, node), p in zip(binds, pods):
            if node not in rack_of:
                faults.setdefault("a", f"t={t:.6f}: pod {pod} bound to {node}, no node of the cluster")
                return
            served += p.to_node(node, rack_of[node])
            if p.total:
                facts["pods_reading"] += 1
                facts["bound_via"][p.via(node, rack_of[node])] += 1
                facts["bytes_read"] += p.total
                facts["bytes_remote"] += p.total - p.local.get(node, 0)
        named = {node for p in pods if p.total for node in p.machines}
        free = {node: node_capacity - load.get(node, 0) for node in named}
        rack_free = {rack: slots - rack_load.get(rack, 0) for rack, slots in rack_slots.items()}
        free_before = total_slots - sum(rack_load.values())
        want = reference_round(free, pods, rack_of, rack_free)
        if want is None:
            facts["rounds_short_of_room"] += 1
        else:
            facts["rounds_compared"] += 1
            facts["served_cost"] += served
            facts["optimum_cost"] += want
            if served != want:
                faults.setdefault("a", (
                    f"t={t:.6f}: the round's {len(binds)} Bindings cost {served} by their cheapest "
                    f"routes, the optimum of the round is {want}"
                ))
        # (b) the pods handed over before this round, less those bound by now
        while polled < len(polls) and polls[polled][0] <= t:
            admitted_so_far += polls[polled][1]
            polled += 1
        bound_so_far += len(binds)
        facts["pods_bound"] = bound_so_far
        waiting = admitted_so_far - bound_so_far
        if polls and waiting > 0:
            facts["pods_left_waiting_at_most"] = max(facts["pods_left_waiting_at_most"], waiting)
            if len(binds) < free_before:
                faults.setdefault("b", (
                    f"t={t:.6f}: {waiting} pods waited after a round that bound {len(binds)} "
                    f"with {free_before} slots free"
                ))
        for pod, node in binds:
            old = where.get(pod)
            if old is not None:
                take(old, -1)
            where[pod] = node
            take(node, +1)
        # the round's `deltas` phase let go of what completed before it
        for pod in leaving:
            node = where.pop(pod, None)
            if node is not None:
                take(node, -1)
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
    reading = max(1, facts["pods_reading"])
    facts["bound_on_preferred_share"] = 100.0 * (facts["bound_via"][0] + facts["bound_via"][1]) / reading
    facts["remote_bytes_share"] = 100.0 * facts["bytes_remote"] / max(1, facts["bytes_read"])
    return [faults[k] for k in sorted(faults)], facts
