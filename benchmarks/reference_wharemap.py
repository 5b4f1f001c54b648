"""The plain reference of `gtrace-12500-wharemap`: Whare-Map's class x
platform x co-runner cost as one equation over a machine's census, the
optimum of one round by a textbook successive shortest path, and the
replay that holds a served run's record to both.

Independent of the code under test: no graph manager, no cost model
class, no solver; nothing of `ksched_tpu` is imported. Whare-MCs (Mars and
Tang, ISCA'13) over the four census classes of whare_map_stats.proto
(sheep, rabbit, devil, turtle) and three platforms A, B, C. The map, x100
(100 = no slowdown), before any runtime was recorded (the served path
reports none, so it is the map of a whole run):

    psi[c, p, k] = PSI_PRIOR[c][k] * PLATFORM_PRIOR[c][p] // 100

for a pod of class c on a machine of platform p beside a co-runner k: one
of the four classes, or ALONE, a pod that has the machine to itself (no
slowdown but its platform's). A machine m of platform p(m) runs n_k(m) pods
of class k and has idle(m) of its slots(m) free; an EMPTY machine counts
one co-runner, ALONE, and any other machine none of those. Placing a pod of
class c there costs, in integers,

    cost(c, m) = clip( sum_k n_k(m) * psi[c, p(m), k] // sum_k n_k(m)
                       - IDLE_BONUS * idle(m) // slots(m), 0, MAX_COST )

and it may take idle(m) pods. Leaving a pod unscheduled costs
UNSCHEDULED_COST, more than any machine. An empty machine costs
PLATFORM_PRIOR[c][p(m)] - IDLE_BONUS: what its platform does to a lone pod
of the class, so no two platforms cost a class alike, empty or not.

A cluster of fake machines is dealt its types by the machine's index:
r = (619 * i) mod 1000 falls into the share (per mille) of one type of the
table, taken in the table's order; the type gives the node its platform
(its name; a name that is no platform is B) and its cores, and slots(m) =
cores x PUs a core x pods a PU.

`cost_matrix` is the equation over every machine; `reference_round` the
least total cost with which a round's pods, by class, fit the idle slots
(or stay unscheduled); `check_interference_map` replays a record round by
round on books of its own and compares.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

import numpy as np

CLASSES = ("sheep", "rabbit", "devil", "turtle")
PLATFORMS = ("A", "B", "C")
NEUTRAL = PLATFORMS.index("B")
#: the co-runner of a pod that has its machine to itself
ALONE = len(CLASSES)
#: PSI_PRIOR[c][k]: class c beside a co-runner of class k, or (last) ALONE
PSI_PRIOR = (
    (105, 103, 140, 100, 100),
    (115, 110, 200, 101, 100),
    (120, 130, 150, 105, 100),
    (100, 100, 102, 100, 100),
)
#: PLATFORM_PRIOR[c][p]: class c on platform p
PLATFORM_PRIOR = (
    (110, 100, 95),
    (130, 100, 85),
    (115, 100, 90),
    (102, 100, 99),
)
IDLE_BONUS = 20
MAX_COST = 2000
UNSCHEDULED_COST = 2500
DEAL_STRIDE = 619

#: one type of machine: (name, cores, share of the machines per mille)
MachineType = Tuple[str, int, int]


def psi() -> np.ndarray:
    """The map: int64 [class, platform, co-runner (a class, or ALONE)]."""
    out = np.zeros((len(CLASSES), len(PLATFORMS), len(CLASSES) + 1), np.int64)
    for c in range(len(CLASSES)):
        for p in range(len(PLATFORMS)):
            for k in range(len(CLASSES) + 1):
                out[c, p, k] = PSI_PRIOR[c][k] * PLATFORM_PRIOR[c][p] // 100
    return out


def machine_type(index: int, types: Sequence[MachineType]) -> MachineType:
    """The type the table deals machine `index`."""
    r = (DEAL_STRIDE * index) % 1000
    for mtype in types:
        if r < mtype[2]:
            return mtype
        r -= mtype[2]
    raise ValueError(f"the shares of {list(types)} do not sum to 1000")


def node_index(node: str) -> int:
    """`fake_node_<i>` -> i."""
    return int(node.rsplit("_", 1)[1])


def node_shape(node: str, types: Sequence[MachineType], pus_per_core: int,
               max_tasks_per_pu: int) -> Tuple[int, int]:
    """(platform as an index into PLATFORMS, slots) of a node, from its
    name and the type table."""
    name, cores, _share = machine_type(node_index(node), types)
    platform = PLATFORMS.index(name) if name in PLATFORMS else NEUTRAL
    return platform, cores * pus_per_core * max_tasks_per_pu


def cost_matrix(census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
                platform: np.ndarray) -> np.ndarray:
    """cost(c, m) for every class and machine: int64 [4, M] from the
    census [M, 4], the idle slots [M], the slots [M] and the platforms
    [M] (indices into PLATFORMS)."""
    the_map = psi()
    running = census.sum(axis=1)
    empty = running == 0
    cost = np.empty((len(CLASSES), len(census)), np.int64)
    for c in range(len(CLASSES)):
        on_its_platform = the_map[c][platform]  # [M, 5]
        # sum_k n_k psi[c, p(m), k] over the classes that run there ...
        weighted = (on_its_platform[:, :ALONE] * census).sum(axis=1) // np.maximum(1, running)
        # ... or, where none does, the one co-runner ALONE
        expected = np.where(empty, on_its_platform[:, ALONE], weighted)
        cost[c] = expected - IDLE_BONUS * idle // np.maximum(1, slots)
    return np.clip(cost, 0, MAX_COST)


def transport(cost: np.ndarray, supply: np.ndarray, capacity: np.ndarray, escape: int) -> int:
    """The least total cost of sending supply[r] units from every row r to
    the columns, column k taking at most capacity[k], a unit that is not
    sent costing `escape`: successive shortest paths. Each pass is one
    Bellman-Ford over the residual network from the rows that still hold
    units (no residual cycle is negative while the flow so far is optimal
    for what it carries), and pushes along the path it finds as many units
    as the path takes."""
    rows = len(supply)
    # the escape is one more column, with room for everything
    cost = np.concatenate([cost.astype(np.int64), np.full((rows, 1), escape, np.int64)], axis=1)
    capacity = np.concatenate([capacity.astype(np.int64), [int(supply.sum())]])
    cols = len(capacity)
    inf = np.iinfo(np.int64).max // 4
    flow = np.zeros((rows, cols), np.int64)
    used = np.zeros(cols, np.int64)
    left = supply.astype(np.int64).copy()
    every_row, every_col = np.arange(rows), np.arange(cols)
    total = 0
    while left.any():
        dist_row = np.where(left > 0, 0, inf)
        dist_col = np.full(cols, inf, np.int64)
        row_of_col = np.full(cols, -1, np.int64)  # the row a column is reached from
        col_of_row = np.full(rows, -1, np.int64)  # the column a row is reached back from
        for _ in range(rows + 1):
            # a row reaches every column at its cost
            reach = np.where(dist_row[:, None] < inf, dist_row[:, None] + cost, inf)
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


def reference_round(census: np.ndarray, idle: np.ndarray, slots: np.ndarray,
                    platform: np.ndarray, pods_by_class: Sequence[int]) -> int:
    """The optimum of one round's transportation problem: `pods_by_class`
    pods of each class onto the machines' idle slots at `cost_matrix`, or
    unscheduled. Machines without an idle slot take nothing, and machines
    that cost every class alike are one column with their slots summed."""
    supply = np.asarray(pods_by_class, np.int64)
    has_room = idle > 0
    cost = cost_matrix(census[has_room], idle[has_room], slots[has_room], platform[has_room])
    alike, which = np.unique(cost.T, axis=0, return_inverse=True)
    room = np.bincount(which.reshape(-1), weights=idle[has_room], minlength=len(alike))
    return transport(alike.T, supply, room.astype(np.int64), UNSCHEDULED_COST)


def check_interference_map(
    log: Iterable[Tuple[str, str, str, float]], class_of: Mapping[str, int],
    nodes: Sequence[str], types: Sequence[MachineType], pus_per_core: int,
    max_tasks_per_pu: int, admitted: Sequence[Tuple[float, int]] = (),
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    record in the loop's order, as `capacity_by_type` does, on books of its
    own: each node's census by class, from the Bindings and completions
    alone. The entries of one `assign_bindings` call share one stamp and
    are one round. A round is priced on the census as it stood when the
    round's batch was taken. A pod whose completion the service took since
    the round before (between two polls, or in the poll that took the
    batch, before or after the pods were taken) still counts in this
    round's census and still holds its slot in this round's solve: the
    scheduler lets go of it in the `deltas` phase of this round, after the
    solve, and the model's census is gathered from the same lists (what the
    model is told, not what the cluster knows). It leaves the books after
    the round.

    A round without Bindings is not in the record, and the rule for it is
    this. A poll that hands over no pod while no pod waits starts no round
    at all, however many completions it took: the scheduler finds no
    runnable task and returns before its `deltas` phase, so those pods are
    still on the model's books when the next batch is priced, and they are
    still on these (they leave with the next round that posts a Binding).
    Only a round that solved for pods and bound none of them lets completed
    pods go unseen by this replay: it happens only with every slot taken
    (the two sides then differ in nothing a pod can be bound to until the
    next round with a Binding, which this replay would price on books that
    still count them: a fault there is this record's limit, not the
    service's). `rounds_short_of_room` says whether a run came near it; a
    cell that keeps a tenth of its slots idle does not.

    Held, round by round:

    (a) no pod of a class the plan does not know, no node outside the
        cluster, and the sum of cost(c, m) over the round's Bindings equals
        `reference_round` on those books, exactly (a round that left pods
        waiting is not priced: the record does not say which);
    (b) with `admitted` ((when a poll ended, the pods it handed over), in
        order): a round leaves a pod waiting only if it took every idle
        slot.

    Returns (the faults, at most one of each kind; facts)."""
    index = {node: i for i, node in enumerate(nodes)}
    shapes = [node_shape(node, types, pus_per_core, max_tasks_per_pu) for node in nodes]
    platform = np.array([p for p, _s in shapes], np.int64)
    slots = np.array([s for _p, s in shapes], np.int64)
    census = np.zeros((len(nodes), len(CLASSES)), np.int64)
    where: Dict[str, int] = {}
    leaving: List[str] = []  # completed since the last round: still counted
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_compared": 0, "rounds_short_of_room": 0,
        "pods_bound": 0, "served_cost": 0, "optimum_cost": 0, "largest_round": 0,
        # the rounds after the fill, where a cell's window lies: how many of those compared
        # cost nothing at their optimum (a comparison of 0 with 0 proves nothing of the map)
        "rounds_costing_zero": 0, "served_cost_but_largest_round": 0,
        "optimum_cost_but_largest_round": 0,
        "pods_left_waiting_at_most": 0, "nodes": len(nodes), "slots": int(slots.sum()),
        "nodes_by_platform": np.bincount(platform, minlength=len(PLATFORMS)).tolist(),
        "bound_by_class_and_platform": np.zeros((len(CLASSES), len(PLATFORMS)), np.int64),
    }
    faults: Dict[str, str] = {}
    polls = list(admitted)
    polled = admitted_so_far = bound_so_far = 0

    largest_cost = [0, 0]  # (served, optimum) of the largest round so far, if it was compared

    def close_round(binds: List[Tuple[str, str]], t: float) -> None:
        nonlocal polled, admitted_so_far, bound_so_far
        facts["rounds"] += 1
        largest = len(binds) > facts["largest_round"]
        if largest:
            facts["largest_round"] = len(binds)
            largest_cost[:] = 0, 0
        for pod, node in binds:
            if node not in index or class_of.get(pod) not in range(len(CLASSES)):
                faults.setdefault("a", (
                    f"t={t:.6f}: pod {pod} of class {class_of.get(pod)} bound to {node}: "
                    "no class of the plan, or no node of the cluster"
                ))
                return
        idle = slots - census.sum(axis=1)
        idle_before = int(idle.sum())
        # (b) the pods handed over before this round, less those bound by now
        while polled < len(polls) and polls[polled][0] <= t:
            admitted_so_far += polls[polled][1]
            polled += 1
        bound_so_far += len(binds)
        facts["pods_bound"] = bound_so_far
        waiting = admitted_so_far - bound_so_far if polls else 0
        if waiting > 0:
            facts["pods_left_waiting_at_most"] = max(facts["pods_left_waiting_at_most"], waiting)
            facts["rounds_short_of_room"] += 1
            if len(binds) < idle_before:
                faults.setdefault("b", (
                    f"t={t:.6f}: {waiting} pods waited after a round that bound {len(binds)} "
                    f"with {idle_before} slots idle"
                ))
        else:
            cost = cost_matrix(census, idle, slots, platform)
            served = sum(int(cost[class_of[pod], index[node]]) for pod, node in binds)
            by_class = np.bincount([class_of[pod] for pod, _n in binds], minlength=len(CLASSES))
            want = reference_round(census, idle, slots, platform, by_class)
            facts["rounds_compared"] += 1
            facts["served_cost"] += served
            facts["optimum_cost"] += want
            facts["rounds_costing_zero"] += want == 0
            if largest:
                largest_cost[:] = served, want
            if served != want:
                faults.setdefault("a", (
                    f"t={t:.6f}: the round's {len(binds)} Bindings cost {served} by the "
                    f"interference map, the optimum of the round is {want}"
                ))
        for pod, node in binds:
            old = where.get(pod)
            if old is not None:
                census[old, class_of[pod]] -= 1
            where[pod] = index[node]
            census[index[node], class_of[pod]] += 1
            facts["bound_by_class_and_platform"][class_of[pod], platform[index[node]]] += 1
        # the round's `deltas` phase let go of what completed before it
        for pod in leaving:
            at = where.pop(pod, None)
            if at is not None:
                census[at, class_of[pod]] -= 1
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
    facts["bound_by_class_and_platform"] = facts["bound_by_class_and_platform"].tolist()
    facts["served_cost_but_largest_round"] = facts["served_cost"] - largest_cost[0]
    facts["optimum_cost_but_largest_round"] = facts["optimum_cost"] - largest_cost[1]
    return [faults[k] for k in sorted(faults)], facts
