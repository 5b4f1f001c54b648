"""The plain reference of `gtrace-12500-wharemap-array`: the replay that
holds a record of the ARRAY round under Whare-Map to the equation and the
optimum of `reference_wharemap.py`, by the array round's own rules.

Independent of the code under test: plain numpy and Python, nothing of
`ksched_tpu` is imported. The equation (`cost_matrix`), the optimum of one
round (`reference_round`, `transport`) and a node's platform and slots from
the type table (`node_shape`) are `reference_wharemap.py`'s, unchanged. What
differs from that file's replay is when a completed pod leaves the books and
what a round is held to:

- a completion the service took before a round leaves the census BEFORE that
  round is priced, and its slot is idle in it: the array round retires
  completed rows before it admits and solves (the graph path lets go of them
  after the solve, which is `check_interference_map`'s rule);
- EVERY round is compared, also one that left pods waiting: what a round had
  to place is what the polls handed over and no round has bound yet
  (`batches`), and a pod it left waiting costs UNSCHEDULED_COST.

At 12,500 machines `cost_matrix` over every machine and `reference_round`
(which prices them again and merges like columns by rows) take ~25 ms a
round, and the array round makes ~3,000 rounds a run. So the replay keeps
the priced matrix and prices a machine again only when its census changed,
with the same `cost_matrix`, and merges like columns by one packed key a
column before the same `transport`; `plain=True` is the replay without
either (every machine priced every round, `reference_round` as it stands),
and the tests hold the two to each other round by round.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

import numpy as np

from benchmarks.reference_wharemap import (
    CLASSES, MAX_COST, PLATFORMS, UNSCHEDULED_COST, MachineType, cost_matrix, node_shape,
    reference_round, transport,
)

#: a column's four costs packed into one integer, each below this
_PACK = MAX_COST + 1


def round_optimum(cost: np.ndarray, idle: np.ndarray, pods_by_class: Sequence[int]) -> int:
    """`reference_round` over a matrix that is priced already: machines
    without an idle slot take nothing, machines that cost every class alike
    are one column with their idle slots summed, and `transport` finds the
    least total cost of the round's pods, by class, on those columns or
    unscheduled."""
    has_room = idle > 0
    columns = cost[:, has_room]
    key = np.zeros(columns.shape[1], np.int64)
    for row in columns:
        key = key * _PACK + row
    alike, which = np.unique(key, return_inverse=True)
    room = np.bincount(which, weights=idle[has_room], minlength=len(alike)).astype(np.int64)
    unpacked = np.empty((len(CLASSES), len(alike)), np.int64)
    for c in reversed(range(len(CLASSES))):
        alike, unpacked[c] = np.divmod(alike, _PACK)
    return transport(unpacked, np.asarray(pods_by_class, np.int64), room, UNSCHEDULED_COST)


def check_interference_map_array(
    log: Iterable[Tuple[str, str, str, float]], class_of: Mapping[str, int],
    nodes: Sequence[str], types: Sequence[MachineType], pus_per_core: int,
    max_tasks_per_pu: int, batches: Sequence[Tuple[float, Sequence[str]]] = (),
    plain: bool = False, completions_leave_after_the_round: bool = False,
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    record in the loop's order on a census of its own, kept from the
    Bindings and completions alone. The entries of one `assign_bindings`
    call share one stamp and are one round. `batches` ((when a poll ended,
    the pods it handed over), in order) says what each round had to place:
    the pods handed over since the round before and those the rounds before
    left waiting; without it a round's batch is what it bound.
    (`completions_leave_after_the_round` is for the control: the graph
    path's rule, under which this record must come out at fault.)

    Held, round by round:

    (a) no pod of a class the plan does not know, no node outside the
        cluster, none bound that no poll had handed over, and the sum of
        cost(c, m) over the round's Bindings on the census as it stood when
        the round began, plus UNSCHEDULED_COST for each pod it left waiting,
        equals the optimum of the round's transportation problem, exactly;
    (b) a round leaves a pod waiting only if it took every idle slot;
    (c) no pod completes without a Binding on record.

    Returns (the faults, at most one of each kind; facts)."""
    index = {node: i for i, node in enumerate(nodes)}
    shapes = [node_shape(node, types, pus_per_core, max_tasks_per_pu) for node in nodes]
    platform = np.array([p for p, _s in shapes], np.int64)
    slots = np.array([s for _p, s in shapes], np.int64)
    census = np.zeros((len(nodes), len(CLASSES)), np.int64)
    cost = np.zeros((len(CLASSES), len(nodes)), np.int64)
    stale = set(range(len(nodes)))  # machines whose census changed since they were priced
    where: Dict[str, int] = {}
    leaving: List[str] = []  # the control's: completed, still counted until a round ends
    waiting: Dict[str, None] = {}  # handed over and not bound yet, in order
    polls = list(batches)
    polled = 0
    faults: Dict[str, str] = {}
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_that_left_pods_waiting": 0, "pods_bound": 0,
        "completions": 0, "served_cost": 0, "optimum_cost": 0, "largest_round": 0,
        # a comparison of 0 with 0 proves nothing of the map: no machine of this map costs 0,
        # so no round that had a pod to place may cost 0 at its optimum
        "rounds_costing_zero": 0, "served_cost_but_largest_round": 0,
        "pods_left_waiting_at_most": 0, "machines_priced": 0, "nodes": len(nodes),
        "slots": int(slots.sum()),
        "nodes_by_platform": np.bincount(platform, minlength=len(PLATFORMS)).tolist(),
        "bound_by_class_and_platform": np.zeros((len(CLASSES), len(PLATFORMS)), np.int64),
    }
    largest_cost = 0

    def leave(pod: str) -> None:
        at = where.pop(pod, None)
        if at is not None:
            census[at, class_of[pod]] -= 1
            stale.add(at)

    def close_round(binds: List[Tuple[str, str]], t: float) -> None:
        nonlocal polled, largest_cost, cost
        facts["rounds"] += 1
        while polled < len(polls) and polls[polled][0] <= t:
            waiting.update(dict.fromkeys(polls[polled][1]))
            polled += 1
        for pod, node in binds:
            if node not in index or class_of.get(pod) not in range(len(CLASSES)) or (
                polls and pod not in waiting
            ):
                faults.setdefault("a", (
                    f"t={t:.6f}: pod {pod} of class {class_of.get(pod)} bound to {node}: no "
                    "class of the plan, no node of the cluster, or a pod no poll handed over"
                ))
                return
        batch = list(waiting) if polls else [pod for pod, _n in binds]
        left = len(batch) - len(binds)
        idle = slots - census.sum(axis=1)
        idle_before = int(idle.sum())
        by_class = np.bincount([class_of[pod] for pod in batch], minlength=len(CLASSES))
        if plain:
            cost = cost_matrix(census, idle, slots, platform)
            want = reference_round(census, idle, slots, platform, by_class)
            facts["machines_priced"] += len(nodes)
        else:
            again = np.fromiter(stale, np.int64, len(stale))
            cost[:, again] = cost_matrix(census[again], idle[again], slots[again], platform[again])
            facts["machines_priced"] += len(again)
            stale.clear()
            want = round_optimum(cost, idle, by_class)
        served = sum(int(cost[class_of[pod], index[node]]) for pod, node in binds)
        served += UNSCHEDULED_COST * left
        facts["served_cost"] += served
        facts["optimum_cost"] += want
        facts["rounds_costing_zero"] += want == 0
        facts["pods_bound"] += len(binds)
        if len(binds) > facts["largest_round"]:
            facts["largest_round"], largest_cost = len(binds), served
        if served != want:
            faults.setdefault("a", (
                f"t={t:.6f}: the round's {len(binds)} Bindings and {left} pods left waiting "
                f"cost {served} by the interference map, the optimum of the round is {want}"
            ))
        if left:
            facts["rounds_that_left_pods_waiting"] += 1
            facts["pods_left_waiting_at_most"] = max(facts["pods_left_waiting_at_most"], left)
            if len(binds) < idle_before:
                faults.setdefault("b", (
                    f"t={t:.6f}: {left} pods waited after a round that bound {len(binds)} "
                    f"with {idle_before} slots idle"
                ))
        for pod, node in binds:
            waiting.pop(pod, None)
            at = where[pod] = index[node]
            census[at, class_of[pod]] += 1
            stale.add(at)
            facts["bound_by_class_and_platform"][class_of[pod], platform[at]] += 1
        for pod in leaving:
            leave(pod)
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
            facts["completions"] += 1
            if pod not in where:
                faults.setdefault("c", f"t={t:.6f}: pod {pod} completed with no Binding on record")
            elif completions_leave_after_the_round:
                leaving.append(pod)
            else:
                leave(pod)
        else:
            faults.setdefault("e", f"t={t:.6f}: a {kind!r} entry: this policy is served without preemption")
    if group:
        close_round(group, group_t)
    facts["bound_by_class_and_platform"] = facts["bound_by_class_and_platform"].tolist()
    facts["served_cost_but_largest_round"] = facts["served_cost"] - largest_cost
    return [faults[k] for k in sorted(faults)], facts
