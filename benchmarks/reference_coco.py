"""The plain reference of `coco-50kx1k-array`: CoCo's interference cost as
one equation over a machine's census, the optimum of one round's
transportation problem by a textbook successive shortest path, and the
replay that holds a served run's record to both.

Independent of the code under test: plain numpy and Python, nothing of
`ksched_tpu` is imported. The four census classes of
coco_interference_scores.proto (sheep, rabbit, devil, turtle); W[c][k] is
what one running pod of class k costs an incoming pod of class c. A
machine m runs n_k(m) pods of class k; every machine has the same slots.
Placing a pod of class c there costs, in integers,

    cost(c, m) = min( sum_k W[c][k] * n_k(m), MAX_COST )

(the fake machines carry no penalty of their own) and the machine may take
free(m) = slots - sum_k n_k(m) pods. Leaving a pod unscheduled costs
UNSCHEDULED_COST, more than any machine.

`cost_matrix` is the equation over every machine; `reference_round` the
least total cost with which a round's pods, by class, fit the free slots or
stay unscheduled (the 4-row transportation problem, by
`reference_wharemap.transport`: successive shortest paths, one Bellman-Ford
a pass); `check_interference_coco` replays a record round by round on a
census of its own and compares.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Sequence, Tuple

import numpy as np

from benchmarks.reference_wharemap import transport

CLASSES = ("sheep", "rabbit", "devil", "turtle")
#: W[c][k]: an incoming pod of class c beside one running pod of class k
W = (
    (2, 1, 8, 0),
    (4, 3, 16, 0),
    (8, 12, 10, 1),
    (0, 0, 1, 0),
)
MAX_COST = 2000
UNSCHEDULED_COST = 2500


def cost_matrix(census: np.ndarray, weights: Sequence[Sequence[int]] = W) -> np.ndarray:
    """cost(c, m) for every class and machine: int64 [4, M] from the
    census [M, 4]."""
    return np.minimum(np.asarray(weights, np.int64) @ census.T.astype(np.int64), MAX_COST)


def reference_round(batch_by_class: Sequence[int], census: np.ndarray, slots: int,
                    weights: Sequence[Sequence[int]] = W) -> int:
    """The optimum of one round's transportation problem: `batch_by_class`
    pods of each class onto the machines' free slots at `cost_matrix`, or
    unscheduled. Machines without a free slot take nothing, and machines
    that cost every class alike are one column with their free slots
    summed."""
    free = slots - census.sum(axis=1)
    has_room = free > 0
    cost = cost_matrix(census[has_room], weights)
    alike, which = np.unique(cost.T, axis=0, return_inverse=True)
    room = np.bincount(which.reshape(-1), weights=free[has_room], minlength=len(alike))
    return transport(
        alike.T, np.asarray(batch_by_class, np.int64), room.astype(np.int64), UNSCHEDULED_COST
    )


def check_interference_coco(
    log: Iterable[Tuple[str, str, str, float]], class_of: Mapping[str, int],
    nodes: Sequence[str], slots: int,
    batches: Sequence[Tuple[float, Sequence[str]]] = (),
    weights: Sequence[Sequence[int]] = W, forget_completions: bool = False,
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    record in the loop's order on a census of its own, kept from the
    Bindings and completions alone. The entries of one `assign_bindings`
    call share one stamp and are one round. A completion the service took
    before a round leaves the census before that round is priced and its
    slot is free in it: the array round retires completed rows before it
    admits and solves. `batches` ((when a poll ended, the pods it handed
    over), in order) says what each round had to place: the pods handed
    over since the round before and those the rounds before left waiting;
    without it a round's batch is what it bound. (`weights` and
    `forget_completions` are for the controls: another matrix, and a
    replay that never lets a completed pod go, must both come out at
    fault.)

    Held, round by round:

    (a) no pod of a class the plan does not know, no node outside the
        cluster, none bound that was not handed over, and the sum of
        cost(c, m) over the round's Bindings on the census as it stood when
        the round began, plus UNSCHEDULED_COST for each pod it left
        waiting, equals `reference_round` on that census, exactly;
    (b) a round leaves a pod waiting only if it took every free slot.

    Returns (the faults, at most one of each kind; facts)."""
    index = {node: i for i, node in enumerate(nodes)}
    census = np.zeros((len(nodes), len(CLASSES)), np.int64)
    where: Dict[str, int] = {}
    waiting: Dict[str, None] = {}  # handed over and not bound yet, in order
    polls = list(batches)
    polled = 0
    faults: Dict[str, str] = {}
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_that_left_pods_waiting": 0, "pods_bound": 0,
        "completions": 0, "served_cost": 0, "optimum_cost": 0, "largest_round": 0,
        # a comparison of 0 with 0 proves nothing of W: how many rounds after the largest
        # (the fill, on an empty cluster) cost nothing at their optimum
        "rounds_costing_zero": 0, "served_cost_but_largest_round": 0,
        "pods_left_waiting_at_most": 0, "nodes": len(nodes), "slots": slots * len(nodes),
    }
    largest_cost = 0

    def close_round(binds: List[Tuple[str, str]], t: float) -> None:
        nonlocal polled, largest_cost
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
        free_before = int(slots * len(nodes) - census.sum())
        cost = cost_matrix(census, weights)
        served = sum(int(cost[class_of[pod], index[node]]) for pod, node in binds)
        served += UNSCHEDULED_COST * left
        by_class = np.bincount([class_of[pod] for pod in batch], minlength=len(CLASSES))
        want = reference_round(by_class, census, slots, weights)
        facts["served_cost"] += served
        facts["optimum_cost"] += want
        facts["rounds_costing_zero"] += want == 0
        facts["pods_bound"] += len(binds)
        if len(binds) > facts["largest_round"]:
            facts["largest_round"], largest_cost = len(binds), served
        if served != want:
            faults.setdefault("a", (
                f"t={t:.6f}: the round's {len(binds)} Bindings and {left} pods left waiting "
                f"cost {served} by CoCo's equation, the optimum of the round is {want}"
            ))
        if left:
            facts["rounds_that_left_pods_waiting"] += 1
            facts["pods_left_waiting_at_most"] = max(facts["pods_left_waiting_at_most"], left)
            if len(binds) < free_before:
                faults.setdefault("b", (
                    f"t={t:.6f}: {left} pods waited after a round that bound {len(binds)} "
                    f"with {free_before} slots free"
                ))
        for pod, node in binds:
            waiting.pop(pod, None)
            where[pod] = index[node]
            census[index[node], class_of[pod]] += 1

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
            at = where.pop(pod, None)
            if at is None:
                faults.setdefault("c", f"t={t:.6f}: pod {pod} completed with no Binding on record")
            elif not forget_completions:
                census[at, class_of[pod]] -= 1
        else:
            faults.setdefault("e", f"t={t:.6f}: a {kind!r} entry: this policy is served without preemption")
    if group:
        close_round(group, group_t)
    facts["served_cost_but_largest_round"] = facts["served_cost"] - largest_cost
    return [faults[k] for k in sorted(faults)], facts
