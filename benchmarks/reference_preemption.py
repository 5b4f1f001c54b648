"""The plain reference of `k8s-5000-preemption`: pod priority and
preemption over slots, as the greedy of the Kubernetes description, and
the replay that holds a served run's log to it.

Independent of the code under test: no graph manager, no cost model
class, no solver; nothing of `ksched_tpu` is imported. Kubernetes ("Pod
Priority and Preemption"): pending pods are served from the highest
priority down; a pod takes a free slot if there is one; else it may evict
ONE pod of strictly lower priority, the lowest there is; a pod never
evicts one of equal or higher priority. A pod's priority is a small whole
tier (0 the lowest). Slots are units, so the numbers bound and evicted
PER TIER are unique, though which node and which victim are not; they are
what `reference_round` returns and what a served round is compared with.

Departures from Kubernetes, each by the slot model (a task is one unit of
flow: Firmament's, SURVEY section 0):

- ONE eviction for each preempting pod, where scheduler_perf's 3,000m
  high-priority pod evicts three 900m pods: requests are not summed.
- An evicted pod stays pending with the scheduler, the same pod, and is
  bound again when a slot frees (ksched's PREEMPT); kube-scheduler
  deletes it and its owner creates another.
- Eviction and the Binding that takes the slot fall in the same round,
  eviction first: no graceful termination, no nominated node.
- Within a tier any victim will do: no PodDisruptionBudget, start time or
  victim count is weighed.

The closed form of a round's objective under the served model's constants
(costmodels/k8s_priority.py states the same ones: e = 2 to place through
the cluster aggregator, u(k) = 5 * 8^k to leave a pod of tier k pending,
evicted or never placed; a pod that keeps running costs 0):
`round_objective`.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

EC_COST = 2  # e: to place a pod through the cluster aggregator
UNSCHEDULED_COST = 5  # u(0): to leave a pod of tier 0 pending
TIER_FACTOR = 8  # u(k) = u(0) * 8^k


def reference_round(
    free_slots: int, running_by_tier: Sequence[int], pending_by_tier: Sequence[int]
) -> Tuple[List[int], List[int]]:
    """(bound_by_tier, evicted_by_tier) of one round. `free_slots`: slots
    no pod holds; `running_by_tier[k]`, `pending_by_tier[k]`: pods of tier
    k that hold a slot, and that wait for one. Tiers from the top; a free
    slot first; else one victim of the lowest tier that has one below the
    pod's own; a pod bound in this round is no victim of a later one
    (later ones are of its tier or below)."""
    tiers = max(len(running_by_tier), len(pending_by_tier))
    running = list(running_by_tier) + [0] * (tiers - len(running_by_tier))
    pending = list(pending_by_tier) + [0] * (tiers - len(pending_by_tier))
    bound = [0] * tiers
    evicted = [0] * tiers
    free = int(free_slots)
    for k in range(tiers - 1, -1, -1):
        for _ in range(pending[k]):
            if free > 0:
                free -= 1
            else:
                victim = next((j for j in range(k) if running[j] > 0), None)
                if victim is None:
                    break  # nor will the next pod of this tier find one
                running[victim] -= 1
                evicted[victim] += 1
            bound[k] += 1
    return bound, evicted


def round_objective(
    bound_by_tier: Sequence[int], evicted_by_tier: Sequence[int], pending_by_tier: Sequence[int]
) -> int:
    """The served round's objective: e for each pod bound, u(k) for each
    pod of tier k left without a slot (`pending_by_tier` before the round,
    less the bound, plus the evicted)."""
    cost = EC_COST * sum(bound_by_tier)
    for k, waiting in enumerate(pending_by_tier):
        left = waiting - bound_by_tier[k] + evicted_by_tier[k]
        cost += left * UNSCHEDULED_COST * TIER_FACTOR ** k
    return cost


def check_priority_preemption(
    log: Iterable[Tuple[str, str, str, float]], tier_of: Dict[str, int], node_capacity: int,
    num_nodes: int = 0,
) -> Tuple[List[str], Dict[str, object]]:
    """Replay the harness's ("bind", pod, node, t) / ("done", pod, "", t)
    / ("evict", pod, node, t) log, with the tier of every pod that was
    submitted (`tier_of`), the slots of a node and, where the caller knows
    it, the number of nodes (without it a round's free slots are read off
    the round itself: its Bindings less its evictions). The entries of one call
    share one stamp; an `evict` group and the `bind` group that follows it
    are one round, a `bind` group alone is a round that evicted nothing.
    Held, round by round, on the replay's own books:

    (a) every evicted pod left a node onto which the same round bound a
        pod of strictly higher tier (node by node, pod for pod);
    (b) every node that lost a pod to eviction is full after the round:
        no eviction without need;
    (c) bound-by-tier and evicted-by-tier equal `reference_round` on the
        books before the round, the pending being the pods the round
        bound and those evicted earlier and not bound since (a pod that
        never got a Binding is not in the log: (d) has it);
    and at the end
    (d) no pod of a higher tier is pending while one of a lower tier runs.

    Returns (the faults, at most one of each kind; facts)."""
    where: Dict[str, str] = {}
    load: Dict[str, int] = {}
    done: set = set()
    evicted_pending: set = set()
    tiers = max(tier_of.values(), default=0) + 1
    running = [0] * tiers  # pods that hold a slot, by tier
    waiting = [0] * tiers  # pods evicted and not bound since, by tier
    facts: Dict[str, object] = {
        "replayed": 0, "rounds": 0, "rounds_evicting": 0, "tiers": tiers,
        "bound_by_tier": [0] * tiers, "evicted_by_tier": [0] * tiers,
        "evicted_then_bound_again": 0, "most_evictions_a_round": 0,
    }
    faults: Dict[str, str] = {}
    capacity_total = num_nodes * node_capacity if num_nodes else None

    def by_tier(pods: Iterable[str]) -> List[int]:
        counts = [0] * tiers
        for p in pods:
            counts[tier_of[p]] += 1
        return counts

    def leave(pod: str) -> None:
        node = where.pop(pod, None)
        if node is not None:
            load[node] -= 1
            running[tier_of[pod]] -= 1

    def close_round(evicts: List[Tuple[str, str]], binds: List[Tuple[str, str]], t: float) -> None:
        # the books before the round: the pending are the pods it bound
        # and those evicted earlier and not bound since
        running_before = list(running)
        pending_before = list(waiting)
        for pod, _node in binds:
            pending_before[tier_of[pod]] += pod not in evicted_pending
        busy_before = len(where)
        for pod, node in evicts:
            if where.get(pod) == node:  # else: `capacity` has the fault
                leave(pod)
            if pod not in evicted_pending:
                evicted_pending.add(pod)
                waiting[tier_of[pod]] += 1
        for pod, node in binds:
            if pod in evicted_pending:
                evicted_pending.discard(pod)
                waiting[tier_of[pod]] -= 1
                facts["evicted_then_bound_again"] += 1
            leave(pod)
            where[pod] = node
            load[node] = load.get(node, 0) + 1
            running[tier_of[pod]] += 1
        facts["rounds"] += 1
        bound, evicted = by_tier(p for p, _n in binds), by_tier(p for p, _n in evicts)
        for k in range(tiers):
            facts["bound_by_tier"][k] += bound[k]
            facts["evicted_by_tier"][k] += evicted[k]
        if evicts:
            facts["rounds_evicting"] += 1
            facts["most_evictions_a_round"] = max(facts["most_evictions_a_round"], len(evicts))
        # (a), (b): node by node, the evicted against the bound, highest first
        lost: Dict[str, List[int]] = {}
        won: Dict[str, List[int]] = {}
        for pod, node in evicts:
            lost.setdefault(node, []).append(tier_of[pod])
        for pod, node in binds:
            won.setdefault(node, []).append(tier_of[pod])
        for node, out in lost.items():
            into = sorted(won.get(node, []), reverse=True)
            out.sort(reverse=True)
            if len(into) < len(out) or any(i <= o for i, o in zip(into, out)):
                faults.setdefault("a", (
                    f"t={t:.6f}: node {node} lost pods of tiers {out} to eviction and the "
                    f"round bound pods of tiers {into} onto it: not a strictly higher one for each"
                ))
            if load.get(node, 0) < node_capacity:
                faults.setdefault("b", (
                    f"t={t:.6f}: node {node} lost {len(out)} pods to eviction and holds "
                    f"{load.get(node, 0)} of {node_capacity} after the round: an eviction without need"
                ))
        # (c) the greedy on the books before the round
        free_before = len(binds) - len(evicts) if capacity_total is None else capacity_total - busy_before
        want = reference_round(max(0, free_before), running_before, pending_before)
        if (bound, evicted) != want:
            faults.setdefault("c", (
                f"t={t:.6f}: the round bound {bound} and evicted {evicted} by tier, the greedy "
                f"binds {want[0]} and evicts {want[1]} (running {running_before}, pending "
                f"{pending_before}, free {max(0, free_before)})"
            ))

    # the entries of one call: consecutive, one kind, one stamp
    groups: List[Tuple[str, float, List[Tuple[str, str]]]] = []
    for kind, pod, node, t in log:
        facts["replayed"] += 1
        if not groups or groups[-1][0] != kind or groups[-1][1] != t:
            groups.append((kind, t, []))
        groups[-1][2].append((pod, node))
    i = 0
    while i < len(groups):
        kind, t, entries = groups[i]
        i += 1
        if kind == "done":
            for pod, _node in entries:
                leave(pod)
                done.add(pod)
                if pod in evicted_pending:
                    evicted_pending.discard(pod)
                    waiting[tier_of[pod]] -= 1
        elif kind == "bind":
            close_round([], entries, t)
        elif i < len(groups) and groups[i][0] == "bind":
            close_round(entries, groups[i][2], groups[i][1])
            i += 1
        else:
            close_round(entries, [], t)  # evictions and no Binding: (a) has it
    # (d) at the end
    pending = [p for p in tier_of if p not in where and p not in done]
    if pending and where:
        highest_waiting = max(tier_of[p] for p in pending)
        lowest_running = min(k for k in range(tiers) if running[k])
        if highest_waiting > lowest_running:
            faults.setdefault("d", (
                f"at the end a pod of tier {highest_waiting} is pending while one of tier "
                f"{lowest_running} runs"
            ))
    facts["running_at_end_by_tier"] = list(running)
    facts["pending_at_end_by_tier"] = by_tier(pending)
    return [faults[k] for k in sorted(faults)], facts
