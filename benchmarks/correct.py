"""The comparison that decides `correct`, made outside the window.

The configuration's guarantees, as far as a run can show them:

  binding   every pod due in the window got exactly one Binding, and no
            pod of the run got a second;
  capacity  replaying the Bindings and completions in the order the loop
            thread made them never puts a node over
            cores x pus_per_core x max_tasks_per_pu pods;
  answer    no NOOP round, no step down the ladder, no program compiled
            inside the window, and the closing round's problem, solved
            again by the independent C++ solver, has the same objective.

Each check returns the faults it found; `correct` is "no fault".
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def check_bindings(due: Iterable[str], bind_stamps: Dict[str, List[float]]) -> List[str]:
    faults = []
    missing = [p for p in due if p not in bind_stamps]
    if missing:
        faults.append(f"{len(missing)} pods due in the window got no Binding (first: {missing[0]})")
    twice = [p for p, s in bind_stamps.items() if len(s) > 1]
    if twice:
        faults.append(f"{len(twice)} pods got more than one Binding (first: {twice[0]})")
    return faults


def check_capacity(log: Sequence[Tuple[str, str, str, float]], node_capacity: int) -> List[str]:
    """Replay ("bind", pod, node, t) / ("done", pod, "", t) in order."""
    where: Dict[str, str] = {}
    load: Dict[str, int] = {}
    for kind, pod, node, _t in log:
        if kind == "bind":
            old = where.get(pod)
            if old is not None:
                load[old] -= 1
            where[pod] = node
            load[node] = load.get(node, 0) + 1
            if load[node] > node_capacity:
                return [f"node {node} held {load[node]} pods, capacity {node_capacity} (pod {pod})"]
        elif kind == "done":
            node = where.pop(pod, None)
            if node is None:
                return [f"pod {pod} completed without a Binding on record"]
            load[node] -= 1
    return []


def check_service(svc, compiles_in_window: int) -> List[str]:
    faults = []
    if svc.noop_rounds:
        faults.append(f"{svc.noop_rounds} NOOP rounds")
    if svc.ladder is not None and svc.ladder.degradations_total:
        faults.append(f"{svc.ladder.degradations_total} steps down the ladder")
    if compiles_in_window:
        faults.append(f"{compiles_in_window} programs compiled inside the window")
    return faults


def check_closing_objective(svc) -> Tuple[List[str], dict]:
    """The last solved round's problem against the native C++ solver."""
    from ksched_tpu.solver.select import make_backend

    solver = svc.scheduler.solver
    if solver.last_result is None:
        return ["no round was solved"], {}
    ours = int(solver.last_result.objective)
    native = make_backend("native", warm_start=False, fallback=False).solve(solver.state.problem())
    facts = {"objective": ours, "native_objective": int(native.objective)}
    if ours != int(native.objective):
        return [f"closing round objective {ours} != native C++ {int(native.objective)}"], facts
    return [], facts
