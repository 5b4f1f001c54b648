"""`answer`: every round is answered by the configured rung, and its
objective equals the native C++ solver's on the same problem.

As far as a run can show it: no NOOP round, no step down the ladder, no
program compiled inside the window (a shape the warm-up did not reach is
a round answered late), no completion refused (the service knew every pod
the cluster said had finished), and the last solved round's problem (the
closing round: traffic.CLOSING_PODS pods after the drain), solved again
by the independent C++ solver, has the same objective. The two objectives
go to `facts["closing"]`.
"""

from typing import List


def check(ctx) -> List[str]:
    from ksched_tpu.solver.select import make_backend

    svc = ctx.svc
    faults = []
    if svc.noop_rounds:
        faults.append(f"{svc.noop_rounds} NOOP rounds")
    if svc.ladder is not None and svc.ladder.degradations_total:
        faults.append(f"{svc.ladder.degradations_total} steps down the ladder")
    if ctx.compiles_in_window:
        faults.append(f"{ctx.compiles_in_window} programs compiled inside the window")
    solver = svc.scheduler.solver
    ctx.facts["closing"] = {}
    if solver.last_result is None:
        faults.append("no round was solved")
    else:
        ours = int(solver.last_result.objective)
        native = make_backend("native", warm_start=False, fallback=False)
        theirs = int(native.solve(solver.state.problem()).objective)
        ctx.facts["closing"] = {"objective": ours, "native_objective": theirs}
        if ours != theirs:
            faults.append(f"closing round objective {ours} != native C++ {theirs}")
    if ctx.completions_refused:
        faults.append(f"{ctx.completions_refused} completions of pods that were not bound")
    return faults
