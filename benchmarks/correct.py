"""The comparison that decides `correct`, made outside the window.

`correct` is the conjunction of the guarantees the configuration states:
for each key of its `guarantees`, in the file's order, the module
`checks/<key>.py` is imported and its `check(ctx)` returns the faults it
found (none: the guarantee held, as far as a run can show it). Nothing
here, and nothing in run.py, names a check: a configuration that states a
new guarantee brings `checks/<guarantee>.py` beside its file, and
`spec.load_cell` refuses one that names a guarantee without a module
before the service is built.

What a check gets is `Context`: what run.py holds once the window has
closed, and nothing with which a check could alter the run.
"""

from __future__ import annotations

import importlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

from .traffic import Plan

#: ("bind", pod, node, t): a Binding posted; ("done", pod, "", t): a
#: completion the service took; ("evict", pod, node, t): the service took
#: the pod off the node it was bound to, and it is pending again
LogEntry = Tuple[str, str, str, float]


@dataclass(frozen=True)
class Context:
    #: the configuration as it was run (under --rehearse-cpu, the fortieth)
    config: dict
    #: what traffic.build_plan drew from the seed: every pod's class
    plan: Plan
    #: the cell's pods module over this run's (config, seed): (pod id, class)
    #: -> the PodEvent that was submitted, but for its `received_s`
    make_pod: Callable[[str, int], object]
    svc: object  # cli.SchedulerService, after `run` returned
    svc_args: object  # the configuration's argv, parsed
    #: pod -> (due, submitted) of every pod due in the window
    due: Dict[str, Tuple[float, float]]
    #: pod -> stamps of every Binding posted for it, the whole run
    bind_stamps: Dict[str, List[float]]
    #: Bindings, completions and evictions of the whole run, in the loop's order
    log: Sequence[LogEntry]
    completions_refused: int
    compiles_in_window: int
    #: what a check compared, for the last line's `facts`: a check adds
    #: one entry under a key of its own
    facts: dict = field(default_factory=dict)


def run_checks(ctx: Context) -> Tuple[List[str], List[str]]:
    """(faults, the names of the checks run, in the configuration's order)."""
    faults: List[str] = []
    names = list(ctx.config["guarantees"])
    seconds = ctx.facts.setdefault("check_seconds", {})
    for name in names:
        t0 = time.perf_counter()
        faults += importlib.import_module(f"benchmarks.checks.{name}").check(ctx)
        seconds[name] = time.perf_counter() - t0
    return faults, names


def pod_classes(plan: Plan, log: Iterable[LogEntry]) -> Dict[str, int]:
    """The class of every pod of the run: the fill, the class sweep, the
    closing round, and the arrivals (open loop) or the waves the log
    names (closed loop; wave k's pods are `w<k>_<i>`)."""
    classes = dict(plan.resident + plan.closing)
    for burst in plan.class_sweep:
        classes.update(burst)
    if plan.arrival_classes is not None:
        classes.update(plan.arrival(i) for i in range(len(plan.arrival_classes)))
    if plan.wave_pods:
        waves = {int(pod[1:pod.index("_")]) for _k, pod, _n, _t in log if pod[0] == "w"}
        for k in waves:
            classes.update(plan.wave(k))
    return classes
