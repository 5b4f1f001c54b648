"""The one general traffic generator: a pure function of (mix, config, seed).

A traffic mix is a data file (`traffic/<mix>.json`); its `kind` picks one
of the arrival processes here, its other keys are that process's
parameters. Everything drawn is drawn from `--seed`, before the window,
in a fixed order, so the same seed gives the same pods, classes, arrival
offsets and completion order.

Kinds:

  open_poisson   open loop. Pods arrive one by one as a Poisson process
                 at `rate_per_s`; with each arrival
                 `completions_per_arrival` resident pods complete, so
                 occupancy is stationary. The process starts `warmup_s`
                 before the window and runs through it.
  closed_waves   closed loop, one client. When every pod of a wave is
                 bound, as many resident pods complete and the next wave
                 is submitted back to back. `wave_pods` is a number, or
                 "config" for the configuration's own `wave_pods`, or
                 absent with `wave_share` (a share of the resident pods).
                 `warmup_waves` whole waves run before the window.

Pods complete in the order of a seeded permutation of the resident pods,
then in the order they were submitted (every pod lives as long as it
takes the traffic to turn the resident set over once).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

KINDS = ("open_poisson", "closed_waves")

#: pods of the closing round, solved after the drain and solved again by
#: the independent C++ solver (correct.py)
CLOSING_PODS = 100

Pod = Tuple[str, int]  # (pod id, task class)


@dataclass
class Plan:
    kind: str
    seed: int
    task_classes: int
    resident: List[Pod]
    #: completion order of the resident pods (later pods follow, FIFO)
    victims: List[str]
    #: warm-up bursts that differ in how many distinct classes they hold
    #: (a dense-transport program is compiled per number of cost rows)
    class_sweep: List[List[Pod]]
    closing: List[Pod]
    # open_poisson
    rate_per_s: float = 0.0
    completions_per_arrival: int = 0
    warmup_s: float = 0.0
    arrival_offsets_s: np.ndarray = None  # from the start of warm-up
    arrival_classes: np.ndarray = None
    # closed_waves
    wave_pods: int = 0
    warmup_waves: int = 0

    def arrival(self, i: int) -> Pod:
        return (f"p{i}", int(self.arrival_classes[i]))

    def wave(self, k: int) -> List[Pod]:
        """Wave k's pods: drawn from (seed, k), so the waves a run gets
        to are the same whatever its speed."""
        rng = np.random.default_rng([self.seed, 1000 + k])
        classes = rng.integers(0, self.task_classes, self.wave_pods)
        return [(f"w{k}_{i}", int(c)) for i, c in enumerate(classes)]


def wave_size(traffic: dict, config: dict) -> int:
    w = traffic.get("wave_pods")
    if w == "config":
        return int(config["wave_pods"])
    if w is not None:
        return int(w)
    return max(1, int(round(float(traffic["wave_share"]) * config["resident_pods"])))


def build_plan(traffic: dict, config: dict, seed: int, seconds: float) -> Plan:
    kind = traffic["kind"]
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    rng = np.random.default_rng([seed, 0])
    n_res = int(config["resident_pods"])
    k_cls = int(config["task_classes"])
    res_classes = rng.integers(0, k_cls, n_res)
    resident = [(f"r{i}", int(c)) for i, c in enumerate(res_classes)]
    victims = [resident[i][0] for i in rng.permutation(n_res)]
    closing = [(f"c{i}", int(c)) for i, c in enumerate(rng.integers(0, k_cls, CLOSING_PODS))]
    # bursts holding exactly 1 .. k_cls-1 distinct classes, 8 pods each
    class_sweep = [
        [(f"s{k}_{i}", i % k) for i in range(8)] for k in range(1, k_cls)
    ]
    plan = Plan(
        kind=kind, seed=seed, task_classes=k_cls, resident=resident,
        victims=victims, class_sweep=class_sweep, closing=closing,
    )
    if kind == "open_poisson":
        plan.rate_per_s = float(traffic["rate_per_s"])
        plan.completions_per_arrival = int(traffic["completions_per_arrival"])
        plan.warmup_s = float(traffic["warmup_s"])
        # enough arrivals for warm-up (and its extensions), window, drain
        horizon = plan.warmup_s * 4 + seconds + 10.0
        n = int(horizon * plan.rate_per_s * 1.2) + 64
        plan.arrival_offsets_s = np.cumsum(rng.exponential(1.0 / plan.rate_per_s, n))
        plan.arrival_classes = rng.integers(0, k_cls, n)
    else:
        plan.wave_pods = wave_size(traffic, config)
        plan.warmup_waves = int(traffic["warmup_waves"])
    return plan
