"""What a cell is made of, found by name.

`BENCHMARK.json` names cells (`<config>.<mix>`), metrics and `paths`.
Whatever belongs to one configuration, one traffic mix or one per-layer
metric is one file under this directory:

  configs/<config>.json        the deployment: source, argv for
                               cli.build_arg_parser(), resident pods,
                               class mix, guarantees, assumed, reduced,
                               and `pods` where a pod carries more than
                               its class
  pods/<name>.py               what a pod of the deployment carries:
                               `make(pod_id, task_class, config, seed)
                               -> PodEvent`, a pure function of its four
                               arguments that draws from no shared
                               generator (the framework's RNG feeds the
                               task and job ids), called at the moment of
                               submission; `class_only` where a
                               configuration names none
  traffic/<mix>.json           parameters of one traffic mix, read by the
                               one general generator (traffic.py)
  checks/<guarantee>.py        one guarantee a configuration states:
                               `check(ctx) -> faults`; `correct` is the
                               conjunction over the configuration's
                               `guarantees`, in the file's order
                               (correct.py)
  layer_metrics/<name>.json    one per-layer metric: layer, unit, the
                               reader (readers/<reader>.py) and its
                               parameters, the reduction, `moves`

A later PR adds files and entries; it edits none that is here. A
configuration that states a guarantee with no module, or none at all, is
refused by `load_cell`: nothing can be stated and left unchecked. So is
one that names a `pods` module that is not there.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

#: the pods of a configuration that names no `pods` module
DEFAULT_PODS = "class_only"

#: --rehearse-cpu divides machines, resident pods and wave sizes by this
REHEARSE_DIVISOR = 40


class SpecError(ValueError):
    """BENCHMARK.json or one of the cell's files does not hold together."""


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    pods: str  # the module under pods/ that builds this deployment's PodEvents
    end_to_end: List[dict]
    per_layer: List[dict]  # BENCHMARK.json entries merged over their own files


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    check_guarantees(config, configs[w["config"]]["file"])
    pods = check_pods(config, configs[w["config"]]["file"])
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _in_cell(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = []
    for m in bench["per_layer"]:
        # a per-layer metric is reported only where the metric it moves is
        if not _in_cell(m, name) or m["moves"] not in e2e_names:
            continue
        own = _load(os.path.join(HERE, "layer_metrics", m["name"] + ".json"))
        for key in ("unit", "better", "layer", "moves", "source"):
            if own.get(key) != m[key]:
                raise SpecError(
                    f"layer_metrics/{m['name']}.json says {key}={own.get(key)!r}, "
                    f"BENCHMARK.json says {m[key]!r}"
                )
        per_layer.append(own)
    return Cell(name, config, traffic, int(w["chips"]), pods, e2e, per_layer)


def check_guarantees(config: dict, file: str) -> None:
    """Every guarantee the configuration states has its check module."""
    guarantees = config.get("guarantees")
    if not isinstance(guarantees, dict) or not guarantees:
        raise SpecError(f"{file} states no `guarantees`: `correct` would hold it to nothing")
    for key in guarantees:
        if not NAME_RE.match(key) or not os.path.isfile(os.path.join(HERE, "checks", key + ".py")):
            raise SpecError(
                f"{file} states the guarantee {key!r} and there is no "
                f"benchmarks/checks/{key}.py to hold a run to it"
            )


def check_pods(config: dict, file: str) -> str:
    """The name of the configuration's `pods` module, which is there."""
    name = config.get("pods", DEFAULT_PODS)
    path = os.path.join(HERE, "pods", f"{name}.py")
    if not isinstance(name, str) or not NAME_RE.match(name) or not os.path.isfile(path):
        raise SpecError(
            f"{file} names the pods module {name!r} and there is no "
            f"{os.path.relpath(path, os.path.dirname(HERE))} to build its pods"
        )
    return name


def pod_maker(name: str, config: dict, seed: int) -> Callable:
    """`make_pod(pod_id, task_class) -> PodEvent`: pods/<name>.py's `make`
    over the configuration as it is run and the run's seed."""
    make = importlib.import_module(f"benchmarks.pods.{name}").make
    return functools.partial(make, config=config, seed=seed)


def rehearsal_config(config: dict) -> dict:
    """The same deployment at 1/REHEARSE_DIVISOR of its scale, for
    --rehearse-cpu and the tests: shapes (PUs, slots, classes, flags)
    stay, the cluster and the backlog shrink."""
    out = dict(config)
    argv = list(config["argv"])
    i = argv.index("--num-machines") + 1
    argv[i] = str(max(4, int(argv[i]) // REHEARSE_DIVISOR))
    out["argv"] = argv
    out["resident_pods"] = max(16, config["resident_pods"] // REHEARSE_DIVISOR)
    out["wave_pods"] = max(4, config["wave_pods"] // REHEARSE_DIVISOR)
    return out


def check_names(bench: dict) -> List[str]:
    """The contract's rules on names, units and sources that can be
    checked without a run; returns the faults found."""
    faults: List[str] = []

    def name_ok(what: str, s: str) -> None:
        if not isinstance(s, str) or not NAME_RE.match(s):
            faults.append(f"{what}: bad name {s!r}")

    for c in bench["configs"]:
        name_ok("config", c["name"])
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
    for w in bench["workloads"]:
        name_ok("workload", w["name"])
        name_ok("workload config", w["config"])
        name_ok("workload traffic", w["traffic"])
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips {w['chips']}")
    seen: Dict[str, str] = {}
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            name_ok(kind, m["name"])
            if m["name"] in seen:
                faults.append(f"metric {m['name']} appears twice")
            seen[m["name"]] = kind
            if not UNIT_RE.match(m["unit"]):
                faults.append(f"metric {m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                faults.append(f"metric {m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                faults.append(f"metric {m['name']}: source={m['source']!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end {m['name']}: source {m['source']!r}")
    return faults
