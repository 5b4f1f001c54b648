"""The `k8s-5000-zonespread` deployment and its cell: the configuration is
scheduler_perf's `TopologySpreading` `5000Nodes` at its source's shapes, the
cell rehearses `correct` at 1/40 scale (125 machines in three zones, 1,250
pods, 16 workloads) traced and untraced with no program compiled in the
window, the three per-layer metrics this deployment brings read a number
there, and `correct` holds the run to the rule: `checks/topology_spread.py`
replays the whole run's Binding log through `check_topology_spread`, so a
Binding re-addressed into a zone that is already `max_skew` above the
lowest, or a replay that reports a violation, prints `correct` false with
the fault."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import spec
from benchmarks.reference_zonespread import check_topology_spread

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "k8s-5000-zonespread"
CELL = CONFIG + ".trickle"
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity", "answer", "topology_spread"]
BROUGHT = ("ec_chain_refresh_ms", "ec_chain_arcs_changed", "spread_fallback_rounds")


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(trace, patch=None, seconds="3"):
    """The cell's rehearsal in a process of its own; with `patch`, the same
    run with those lines executed before `benchmarks.run.main`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", seconds,
            "--trace", str(trace), "--rehearse-cpu"]
    if patch is None:
        entry = BENCH["command"][1:]
    else:
        entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
                 "import benchmarks.run as run\n" + patch + "sys.exit(run.main())\n"]
    r = subprocess.run(
        [sys.executable, *entry, *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["stderr_tail"] = r.stderr.strip().splitlines()[-1]
    return out


@pytest.fixture(scope="module")
def traced():
    return _rehearse(1)


def test_the_configuration_is_the_sources_shapes():
    c = _config()
    assert c["argv"] == (
        "--fake-machines --num-machines 5000 --fake-zones 3 --cores-per-machine 1 "
        "--pus-per-core 1 --max-tasks-per-pu 110 --cost-model k8s_zonespread --backend jax "
        "--pod-batch-timeout 0.002 --pod-chan-size 53000"
    ).split()
    # the anti-affinity deployment's argv but for the model and the zones
    other = _config("k8s-5000-antiaffinity")["argv"]
    assert [a for a in c["argv"] if a not in ("--fake-zones", "3", "k8s_zonespread")] == [
        a for a in other if a != "k8s_antiaffinity"
    ]
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (50000, 16, 2500)
    assert (c["zones"], c["max_skew"], c["architecture"]) == (3, 5, None)
    assert c["reduced"] == [] and len(c["assumed"]) >= 6 and "maxSkew 5" in c["assumed"][0]
    assert len(c["kept_from_the_source"]) >= 4
    assert list(c["guarantees"]) == GUARANTEES
    others = _config("coco-50kx1k")["guarantees"]
    assert {k: c["guarantees"][k] for k in others} == others
    assert "f(g, z) <= min" in c["guarantees"]["topology_spread"]
    entry = next(e for e in BENCH["configs"] if e["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("scheduler_perf", "TopologySpreading", "5000Nodes", "large clusters"):
        assert word in entry["source"] and word in c["source"]
    # no other configuration has this source or this file
    assert sum(1 for e in BENCH["configs"] if e["source"] == entry["source"]) == 1
    assert sum(1 for e in BENCH["configs"] if e["file"] == entry["file"]) == 1
    spec.check_guarantees(c, entry["file"])  # each stated guarantee has its module


def test_the_cell_takes_one_chip_and_the_trickle_as_it_stands():
    w = next(e for e in BENCH["workloads"] if e["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200
    assert spec.check_names(BENCH) == []
    cell = spec.load_cell(CELL)
    assert cell.traffic == spec.load_cell("k8s-5000-antiaffinity.trickle").traffic
    assert {m["name"] for m in cell.end_to_end} >= {"bind_p50_ms", "setup_s"}
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} >= everywhere | set(BROUGHT)


def test_the_guarantees_stated_today_are_the_check_modules_and_each_cell_loads():
    """What the configurations state is what `checks/` holds a module for,
    no more and no less (the form `test_benchmark_checks.py`'s test of this
    name took from here, PR 37), and the six of today are among them."""
    stated = set()
    for entry in BENCH["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            stated |= set(json.load(f)["guarantees"])
    modules = {
        name[:-3] for name in os.listdir(os.path.join(spec.HERE, "checks"))
        if name.endswith(".py") and not name.startswith("_")
    }
    assert stated == modules
    assert stated >= {
        "binding", "capacity", "answer", "resident", "anti_affinity", "topology_spread",
    }
    for w in BENCH["workloads"]:
        assert spec.load_cell(w["name"]).config["guarantees"]


@pytest.mark.parametrize("name", BROUGHT)
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell_alone(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["better"], entry["layer"]) == (
        "bind_p50_ms", "lower", "graph update / export",
    )
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    # read by a reader that was there: no benchmark code comes with it
    assert own["reader"] in ("span_sum", "round_field") and own["what"]
    # the other cells do not get it
    assert name not in {m["name"] for m in spec.load_cell("k8s-5000-antiaffinity.trickle").per_layer}


def test_the_rehearsal_is_the_fortieth_in_three_zones(traced):
    r = spec.rehearsal_config(_config())
    assert r["argv"][r["argv"].index("--num-machines") + 1] == "125"
    assert r["argv"][r["argv"].index("--fake-zones") + 1] == "3"
    assert (r["resident_pods"], r["task_classes"], r["zones"]) == (1250, 16, 3)
    shapes = traced["facts"]["shapes"]
    assert (shapes["nodes"], shapes["machines"], shapes["task_classes"], shapes["path"]) == (
        2048, 125, 16, "csr",
    )
    # half the anti-affinity rehearsal's arcs: a workload's fan-out is three zones
    assert shapes["arcs"] == 4096


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["warmup_extensions"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["topology_spread"]
    assert replay["replayed"] == out["facts"]["capacity"]["replayed"] > 1250
    assert (replay["zones"], replay["workloads"]) == (3, 16)
    # with room the allotment ends every receiver within 1 of the lowest zone
    assert 0 <= replay["largest_skew"] <= 1 and replay["rounds"] > 100
    assert "+ 5" in replay["limit"]
    assert out["stderr_tail"].startswith('correct: {"correct": true')
    assert '"topology_spread"' in out["stderr_tail"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0 and metrics["device_round_share"] == 100.0
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in (everywhere - {"solve_roofline"}) | set(BROUGHT):
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "graph_update_ms", "backend_solve_ms", "supersteps_p50",
                 "apply_ms", "round_accounted_share", "graph_refresh_ms", "ec_refresh_ms",
                 "ec_arcs_changed", "ec_chain_refresh_ms", "ec_chain_arcs_changed"):
        assert metrics[name] > 0.0, name
    # the chain half of an EC node's update lies inside the refresh
    assert metrics["ec_chain_refresh_ms"] < metrics["graph_refresh_ms"]
    assert metrics["spread_fallback_rounds"] == 0.0 and metrics["unscheduled_by_rule"] == 0.0


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES


#: the Bindings of workload 0's arrivals (`p<i>`) are re-addressed to one
#: node of zone 0 (123 = 0 mod 3; the service fills the low-numbered nodes first), whatever zone it chose: the service solved every
#: round rightly, and the cluster hears answers that pile the workload up in
#: a zone that is soon more than `max_skew` above the lowest
ALL_INTO_ONE_ZONE = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "submit, post = client.BenchClusterAPI.submit_pod, client.BenchClusterAPI.assign_bindings\n"
    "workload = {}\n"
    "def submit_pod(self, ev):\n"
    "    workload[ev.pod_id] = ev.task_class\n"
    "    return submit(self, ev)\n"
    "def assign_bindings(self, bindings):\n"
    "    post(self, [\n"
    "        Binding(pod_id=b.pod_id, node_id='fake_node_123')\n"
    "        if b.pod_id.startswith('p') and workload[b.pod_id] == 0 else b\n"
    "        for b in bindings\n"
    "    ])\n"
    "client.BenchClusterAPI.submit_pod = submit_pod\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)
#: the replay itself reports a violation (the run is sound)
REPLAY_SAYS_NO = (
    "import benchmarks.reference_zonespread as ref\n"
    "ref.check_topology_spread = lambda log, group_of, zone_of, max_skew: ("
    "'t=1.000000: zone 2 holds 9 pods of workload 3, 6 above the lowest zone\\'s 3 (maxSkew 5)', "
    "{'replayed': 7, 'rounds': 2, 'largest_skew': 6})\n"
)


@pytest.mark.parametrize("patch, word", [
    (ALL_INTO_ONE_ZONE, "pods of workload 0, 6 above the lowest zone's"),
    (REPLAY_SAYS_NO, "zone 2 holds 9 pods of workload 3, 6 above the lowest zone's 3 (maxSkew 5)"),
], ids=["bindings-readdressed-into-a-zone-already-max-skew-above", "the-replay-reports-a-violation"])
def test_a_run_that_breaks_the_rule_prints_correct_false_and_the_fault(patch, word):
    out = _rehearse(0, patch=patch, seconds="3")
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    (fault,) = [f for f in out["facts"]["faults"] if "topology spread broken" in f]
    assert word in fault
    # the other three guarantees held: it is this check that tells
    assert out["facts"]["faults"] == [fault] and out["failed"] == 0
    assert out["facts"]["topology_spread"]["largest_skew"] == 6
    assert '"correct": false' in out["stderr_tail"] and "topology spread broken" in out["stderr_tail"]


GROUP_OF = {"a": 0, "b": 0, "c": 0, "d": 0, "e": 0, "f": 0, "g": 0, "h": 0, "x": 1, "y": 1}
ZONE_OF = {"n0": 0, "n1": 1, "n2": 2, "m0": 0}


def _binds(t, *pairs):
    return [("bind", pod, node, t) for pod, node in pairs]


def test_the_replay_on_logs_built_by_hand():
    check = lambda log, s=2: check_topology_spread(log, GROUP_OF, ZONE_OF, s)  # noqa: E731
    # one round, level: (1, 1, 1)
    fault, facts = check(_binds(1.0, ("a", "n0"), ("b", "n1"), ("c", "n2")))
    assert fault is None and facts == {"replayed": 3, "rounds": 1, "largest_skew": 0}
    # a round is judged at its end, not pod by pod: (3, 1, 0) within one
    # round, whatever the order of its Bindings in the log
    log = _binds(1.0, ("a", "n0"), ("b", "m0"), ("c", "n0"), ("d", "n1"))
    fault, facts = check(log)
    assert fault is not None and "zone 0 holds 3 pods of workload 0, 3 above" in fault
    assert "t=1.000000" in fault and facts["largest_skew"] == 3
    assert check(log, s=3)[0] is None
    # the same four pods over two rounds that each end within 2: no fault
    log = _binds(1.0, ("a", "n0"), ("d", "n1"), ("e", "n2")) + _binds(2.0, ("b", "m0"), ("c", "n0"))
    fault, facts = check(log)
    assert fault is None and facts == {"replayed": 5, "rounds": 2, "largest_skew": 2}
    # only the zones that RECEIVED in the round are held to the rule: zone 0
    # stands 3 above after completions elsewhere, and a Binding into zone 1 passes
    log = (
        _binds(1.0, ("a", "n0"), ("b", "n0"), ("c", "n0"), ("d", "n1"), ("e", "n1"), ("f", "n2"))
        + [("done", "d", "", 1.5), ("done", "e", "", 1.6), ("done", "f", "", 1.7)]
        + _binds(2.0, ("g", "n1"))
    )
    fault, facts = check(log)
    assert fault is None and facts["rounds"] == 2 and facts["largest_skew"] == 2
    # ... and one into zone 0 does not
    fault, _ = check(log[:-1] + _binds(2.0, ("g", "n0")))
    assert fault is not None and "zone 0 holds 4 pods of workload 0, 4 above the lowest zone's 0" in fault
    # a completion lowers f in the log's own order; another workload has its own counts
    log = (
        _binds(1.0, ("a", "n0"), ("b", "n0"), ("x", "n0"), ("y", "n0"))
        + [("done", "a", "", 1.5)]
        + _binds(2.0, ("c", "n0"), ("d", "n0"))
    )
    fault, _ = check(log)
    assert fault is not None and "t=2.000000" in fault and "holds 3 pods of workload 0" in fault
    assert check(log[:4])[0] is None  # (2, 0, 0) of each workload: within 2
    # a pod bound again moves: its old zone loses it
    log = _binds(1.0, ("a", "n0"), ("b", "n0")) + _binds(2.0, ("a", "n1"), ("c", "n0"))
    fault, facts = check(log)
    assert fault is None and facts["largest_skew"] == 2
    # an empty log
    assert check([]) == (None, {"replayed": 0, "rounds": 0, "largest_skew": 0})


def test_the_check_module_reads_zones_from_the_file_and_holds_them_against_the_labels():
    from types import SimpleNamespace

    from benchmarks.checks import topology_spread
    from ksched_tpu.data import ZONE_LABEL

    def ctx(labels, log):
        machines = {i: SimpleNamespace(descriptor=SimpleNamespace(labels=l)) for i, l in enumerate(labels)}
        svc = SimpleNamespace(
            node_to_machine={f"fake_node_{i}": i for i in machines},
            resource_map=SimpleNamespace(find=machines.get),
        )
        plan = SimpleNamespace(
            resident=[("r0", 0), ("r1", 0), ("r2", 0)], closing=[], class_sweep=[],
            arrival_classes=None, wave_pods=0,
        )
        return SimpleNamespace(
            config={"zones": 3, "max_skew": 1}, svc=svc, plan=plan, log=log, facts={},
        )

    good = [{ZONE_LABEL: f"z{i % 3}"} for i in range(6)]
    log = _binds(1.0, ("r0", "fake_node_0"), ("r1", "fake_node_4"), ("r2", "fake_node_5"))
    c = ctx(good, log)
    assert topology_spread.check(c) == []
    assert c.facts["topology_spread"]["replayed"] == 3 and c.facts["topology_spread"]["zones"] == 3
    # nodes 0 and 3 are one zone by their names: two pods there is 2 above
    c = ctx(good, _binds(1.0, ("r0", "fake_node_0"), ("r1", "fake_node_3")))
    (fault,) = topology_spread.check(c)
    assert "topology spread broken" in fault and "2 above" in fault
    # labels that deal the nodes another way, or are missing, are a fault
    bad = [dict(l) for l in good]
    bad[4] = {ZONE_LABEL: "z2"}
    assert "fake_node_4" in topology_spread.check(ctx(bad, log))[0]
    bad[4] = {}
    assert "carries no" in topology_spread.check(ctx(bad, log))[0]
