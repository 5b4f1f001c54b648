"""The `k8s-5000-preemption` deployment and its cell: the configuration is
scheduler_perf's `PreemptionBasic` `5000Nodes` at its source's shapes, a
pod's tier comes from its role (`pods/by_role.py`, recomputed through
`ctx.make_pod`), the cell rehearses `correct` at 1/40 scale (125 nodes x 4
slots, filled exactly by 500 low pods) traced and untraced, the six
per-layer metrics this deployment brings read a number there, and the two
checks it brings tell: a log with an eviction by an equal tier, an
eviction from a node left part-full, or a second Binding with no `evict`
between each print `correct` false with the fault.

The rehearsal offers 10 pods/s, not the mix's 100: `spec.rehearsal_config`
divides the cluster by 40 and leaves a mix's rate alone, which the
`trickle` cells bear (a completion for each arrival) and this one does
not: 500 slots hold no more than 500 high pods, and the 146th arrival
takes the graph past its 1,024-node bucket, a compile the full size (39,400
of 65,536 nodes at its end) never meets (PERF.md section 7)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import spec
from benchmarks.checks import binding_after_eviction, capacity, priority_preemption
from benchmarks.reference_preemption import check_priority_preemption, reference_round
from benchmarks.traffic import build_plan

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "k8s-5000-preemption"
CELL = CONFIG + ".rollout"
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding_after_eviction", "capacity", "answer", "priority_preemption"]
BROUGHT = {
    "evictions_post_ms": ("span_sum", "decode / apply / post"),
    "preempt_deltas_ms": ("span_sum", "decode / apply / post"),
    "pods_evicted": ("round_field", "decode / apply / post"),
    "pods_migrated": ("round_field", "decode / apply / post"),
    "tasks_unpinned": ("round_field", "graph update / export"),
    "pods_pending_evicted": ("round_field", "service loop"),
}
APPENDED = (
    "task_refresh_ms", "res_nodes_visited", "res_arcs_changed", "journal_collect_ms",
    "journal_apply_ms", "journal_changes", "problem_snapshot_ms", "ec_purge_ms", "ec_purges",
    "apply_nodes_visited", "apply_full_walks", "bindings_post_ms", "bind_tail_ms",
)

#: the mix at a tenth of its rate: see the module docstring
SLOWER = (
    "import benchmarks.spec as spec\n"
    "_load_cell = spec.load_cell\n"
    "def load_cell(name, root=spec.ROOT):\n"
    "    cell = _load_cell(name, root)\n"
    "    cell.traffic = dict(cell.traffic, rate_per_s=10.0)\n"
    "    return cell\n"
    "spec.load_cell = load_cell\n"
)


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(trace, patch="", seconds="3"):
    """The cell's rehearsal in a process of its own, with `SLOWER` and the
    lines of `patch` executed before `benchmarks.run.main`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", seconds,
            "--trace", str(trace), "--rehearse-cpu"]
    entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
             "import benchmarks.run as run\n" + SLOWER + patch + "sys.exit(run.main())\n"]
    r = subprocess.run(
        [sys.executable, *entry, *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
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
        "--fake-machines --num-machines 5000 --cores-per-machine 1 --pus-per-core 1 "
        "--max-tasks-per-pu 4 --cost-model k8s_priority --preemption --backend jax "
        "--pod-batch-timeout 0.002 --pod-chan-size 26000"
    ).split()
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (20000, 2, 500)
    assert c["resident_pods"] == 5000 * 4  # the fill is exact: no arrival fits without an eviction
    assert (c["pods"], c["priority_by_role"], c["architecture"]) == (
        "by_role", {"fill": 0, "measured": 1}, None,
    )
    assert c["reduced"] == [] and len(c["kept_from_the_source"]) >= 4
    # every assumption is listed, the one-eviction-a-pod departure first
    assert len(c["assumed"]) >= 8 and c["assumed"][0].startswith("ONE eviction for each high-priority pod")
    assert "stays pending" in c["assumed"][1]
    assert list(c["guarantees"]) == GUARANTEES
    others = _config("k8s-5000-zonespread")["guarantees"]
    assert c["guarantees"]["capacity"].startswith(others["capacity"])
    entry = next(e for e in BENCH["configs"] if e["name"] == CONFIG)
    assert entry == BENCH["configs"][-1]  # appended
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("scheduler_perf", "performance-config.yaml", "PreemptionBasic", "5000Nodes",
                 "pod-low-priority.yaml", "pod-high-priority.yaml"):
        assert word in entry["source"] and word in c["source"]
    assert sum(1 for e in BENCH["configs"] if e["source"] == entry["source"]) == 1
    assert sum(1 for e in BENCH["configs"] if e["file"] == entry["file"]) == 1
    spec.check_guarantees(c, entry["file"])
    assert spec.check_pods(c, entry["file"]) == "by_role"


def test_the_cell_takes_one_chip_and_the_new_mix_completes_nothing():
    w = next(e for e in BENCH["workloads"] if e["name"] == CELL)
    assert w == BENCH["workloads"][-1] and len(BENCH["workloads"]) == 9
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "rollout", 1)
    assert len(w["why"]) <= 200 and "not stationary" in w["why"]
    assert spec.check_names(BENCH) == []
    cell = spec.load_cell(CELL)
    mix = cell.traffic
    assert (mix["kind"], mix["rate_per_s"], mix["completions_per_arrival"], mix["warmup_s"]) == (
        "open_poisson", 100.0, 0, 3.0,
    )
    trickle = spec.load_cell("k8s-5000-zonespread.trickle").traffic
    assert (mix["rate_per_s"], mix["warmup_s"]) == (trickle["rate_per_s"], trickle["warmup_s"])
    assert {m["name"] for m in cell.end_to_end} == {"bind_p50_ms", "setup_s"}
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} == everywhere | set(BROUGHT) | set(APPENDED)
    # what the appended lists had, they have: the cell's name came last
    for name in APPENDED:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL and entry["workloads"].count(CELL) == 1
    plan = build_plan(mix, cell.config, SEED, 40.0)
    assert len(plan.resident) == 20000 and len(plan.closing) == 100
    # two classes the model prices alike, for the sake of the ONE synchronous burst between
    # the fill and the open loop: the first round at a full cluster's shapes compiles there
    assert plan.class_sweep == [[(f"s1_{i}", 0) for i in range(8)]]
    make = spec.pod_maker(cell.pods, cell.config, SEED)
    assert {make(pod, c).priority for pod, c in plan.class_sweep[0]} == {1}  # each evicts
    assert set(plan.arrival_classes) == {0, 1}


@pytest.mark.parametrize("name", sorted(BROUGHT))
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell_alone(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    reader, layer = BROUGHT[name]
    assert (entry["moves"], entry["better"], entry["layer"]) == ("bind_p50_ms", "lower", layer)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert own["reader"] == reader and own["what"]  # a reader that was there
    assert name not in {m["name"] for m in spec.load_cell("k8s-5000-zonespread.trickle").per_layer}
    # on a program that has no such span or field the reader finds nothing and says so
    import importlib

    from benchmarks.observe import Observation, Round

    read = importlib.import_module(f"benchmarks.readers.{reader}").read
    parent = Observation(
        device_kind="cpu", rounds=[Round(0.0, 1.0, 3, True, {"round": 1.0, "deltas": 0.2})],
        records=[{"num_scheduled": 3}], client={}, counters={}, shapes={},
    )
    assert read(own["params"], parent) is None


def test_a_pods_tier_is_its_role_and_the_check_recomputes_it():
    c = _config()
    make = spec.pod_maker("by_role", c, SEED)
    assert [make(p, 0).priority for p in ("r0", "r19999", "p0", "p4399", "c0", "c99", "s1_0", "w3_2")] == [
        0, 0, 1, 1, 1, 1, 1, 1,
    ]
    ev = make("p7", 0)
    assert (ev.pod_id, ev.task_class, ev.cpu_request) == ("p7", 0, 0.0)
    other = spec.pod_maker("by_role", dict(c, priority_by_role={"fill": 2, "measured": 3}), 1)
    assert (other("r1", 0).priority, other("p1", 0).priority) == (2, 3)  # the file's, not the module's
    # the seed is not read, no generator is drawn from
    assert spec.pod_maker("by_role", c, 1)("r5", 0) == spec.pod_maker("by_role", c, 2)("r5", 0)
    # the eight old cells name no module: their pods carry no priority
    assert spec.pod_maker(spec.load_cell("trivial-10kx1k.trickle").pods, c, SEED)("p0", 0).priority == 0


@pytest.mark.parametrize("module", ["class_only", "by_role"])
def test_a_pods_module_stamps_each_event_as_it_is_made_and_draws_nothing_from_the_frameworks_rng(module):
    # the half of test_benchmark_seams.py's test of `class_only` that stays true with a
    # second module under pods/ (its listing of that directory is an expected failure
    # since PR 38: tests/conftest.py), here for both modules
    from ksched_tpu.utils import rng, seed_rng

    c = _config()
    seed_rng(77)
    state = rng().getstate()
    make = spec.pod_maker(module, c, 77)
    events = [make(f"p{i}", 0) for i in range(1000)]
    assert rng().getstate() == state  # one draw would shift every task and job id of the run
    stamps = [e.received_s for e in events]
    assert stamps == sorted(stamps) and stamps[0] < stamps[-1]  # made at submission, not ahead


def test_the_rehearsal_is_the_fortieth_and_still_exactly_full(traced):
    r = spec.rehearsal_config(_config())
    assert r["argv"][r["argv"].index("--num-machines") + 1] == "125"
    assert (r["resident_pods"], r["wave_pods"]) == (500, 12)
    assert r["resident_pods"] == 125 * 4
    shapes = traced["facts"]["shapes"]
    assert (shapes["machines"], shapes["task_classes"], shapes["path"]) == (125, 2, "csr")


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["priority_preemption"]
    held = out["facts"]["binding_after_eviction"]
    assert replay["replayed"] == out["facts"]["capacity"]["replayed"] > 800
    assert (replay["tiers"], replay["nodes"], replay["node_capacity"]) == (2, 125, 4)
    # every pod bound after the fill evicted exactly one pod of tier 0; none of tier 1 ever left
    assert replay["bound_by_tier"][0] == 500 and replay["evicted_by_tier"][1] == 0
    assert replay["evicted_by_tier"][0] == replay["bound_by_tier"][1] == held["evictions"] > 100  # the closing round alone is 100
    assert replay["evicted_then_bound_again"] == 0  # nothing completes in this mix
    assert replay["running_at_end_by_tier"] == [500 - held["evictions"], held["evictions"]]
    assert replay["pending_at_end_by_tier"] == [held["evictions"], 0]
    assert held["pending_evicted_at_end"] == held["evictions"] and held["unbound"] == 0
    assert out["facts"]["capacity"]["peak_node_load"] == 4
    assert out["stderr_tail"].startswith('correct: {"correct": true')
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0 and metrics["device_round_share"] == 100.0
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in (everywhere - {"solve_roofline"}) | set(BROUGHT) | set(APPENDED):
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "backend_solve_ms", "supersteps_p50", "decode_deltas_ms",
                 "evictions_post_ms", "preempt_deltas_ms", "pods_evicted", "tasks_unpinned",
                 "pods_pending_evicted", "round_accounted_share", "stats_full_walks",
                 "apply_full_walks"):
        assert metrics[name] > 0.0, name
    assert metrics["pods_migrated"] == 0.0 and metrics["unscheduled_by_rule"] == 0.0
    assert metrics["res_nodes_visited"] == 0.0 and metrics["ec_arcs_changed"] == 0.0
    # no running task is pinned: the decode maps every task, the walk of the lists lies in deltas
    assert metrics["tasks_unpinned"] == 500.0 and metrics["decode_tasks"] >= 500.0
    assert metrics["preempt_deltas_ms"] < metrics["decode_deltas_ms"]
    assert metrics["stats_full_walks"] == metrics["apply_full_walks"]


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES


#: the pods module is not pure: `p3` was submitted as a pod of tier 1 and
#: reads as one of tier 0 when the check recomputes it, so the record shows
#: a pod of tier 0 evicted for a pod of tier 0
EQUAL_TIER = (
    "import benchmarks.pods.by_role as by_role\n"
    "from ksched_tpu.cluster.api import PodEvent\n"
    "make, seen = by_role.make, set()\n"
    "def impure(pod_id, task_class, config, seed):\n"
    "    if pod_id == 'p3' and pod_id in seen:\n"
    "        return PodEvent(pod_id=pod_id, task_class=task_class, priority=0)\n"
    "    seen.add(pod_id)\n"
    "    return make(pod_id, task_class, config, seed)\n"
    "by_role.make = impure\n"
)
#: the cluster hears, with a round's evictions, one more: a resident pod
#: that the service left where it was, from a node no pod is bound to in
#: that round; the slot it held stays free
PART_FULL = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "evict, post = client.BenchClusterAPI.evict_pods, client.BenchClusterAPI.assign_bindings\n"
    "where, told = {}, []\n"
    "def assign_bindings(self, bindings):\n"
    "    where.update((b.pod_id, b.node_id) for b in bindings)\n"
    "    post(self, bindings)\n"
    "def evict_pods(self, evictions):\n"
    "    for e in evictions:\n"
    "        where.pop(e.pod_id, None)\n"
    "    busy = {e.node_id for e in evictions}\n"
    "    if not told:\n"
    "        told.append(next((p, n) for p, n in where.items() if p[0] == 'r' and n not in busy))\n"
    "        where.pop(told[0][0])\n"
    "        evictions = list(evictions) + [Binding(*told[0])]\n"
    "    evict(self, evictions)\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
    "client.BenchClusterAPI.evict_pods = evict_pods\n"
)
#: the Binding of a resident pod that was never evicted is posted again with
#: the window's first Bindings, to the node it is on, and no eviction lies
#: between the two
BOUND_TWICE = (
    "import benchmarks.client as client\n"
    "evict, post = client.BenchClusterAPI.evict_pods, client.BenchClusterAPI.assign_bindings\n"
    "held, again = {}, []\n"
    "def evict_pods(self, evictions):\n"
    "    for e in evictions:\n"
    "        held.pop(e.pod_id, None)\n"
    "    evict(self, evictions)\n"
    "def assign_bindings(self, bindings):\n"
    "    if not again and bindings[0].pod_id.startswith('p'):\n"
    "        again.append(next(iter(held.values())))\n"
    "        bindings = list(bindings) + again\n"
    "    held.update((b.pod_id, b) for b in bindings if b.pod_id[0] == 'r')\n"
    "    post(self, bindings)\n"
    "client.BenchClusterAPI.evict_pods = evict_pods\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)


@pytest.mark.parametrize("patch, check, word", [
    (EQUAL_TIER, "priority preemption broken",
     "not a strictly higher one for each"),
    (PART_FULL, "priority preemption broken", "of 4 after the round: an eviction without need"),
    (BOUND_TWICE, "no eviction between", "1 Bindings of a pod that held one"),
], ids=["an-eviction-by-an-equal-tier", "an-eviction-from-a-node-left-part-full",
        "a-second-binding-with-no-evict-between"])
def test_a_run_that_breaks_a_guarantee_prints_correct_false_and_the_fault(patch, check, word):
    out = _rehearse(0, patch=patch)
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    faults = [f for f in out["facts"]["faults"] if check in f]
    assert faults and any(word in f for f in faults), out["facts"]["faults"]
    assert out["failed"] == 0  # the service bound every pod: it is the record that tells
    assert '"correct": false' in out["stderr_tail"] and word in out["stderr_tail"]


# -- the replay and the two modules on logs built by hand ---------------------

TIER = {"a": 0, "b": 0, "c": 0, "d": 0, "h": 1, "i": 1, "x": 2}


def _group(kind, t, *pairs):
    return [(kind, pod, node, t) for pod, node in pairs]


FILL = _group("bind", 1.0, ("a", "n0"), ("b", "n0"), ("c", "n1"), ("d", "n1"))


def _replay(log, tier_of=TIER, capacity=2, nodes=2):
    return check_priority_preemption(log, tier_of, capacity, num_nodes=nodes)


def test_the_replay_on_logs_built_by_hand():
    # the round the source describes: every arrival evicts one pod of the tier below
    log = FILL + _group("evict", 2.0, ("a", "n0"), ("c", "n1")) + _group("bind", 2.1, ("h", "n0"), ("i", "n1"))
    faults, facts = _replay(log, {k: v for k, v in TIER.items() if k != "x"})
    assert faults == []
    assert (facts["rounds"], facts["rounds_evicting"], facts["most_evictions_a_round"]) == (2, 1, 2)
    assert (facts["bound_by_tier"], facts["evicted_by_tier"]) == ([4, 2], [2, 0])
    assert (facts["running_at_end_by_tier"], facts["pending_at_end_by_tier"]) == ([2, 2], [2, 0])
    # (a) an eviction by an equal tier; the greedy would have bound and evicted nothing
    equal = {pod: 0 for pod in "abcdh"}
    faults, _ = _replay(FILL + _group("evict", 2.0, ("a", "n0")) + _group("bind", 2.1, ("h", "n0")), equal)
    assert len(faults) == 2 and "tiers [0] to eviction and the round bound pods of tiers [0]" in faults[0]
    assert "the greedy binds [0] and evicts [0]" in faults[1]
    # (a) an eviction for a LOWER tier
    faults, _ = _replay(
        FILL[:3] + _group("bind", 1.0, ("h", "n1"))
        + _group("evict", 2.0, ("h", "n1")) + _group("bind", 2.1, ("d", "n1"))
    )
    assert "lost pods of tiers [1]" in faults[0] and "bound pods of tiers [0]" in faults[0]
    # (b) the pod that took the slot went to another node, which had room: the loser's node is part-full
    part = FILL[:3] + _group("evict", 2.0, ("a", "n0")) + _group("bind", 2.1, ("h", "n1"))
    faults, _ = _replay(part)
    assert any("node n0 lost 1 pods to eviction and holds 1 of 2 after the round" in f for f in faults)
    assert any("the greedy binds [0, 1, 0] and evicts [0, 0, 0]" in f for f in faults)  # a free slot first
    # (a) evictions and no Binding at all
    faults, _ = _replay(FILL + _group("evict", 2.0, ("a", "n0")))
    assert "bound pods of tiers [] onto it" in faults[0]
    # (c) the victim is not of the lowest tier that has one
    mixed = (
        _group("bind", 1.0, ("a", "n0"), ("h", "n0"), ("i", "n1"), ("b", "n1"))
        + _group("evict", 2.0, ("h", "n0")) + _group("bind", 2.1, ("x", "n0"))
    )
    faults, _ = _replay(mixed, {k: v for k, v in TIER.items() if k in "abhix"})
    assert faults == [
        "t=2.100000: the round bound [0, 0, 1] and evicted [0, 1, 0] by tier, the greedy "
        "binds [0, 0, 1] and evicts [1, 0, 0] (running [2, 2, 0], pending [0, 0, 1], free 0)",
        "at the end a pod of tier 1 is pending while one of tier 0 runs",  # (d): the same mistake
    ]
    # a completion frees a slot, the evicted pod is bound again: no eviction is needed
    again = (
        FILL + _group("evict", 2.0, ("a", "n0")) + _group("bind", 2.1, ("h", "n0"))
        + [("done", "c", "", 3.0)] + _group("bind", 3.5, ("a", "n1"))
    )
    faults, facts = _replay(again, {k: v for k, v in TIER.items() if k in "abcdh"})
    assert faults == [] and facts["evicted_then_bound_again"] == 1
    assert facts["pending_at_end_by_tier"] == [0, 0] and facts["running_at_end_by_tier"] == [3, 1]
    # (d) at the end a higher tier waits while a lower one runs: `i` never got a Binding
    faults, _ = _replay(FILL, {k: v for k, v in TIER.items() if k in "abcdi"})
    assert faults == ["at the end a pod of tier 1 is pending while one of tier 0 runs"]
    # without the number of nodes a round's free slots are read off the round itself
    assert check_priority_preemption(log, {k: v for k, v in TIER.items() if k != "x"}, 2)[0] == []
    assert reference_round(1, [3, 0], [2, 2]) == ([0, 2], [1, 0])  # a free slot first, then one victim
    assert _replay([]) == ([], {
        "replayed": 0, "rounds": 0, "rounds_evicting": 0, "tiers": 3,
        "bound_by_tier": [0, 0, 0], "evicted_by_tier": [0, 0, 0], "evicted_then_bound_again": 0,
        "most_evictions_a_round": 0, "running_at_end_by_tier": [0, 0, 0],
        "pending_at_end_by_tier": [2 + 2, 2, 1],
    })


def _ctx(log, due=(), bind_stamps=None):
    stamps = {}
    for kind, pod, _node, t in log:
        if kind == "bind":
            stamps.setdefault(pod, []).append(t)
    plan = SimpleNamespace(
        resident=[("r0", 0), ("r1", 0)], closing=[("c0", 0)], class_sweep=[],
        arrival_classes=[0] * 6, arrival=lambda i: (f"p{i}", 0), wave_pods=0,
    )
    config = {"priority_by_role": {"fill": 0, "measured": 1}}
    return SimpleNamespace(
        config=config, plan=plan, make_pod=spec.pod_maker("by_role", config, 1), log=log, facts={},
        due={p: (0.0, 0.0) for p in due}, bind_stamps=stamps if bind_stamps is None else bind_stamps,
        svc=SimpleNamespace(node_to_machine={"n0": 0}),
        svc_args=SimpleNamespace(cores_per_machine=1, pus_per_core=1, max_tasks_per_pu=2),
    )


def test_the_two_check_modules_on_records_built_by_hand():
    good = (
        _group("bind", 1.0, ("r0", "n0"), ("r1", "n0"))
        + _group("evict", 2.0, ("r0", "n0")) + _group("bind", 2.1, ("p0", "n0"))
        + _group("evict", 3.0, ("r1", "n0")) + _group("bind", 3.1, ("c0", "n0"))
    )
    c = _ctx(good, due=["p0"])
    assert binding_after_eviction.check(c) == [] and capacity.check(c) == []
    assert priority_preemption.check(c) == []
    assert c.facts["binding_after_eviction"] == {
        "due": 1, "unbound": 0, "bound": 4, "evictions": 2, "bound_again": 0,
        "pending_evicted_at_end": 2, "second_binding_unevicted": 0, "evicted_unbound": 0,
    }
    # the arrivals past the last one due were never submitted: p1..p5 are not pending pods
    assert c.facts["priority_preemption"]["pods"] == 4 and c.facts["priority_preemption"]["nodes"] == 1
    assert set(priority_preemption.submitted_pods(_ctx(good, due=["p0", "p2"]))) == {
        "r0", "r1", "c0", "p0", "p1", "p2",
    }
    # a pod due in the window that got no Binding
    (fault,) = binding_after_eviction.check(_ctx(good, due=["p0", "p1"]))
    assert "1 pods due in the window got no Binding (first: p1)" in fault
    # a second Binding with no eviction between; `capacity` reads a move, this check tells
    twice = good[:2] + _group("bind", 1.5, ("r1", "n0")) + good[2:]
    c = _ctx(twice, due=["p0"])
    (fault,) = binding_after_eviction.check(c)
    assert "1 Bindings of a pod that held one, with no eviction between (first: r1 at t=1.500000)" in fault
    assert capacity.check(c) == [] and c.facts["binding_after_eviction"]["second_binding_unevicted"] == 1
    # an evicted pod bound again after its eviction is what the guarantee allows
    back = good + _group("evict", 4.0, ("c0", "n0")) + _group("bind", 4.1, ("r0", "n0"))
    c = _ctx(back, due=["p0"])
    assert binding_after_eviction.check(c) == []
    assert c.facts["binding_after_eviction"]["bound_again"] == 1
    assert any("lost pods of tiers [1]" in f for f in priority_preemption.check(c))
    # an eviction of a pod that holds no Binding
    (fault,) = binding_after_eviction.check(_ctx(good + _group("evict", 5.0, ("r0", "n0")), due=["p0"]))
    assert "1 evictions of a pod that held no Binding (first: r0 at t=5.000000)" in fault
    # the module hands the replay's faults on, each under the guarantee's name
    c = _ctx(good[:2] + _group("evict", 2.0, ("r0", "n0")) + _group("bind", 2.1, ("r0", "n0")), due=[])
    faults = priority_preemption.check(c)
    assert faults and all(f.startswith("priority preemption broken: ") for f in faults)
