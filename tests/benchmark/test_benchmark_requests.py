"""The `k8s-5000-requests` deployment and its cell: the configuration is
scheduler_perf's SchedulingBasic 5000Nodes (node template 4 CPUs, 32 Gi, 110
pods; pod template 100m, 500Mi: one size) at the 150,000 pods the Kubernetes
documentation gives 5,000 nodes, under kube-scheduler's default filter and
scores; the cell rehearses `correct` at 1/40 scale (125 nodes, 3,750
resident pods) traced and untraced, every round on the scan-CSR rung (one
size is one row, which the dense rung answers on the host with no device
op), the four per-layer metrics this deployment brings read a number
there, and the two checks it brings tell: a model whose k(m) is one too
large, a Binding moved to a costlier node that had room and a node built
with another vector than the file's each turn `correct` false with the
fault. Pods that differ in size are the model's to serve and no cell's yet
(no public mix of requests): the same harness run over four sizes of the
tests' own (`MIXED`) is `correct` on the dense rung, rows with holes, and
there a model that ignores memory is told too.

Entries are looked up by name and lists are stated as "what they had, then
this cell". What earlier tests pinned and this deployment made false (the
three pods modules of `benchmarks/pods/`, `class_only` for every cell, the
five plan lists, `runnable_tasks_scanned`'s cells, PR 46's six entries at
the end of `per_layer` with their cell alone) is an expected failure there
since this PR (tests/conftest.py); what stays true of each is held here."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import reference_requests as ref
from benchmarks import spec
from benchmarks.checks import allocatable, requests_fit
from benchmarks.pods import by_request
from benchmarks.traffic import build_plan
from ksched_tpu.cluster.api import PodEvent

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "k8s-5000-requests"
CELL = CONFIG + ".trickle"
WHAREMAP = "gtrace-12500-wharemap.trickle"
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "allocatable", "answer", "requests_fit"]
A, P = (4000, 32768), 110
SIZES = [(100, 500)]  # pod-default.yaml: the source's one pod
#: four sizes of the tests' own, for the runs that hold the checks to a mix
TEST_SIZES = [(100, 256), (250, 1024), (500, 1024), (250, 4096)]
BROUGHT = {
    "requests_costs_ms": ("span_sum", "graph update / export", "ms", "lower"),
    "books_machines_dirty": ("round_field", "graph update / export", "count", "lower"),
    "machines_gated": ("round_field", "solver dispatch", "count", "lower"),
    "columns_offered": ("round_field", "solver dispatch", "count", "higher"),
}
#: PR 46's six, which `test_benchmark_wharemap.py` pins to the end of `per_layer`
WHAREMAPS = ("collapse_rows", "collapse_cols", "audit_tasks_grouped", "census_machines_dirty",
             "ec_arcs_repriced", "platform_costs_ms")
PLAN = ("plan_rows", "plan_rows_live", "plan_refits", "plan_regrowths", "plan_relayouts")
APPENDED = PLAN + (
    "bind_tail_ms", "bindings_post_ms", "task_refresh_ms", "res_nodes_visited", "res_arcs_changed",
    "journal_collect_ms", "journal_apply_ms", "journal_changes", "problem_snapshot_ms",
    "ec_purge_ms", "ec_purges", "apply_nodes_visited", "apply_full_walks",
    "runnable_tasks_scanned", "ec_arcs_repriced",
)
#: the dense rung's lists: a one-row problem is answered on the host and reads none of them
DENSE = (
    "collapse_audit_ms", "transport_ms", "flow_reconstruct_ms", "audit_index_ms", "audit_pins_ms",
    "audit_subtrees_ms", "audit_task_arcs_ms", "audit_ec_routes_ms", "audit_escapes_ms",
    "audit_rows_ms", "collapse_rows", "collapse_cols", "audit_tasks_grouped",
)


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _entry(kind, name):
    return next(e for e in BENCH[kind] if e["name"] == name)


def _rehearse(trace, patch="", seconds="3"):
    """The cell's rehearsal in a process of its own, with the lines of
    `patch` executed before `benchmarks.run.main`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", seconds,
            "--trace", str(trace), "--rehearse-cpu"]
    entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
             "import benchmarks.run as run\n" + patch + "sys.exit(run.main())\n"]
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


# -- the files ------------------------------------------------------------------------


def test_the_configuration_is_the_sources_shapes():
    c = _config()
    assert c["argv"] == (
        "--fake-machines --num-machines 5000 --cores-per-machine 1 --pus-per-core 1 "
        "--max-tasks-per-pu 110 --fake-node-allocatable 4000:32768 --cost-model k8s_requests "
        "--backend jax --pod-batch-timeout 0.002 --pod-chan-size 154000"
    ).split()
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (150000, 1, 2500)
    assert (c["pods"], c["architecture"], c["reduced"]) == ("by_request", None, [])
    assert (tuple(c["node_allocatable"]), c["node_pod_limit"]) == (A, P)
    assert [tuple(r) for r in c["requests"]] == SIZES and len(SIZES) == c["task_classes"]
    # the file's vector is the flag's, its limit the slots the flags give a node
    argv = c["argv"]
    assert argv[argv.index("--fake-node-allocatable") + 1] == f"{A[0]}:{A[1]}"
    assert int(argv[argv.index("--max-tasks-per-pu") + 1]) == P
    # the source's one pod, at the 30 a node the large-cluster envelope gives 5,000 nodes:
    # 75% of the CPU and 45.8% of the memory reserved; CPU alone fills a node, at 40 pods
    assert c["resident_pods"] == 30 * 5000
    assert c["resident_pods"] * SIZES[0][0] / (5000 * A[0]) == 0.75
    assert c["resident_pods"] * SIZES[0][1] / (5000 * A[1]) == pytest.approx(0.4578, abs=1e-4)
    assert min(A[0] // SIZES[0][0], A[1] // SIZES[0][1], P) == 40
    # nothing of the pods is the builder's: the file says where each number is from
    kept = " ".join(c["kept_from_the_source"])
    assert "pod-default.yaml" in kept and "150,000" in kept and "node-default.yaml" in kept
    assert "pod-default.yaml (100m, 500Mi)" in c["source"] and "150,000 pods" in c["source"]
    # the constants the file states are the model's and the reference's
    from ksched_tpu.costmodels.k8s_requests import K8sRequestsCostModel

    assert c["policy"]["unscheduled_cost"] == K8sRequestsCostModel.UNSCHEDULED_COST == ref.UNSCHEDULED_COST
    assert list(c["guarantees"]) == GUARANTEES and "capacity" not in c["guarantees"]
    spec.check_guarantees(c, "the file")
    assert spec.check_pods(c, "the file") == "by_request"
    assert len(c["assumed"]) >= 8 and len(c["kept_from_the_source"]) >= 6
    e = _entry("configs", CONFIG)
    assert (e["file"], e["reduced"]) == ("benchmarks/configs/k8s-5000-requests.json", [])
    assert len(e["source"]) <= 200 and len(e["why"]) <= 200 and "scheduler_perf" in e["source"]
    assert BENCH["configs"][-1] is e  # appended: nothing that was there moved


def test_the_cell_takes_one_chip_and_the_mix_it_shares_is_unchanged():
    w = _entry("workloads", CELL)
    names = [e["name"] for e in BENCH["workloads"]]
    assert len(names) == len(set(names)) == 12 and names[-1] == CELL
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200 and "one size" in w["why"] and "100m, 500Mi" in w["why"]
    assert not any(e["chips"] == 4 for e in BENCH["workloads"])
    assert spec.check_names(BENCH) == []
    cell = spec.load_cell(CELL)
    assert cell.traffic == spec.load_cell("trivial-10kx1k.trickle").traffic
    assert {m["name"] for m in cell.end_to_end} == {"bind_p50_ms", "setup_s"}
    lists = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}
    assert lists == {
        "bind_p50_ms": None, "setup_s": None, "bind_p95_ms": ["trivial-10kx1k.trickle"],
        "bound_pods_per_s": ["trivial-10kx1k.waves", "coco-50kx1k.waves"],
    }
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} == everywhere | set(BROUGHT) | set(APPENDED)
    plan = build_plan(cell.traffic, cell.config, SEED, 40.0)
    assert len(plan.resident) == 150000 and {c for _p, c in plan.resident} == {0}
    assert plan.class_sweep == []  # one class: no burst to sweep
    for name in names:
        spec.load_cell(name)  # every cell loads
    # a one-row problem is answered on the host: the cell is on no list of the dense rung
    assert not {m["name"] for m in cell.per_layer} & set(DENSE)
    for name in DENSE:
        assert CELL not in _entry("per_layer", name)["workloads"]
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", APPENDED)
def test_a_list_it_joined_holds_what_it_held_and_this_cell_last(name):
    cells = _entry("per_layer", name)["workloads"]
    assert cells[-1] == CELL and cells.count(CELL) == 1 and len(cells) == len(set(cells))
    # the deployment before it is still there, as PR 42 and PR 46 left it
    assert cells[-2] == ("gtrace-12500-quincy.trickle" if name in PLAN else WHAREMAP)
    if name in PLAN:
        assert cells[:2] == ["trivial-10kx1k.trickle", "trivial-10kx1k.waves"]
    for w in BENCH["workloads"]:
        loaded = {m["name"] for m in spec.load_cell(w["name"]).per_layer}
        assert (name in loaded) == (w["name"] in cells)


@pytest.mark.parametrize("name", sorted(BROUGHT))
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell_alone(name):
    entry = _entry("per_layer", name)
    reader, layer, unit, better = BROUGHT[name]
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["better"], entry["layer"], entry["unit"]) == (
        "bind_p50_ms", better, layer, unit)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    others = [m for m in BENCH["per_layer"] if m["name"] not in BROUGHT]
    assert layer in {m["layer"] for m in others}  # a layer the benchmark names
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > max(names.index(m["name"]) for m in others)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert own["reader"] == reader and len(own["what"]) > 40  # a reader that was there
    assert name not in {m["name"] for m in spec.load_cell(WHAREMAP).per_layer}
    # on a program that has no such span or field (the parent) the reader finds nothing
    from benchmarks.observe import Observation, Round

    read = importlib.import_module(f"benchmarks.readers.{reader}").read
    parent = Observation(
        device_kind="cpu", rounds=[Round(0.0, 1.0, 3, True, {"round": 1.0, "ec_refresh": 0.2})],
        records=[{"num_scheduled": 3, "ec_arcs_changed": 1}], client={}, counters={}, shapes={},
    )
    assert read(own["params"], parent) is None
    change = Observation(
        device_kind="cpu",
        rounds=[Round(0.0, 1.0, 3, True, {"round": 1.0, "ec_refresh": 0.2, "requests_costs": 0.1})],
        records=[{"num_scheduled": 3, "books_machines_dirty": 7, "machines_gated": 5,
                  "columns_offered": 40}],
        client={}, counters={}, shapes={},
    )
    assert read(own["params"], change) in (0.1, 7.0, 5.0, 40.0)


@pytest.mark.parametrize("name", WHAREMAPS)
def test_what_stays_true_of_the_six_entries_before_them(name):
    # test_benchmark_wharemap.py pins each to its cell alone and to the end of `per_layer`
    entry = _entry("per_layer", name)
    assert entry["workloads"][0] == WHAREMAP
    assert entry["workloads"] == ([WHAREMAP, CELL] if name == "ec_arcs_repriced" else [WHAREMAP])
    names = [m["name"] for m in BENCH["per_layer"]]
    before = [n for n in names if n not in BROUGHT and n not in WHAREMAPS]
    assert max(names.index(n) for n in before) < names.index(name) < min(names.index(n) for n in BROUGHT)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }


# -- the pods module ------------------------------------------------------------------------


def test_a_pod_carries_the_requests_of_its_class_and_the_same_seed_draws_the_same_plan():
    cell = spec.load_cell(CELL)
    assert cell.pods == "by_request"
    for seed in (5, SEED):
        plan = build_plan(cell.traffic, cell.config, seed, 40.0)
        again = build_plan(cell.traffic, cell.config, seed, 40.0)
        assert plan.resident == again.resident and plan.victims == again.victims
        make_pod = spec.pod_maker(cell.pods, cell.config, seed)
        for pod_id, task_class in plan.resident[:200] + plan.closing + sum(plan.class_sweep, []):
            cpu, mem = SIZES[task_class]
            assert make_pod(pod_id, task_class) == PodEvent(
                pod_id=pod_id, task_class=task_class, cpu_request=cpu / 1000.0, memory_request=mem)
            assert by_request.make(pod_id, task_class, cell.config, seed) == make_pod(pod_id, task_class)
            ctx = SimpleNamespace(make_pod=make_pod)
            assert allocatable.pod_request(ctx, pod_id, task_class) == (cpu, mem)
    # the class is an index into the file's table, whatever its length
    mixed = {"requests": [list(size) for size in TEST_SIZES]}
    for task_class, (cpu, mem) in enumerate(TEST_SIZES):
        event = by_request.make("x", task_class, mixed, 0)
        assert (event.cpu_request, event.memory_request) == (cpu / 1000.0, mem)


@pytest.mark.parametrize("module", ["class_only", "by_role", "quincy_blocks", "by_request"])
def test_a_pods_module_stamps_each_event_as_it_is_made_and_draws_nothing_from_the_frameworks_rng(module):
    # the half of test_benchmark_quincy.py's test that stays true with a fourth module
    from ksched_tpu.utils import rng, seed_rng

    c = {**_config("k8s-5000-preemption"), **{k: _config("gtrace-12500-quincy")[k] for k in ("argv", "input")},
         "requests": [list(size) for size in TEST_SIZES[:2]]}
    seed_rng(77)
    state = rng().getstate()
    make = spec.pod_maker(module, c, 77)
    events = [make(f"p{i}", i % 2) for i in range(300)]
    assert rng().getstate() == state  # one draw would shift every task and job id of the run
    stamps = [e.received_s for e in events]
    assert stamps == sorted(stamps) and stamps[0] < stamps[-1]  # made at submission, not ahead
    assert sorted(f.name for f in os.scandir(os.path.join(spec.HERE, "pods")) if f.is_file()) == [
        "by_request.py", "by_role.py", "class_only.py", "quincy_blocks.py",
    ]


# -- the rehearsal --------------------------------------------------------------------------


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    shapes = out["facts"]["shapes"]
    assert (shapes["machines"], shapes["task_classes"], shapes["path"]) == (125, 1, "csr")
    fit = out["facts"]["requests_fit"]
    # (how many rounds a 3 s window holds is the host's speed: ~100 on the scan-CSR rung here)
    assert fit["rounds_compared"] == fit["rounds"] > 30 and fit["rounds_costing_zero"] == 0
    assert fit["served_cost"] == fit["optimum_cost"] > fit["served_cost_after_fill"] > 0
    # the fill is one round: 3,750 pods onto 125 x 40 places, every node at the same price
    assert fit["rounds_that_left_pods"] == 0 and fit["largest_round"] == 3750
    assert fit["r_max"] == list(SIZES[0]) and fit["nodes"] == 125
    assert fit["peak_cpu"] <= A[0] and fit["peak_mem"] <= A[1] and fit["peak_pods"] <= 40
    # when the bound acts: in the fill (nodes filled to cap(m)), hardly ever after it
    assert fit["columns_saturated"] - fit["columns_saturated_after_fill"] >= 90
    assert fit["rounds_saturating_after_fill"] <= fit["rounds"] // 10
    assert 70 < fit["reserved_cpu_percent"] < 80 and 40 < fit["reserved_mem_percent"] < 50
    held = out["facts"]["allocatable"]
    assert held["limits"] == [A[0], A[1], P] and held["replayed"] == fit["replayed"]
    assert held["peak"][0] <= A[0] and held["peak"][1] <= A[1] and held["peak"][2] <= 40
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    cell = spec.load_cell(CELL)
    missing = {m["name"] for m in cell.per_layer} - set(metrics)
    # no device op on the CPU's trace for the roofline
    assert missing <= {"solve_roofline", "solve_device_ms"}, missing
    for name in BROUGHT:
        assert metrics[name] >= 0
    assert metrics["supersteps_p50"] > 0 and metrics["plan_rows"] > 0
    assert metrics["res_nodes_visited"] == 0 and metrics["stats_full_walks"] == 0
    assert metrics["unscheduled_by_rule"] == 0 and metrics["compiles_in_window"] == 0
    assert 0 < metrics["books_machines_dirty"] <= 2 * metrics["batch_pods_p50"] + 2
    assert 0 < metrics["columns_offered"] <= 125 * 40 - 3750 + 2 * metrics["batch_pods_p50"] + 2
    assert 0 <= metrics["machines_gated"] <= 125
    # one EC patches the machines whose books moved
    assert metrics["ec_arcs_changed"] <= metrics["ec_arcs_repriced"] <= 125
    assert out["facts"]["rounds"]["solved"] > 30


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES and out["facts"]["shapes"]["path"] == "csr"


# -- four sizes of the tests' own, through the same harness: rows with holes ---------------------

#: the rehearsal's configuration with the tests' four sizes, 1,275 pods and the dense rung
MIXED = (
    "import benchmarks.spec as spec\n"
    "shrink = spec.rehearsal_config\n"
    "def mixed(config):\n"
    "    out = shrink(config)\n"
    f"    out['requests'] = {[list(size) for size in TEST_SIZES]!r}\n"
    "    out['task_classes'], out['resident_pods'] = 4, 1275\n"
    "    out['argv'] = ['auto' if a == 'jax' else a for a in out['argv']]\n"
    "    return out\n"
    "spec.rehearsal_config = mixed\n"
)


def test_a_rehearsal_over_four_sizes_is_correct_on_the_dense_rung():
    out = _rehearse(1, patch=MIXED)
    assert out["correct"] is True, out["facts"]["faults"]
    shapes = out["facts"]["shapes"]
    assert (shapes["machines"], shapes["task_classes"], shapes["path"]) == (125, 4, "dense")
    fit = out["facts"]["requests_fit"]
    assert fit["rounds_compared"] == fit["rounds"] > 100 and fit["rounds_costing_zero"] == 0
    assert fit["served_cost"] == fit["optimum_cost"] > fit["served_cost_after_fill"] > 0
    # k(m) by r_max cuts the offer: the fill takes two rounds, 8 a node and then the rest
    assert fit["rounds_that_left_pods"] >= 1 and fit["largest_round"] == 125 * 8
    assert fit["r_max"] == [500, 4096]
    assert fit["peak_cpu"] <= A[0] and fit["peak_mem"] <= A[1] and fit["peak_pods"] <= P
    assert 60 < fit["reserved_cpu_percent"] < 80


# -- controls: a planted fault turns `correct` false ------------------------------------------

#: the model reads no memory request: its books hold CPU alone
NO_MEMORY = (
    "from ksched_tpu.costmodels import k8s_requests\n"
    "read = k8s_requests.request_of\n"
    "k8s_requests.request_of = lambda td: (read(td)[0], 0)\n"
)
#: k(m) one too large: a node takes one pod more a round than fit together
ONE_TOO_MANY = (
    "from ksched_tpu.costmodels import k8s_requests\n"
    "count = k8s_requests.fit_count\n"
    "k8s_requests.fit_count = lambda *a: count(*a) + 1\n"
)
#: the cluster hears, for one pod of a round after the fill, the costliest node that still
#: had an arc for it instead of the one the service chose
MOVED = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "from ksched_tpu.costmodels import k8s_requests\n"
    "post = client.BenchClusterAPI.assign_bindings\n"
    "moved = []\n"
    "def assign_bindings(self, bindings):\n"
    "    if not moved and len(self.log) > 1300 and len(bindings) <= 8:\n"
    "        svc, b = self.svc, bindings[0]\n"
    "        model = svc.scheduler.cost_model\n"
    "        request = k8s_requests.request_of(svc.task_map.find(svc.pod_to_task[b.pod_id]))\n"
    "        rids = [r for r in model._row if r != svc.node_to_machine[b.node_id]]\n"
    "        costs, caps = model.ec_to_resource_batch(k8s_requests.request_ec(request), rids)\n"
    "        cost, rid = max((c, r) for c, cap, r in zip(costs, caps, rids) if cap > 0)\n"
    "        moved.append(b.pod_id)\n"
    "        bindings = [Binding(pod_id=b.pod_id, node_id=svc.machine_to_node[rid])] + list(bindings[1:])\n"
    "    post(self, bindings)\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)
#: node 3 is built with twice the CPU the file gives a node
WRONG_VECTOR = (
    "import dataclasses\n"
    "from ksched_tpu import cli\n"
    "add = cli.SchedulerService.add_node\n"
    "def add_node(self, node):\n"
    "    if node.node_id == 'fake_node_3':\n"
    "        node = dataclasses.replace(node, cpu_allocatable_millis=8000)\n"
    "    add(self, node)\n"
    "cli.SchedulerService.add_node = add_node\n"
)


@pytest.mark.parametrize("patch, word", [
    (ONE_TOO_MANY, "received 41 pods in one round, cap(m) was 40"),
    (MOVED.replace("1300", "3800"), "the optimum of the round is"),
    (WRONG_VECTOR, "node fake_node_3: allocatable (8000, 32768) on the service, (4000, 32768) in the file"),
    (MIXED + NO_MEMORY, "requests do not fit"),
    (MIXED + ONE_TOO_MANY, "received 9 pods in one round, cap(m) was 8"),
    (MIXED + MOVED, "the optimum of the round is"),
], ids=["a-model-with-k-one-too-large", "a-binding-moved-to-a-costlier-node-that-had-room",
        "a-node-built-with-another-vector", "four-sizes-a-model-that-ignores-memory",
        "four-sizes-a-model-with-k-one-too-large", "four-sizes-a-binding-moved"])
def test_a_run_with_a_planted_fault_prints_correct_false_and_the_fault(patch, word):
    out = _rehearse(0, patch=patch)
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    faults = [f for f in out["facts"]["faults"] if word in f]
    assert faults, out["facts"]["faults"]
    assert '"correct": false' in out["stderr_tail"] and word in out["stderr_tail"]


# -- the checks on a run built by hand -----------------------------------------------------------


def _ctx(log, classes, machines=4, polls=(), held=None, slots=P):
    """Four nodes of the file's vector; `classes` pod -> size class in submission order."""
    nodes = {f"fake_node_{i}": 100 + i for i in range(machines)}
    status = {
        100 + i: SimpleNamespace(
            descriptor=SimpleNamespace(capacity=SimpleNamespace(
                cpu_cores=(held or {}).get(i, A)[0] / 1000.0, ram_cap=(held or {}).get(i, A)[1])),
            topology_node=SimpleNamespace(children=[SimpleNamespace(children=[object()])]),
        ) for i in range(machines)
    }
    config = {"node_allocatable": list(A), "node_pod_limit": P, "requests": [list(s) for s in TEST_SIZES]}
    plan = SimpleNamespace(
        resident=list(classes.items()), closing=[], class_sweep=[], arrival_classes=None, wave_pods=0,
    )
    return SimpleNamespace(
        config=config, plan=plan, log=log, facts={},
        make_pod=lambda pod, c: by_request.make(pod, c, config, 0),
        svc=SimpleNamespace(node_to_machine=nodes, resource_map=SimpleNamespace(find=status.get),
                            api=SimpleNamespace(polls=list(polls))),
        svc_args=SimpleNamespace(num_machines=machines, max_tasks_per_pu=slots),
    )


def test_allocatable_holds_a_node_to_its_three_limits():
    bind = lambda pods, node, t: [("bind", p, node, t) for p in pods]  # noqa: E731
    eight = {f"c{i}": 2 for i in range(9)}  # 500m each
    assert allocatable.check(_ctx(bind(list(eight)[:8], "fake_node_0", 1.0), eight)) == []
    over = allocatable.check(_ctx(bind(list(eight), "fake_node_0", 1.0), eight))
    assert len(over) == 1 and "holds 4500m, 9216 MiB in 9 pods" in over[0]
    # a completion frees the requests at once: the ninth fits after it
    log = bind(list(eight)[:8], "fake_node_0", 1.0) + [("done", "c0", "", 2.0)] + bind(["c8"], "fake_node_0", 3.0)
    ctx = _ctx(log, eight)
    assert allocatable.check(ctx) == [] and ctx.facts["allocatable"]["peak"] == [4000, 8192, 8]
    memory = {f"m{i}": 3 for i in range(9)}  # 4 GiB each
    over = allocatable.check(_ctx(bind(list(memory), "fake_node_1", 1.0), memory))
    assert len(over) == 1 and "36864 MiB" in over[0]
    small = {f"s{i}": 0 for i in range(41)}  # 100m each: CPU allows 40
    over = allocatable.check(_ctx(bind(list(small), "fake_node_2", 1.0), small))
    assert len(over) == 1 and "4100m" in over[0]
    assert "without a Binding" in allocatable.check(_ctx([("done", "s0", "", 1.0)], small))[0]
    assert "served without preemption" in allocatable.check(_ctx([("evict", "s0", "fake_node_0", 1.0)], small))[0]
    # the service's descriptors are cross-checked against the file
    other = allocatable.check(_ctx([], small, held={2: (4000, 65536)}))
    assert other == ["node fake_node_2: allocatable (4000, 65536) on the service, (4000, 32768) in the file"]
    fewer = allocatable.check(_ctx([], small, slots=100))
    assert "100 slots on the service, a pod limit of 110" in fewer[0]


def _replay(log, classes, polls, machines=4):
    ctx = _ctx(log, classes, machines=machines, polls=polls)
    return requests_fit.check(ctx), ctx.facts["requests_fit"]


def test_requests_fit_on_a_run_built_by_hand_and_three_faults():
    # four empty nodes; one round of a 500m pod and a 4 GiB pod: any two nodes, 11 + 12
    classes = {"a": 2, "b": 3}
    polls = [(0.0, 0.5, 2)]
    good = [("bind", "a", "fake_node_0", 1.0), ("bind", "b", "fake_node_1", 1.0)]
    faults, facts = _replay(good, classes, polls)
    assert faults == [] and (facts["served_cost"], facts["optimum_cost"]) == (23, 23)
    assert facts["r_max"] == [500, 4096] and facts["rounds_compared"] == 1
    # a second round prices on the books the first left: node 0 holds 500m / 1 GiB now
    classes2 = {**classes, "c": 2}
    polls2 = polls + [(1.5, 1.6, 1)]
    onto_an_empty = good + [("bind", "c", "fake_node_2", 2.0)]
    onto_the_used = good + [("bind", "c", "fake_node_0", 2.0)]
    assert _replay(onto_an_empty, classes2, polls2)[0] == []
    faults, facts = _replay(onto_the_used, classes2, polls2)
    # u = (25, 6): 15 + 9 = 24 against 11 on an empty node
    assert len(faults) == 1 and "Bindings cost 24" in faults[0] and "optimum of the round is 11" in faults[0]
    # (a) more than cap(m) in a round: nine 500m pods onto one empty node, r_max (500, 1024)
    nine = {f"n{i}": 2 for i in range(9)}
    faults, _f = _replay([("bind", p, "fake_node_0", 1.0) for p in nine], nine, [(0.0, 0.5, 9)])
    assert any("received 9 pods in one round, cap(m) was 8" in f for f in faults)
    # (a) no arc: a ninth 500m pod onto a node that eight of them fill
    ten = {f"n{i}": 2 for i in range(10)}
    fill = [("bind", f"n{i}", "fake_node_0", 1.0) for i in range(8)]
    faults, _f = _replay(fill + [("bind", "n8", "fake_node_0", 2.0), ("bind", "n9", "fake_node_1", 2.0)],
                         ten, [(0.0, 0.5, 8), (1.5, 1.6, 2)])
    assert any("no arc" in f and "pod n8" in f for f in faults)
    # (c) a pod waits while a node has an arc for it and room
    faults, facts = _replay([("bind", "a", "fake_node_0", 1.0)], classes, polls)
    assert any("pod b" in f and "waited while node" in f for f in faults)
    assert facts["rounds_that_left_pods"] == 1
    # a completed pod holds its place through the next round's solve and leaves after it
    done = good + [("done", "a", "", 1.5), ("bind", "c", "fake_node_2", 2.0), ("bind", "d", "fake_node_0", 3.0)]
    faults, facts = _replay(done, {**classes2, "d": 2}, polls2 + [(2.5, 2.6, 1)])
    assert faults == [] and facts["served_cost"] == 23 + 11 + 11  # node 0 is empty again for d


def test_the_submission_order_is_the_plans():
    plan = SimpleNamespace(
        resident=[("r0", 0), ("r1", 1)], class_sweep=[[("s1_0", 0)], [("s2_0", 0), ("s2_1", 1)]],
        closing=[("c0", 0)], arrival_classes=[0, 1, 2, 3], arrival=lambda i: (f"p{i}", i),
    )
    ctx = SimpleNamespace(plan=plan)
    assert requests_fit.submission_order(ctx, 9) == ["r0", "r1", "s1_0", "s2_0", "s2_1", "p0", "p1", "p2", "c0"]
    assert requests_fit.submission_order(ctx, 6) == ["r0", "r1", "s1_0", "s2_0", "s2_1", "c0"]


def test_the_reference_round_is_the_optimum_with_holes_and_its_two_reductions_change_nothing():
    import numpy as np

    rng = np.random.default_rng(4)
    for _ in range(30):
        m, s = int(rng.integers(3, 40)), int(rng.integers(1, 5))
        cost = rng.integers(0, 150, (s, m))
        open_cell = rng.random((s, m)) < 0.7
        cap = rng.integers(0, 4, m)
        supply = rng.integers(0, 6, s)
        got = ref.reference_round(cost, open_cell, cap, supply)
        # the plain problem, no reduction: every column, holes at HOLE
        plain = ref.transport(np.where(open_cell & (cap > 0)[None, :], cost, ref.HOLE), supply, cap,
                              ref.UNSCHEDULED_COST)
        assert got == plain
    # by hand: two pods of one size, columns (5, cap 1), (9, cap 1), a hole: 14; a third pod waits
    cost = np.array([[5, 9, 1]])
    assert ref.reference_round(cost, np.array([[True, True, False]]), np.array([1, 1, 5]), [2]) == 14
    assert ref.reference_round(cost, np.array([[True, True, False]]), np.array([1, 1, 5]), [3]) == 514


def test_the_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(ROOT, "benchmarks", "reference_requests.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "typing", "numpy"}
