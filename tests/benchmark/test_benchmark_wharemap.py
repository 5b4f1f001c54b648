"""The `gtrace-12500-wharemap` deployment and its cell: the configuration is
BASELINE.json `configs[3]` (the Whare-Map cost model on a heterogeneous
resource topology) on the Google-2011 cluster's machine mix, the type table
deals every prefix of the cluster its shares, the cell rehearses `correct`
at 1/40 scale (312 machines: 1 A, 284 B, 27 C; 3,550 resident pods) traced
and untraced, every round on the dense rung, the six per-layer metrics this
deployment brings read a number there, and the two checks it brings tell: a
seventh pod on an A node, a Binding swapped for a dearer one, a node whose
label is another platform than its type, and a model with another
PLATFORM_PRIOR each turn `correct` false with the fault.

Entries are looked up by name and lists are stated as "what they had, then
this cell". What earlier tests pinned and this deployment made false (the
last place of `configs`, the dense-rung lists of the two `coco-50kx1k`
cells, nine cells on `runnable_tasks_scanned`, no configuration that names
`pods`) is an expected failure there since this PR (tests/conftest.py); what
stays true of each is held here."""

import hashlib
import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import reference_wharemap as ref
from benchmarks import spec
from benchmarks.checks import capacity_by_type, interference_map
from benchmarks.traffic import build_plan

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "gtrace-12500-wharemap"
CELL = CONFIG + ".trickle"
COCO = ["coco-50kx1k.trickle", "coco-50kx1k.waves"]
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity_by_type", "answer", "interference_map"]
TABLE = [("A", 1, 10), ("B", 2, 930), ("C", 4, 60)]
BROUGHT = {
    "collapse_rows": ("round_field", "solver dispatch", "rows"),
    "collapse_cols": ("round_field", "solver dispatch", "cols"),
    "audit_tasks_grouped": ("round_field", "solver dispatch", "count"),
    "census_machines_dirty": ("round_field", "graph update / export", "count"),
    "ec_arcs_repriced": ("round_field", "graph update / export", "count"),
    "platform_costs_ms": ("span_sum", "graph update / export", "ms"),
}
DENSE = (
    "collapse_audit_ms", "transport_ms", "flow_reconstruct_ms", "audit_index_ms", "audit_pins_ms",
    "audit_subtrees_ms", "audit_task_arcs_ms", "audit_ec_routes_ms", "audit_escapes_ms",
    "audit_rows_ms",
)
APPENDED = DENSE + (
    "bind_tail_ms", "bindings_post_ms", "task_refresh_ms", "res_nodes_visited", "res_arcs_changed",
    "journal_collect_ms", "journal_apply_ms", "journal_changes", "problem_snapshot_ms",
    "ec_purge_ms", "ec_purges", "apply_nodes_visited", "apply_full_walks",
    "runnable_tasks_scanned",
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
        "--fake-machines --num-machines 12500 --pus-per-core 2 --max-tasks-per-pu 3 "
        "--fake-machine-types A:1:10,B:2:930,C:4:60 --cost-model whare --backend auto "
        "--pod-batch-timeout 0.002 --pod-chan-size 150000"
    ).split()
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (142000, 4, 5000)
    assert (c["pods"], c["architecture"]) == ("class_only", None)
    # the table of the file is the table of the flag
    assert [tuple(t) for t in c["machine_types"]] == TABLE
    assert c["argv"][c["argv"].index("--fake-machine-types") + 1] == ",".join(
        f"{n}:{cores}:{share}" for n, cores, share in TABLE)
    # 121 / 11,623 / 756 machines of 6 / 12 / 24 slots: 158,346, nine tenths of them resident
    slots = sum(ref.node_shape(f"fake_node_{i}", TABLE, 2, 3)[1] for i in range(12500))
    assert slots == 121 * 6 + 11623 * 12 + 756 * 24 == 158346
    assert 0.89 < c["resident_pods"] / slots < 0.90
    # the policy's numbers as the file states them are the reference's and the model's
    from ksched_tpu.costmodels import whare

    assert c["policy"] == {
        "classes": list(ref.CLASSES), "platforms": list(ref.PLATFORMS),
        "psi_prior": [list(r) for r in ref.PSI_PRIOR],
        "platform_prior": [list(r) for r in ref.PLATFORM_PRIOR],
        "idle_bonus": ref.IDLE_BONUS, "max_cost": ref.MAX_COST,
        "unscheduled_cost": ref.UNSCHEDULED_COST,
        "co_runners": list(ref.CLASSES) + ["alone"],
    } == {
        "classes": ["sheep", "rabbit", "devil", "turtle"], "platforms": list(whare.PLATFORMS),
        "psi_prior": whare.PSI_PRIOR.tolist(), "platform_prior": whare.PLATFORM_PRIOR.tolist(),
        "idle_bonus": whare.IDLE_BONUS, "max_cost": whare.MAX_COST,
        "unscheduled_cost": whare.UNSCHEDULED_COST,
        "co_runners": ["sheep", "rabbit", "devil", "turtle", "alone"],
    }
    assert (ref.ALONE, whare.ALONE) == (4, 4) and c["policy"]["co_runners"][ref.ALONE] == "alone"
    assert any("ALONE" in a and "ISSUE 46's equation" in a for a in c["assumed"])
    assert c["reduced"] == [] and "one chip holds the cluster whole" in c["why_nothing_is_reduced"]
    assert len(c["kept_from_the_source"]) >= 5 and len(c["assumed"]) >= 10
    assert "remembered, not confirmed" in c["assumed"][0]
    assert any("stays at its prior" in a for a in c["assumed"])  # no runtimes are reported
    assert any("Ours" in a and "PLATFORM_PRIOR" in a for a in c["assumed"])
    assert list(c["guarantees"]) == GUARANTEES
    others = _config("coco-50kx1k")["guarantees"]
    assert all(c["guarantees"][k] == others[k] for k in ("binding", "answer"))
    entry = _entry("configs", CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("BASELINE.json configs[3]", "Whare-Map cost model", "whare_map_stats.proto",
                 "heterogeneous resource topology", "ISCA'13", "SoCC'12", "table 1"):
        assert word in entry["source"] and word in c["source"], word
    assert sum(1 for e in BENCH["configs"] if e["source"] == entry["source"]) == 1
    assert sum(1 for e in BENCH["configs"] if e["file"] == entry["file"]) == 1
    assert "." not in CONFIG  # a cell's name is split at every "."
    spec.check_guarantees(c, entry["file"])
    assert spec.check_pods(c, entry["file"]) == "class_only"
    assert interference_map.PLATFORM_LABEL == importlib.import_module("ksched_tpu.data").PLATFORM_LABEL


def test_the_cell_takes_one_chip_and_the_mix_it_shares_is_unchanged():
    w = _entry("workloads", CELL)
    names = [e["name"] for e in BENCH["workloads"]]
    assert len(names) == len(set(names)) and names.count(CELL) == 1
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200 and "3 types" in w["why"]
    assert not any(e["chips"] == 4 for e in BENCH["workloads"])
    assert spec.check_names(BENCH) == []
    cell = spec.load_cell(CELL)
    mix = cell.traffic
    assert mix == spec.load_cell("trivial-10kx1k.trickle").traffic
    assert (mix["kind"], mix["rate_per_s"], mix["completions_per_arrival"], mix["warmup_s"]) == (
        "open_poisson", 100.0, 1, 3.0,
    )
    assert {m["name"] for m in cell.end_to_end} == {"bind_p50_ms", "setup_s"}
    # the lists of the other end-to-end metrics are as they were
    lists = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}
    assert lists == {
        "bind_p50_ms": None, "setup_s": None, "bind_p95_ms": ["trivial-10kx1k.trickle"],
        "bound_pods_per_s": ["trivial-10kx1k.waves", "coco-50kx1k.waves"],
    }
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} >= everywhere | set(BROUGHT) | set(APPENDED)
    # no slot plan under --backend auto, no chain arcs, no preemption: not on those lists
    absent = {"plan_rows", "plan_refits", "ec_chain_refresh_ms", "pref_refresh_ms", "pods_evicted",
              "upload_ms", "spread_fallback_rounds", "remote_bytes_share"}
    assert not absent & {m["name"] for m in cell.per_layer}
    plan = build_plan(mix, cell.config, SEED, 40.0)
    assert len(plan.resident) == 142000 and len(plan.closing) == 100
    # four classes: three synchronous bursts of 1, 2 and 3 classes warm the dense shapes up
    assert [len({c for _p, c in burst}) for burst in plan.class_sweep] == [1, 2, 3]
    for name in names:
        spec.load_cell(name)  # every cell loads


@pytest.mark.parametrize("name", APPENDED)
def test_a_list_it_joined_holds_what_it_held_and_this_cell_once(name):
    cells = _entry("per_layer", name)["workloads"]
    assert cells.count(CELL) == 1 and len(cells) == len(set(cells))
    before = cells[: cells.index(CELL)]
    if name in DENSE:
        # test_benchmark_layer_spans.py and test_benchmark_pass_metrics.py pin these ten to
        # the two coco-50kx1k cells: they come first, as they did
        assert before == COCO
    elif name == "runnable_tasks_scanned":
        from test_benchmark_runnable_scan import CELLS as ITS_CELLS

        assert before == ITS_CELLS and "k8s-5000-preemption.rollout" not in cells
    else:
        assert set(COCO) <= set(before) and "gtrace-12500-quincy.trickle" in before
    # every cell on the list loads the metric by name, and no other cell does
    for w in BENCH["workloads"]:
        loaded = {m["name"] for m in spec.load_cell(w["name"]).per_layer}
        assert (name in loaded) == (w["name"] in cells)


def test_what_stays_true_of_the_configurations_before_it():
    # test_benchmark_quincy.py pins its configuration to the last place; it is followed now
    names = [e["name"] for e in BENCH["configs"]]
    assert names.index("gtrace-12500-quincy") == names.index(CONFIG) - 1
    assert names.index("k8s-5000-preemption") == names.index("gtrace-12500-quincy") - 1
    cells = [e["name"] for e in BENCH["workloads"]]
    assert cells.index("gtrace-12500-quincy.trickle") == cells.index(CELL) - 1
    assert _entry("configs", "gtrace-12500-quincy")["file"] == "benchmarks/configs/gtrace-12500-quincy.json"
    # every configuration is some cell's, every file one configuration's
    assert {w["config"] for w in BENCH["workloads"]} == set(names)
    assert len({e["file"] for e in BENCH["configs"]}) == len(names)


def test_class_only_by_name_is_class_only_by_default_and_the_same_seed_draws_the_same_plan():
    # test_benchmark_seams.py states that no configuration names `pods`; this one names the
    # default, and what a pod of it carries is what every `class_only` pod carries
    from benchmarks.pods import class_only
    from ksched_tpu.cluster.api import PodEvent

    cell = spec.load_cell(CELL)
    assert cell.pods == spec.DEFAULT_PODS == "class_only"
    for seed, want in ((5, "f1a9e94888cba1d4"), (SEED, "27fb8f28262cd63d")):
        plan = build_plan(cell.traffic, cell.config, seed, 40.0)
        again = build_plan(cell.traffic, cell.config, seed, 40.0)
        digest = hashlib.sha256(repr((
            plan.resident[:300], plan.victims[:300], plan.closing, plan.class_sweep,
            plan.arrival_offsets_s[:300].tolist(), plan.arrival_classes[:300].tolist(),
        )).encode()).hexdigest()[:16]
        assert digest == want, digest  # the same bytes on every machine and in every session
        assert plan.resident == again.resident and plan.victims == again.victims
        make_pod = spec.pod_maker(cell.pods, cell.config, seed)
        for pod_id, task_class in plan.resident[:200] + plan.closing + sum(plan.class_sweep, []):
            assert make_pod(pod_id, task_class) == PodEvent(pod_id=pod_id, task_class=task_class)
            assert class_only.make(pod_id, task_class, cell.config, seed) == make_pod(pod_id, task_class)
        assert {c for _p, c in plan.resident} == {0, 1, 2, 3}


@pytest.mark.parametrize("name", sorted(BROUGHT))
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell_alone(name):
    entry = _entry("per_layer", name)
    assert entry["workloads"] == [CELL]
    reader, layer, unit = BROUGHT[name]
    assert (entry["moves"], entry["better"], entry["layer"], entry["unit"]) == (
        "bind_p50_ms", "lower", layer, unit)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    others = [m for m in BENCH["per_layer"] if m["name"] not in BROUGHT]
    assert layer in {m["layer"] for m in others}  # a layer the benchmark names
    # it came after every entry that was there
    position = [m["name"] for m in BENCH["per_layer"]].index(name)
    assert position > max(i for i, m in enumerate(BENCH["per_layer"]) if m["name"] not in BROUGHT)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert own["reader"] == reader and len(own["what"]) > 40  # a reader that was there
    assert name not in {m["name"] for m in spec.load_cell("coco-50kx1k.trickle").per_layer}
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
        rounds=[Round(0.0, 1.0, 3, True, {"round": 1.0, "ec_refresh": 0.2, "platform_costs": 0.1})],
        records=[{"num_scheduled": 3, **{k: 7 for k in BROUGHT}}], client={}, counters={}, shapes={},
    )
    assert read(own["params"], change) == (0.1 if name == "platform_costs_ms" else 7.0)


# -- the type table ----------------------------------------------------------------------


def test_the_type_dealing_gives_every_prefix_its_shares():
    from collections import Counter

    names = [ref.machine_type(i, TABLE)[0] for i in range(12500)]
    assert Counter(names) == {"A": 121, "B": 11623, "C": 756}
    assert Counter(names[:312]) == {"A": 1, "B": 284, "C": 27}  # the 1/40 rehearsal keeps all three
    # every thousand consecutive nodes hold exactly 10 / 930 / 60, wherever the thousand starts
    for start in (0, 1, 137, 999, 4321, 11500):
        assert Counter(names[start:start + 1000]) == {"A": 10, "B": 930, "C": 60}
    # every prefix holds its shares to within ten machines (9.56 at the worst)
    seen = Counter()
    for n, name in enumerate(names, 1):
        seen[name] += 1
        for t, _cores, share in TABLE:
            assert abs(seen[t] - n * share / 1000) < 10, (n, t, seen[t])
    # a type is a pure function of the index, and the types are spread, not in runs
    assert names[0] == "A" and names[8] == "C" and set(names[1:8]) == {"B"}
    assert max(len(list(g)) for k, g in __import__("itertools").groupby(names) if k == "C") == 1
    with pytest.raises(ValueError):
        ref.machine_type(3, [("A", 1, 500)])  # shares that do not sum to 1000
    # a name that is no platform is the neutral one
    assert ref.node_shape("fake_node_0", [("Z", 3, 1000)], 2, 3) == (ref.NEUTRAL, 18)


# -- the rehearsal --------------------------------------------------------------------------


def test_the_rehearsal_is_the_fortieth_with_all_three_types(traced):
    r = spec.rehearsal_config(_config())
    assert r["argv"][r["argv"].index("--num-machines") + 1] == "312"
    assert r["argv"][r["argv"].index("--fake-machine-types") + 1] == "A:1:10,B:2:930,C:4:60"
    assert (r["resident_pods"], r["wave_pods"]) == (3550, 125)
    shapes = traced["facts"]["shapes"]
    assert (shapes["machines"], shapes["task_classes"], shapes["path"]) == (312, 4, "dense")
    replay = traced["facts"]["interference_map"]
    assert replay["nodes_by_platform"] == [1, 284, 27] and replay["slots"] == 6 + 284 * 12 + 27 * 24


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["interference_map"]
    assert replay["replayed"] == out["facts"]["capacity_by_type"]["replayed"] > 3550 + 400
    assert replay["rounds_compared"] == replay["rounds"] > 20 and replay["rounds_short_of_room"] == 0
    assert replay["served_cost"] == replay["optimum_cost"] > 0
    assert replay["largest_round"] == 3550 and replay["pods_left_waiting_at_most"] == 0
    assert sum(map(sum, replay["bound_by_class_and_platform"])) == replay["pods_bound"]
    assert replay["polls"] > replay["rounds"]
    # no node of any size was ever over its own capacity, and the largest were filled
    peaks = out["facts"]["capacity_by_type"]["peak_load_by_capacity"]
    assert set(peaks) <= {"6", "12", "24"} and all(v <= int(k) for k, v in peaks.items())
    assert peaks["12"] == 12
    assert out["stderr_tail"].startswith('correct: {"correct": true')
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in (everywhere - {"solve_roofline"}) | set(BROUGHT) | set(APPENDED):
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "backend_solve_ms", "collapse_audit_ms", "transport_ms",
                 "audit_pins_ms", "audit_rows_ms", "ec_refresh_ms", "platform_costs_ms",
                 "ec_arcs_repriced", "census_machines_dirty", "audit_tasks_grouped"):
        assert metrics[name] > 0.0, name
    # the dense problem as solved: at most a row a class, 312 machines and the unscheduled
    # column padded to 384; the tasks the rows pass grouped are the round's batch
    assert 1.0 <= metrics["collapse_rows"] <= 4.0 and metrics["collapse_cols"] == 384.0
    assert metrics["audit_tasks_grouped"] == metrics["decode_tasks"] >= metrics["collapse_rows"]
    # every class EC of the batch sweeps every machine; few of those arcs change
    assert metrics["ec_arcs_repriced"] % 312 == 0 and metrics["ec_arcs_repriced"] <= 4 * 312
    assert metrics["ec_arcs_changed"] < metrics["ec_arcs_repriced"]
    assert metrics["census_machines_dirty"] <= 2 * metrics["batch_pods_p50"] + 2
    # the span lies inside the EC sweep
    assert metrics["platform_costs_ms"] < metrics["ec_refresh_ms"]
    # the guards of PRs 25-36, read in the new cell: no resource turn, no full walk
    assert metrics["res_nodes_visited"] == 0.0 and metrics["res_arcs_changed"] == 0.0
    assert metrics["stats_full_walks"] == 0.0 and metrics["apply_full_walks"] == 0.0
    assert metrics["unscheduled_by_rule"] == 0.0


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES and out["facts"]["shapes"]["path"] == "dense"


# -- controls: a planted fault turns `correct` false ------------------------------------------

#: the model's platform prior with its columns swapped: the newest platform slower, the
#: oldest faster
WRONG_PRIOR = (
    "from ksched_tpu.costmodels import whare\n"
    "whare.PLATFORM_PRIOR = whare.PLATFORM_PRIOR[:, ::-1].copy()\n"
)
#: node 8, a C by its index (24 slots), is built and labelled as a B (12 slots)
WRONG_TYPE = (
    "from ksched_tpu import cli\n"
    "deal = cli.machine_type_of\n"
    "cli.machine_type_of = lambda i, types: types[1] if i == 8 else deal(i, types)\n"
)
#: the cluster hears, for the first seven pods the service binds anywhere, node 0 (the one
#: A, six slots, the dearest node of the cluster and so the last the service would fill): a
#: seventh pod on an A node
SEVENTH_POD = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "post = client.BenchClusterAPI.assign_bindings\n"
    "moved = []\n"
    "def assign_bindings(self, bindings):\n"
    "    out = []\n"
    "    for b in bindings:\n"
    "        if len(moved) < 7:\n"
    "            moved.append(b.pod_id)\n"
    "            b = Binding(pod_id=b.pod_id, node_id='fake_node_0')\n"
    "        out.append(b)\n"
    "    post(self, out)\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)


@pytest.mark.parametrize("patch, word", [
    (WRONG_PRIOR, "by the interference map, the optimum of the round is"),
    (WRONG_TYPE, "node fake_node_8: type C by its index, label 'B' on the service"),
    (SEVENTH_POD, "node fake_node_0 held 7 pods, its own capacity is 6"),
], ids=["a-model-with-another-platform-prior", "a-node-built-as-another-type",
        "a-seventh-pod-on-an-a-node"])
def test_a_run_with_a_planted_fault_prints_correct_false_and_the_fault(patch, word):
    out = _rehearse(0, patch=patch)
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    faults = [f for f in out["facts"]["faults"] if word in f]
    assert faults, out["facts"]["faults"]
    assert out["failed"] == 0  # the service bound every pod: it is the record that tells
    assert '"correct": false' in out["stderr_tail"] and word in out["stderr_tail"]


# -- the checks on a run built by hand -----------------------------------------------------------


def _ctx(log, classes, machines=9, pus_per_core=1, max_tasks_per_pu=1, labels=None, polls=()):
    """A nine-node cluster by default: node 0 an A, 1-7 Bs, 8 a C; with one PU a core and
    one pod a PU they hold 1 / 2 / 4 pods."""
    nodes = {f"fake_node_{i}": 100 + i for i in range(machines)}
    status = {}
    for i in range(machines):
        name, cores, _share = ref.machine_type(i, TABLE)
        status[100 + i] = SimpleNamespace(
            descriptor=SimpleNamespace(labels={interference_map.PLATFORM_LABEL: name} if labels is None else labels[i]),
            topology_node=SimpleNamespace(children=[
                SimpleNamespace(children=[object()] * pus_per_core) for _ in range(cores)
            ]),
        )
    svc = SimpleNamespace(
        node_to_machine=nodes, resource_map=SimpleNamespace(find=status.get),
        api=SimpleNamespace(polls=list(polls)),
    )
    plan = SimpleNamespace(
        resident=sorted(classes.items()), closing=[], class_sweep=[], arrival_classes=None, wave_pods=0,
    )
    return SimpleNamespace(
        config={"machine_types": [list(t) for t in TABLE]}, plan=plan, log=log, svc=svc, facts={},
        svc_args=SimpleNamespace(
            num_machines=machines, pus_per_core=pus_per_core, max_tasks_per_pu=max_tasks_per_pu),
    )


def test_capacity_by_type_holds_each_node_to_its_own_size():
    # six pods a PU on one PU: the A holds 6, a B 12, the C 24
    classes = {f"p{i}": 0 for i in range(40)}
    fill = lambda node, pods, t: [("bind", p, node, t) for p in pods]  # noqa: E731
    pods = list(classes)
    log = fill("fake_node_0", pods[:6], 1.0) + fill("fake_node_8", pods[6:30], 1.0) + fill("fake_node_1", pods[30:40], 1.0)
    ctx = _ctx(log, classes, max_tasks_per_pu=6)
    assert capacity_by_type.check(ctx) == []
    assert ctx.facts["capacity_by_type"] == {
        "replayed": 40, "peak_load_by_capacity": {"6": 6, "24": 24, "12": 10}}
    # a seventh pod on the A node: seven is well under a B's twelve, and over its own six
    ctx = _ctx(fill("fake_node_0", pods[:7], 1.0), classes, max_tasks_per_pu=6)
    assert capacity_by_type.check(ctx) == ["node fake_node_0 held 7 pods, its own capacity is 6 (pod p6)"]
    # a completion frees the slot; one of a pod never bound is a fault
    ctx = _ctx(fill("fake_node_0", pods[:6], 1.0) + [("done", "p0", "", 1.5)] + fill("fake_node_0", pods[6:7], 2.0),
               classes, max_tasks_per_pu=6)
    assert capacity_by_type.check(ctx) == []
    ctx = _ctx([("done", "p0", "", 1.5)], classes)
    assert capacity_by_type.check(ctx) == ["pod p0 completed without a Binding on record"]
    assert capacity_by_type.node_capacity(_ctx([], {}, pus_per_core=2, max_tasks_per_pu=3), "fake_node_8") == 24


def test_interference_map_on_a_run_built_by_hand_and_a_swapped_binding():
    # nine nodes of one pod a PU: the A (node 0) holds 1, the Bs (1-7) 2, the C (node 8) 4. An
    # empty node costs its platform: a turtle 82 / 80 / 79, a rabbit 110 / 80 / 65, a sheep 90 /
    # 80 / 75. Round 1: two turtles, both on the C: 158. Round 2: a rabbit and a sheep; beside
    # two turtles, with two slots of four idle (bonus 10), the C costs the rabbit 85 - 10 and the
    # sheep 95 - 10: the rabbit goes there, the sheep to an empty B at 80
    classes = {"t1": 3, "t2": 3, "r": 1, "s": 0, "r2": 1, "s2": 0}
    log = [("bind", "t1", "fake_node_8", 1.0), ("bind", "t2", "fake_node_8", 1.0),
           ("bind", "r", "fake_node_8", 2.0), ("bind", "s", "fake_node_2", 2.0)]
    ctx = _ctx(log, classes, polls=[(0.0, 0.5, 2), (1.2, 1.5, 2), (2.5, 2.6, 0)])
    assert interference_map.check(ctx) == []
    facts = ctx.facts["interference_map"]
    assert (facts["rounds"], facts["rounds_compared"], facts["served_cost"], facts["optimum_cost"]) == (
        2, 2, 158 + 155, 158 + 155)
    assert (facts["nodes"], facts["slots"], facts["nodes_by_platform"], facts["polls"]) == (9, 1 + 14 + 4, [1, 7, 1], 3)
    assert facts["rounds_costing_zero"] == 0
    # a turtle on a B where the C has room: one dearer than the round's optimum
    ctx = _ctx([("bind", "t1", "fake_node_1", 1.0), ("bind", "t2", "fake_node_8", 1.0)], classes)
    assert "cost 159 by the interference map, the optimum of the round is 158" in interference_map.check(ctx)[0]
    # a turtle alone on the C (79); then thirteen turtles, onto empty Bs at 80 each (beside the
    # turtle the C costs 99 - 15, the A 82): every B full but node 1, which holds one. Then a
    # rabbit and a sheep arrive: beside a turtle a rabbit costs 101 on the B and 85 on the C, a
    # sheep 100 and 95; the B has one idle slot of two (bonus 10), the C three of four (15); the
    # empty A costs them 110 and 90: rabbit -> C 70, sheep -> C 80
    others = {f"f{i}": 3 for i in range(12)}
    spots = [f"fake_node_{i}" for i in range(2, 8) for _ in range(2)]
    base = [("bind", "t2", "fake_node_8", 0.5), ("bind", "t1", "fake_node_1", 1.0)] + [
        ("bind", p, n, 1.0) for p, n in zip(others, spots)]
    good = base + [("bind", "r2", "fake_node_8", 2.0), ("bind", "s2", "fake_node_8", 2.0)]
    ctx = _ctx(good, {**classes, **others})
    assert interference_map.check(ctx) == []
    facts = ctx.facts["interference_map"]
    assert facts["served_cost"] == 79 + 13 * 80 + 70 + 80 and facts["largest_round"] == 13
    assert facts["served_cost_but_largest_round"] == facts["optimum_cost_but_largest_round"] == 79 + 70 + 80
    # the sheep's Binding swapped for the B: 100 - 10: the round costs more than its optimum
    swapped = base + [("bind", "r2", "fake_node_8", 2.0), ("bind", "s2", "fake_node_1", 2.0)]
    ctx = _ctx(swapped, {**classes, **others})
    (fault,) = interference_map.check(ctx)
    assert fault == ("interference map broken: t=2.000000: the round's 2 Bindings cost 160 by the "
                     "interference map, the optimum of the round is 150")
    # the rabbit and the sheep swapped between the C and the B: dearer still
    ctx = _ctx(base + [("bind", "r2", "fake_node_1", 2.0), ("bind", "s2", "fake_node_8", 2.0)], {**classes, **others})
    assert "cost 171 by the interference map, the optimum of the round is 150" in interference_map.check(ctx)[0]
    # a pod that waits while a slot is idle
    ctx = _ctx(base, {**classes, **others}, polls=[(0.0, 0.4, 1), (0.6, 0.9, 14)])
    assert "1 pods waited after a round that bound 13 with 18 slots idle" in interference_map.check(ctx)[0]
    # a label that is another platform than the node's type; a node with other PUs than its type
    labels = [{interference_map.PLATFORM_LABEL: "B"}] * 9
    assert interference_map.check(_ctx(log, classes, labels=labels))[0] == (
        "node fake_node_0: type A by its index, label 'B' on the service")
    ctx = _ctx(log, classes)
    ctx.svc_args.pus_per_core = 2
    assert "node fake_node_0: 2 PUs by its type A, 1 on the service" in interference_map.check(ctx)[0]
    # a service without the benchmark's polls is held to the costs alone
    ctx = _ctx(good, {**classes, **others})
    del ctx.svc.api
    assert interference_map.check(ctx) == [] and ctx.facts["interference_map"]["polls"] == 0
    # a completed pod still counts in the round that follows its completion, and not after:
    # the rabbit r2 completes, then a rabbit arrives. Priced with r2 on the C (a turtle, a
    # rabbit and a sheep beside it, one slot idle) the C costs (85 + 93 + 97) // 3 = 91, less
    # a bonus of 5; without r2 it would cost (85 + 97) // 2 = 91 less 10. The B (a turtle, one
    # slot of two): 101 - 10; the empty A 110. The round is held to 86 on the C, the next to
    # the books without r2
    import numpy as np

    census = np.array([[0, 0, 0, 1], [1, 1, 0, 1]])  # the B (node 1), the C (node 8)
    cost = ref.cost_matrix(census, np.array([1, 1]), np.array([2, 4]), np.array([1, 2]))
    assert cost[1].tolist() == [91, 86]
    late = good + [("done", "r2", "", 2.5), ("bind", "r", "fake_node_8", 3.0)]
    ctx = _ctx(late, {**classes, **others})
    assert interference_map.check(ctx) == []
    assert ctx.facts["interference_map"]["served_cost"] == 79 + 13 * 80 + 70 + 80 + 86
    ctx = _ctx(good + [("done", "r2", "", 2.5), ("bind", "r", "fake_node_1", 3.0)], {**classes, **others})
    assert "cost 91 by the interference map, the optimum of the round is 86" in interference_map.check(ctx)[0]
    # the round after: r2 has left the books, the C holds a turtle, a sheep and a rabbit again
    ctx = _ctx(late + [("bind", "s", "fake_node_1", 4.0)], {**classes, **others})
    assert interference_map.check(ctx) == []
    assert ctx.facts["interference_map"]["served_cost"] == 79 + 13 * 80 + 70 + 80 + 86 + 90
    # a poll that takes a completion and hands over no pod while none waits starts no round:
    # r2 is still on the books when the next batch is priced, two polls later
    ctx = _ctx(late, {**classes, **others},
               polls=[(0.0, 0.4, 1), (0.6, 0.9, 13), (1.5, 1.9, 2), (2.4, 2.6, 0), (2.7, 2.9, 1)])
    assert interference_map.check(ctx) == [] and ctx.facts["interference_map"]["polls"] == 5
    # an eviction is no part of this policy's record
    ctx = _ctx(good + [("evict", "r2", "fake_node_8", 3.0)], {**classes, **others})
    assert "'evict' entry" in interference_map.check(ctx)[0]


def test_the_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(ROOT, "benchmarks", "reference_wharemap.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "typing", "numpy"}
