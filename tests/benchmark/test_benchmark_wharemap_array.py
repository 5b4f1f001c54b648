"""The `gtrace-12500-wharemap-array` deployment and its cell:
`gtrace-12500-wharemap`'s cluster (12,500 machines of three platforms and
sizes), backlog, classes and policy constants, letter for letter, served by
`--array-round`: one table on the device for machines that differ (every
machine padded to the widest one's PUs, a per-PU slot vector), Whare-Map's
costs computed there by each machine's own platform and slots, a round one
device program. The cell rehearses `correct` at 1/40 scale (312 machines: 1 A,
284 B, 27 C; 3,550 resident pods), traced and untraced, under its four
guarantees in the file's order; every per-layer metric that lists it reads a
number, `array_decode_width` and `array_machines_open` among them; each
guarantee on a run built by hand with its fault planted comes out false, and
three faults planted in a rehearsal turn `correct` false with the fault named:
a model with another PLATFORM_PRIOR, a table that reads every padded PU as a
PU (the scalar-S capacity), a Binding moved to another node.

Entries are looked up by name and lists are stated as "what they had, then
this cell". What earlier tests pinned and this deployment made false is an
expected failure there (tests/conftest.py); what stays true is held here."""

import ast
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import reference_wharemap as ref
from benchmarks import reference_wharemap_array as ref_array
from benchmarks import spec
from benchmarks.checks import array_round, binding, capacity_by_type, interference_map_array
from benchmarks.traffic import build_plan

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "gtrace-12500-wharemap-array"
CELL = CONFIG + ".trickle"
CONTROL_CONFIG = "gtrace-12500-wharemap"
CONTROL = CONTROL_CONFIG + ".trickle"
FIRST_PAIR = "coco-50kx1k-array.trickle"
SEED = 2147483707  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity_by_type", "array_round", "interference_map_array"]
TABLE = [("A", 1, 10), ("B", 2, 930), ("C", 4, 60)]
#: the ten entries PR 53 brought and the three lists it joined: this cell's name at the end of each
JOINED = (
    "array_completions_ms", "array_admit_ms", "array_launch_ms", "array_wait_ms",
    "array_readback_ms", "array_rows_live", "array_h2d_bytes", "array_d2h_bytes",
    "array_pods_waiting", "array_unconverged_rounds", "bind_tail_ms", "bindings_post_ms",
    "gc_pause_ms",
)
BROUGHT = {"array_decode_width": "rows", "array_machines_open": "machines"}
#: the graph path's metrics that have no cell list and read here as they do in the control
SHARED = (
    "round_p50_ms", "batch_pods_p50", "admit_ms", "queue_wait_ms", "bindings_collect_ms",
    "device_round_share", "supersteps_p50", "solve_device_ms", "device_idle_share",
    "traced_bind_p50_ms", "gen_late_p99_ms", "compiles_in_window", "apply_ms",
    "round_accounted_share",
)
LISTED = JOINED + tuple(BROUGHT) + SHARED


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
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced():
    return _rehearse(1)


# -- the files ------------------------------------------------------------------------


def test_the_configuration_is_the_controls_cluster_served_by_the_array_round():
    c, control = _config(), _config(CONTROL_CONFIG)
    assert c["argv"] == (
        "--fake-machines --num-machines 12500 --pus-per-core 2 --max-tasks-per-pu 3 "
        "--fake-machine-types A:1:10,B:2:930,C:4:60 --cost-model whare --array-round "
        "--pod-batch-timeout 0.002 --pod-chan-size 150000"
    ).split()
    # the control's argv but for the path: --backend auto there, --array-round here
    swap = [a for a in control["argv"] if a not in ("--backend", "auto")]
    assert swap == [a for a in c["argv"] if a != "--array-round"]
    for key in ("resident_pods", "task_classes", "wave_pods", "machine_types", "pods", "policy"):
        assert c[key] == control[key], key  # the policy letter for letter
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"], c["pods"]) == (142000, 4, 5000, "class_only")
    assert [tuple(t) for t in c["machine_types"]] == TABLE
    assert c["reduced"] == [] and "262,144 rows" in c["why_nothing_is_reduced"]
    assert list(c["guarantees"]) == GUARANTEES
    for key in ("binding", "capacity_by_type"):
        assert c["guarantees"][key] == control["guarantees"][key]
    first = _config("coco-50kx1k-array")
    assert c["guarantees"]["array_round"] == first["guarantees"]["array_round"].replace("above 16", "above 3")
    # assumed: the control's list, the table's rows, the padded layout, PR 53's sentences
    n = len(control["assumed"])
    assert c["assumed"][:n] == control["assumed"] and c["assumed"][-2:] == first["assumed"][-2:]
    between = " ".join(c["assumed"][n:-2])
    assert "262,144 rows" in between and "padded to the widest machine's 8 PUs" in between
    assert "100,000 PU entries for 52,782 PUs" in between
    assert len(c["source"]) <= 200 and "\n" not in c["source"]
    assert c["source"].startswith("BASELINE.json configs[3]: Whare-Map") and "north_star" in c["source"]
    # the constants the file states are the reference's and the model's
    from ksched_tpu.costmodels import whare

    policy = c["policy"]
    assert policy["psi_prior"] == [list(r) for r in ref.PSI_PRIOR] == whare.PSI_PRIOR.tolist()
    assert policy["platform_prior"] == [list(r) for r in ref.PLATFORM_PRIOR] == whare.PLATFORM_PRIOR.tolist()
    assert (policy["idle_bonus"], policy["max_cost"], policy["unscheduled_cost"]) == (
        ref.IDLE_BONUS, ref.MAX_COST, ref.UNSCHEDULED_COST) == (
        whare.IDLE_BONUS, whare.MAX_COST, whare.UNSCHEDULED_COST) == (20, 2000, 2500)


def test_the_shapes_the_file_states_are_what_the_table_deals():
    shapes = [ref.node_shape(f"fake_node_{i}", TABLE, 2, 3) for i in range(12500)]
    slots = np.array([s for _p, s in shapes])
    assert np.bincount(slots)[[6, 12, 24]].tolist() == [121, 11623, 756]
    assert (int(slots.sum()), int(slots.sum()) // 3) == (158346, 52782)
    assert 142000 / slots.sum() == pytest.approx(0.9, abs=0.005)
    from ksched_tpu.utils import next_pow2

    assert next_pow2(158346 + 1024) == 262144 and 12500 * 8 == 100000


def test_the_reference_imports_nothing_of_the_program():
    for name, allowed in (
        ("reference_wharemap_array.py", {"__future__", "typing", "numpy", "benchmarks"}),
        ("reference_wharemap.py", {"__future__", "typing", "numpy"}),
    ):
        with open(os.path.join(ROOT, "benchmarks", name)) as f:
            tree = ast.parse(f.read())
        imported, modules = set(), set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                imported.add((node.module or "").split(".")[0])
                modules.add(node.module)
        assert imported == allowed, name
    # of the benchmark it imports the other reference alone: the equation, the optimum, the shapes
    assert {m for m in modules if m and m.startswith("benchmarks")} == set()
    assert ref_array.cost_matrix is ref.cost_matrix and ref_array.reference_round is ref.reference_round
    assert ref_array.node_shape is ref.node_shape and ref_array.transport is ref.transport


def test_the_entries_of_the_configuration_and_the_cell():
    c = _entry("configs", CONFIG)
    assert c["file"] == f"benchmarks/configs/{CONFIG}.json" and c["reduced"] == []
    assert c["source"] == _config()["source"] != _entry("configs", CONTROL_CONFIG)["source"]
    configs = [x["name"] for x in BENCH["configs"]]
    assert configs.index(CONFIG) > configs.index("coco-50kx1k-array")  # appended, nothing moved
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    names = [x["name"] for x in BENCH["workloads"]]
    assert names.index(CELL) > names.index(FIRST_PAIR) and names.count(CELL) == 1
    assert not [x for x in names if x.startswith(CONFIG) and x != CELL]  # one cell, no second
    assert all(x["chips"] == 1 for x in BENCH["workloads"])
    # the mix it shares with the control is the control's file, unchanged
    with open(os.path.join(ROOT, "benchmarks", "traffic", "trickle.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["rate_per_s"], mix["completions_per_arrival"], mix["warmup_s"]) == (
        "open_poisson", 100.0, 1, 3.0)
    assert spec.check_names(BENCH) == []
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024 and BENCH["run_seconds"] == 40


@pytest.mark.parametrize("name", JOINED)
def test_a_list_it_joined_holds_what_it_had_then_this_cell(name):
    entry = _entry("per_layer", name)
    cells = entry["workloads"]
    assert cells.count(CELL) == 1 and cells.index(CELL) == cells.index(FIRST_PAIR) + 1
    accepted = [w["name"] for w in BENCH["workloads"]]
    assert [c for c in accepted if c in cells] == cells  # in the order the cells were appended
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key]
    assert entry["moves"] == "bind_p50_ms"


@pytest.mark.parametrize("name", sorted(BROUGHT))
def test_each_metric_it_brings_is_an_entry_with_its_file_for_the_two_array_cells(name):
    entry = _entry("per_layer", name)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key]
    assert (entry["layer"], entry["moves"], entry["better"], entry["source"], entry["unit"]) == (
        "array round", "bind_p50_ms", "lower", "program_counter", BROUGHT[name])
    assert entry["workloads"][:2] == [FIRST_PAIR, CELL]
    assert (own["reader"], own["params"]) == ("round_field", {"field": name, "reduce": "p50"})
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # appended after every entry that was there: PR 54's is the last before them
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("price_updates_p50")
    assert names.index("array_machines_open") == names.index("array_decode_width") + 1


def test_the_cell_loads_its_metrics_by_name_and_the_control_loads_none_of_the_array_rounds():
    mine = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(LISTED) <= mine
    assert mine == {m["name"] for m in spec.load_cell(FIRST_PAIR).per_layer}  # what the first pair reads
    theirs = {m["name"] for m in spec.load_cell(CONTROL).per_layer}
    assert not (set(BROUGHT) | set(JOINED[:10])) & theirs
    assert [m["name"] for m in spec.load_cell(CELL).end_to_end] == ["bind_p50_ms", "setup_s"]
    # every list but these fifteen leaves both array cells out: the dense rung's, the plan's, ...
    for m in BENCH["per_layer"]:
        if "workloads" in m and m["name"] not in JOINED + tuple(BROUGHT):
            assert CELL not in m["workloads"] and FIRST_PAIR not in m["workloads"], m["name"]
    cell = spec.load_cell(CELL)
    assert cell.pods == spec.DEFAULT_PODS == "class_only" and cell.chips == 1
    small = spec.rehearsal_config(cell.config)
    assert small["argv"].count("--array-round") == 1 and small["argv"][2] == "312"
    assert (small["resident_pods"], small["wave_pods"]) == (3550, 125)


@pytest.mark.parametrize("seed", [5, 11, 2147483693])
def test_the_plan_of_the_cell_is_its_controls_seed_for_seed(seed):
    cell, control = spec.load_cell(CELL), spec.load_cell(CONTROL)
    assert cell.traffic == control.traffic
    a = build_plan(cell.traffic, cell.config, seed, 40.0)
    b = build_plan(control.traffic, control.config, seed, 40.0)
    assert a.resident == b.resident and a.victims == b.victims and a.closing == b.closing
    assert a.class_sweep == b.class_sweep and len(a.resident) == 142000
    assert np.array_equal(a.arrival_offsets_s, b.arrival_offsets_s)
    assert np.array_equal(a.arrival_classes, b.arrival_classes)
    # what a pod carries is its id and class, as the control's
    make, theirs = (spec.pod_maker(c.pods, c.config, seed) for c in (cell, control))
    assert all(make(p, k) == theirs(p, k) for p, k in a.resident[:100] + a.closing)


# -- the rehearsal ----------------------------------------------------------------------


def test_the_untraced_rehearsal_is_correct_under_the_four_guarantees():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["checks"] == GUARANTEES
    assert out["facts"]["shapes"] == {"machines": 312, "task_classes": 4}
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}


def test_the_traced_rehearsal_is_correct_and_holds_the_facts_of_every_guarantee(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["checks"] == GUARANTEES == list(out["facts"]["check_seconds"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    facts = out["facts"]
    assert facts["rounds"]["solved"] > 50
    assert facts["capacity_by_type"]["peak_load_by_capacity"].keys() <= {"6", "12", "24"}
    table = facts["array_round"]
    assert (table["rows"], table["pu_slots"], table["limit"]) == (8192, 3, 0)
    assert table["rows_placed"] == table["pods_bound_by_the_record"] == table["rows_live"] > 3000
    assert table["pu_running_differs"] == 0 == table["pods_not_where_the_record_has_them"]
    assert table["pu_peak"] == 3 and table["unconverged_rounds"] == 0 == table["cost_overflows"]
    replay = facts["interference_map_array"]
    assert replay["served_cost"] == replay["optimum_cost"] > 0
    assert replay["rounds"] > 100 and replay["largest_round"] == 3550
    assert replay["rounds_costing_zero"] == 0  # no machine of this map costs 0: every round compares
    assert replay["served_cost_but_largest_round"] > 65 * (replay["pods_bound"] - 3550)
    assert replay["completions"] > 100 and replay["pods_left_waiting_at_most"] == 0
    assert (replay["nodes"], replay["slots"], replay["nodes_by_platform"]) == (312, 4062, [1, 284, 27])
    # a machine is priced again only when its census changed: a few a round after the fill
    assert replay["machines_priced"] < 312 + 8 * replay["rounds"]


@pytest.mark.parametrize("name", LISTED)
def test_every_metric_that_lists_the_cell_reads_a_number_in_the_traced_rehearsal(traced, name):
    metrics = {k: v["value"] for k, v in traced["metrics"].items()}
    assert isinstance(metrics[name], float), name
    if name.endswith("_ms") and name.startswith("array_"):
        assert metrics[name] > 0.0
    want = {
        "compiles_in_window": 0, "array_unconverged_rounds": 0, "array_pods_waiting": 0,
        "device_round_share": 100.0, "array_decode_width": 256,
        # a completion and an admission, a 256-wide bucket each; scalars, the count, 256 pairs back
        "array_d2h_bytes": 28 + 4 + 8 * 256,
    }
    if name in want:
        assert metrics[name] == want[name]
    if name == "array_machines_open":  # of 312, nine tenths full: most have a slot
        assert 100 <= metrics[name] <= 312
    if name == "array_rows_live":
        assert 3400 <= metrics[name] <= 3800
    if name == "array_h2d_bytes":
        assert metrics[name] in (4 * 256 + 4, 2 * (4 * 256 + 4))


# -- the controls: each must print `correct` false with the fault named ----------------------

ANOTHER_PLATFORM_PRIOR = """
from ksched_tpu.costmodels import whare
whare.PLATFORM_PRIOR = whare.PLATFORM_PRIOR[:, ::-1].copy()  # A the fastest, C the slowest
"""
EVERY_PADDED_PU_IS_A_PU = """
from ksched_tpu.scheduler import device_bulk
_init = device_bulk.DeviceBulkCluster.__init__
def init(self, *a, pu_slots=None, **k):  # the scalar S for every PU of the padded table
    _init(self, *a, **k)
device_bulk.DeviceBulkCluster.__init__ = init
"""
MOVE_A_BINDING = """
import benchmarks.client as client
from ksched_tpu.cluster.api import Binding
_assign, _seen = client.BenchClusterAPI.assign_bindings, {"calls": 0}
def assign(self, bindings):
    _seen["calls"] += 1
    if _seen["calls"] == 9:  # onto the one A machine, the dearest for every class
        bindings = [Binding(bindings[0].pod_id, "fake_node_0")] + list(bindings[1:])
    return _assign(self, bindings)
client.BenchClusterAPI.assign_bindings = assign
"""
CONTROLS = {
    "a-model-with-another-platform-prior": (ANOTHER_PLATFORM_PRIOR, [
        "interference map broken", "the optimum of the round is"]),
    "every-padded-pu-read-as-a-pu": (EVERY_PADDED_PU_IS_A_PU, [
        "its own capacity is", "8 PUs and 24 slots in the service's table"]),
    "a-binding-moved-to-another-node": (MOVE_A_BINDING, [
        "interference map broken", "are not on a PU of their node"]),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_planted_fault_prints_correct_false_with_the_fault_named(control):
    patch, said = CONTROLS[control]
    out = _rehearse(0, patch=patch, seconds="2")
    assert out["correct"] is False
    faults = " | ".join(out["facts"]["faults"])
    for words in said:
        assert words in faults, faults
    assert out["facts"]["checks"] == GUARANTEES


# -- each guarantee on a run built by hand, its fault planted --------------------------------

#: nine nodes: node 0 an A, 1-7 Bs, 8 a C; one PU a core and one pod a PU: 1 / 2 / 4 slots
NODES = [f"fake_node_{i}" for i in range(9)]
CLASSES = {"t1": 3, "t2": 3, "r": 1, "s": 0, "r2": 1, "s2": 0, "x": 3}


def _replay(log, batches=(), **kw):
    return ref_array.check_interference_map_array(log, CLASSES, NODES, TABLE, 1, 1, batches=batches, **kw)


def test_binding_wants_one_binding_for_every_pod_due():
    ctx = SimpleNamespace(due={"a": (1.0, 1.0), "b": (2.0, 2.0)}, bind_stamps={"a": [1.5], "b": [2.5]}, facts={})
    assert binding.check(ctx) == []
    ctx.bind_stamps = {"a": [1.5, 1.7]}
    faults = binding.check(ctx)
    assert "1 pods due in the window got no Binding (first: b)" in faults[0]
    assert "1 pods got more than one Binding (first: a)" in faults[1]


def test_capacity_by_type_holds_a_node_to_its_own_slots():
    def ctx(log):
        return SimpleNamespace(
            config={"machine_types": [list(t) for t in TABLE]}, log=log, facts={},
            svc_args=SimpleNamespace(pus_per_core=2, max_tasks_per_pu=3),
        )

    fill = [("bind", f"p{i}", "fake_node_0", 1.0) for i in range(6)]
    assert capacity_by_type.check(ctx(fill)) == []
    # a seventh pod on the A: well under a B's twelve, over its own six (what the scalar S allows)
    over = fill + [("bind", "p6", "fake_node_0", 2.0)]
    assert capacity_by_type.check(ctx(over)) == ["node fake_node_0 held 7 pods, its own capacity is 6 (pod p6)"]
    assert capacity_by_type.check(ctx(fill + [("done", "p0", "", 1.5), ("bind", "p6", "fake_node_0", 2.0)])) == []


def test_array_round_holds_a_padded_table_to_books_from_the_record():
    # two machines padded to 4 PUs: node 0 has PUs 0-1, node 1 PUs 4-7
    def table(pu, running=None):
        pu = np.asarray(pu, np.int32)
        live = pu > -2
        counts = np.bincount(pu[pu >= 0], minlength=8)
        return {"live": live, "pu": np.where(live, pu, -1),
                "pu_running": counts if running is None else np.asarray(running)}

    bound = {"a": "fake_node_0", "b": "fake_node_1", "c": "fake_node_1"}
    rows = {"a": 0, "b": 2, "c": 3, "waits": 1}

    def check(state, facts):
        return array_round.table_faults(state, bound, rows, NODES[:2], 4, 3, facts)

    facts = {}
    assert check(table([1, -1, 4, 7, -2]), facts) == []
    assert (facts["rows_live"], facts["rows_placed"], facts["pu_peak"], facts["pu_slots"]) == (4, 3, 1, 3)
    assert "1 pods the record has bound are not on a PU of their node" in check(table([5, -1, 4, 7, -2]), {})[0]
    assert check(table([1, 0, 4, 7, -2]), {}) == ["the device's table has 4 rows placed, the record 3 pods bound"]
    faults = check(table([1, -1, 4, 7, -2], running=[0, 2, 0, 0, 1, 0, 0, 1]), {})
    assert "pu_running differs from a recount of the pu column on 1 PUs (first: PU 1 says 2, holds 1)" in faults[0]
    four = {"a": "fake_node_0", "b": "fake_node_1", "c": "fake_node_1", "d": "fake_node_1", "e": "fake_node_1"}
    faults = array_round.table_faults(
        table([1, 4, 4, 4, 4]), four, dict(zip(four, range(5))), NODES[:2], 4, 3, {})
    assert faults == ["a PU holds 4 pods, it has 3 slots"]
    assert "no table on the device" in array_round.check(SimpleNamespace(svc=SimpleNamespace()))[0]


def test_interference_map_array_prices_a_round_on_the_census_of_its_start():
    # an empty node costs its platform: a turtle 82 / 80 / 79, a rabbit 110 / 80 / 65, a sheep
    # 90 / 80 / 75. Round 1: two turtles, both on the C: 158. Round 2: a rabbit and a sheep;
    # beside two turtles, two slots of four idle (bonus 10), the C costs the rabbit 85 - 10 and
    # the sheep 95 - 10: the rabbit goes there, the sheep to an empty B at 80
    good = [("bind", "t1", "fake_node_8", 1.0), ("bind", "t2", "fake_node_8", 1.0),
            ("bind", "r", "fake_node_8", 2.0), ("bind", "s", "fake_node_2", 2.0)]
    faults, facts = _replay(good)
    assert faults == []
    assert (facts["rounds"], facts["served_cost"], facts["optimum_cost"]) == (2, 158 + 155, 158 + 155)
    assert (facts["nodes"], facts["slots"], facts["nodes_by_platform"]) == (9, 1 + 14 + 4, [1, 7, 1])
    assert facts["rounds_costing_zero"] == 0 and facts["machines_priced"] == 9 + 1
    # a turtle on a B where the C has room: one dearer than the round's optimum
    faults, _ = _replay([("bind", "t1", "fake_node_1", 1.0), ("bind", "t2", "fake_node_8", 1.0)])
    assert "cost 159 by the interference map, the optimum of the round is 158" in faults[0]
    # the rabbit and the sheep swapped: 80 + 85 against 75 + 80
    faults, _ = _replay(good[:2] + [("bind", "r", "fake_node_2", 2.0), ("bind", "s", "fake_node_8", 2.0)])
    assert "cost 165 by the interference map, the optimum of the round is 155" in faults[0]
    # a class the plan does not know, a node outside the cluster, an eviction, a stray completion
    assert "no class of the plan" in _replay([("bind", "nobody", "fake_node_1", 1.0)])[0][0]
    assert "no node of the cluster" in _replay([("bind", "t1", "fake_node_9", 1.0)])[0][0]
    assert "'evict' entry" in _replay([("evict", "t1", "fake_node_8", 1.0)])[0][0]
    assert "completed with no Binding on record" in _replay([("done", "t1", "", 1.0)])[0][0]
    # the plain replay (every machine priced every round, `reference_round`) says the same
    plain_faults, plain = _replay(good, plain=True)
    assert plain_faults == [] and plain["served_cost"] == plain["optimum_cost"] == 158 + 155
    assert plain["machines_priced"] == 18


def test_a_completion_taken_before_a_round_has_left_the_books_when_it_is_priced():
    # a cluster of one machine, the C of four slots. Round 1 fills it, every pod priced on the
    # empty machine: two turtles at 79, a rabbit at 65, a sheep at 75. The rabbit completes; a
    # turtle arrives. By the array round's rule the rabbit has left when the round is priced: one
    # slot of four idle (bonus 5) beside two turtles and a sheep, (99 + 99 + 99) // 3 - 5 = 94
    one = ["fake_node_8"]
    fill = [("bind", p, "fake_node_8", 1.0) for p in ("t1", "t2", "r", "s2")]
    late = fill + [("done", "r", "", 1.5), ("bind", "x", "fake_node_8", 2.0)]
    batches = [(0.5, ["t1", "t2", "r", "s2"]), (1.8, ["x"])]
    faults, facts = ref_array.check_interference_map_array(late, CLASSES, one, TABLE, 1, 1, batches=batches)
    assert faults == [] and facts["served_cost"] == 79 + 79 + 65 + 75 + 94 == facts["optimum_cost"]
    assert (facts["rounds"], facts["completions"], facts["rounds_that_left_pods_waiting"]) == (2, 1, 0)
    # the graph path's rule keeps the rabbit on the books through that round: no slot is idle,
    # the round's optimum is to leave the turtle waiting, and the Binding is a fault
    faults, _ = ref_array.check_interference_map_array(
        late, CLASSES, one, TABLE, 1, 1, batches=batches, completions_leave_after_the_round=True)
    assert len(faults) == 1 and "the optimum of the round is 2500" in faults[0]
    # without the completion the turtle has to wait, at 2,500, and that round is compared too
    faults, facts = ref_array.check_interference_map_array(fill, CLASSES, one, TABLE, 1, 1, batches=batches)
    assert faults == [] and facts["rounds"] == 1
    faults, _ = ref_array.check_interference_map_array(
        fill + [("bind", "x", "fake_node_8", 2.0)], CLASSES, one, TABLE, 1, 1, batches=batches)
    assert "the optimum of the round is 2500" in faults[0]


def test_a_round_leaves_a_pod_waiting_only_if_it_took_every_idle_slot():
    batches = [(0.5, ["t1", "t2", "r"])]
    one = [("bind", "t1", "fake_node_8", 1.0), ("bind", "t2", "fake_node_8", 1.0)]
    faults, facts = _replay(one, batches=batches)
    assert any("1 pods waited after a round that bound 2 with 19 slots idle" in f for f in faults)
    assert any("cost 2658 by the interference map, the optimum of the round is 223" in f for f in faults)
    assert facts["rounds_that_left_pods_waiting"] == 1 == facts["pods_left_waiting_at_most"]
    # the pod that waited is in the next round's batch, with the pod handed over since
    later = one + [("bind", "r", "fake_node_8", 2.0), ("bind", "s", "fake_node_2", 2.0)]
    faults, facts = _replay(later, batches=batches + [(1.5, ["s"])])
    assert [f for f in faults if "t=2." in f] == [] and facts["pods_bound"] == 4
    # a pod no poll handed over
    faults, _ = _replay(one + [("bind", "s", "fake_node_2", 2.0)], batches=[(0.5, ["t1", "t2"])])
    assert len(faults) == 1 and "a pod no poll handed over" in faults[0]


def test_the_services_table_is_cross_checked_against_the_type_table():
    def svc(pus=(2, 4, 4, 4, 4, 4, 4, 4, 8), platform=(0, 1, 1, 1, 1, 1, 1, 1, 2), slots=3):
        pu_slots = np.where(np.arange(8)[None, :] < np.asarray(pus)[:, None], slots, 0)
        return SimpleNamespace(
            nodes=NODES, machine_platform=np.asarray(platform),
            cluster=SimpleNamespace(pu_slots=pu_slots.reshape(-1)),
        )

    check = interference_map_array.service_disagrees
    assert check(svc(), TABLE, 2, 3) == []
    assert check(svc(pus=(8,) * 9), TABLE, 2, 3) == [
        "node fake_node_0: 2 PUs of 3 slots by its type A, 8 PUs and 24 slots in the service's table"]
    assert "node fake_node_8: 8 PUs of 3 slots by its type C, 4 PUs" in check(
        svc(pus=(2, 4, 4, 4, 4, 4, 4, 4, 4)), TABLE, 2, 3)[0]
    assert check(svc(platform=(1,) * 9), TABLE, 2, 3) == [
        "node fake_node_0: platform A by its index, 1 on the service"]
    assert "no table on the device" in check(SimpleNamespace(), TABLE, 2, 3)[0]
    assert "names no platform" in check(SimpleNamespace(
        nodes=NODES, cluster=SimpleNamespace(pu_slots=np.zeros(72))), TABLE, 2, 3)[0]


def test_round_optimum_is_reference_round_on_random_censuses_over_three_types():
    rng = np.random.default_rng(3)
    for _ in range(20):
        M = 60
        kind = rng.choice(3, M, p=[0.05, 0.8, 0.15])
        slots, platform = np.array([6, 12, 24])[kind], kind.astype(np.int64)
        running = np.where(rng.random(M) < 0.3, slots, rng.integers(0, slots + 1))
        census = np.stack([rng.multinomial(n, [0.25] * 4) for n in running]).astype(np.int64)
        idle = slots - running
        pods = rng.integers(0, 12, 4) * (1 if rng.random() < 0.7 else 8)  # some rounds beyond the room
        cost = ref.cost_matrix(census, idle, slots, platform)
        assert ref_array.round_optimum(cost, idle, pods) == ref.reference_round(
            census, idle, slots, platform, pods)


# -- what stays true of what earlier tests pinned ----------------------------------------------


@pytest.mark.parametrize("name", ("bind_tail_ms", "bindings_post_ms", "gc_pause_ms"))
def test_a_list_pr_53_joined_holds_what_it_had_then_its_cell_then_this_one(name):
    # test_benchmark_array.py states these lists over the twelve cells it found plus its own
    cells = _entry("per_layer", name)["workloads"]
    accepted = [w["name"] for w in BENCH["workloads"]][:12]
    at = cells.index(FIRST_PAIR)
    assert cells[at + 1] == CELL and set(cells[:at]) <= set(accepted)
    assert cells[at - 1] == "k8s-5000-requests.trickle"


def test_what_stays_true_of_the_pins_this_cell_made_false():
    # test_benchmark_seams.py wants no `pods` key and a digest on file for every cell: this one
    # names the default, as its control does, and its plan is the control's (tested above)
    assert _config()["pods"] == _config(CONTROL_CONFIG)["pods"] == spec.DEFAULT_PODS
    # test_benchmark_solve_split.py and test_benchmark_runnable_scan.py draw a case a cell and want
    # the graph path's metrics loaded: this cell loads what the first array cell loads, no more
    mine = {m["name"] for m in spec.load_cell(CELL).per_layer}
    for name in ("runnable_scan_ms", "stats_children_gathered", "round_unnamed_ms", "collapse_audit_ms"):
        assert name not in mine and name in {m["name"] for m in spec.load_cell(CONTROL).per_layer}
    assert "gc_pause_ms" in mine and "batch_pods_p50" in mine
    # the ten entries PR 53 gave the list of twelve cells keep that list
    accepted = [w["name"] for w in BENCH["workloads"]][:12]
    for name in ("graph_update_ms", "graph_export_ms", "backend_solve_ms", "solve_roofline", "stats_ms",
                 "graph_refresh_ms", "ec_refresh_ms", "decode_deltas_ms", "apply_walk_ms", "runnable_scan_ms"):
        assert _entry("per_layer", name)["workloads"] == accepted
