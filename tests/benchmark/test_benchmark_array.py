"""The `coco-50kx1k-array` deployment and its cell: `coco-50kx1k`'s cluster,
backlog, classes and policy, letter for letter, served by `--array-round`
(the cluster's state in device arrays, a round one device program). The
cell rehearses `correct` at 1/40 scale (25 nodes, 1,250 resident pods),
traced and untraced, under its four guarantees in the file's order; the ten
per-layer metrics it brings and those of the graph path's that mean the same
here read a number; and the two checks it brings tell: a Binding moved to a
costlier machine that had a free slot, a reference with another W, a replay
whose census forgets completions, a `pu_running` off by one in the fetched
table and a planted non-converged round each turn `correct` false with the
fault named. Each check module is also held to a hand-built record.

Entries are looked up by name and lists are stated as "what they had, then
this cell". Ten entries that had no cell list read nothing in a service with
no graph path and carry the twelve accepted cells since this PR; what
earlier tests pinned of them, and of `class_only` for every cell, is an
expected failure there (tests/conftest.py) and what stays true is held
here."""

import functools
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import reference_coco as ref
from benchmarks import spec
from benchmarks.checks import array_round, interference_coco
from benchmarks.traffic import build_plan

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "coco-50kx1k-array"
CELL = CONFIG + ".trickle"
CONTROL = "coco-50kx1k.trickle"
SEED = 2147483699  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity", "array_round", "interference_coco"]
SPANS = {f"array_{k}_ms": f"array_{k}" for k in ("completions", "admit", "launch", "wait", "readback")}
FIELDS = {
    "array_rows_live": ("array_rows_live", "p50", "rows"),
    "array_h2d_bytes": ("array_h2d_bytes", "p50", "B"),
    "array_d2h_bytes": ("array_d2h_bytes", "p50", "B"),
    "array_pods_waiting": ("array_pods_waiting", "p50", "pods"),
    "array_unconverged_rounds": ("array_unconverged", "sum", "count"),
}
BROUGHT = tuple(SPANS) + tuple(FIELDS)
#: lists the cell was appended to: the service emits the span or field with the same meaning
APPENDED = ("bind_tail_ms", "bindings_post_ms", "gc_pause_ms")
#: entries without a cell list until this PR, which read nothing where no graph path runs
SILENT = (
    "graph_update_ms", "graph_export_ms", "backend_solve_ms", "solve_roofline", "stats_ms",
    "graph_refresh_ms", "ec_refresh_ms", "decode_deltas_ms", "apply_walk_ms", "runnable_scan_ms",
)
#: the graph path's metrics that read here as they do in the control (ISSUE 53, item 6)
SHARED = (
    "round_p50_ms", "batch_pods_p50", "admit_ms", "queue_wait_ms", "bindings_collect_ms",
    "device_round_share", "supersteps_p50", "solve_device_ms", "device_idle_share",
    "traced_bind_p50_ms", "gen_late_p99_ms", "compiles_in_window",
)
ACCEPTED = [w["name"] for w in BENCH["workloads"] if w["name"] != CELL][:12]


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
    c, control = _config(), _config("coco-50kx1k")
    assert c["argv"] == (
        "--fake-machines --num-machines 1000 --cores-per-machine 1 --pus-per-core 4 "
        "--max-tasks-per-pu 16 --cost-model coco --array-round --pod-batch-timeout 0.002 "
        "--pod-chan-size 53000"
    ).split()
    # the control's argv but for the path: --backend auto there, --array-round here
    swap = [a for a in control["argv"] if a not in ("--backend", "auto")]
    assert swap == [a for a in c["argv"] if a != "--array-round"]
    for key in ("resident_pods", "task_classes", "wave_pods"):
        assert c[key] == control[key]
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (50000, 4, 2500)
    assert "pods" not in c and c["reduced"] == [] and c["why_nothing_is_reduced"]
    assert list(c["guarantees"]) == GUARANTEES
    assert c["assumed"][:4] == control["assumed"]
    assumed = " ".join(c["assumed"])
    assert "65,536 rows" in assumed and "NEXT round" in assumed and "one job" in assumed
    assert len(c["source"]) <= 200 and c["source"].startswith("BASELINE.json configs[2]")
    assert set(c["policy"]) == {"cost", "capacity", "unscheduled", "round"}
    # the constants the file and the reference state are the model's
    from ksched_tpu.costmodels import coco

    assert np.array_equal(np.asarray(ref.W), coco.INTERFERENCE)
    assert (ref.MAX_COST, ref.UNSCHEDULED_COST) == (coco.MAX_COST, coco.UNSCHEDULED_COST) == (2000, 2500)
    assert "2000" in c["policy"]["cost"] and "2,500" in c["policy"]["unscheduled"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "reference_coco.py")) as f:
        source = f.read()
    assert "ksched_tpu" not in source.split('"""', 2)[2]


def test_the_entries_of_the_configuration_and_the_cell():
    c = _entry("configs", CONFIG)
    assert c["file"] == f"benchmarks/configs/{CONFIG}.json" and c["reduced"] == []
    assert c["source"] == _config()["source"]
    w = _entry("workloads", CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200 and len(c["why"]) <= 200
    names = [x["name"] for x in BENCH["workloads"]]
    assert names.index(CELL) > names.index("k8s-5000-requests.trickle")
    assert not [x for x in names if x.startswith(CONFIG) and x != CELL]  # one cell, no second
    # the mix it shares with the control is the control's file, unchanged
    with open(os.path.join(ROOT, "benchmarks", "traffic", "trickle.json")) as f:
        mix = json.load(f)
    assert (mix["kind"], mix["rate_per_s"], mix["completions_per_arrival"], mix["warmup_s"]) == (
        "open_poisson", 100.0, 1, 3.0)
    assert spec.check_names(BENCH) == []
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024


@pytest.mark.parametrize("name", BROUGHT)
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell(name):
    entry = _entry("per_layer", name)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key]
    assert (entry["layer"], entry["moves"], entry["better"]) == ("array round", "bind_p50_ms", "lower")
    assert entry["workloads"][0] == CELL
    if name in SPANS:
        assert (own["reader"], own["params"]) == ("span_sum", {"spans": [SPANS[name]], "reduce": "p50"})
        assert (entry["unit"], entry["source"]) == ("ms", "program_span")
    else:
        field, how, unit = FIELDS[name]
        assert (own["reader"], own["params"]) == ("round_field", {"field": field, "reduce": how})
        assert (entry["unit"], entry["source"]) == (unit, "program_counter")
    # appended after every entry that was there
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("supply_prerouted")


@pytest.mark.parametrize("name", APPENDED)
def test_a_list_it_was_appended_to_holds_what_it_had_then_this_cell(name):
    cells = _entry("per_layer", name)["workloads"]
    assert CELL in cells and cells.index(CELL) > cells.index("k8s-5000-requests.trickle")
    assert set(cells) - {CELL} <= set(ACCEPTED)


@pytest.mark.parametrize("name", SILENT)
def test_an_entry_that_reads_nothing_without_a_graph_path_lists_the_twelve_accepted_cells(name):
    # the driver reads a list of exactly the accepted cells as no change
    assert _entry("per_layer", name)["workloads"] == ACCEPTED and len(ACCEPTED) == 12
    assert name not in {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert name in {m["name"] for m in spec.load_cell(CONTROL).per_layer}


def test_the_cell_loads_its_metrics_by_name_and_the_control_loads_none_of_them():
    mine = {m["name"] for m in spec.load_cell(CELL).per_layer}
    assert set(BROUGHT) | set(APPENDED) | set(SHARED) <= mine
    assert not set(BROUGHT) & {m["name"] for m in spec.load_cell(CONTROL).per_layer}
    assert [m["name"] for m in spec.load_cell(CELL).end_to_end] == ["bind_p50_ms", "setup_s"]
    # every other list that was there leaves the cell out: the dense rung's, the plan's, ...
    for m in BENCH["per_layer"]:
        if "workloads" in m and m["name"] not in BROUGHT + APPENDED:
            assert CELL not in m["workloads"], m["name"]
    assert spec.load_cell(CELL).pods == "class_only"
    assert spec.rehearsal_config(spec.load_cell(CELL).config)["argv"].count("--array-round") == 1


# -- the rehearsal ----------------------------------------------------------------------


def test_the_untraced_rehearsal_is_correct_under_the_four_guarantees():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["checks"] == GUARANTEES
    assert out["facts"]["shapes"] == {"machines": 25, "task_classes": 4}
    assert out["attempted"] > 100 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["checks"] == GUARANTEES
    assert out["facts"]["shapes"] == {"machines": 25, "task_classes": 4}
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(BROUGHT) | set(APPENDED) | set(SHARED) <= set(metrics)
    assert not set(SILENT) & set(metrics)
    assert all(isinstance(v, float) for v in metrics.values())
    for name in SPANS:
        assert metrics[name] > 0.0, name
    assert metrics["compiles_in_window"] == 0 == metrics["array_unconverged_rounds"]
    assert metrics["array_pods_waiting"] == 0 and metrics["device_round_share"] == 100.0
    assert 1200 <= metrics["array_rows_live"] <= 1400 and metrics["supersteps_p50"] > 0
    # a completion and an admission, a 256-wide bucket each; scalars, the count, 256 pairs back
    assert metrics["array_h2d_bytes"] in (4 * 256 + 4, 2 * (4 * 256 + 4))
    assert metrics["array_d2h_bytes"] == 28 + 4 + 8 * 256
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    facts = out["facts"]
    assert facts["rounds"]["solved"] > 50
    table = facts["array_round"]
    assert (table["rows"], table["pu_slots"], table["limit"]) == (4096, 16, 0)
    assert table["rows_placed"] == table["pods_bound_by_the_record"] == table["rows_live"] > 1000
    assert table["pu_running_differs"] == 0 == table["pods_not_where_the_record_has_them"]
    replay = facts["interference_coco"]
    assert replay["served_cost"] == replay["optimum_cost"] > 0
    assert replay["rounds"] > 100 and replay["largest_round"] == 1250
    assert replay["rounds_costing_zero"] < replay["rounds"] // 2
    assert replay["completions"] > 100 and replay["pods_left_waiting_at_most"] == 0


# -- the controls: each must print `correct` false with the fault named ----------------------

MOVE_A_BINDING = """
import numpy as np
import benchmarks.client as client
from benchmarks import reference_coco as ref
from ksched_tpu.cluster.api import Binding
_assign, _seen = client.BenchClusterAPI.assign_bindings, {"calls": 0, "moved": 0}
def assign(self, bindings):
    _seen["calls"] += 1
    if not _seen["moved"] and _seen["calls"] > 8:
        svc = self.svc
        st = svc.cluster.fetch_state()
        mine = [svc.row_of[b.pod_id] for b in bindings]
        placed = st["live"] & (st["pu"] >= 0)
        placed[mine] = False  # the census of the round's start
        census = np.zeros((len(svc.nodes), 4), np.int64)
        np.add.at(census, (st["pu"][placed] // svc.cluster.P, st["cls"][placed]), 1)
        cost = ref.cost_matrix(census)[st["cls"][mine[0]]]
        at = svc.nodes.index(bindings[0].node_id)
        room = 64 - census.sum(axis=1) >= len(bindings) + 1
        worse = int(np.argmax(np.where(room, cost, -1)))
        if cost[worse] > cost[at]:
            bindings = [Binding(bindings[0].pod_id, svc.nodes[worse])] + list(bindings[1:])
            _seen["moved"] = 1
    return _assign(self, bindings)
client.BenchClusterAPI.assign_bindings = assign
"""
ANOTHER_W = """
import functools
from benchmarks import reference_coco as ref
ref.check_interference_coco = functools.partial(
    ref.check_interference_coco, weights=ref.W[::-1])  # the classes' rows in reverse
"""
FORGET_COMPLETIONS = """
import functools
from benchmarks import reference_coco as ref
ref.check_interference_coco = functools.partial(ref.check_interference_coco, forget_completions=True)
"""
PU_RUNNING_OFF_BY_ONE = """
from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
_fetch = DeviceBulkCluster.fetch_state
def fetch_state(self):
    st = {k: v.copy() for k, v in _fetch(self).items()}
    st["pu_running"][7] += 1
    return st
DeviceBulkCluster.fetch_state = fetch_state
"""
A_NON_CONVERGED_ROUND = """
from ksched_tpu.scheduler.array_service import ArrayRoundService
_note, _seen = ArrayRoundService._note_faults, {"rounds": 0}
def note(self, got, *rest):
    _seen["rounds"] += 1
    if _seen["rounds"] == 20:
        got = dict(got, converged=0)
    return _note(self, got, *rest)
ArrayRoundService._note_faults = note
"""
CONTROLS = {
    "a-binding-moved-to-a-costlier-machine": (MOVE_A_BINDING, [
        "CoCo's interference equation broken", "the optimum of the round is",
        "are not on a PU of their node"]),
    "a-reference-with-another-W": (ANOTHER_W, ["CoCo's interference equation broken"]),
    "a-replay-that-forgets-completions": (FORGET_COMPLETIONS, ["CoCo's interference equation broken"]),
    "pu_running-off-by-one": (PU_RUNNING_OFF_BY_ONE, ["pu_running differs from a recount", "PU 7"]),
    "a-non-converged-round": (A_NON_CONVERGED_ROUND, ["1 rounds fetched with `converged` false"]),
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


# -- each check module on a hand-built record ---------------------------------------------

NODES = ["fake_node_0", "fake_node_1"]
CLASS_OF = {"devil": 2, "sheep": 0, "rabbit": 1, "turtle": 3, "sheep2": 0}


def _replay(log, slots=2, **kw):
    return ref.check_interference_coco(log, CLASS_OF, NODES, slots, **kw)


def test_the_reference_prices_a_round_on_the_census_of_its_start():
    good = [
        ("bind", "devil", "fake_node_0", 1.0),
        ("bind", "sheep", "fake_node_1", 2.0),  # beside the devil it would cost 8
        ("done", "devil", "", 3.0),
        ("bind", "rabbit", "fake_node_0", 4.0),  # the devil left: 0 there, 4 beside the sheep
    ]
    faults, facts = _replay(good)
    assert faults == [] and facts["rounds"] == 3 and facts["served_cost"] == 0 == facts["optimum_cost"]
    # moved to the costlier machine, which had a free slot
    bad = [good[0], ("bind", "sheep", "fake_node_0", 2.0)] + good[2:]
    faults, _ = _replay(bad)
    assert len(faults) == 1 and "cost 8 by CoCo's equation, the optimum of the round is 0" in faults[0]
    # a census that forgets the completion prices the last round 16 against 4
    faults, _ = _replay(good, forget_completions=True)
    assert len(faults) == 1 and "cost 16 by CoCo's equation, the optimum of the round is 4" in faults[0]
    # another W: a turtle beside a devil costs 1, so the empty machine is the optimum's
    other = ((2, 1, 8, 0), (4, 3, 16, 0), (8, 12, 10, 1), (0, 0, 0, 0))
    log = [good[0], ("bind", "turtle", "fake_node_0", 2.0)]
    assert _replay(log, weights=other)[0] == []
    faults, _ = _replay(log)
    assert "cost 1 by CoCo's equation, the optimum of the round is 0" in faults[0]
    # a completion with no Binding on record, an eviction
    assert "completed with no Binding" in _replay([("done", "sheep", "", 1.0)])[0][0]
    assert "without preemption" in _replay([("evict", "sheep", "fake_node_0", 1.0)])[0][0]


def test_the_reference_counts_2500_for_a_pod_left_waiting_and_wants_every_slot_taken():
    batches = [(0.5, ["devil", "sheep", "rabbit"])]
    full = [("bind", "devil", "fake_node_0", 1.0), ("bind", "sheep", "fake_node_1", 1.0)]
    faults, facts = _replay(full, slots=1, batches=batches)
    assert faults == [] and facts["served_cost"] == 2500 == facts["optimum_cost"]
    assert facts["rounds_that_left_pods_waiting"] == 1 == facts["pods_left_waiting_at_most"]
    # one slot stayed free while two pods waited
    faults, _ = _replay(full[:1], slots=1, batches=batches)
    assert any("2 pods waited after a round that bound 1 with 2 slots free" in f for f in faults)
    assert any("cost 5000 by CoCo's equation, the optimum of the round is 2500" in f for f in faults)
    # the pod that waited is in the next round's batch, with the pod handed over since
    later = full + [("done", "devil", "", 2.0), ("bind", "rabbit", "fake_node_0", 3.0)]
    faults, facts = _replay(later, slots=1, batches=batches + [(2.5, ["sheep2"])])
    assert faults == [] and facts["served_cost"] == 2500 + 2500
    # a pod no poll handed over
    faults, _ = _replay(full[:1] + [("bind", "turtle", "fake_node_1", 3.0)], batches=[(0.5, ["devil"])])
    assert len(faults) == 1 and "a pod no poll handed over" in faults[0]


def test_reference_round_is_the_optimum_of_a_round_by_hand():
    census = np.array([[0, 0, 2, 0], [1, 0, 0, 0], [0, 0, 0, 0]])
    # free: 0, 1, 2 of 2 slots; a sheep costs 16 / 2 / 0, a devil 20 / 8 / 0
    assert ref.cost_matrix(census).tolist() == [[16, 2, 0], [32, 4, 0], [20, 8, 0], [2, 0, 0]]
    assert ref.reference_round([1, 0, 0, 0], census, 2) == 0
    assert ref.reference_round([2, 0, 1, 0], census, 2) == 2  # two on the empty one, a sheep beside the sheep
    assert ref.reference_round([2, 0, 2, 0], census, 2) == 2 + 2500  # three slots for four pods
    assert ref.cost_matrix(np.array([[0, 0, 300, 0]]))[1, 0] == 2000  # the clamp


def test_handed_over_reads_the_polls_counts_in_the_plans_order():
    config = spec.rehearsal_config(_config())
    with open(os.path.join(ROOT, "benchmarks", "traffic", "trickle.json")) as f:
        plan = build_plan(json.load(f), config, 7, 1.0)
    fixed = len(plan.resident) + sum(len(b) for b in plan.class_sweep)
    polls = [(0.0, 1.0, len(plan.resident)), (1.0, 1.1, 0), (1.1, 1.2, 16), (1.2, 1.3, 8),
             (2.0, 2.1, 3), (3.0, 3.1, 2), (9.0, 9.1, len(plan.closing))]
    got = interference_coco.handed_over(plan, polls)
    assert [t for t, _p in got] == [1.0, 1.2, 1.3, 2.1, 3.1, 9.1]
    assert got[0][1] == [p for p, _c in plan.resident] and got[-1][1] == [p for p, _c in plan.closing]
    assert got[3][1] == ["p0", "p1", "p2"] and got[4][1] == ["p3", "p4"]
    assert sum(len(p) for _t, p in got) == fixed + 5 + len(plan.closing)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "waves.json")) as f:
        waves = build_plan(json.load(f), config, 7, 1.0)
    w = waves.wave_pods
    got = interference_coco.handed_over(waves, [(0.0, 1.0, fixed), (1.0, 2.0, w), (2.0, 3.0, w + len(waves.closing))])
    assert got[1][1] == [p for p, _c in waves.wave(0)]
    assert got[2][1] == [p for p, _c in waves.wave(1)] + [p for p, _c in waves.closing]


def _table(pu, running=None):
    pu = np.asarray(pu, np.int32)
    live = pu > -2
    counts = np.bincount(pu[pu >= 0], minlength=4)
    return {"live": live, "pu": np.where(live, pu, -1),
            "pu_running": counts if running is None else np.asarray(running)}


def test_array_round_holds_the_fetched_table_to_books_from_the_record():
    bound = {"a": "fake_node_0", "b": "fake_node_1", "c": "fake_node_1"}
    rows = {"a": 0, "b": 2, "c": 3, "waits": 1}
    check = functools.partial(array_round.table_faults, bound=bound, row_of=rows, nodes=NODES,
                              pus_per_machine=2, slots_per_pu=2)
    facts = {}
    assert check(_table([1, -1, 2, 3, -2]), facts=facts) == []  # row 1 waits, row 4 is free
    assert (facts["rows_live"], facts["rows_placed"], facts["pu_peak"]) == (4, 3, 1)
    assert "1 pods the record has bound are not on a PU of their node" in check(
        _table([2, -1, 2, 3, -2]), facts={})[0]  # `a` sits on node 1
    faults = check(_table([1, 0, 2, 3, -2]), facts={})  # a row placed that the record does not know
    assert faults == ["the device's table has 4 rows placed, the record 3 pods bound"]
    faults = check(_table([1, -1, 2, 3, -2], running=[0, 2, 1, 1]), facts={})
    assert "pu_running differs from a recount of the pu column on 1 PUs (first: PU 1 says 2, holds 1)" in faults[0]
    faults = check(_table([1, -1, 2, 2, -2], running=[0, 1, 3, 0]), facts={})
    assert any("a PU holds 3 pods, it has 2 slots" in f for f in faults)
    assert "no table on the device" in array_round.check(SimpleNamespace(svc=SimpleNamespace()))[0]


# -- what stays true of what earlier tests pinned ----------------------------------------------

#: entries whose list earlier tests pin to "every cell of the benchmark" (but the rollout, for
#: some), or to no list at all: twenty of the twenty-eight cases this cell made false
PINNED_TO_EVERY_CELL = SILENT[4:] + ("round_unnamed_ms", "stats_children_gathered") + (
    "apply_full_walks", "apply_nodes_visited", "ec_purge_ms", "ec_purges", "journal_apply_ms",
    "journal_changes", "journal_collect_ms", "problem_snapshot_ms", "res_arcs_changed",
    "res_nodes_visited", "task_refresh_ms", "runnable_tasks_scanned",
)


@pytest.mark.parametrize("name", PINNED_TO_EVERY_CELL)
def test_an_entry_pinned_to_every_cell_still_equals_its_file_and_lists_accepted_cells_only(name):
    entry = _entry("per_layer", name)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key]
    cells = entry["workloads"]
    assert set(cells) <= set(ACCEPTED) and len(cells) in (11, 12) and len(set(cells)) == len(cells)
    assert [c for c in ACCEPTED if c in cells] == cells  # in the order the cells were appended
    loaded = {c: {m["name"] for m in spec.load_cell(c).per_layer} for c in (CELL, CONTROL)}
    assert name in loaded[CONTROL] and name not in loaded[CELL]



def test_what_stays_true_of_the_pins_this_cell_made_false():
    # test_benchmark_seams.py pins every cell's plan to a digest it has on file: this cell
    # names no pods module, so its pods are `class_only`'s, and its plan is the control's
    cell, control = spec.load_cell(CELL), spec.load_cell(CONTROL)
    a = build_plan(cell.traffic, cell.config, 11, 40.0)
    b = build_plan(control.traffic, control.config, 11, 40.0)
    assert a.resident == b.resident and a.victims == b.victims and a.closing == b.closing
    assert np.array_equal(a.arrival_offsets_s, b.arrival_offsets_s)
    assert np.array_equal(a.arrival_classes, b.arrival_classes)
    # the ten entries that gained a list still load in every accepted cell that loaded them
    for name in ACCEPTED:
        assert set(SILENT) <= {m["name"] for m in spec.load_cell(name).per_layer}
