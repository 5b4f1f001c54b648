"""The per-layer metric of scan-CSR's active-set superstep (PR 50):
`supersteps_sparse_p50`'s entry equals its file and loads, by name, in the
eight cells `plan_rows` lists (the cells that solve on a slot plan and whose
metric set is not pinned) and in no other: not in the three dense cells, not
in `k8s-5000-preemption.rollout`; its reader (`round_field`, which the
benchmark had) gives the expected number on synthetic records and nothing,
without raising, on a program that stamps no such field (the parent); the
rehearsal of a claimed cell prints it beside `supersteps_p50`, `correct`.

The entry stands after PR 49's four and lists PR 49's cell, which makes three
pins of `test_benchmark_requests.py` false (that its four are the last of
`per_layer`, that PR 46's six stand right before them, that its cell's metrics
are exactly the ones it names): expected failures since this PR
(tests/conftest.py); what stays true of each is held here, a case an entry."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import observe, spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
SEED = 2147483893  # more than 32 signed bits hold, as the driver's are
NAME = "supersteps_sparse_p50"
PARAMS = {"field": "supersteps_sparse", "reduce": "p50"}
REQUESTS = "k8s-5000-requests.trickle"
WHAREMAP = "gtrace-12500-wharemap.trickle"
#: PR 49's four entries and PR 46's six before them (test_benchmark_requests.py)
BROUGHT = ("requests_costs_ms", "books_machines_dirty", "machines_gated", "columns_offered")
WHAREMAPS = ("collapse_rows", "collapse_cols", "audit_tasks_grouped", "census_machines_dirty",
             "ec_arcs_repriced", "platform_costs_ms")
RECORDS = [
    {"num_scheduled": 150000, "solver_work": 2, "supersteps_sparse": 0},  # the fill: all bulk
    {"num_scheduled": 28, "solver_work": 10, "supersteps_sparse": 8},
    {"num_scheduled": 19, "solver_work": 10, "supersteps_sparse": 10},
    {"num_scheduled": 0, "solver_work": 9, "supersteps_sparse": 9},  # bound nothing: no sample
]


def _entry(name):
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


def _file(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        return json.load(f)


def _observation(records):
    return observe.Observation(
        device_kind="cpu", rounds=[], records=records, client={}, counters={}, shapes={},
        trace=None, rehearsal=True,
    )


def test_the_entry_equals_its_file_and_lists_the_cells_of_plan_rows():
    entry, own = _entry(NAME), _file(NAME)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == ("round_field", PARAMS)
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"], entry["layer"]) == (
        "supersteps", "higher", "program_counter", "bind_p50_ms", "solver rungs",
    )
    assert entry["workloads"] == _entry("plan_rows")["workloads"] and len(entry["workloads"]) == 8
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["layer"] == _entry("supersteps_p50")["layer"] and len(own["what"]) > 40
    assert BENCH["per_layer"][-1] is entry  # appended: nothing that was there moved
    assert spec.check_names(BENCH) == [] and len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_loads_it_by_name_if_it_solves_on_a_slot_plan_and_not_otherwise(cell):
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert (NAME in loaded) == ("plan_rows" in loaded) == (cell in _entry(NAME)["workloads"])
    assert "supersteps_p50" in loaded  # which it is a share of, everywhere
    if cell.startswith("coco-") or cell in (WHAREMAP, "k8s-5000-preemption.rollout"):
        assert NAME not in loaded


def test_the_reader_reads_the_records_and_nothing_from_a_program_without_the_field():
    read = importlib.import_module("benchmarks.readers.round_field").read
    assert read(PARAMS, _observation(RECORDS)) == 8.0
    parent = [{k: v for k, v in r.items() if k != "supersteps_sparse"} for r in RECORDS]
    assert read(PARAMS, _observation(parent)) is None
    assert read(PARAMS, _observation([])) is None


# -- what stays true of the pins this entry made false --------------------------------------


@pytest.mark.parametrize("name", BROUGHT)
def test_each_metric_pr_49_brought_is_still_its_file_for_its_cell_alone(name):
    entry, own = _entry(name), _file(name)
    assert entry["workloads"] == [REQUESTS]
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    names = [m["name"] for m in BENCH["per_layer"]]
    # after everything that was there before PR 49, and before what PR 50 appended
    before = [n for n in names if n not in BROUGHT and n != NAME]
    assert max(names.index(n) for n in before) < names.index(name) < names.index(NAME)
    assert name not in {m["name"] for m in spec.load_cell(WHAREMAP).per_layer}


@pytest.mark.parametrize("name", WHAREMAPS)
def test_the_six_entries_of_pr_46_still_stand_right_before_pr_49s_four(name):
    entry, own = _entry(name), _file(name)
    assert entry["workloads"] == ([WHAREMAP, REQUESTS] if name == "ec_arcs_repriced" else [WHAREMAP])
    names = [m["name"] for m in BENCH["per_layer"]]
    before = [n for n in names if n not in BROUGHT and n not in WHAREMAPS and n != NAME]
    assert max(names.index(n) for n in before) < names.index(name) < min(names.index(n) for n in BROUGHT)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }


def test_the_requests_cell_is_still_the_twelfth_and_reads_what_it_read_and_this():
    names = [e["name"] for e in BENCH["workloads"]]
    assert len(names) == len(set(names)) == 12 and names[-1] == REQUESTS
    assert not any(e["chips"] == 4 for e in BENCH["workloads"])
    cell = spec.load_cell(REQUESTS)
    assert {m["name"] for m in cell.end_to_end} == {"bind_p50_ms", "setup_s"}
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    listed = {m["name"] for m in BENCH["per_layer"] if REQUESTS in m.get("workloads", ())}
    assert {m["name"] for m in cell.per_layer} == everywhere | listed
    assert set(BROUGHT) | {NAME, "plan_rows", "ec_arcs_repriced"} <= listed
    assert not listed & {"collapse_audit_ms", "transport_ms", "collapse_rows"}  # no dense-rung list


# -- the rehearsal --------------------------------------------------------------------------


def test_the_rehearsal_of_a_claimed_cell_prints_it_beside_the_supersteps():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", REQUESTS,
         "--seed", str(SEED), "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0
    # a rehearsal's plan (16,384 rows) lies below `_ACTIVE_MIN_PLAN_ROWS`: the dense-only
    # program, which stamps 0; at the cell's size the chip says how many (PERF.md section 5)
    assert 0.0 <= metrics[NAME] <= metrics["supersteps_p50"]
    assert out["metrics"][NAME]["unit"] == "supersteps"
