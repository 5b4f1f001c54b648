"""The five per-layer metrics of the slot plan's re-fit (PR 41): each entry
equals its file and loads, by name, in the six scan-CSR cells that list it
and in no other; its reader (`round_field`, which the benchmark had) gives
the expected number on two synthetic records and nothing, without raising,
on a program that stamps no such field (the parent); and the rehearsals of
the two claimed cells print all five, `correct`, with no program compiled
in the window. Entries are looked up by name: nothing here depends on where
one stands or on how many there are."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import observe, spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
SEED = 2147483741  # more than 32 signed bits hold, as the driver's are
#: the cells whose service keeps a slot plan, less `k8s-5000-preemption.rollout`:
#: tests/benchmark/test_benchmark_preemption.py pins that cell's metrics to the
#: set PR 38 left, and no file the benchmark has is this PR's to edit
CELLS = [
    "trivial-10kx1k.trickle", "trivial-10kx1k.waves", "trivial-10kx1k-resident.trickle",
    "trivial-10kx1k-resident.waves", "k8s-5000-antiaffinity.trickle",
    "k8s-5000-zonespread.trickle",
]
CLAIMED = ["trivial-10kx1k.trickle", "k8s-5000-zonespread.trickle"]
#: metric -> (field, reduce, unit, value on RECORDS)
NEW = {
    "plan_rows": ("plan_rows", "p50", "rows", 98304.0),
    "plan_rows_live": ("plan_rows_live", "p50", "rows", 34000.0),
    "plan_refits": ("plan_refits", "sum", "count", 1.0),
    "plan_regrowths": ("plan_regrowths", "sum", "count", 0.0),
    "plan_relayouts": ("plan_relayouts", "sum", "count", 3.0),
}
RECORDS = [
    {"num_scheduled": 8, "plan_rows": 131072, "plan_rows_live": 35000, "plan_refits": 1,
     "plan_regrowths": 0, "plan_relayouts": 2},
    {"num_scheduled": 4, "plan_rows": 65536, "plan_rows_live": 33000, "plan_refits": 0,
     "plan_regrowths": 0, "plan_relayouts": 1},
    {"num_scheduled": 0, "plan_rows": 7, "plan_rows_live": 7, "plan_refits": 7,
     "plan_regrowths": 7, "plan_relayouts": 7},  # bound nothing: no sample
]


def _observation(records):
    return observe.Observation(
        device_kind="cpu", rounds=[], records=records, client={}, counters={}, shapes={},
        trace=None, rehearsal=True,
    )


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_equals_its_file_and_lists_the_scan_csr_cells(name):
    field, reduce_, unit, _value = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == ("round_field", {"field": field, "reduce": reduce_})
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"], entry["layer"]) == (
        unit, "lower", "program_counter", "bind_p50_ms", "graph update / export",
    )
    assert entry["workloads"] == CELLS and len(own["what"]) > 40
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_loads_the_five_by_name_if_it_is_listed_and_none_otherwise(cell):
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert (loaded & set(NEW)) == (set(NEW) if cell in CELLS else set())


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_reader_reads_the_records_and_nothing_from_a_program_without_the_field(name):
    field, reduce_, _unit, value = NEW[name]
    read = importlib.import_module("benchmarks.readers.round_field").read
    params = {"field": field, "reduce": reduce_}
    assert read(params, _observation(RECORDS)) == value
    parent = [{k: v for k, v in r.items() if not k.startswith("plan_")} for r in RECORDS]
    assert read(params, _observation(parent)) is None


@pytest.fixture(scope="module", params=CLAIMED)
def rehearsed(request):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", request.param, "--seed", str(SEED),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_rehearsal_of_a_claimed_cell_prints_the_five_and_is_correct(rehearsed):
    metrics = {k: v["value"] for k, v in rehearsed["metrics"].items()}
    assert rehearsed["correct"] and rehearsed["failed"] == 0, rehearsed["facts"]["faults"]
    assert set(NEW) <= set(metrics)
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["plan_rows_live"] < metrics["plan_rows"]
    rows = int(metrics["plan_rows"])
    assert rows & (rows - 1) == 0  # a pow2 bucket, whichever the 1/40 cluster lands in
    assert metrics["plan_regrowths"] == 0 and metrics["plan_refits"] <= 1
    assert metrics["plan_relayouts"] >= metrics["plan_refits"]
    assert all(rehearsed["metrics"][k]["unit"] == NEW[k][2] for k in NEW)
