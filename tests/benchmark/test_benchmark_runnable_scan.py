"""The per-layer metric of the event-fed scan for runnable tasks (PR 44):
the entry equals its file and loads, by name, in the nine cells that list
it and not in `k8s-5000-preemption.rollout`; its reader (`round_field`,
which the benchmark had) gives the expected number on synthetic records
and nothing, without raising, on a program that stamps no such field (the
parent); and the rehearsals of the two claimed cells print it beside
`batch_pods_p50`, equal to it, `correct`, with no program compiled in the
window. The entry is looked up by name: nothing here depends on where it
stands or on how many there are."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import observe, spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
SEED = 2147483783  # more than 32 signed bits hold, as the driver's are
NAME = "runnable_tasks_scanned"
PARAMS = {"field": NAME, "reduce": "p50"}
#: every cell but `k8s-5000-preemption.rollout`:
#: tests/benchmark/test_benchmark_preemption.py pins that cell's metrics to
#: the set PR 38 left, and no file the benchmark has is this PR's to edit
CELLS = [
    "trivial-10kx1k.trickle", "trivial-10kx1k.waves", "coco-50kx1k.trickle",
    "coco-50kx1k.waves", "trivial-10kx1k-resident.trickle", "trivial-10kx1k-resident.waves",
    "k8s-5000-antiaffinity.trickle", "k8s-5000-zonespread.trickle",
    "gtrace-12500-quincy.trickle",
]
CLAIMED = ["trivial-10kx1k.waves", "trivial-10kx1k-resident.waves"]
RECORDS = [
    {"num_scheduled": 10000, NAME: 10000},  # the fill: the round that meets the job
    {"num_scheduled": 3, NAME: 3},
    {"num_scheduled": 5, NAME: 5},
    {"num_scheduled": 0, NAME: 700},  # bound nothing: no sample
]


def _observation(records):
    return observe.Observation(
        device_kind="cpu", rounds=[], records=records, client={}, counters={}, shapes={},
        trace=None, rehearsal=True,
    )


def test_the_entry_equals_its_file_and_lists_the_nine_cells():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", NAME + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == ("round_field", PARAMS)
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"], entry["layer"]) == (
        "count", "lower", "program_counter", "bind_p50_ms", "service loop",
    )
    assert entry["workloads"] == CELLS and len(own["what"]) > 40
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # the layer is one the benchmark names already, letter for letter
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_loads_it_by_name_if_it_is_listed_and_not_otherwise(cell):
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert (NAME in loaded) == (cell in CELLS)
    assert "batch_pods_p50" in loaded and "runnable_scan_ms" in loaded


def test_the_reader_reads_the_records_and_nothing_from_a_program_without_the_field():
    read = importlib.import_module("benchmarks.readers.round_field").read
    assert read(PARAMS, _observation(RECORDS)) == 5.0
    parent = [{k: v for k, v in r.items() if k != NAME} for r in RECORDS]
    assert read(PARAMS, _observation(parent)) is None
    assert read(PARAMS, _observation([])) is None


def test_the_program_stamps_the_field_on_its_timing_and_its_record():
    import dataclasses

    from ksched_tpu.runtime.trace import RoundRecord
    from ksched_tpu.scheduler.flow_scheduler import RoundTiming

    for stamped in (RoundTiming, RoundRecord):
        assert NAME in {f.name for f in dataclasses.fields(stamped)}


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


def test_the_rehearsal_of_a_claimed_cell_scans_its_batch_and_is_correct(rehearsed):
    metrics = {k: v["value"] for k, v in rehearsed["metrics"].items()}
    assert rehearsed["correct"] and rehearsed["failed"] == 0, rehearsed["facts"]["faults"]
    assert metrics["compiles_in_window"] == 0
    assert rehearsed["metrics"][NAME]["unit"] == "count"
    # a wave is admitted whole and bound whole: what the scan looks at is the
    # wave, though the tree holds every wave before it
    assert metrics[NAME] == metrics["batch_pods_p50"] > 0
    assert metrics["runnable_scan_ms"] > 0
