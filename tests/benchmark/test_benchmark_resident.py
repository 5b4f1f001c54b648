"""The `trivial-10kx1k-resident` deployment and its two cells: the
configuration is `trivial-10kx1k` plus the two flags and nothing else, both
cells rehearse `correct` with no program compiled in the window, and what a
resident round adds (three export spans, the two halves of a pipelined
solve, three RoundRecord fields) is read by readers the benchmark has, from
parameters alone. The six metrics were a proposal (PROPOSED) until a
`benchmark` PR could append them; they are entries of BENCHMARK.json now,
each with its `layer_metrics/<name>.json`, for the two resident cells."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import observe, spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CELLS = ("trivial-10kx1k-resident.trickle", "trivial-10kx1k-resident.waves")
#: metric -> (reader, params, unit, source, layer); each moves bind_p50_ms.
#: What PR 26 proposed, and what the committed entries and files now say.
PROPOSED = {
    "upload_ms": ("span_sum", {"spans": ["delta_pack", "delta_upload", "plan_upload"],
                               "reduce": "p50"}, "ms", "program_span", "graph update / export"),
    "upload_bytes": ("round_field", {"field": "upload_bytes", "reduce": "p50"},
                     "B", "program_counter", "graph update / export"),
    "full_uploads": ("round_field", {"field": "upload_full", "reduce": "sum"},
                     "count", "program_counter", "graph update / export"),
    "solve_dispatch_ms": ("span_sum", {"spans": ["solve_dispatch"], "reduce": "p50"},
                          "ms", "program_span", "solver rungs"),
    "solve_sync_ms": ("span_sum", {"spans": ["solve_sync"], "reduce": "p50"},
                      "ms", "program_span", "solver rungs"),
    "post_defer_ms": ("round_field", {"field": "post_defer_ms", "reduce": "p50"},
                      "ms", "program_counter", "decode / apply / post"),
}
#: two resident rounds as the tracers give them, and their value per metric
RESIDENT_SPANS = [
    {"round": 200.0, "solve_dispatch": 16.0, "graph_export": 12.0, "delta_pack": 1.0,
     "delta_upload": 1.5, "plan_upload": 0.5, "solve_sync": 140.0},
    {"round": 210.0, "solve_dispatch": 18.0, "graph_export": 14.0, "delta_pack": 2.0,
     "delta_upload": 2.5, "plan_upload": 0.5, "solve_sync": 150.0},
]
RESIDENT_RECORDS = [
    {"num_scheduled": 25, "upload_bytes": 100_000, "upload_full": 0, "post_defer_ms": 60.0},
    {"num_scheduled": 30, "upload_bytes": 140_000, "upload_full": 1, "post_defer_ms": 64.0},
    {"num_scheduled": 0, "upload_bytes": 0, "upload_full": 0, "post_defer_ms": 0.0},
]
EXPECTED = {
    "upload_ms": 4.0, "upload_bytes": 120_000.0, "full_uploads": 1.0,
    "solve_dispatch_ms": 17.0, "solve_sync_ms": 145.0, "post_defer_ms": 62.0,
}


def _obs(spans, records):
    rounds = [observe.Round(t0=0.0, t1=1.0, pods=1, solve=True, spans_ms=dict(s)) for s in spans]
    return observe.Observation(
        device_kind="cpu", rounds=rounds, records=list(records), client={}, counters={}, shapes={},
    )


def _read(name, obs):
    reader, params = PROPOSED[name][:2]
    return importlib.import_module(f"benchmarks.readers.{reader}").read(params, obs)


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(cell, trace):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed", "2147483659",
         "--seconds", "3", "--trace", str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced_trickle():
    return _rehearse(CELLS[0], 1)


@pytest.fixture(scope="module")
def control():
    return _rehearse("trivial-10kx1k.trickle", 1)


def test_the_configuration_is_its_control_plus_the_two_flags():
    ours, control = _config("trivial-10kx1k-resident"), _config("trivial-10kx1k")
    assert ours["argv"] == control["argv"] + ["--device-resident", "--pipeline"]
    differ = {k for k in set(ours) | set(control) if ours.get(k) != control.get(k)}
    assert differ == {"name", "source", "why", "argv", "guarantees", "assumed"}
    assert ours["reduced"] == []
    assert {k: ours["guarantees"][k] for k in control["guarantees"]} == control["guarantees"]
    assert set(ours["guarantees"]) - set(control["guarantees"]) == {"resident"}
    assert ours["assumed"][: len(control["assumed"])] == control["assumed"]
    entry = next(c for c in BENCH["configs"] if c["name"] == ours["name"])
    assert entry["source"] == ours["source"] and entry["reduced"] == []
    assert "north_star" in entry["source"] and "configs[1]" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_takes_one_chip_and_the_traffic_as_it_stands(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert w["config"] == "trivial-10kx1k-resident" and w["chips"] == 1
    assert w["traffic"] == cell.rsplit(".", 1)[1] and len(w["why"]) <= 200
    loaded = spec.load_cell(cell)
    assert {m["name"] for m in loaded.end_to_end} == {"bind_p50_ms", "setup_s"}
    control = spec.load_cell(cell.replace("-resident", ""))
    assert loaded.traffic == control.traffic


def test_per_layer_is_the_parents_and_the_new_cells_report_every_metric_without_a_list():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(PROPOSED) <= set(by_name)
    for name in PROPOSED:
        assert by_name[name]["workloads"] == list(CELLS)
        assert os.path.exists(os.path.join(spec.HERE, "layer_metrics", name + ".json"))
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for cell in CELLS:
        loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
        assert everywhere | set(PROPOSED) <= loaded
        assert loaded - everywhere == {
            m["name"] for m in BENCH["per_layer"] if cell in m.get("workloads", ())
        }
    # and no other cell reports one of the six
    for w in BENCH["workloads"]:
        if w["name"] not in CELLS:
            assert not set(PROPOSED) & {m["name"] for m in spec.load_cell(w["name"]).per_layer}


@pytest.mark.parametrize("name", sorted(PROPOSED))
def test_a_reader_that_exists_reads_what_a_resident_round_adds(name):
    reader, params, unit, source, layer = PROPOSED[name]
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "readers", reader + ".py"))
    assert spec.UNIT_RE.match(unit) and source in spec.SOURCES
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in PROPOSED}
    assert _read(name, _obs(RESIDENT_SPANS, RESIDENT_RECORDS)) == pytest.approx(EXPECTED[name])
    # the committed entry and its file are the proposal, word for word
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    with open(os.path.join(spec.HERE, "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert (own["reader"], own["params"]) == (reader, params)
    for m in (entry, own):
        assert (m["unit"], m["source"], m["layer"], m["better"], m["moves"]) == (
            unit, source, layer, "lower", "bind_p50_ms"
        )


@pytest.mark.parametrize("name", sorted(PROPOSED))
def test_the_reader_finds_nothing_on_a_program_that_lacks_it_and_does_not_raise(name):
    synchronous = [{"round": 200.0, "graph_export": 12.0, "backend_solve": 110.0}]
    parents = [{"num_scheduled": 25, "queue_wait_ms": 140.0}]
    assert _read(name, _obs(synchronous, parents)) is None
    assert _read(name, _obs([], [])) is None


def test_the_traced_trickle_rehearsal_is_correct_and_compiles_nothing(traced_trickle):
    out = traced_trickle
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["compiles_in_window"]["value"] == 0.0
    assert out["metrics"]["device_round_share"]["value"] == 100.0
    assert out["facts"]["warmup_extensions"] == 0 and out["facts"]["shapes"]["path"] == "csr"
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]


def test_the_traced_trickle_rehearsal_reports_what_its_control_reports(traced_trickle, control):
    assert set(traced_trickle["metrics"]) == set(control["metrics"]) | set(PROPOSED)
    assert not set(PROPOSED) & set(control["metrics"])
    values = {k: v["value"] for k, v in traced_trickle["metrics"].items()}
    for name in ("upload_ms", "upload_bytes", "solve_dispatch_ms", "solve_sync_ms", "post_defer_ms"):
        assert values[name] > 0.0, name
    assert values["full_uploads"] >= 0.0
    assert traced_trickle["facts"]["checks"] == ["binding", "capacity", "answer", "resident"]
    assert control["facts"]["checks"] == ["binding", "capacity", "answer"]
    mirror = traced_trickle["facts"]["resident"]
    assert mirror["differ"] == 0 and mirror["mirror_entries"] > 0 and mirror["refreshes"] > 10


def test_the_untraced_waves_rehearsal_is_correct_and_compiles_nothing():
    out = _rehearse(CELLS[1], 0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}
    assert not any("compiled" in f for f in out["facts"]["faults"])


def test_the_control_rehearses_correct_on_the_same_problem(traced_trickle, control):
    assert control["correct"] is True, control["facts"]["faults"]
    assert control["facts"]["shapes"] == traced_trickle["facts"]["shapes"]
    assert control["attempted"] == traced_trickle["attempted"]
