"""The nineteen per-layer metrics appended for the spans and counters the
program has stamped since PR 25: each entry equals its file, loads in its
cells and in no other, and its reader (one the benchmark had) returns the
expected number on two synthetic rounds and nothing, without raising, on
a program that has no such span or field. Entries are looked up by name:
nothing here depends on where one stands or on how many there are."""

import importlib
import json
import os

import pytest

from benchmarks import observe, spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
RESIDENT = [c for c in CELLS if c.startswith("trivial-10kx1k-resident.")]
GU, DAP = "graph update / export", "decode / apply / post"
SPAN, FIELD = ("span_sum", "program_span"), ("round_field", "program_counter")
#: metric -> (reader and source, params, unit, layer, cells, value on ROUNDS / RECORDS)
NEW = {
    "stats_ms": (SPAN, {"spans": ["stats"], "reduce": "p50"}, "ms", GU, CELLS, 2.0),
    "graph_refresh_ms": (SPAN, {"spans": ["graph_update"], "reduce": "p50"}, "ms", GU, CELLS, 18.0),
    "ec_refresh_ms": (SPAN, {"spans": ["ec_refresh"], "reduce": "p50"}, "ms", GU, CELLS, 3.0),
    "decode_deltas_ms": (SPAN, {"spans": ["decode", "deltas"], "reduce": "p50"}, "ms", DAP,
                         CELLS, 0.75),
    "apply_walk_ms": (SPAN, {"spans": ["apply"], "reduce": "p50"}, "ms", DAP, CELLS, 15.0),
    "bindings_post_ms": (SPAN, {"spans": ["bindings_post"], "reduce": "p50"}, "ms", DAP,
                         [c for c in CELLS if c != "trivial-10kx1k-resident.waves"], 0.25),
    "runnable_scan_ms": (SPAN, {"spans": ["runnable_scan"], "reduce": "p50"}, "ms",
                         "service loop", CELLS, 3.0),
    "graph_tasks_visited": (FIELD, {"field": "graph_tasks_visited", "reduce": "p50"}, "count", GU,
                            CELLS, 9.0),
    "stats_nodes_visited": (FIELD, {"field": "stats_nodes_visited", "reduce": "p50"}, "count", GU,
                            CELLS, 3022.0),
    "stats_full_walks": (FIELD, {"field": "stats_full_walk", "reduce": "sum"}, "count", GU,
                         CELLS, 1.0),
    "decode_tasks": (FIELD, {"field": "decode_tasks", "reduce": "p50"}, "count", DAP, CELLS, 10.0),
    "ec_arcs_changed": (FIELD, {"field": "ec_arcs_changed", "reduce": "p50"}, "count", GU,
                        CELLS, 30.0),
    "unscheduled_by_rule": (FIELD, {"field": "unscheduled_by_rule", "reduce": "sum"}, "count",
                            "solver dispatch", CELLS, 2.0),
    "upload_ms": (SPAN, {"spans": ["delta_pack", "delta_upload", "plan_upload"], "reduce": "p50"},
                  "ms", GU, RESIDENT, 4.0),
    "upload_bytes": (FIELD, {"field": "upload_bytes", "reduce": "p50"}, "B", GU, RESIDENT,
                     120_000.0),
    "full_uploads": (FIELD, {"field": "upload_full", "reduce": "sum"}, "count", GU, RESIDENT, 1.0),
    "solve_dispatch_ms": (SPAN, {"spans": ["solve_dispatch"], "reduce": "p50"}, "ms",
                          "solver rungs", RESIDENT, 17.0),
    "solve_sync_ms": (SPAN, {"spans": ["solve_sync"], "reduce": "p50"}, "ms", "solver rungs",
                      RESIDENT, 145.0),
    "post_defer_ms": (FIELD, {"field": "post_defer_ms", "reduce": "p50"}, "ms", DAP, RESIDENT,
                      62.0),
}
#: two solved rounds and an idle sweep, as the tracers give them
ROUNDS = [
    {"round": 200.0, "stats": 1.0, "graph_update": 16.0, "ec_refresh": 2.0, "decode": 0.4,
     "deltas": 0.1, "apply": 14.0, "bindings_post": 0.2, "runnable_scan": 2.0,
     "solve_dispatch": 16.0, "delta_pack": 1.0, "delta_upload": 1.5, "plan_upload": 0.5,
     "solve_sync": 140.0},
    {"round": 210.0, "stats": 3.0, "graph_update": 20.0, "ec_refresh": 4.0, "decode": 0.8,
     "deltas": 0.2, "apply": 16.0, "bindings_post": 0.3, "runnable_scan": 4.0,
     "solve_dispatch": 18.0, "delta_pack": 2.0, "delta_upload": 2.5, "plan_upload": 0.5,
     "solve_sync": 150.0},
    {"service_round": 1.0, "bindings_post": 50.0, "runnable_scan": 50.0},  # not solved
]
RECORDS = [
    {"num_scheduled": 8, "graph_tasks_visited": 8, "stats_nodes_visited": 43, "stats_full_walk": 0,
     "decode_tasks": 8, "ec_arcs_changed": 20, "unscheduled_by_rule": 0,
     "upload_bytes": 100_000, "upload_full": 0, "post_defer_ms": 60.0},
    {"num_scheduled": 12, "graph_tasks_visited": 10, "stats_nodes_visited": 6001,
     "stats_full_walk": 1, "decode_tasks": 12, "ec_arcs_changed": 40, "unscheduled_by_rule": 2,
     "upload_bytes": 140_000, "upload_full": 1, "post_defer_ms": 64.0},
    {"num_scheduled": 0, "graph_tasks_visited": 500, "stats_nodes_visited": 6001,
     "stats_full_walk": 1, "decode_tasks": 500, "ec_arcs_changed": 900, "unscheduled_by_rule": 7,
     "upload_bytes": 9, "upload_full": 1, "post_defer_ms": 900.0},  # bound nothing: no sample
]


def _obs(spans, records):
    rounds = [observe.Round(t0=0.0, t1=1.0, pods=1, solve=True, spans_ms=dict(s)) for s in spans]
    return observe.Observation(
        device_kind="cpu", rounds=rounds, records=list(records), client={}, counters={}, shapes={},
    )


def test_the_nineteen_are_entries_and_the_entries_before_them_are_the_parents():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(NEW) == 19 and set(NEW) <= set(names) and len(set(names)) == len(names)
    for older in ("round_p50_ms", "graph_update_ms", "apply_ms", "solve_roofline",
                  "queue_wait_ms", "round_accounted_share"):
        assert older in names and older not in NEW
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(spec.HERE, "layer_metrics", m["name"] + ".json"))


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_appended_metric_is_its_file_loads_in_its_cells_and_reads_what_it_names(name):
    (reader, source), params, unit, layer, cells, expected = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    own = json.load(open(os.path.join(spec.HERE, "layer_metrics", name + ".json")))
    # the entry is the file's head, plus its cell list where it has one
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
        "moves": "bind_p50_ms",
    }
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == (reader, params) and own["what"]
    assert entry.get("workloads", CELLS) == cells and ("workloads" in entry) == (cells != CELLS)
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    # it loads in its cells and in no other
    for cell in CELLS:
        loaded = {m["name"]: m for m in spec.load_cell(cell).per_layer}
        assert (name in loaded) == (cell in cells), cell
    # a reader the benchmark had reads it: the two solved rounds, or the two
    # records that bound a pod
    read = importlib.import_module(f"benchmarks.readers.{reader}").read
    assert reader in ("span_sum", "round_field")
    assert read(params, _obs(ROUNDS, RECORDS)) == pytest.approx(expected)
    # and finds nothing, without raising, on a program that lacks it
    older = _obs([{"round": 200.0, "graph_export": 12.0, "backend_solve": 110.0}],
                 [{"num_scheduled": 25, "queue_wait_ms": 140.0}])
    assert read(params, older) is None
    assert read(params, _obs([], [])) is None


def test_the_halves_add_up_to_the_metrics_they_split():
    """`graph_update_ms` = `stats` + `graph_update`; `apply_ms` = `decode` +
    `deltas` + `apply` + `bindings_post`: the new files name the same spans,
    each once."""
    def spans(name):
        path = os.path.join(spec.HERE, "layer_metrics", name + ".json")
        return json.load(open(path))["params"]["spans"]

    assert sorted(spans("stats_ms") + spans("graph_refresh_ms")) == sorted(spans("graph_update_ms"))
    parts = spans("decode_deltas_ms") + spans("apply_walk_ms") + spans("bindings_post_ms")
    assert sorted(parts) == sorted(spans("apply_ms"))
