"""The per-layer metrics appended for the spans one level below
`graph_update`, `collapse_audit` and `graph_export`, for the span on the EC
purge and the counters that go with them, and for two of the counters
`apply`'s refresh has stamped since PR 34: each entry equals its file, names
its cells, loads in them and in no other, and its reader (one the benchmark
had) returns the expected number on two synthetic rounds and nothing,
without raising, on a program that has no such span or field. Entries are
looked up by name: nothing here depends on where one stands or on how many
there are."""

import importlib
import json
import os

import pytest

from benchmarks import observe, spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
COCO = [c for c in CELLS if c.startswith("coco-50kx1k.")]
GU, SD, DAP = "graph update / export", "solver dispatch", "decode / apply / post"
SPAN, FIELD = ("span_sum", "program_span"), ("round_field", "program_counter")
AUDIT = {
    "audit_index": 3.0, "audit_pins": 9.0, "audit_subtrees": 6.0, "audit_task_arcs": 1.5,
    "audit_ec_routes": 1.0, "audit_escapes": 0.75, "audit_rows": 4.0,
}


def _span(name, layer, cells, expected):
    return (SPAN, {"spans": [name], "reduce": "p50"}, "ms", layer, cells, expected)


#: metric -> (reader and source, params, unit, layer, cells, value on ROUNDS / RECORDS)
NEW = {
    "task_refresh_ms": _span("task_refresh", GU, CELLS, 0.6),
    "journal_collect_ms": _span("journal_collect", GU, CELLS, 0.3),
    "journal_apply_ms": _span("journal_apply", GU, CELLS, 0.5),
    "problem_snapshot_ms": _span("problem_snapshot", GU, CELLS, 0.2),
    "ec_purge_ms": _span("ec_purge", GU, CELLS, 0.05),
    **{f"{name}_ms": _span(name, SD, COCO, value) for name, value in AUDIT.items()},
    "res_nodes_visited": (FIELD, {"field": "res_nodes_visited", "reduce": "p50"}, "count", GU,
                          CELLS, 6000.0),
    "res_arcs_changed": (FIELD, {"field": "res_arcs_changed", "reduce": "p50"}, "count", GU,
                         CELLS, 2.0),
    "journal_changes": (FIELD, {"field": "journal_changes", "reduce": "p50"}, "count", GU,
                        CELLS, 70.0),
    "ec_purges": (FIELD, {"field": "ec_purged", "reduce": "sum"}, "count", GU, CELLS, 3.0),
    "apply_nodes_visited": (FIELD, {"field": "apply_nodes_visited", "reduce": "p50"}, "count",
                            DAP, CELLS, 3022.0),
    "apply_full_walks": (FIELD, {"field": "apply_full_walk", "reduce": "sum"}, "count", DAP,
                         CELLS, 1.0),
}
#: two solved rounds and an idle sweep, as the tracers give them; a span that
#: opens once a run or once an EC node arrives summed over the round (no
#: resource node takes a turn since PR 36: what is not the tasks' is the ECs')
ROUNDS = [
    {"round": 60.0, "graph_update": 17.0, "task_refresh": 0.4, "ec_refresh": 14.0,
     "collapse_audit": 25.0, "graph_export": 1.0, "journal_collect": 0.2, "journal_apply": 0.4,
     "problem_snapshot": 0.1, "ec_purge": 0.04,
     **{name: value - 0.5 for name, value in AUDIT.items()}},
    {"round": 62.0, "graph_update": 19.0, "task_refresh": 0.8, "ec_refresh": 16.0,
     "collapse_audit": 27.0, "graph_export": 1.2, "journal_collect": 0.4, "journal_apply": 0.6,
     "problem_snapshot": 0.3, "ec_purge": 0.06,
     **{name: value + 0.5 for name, value in AUDIT.items()}},
    {"service_round": 1.0, "task_refresh": 50.0, "ec_purge": 50.0},  # not solved
]
RECORDS = [
    {"num_scheduled": 6, "res_nodes_visited": 6000, "res_arcs_changed": 0, "journal_changes": 60,
     "ec_purged": 0, "apply_nodes_visited": 43, "apply_full_walk": 0},
    {"num_scheduled": 9, "res_nodes_visited": 6000, "res_arcs_changed": 4, "journal_changes": 80,
     "ec_purged": 3, "apply_nodes_visited": 6001, "apply_full_walk": 1},
    {"num_scheduled": 0, "res_nodes_visited": 0, "res_arcs_changed": 900, "journal_changes": 9,
     "ec_purged": 7, "apply_nodes_visited": 6001, "apply_full_walk": 1},  # bound nothing: no sample
]


def _obs(spans, records):
    rounds = [observe.Round(t0=0.0, t1=1.0, pods=1, solve=True, spans_ms=dict(s)) for s in spans]
    return observe.Observation(
        device_kind="cpu", rounds=rounds, records=list(records), client={}, counters={}, shapes={},
    )


def _own(name):
    return json.load(open(os.path.join(spec.HERE, "layer_metrics", name + ".json")))


def test_every_one_is_an_entry_with_a_cell_list_and_the_spans_it_splits_stay():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(by_name) and len(by_name) == len(BENCH["per_layer"])
    # a later configuration's cells do not inherit them
    assert all(by_name[name].get("workloads") for name in NEW)
    for older in ("graph_refresh_ms", "graph_update_ms", "graph_export_ms", "collapse_audit_ms",
                  "ec_refresh_ms", "apply_walk_ms", "round_accounted_share"):
        assert older in by_name and older not in NEW


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_pass_metric_is_its_file_loads_in_its_cells_and_reads_what_it_names(name):
    (reader, source), params, unit, layer, cells, expected = NEW[name]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    own = _own(name)
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source, "layer": layer,
        "moves": "bind_p50_ms", "workloads": cells,
    }
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == (reader, params) and own["what"]
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
    older = _obs([{"round": 200.0, "graph_update": 18.0, "graph_export": 12.0,
                   "collapse_audit": 24.0, "apply": 1.0}],
                 [{"num_scheduled": 25, "queue_wait_ms": 140.0, "ec_arcs_changed": 12}])
    assert read(params, older) is None
    assert read(params, _obs([], [])) is None


def _spans(name):
    return _own(name)["params"]["spans"]


def test_the_children_name_each_span_once_and_none_their_parents():
    """The parts of `graph_refresh_ms`, `collapse_audit_ms` and
    `graph_export_ms` name spans one level below those metrics' own, each
    once; the metrics that time the same work from one level up stay."""
    refresh = _spans("task_refresh_ms") + _spans("ec_refresh_ms") + _spans("ec_chain_refresh_ms")
    audit = [s for name in AUDIT for s in _spans(f"{name}_ms")]
    export = _spans("journal_collect_ms") + _spans("journal_apply_ms")
    export += _spans("problem_snapshot_ms")
    for parts, parent in ((refresh, "graph_refresh_ms"), (audit, "collapse_audit_ms"),
                          (export, "graph_export_ms")):
        assert len(set(parts)) == len(parts) and not set(parts) & set(_spans(parent))
    assert audit == list(AUDIT)
    # the resident path takes the same snapshot, then ships the deltas
    assert not set(_spans("upload_ms")) & set(export)


def test_the_parts_close_on_the_synthetic_rounds():
    """What the acceptance asks of the chip, on the two synthetic rounds:
    the parts sum to no more than the span they split, and to most of it."""
    obs = _obs(ROUNDS, RECORDS)

    def value(name):
        own = _own(name)
        return importlib.import_module(f"benchmarks.readers.{own['reader']}").read(
            own["params"], obs)

    audit = sum(value(f"{name}_ms") for name in AUDIT)
    assert 0.95 * value("collapse_audit_ms") <= audit <= value("collapse_audit_ms") + 1e-9
    export = value("journal_collect_ms") + value("journal_apply_ms") + value("problem_snapshot_ms")
    assert 0.9 * value("graph_export_ms") <= export <= value("graph_export_ms")
    refresh = value("task_refresh_ms") + value("ec_refresh_ms")
    assert 0.85 * value("graph_refresh_ms") <= refresh <= value("graph_refresh_ms")
