"""The per-layer metrics that read the service loop's own spans, the
AutoSolver's and the pods' queue wait: each is a file that loads in its
cells and in no other, and the two readers they brought return nothing,
and do not raise, on a program that has no such span or field."""

import importlib
import json
import os

import pytest

from benchmarks import observe, spec
from benchmarks.readers import round_field, span_ratio, span_sum

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
COCO = [c for c in CELLS if c.startswith("coco-50kx1k.")]
#: metric -> (reader, the cells that report it)
NEW = {
    "admit_ms": ("span_sum", CELLS),
    "bindings_collect_ms": ("span_sum", CELLS),
    "queue_wait_ms": ("round_field", CELLS),
    "collapse_audit_ms": ("span_sum", COCO),
    "transport_ms": ("span_sum", COCO),
    "flow_reconstruct_ms": ("span_sum", COCO),
    "round_accounted_share": ("span_ratio", CELLS),
}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_loads_in_its_cells_and_is_absent_from_the_others(name, cell):
    reader, cells = NEW[name]
    loaded = {m["name"]: m for m in spec.load_cell(cell).per_layer}
    assert (name in loaded) == (cell in cells)
    if name in loaded:
        m = loaded[name]
        assert m["reader"] == reader and m["moves"] == "bind_p50_ms" and m["what"]
        assert callable(importlib.import_module(f"benchmarks.readers.{reader}").read)


def test_each_of_the_seven_entries_is_there_by_name_and_equals_its_file():
    """Looked up by name: where an entry stands in `per_layer`, and how
    many follow it, is nobody's business (a later PR appends)."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(by_name)
    assert len(by_name) == len(BENCH["per_layer"])  # no name twice
    for name, (_reader, cells) in NEW.items():
        m = by_name[name]
        own = json.load(open(os.path.join(spec.HERE, "layer_metrics", name + ".json")))
        assert {k: own[k] for k in m if k != "workloads"} == {
            k: v for k, v in m.items() if k != "workloads"
        }
        assert m.get("workloads", CELLS) == cells
        assert (m["better"], m["unit"]) == (
            ("higher", "%") if name == "round_accounted_share" else ("lower", "ms")
        )
        assert m["source"] == ("program_counter" if name == "queue_wait_ms" else "program_span")


def _round(spans_ms, pods=1):
    return observe.Round(t0=0.0, t1=1.0, pods=pods, solve=True, spans_ms=dict(spans_ms))


def _obs(rounds=(), records=()):
    return observe.Observation(
        device_kind="cpu", rounds=list(rounds), records=list(records),
        client={}, counters={}, shapes={},
    )


RATIO = {"num": ["stats", "apply", "pods_admit"], "den": "service_round", "reduce": "mean"}


def test_span_ratio_is_the_share_of_the_round_inside_the_named_spans():
    whole = _round({"round": 90, "service_round": 100, "stats": 40, "apply": 50, "pods_admit": 10})
    half = _round({"round": 40, "service_round": 80, "stats": 30, "pods_admit": 10, "decode": 25})
    no_den = _round({"round": 10, "stats": 10})
    idle = _round({"service_round": 5, "round_accounting": 5})  # not solved: no `round`
    obs = _obs([whole, half, no_den, idle])
    assert span_ratio.read(RATIO, obs) == pytest.approx((100.0 + 50.0) / 2)
    assert span_ratio.read({**RATIO, "reduce": "max"}, obs) == pytest.approx(100.0)
    assert span_ratio.read({**RATIO, "reduce": "count"}, obs) == 2.0


def test_span_ratio_finds_nothing_without_the_denominator():
    assert span_ratio.read(RATIO, _obs([_round({"round": 10, "stats": 10})])) is None
    assert span_ratio.read(RATIO, _obs()) is None
    zero = _round({"round": 0.0, "service_round": 0.0})
    assert span_ratio.read(RATIO, _obs([zero])) is None


def test_the_shipped_ratio_names_every_leaf_span_of_a_round_once():
    params = json.load(
        open(os.path.join(spec.HERE, "layer_metrics", "round_accounted_share.json"))
    )["params"]
    assert params["den"] == "service_round" and len(set(params["num"])) == len(params["num"])
    # leaves only: a span and one that contains it would count the time twice
    assert not {"round", "solve", "service_round", "collapse_audit", "transport",
                "flow_reconstruct"} & set(params["num"])
    assert {"pods_admit", "runnable_scan", "bindings_collect", "bindings_post",
            "round_accounting"} <= set(params["num"])


def test_round_field_reads_rounds_that_bound_a_pod_and_none_from_an_older_program():
    params = {"field": "queue_wait_ms", "reduce": "p50"}
    records = [
        {"num_scheduled": 3, "queue_wait_ms": 10.0},
        {"num_scheduled": 1, "queue_wait_ms": 30.0},
        {"num_scheduled": 0, "queue_wait_ms": 500.0},  # bound nothing: not a sample
    ]
    assert round_field.read(params, _obs(records=records)) == pytest.approx(20.0)
    older = [{"num_scheduled": 3}, {"num_scheduled": 1}]  # the parent's RoundRecord
    assert round_field.read(params, _obs(records=older)) is None
    assert round_field.read(params, _obs()) is None


@pytest.mark.parametrize("name", [n for n, (r, _c) in sorted(NEW.items()) if r == "span_sum"])
def test_a_span_metric_is_left_out_where_the_program_has_no_such_span(name):
    params = json.load(open(os.path.join(spec.HERE, "layer_metrics", name + ".json")))["params"]
    (span,) = params["spans"]
    older = _obs([_round({"round": 90, "service_round": 100, "stats": 10})])
    assert span_sum.read(params, older) is None
    newer = _obs([
        _round({"round": 90, "service_round": 100, span: 4.0}),
        _round({"round": 90, "service_round": 100, span: 8.0}),
        _round({"service_round": 1, span: 100.0}),  # an idle sweep is not a sample
    ])
    assert span_sum.read(params, newer) == pytest.approx(6.0)
