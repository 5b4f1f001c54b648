"""The per-layer metrics of the solve's split, of the statistics pass's
fan-in, of the collector's pauses and of what a round leaves unnamed (PR 51).

Each of the twelve entries is looked up by name, equals its file, lists its
cells (the eight of `plan_rows`; the eleven other than
`k8s-5000-preemption.rollout`, whose metric set is pinned) and
loads in those cells and in no other. The new reader `span_residual` gives
the expected number on a synthetic span list, refuses leaves that overlap,
and reads an older program (no new leaf) without raising; the readers the
benchmark had return nothing for a program that lacks the spans and fields.
A traced rehearsal of one scan-CSR cell and of one dense cell, each from a
copy of the benchmark so that no other file's capture is in its way, prints
every new metric of its lists, `correct`.

The twelve stand after `supersteps_sparse_p50`, which makes eleven pins of
`test_benchmark_sparse_supersteps.py` false (that its entry is the last of
`per_layer`; that nothing but it follows PR 49's four and PR 46's six):
expected failures since this PR (tests/conftest.py); what stays true of each
is held here, a case an entry. Nothing here states a position from the end or
a count of `per_layer`: the next PR appends after these."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import observe, spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
SEED = 2147483929  # more than 32 signed bits hold, as the driver's are
PREEMPTION = "k8s-5000-preemption.rollout"
DENSE = ["coco-50kx1k.trickle", "coco-50kx1k.waves", "gtrace-12500-wharemap.trickle"]
SPARSE = "supersteps_sparse_p50"
#: PR 49's four entries and PR 46's six before them
BROUGHT = ("requests_costs_ms", "books_machines_dirty", "machines_gated", "columns_offered")
WHAREMAPS = ("collapse_rows", "collapse_cols", "audit_tasks_grouped", "census_machines_dirty",
             "ec_arcs_repriced", "platform_costs_ms")
ACCOUNTED = (
    "stats", "graph_update", "graph_export", "backend_solve", "decode", "deltas", "apply",
    "pods_admit", "runnable_scan", "bindings_collect", "bindings_post", "round_accounting",
)
LEAVES = [*ACCOUNTED, "ec_purge", "evictions_post", "decode_set", "solve_prepare",
          "problem_upload", "solve_launch"]


def _entry(name):
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


def _file(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        return json.load(f)


EIGHT = _entry("plan_rows")["workloads"]
ELEVEN = [w["name"] for w in BENCH["workloads"] if w["name"] != PREEMPTION]


def _span_ms(span):
    return ("ms", "program_span", "solver rungs", "span_sum", {"spans": [span], "reduce": "p50"})


#: metric -> (unit, source, layer, reader, params, cells)
NEW = {
    "solve_prepare_ms": (*_span_ms("solve_prepare"), EIGHT),
    "problem_upload_ms": (*_span_ms("problem_upload"), EIGHT),
    "solve_launch_ms": (*_span_ms("solve_launch"), EIGHT),
    "solve_wait_ms": (*_span_ms("solve_wait"), EIGHT),
    "result_readback_ms": (*_span_ms("result_readback"), EIGHT),
    "result_unpack_ms": (*_span_ms("result_unpack"), EIGHT),
    "solve_h2d_bytes": ("B", "program_counter", "solver rungs", "round_field",
                        {"field": "solve_h2d_bytes", "reduce": "p50"}, EIGHT),
    "solve_d2h_bytes": ("B", "program_counter", "solver rungs", "round_field",
                        {"field": "solve_d2h_bytes", "reduce": "p50"}, EIGHT),
    # not the dense cells: the fused Pallas kernel keeps no telemetry ring to publish
    "soltel_publish_ms": (*_span_ms("soltel_publish"), EIGHT),
    "stats_children_gathered": ("count", "program_counter", "graph update / export", "round_field",
                                {"field": "stats_children_gathered", "reduce": "p50"}, ELEVEN),
    "gc_pause_ms": ("ms", "program_counter", "service loop", "round_field",
                    {"field": "gc_pause_ms", "reduce": "sum"}, ELEVEN),
    "round_unnamed_ms": ("ms", "program_span", "benchmark", "span_residual",
                         {"den": "service_round", "leaves": LEAVES, "reduce": "p50"}, ELEVEN),
}


def _observation(rounds=(), records=()):
    return observe.Observation(
        device_kind="cpu", rounds=list(rounds), records=list(records), client={}, counters={},
        shapes={}, trace=None, rehearsal=True,
    )


def _round(**spans_ms):
    return observe.Round(t0=0.0, t1=1.0, pods=3, solve=True, spans_ms=dict(spans_ms))


# -- the entries ----------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_is_there_by_name_equals_its_file_and_lists_its_cells(name):
    unit, source, layer, reader, params, cells = NEW[name]
    entry, own = _entry(name), _file(name)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (entry["unit"], entry["source"], entry["layer"], entry["better"], entry["moves"]) == (
        unit, source, layer, "lower", "bind_p50_ms",
    )
    assert (own["reader"], own["params"]) == (reader, params) and len(own["what"]) > 40
    assert entry["workloads"] == cells and PREEMPTION not in cells
    # a layer the benchmark had, letter for letter
    assert layer in {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index(SPARSE) and names.count(name) == 1


def test_the_lists_are_the_cells_the_issue_names_and_the_file_holds_together():
    assert len(EIGHT) == 8 and len(ELEVEN) == 11 and not set(DENSE) & set(EIGHT)
    assert set(EIGHT) | set(DENSE) == set(ELEVEN)
    assert spec.check_names(BENCH) == [] and len(json.dumps(BENCH)) < 64 * 1024
    # the leaves of the residual: the twelve `round_accounted_share` sums, which stay as they
    # were, and six more; none of them a child of another
    assert _file("round_accounted_share")["params"]["num"] == list(ACCOUNTED)
    assert LEAVES[:12] == list(ACCOUNTED) and len(set(LEAVES)) == len(LEAVES) == 18
    inside = {"plan_upload", "solve_wait", "result_readback", "result_unpack", "soltel_publish",
              "journal_apply", "task_refresh", "export_accounting", "gc_pause"}
    assert not inside & set(LEAVES)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_loads_each_by_name_if_it_is_listed_and_not_otherwise(cell):
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    for name, (*_rest, cells) in NEW.items():
        assert (name in loaded) == (cell in cells), (cell, name)
    if cell == PREEMPTION:
        assert not loaded & set(NEW)  # its metric set is pinned
    assert {"backend_solve_ms", "solve_device_ms", "round_accounted_share"} <= loaded  # as before


# -- the readers ----------------------------------------------------------------------------


def test_span_residual_is_the_round_less_its_leaves_reduced_over_the_solved_rounds():
    read = importlib.import_module("benchmarks.readers.span_residual").read
    params = {"den": "service_round", "leaves": ["a", "b", "c"], "reduce": "p50"}
    rounds = [
        _round(round=9.0, service_round=10.0, a=4.0, b=3.0, c=1.0, nested_in_a=2.0),  # 2.0 left
        _round(round=19.0, service_round=20.0, a=4.0, b=10.0),  # `c` did not open: 6.0 left
        _round(service_round=5.0),  # an idle sweep: no `round`, no sample
        _round(round=1.0, service_round=1.0, a=0.5, b=0.5, c=0.0),  # all named: 0.0
        _round(round=3.0, a=1.0),  # no `service_round`: no sample
    ]
    assert read(params, _observation(rounds)) == 2.0
    assert read({**params, "reduce": "max"}, _observation(rounds)) == 6.0
    assert read({**params, "reduce": "count"}, _observation(rounds)) == 3.0
    assert read(params, _observation([])) is None
    assert read(params, _observation(rounds[2:3])) is None
    # a program without one of the leaves (the parent) reads more, and does not raise
    assert read({**params, "leaves": ["a", "not_there"]}, _observation(rounds[:1])) == 6.0
    # within the clock's grain above the whole is the whole
    assert read(params, _observation([_round(round=1.0, service_round=1.0, a=1.0, b=0.04)])) == 0.0


@pytest.mark.parametrize("leaves, why", [
    (["a", "nested_in_a", "b", "c"], "overlap"),  # a child beside its parent: 10 < 4 + 2 + 3 + 1...
    (["a", "a"], "repeat"),
    (["a", "service_round"], "repeat"),
])
def test_span_residual_refuses_leaves_that_overlap(leaves, why):
    read = importlib.import_module("benchmarks.readers.span_residual").read
    rounds = [_round(round=9.0, service_round=10.0, a=4.0, b=3.0, c=1.0, nested_in_a=2.5)]
    with pytest.raises(ValueError, match=why):
        read({"den": "service_round", "leaves": leaves, "reduce": "p50"}, _observation(rounds))


def test_the_readers_it_had_read_the_new_spans_and_fields_and_nothing_from_the_parent():
    span_sum = importlib.import_module("benchmarks.readers.span_sum").read
    round_field = importlib.import_module("benchmarks.readers.round_field").read
    rounds = [
        _round(round=9.0, service_round=10.0, solve_launch=0.5, problem_upload=2.0, plan_upload=1.5),
        _round(round=9.0, service_round=10.0, solve_launch=0.25, problem_upload=1.0),
        _round(round=9.0, service_round=10.0, solve_launch=0.75, problem_upload=3.0, plan_upload=2.5),
    ]
    # a retry's two launches are one sum a round; `plan_upload` is inside `problem_upload`
    assert span_sum(_file("solve_launch_ms")["params"], _observation(rounds)) == 0.5
    assert span_sum(_file("problem_upload_ms")["params"], _observation(rounds)) == 2.0
    parent = [_round(round=9.0, service_round=10.0, backend_solve=4.0)]
    for name, (_u, _s, _l, reader, params, _cells) in NEW.items():
        if reader == "span_sum":
            assert span_sum(params, _observation(parent)) is None, name
    records = [
        {"num_scheduled": 10000, "solve_h2d_bytes": 5_000_000, "gc_pause_ms": 30.0,
         "stats_children_gathered": 4001},
        {"num_scheduled": 3, "solve_h2d_bytes": 1_708_036, "gc_pause_ms": 0.0,
         "stats_children_gathered": 1012},
        {"num_scheduled": 2, "solve_h2d_bytes": 1_708_036, "gc_pause_ms": 412.5,
         "stats_children_gathered": 1008},
        {"num_scheduled": 0, "solve_h2d_bytes": 1_708_036, "gc_pause_ms": 99.0,
         "stats_children_gathered": 1000},  # bound nothing: no sample
    ]
    obs = _observation(records=records)
    assert round_field(_file("solve_h2d_bytes")["params"], obs) == 1_708_036.0
    assert round_field(_file("gc_pause_ms")["params"], obs) == 442.5  # summed over the window
    assert round_field(_file("stats_children_gathered")["params"], obs) == 1012.0
    bare = _observation(records=[{"num_scheduled": 3}])
    for name, (_u, _s, _l, reader, params, _cells) in NEW.items():
        if reader == "round_field":
            assert round_field(params, bare) is None, name


# -- what stays true of the pins these entries made false -------------------------------------


def test_the_sparse_supersteps_entry_still_equals_its_file_and_lists_the_cells_of_plan_rows():
    entry, own = _entry(SPARSE), _file(SPARSE)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == (
        "round_field", {"field": "supersteps_sparse", "reduce": "p50"},
    )
    assert entry["workloads"] == EIGHT and entry["layer"] == _entry("supersteps_p50")["layer"]
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended after PR 49's four, nothing between; what PR 51 appended follows it
    assert names[names.index(SPARSE) - 4:names.index(SPARSE)] == list(BROUGHT)
    assert all(names.index(n) > names.index(SPARSE) for n in NEW)


@pytest.mark.parametrize("name", BROUGHT)
def test_each_metric_pr_49_brought_still_stands_between_pr_46s_six_and_the_sparse_entry(name):
    entry, own = _entry(name), _file(name)
    assert entry["workloads"] == ["k8s-5000-requests.trickle"]
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    names = [m["name"] for m in BENCH["per_layer"]]
    assert max(names.index(n) for n in WHAREMAPS) < names.index(name) < names.index(SPARSE)
    assert name not in {m["name"] for m in spec.load_cell(DENSE[2]).per_layer}


@pytest.mark.parametrize("name", WHAREMAPS)
def test_each_of_pr_46s_six_still_stands_right_before_pr_49s_four(name):
    entry, own = _entry(name), _file(name)
    assert entry["workloads"] == (
        [DENSE[2], "k8s-5000-requests.trickle"] if name == "ec_arcs_repriced" else [DENSE[2]]
    )
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(WHAREMAPS[0])
    # the six, the four and the sparse entry in one run, in the order they were appended
    assert names[first:first + 11] == [*WHAREMAPS, *BROUGHT, SPARSE]


# -- the rehearsals -------------------------------------------------------------------------


def _rehearse(tmp_path, cell):
    """A traced rehearsal of `cell` from a copy of the benchmark (the program comes from
    this checkout): its capture lies under the copy, in no other test's way."""
    shutil.copytree(spec.HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed", str(SEED),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=400,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_rehearsal_of_a_scan_csr_cell_prints_every_new_metric(tmp_path):
    out = _rehearse(tmp_path, "trivial-10kx1k.trickle")
    assert out["correct"] is True and out["failed"] == 0, out["facts"]["faults"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(metrics)
    for name in NEW:
        assert out["metrics"][name]["unit"] == NEW[name][0] and metrics[name] >= 0.0
    assert metrics["compiles_in_window"] == 0.0
    # the rung's two halves: what runs before `backend_solve` opens, and what fills it
    inside = sum(metrics[n] for n in (
        "solve_wait_ms", "result_readback_ms", "result_unpack_ms", "soltel_publish_ms"))
    assert 0.0 < metrics["solve_wait_ms"] <= inside <= 1.2 * metrics["backend_solve_ms"]
    assert min(metrics[n] for n in ("solve_prepare_ms", "problem_upload_ms", "solve_launch_ms")) > 0
    # bytes from the shapes: cap, cost, warm flow, supply, eps; the plan's values at least
    n_cap, m_cap = out["facts"]["shapes"]["nodes"], out["facts"]["shapes"]["arcs"]
    rows = metrics["plan_rows"]
    problem, values = 4 * (3 * m_cap + n_cap) + 4, 4 * (4 * rows + 2 * m_cap)
    static = 5 * rows + 9 * n_cap
    assert metrics["solve_h2d_bytes"] in (problem + values, problem + values + static)
    assert metrics["solve_d2h_bytes"] == 4 * m_cap + 4 * 8 * 512 + 6  # flow, ring, three scalars
    # a patched statistics pass re-reads the coordinator's machines for a few dirty paths
    machines = out["facts"]["shapes"]["machines"] // spec.REHEARSE_DIVISOR
    assert machines <= metrics["stats_children_gathered"] <= machines + 10 * metrics["batch_pods_p50"] + 40
    # what is left without a name is a small part of the round
    assert metrics["round_unnamed_ms"] < 0.25 * metrics["round_p50_ms"] + 0.5
    assert metrics["round_accounted_share"] > 50.0  # and the old share reads as it read


def test_the_rehearsal_of_a_dense_cell_prints_the_three_of_its_lists_and_none_of_the_solves(tmp_path):
    out = _rehearse(tmp_path, "coco-50kx1k.trickle")
    assert out["correct"] is True and out["failed"] == 0, out["facts"]["faults"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    mine = {"stats_children_gathered", "gc_pause_ms", "round_unnamed_ms"}
    assert mine <= set(metrics) and not (set(NEW) - mine) & set(metrics)
    assert metrics["round_unnamed_ms"] < 0.25 * metrics["round_p50_ms"] + 0.5
    assert metrics["gc_pause_ms"] >= 0.0 and metrics["stats_children_gathered"] > 0
