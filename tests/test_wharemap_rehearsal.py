"""The traced 1/40 rehearsal of `gtrace-12500-wharemap.trickle`, as
`tests/benchmark/test_benchmark_wharemap.py::
test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number` holds
it, with the one sentence that PR 48 made false restated.

The accepted test states the sweep as a fact of the median round
(`ec_arcs_repriced % 312 == 0`: every class EC of the batch writes an arc to
every machine) and that few of those writes change anything
(`ec_arcs_changed < ec_arcs_repriced`). Since PR 48 a class EC re-prices the
machines the census gathered again since its last turn: in a round that
patched, `ec_arcs_repriced` is (class ECs visited) x `census_machines_dirty`
plus what an EC that sat rounds out owes, and nearly every write changes its
arc. No PR but a `benchmark` one may edit that file, so the accepted case is
an expected failure (`tests/conftest.py`, `_STALE`) and every other assertion
of it is held here, on the same run, with that sentence read from the run's
own spans round by round."""

import json
import os
import subprocess
import sys
from collections import defaultdict

import pytest

from benchmarks import spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CELL = "gtrace-12500-wharemap.trickle"
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
MACHINES = 312
GUARANTEES = ["binding", "capacity_by_type", "answer", "interference_map"]
#: the six metrics PR 46 brought, and the lists it appended its cell to
BROUGHT = (
    "collapse_rows", "collapse_cols", "audit_tasks_grouped", "census_machines_dirty",
    "ec_arcs_repriced", "platform_costs_ms",
)
APPENDED = (
    "collapse_audit_ms", "transport_ms", "flow_reconstruct_ms", "audit_index_ms", "audit_pins_ms",
    "audit_subtrees_ms", "audit_task_arcs_ms", "audit_ec_routes_ms", "audit_escapes_ms",
    "audit_rows_ms", "bind_tail_ms", "bindings_post_ms", "task_refresh_ms", "res_nodes_visited",
    "res_arcs_changed", "journal_collect_ms", "journal_apply_ms", "journal_changes",
    "problem_snapshot_ms", "ec_purge_ms", "ec_purges", "apply_nodes_visited", "apply_full_walks",
    "runnable_tasks_scanned",
)
#: executed before `benchmarks.run.main`: the spans of the graph update, as the run's tracer
#: hands them to the harness, also go to the file the test names; and the run keeps its
#: capture in a directory of its own, since the accepted case's run of the same cell may be
#: under way in another worker
KEEP_SPANS = (
    "import json, os\n"
    "run.SCRATCH = os.path.dirname({path!r})\n"
    "from ksched_tpu.obs.spans import SpanTracer\n"
    "handed = SpanTracer.events\n"
    "def events(self):\n"
    "    out = handed(self)\n"
    "    kept = ('round', 'stats', 'graph_update', 'ec_refresh', 'platform_costs')\n"
    "    with open({path!r}, 'w') as f:\n"
    "        json.dump([e for e in out if e['name'] in kept], f)\n"
    "    return out\n"
    "SpanTracer.events = events\n"
)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The cell's traced rehearsal in a process of its own, and its spans."""
    path = str(tmp_path_factory.mktemp("wharemap") / "spans.json")
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", "3", "--trace", "1",
            "--rehearse-cpu"]
    entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
             "import benchmarks.run as run\n" + KEEP_SPANS.format(path=path)
             + "sys.exit(run.main())\n"]
    r = subprocess.run(
        [sys.executable, *entry, *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["stderr_tail"] = r.stderr.strip().splitlines()[-1]
    with open(path) as f:
        out["spans"] = json.load(f)
    return out


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["interference_map"]
    assert replay["replayed"] == out["facts"]["capacity_by_type"]["replayed"] > 3550 + 400
    assert replay["rounds_compared"] == replay["rounds"] > 20 and replay["rounds_short_of_room"] == 0
    assert replay["served_cost"] == replay["optimum_cost"] > 0 and replay["rounds_costing_zero"] == 0
    assert replay["largest_round"] == 3550 and replay["pods_left_waiting_at_most"] == 0
    assert sum(map(sum, replay["bound_by_class_and_platform"])) == replay["pods_bound"]
    assert replay["polls"] > replay["rounds"]
    # no node of any size was ever over its own capacity, and the largest were filled
    peaks = out["facts"]["capacity_by_type"]["peak_load_by_capacity"]
    assert set(peaks) <= {"6", "12", "24"} and all(v <= int(k) for k, v in peaks.items())
    assert peaks["12"] == 12
    assert out["stderr_tail"].startswith('correct: {"correct": true')
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in (everywhere - {"solve_roofline"}) | set(BROUGHT) | set(APPENDED):
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "backend_solve_ms", "collapse_audit_ms", "transport_ms",
                 "audit_pins_ms", "audit_rows_ms", "ec_refresh_ms", "platform_costs_ms",
                 "ec_arcs_repriced", "census_machines_dirty", "audit_tasks_grouped"):
        assert metrics[name] > 0.0, name
    # the dense problem as solved: at most a row a class, 312 machines and the unscheduled
    # column padded to 384; the tasks the rows pass grouped are the round's batch
    assert 1.0 <= metrics["collapse_rows"] <= 4.0 and metrics["collapse_cols"] == 384.0
    assert metrics["audit_tasks_grouped"] == metrics["decode_tasks"] >= metrics["collapse_rows"]
    # RESTATED (PR 48): a class EC of the batch writes the arcs of the machines the census
    # gathered again since its last turn, every machine only where it lists; the rounds below
    # say it turn by turn. Nearly every arc it writes changes
    assert metrics["ec_arcs_changed"] <= metrics["ec_arcs_repriced"] <= 4 * MACHINES
    assert metrics["census_machines_dirty"] <= 2 * metrics["batch_pods_p50"] + 2
    # the span lies inside the EC's turn
    assert metrics["platform_costs_ms"] < metrics["ec_refresh_ms"]
    # the guards of PRs 25-36, read in the new cell: no resource turn, no full walk
    assert metrics["res_nodes_visited"] == 0.0 and metrics["res_arcs_changed"] == 0.0
    assert metrics["stats_full_walks"] == 0.0 and metrics["apply_full_walks"] == 0.0
    assert metrics["unscheduled_by_rule"] == 0.0


def test_in_a_round_that_patched_the_arcs_written_are_visited_ecs_times_machines_gathered(traced):
    by_sid = {e["args"]["sid"]: e for e in traced["spans"]}

    def round_of(e):
        while e["name"] != "round":
            e = by_sid[e["args"]["parent_sid"]]
        return e["args"]["sid"]

    priced = {e["args"]["parent_sid"]: e["args"]["machines"]
              for e in traced["spans"] if e["name"] == "platform_costs"}
    rounds = defaultdict(lambda: {"turns": []})
    for e in traced["spans"]:
        if e["name"] == "stats":
            rounds[round_of(e)].update(
                dirty=e["args"]["census_machines_dirty"], full_walk=e["args"]["stats_full_walk"])
        elif e["name"] == "graph_update":
            rounds[round_of(e)]["repriced"] = e["args"]["ec_arcs_repriced"]
        elif e["name"] == "ec_refresh":
            # a turn with nothing on its record prices nothing and opens no `platform_costs`
            rounds[round_of(e)]["turns"].append((e["args"]["swept"], priced.get(e["args"]["sid"], 0)))
    served = [r for r in rounds.values() if r["turns"]]
    assert len(served) > 20
    exact = 0
    for r in served:
        assert sum(machines for _swept, machines in r["turns"]) == r["repriced"]
        for swept, machines in r["turns"]:
            if swept:
                assert machines == MACHINES  # the EC lists: an arc a machine
            else:
                # the machines gathered again since this EC's last turn: this round's, and
                # those of the rounds it sat out
                assert r["dirty"] <= machines < MACHINES
        if not r["full_walk"] and not any(swept for swept, _m in r["turns"]):
            exact += r["repriced"] == len(r["turns"]) * r["dirty"]
    # the fill and the rounds of the class sweep list; later an EC lists again only after the
    # purge took it or a pass walked every node
    assert sum(swept for r in served for swept, _m in r["turns"]) >= 4
    assert exact >= 3
