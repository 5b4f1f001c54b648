"""The `gtrace-12500-quincy` deployment and its cell: the configuration is
BASELINE.json `configs[4]` under Quincy's policy at its source's shapes, a
pod's input blocks come from `pods/quincy_blocks.py` (pure in (pod id,
class, configuration, seed), seed-stable past 32 bits, recomputed through
`ctx.make_pod`), the cell rehearses `correct` at 1/40 scale (312 nodes x 12
slots in 250 racks, 3,375 resident pods) traced and untraced, the five
per-layer metrics this deployment brings read a number there, and the check
it brings tells: a Binding moved off a preferred machine that had a slot, a
model without the rack tier, a model that prices a preferred machine at its
rack's bound, and a rack label that disagrees with the file each print
`correct` false with the fault.

PR 38's test of its own cell pins it to the last place of `configs`,
`workloads` and thirteen lists (`test_benchmark_preemption.py`); this cell
came after it, so those two tests are expected failures since this PR
(tests/conftest.py), and what stays true of that cell is held here; so are
the pins of the two `ec_chain_*` and the five `plan_*` lists
(`test_benchmark_zonespread.py`, `test_benchmark_plan_refit.py`), on which
this cell's name came last."""

import hashlib
import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import reference_quincy as ref
from benchmarks import spec
from benchmarks.checks import data_locality
from benchmarks.pods import quincy_blocks
from benchmarks.traffic import build_plan

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "gtrace-12500-quincy"
CELL = CONFIG + ".trickle"
SEED = 2147483693  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity", "answer", "data_locality"]
BROUGHT = {
    "pref_refresh_ms": ("span_sum", "graph update / export"),
    "pref_arcs_live": ("round_field", "graph update / export"),
    "pref_arcs_changed": ("round_field", "graph update / export"),
    "bound_on_preferred_share": ("round_field", "solver rungs"),
    "remote_bytes_share": ("round_field", "solver rungs"),
}
APPENDED = (
    "bind_tail_ms", "bindings_post_ms", "ec_chain_refresh_ms", "ec_chain_arcs_changed",
    "task_refresh_ms", "res_nodes_visited", "res_arcs_changed", "journal_collect_ms",
    "journal_apply_ms", "journal_changes", "problem_snapshot_ms", "ec_purge_ms", "ec_purges",
    "apply_nodes_visited", "apply_full_walks", "plan_rows", "plan_rows_live", "plan_refits",
    "plan_regrowths", "plan_relayouts",
)


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


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
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["stderr_tail"] = r.stderr.strip().splitlines()[-1]
    return out


@pytest.fixture(scope="module")
def traced():
    return _rehearse(1)


# -- the files ------------------------------------------------------------------------


def test_the_configuration_is_the_sources_shapes():
    c = _config()
    assert c["argv"] == (
        "--fake-machines --num-machines 12500 --cores-per-machine 1 --pus-per-core 1 "
        "--max-tasks-per-pu 12 --fake-racks 250 --cost-model quincy --backend jax "
        "--pod-batch-timeout 0.002 --pod-chan-size 145000"
    ).split()
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (135000, 2, 5000)
    assert c["resident_pods"] * 10 == 12500 * 12 * 9  # nine tenths of 150,000 slots
    assert (c["pods"], c["racks"], c["architecture"]) == ("quincy_blocks", 250, None)
    assert c["input"] == {
        "block_bytes": 64 << 20, "file_blocks": 64, "files": 2000, "zipf_s": 1.0,
        "blocks_median": 4, "blocks_sigma": 0.9, "blocks_max": 32, "replicas": 3,
    }
    # the policy's numbers as the file states them are the reference's and the model's
    from ksched_tpu.costmodels import QuincyCostModel as model

    assert c["policy"] == {
        "quantum_bytes": ref.QUANTUM, "psi": ref.PSI, "xi": ref.XI, "delta_pct": ref.DELTA_PCT,
        "max_prefs": ref.MAX_PREFS, "omega": ref.OMEGA, "largest_cost": ref.LARGEST_COST,
    } == {
        "quantum_bytes": model.QUANTUM, "psi": model.PSI, "xi": model.XI, "delta_pct": model.DELTA_PCT,
        "max_prefs": model.MAX_PREFS, "omega": model.OMEGA, "largest_cost": model.largest_cost,
    }
    assert c["reduced"] == [] and "one chip holds the cluster whole" in c["why_nothing_is_reduced"]
    assert len(c["kept_from_the_source"]) >= 5 and len(c["assumed"]) >= 10
    assert c["assumed"][0].startswith("every value of the two papers is remembered, not confirmed")
    assert any("read nothing" in a for a in c["assumed"])  # the resident pods, and why
    assert list(c["guarantees"]) == GUARANTEES
    others = _config("k8s-5000-zonespread")["guarantees"]
    assert all(c["guarantees"][k] == others[k] for k in ("binding", "capacity", "answer"))
    entry = next(e for e in BENCH["configs"] if e["name"] == CONFIG)
    assert entry == BENCH["configs"][-1]  # appended
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json" and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("BASELINE.json configs[4]", "Google 2011 cluster-trace replay", "12.5k machines",
                 "SOSP'09", "section 4.2", "OSDI'16", "section 7"):
        assert word in entry["source"] and word in c["source"], word
    assert sum(1 for e in BENCH["configs"] if e["source"] == entry["source"]) == 1
    assert sum(1 for e in BENCH["configs"] if e["file"] == entry["file"]) == 1
    assert "." not in CONFIG  # a cell's name is split at every "."
    spec.check_guarantees(c, entry["file"])
    assert spec.check_pods(c, entry["file"]) == "quincy_blocks"


def test_the_cell_takes_one_chip_and_the_mix_it_shares_is_unchanged():
    w = next(e for e in BENCH["workloads"] if e["name"] == CELL)
    assert w == BENCH["workloads"][-1] and len(BENCH["workloads"]) == 10 == len({e["name"] for e in BENCH["workloads"]})
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200 and "250 racks" in w["why"]
    assert not any(e["chips"] == 4 for e in BENCH["workloads"])
    assert spec.check_names(BENCH) == []
    cell = spec.load_cell(CELL)
    mix = cell.traffic
    assert mix == spec.load_cell("trivial-10kx1k.trickle").traffic
    assert (mix["kind"], mix["rate_per_s"], mix["completions_per_arrival"], mix["warmup_s"]) == (
        "open_poisson", 100.0, 1, 3.0,
    )
    assert {m["name"] for m in cell.end_to_end} == {"bind_p50_ms", "setup_s"}
    # the lists of the other end-to-end metrics are as they were
    lists = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}
    assert lists == {
        "bind_p50_ms": None, "setup_s": None, "bind_p95_ms": ["trivial-10kx1k.trickle"],
        "bound_pods_per_s": ["trivial-10kx1k.waves", "coco-50kx1k.waves"],
    }
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} == everywhere | set(BROUGHT) | set(APPENDED)
    # what the appended lists had, they have: the cell's name came last
    for name in APPENDED:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL and entry["workloads"].count(CELL) == 1
    # the five entries came last, in one block, after every entry that was there
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(BROUGHT)
    plan = build_plan(mix, cell.config, SEED, 40.0)
    assert len(plan.resident) == 135000 and len(plan.closing) == 100
    # two classes the model prices alike: one synchronous burst lies between the fill
    # and the open loop, the first round whose pods carry preference arcs
    assert plan.class_sweep == [[(f"s1_{i}", 0) for i in range(8)]]
    make = spec.pod_maker(cell.pods, cell.config, SEED)
    assert all(make(pod, c).inputs for pod, c in plan.class_sweep[0])
    for name in [w["name"] for w in BENCH["workloads"]]:
        spec.load_cell(name)  # all ten load


def test_what_stays_true_of_the_cell_before_it():
    # test_benchmark_preemption.py pins its cell to the last place; it is the ninth of ten now
    names = [e["name"] for e in BENCH["workloads"]]
    assert names.index("k8s-5000-preemption.rollout") == 8 == names.index(CELL) - 1
    assert [e["name"] for e in BENCH["configs"]][-2:] == ["k8s-5000-preemption", CONFIG]
    from test_benchmark_preemption import APPENDED as ITS_APPENDED
    from test_benchmark_preemption import BROUGHT as ITS_BROUGHT

    cell = spec.load_cell("k8s-5000-preemption.rollout")
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} == everywhere | set(ITS_BROUGHT) | set(ITS_APPENDED)
    for name in ITS_APPENDED:
        lists = next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"]
        assert lists.count("k8s-5000-preemption.rollout") == 1
        # this cell, where it joined the list, came after it
        assert CELL not in lists or lists.index(CELL) > lists.index("k8s-5000-preemption.rollout")
    for name in ITS_BROUGHT:
        assert next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"] == [
            "k8s-5000-preemption.rollout"
        ]


def test_what_stays_true_of_the_seven_lists_two_earlier_tests_pin():
    # test_benchmark_zonespread.py pins the two ec_chain_* lists to its cell alone and
    # test_benchmark_plan_refit.py the five plan_* lists to PR 41's six cells; this cell's
    # name came last on each (expected failures there since this PR: tests/conftest.py)
    from test_benchmark_plan_refit import CELLS as PLAN_CELLS
    from test_benchmark_plan_refit import NEW as PLAN_METRICS

    lists = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    for name in ("ec_chain_refresh_ms", "ec_chain_arcs_changed"):
        assert lists[name] == ["k8s-5000-zonespread.trickle", CELL]
    assert lists["spread_fallback_rounds"] == ["k8s-5000-zonespread.trickle"]
    for name in PLAN_METRICS:
        assert lists[name] == PLAN_CELLS + [CELL]
    for cell in (w["name"] for w in BENCH["workloads"]):
        loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
        assert (loaded & set(PLAN_METRICS)) == (set(PLAN_METRICS) if cell in PLAN_CELLS + [CELL] else set())


@pytest.mark.parametrize("name", sorted(BROUGHT))
def test_each_metric_it_brings_is_an_entry_with_its_file_for_this_cell_alone(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    reader, layer = BROUGHT[name]
    better = "higher" if name == "bound_on_preferred_share" else "lower"
    assert (entry["moves"], entry["better"], entry["layer"]) == ("bind_p50_ms", better, layer)
    assert layer in {m["layer"] for m in BENCH["per_layer"][:-5]}  # a layer the benchmark names
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert own["reader"] == reader and own["what"]  # a reader that was there
    assert name not in {m["name"] for m in spec.load_cell("k8s-5000-zonespread.trickle").per_layer}
    # on a program that has no such span or field (the parent) the reader finds nothing
    from benchmarks.observe import Observation, Round

    read = importlib.import_module(f"benchmarks.readers.{reader}").read
    parent = Observation(
        device_kind="cpu", rounds=[Round(0.0, 1.0, 3, True, {"round": 1.0, "task_refresh": 0.2})],
        records=[{"num_scheduled": 3, "pref": 1}], client={}, counters={}, shapes={},
    )
    assert read(own["params"], parent) is None


# -- what a pod carries ------------------------------------------------------------------


def _digest(events) -> str:
    return hashlib.sha256(repr([(e.pod_id, e.task_class, e.inputs) for e in events]).encode()).hexdigest()[:16]


def test_the_pods_module_is_pure_and_seed_stable_past_32_bits():
    c = _config()
    make = spec.pod_maker("quincy_blocks", c, SEED)
    pods = [f"p{i}" for i in range(40)] + ["c0", "c99", "s1_0", "w3_2"]
    events = [make(p, i % 2) for i, p in enumerate(pods)]
    again = [spec.pod_maker("quincy_blocks", c, SEED)(p, i % 2) for i, p in enumerate(pods)]
    assert events == again  # the same four arguments, the same event (but for its stamp)
    # the same bytes on every machine and in every session: pinned
    assert _digest(events) == "a9fadf9b3d3b9c39"
    assert _digest([spec.pod_maker("quincy_blocks", c, 5)(p, 0) for p in pods]) == "fa83e61f9c3f30bf"
    # another seed, other inputs; the seed's high bits count
    assert make("p7", 0).inputs != spec.pod_maker("quincy_blocks", c, SEED - (1 << 31))("p7", 0).inputs
    # a resident pod of the fill reads nothing; everything after it reads 1..32 blocks of 64 MiB
    assert [make(p, 0).inputs for p in ("r0", "r134999")] == [(), ()]
    for e in events:
        assert 1 <= len(e.inputs) <= 32
        ids = [b for b, _size, _nodes in e.inputs]
        assert ids == list(range(ids[0], ids[0] + len(ids)))  # a run of one file's blocks
        assert ids[0] // 64 == ids[-1] // 64 < 2000
        for _b, size, nodes in e.inputs:
            a, b, c3 = (int(n.rsplit("_", 1)[1]) for n in nodes)
            assert size == 64 << 20 and max(a, b, c3) < 12500
            assert a % 250 != b % 250 and b % 250 == c3 % 250 and b != c3
    # a block's replicas are the block's, whoever reads it
    first = {}
    for e in events:
        for b, _size, nodes in e.inputs:
            assert first.setdefault(b, nodes) == nodes
    # the cluster comes from the file's own argv: the rehearsal draws inside its 312 machines
    small = spec.pod_maker("quincy_blocks", spec.rehearsal_config(c), SEED)
    for p in pods[:40]:
        for _b, _size, nodes in small(p, 0).inputs:
            idx = [int(n.rsplit("_", 1)[1]) for n in nodes]
            assert max(idx) < 312 and 2 <= len(idx) <= 3 and idx[0] % 250 != idx[1] % 250


def test_the_draw_is_skewed_as_the_file_says():
    c = _config()
    make = spec.pod_maker("quincy_blocks", c, 11)
    events = [make(f"p{i}", 0) for i in range(3000)]
    sizes = sorted(len(e.inputs) for e in events)
    assert sizes[len(sizes) // 2] == 4 and sizes[0] == 1 and sizes[-1] == 32  # median 4 on 1..32
    files = [e.inputs[0][0] // 64 for e in events]
    hot = max(set(files), key=files.count)
    assert hot == 0 and 0.08 < files.count(0) / len(files) < 0.18  # Zipf(1.0) over 2,000 files
    rack_of = {f"fake_node_{i}": i % 250 for i in range(12500)}
    routes = [ref.Routes(e.inputs, rack_of) for e in events]
    with_machines = sum(1 for r in routes if r.machines) / len(routes)
    only_racks = sum(1 for r in routes if not r.machines and r.racks) / len(routes)
    assert 0.65 < with_machines < 0.9 and 0.02 < only_racks < 0.15  # most, some, and the rest only X
    assert max(len(r.machines) + len(r.racks) for r in routes) == 14


@pytest.mark.parametrize("module", ["class_only", "by_role", "quincy_blocks"])
def test_a_pods_module_stamps_each_event_as_it_is_made_and_draws_nothing_from_the_frameworks_rng(module):
    # the half of test_benchmark_seams.py's test of `class_only` that stays true with other
    # modules under pods/ (its listing of that directory is an expected failure since PR 38)
    from ksched_tpu.utils import rng, seed_rng

    c = {**_config("k8s-5000-preemption"), **{k: _config()[k] for k in ("argv", "input")}}
    seed_rng(77)
    state = rng().getstate()
    make = spec.pod_maker(module, c, 77)
    events = [make(f"p{i}", 0) for i in range(300)]
    assert rng().getstate() == state  # one draw would shift every task and job id of the run
    stamps = [e.received_s for e in events]
    assert stamps == sorted(stamps) and stamps[0] < stamps[-1]  # made at submission, not ahead
    assert sorted(f.name for f in os.scandir(os.path.join(spec.HERE, "pods")) if f.is_file()) == [
        "by_role.py", "class_only.py", "quincy_blocks.py",
    ]


# -- the rehearsal --------------------------------------------------------------------------


def test_the_rehearsal_is_the_fortieth_in_the_files_own_racks(traced):
    r = spec.rehearsal_config(_config())
    assert r["argv"][r["argv"].index("--num-machines") + 1] == "312"
    assert r["argv"][r["argv"].index("--fake-racks") + 1] == "250"
    assert (r["resident_pods"], r["wave_pods"]) == (3375, 125)
    assert quincy_blocks.cluster_of(r) == (312, 250)
    shapes = traced["facts"]["shapes"]
    assert (shapes["machines"], shapes["task_classes"], shapes["path"]) == (312, 2, "csr")


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["data_locality"]
    assert replay["replayed"] == out["facts"]["capacity"]["replayed"] > 3375 + 400
    assert (replay["racks"], replay["nodes"], replay["node_capacity"]) == (250, 312, 12)
    assert replay["rounds_compared"] == replay["rounds"] > 20 and replay["rounds_short_of_room"] == 0
    assert replay["served_cost"] == replay["optimum_cost"] > 0
    assert replay["largest_round"] == 3375 and replay["pods_left_waiting_at_most"] == 0
    assert replay["pods_reading"] == replay["pods_bound"] - 3375 == sum(replay["bound_via"])
    assert replay["most_arcs_a_pod"] <= 14 and replay["polls"] > replay["rounds"]
    assert out["stderr_tail"].startswith('correct: {"correct": true')
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0 and metrics["device_round_share"] == 100.0
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in (everywhere - {"solve_roofline"}) | set(BROUGHT) | set(APPENDED):
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "backend_solve_ms", "supersteps_p50", "task_refresh_ms",
                 "pref_refresh_ms", "pref_arcs_live", "pref_arcs_changed", "ec_chain_refresh_ms",
                 "journal_changes", "plan_rows", "plan_rows_live", "remote_bytes_share"):
        assert metrics[name] > 0.0, name
    # the span lies inside the task turns; what a round adds, its pins take away
    assert metrics["pref_refresh_ms"] < metrics["task_refresh_ms"]
    assert metrics["pref_arcs_changed"] == 2 * metrics["pref_arcs_live"]
    assert 0.0 <= metrics["bound_on_preferred_share"] <= 100.0 and metrics["remote_bytes_share"] <= 100.0
    # the guards of PRs 25-36, read in the new cell: no resource turn, no full walk
    assert metrics["res_nodes_visited"] == 0.0 and metrics["res_arcs_changed"] == 0.0
    assert metrics["stats_full_walks"] == 0.0 and metrics["apply_full_walks"] == 0.0
    assert metrics["unscheduled_by_rule"] == 0.0 and metrics["plan_regrowths"] == 0.0


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES


# -- controls: a planted fault turns `correct` false ------------------------------------------

#: the cluster hears, for the first pod of the open loop that was bound to a node that
#: holds a block of its input, another node: one with room, in a rack that holds none
MOVED = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "post, submit = client.BenchClusterAPI.assign_bindings, client.BenchClusterAPI.submit_pod\n"
    "inputs, load, moved = {}, {}, []\n"
    "def submit_pod(self, pod):\n"
    "    inputs[pod.pod_id] = pod.inputs\n"
    "    return submit(self, pod)\n"
    "def assign_bindings(self, bindings):\n"
    "    out = []\n"
    "    for b in bindings:\n"
    "        holders = {n for _b, _s, nodes in inputs.get(b.pod_id, ()) for n in nodes}\n"
    "        if not moved and b.pod_id[0] == 'p' and b.node_id in holders:\n"
    "            racks = {int(n.rsplit('_', 1)[1]) % 250 for n in holders}\n"
    "            other = next(f'fake_node_{i}' for i in range(312)\n"
    "                         if i % 250 not in racks and load.get(f'fake_node_{i}', 0) < 9)\n"
    "            moved.append(b.pod_id)\n"
    "            b = Binding(pod_id=b.pod_id, node_id=other)\n"
    "        load[b.node_id] = load.get(b.node_id, 0) + 1\n"
    "        out.append(b)\n"
    "    post(self, out)\n"
    "client.BenchClusterAPI.submit_pod = submit_pod\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)
#: the model lists no rack aggregator for a task: a pod whose machines are full goes
#: through X to wherever a slot is, not to the rack that holds its input
NO_RACK_TIER = (
    "from ksched_tpu.costmodels import quincy\n"
    "from ksched_tpu.costmodels.base import CLUSTER_AGGREGATOR_EC\n"
    "quincy.QuincyCostModel.get_task_equiv_classes = lambda self, task_id: [CLUSTER_AGGREGATOR_EC]\n"
)
#: the model prices a preferred machine at its rack's bound, rho(t, l) where d(t, m) belongs
#: (and at alpha where the rack is not preferred): the machine that holds the input is no
#: better than its neighbours
MACHINE_AT_RACK_BOUND = (
    "from ksched_tpu.costmodels import quincy\n"
    "def cost(self, task_id, resource_id):\n"
    "    inp = self._input(task_id)\n"
    "    rack = self._machine_rack.get(resource_id)\n"
    "    return next((c for l, c in inp.racks if l == rack), inp.alpha)\n"
    "quincy.QuincyCostModel.task_to_resource_node_cost = cost\n"
)
#: node 5 carries the label of rack 6 (the check meets the clash at node 6: two racks, one label)
WRONG_LABEL = (
    "from ksched_tpu import cli\n"
    "from ksched_tpu.cluster.api import NodeEvent\n"
    "from ksched_tpu.data import RACK_LABEL\n"
    "add = cli.SchedulerService.add_node\n"
    "def add_node(self, node):\n"
    "    if node.node_id == 'fake_node_5':\n"
    "        node = NodeEvent(node_id=node.node_id, num_cores=node.num_cores,\n"
    "                         pus_per_core=node.pus_per_core, labels=((RACK_LABEL, 'rack-6'),))\n"
    "    add(self, node)\n"
    "cli.SchedulerService.add_node = add_node\n"
)


@pytest.mark.parametrize("patch, word", [
    (MOVED, "by their cheapest routes, the optimum of the round is"),
    (NO_RACK_TIER, "by their cheapest routes, the optimum of the round is"),
    (MACHINE_AT_RACK_BOUND, "by their cheapest routes, the optimum of the round is"),
    (WRONG_LABEL, "rack 6 by its name, label 'rack-6' on the service"),
], ids=["a-binding-moved-off-a-preferred-machine", "a-model-without-the-rack-tier",
        "a-preferred-machine-priced-at-the-racks-bound", "a-rack-label-that-disagrees-with-the-file"])
def test_a_run_with_a_planted_fault_prints_correct_false_and_the_fault(patch, word):
    out = _rehearse(0, patch=patch)
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    faults = [f for f in out["facts"]["faults"] if word in f]
    assert faults, out["facts"]["faults"]
    assert out["failed"] == 0  # the service bound every pod: it is the record that tells
    assert '"correct": false' in out["stderr_tail"] and word in out["stderr_tail"]


# -- the check on a run built by hand -----------------------------------------------------------


def _ctx(log, inputs, labels, racks=2, polls=()):
    nodes = {f"fake_node_{i}": 100 + i for i in range(len(labels))}
    descriptors = {100 + i: SimpleNamespace(descriptor=SimpleNamespace(labels=l)) for i, l in enumerate(labels)}
    svc = SimpleNamespace(
        node_to_machine=nodes, resource_map=SimpleNamespace(find=descriptors.get),
        api=SimpleNamespace(polls=list(polls)),
    )
    pods = sorted({pod for _k, pod, _n, _t in log})
    plan = SimpleNamespace(
        resident=[(p, 0) for p in pods], closing=[], class_sweep=[], arrival_classes=None, wave_pods=0,
    )
    return SimpleNamespace(
        config={"racks": racks}, plan=plan, log=log, svc=svc, facts={},
        svc_args=SimpleNamespace(cores_per_machine=1, pus_per_core=1, max_tasks_per_pu=1),
        make_pod=lambda pod, c: SimpleNamespace(inputs=inputs.get(pod, ())),
    )


def test_the_check_on_a_run_built_by_hand():
    from ksched_tpu.data import RACK_LABEL

    labels = [{RACK_LABEL: f"rack-{i % 2}"} for i in range(4)]
    on_0 = ((1, 64 << 20, ("fake_node_0",)),)
    log = [("bind", "a", "fake_node_0", 1.0), ("bind", "b", "fake_node_2", 2.0)]
    ctx = _ctx(log, {"a": on_0, "b": on_0}, labels, polls=[(0.1, 0.5, 1), (1.2, 1.5, 1), (2.5, 2.6, 0)])
    assert data_locality.check(ctx) == []
    facts = ctx.facts["data_locality"]
    assert (facts["rounds_compared"], facts["served_cost"], facts["optimum_cost"]) == (2, 4, 4)
    assert (facts["racks"], facts["nodes"], facts["node_capacity"], facts["polls"]) == (2, 4, 1, 3)
    assert facts["bound_via"] == [1, 1, 0] and facts["bound_on_preferred_share"] == 100.0
    # b across the core switch though its rack had room
    ctx = _ctx([log[0], ("bind", "b", "fake_node_1", 2.0)], {"a": on_0, "b": on_0}, labels)
    (fault,) = data_locality.check(ctx)
    assert fault.startswith("data locality broken: t=2.000000: the round's 1 Bindings cost 8")
    # the service's labels are another partition than the file's
    ctx = _ctx(log, {"a": on_0, "b": on_0}, [{RACK_LABEL: "rack-0"}] * 4)
    assert "node fake_node_1: rack 1 by its name, label 'rack-0' on the service" in data_locality.check(ctx)[0]
    ctx = _ctx(log, {"a": on_0, "b": on_0}, [{}] * 4)
    assert data_locality.check(ctx)[0] == f"node fake_node_0 carries no {RACK_LABEL} label"
    # a service without the benchmark's polls is held to (a) alone
    ctx = _ctx(log, {"a": on_0, "b": on_0}, labels)
    del ctx.svc.api
    assert data_locality.check(ctx) == [] and ctx.facts["data_locality"]["polls"] == 0
