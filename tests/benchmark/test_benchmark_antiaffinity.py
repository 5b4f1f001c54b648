"""The `k8s-5000-antiaffinity` deployment and its cell: the configuration is
scheduler_perf's `SchedulingPodAntiAffinity` `5000Nodes` at its source's
shapes, the cell rehearses `correct` at 1/40 scale (125 machines, 1,250
pods, 16 workloads) traced and untraced with no program compiled in the
window, every per-layer metric without a cell list reads a number there,
and `correct` holds the run to the rule: `checks/anti_affinity.py` replays
the whole run's Binding log through `check_anti_affinity`, so a Binding
altered where it is posted, or a replay that reports a violation, prints
`correct` false with the fault."""

import json
import os
import subprocess
import sys
import threading

import pytest

from benchmarks import spec
from benchmarks.reference_antiaffinity import check_anti_affinity

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CONFIG = "k8s-5000-antiaffinity"
CELL = CONFIG + ".trickle"
SEED = 2147483659  # more than 32 signed bits hold, as the driver's are
GUARANTEES = ["binding", "capacity", "answer", "anti_affinity"]


def _config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _rehearse(trace, patch=None, seconds="3"):
    """The cell's rehearsal in a process of its own; with `patch`, the same
    run with those lines executed before `benchmarks.run.main`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", seconds,
            "--trace", str(trace), "--rehearse-cpu"]
    if patch is None:
        entry = BENCH["command"][1:]
    else:
        entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
                 "import benchmarks.run as run\n" + patch + "sys.exit(run.main())\n"]
    r = subprocess.run(
        [sys.executable, *entry, *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    out["stderr_tail"] = r.stderr.strip().splitlines()[-1]
    return out


@pytest.fixture(scope="module")
def traced():
    return _rehearse(1)


def test_the_configuration_is_the_sources_shapes():
    c = _config()
    assert c["argv"] == (
        "--fake-machines --num-machines 5000 --cores-per-machine 1 --pus-per-core 1 "
        "--max-tasks-per-pu 110 --cost-model k8s_antiaffinity --backend jax "
        "--pod-batch-timeout 0.002 --pod-chan-size 53000"
    ).split()
    assert (c["resident_pods"], c["task_classes"], c["wave_pods"]) == (50000, 16, 2500)
    assert c["reduced"] == [] and len(c["assumed"]) >= 5
    assert list(c["guarantees"]) == GUARANTEES
    others = _config("coco-50kx1k")["guarantees"]
    assert {k: c["guarantees"][k] for k in others} == others
    assert "n(g, m) <= 1" in c["guarantees"]["anti_affinity"]
    entry = next(e for e in BENCH["configs"] if e["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == c["source"] and entry["reduced"] == [] and entry["why"] == c["why"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("scheduler_perf", "SchedulingPodAntiAffinity", "5000Nodes", "large clusters"):
        assert word in entry["source"]


def test_the_cell_takes_one_chip_and_the_trickle_as_it_stands():
    w = next(e for e in BENCH["workloads"] if e["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "trickle", 1)
    assert len(w["why"]) <= 200
    cell = spec.load_cell(CELL)
    assert cell.traffic == spec.load_cell("coco-50kx1k.trickle").traffic
    assert {m["name"] for m in cell.end_to_end} >= {"bind_p50_ms", "setup_s"}
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {m["name"] for m in cell.per_layer} >= everywhere


def test_the_rehearsal_is_the_fortieth(traced):
    r = spec.rehearsal_config(_config())
    assert r["argv"][r["argv"].index("--num-machines") + 1] == "125"
    assert (r["resident_pods"], r["task_classes"]) == (1250, 16)
    assert traced["facts"]["shapes"] == {
        "nodes": 2048, "arcs": 8192, "machines": 125, "task_classes": 16, "path": "csr",
    }


def test_the_traced_rehearsal_is_correct_and_every_metric_reads_a_number(traced):
    out = traced
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["facts"]["warmup_extensions"] == 0
    assert out["facts"]["closing"]["objective"] == out["facts"]["closing"]["native_objective"]
    # `correct` ran the configuration's guarantees, in the file's order, and
    # the rule's replay took in the whole run: the fill, the sweep, the window
    assert out["facts"]["checks"] == GUARANTEES
    assert list(out["facts"]["check_seconds"]) == GUARANTEES
    replay = out["facts"]["anti_affinity"]
    assert replay["workloads"] == 16 and replay["replayed"] > 1250 + 15 * 8 + out["attempted"]
    assert replay["replayed"] == out["facts"]["capacity"]["replayed"]
    assert out["stderr_tail"].startswith('correct: {"correct": true') and "n(g, m) <= 1" in out["stderr_tail"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert metrics["compiles_in_window"] == 0.0 and metrics["device_round_share"] == 100.0
    # all but the roofline share, which the host has no peaks for
    everywhere = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    for name in everywhere - {"solve_roofline"}:
        assert isinstance(metrics[name], float) and metrics[name] == metrics[name], name
    for name in ("round_p50_ms", "graph_update_ms", "backend_solve_ms", "solve_device_ms",
                 "supersteps_p50", "apply_ms", "queue_wait_ms", "round_accounted_share",
                 "stats_ms", "graph_refresh_ms", "ec_refresh_ms", "apply_walk_ms",
                 "runnable_scan_ms", "ec_arcs_changed", "decode_tasks"):
        assert metrics[name] > 0.0, name


def test_the_untraced_rehearsal_is_correct_and_reports_the_two_end_to_end_metrics():
    out = _rehearse(0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"bind_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["facts"]["checks"] == GUARANTEES


#: the Bindings of the first eight arrivals (`p<i>`) are re-addressed to the
#: node the last pod of their workload was bound to: the service solved the
#: round rightly, and the cluster hears an answer that breaks the rule
TWIN_BINDINGS = (
    "import benchmarks.client as client\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "submit, post = client.BenchClusterAPI.submit_pod, client.BenchClusterAPI.assign_bindings\n"
    "workload, last, moved = {}, {}, []\n"
    "def submit_pod(self, ev):\n"
    "    workload[ev.pod_id] = ev.task_class\n"
    "    return submit(self, ev)\n"
    "def assign_bindings(self, bindings):\n"
    "    out = []\n"
    "    for b in bindings:\n"
    "        g = workload[b.pod_id]\n"
    "        if b.pod_id.startswith('p') and g in last and len(moved) < 8:\n"
    "            b = Binding(pod_id=b.pod_id, node_id=last[g])\n"
    "            moved.append(b.pod_id)\n"
    "        last[g] = b.node_id\n"
    "        out.append(b)\n"
    "    post(self, out)\n"
    "client.BenchClusterAPI.submit_pod = submit_pod\n"
    "client.BenchClusterAPI.assign_bindings = assign_bindings\n"
)
#: the replay itself reports a violation (the run is sound)
REPLAY_SAYS_NO = (
    "import benchmarks.reference_antiaffinity as ref\n"
    "ref.check_anti_affinity = lambda log, group_of: "
    "'t=1.000000: node node_7 holds 2 pods of workload 3 (pod p9)'\n"
)


@pytest.mark.parametrize("patch, word", [
    (TWIN_BINDINGS, "holds 2 pods of workload"),
    (REPLAY_SAYS_NO, "node node_7 holds 2 pods of workload 3 (pod p9)"),
], ids=["a-binding-altered-where-it-is-posted", "the-replay-reports-a-violation"])
def test_a_run_that_breaks_the_rule_prints_correct_false_and_the_fault(patch, word):
    out = _rehearse(0, patch=patch, seconds="2")
    assert out["correct"] is False and out["facts"]["checks"] == GUARANTEES
    (fault,) = [f for f in out["facts"]["faults"] if "anti-affinity broken" in f]
    assert word in fault
    # the other three guarantees held: it is this check that tells
    assert out["facts"]["faults"] == [fault] and out["failed"] == 0
    assert '"correct": false' in out["stderr_tail"] and "anti-affinity broken" in out["stderr_tail"]


def test_a_stream_served_as_the_harness_serves_it_passes_the_replay():
    """The harness's own pieces in this process (the service from the
    rehearsal's argv, the plan, the traffic driver, `svc.run` in this
    thread), then the replay over the log it kept, with each pod's
    workload from the plan."""
    from benchmarks import correct, run
    from benchmarks.checks import anti_affinity, binding, capacity
    from benchmarks.client import CompileWatch, TrafficDriver
    from benchmarks.traffic import build_plan
    from ksched_tpu.utils import seed_rng

    cell = spec.load_cell(CELL)
    config = spec.rehearsal_config(cell.config)
    seed_rng(SEED)
    plan = build_plan(cell.traffic, config, SEED, 2.0)
    make_pod = spec.pod_maker(cell.pods, config, SEED)
    svc, api, svc_args, _spans, _rounds = run.build_service(config, traced=False)
    api.expect(len(plan.resident))
    for pod_id, task_class in plan.resident:
        api.submit_pod(make_pod(pod_id, task_class))
    driver = TrafficDriver(api, plan, 2.0, CompileWatch(), make_pod)
    driver.start()
    watchdog = threading.Timer(120.0, api.close)  # a hung loop ends, and the test fails below
    watchdog.start()
    try:
        svc.run(pod_batch_timeout_s=svc_args.pod_batch_timeout)
    finally:
        watchdog.cancel()
        api.close()
        driver.join(timeout=30.0)
    assert not driver.is_alive() and driver.error is None, driver.error
    group_of = correct.pod_classes(plan, api.log)
    binds = [e for e in api.log if e[0] == "bind"]
    assert len(binds) >= len(plan.resident) + len(driver.due) > len(plan.resident)
    assert any(e[0] == "done" for e in api.log)
    assert len({group_of[e[1]] for e in binds}) == 16
    assert check_anti_affinity(api.log, group_of) is None
    ctx = correct.Context(
        config=config, plan=plan, make_pod=make_pod, svc=svc, svc_args=svc_args,
        due=driver.due, bind_stamps=api.bind_stamps, log=api.log, completions_refused=0,
        compiles_in_window=0,
    )
    assert anti_affinity.check(ctx) == [] and binding.check(ctx) == []
    assert capacity.check(ctx) == [] and ctx.facts["capacity"]["node_capacity"] == 110
    assert correct.run_checks(ctx) == ([], GUARANTEES)
    # the replay does find what the rule forbids
    pod, node = next((e[1], e[2]) for e in binds)
    twin = next(p for p, g in group_of.items() if g == group_of[pod] and p != pod)
    fault = check_anti_affinity([("bind", pod, node, 0.0), ("bind", twin, node, 1.0)], group_of)
    assert fault is not None and f"workload {group_of[pod]}" in fault
    done_first = [("bind", pod, node, 0.0), ("done", pod, "", 0.5), ("bind", twin, node, 1.0)]
    assert check_anti_affinity(done_first, group_of) is None
