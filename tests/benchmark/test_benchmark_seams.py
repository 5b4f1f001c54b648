"""Three decisions the harness took for every deployment now come from the
configuration's files, each with a default that is the harness's former
behaviour: what a pod carries (`pods/<name>.py`, default `class_only`),
what the record holds (a third kind, `("evict", pod, node, t)`, which
`capacity` replays and the traffic driver reads), and what a service is
(`shapes` from what is there: a service with no graph path still gets its
result line). At rehearsal size on the CPU; entries are looked up by name."""

import hashlib
import importlib
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import run, spec, traffic
from benchmarks.client import BenchClusterAPI, DriverError, TrafficDriver
from benchmarks.pods import class_only
from ksched_tpu.cluster.api import Binding, PodEvent
from ksched_tpu.utils import rng, seed_rng

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEEDS = (5, 2147483693)  # the second: more than 32 signed bits hold, as the driver's are
#: `digest` of build_plan(cell's mix, cell's configuration, seed, 40 s) at the
#: parent of the PR that brought pods/ (0efee75): the same seed draws the same
#: pods, classes, offsets and victims in the same order
PLAN_DIGESTS = {
    "trivial-10kx1k.trickle": ("69166e71385d597a", "dc3f4d73106a5155"),
    "trivial-10kx1k.waves": ("3e9a87e04696f831", "09d15733cfbf74ea"),
    "coco-50kx1k.trickle": ("24a3de60faa865a6", "b08f334ec02def8e"),
    "coco-50kx1k.waves": ("4567e2eaa149a017", "37c18aad184d8afb"),
    "trivial-10kx1k-resident.trickle": ("69166e71385d597a", "dc3f4d73106a5155"),
    "trivial-10kx1k-resident.waves": ("3e9a87e04696f831", "09d15733cfbf74ea"),
    "k8s-5000-antiaffinity.trickle": ("bbf4ea80528ba4fe", "22f88ad91a65639a"),
    "k8s-5000-zonespread.trickle": ("bbf4ea80528ba4fe", "22f88ad91a65639a"),
}


def digest(plan) -> str:
    h = hashlib.sha256()
    h.update(repr((
        plan.kind, plan.seed, plan.task_classes, plan.resident, plan.victims, plan.class_sweep,
        plan.closing, plan.rate_per_s, plan.completions_per_arrival, plan.warmup_s,
        plan.wave_pods, plan.warmup_waves,
    )).encode())
    if plan.arrival_offsets_s is not None:
        h.update(plan.arrival_offsets_s.tobytes() + plan.arrival_classes.tobytes())
    if plan.wave_pods:
        h.update(repr([plan.wave(k) for k in (0, 7)]).encode())
    return h.hexdigest()[:16]


def _root_with(tmp_path, config_name, edit, files=None):
    """A copy of the benchmark whose `config_name` file `edit` changed, with
    `files` (path under benchmarks/ -> text) added; returns its root."""
    shutil.copytree(spec.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    entry = next(c for c in BENCH["configs"] if c["name"] == config_name)
    path = tmp_path / entry["file"]
    config = json.loads(path.read_text())
    edit(config)
    path.write_text(json.dumps(config))
    for name, text in (files or {}).items():
        (tmp_path / "benchmarks" / name).write_text(text)
    return str(tmp_path)


def _rehearse(root, cell, trace, patch=None, seconds="2"):
    """`root`'s copy of the benchmark rehearsing `cell` in a process of its
    own (the program comes from this checkout); with `patch`, those lines
    run before `benchmarks.run.main`."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    argv = ["--workload", cell, "--seed", str(SEEDS[1]), "--seconds", seconds,
            "--trace", str(trace), "--rehearse-cpu"]
    if patch is None:
        entry = BENCH["command"][1:]
    else:
        entry = ["-c", "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
                 "import benchmarks.run as run\n"
                 + patch + "sys.exit(run.main())\n"]
    r = subprocess.run(
        [sys.executable, *entry, *argv], cwd=root, env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


# -- (a) what a pod carries ---------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell_name", CELLS)
def test_class_only_is_the_old_expression_and_the_same_seed_draws_the_same_plan(cell_name, seed):
    cell = spec.load_cell(cell_name)
    assert cell.pods == "class_only" and "pods" not in cell.config
    plan = traffic.build_plan(cell.traffic, cell.config, seed, 40.0)
    assert digest(plan) == PLAN_DIGESTS[cell_name][SEEDS.index(seed)]
    make_pod = spec.pod_maker(cell.pods, cell.config, seed)
    for pod_id, task_class in plan.resident[:200] + plan.closing + sum(plan.class_sweep, []):
        assert make_pod(pod_id, task_class) == PodEvent(pod_id=pod_id, task_class=task_class)
        assert class_only.make(pod_id, task_class, cell.config, seed) == make_pod(pod_id, task_class)


def test_class_only_stamps_each_event_as_it_is_made_and_draws_nothing_from_the_frameworks_rng():
    seed_rng(77)
    state = rng().getstate()
    events = [class_only.make(f"p{i}", i % 4, {"task_classes": 4}, 77) for i in range(1000)]
    assert rng().getstate() == state  # one draw would shift every task and job id of the run
    stamps = [e.received_s for e in events]
    assert stamps == sorted(stamps) and stamps[0] < stamps[-1]  # made at submission, not ahead
    assert [f.name for f in os.scandir(os.path.join(spec.HERE, "pods")) if f.is_file()] == [
        "class_only.py"
    ]


#: pods that carry a CPU request that follows from their class, and a check
#: that recomputes what every pod carried from the plan and `ctx.make_pod`
#: and holds the service's task descriptors to it
WITH_REQUESTS = (
    "from ksched_tpu.cluster.api import PodEvent\n"
    "def make(pod_id, task_class, config, seed):\n"
    "    return PodEvent(pod_id=pod_id, task_class=task_class,\n"
    "                    cpu_request=config['cpu_request_per_class'] * (1 + task_class))\n"
)
PODS_CARRIED = (
    '"""`pods_carried`: a task holds what its pod carried."""\n'
    "from benchmarks import correct\n"
    "def check(ctx):\n"
    "    svc, faults = ctx.svc, []\n"
    "    classes = correct.pod_classes(ctx.plan, ctx.log)\n"
    "    for pod, task in svc.pod_to_task.items():\n"
    "        want = ctx.make_pod(pod, classes[pod]).cpu_request\n"
    "        have = svc.task_map.find(task).resource_request.cpu_cores\n"
    "        if have != want:\n"
    "            faults.append(f'pod {pod} carried {want} cores, its task holds {have}')\n"
    "    ctx.facts['pods_carried'] = {'compared': len(svc.pod_to_task), 'largest_request':\n"
    "        max(svc.task_map.find(t).resource_request.cpu_cores for t in svc.pod_to_task.values())}\n"
    "    return faults[:3]\n"
)


REQUESTS_LOST = (
    "import dataclasses\n"
    "import benchmarks.client as client\n"
    "keep = client.BenchClusterAPI.submit_pod\n"
    "def lossy(self, ev):\n"
    "    keep(self, dataclasses.replace(ev, cpu_request=0.0) if ev.pod_id[0] == 'p' else ev)\n"
    "client.BenchClusterAPI.submit_pod = lossy\n"
)


def _with_requests(config):
    config.update(pods="with_requests", cpu_request_per_class=0.125)
    config["guarantees"]["pods_carried"] = "a task holds what its pod carried"


def test_a_configuration_that_names_a_pods_module_gets_its_pods_and_a_check_recomputes_them(
        tmp_path):
    root = _root_with(tmp_path, "coco-50kx1k", _with_requests, files={
        "pods/with_requests.py": WITH_REQUESTS, "checks/pods_carried.py": PODS_CARRIED,
    })
    out = _rehearse(root, "coco-50kx1k.trickle", 0)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["checks"][-1] == "pods_carried" and out["failed"] == 0
    # four classes: requests of 0.125 .. 0.5 cores reached the descriptors
    carried = out["facts"]["pods_carried"]
    assert carried["compared"] >= 1250 and carried["largest_request"] == 0.5
    # the same run over an API that loses what the arrivals carried is held to the check
    out = _rehearse(root, "coco-50kx1k.trickle", 0, patch=REQUESTS_LOST)
    assert out["correct"] is False and out["facts"]["faults"]
    assert all("cores, its task holds 0.0" in f for f in out["facts"]["faults"])


def test_a_pods_module_that_is_not_there_is_refused_with_the_path_looked_for(
        tmp_path, monkeypatch):
    root = _root_with(tmp_path, "coco-50kx1k", lambda c: c.update(pods="no_such_pods"))
    monkeypatch.setattr(spec, "HERE", os.path.join(root, "benchmarks"))
    with pytest.raises(spec.SpecError, match=r"'no_such_pods'.*benchmarks/pods/no_such_pods\.py"):
        spec.load_cell("coco-50kx1k.waves", root=root)
    for bad in ("../checks/binding", "", 3):
        with pytest.raises(spec.SpecError, match="names the pods module"):
            spec.check_pods({"pods": bad}, "configs/x.json")
    # the other configurations of that root are as they were
    assert spec.load_cell("trivial-10kx1k.waves", root=root).pods == spec.DEFAULT_PODS


# -- (b) what the record holds --------------------------------------------------------


def _capacity(log, slots=2):
    ctx = SimpleNamespace(
        svc_args=SimpleNamespace(cores_per_machine=1, pus_per_core=1, max_tasks_per_pu=slots),
        log=log, facts={},
    )
    return importlib.import_module("benchmarks.checks.capacity").check(ctx), ctx.facts["capacity"]


FULL = [("bind", "a", "n0", 1.0), ("bind", "b", "n0", 1.0)]


@pytest.mark.parametrize("log, word", [
    (FULL + [("evict", "a", "n0", 2.0), ("bind", "c", "n0", 2.0)], None),
    (FULL + [("bind", "c", "n0", 2.0)], "node n0 held 3 pods, capacity 2 (pod c)"),
    (FULL + [("evict", "a", "n1", 2.0), ("bind", "c", "n0", 2.0)],
     "pod a evicted from node n1, the record has it on n0"),
    (FULL + [("evict", "z", "n0", 2.0)], "pod z evicted from node n0, the record has it on None"),
    # an evicted pod is pending: it binds again elsewhere, and completes from there
    (FULL + [("evict", "a", "n0", 2.0), ("bind", "c", "n0", 2.0), ("bind", "a", "n1", 3.0),
             ("done", "a", "", 4.0), ("bind", "d", "n1", 5.0), ("bind", "e", "n1", 5.0)], None),
    (FULL + [("evict", "a", "n0", 2.0), ("done", "a", "", 3.0)],
     "pod a completed without a Binding on record"),
    (FULL + [("evict", "a", "n0", 2.0), ("evict", "a", "n0", 2.5)],
     "pod a evicted from node n0, the record has it on None"),
], ids=["bind-to-full-evict-bind", "no-evict-is-todays-fault", "evict-from-the-wrong-node",
        "evict-of-a-pod-never-bound", "evicted-binds-again-and-completes",
        "evicted-and-pending-cannot-complete", "evicted-twice"])
def test_capacity_replays_an_eviction_and_faults_as_before_without_one(log, word):
    faults, facts = _capacity(log)
    assert faults == ([] if word is None else [word])
    assert facts["replayed"] == len(log) and facts["peak_node_load"] >= 2


def _driver(resident=8):
    config = {"resident_pods": resident, "task_classes": 2, "wave_pods": 4}
    mix = {"kind": "closed_waves", "wave_pods": "config", "warmup_waves": 1}
    plan = traffic.build_plan(mix, config, 11, 1.0)
    api = BenchClusterAPI(pod_chan_size=64)
    completed = []
    api.svc = SimpleNamespace(complete_pod=lambda pod: completed.append(pod) or True)
    driver = TrafficDriver(api, plan, 1.0, None, spec.pod_maker("class_only", config, 11))
    return driver, api, plan, completed


def test_the_api_records_an_eviction_in_the_loops_order_and_a_binding_ends_it():
    _d, api, plan, _completed = _driver()
    a, b = plan.victims[:2]
    api.assign_bindings([Binding(a, "n0"), Binding(b, "n0")])
    api.evict_pods([Binding(a, "n0")])
    assert [e[:3] for e in api.log] == [
        ("bind", a, "n0"), ("bind", b, "n0"), ("evict", a, "n0"),
    ]
    assert api.log[-1][3] >= api.log[0][3] and api.evicted == {a}
    api.assign_bindings([Binding(a, "n1")])
    assert api.evicted == set() and api.log[-1][:3] == ("bind", a, "n1")
    assert len(api.bind_stamps[a]) == 2  # `binding` would say so: a deployment under
    # preemption states a guarantee of its own instead
    assert _capacity(api.log)[0] == []


def test_the_driver_does_not_complete_an_evicted_pod_that_is_pending():
    driver, api, plan, completed = _driver()
    head, second, third = plan.victims[:3]
    api.assign_bindings([Binding(p, f"n{i}") for i, p in enumerate(plan.victims)])
    api.evict_pods([Binding(head, "n0")])
    driver._complete_next(1)
    api._deliver_completions()
    assert completed == [second] and driver.victims[-1] == head
    assert list(driver.victims)[:-1] == plan.victims[2:]
    assert api.completions_refused == 0 and api.log[-1][:2] == ("done", second)
    # bound again, it completes when its turn at the tail comes
    api.assign_bindings([Binding(head, "n5")])
    driver._complete_next(len(driver.victims))
    api._deliver_completions()
    assert completed == [second, third, *plan.victims[3:], head] and not driver.victims
    assert api.completions_refused == 0


def test_with_no_eviction_the_victims_go_first_in_first_out_as_before():
    driver, api, plan, completed = _driver()
    for pod in plan.wave(0):
        driver._submit(pod)
    order = plan.victims + [p for p, _c in plan.wave(0)]
    driver._complete_next(3)
    driver._complete_next(len(order) - 3)
    api._deliver_completions()
    assert completed == order and not api.evicted and not driver.victims
    with pytest.raises(DriverError, match="no pod is left to complete"):
        driver._complete_next(1)
    # and a deque of none but evicted, pending pods ends the plan, it does not spin
    driver, api, plan, _completed = _driver()
    api.evict_pods([Binding(p, "n0") for p in plan.victims])
    with pytest.raises(DriverError, match="every victim is evicted"):
        driver._complete_next(1)


# -- (c) what a service is ------------------------------------------------------------


def test_shapes_are_what_the_service_has():
    args, config = SimpleNamespace(num_machines=25), {"task_classes": 4}
    assert run.service_shapes(SimpleNamespace(), args, config) == {
        "machines": 25, "task_classes": 4,
    }
    assert run.service_shapes(SimpleNamespace(scheduler=SimpleNamespace()), args, config) == {
        "machines": 25, "task_classes": 4,
    }
    solver = SimpleNamespace(
        state=SimpleNamespace(n_cap=1024, m_cap=4096),
        backend=SimpleNamespace(primary=SimpleNamespace(last_path="dense")),
    )
    svc = SimpleNamespace(scheduler=SimpleNamespace(solver=solver), ladder=object())
    shapes = run.service_shapes(svc, args, config)
    assert shapes == {"nodes": 1024, "arcs": 4096, "machines": 25, "task_classes": 4,
                      "path": "dense"}
    assert list(shapes) == ["nodes", "arcs", "machines", "task_classes", "path"]  # as it printed
    solver.backend = SimpleNamespace()  # no ladder: the backend is the rung, scan-CSR
    svc.ladder = None
    assert run.service_shapes(svc, args, config)["path"] == "csr"


@pytest.mark.parametrize("shapes, reads", [
    ({"nodes": 16384, "arcs": 32768, "machines": 1000, "task_classes": 1, "path": "csr"}, True),
    ({"machines": 1000, "task_classes": 4, "path": "dense"}, True),
    ({"machines": 1000, "task_classes": 1}, False),
    ({"machines": 1000, "task_classes": 1, "path": "csr"}, False),
    ({"nodes": 16384, "arcs": 32768, "path": "dense"}, False),
], ids=["scan-csr", "transport", "no-graph-path", "no-sizes", "no-tile"])
def test_solve_roofline_reads_nothing_where_the_path_or_a_size_is_missing(shapes, reads):
    from benchmarks import observe
    from benchmarks.readers import solve_roofline

    own = json.load(open(os.path.join(spec.HERE, "layer_metrics", "solve_roofline.json")))
    obs = observe.Observation(
        device_kind="TPU v5 lite", rounds=[], records=[], client={}, counters={}, shapes=shapes,
        trace={"busy_s": 2.0, "supersteps": 1000},
    )
    value = solve_roofline.read(own["params"], obs)
    assert (value is not None and 0 < value < 100) if reads else value is None


#: a service with no graph path (as one whose round is an array program would
#: be): it takes the batch, runs one program on the device, and binds each pod
#: to the first node with a free slot
STUB_SERVICE = (
    "import jax.numpy as jnp\n"
    "from benchmarks.client import BenchClusterAPI\n"
    "from ksched_tpu import cli\n"
    "from ksched_tpu.cluster.api import Binding\n"
    "from ksched_tpu.obs.spans import SpanTracer\n"
    "from ksched_tpu.runtime.trace import RoundTracer\n"
    "class ArrayService:\n"
    "    def __init__(self, api, args):\n"
    "        self.api, self.where = api, {}\n"
    "        slots = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu\n"
    "        self.free = {f'node_{i}': slots for i in range(args.num_machines)}\n"
    "    def complete_pod(self, pod):\n"
    "        node = self.where.pop(pod, None)\n"
    "        if node is not None:\n"
    "            self.free[node] += 1\n"
    "        return node is not None\n"
    "    def run(self, pod_batch_timeout_s):\n"
    "        while True:\n"
    "            pods = self.api.poll_pod_batch(pod_batch_timeout_s)\n"
    "            if not pods and self.api.is_closed():\n"
    "                return\n"
    "            jnp.cumsum(jnp.ones(1024)).block_until_ready()\n"
    "            out = []\n"
    "            for pod in pods:\n"
    "                node = next(n for n, k in self.free.items() if k)\n"
    "                self.free[node] -= 1\n"
    "                self.where[pod.pod_id] = node\n"
    "                out.append(Binding(pod.pod_id, node))\n"
    "            if out:\n"
    "                self.api.assign_bindings(out)\n"
    "def build_service(config, traced):\n"
    "    args = cli.build_arg_parser().parse_args(config['argv'])\n"
    "    api = BenchClusterAPI(pod_chan_size=args.pod_chan_size)\n"
    "    api.svc = svc = ArrayService(api, args)\n"
    "    spans = SpanTracer(capacity=1 << 16).install() if traced else None\n"
    "    return svc, api, args, spans, RoundTracer() if traced else None\n"
    "run.build_service = build_service\n"
)


def _no_answer(config):
    # `answer` and `resident` reach into the graph path: guarantees a
    # configuration chooses to state, and this one does not
    for key in ("answer", "resident"):
        config["guarantees"].pop(key, None)


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_a_service_with_no_graph_path_gets_its_result_line(tmp_path, trace):
    root = _root_with(tmp_path, "trivial-10kx1k", _no_answer)
    out = _rehearse(root, "trivial-10kx1k.trickle", trace, patch=STUB_SERVICE)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["facts"]["shapes"] == {"machines": 25, "task_classes": 1}
    assert out["facts"]["checks"] == ["binding", "capacity"]
    assert out["attempted"] > 0 and out["failed"] == 0
    if not trace:
        assert {"bind_p50_ms", "bind_p95_ms", "setup_s"} <= set(out["metrics"])
        return
    # every reader of the cell found nothing to read, or read the client's
    # side, the benchmark's own counters or the device trace
    assert "solve_roofline" not in out["metrics"] and "round_p50_ms" not in out["metrics"]
    assert {"gen_late_p99_ms", "traced_bind_p50_ms", "compiles_in_window",
            "device_idle_share"} <= set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
