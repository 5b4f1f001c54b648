"""`--array-round`: the service whose round is `DeviceBulkCluster`'s device
round, built by `cli.build_service` as every service is, over
`SyntheticClusterAPI`, at 25 machines x 4 PUs x 16 slots (1,600 slots, a
table of 4,096 rows).

Every served round is held to the plain reference of
`benchmarks/reference_coco.py` on books the test keeps from the Bindings and
completions alone: the sum of cost(c, m) over the round's Bindings on the
census of the round's start, plus 2,500 for each pod it left waiting, equals
`reference_round`'s optimum, exactly. After every round the host's mirror
(which row a pod holds, which PU a row) equals the device's table."""

import warnings

import numpy as np
import pytest

from benchmarks import reference_coco as ref
from ksched_tpu import cli
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.cluster.api import Binding, NodeEvent, PodEvent
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.ops import get_pallas_mode, set_pallas_mode
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.scheduler import array_service
from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster

MACHINES, SLOTS = 25, 64
ARGV = (
    f"--fake-machines --num-machines {MACHINES} --cores-per-machine 1 --pus-per-core 4 "
    "--max-tasks-per-pu 16 --cost-model coco --pod-batch-timeout 0.002 --pod-chan-size 8000"
).split()


def build(extra=("--array-round",), tracer=None, span_tracer=None, supersteps=None):
    args = cli.build_arg_parser().parse_args(ARGV + list(extra))
    api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
    svc = cli.build_service(args, api, tracer=tracer, span_tracer=span_tracer)
    if supersteps is not None:
        svc.supersteps = supersteps
    svc.init_topology(
        fake_machines=args.num_machines, cores_per_machine=args.cores_per_machine,
        pus_per_core=args.pus_per_core,
    )
    return svc, api


class Books:
    """The test's own census, from Bindings and completions alone."""

    def __init__(self):
        self.census = np.zeros((MACHINES, 4), np.int64)
        self.where, self.class_of, self.posted = {}, {}, set()
        self.waiting = []

    def pods(self, rng, n, tag):
        pods = [PodEvent(f"{tag}_{i}", task_class=int(c)) for i, c in enumerate(rng.integers(0, 4, n))]
        self.class_of.update((p.pod_id, p.task_class) for p in pods)
        return pods

    def complete(self, svc, pod):
        assert svc.complete_pod(pod)
        self.census[self.where.pop(pod), self.class_of[pod]] -= 1

    def close_round(self, api, batch):
        """Hold the round that just ran to the reference; returns
        (Bindings of the round, pods left waiting)."""
        self.waiting += [p.pod_id for p in batch]
        new = {p: n for p, n in api.bindings().items() if p not in self.posted}
        self.posted.update(new)
        assert set(new) <= set(self.waiting)
        left = [p for p in self.waiting if p not in new]
        cost = ref.cost_matrix(self.census)
        index = {pod: int(node.rsplit("_", 1)[1]) for pod, node in new.items()}
        served = sum(int(cost[self.class_of[p], m]) for p, m in index.items())
        served += ref.UNSCHEDULED_COST * len(left)
        by_class = np.bincount([self.class_of[p] for p in self.waiting], minlength=4)
        free = MACHINES * SLOTS - int(self.census.sum())
        assert served == ref.reference_round(by_class, self.census, SLOTS)
        assert not left or len(new) == free  # a pod waits only if every slot was taken
        for pod, m in index.items():
            self.where[pod] = m
            self.census[m, self.class_of[pod]] += 1
        self.waiting = left
        return new, left


def mirror_is_the_table(svc):
    st = svc.cluster.fetch_state()
    live, pu = np.asarray(st["live"]), np.asarray(st["pu"])
    assert set(np.flatnonzero(live).tolist()) == set(svc.row_of.values())
    assert (np.where(live, pu, -1) == svc.pu_of_row).all()
    assert [svc.pod_at[r] for r in svc.row_of.values()] == list(svc.row_of)
    placed = live & (pu >= 0)
    running = np.asarray(st["pu_running"])
    assert (np.bincount(pu[placed], minlength=running.size) == running).all()
    assert running.max(initial=0) <= 16


@pytest.fixture
def pallas_interpret():
    prev = get_pallas_mode()
    set_pallas_mode("interpret")
    yield
    set_pallas_mode(prev)


def serve_a_stream(seed, rounds=8):
    """Fill, trickle rounds of arrivals and completions, a burst larger
    than the free slots, the quiet-channel round after a completion."""
    rng = np.random.default_rng(seed)
    svc, api = build()
    books = Books()
    fill = books.pods(rng, 1200, "r")
    assert svc.run_round(fill) == 1200
    new, left = books.close_round(api, fill)
    assert len(new) == 1200 and not left
    mirror_is_the_table(svc)
    nonzero = 0
    for r in range(rounds):
        for pod in list(books.where)[: int(rng.integers(0, 6))]:
            books.complete(svc, pod)
        batch = books.pods(rng, int(rng.integers(1, 9)), f"p{r}")
        assert svc.run_round(batch) == len(batch)
        before = int(books.census.sum())
        new, left = books.close_round(api, batch)
        cost = ref.cost_matrix(books.census)
        nonzero += any(cost[books.class_of[p], m] for p, m in ((p, books.where[p]) for p in new))
        assert len(new) == len(batch) and int(books.census.sum()) == before + len(batch)
        mirror_is_the_table(svc)
    # a burst larger than the free slots: every slot is taken, the rest wait
    free = MACHINES * SLOTS - int(books.census.sum())
    burst = books.pods(rng, free + 7, "b")
    assert svc.run_round(burst) == free
    new, left = books.close_round(api, burst)
    assert len(left) == 7 and len(svc._waiting_rows) == 7
    mirror_is_the_table(svc)
    return svc, api, books, nonzero


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_round_costs_the_references_optimum_and_the_mirror_is_the_table(seed):
    svc, _api, _books, nonzero = serve_a_stream(seed)
    assert nonzero  # rounds whose Bindings cost something: W was really compared
    assert (svc.unconverged_rounds, svc.admissions_short, svc.admissions_deferred,
            svc.cost_overflows, svc.completions_refused) == (0, 0, 0, 0, 0)


def test_the_compiled_kernel_under_the_interpreter_serves_the_same_optimum(pallas_interpret):
    serve_a_stream(3, rounds=2)


def test_a_waiting_pod_binds_on_a_quiet_channel_once_a_completion_frees_a_slot():
    svc, api, books, _ = serve_a_stream(4, rounds=2)
    rounds = svc.rounds
    # nothing changed: a quiet poll is an idle sweep, no device round
    assert not svc.backlog_dirty
    assert svc.run_round([], solve=svc.backlog_dirty) == 0 and svc.rounds == rounds
    # a completion frees a slot while pods wait: the next quiet poll is a round
    books.complete(svc, next(iter(books.where)))
    assert svc.backlog_dirty
    assert svc.run_round([], solve=svc.backlog_dirty) == 1 and svc.rounds == rounds + 1
    new, left = books.close_round(api, [])
    assert len(new) == 1 and len(left) == 6 and not svc.backlog_dirty
    mirror_is_the_table(svc)


@pytest.mark.parametrize("pods", [1200, 1700], ids=["fits", "oversubscribed"])
def test_the_fill_round_costs_what_the_graph_paths_costs_on_the_same_batch(pods):
    rng = np.random.default_rng(5)
    classes = rng.integers(0, 4, pods)
    costs = []
    for extra in (("--array-round",), ("--backend", "auto")):
        svc, api = build(extra)
        bound = svc.run_round([PodEvent(f"r{i}", task_class=int(c)) for i, c in enumerate(classes)])
        assert bound == min(pods, MACHINES * SLOTS) == len(api.bindings())
        # the census of the round's start is empty: a Binding costs nothing, a pod left waiting 2,500
        costs.append(ref.UNSCHEDULED_COST * (pods - bound))
        load = np.bincount([int(n.rsplit("_", 1)[1]) for n in api.bindings().values()], minlength=MACHINES)
        assert load.max() <= SLOTS
    assert costs[0] == costs[1] == ref.reference_round(np.bincount(classes, minlength=4),
                                                       np.zeros((MACHINES, 4), np.int64), SLOTS)


def test_a_batch_the_table_cannot_hold_waits_on_the_host_and_is_counted():
    svc, api = build()
    rows = svc.cluster.Tcap
    assert rows == 4096 and svc.widths == (256, 4096)
    rng = np.random.default_rng(6)
    pods = [PodEvent(f"r{i}", task_class=int(c)) for i, c in enumerate(rng.integers(0, 4, rows + 50))]
    with pytest.warns(RuntimeWarning, match="the task table is full: 50 pods wait on the host"):
        assert svc.run_round(pods) == MACHINES * SLOTS
    assert svc.admissions_deferred == 1 and len(svc._deferred) == 50 and len(svc.row_of) == rows
    mirror_is_the_table(svc)
    # rows free: the pods put off take them first, in the order they came
    for pod in list(api.bindings())[:60]:
        assert svc.complete_pod(pod)
    assert svc.backlog_dirty
    svc.run_round([], solve=True)
    assert not svc._deferred and svc.admissions_deferred == 1 and svc.admissions_short == 0
    assert all(f"r{rows + i}" in svc.row_of for i in range(50))
    mirror_is_the_table(svc)


def test_a_round_that_reaches_its_superstep_bound_is_counted_and_said():
    tracer = RoundTracer()
    svc, _api = build(tracer=tracer, supersteps=1)
    rng = np.random.default_rng(7)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the fill may stop at the bound too
        svc.run_round([PodEvent(f"r{i}", task_class=int(c)) for i, c in enumerate(rng.integers(0, 4, 900))])
    with pytest.warns(RuntimeWarning, match="reached its bound of 1 supersteps"):
        svc.run_round([PodEvent(f"p{i}", task_class=i % 4) for i in range(40)])
    assert svc.unconverged_rounds >= 1 and tracer.records[-1].array_unconverged == 1


def test_a_completion_for_a_pod_that_holds_no_pu_is_refused_and_counted():
    svc, _api, _books, _ = serve_a_stream(8, rounds=1)
    waiting = svc.pod_at[next(iter(svc._waiting_rows))]
    assert svc.complete_pod("never_submitted") is False
    assert svc.complete_pod(waiting) is False  # holds a row, no PU yet
    assert svc.completions_refused == 2 and waiting in svc.row_of


def test_a_pod_delivered_again_keeps_its_row_and_gets_its_binding_again():
    svc, api = build()
    pods = [PodEvent(f"r{i}", task_class=i % 4) for i in range(10)]
    svc.run_round(pods)
    first = api.bindings()["r3"]
    rows = dict(svc.row_of)
    posted = []
    api.assign_bindings = lambda out: posted.extend(out)
    assert svc.run_round([pods[3], PodEvent("fresh", task_class=1)]) == 2
    assert Binding("r3", first) in posted and len(posted) == 2
    assert {p: svc.row_of[p] for p in rows} == rows and len(svc.row_of) == 11


def test_a_pod_of_no_coco_class_is_refused_by_name():
    svc, _api = build()
    with pytest.raises(ValueError, match="pod odd: task class 4, CoCo has 4"):
        svc.run_round([PodEvent("odd", task_class=4)])


def test_the_round_opens_its_spans_and_stamps_its_record_with_exact_bytes():
    tracer = RoundTracer()
    with SpanTracer() as spans:
        svc, api = build(tracer=tracer, span_tracer=spans)
        svc.run_round([PodEvent(f"r{i}", task_class=i % 4) for i in range(300)])
        svc.complete_pod("r0")
        mark = spans.mark()
        svc.run_round([PodEvent(f"p{i}", task_class=i % 4) for i in range(3)])
        events = spans.events_since(mark)
        svc.run_round([], solve=False)
    names = [e["name"] for e in events if e["name"] != "gc_pause"]
    assert names == [
        "array_completions", "array_admit", "pods_admit", "array_launch", "array_wait",
        "array_readback", "round", "bindings_collect", "bindings_post", "round_accounting",
        "service_round",
    ]
    by_name = {e["name"]: e for e in events}
    assert by_name["service_round"]["args"]["pods"] == 3
    assert by_name["array_launch"]["args"]["width"] == 256
    assert by_name["bindings_collect"]["args"]["new"] == 3
    fill, served, idle = tracer.records
    # 300 classes ride a 4,096-wide bucket (the next above 256); 300 placed rows come back as wide
    assert (fill.array_h2d_bytes, fill.array_d2h_bytes) == (4 * 4096 + 4, 28 + 4 + 8 * 4096)
    # one completed row and three classes, a 256-wide bucket each; 28 B of scalars, the admitted
    # count, 256 (row, PU) pairs
    assert (served.array_h2d_bytes, served.array_d2h_bytes) == (2 * (4 * 256 + 4), 28 + 4 + 8 * 256)
    assert (served.num_scheduled, served.solver_rung, served.array_rows_live) == (3, 0, 302)
    assert served.solver_work > 0 and served.array_pods_waiting == 0 == served.array_unconverged
    assert served.phases_ms["total"] > 0
    assert (idle.solver_rung, idle.num_scheduled, idle.array_h2d_bytes) == (-1, 0, 0)
    assert len(api.bindings()) == 303


REFUSED = {
    "--preemption": ["--preemption"],
    "--pipeline": ["--pipeline"],
    "--device-resident": ["--device-resident"],
    "--audit-every": ["--audit-every", "4"],
    "--tenants": ["--tenants", "2"],
    "--fake-machine-types": ["--fake-machine-types", "A:1:500,B:2:500"],
    "--fake-zones": ["--fake-zones", "3"],
    "--fake-racks": ["--fake-racks", "5"],
    "--fake-node-allocatable": ["--fake-node-allocatable", "4000:32768"],
    "--machine-timeout": ["--machine-timeout", "30"],
    "--cost-model whare": ["--cost-model", "whare"],
    "--cost-model trivial": ["--cost-model", "trivial"],
    "--backend jax": ["--backend", "jax"],
    "--backend auto": ["--backend", "auto"],
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_what_the_array_round_does_not_serve_is_refused_at_build_with_the_flag_named(flag):
    argv = [a for a in ARGV if a not in ("--cost-model", "coco")] if "cost-model" in flag else ARGV
    args = cli.build_arg_parser().parse_args(list(argv) + ["--array-round"] + REFUSED[flag])
    with pytest.raises(ValueError, match=f"--array-round is not served together with {flag}: "):
        cli.build_service(args, SyntheticClusterAPI(pod_chan_size=10))


@pytest.mark.parametrize("extra", [["--pipeline"], ["--tenants", "2"]], ids=["pipeline", "tenants"])
def test_main_refuses_the_same_with_argparses_exit(extra, capsys):
    with pytest.raises(SystemExit) as e:
        cli.main(ARGV + ["--array-round", "--podgen", "4", "--one-shot"] + extra)
    assert e.value.code == 2
    assert "--array-round is not served together with " + extra[0] in capsys.readouterr().err


def test_main_serves_pods_one_shot_through_the_array_round(capsys):
    assert cli.main(ARGV + ["--array-round", "--podgen", "40", "--one-shot"]) == 0
    assert "scheduled 40/40 pods" in capsys.readouterr().err


def test_checkpoints_heartbeats_and_late_or_unlike_nodes_are_refused_with_a_sentence():
    svc, _api = build()
    with pytest.raises(NotImplementedError, match="no checkpoint yet"):
        svc.save_checkpoint("/nowhere")
    with pytest.raises(NotImplementedError, match="restores from no checkpoint"):
        type(svc).restore("/nowhere")
    with pytest.raises(ValueError, match="--machine-timeout"):
        svc.enable_heartbeats(machine_timeout_s=30.0)
    with pytest.raises(ValueError, match="node late: --array-round builds its table for the 25 machines"):
        svc.add_node(NodeEvent("late", num_cores=1, pus_per_core=4))


def test_polled_nodes_that_are_alike_are_served_under_their_own_names_and_unlike_ones_refused():
    args = cli.build_arg_parser().parse_args(
        "--max-tasks-per-pu 16 --cost-model coco --array-round --node-batch-timeout 0.05".split()
    )
    api = SyntheticClusterAPI(pod_chan_size=100)
    for name in ("alpha", "beta", "gamma"):
        api.submit_node(NodeEvent(name, num_cores=1, pus_per_core=2))
    svc = cli.build_service(args, api)
    assert svc.init_topology(node_batch_timeout_s=0.05) == 3 and svc.cluster.P == 2
    svc.run_round([PodEvent(f"r{i}", task_class=i % 4) for i in range(70)])
    assert set(api.bindings().values()) == {"alpha", "beta", "gamma"}
    api2 = SyntheticClusterAPI(pod_chan_size=100)
    api2.submit_node(NodeEvent("one", num_cores=1, pus_per_core=2))
    api2.submit_node(NodeEvent("two", num_cores=2, pus_per_core=2))
    with pytest.raises(ValueError, match="node two: 2 cores x 2 PUs, the nodes before it 1 x 2"):
        cli.build_service(args, api2).init_topology(node_batch_timeout_s=0.05)


# -- scheduler/device_bulk.py: what serving added ---------------------------------------


def _cluster(rows=2048):
    from ksched_tpu.costmodels import coco
    from ksched_tpu.costmodels.device_costs import coco_device_cost_fn

    return DeviceBulkCluster(
        num_machines=12, pus_per_machine=4, slots_per_pu=16, num_jobs=1, num_task_classes=4,
        task_capacity=rows, class_cost_fn=coco_device_cost_fn(),
        unsched_cost=coco.UNSCHEDULED_COST, ec_cost=0,
    )


@pytest.mark.parametrize("width", [256, None], ids=["window-256", "every-row"])
def test_serve_round_is_round_and_names_the_rows_it_placed(width):
    import jax

    rng = np.random.default_rng(9)
    a, b = _cluster(), _cluster()
    for dev in (a, b):
        dev.add_tasks(500, classes=rng.integers(0, 4, 500).astype(np.int32))
        rng = np.random.default_rng(9)
    a.round(), b.serve_round()
    for dev in (a, b):
        dev.complete_tasks([3, 40, 77])
        dev.add_tasks(100, classes=np.arange(100, dtype=np.int32) % 4)
    before = b.fetch_state()
    stats = a.fetch_stats(a.round())
    summary, rows, pus = jax.device_get(b.serve_round(decode_width=width))
    got = dict(zip(array_service.SERVED_SUMMARY, summary.tolist()))
    assert got == {k: int(stats[k]) for k in array_service.SERVED_SUMMARY}
    sa, sb = a.fetch_state(), b.fetch_state()
    for key in sa:
        assert np.array_equal(sa[key], sb[key]), key
    moved = np.flatnonzero(before["live"] & (before["pu"] < 0) & (sb["pu"] >= 0))
    assert got["placed"] == len(moved) == 100
    kept = rows < b.Tcap
    assert rows[kept].tolist() == moved.tolist() and pus[kept].tolist() == sb["pu"][moved].tolist()
    assert rows.size == (b.Tcap if width is None else width)
    if width is not None:  # compacted to the front
        assert kept[:100].all() and not kept[100:].any()


def test_uploads_of_a_batchs_width_admit_and_retire_what_the_tables_width_does():
    a, b = _cluster(), _cluster()
    classes = (np.arange(40, dtype=np.int32) * 7) % 4
    a.add_tasks(40, classes=classes)
    b.add_tasks(40, classes=classes, width=256)
    a.round(), b.round()
    a.complete_tasks([1, 5, 39])
    b.complete_tasks([1, 5, 39], width=256)
    a.add_tasks(2, classes=[3, 2])
    b.add_tasks(2, classes=[3, 2], width=256)
    sa, sb = a.fetch_state(), b.fetch_state()
    for key in sa:
        assert np.array_equal(sa[key], sb[key]), key
    assert int(b.last_admitted) == 2 and sb["live"].sum() == 39
    with pytest.raises(ValueError, match="300 tasks do not fit sources 256 wide"):
        b.add_tasks(300, classes=np.zeros(300, np.int32), width=256)


def test_a_preempting_cluster_serves_no_round():
    dev = DeviceBulkCluster(
        num_machines=4, pus_per_machine=2, slots_per_pu=2, num_jobs=1, num_task_classes=2,
        task_capacity=64, preemption=True,
    )
    with pytest.raises(ValueError, match="preemption is not served"):
        dev.serve_round()
