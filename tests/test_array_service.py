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
from benchmarks import reference_wharemap_array as ref_array
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
    assert (running <= svc.cluster.pu_slots).all()  # 0 for a PU its machine does not have
    load = running.reshape(len(svc.nodes), -1).sum(axis=1)
    assert (load == svc._machine_load).all() and (load <= svc.machine_slots).all()


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
    with pytest.raises(ValueError, match="pod odd: task class 4, the census has 4"):
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
    # the bucket each round's program ran at, and the machines with a free slot at its start
    assert (fill.array_decode_width, served.array_decode_width, idle.array_decode_width) == (4096, 256, 0)
    load = np.bincount([int(n.rsplit("_", 1)[1]) for p, n in api.bindings().items() if p[0] == "r" and p != "r0"],
                       minlength=MACHINES)
    assert fill.array_machines_open == MACHINES > served.array_machines_open == int((load < SLOTS).sum())
    assert len(api.bindings()) == 303


REFUSED = {
    "--preemption": ["--preemption"],
    "--pipeline": ["--pipeline"],
    "--device-resident": ["--device-resident"],
    "--audit-every": ["--audit-every", "4"],
    "--tenants": ["--tenants", "2"],
    "--fake-zones": ["--fake-zones", "3"],
    "--fake-racks": ["--fake-racks", "5"],
    "--fake-node-allocatable": ["--fake-node-allocatable", "4000:32768"],
    "--machine-timeout": ["--machine-timeout", "30"],
    "--cost-model quincy": ["--cost-model", "quincy"],
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


def test_polled_nodes_of_several_shapes_are_served_under_their_own_names_each_to_its_own_slots():
    args = cli.build_arg_parser().parse_args(
        "--max-tasks-per-pu 16 --cost-model coco --array-round --node-batch-timeout 0.05".split()
    )
    api = SyntheticClusterAPI(pod_chan_size=200)
    shapes = {"alpha": (1, 2), "beta": (2, 2), "gamma": (1, 1)}
    for name, (cores, pus) in shapes.items():
        api.submit_node(NodeEvent(name, num_cores=cores, pus_per_core=pus))
    svc = cli.build_service(args, api)
    assert svc.init_topology(node_batch_timeout_s=0.05) == 3
    # one table, every machine padded to the widest one's 4 PUs; a PU that is not there holds 0
    assert svc.cluster.P == 4 and svc.machine_slots.tolist() == [32, 64, 16]
    assert svc.cluster.pu_slots.reshape(3, 4).tolist() == [[16, 16, 0, 0], [16] * 4, [16, 0, 0, 0]]
    assert svc.run_round([PodEvent(f"r{i}", task_class=i % 4) for i in range(120)]) == 112
    load = np.bincount([list(shapes).index(n) for n in api.bindings().values()], minlength=3)
    assert load.tolist() == [32, 64, 16] and len(svc._waiting_rows) == 8
    running = np.asarray(svc.cluster.fetch_state()["pu_running"])
    assert (running <= svc.cluster.pu_slots).all() and running.sum() == 112
    with pytest.raises(ValueError, match="node none: 0 cores x 2 PUs"):
        cli.build_service(args, SyntheticClusterAPI(pod_chan_size=1)).add_node(
            NodeEvent("none", num_cores=0, pus_per_core=2))


# -- Whare-Map on machines of three types -----------------------------------------------------

TYPES = "A:1:10,B:2:930,C:4:60"
WHARE_MACHINES = 312  # the fortieth of 12,500: 1 / 284 / 27 machines of 2 / 4 / 8 PUs
WHARE_ARGV = (
    f"--fake-machines --num-machines {WHARE_MACHINES} --pus-per-core 2 --max-tasks-per-pu 3 "
    f"--fake-machine-types {TYPES} --cost-model whare --array-round --pod-batch-timeout 0.002 "
    "--pod-chan-size 8000"
).split()


def build_whare(tracer=None):
    args = cli.build_arg_parser().parse_args(WHARE_ARGV)
    api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
    svc = cli.build_service(args, api, tracer=tracer)
    svc.init_topology(fake_machines=args.num_machines, pus_per_core=args.pus_per_core)
    return svc, api, args


class Record:
    """The harness's record of a run, kept by the test: Bindings and completions in the loop's
    order, and what each round was handed; replayed by the plain reference."""

    def __init__(self, api):
        self.api, self.log, self.batches, self.class_of, self.t = api, [], [], {}, 0.0
        self.posted, self.ever = {}, set()  # bound now; bound at any time

    def pods(self, rng, n, tag):
        pods = [PodEvent(f"{tag}_{i}", task_class=int(c)) for i, c in enumerate(rng.integers(0, 4, n))]
        self.class_of.update((p.pod_id, p.task_class) for p in pods)
        return pods

    def complete(self, svc, pod):
        assert svc.complete_pod(pod)
        self.log.append(("done", pod, "", self.t))
        del self.posted[pod]

    def round(self, svc, batch, solve=True):
        self.t += 1.0
        self.batches.append((self.t - 0.5, [p.pod_id for p in batch]))
        bound = svc.run_round(batch, solve=solve)
        new = {p: n for p, n in self.api.bindings().items() if p not in self.ever}
        self.posted.update(new)
        self.ever.update(new)
        self.log += [("bind", pod, node, self.t) for pod, node in new.items()]
        assert bound == len(new)
        return new

    def replay(self, args, **kw):
        return ref_array.check_interference_map_array(
            self.log, self.class_of, [f"fake_node_{i}" for i in range(args.num_machines)],
            cli.parse_machine_types(TYPES), args.pus_per_core, args.max_tasks_per_pu,
            batches=self.batches, **kw,
        )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_whare_on_three_types_costs_the_references_optimum_round_by_round(seed):
    """Fill, arrivals and completions, a burst beyond the idle slots (a full cluster, pods that
    wait), the quiet-channel round after completions: every round at the plain reference's
    optimum on a census of the reference's own, the table equal to the mirror after each."""
    rng = np.random.default_rng(seed)
    tracer = RoundTracer()
    svc, api, args = build_whare(tracer)
    slots = int(svc.machine_slots.sum())
    assert (slots, svc.cluster.P, svc.cluster.Tcap, svc.widths) == (4062, 8, 8192, (256, 4096, 8192))
    assert np.bincount(svc.machine_slots).nonzero()[0].tolist() == [6, 12, 24]
    assert np.bincount(svc.machine_platform, minlength=3).tolist() == [1, 284, 27]
    rec = Record(api)
    assert len(rec.round(svc, rec.pods(rng, 3600, "r"))) == 3600
    mirror_is_the_table(svc)
    for r in range(6):
        for pod in list(rec.posted)[: int(rng.integers(0, 6))]:
            rec.complete(svc, pod)
        batch = rec.pods(rng, int(rng.integers(1, 9)), f"p{r}")
        assert len(rec.round(svc, batch)) == len(batch)
        mirror_is_the_table(svc)
    idle = slots - len(rec.posted)
    assert len(rec.round(svc, rec.pods(rng, idle + 5, "b"))) == idle  # the cluster is full
    assert len(svc._waiting_rows) == 5 and tracer.records[-1].array_machines_open > 0
    mirror_is_the_table(svc)
    for pod in list(rec.posted)[:3]:
        rec.complete(svc, pod)
    assert svc.backlog_dirty and len(rec.round(svc, [], solve=True)) == 3  # the quiet-channel round
    assert 1 <= tracer.records[-1].array_machines_open <= 3  # the machines the three left
    mirror_is_the_table(svc)
    faults, facts = rec.replay(args)
    assert faults == [], faults
    assert facts["rounds"] == 9 and facts["rounds_costing_zero"] == 0
    assert facts["served_cost"] == facts["optimum_cost"] > 2500 * 7
    assert facts["rounds_that_left_pods_waiting"] == 2 and facts["pods_left_waiting_at_most"] == 5
    assert facts["nodes_by_platform"] == [1, 284, 27] and facts["slots"] == slots
    # the incremental replay is the plain one: every machine priced every round, reference_round
    plain_faults, plain = rec.replay(args, plain=True)
    assert plain_faults == [] and plain["machines_priced"] == 9 * WHARE_MACHINES > facts["machines_priced"]
    for key in ("served_cost", "optimum_cost", "rounds", "pods_bound", "bound_by_class_and_platform"):
        assert plain[key] == facts[key], key
    # the graph path's rule (a completed pod leaves after the round) does not describe this service
    assert any("the optimum of the round is" in f for f in rec.replay(args, completions_leave_after_the_round=True)[0])
    assert (svc.unconverged_rounds, svc.admissions_short, svc.cost_overflows) == (0, 0, 0)
    assert [r.array_decode_width for r in tracer.records] == [4096] + [256] * 6 + [4096, 256]


def test_whare_on_machines_alike_is_served_too_and_main_serves_three_types_one_shot(capsys):
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {MACHINES} --pus-per-core 4 --max-tasks-per-pu 16 "
        "--cost-model whare --array-round".split()
    )
    api = SyntheticClusterAPI(pod_chan_size=2000)
    svc = cli.build_service(args, api)
    svc.init_topology(fake_machines=MACHINES, cores_per_machine=1, pus_per_core=4)
    assert svc.cluster.unsched_cost == 2500 and (svc.machine_platform == 1).all()  # no label: B
    assert svc.run_round([PodEvent(f"r{i}", task_class=i % 4) for i in range(200)]) == 200
    load = np.bincount([int(n.rsplit("_", 1)[1]) for n in api.bindings().values()], minlength=MACHINES)
    assert load.sum() == 200 and load.max() <= SLOTS
    assert cli.main(WHARE_ARGV + ["--podgen", "60", "--one-shot"]) == 0
    assert "scheduled 60/60 pods" in capsys.readouterr().err


@pytest.mark.parametrize("seed", range(4))
def test_the_device_cost_function_is_the_hosts_and_the_references_on_three_types(seed):
    import jax.numpy as jnp

    from benchmarks import reference_wharemap as ref_w
    from ksched_tpu.costmodels.device_costs import whare_device_cost_fn
    from ksched_tpu.costmodels.whare import PLATFORM_PRIOR, PSI_PRIOR, whare_cost_matrix

    rng = np.random.default_rng(seed)
    M = 400
    kind = rng.choice(3, M, p=[0.05, 0.8, 0.15])
    slots, platform = np.array([6, 12, 24])[kind], kind.astype(np.int64)
    running = np.where(rng.random(M) < 0.15, 0, rng.integers(0, slots + 1))  # some empty, some full
    census = np.stack([rng.multinomial(n, [0.25] * 4) for n in running]).astype(np.int64)
    got = np.asarray(whare_device_cost_fn(slots, platform)(jnp.asarray(census, jnp.int32)))
    idle = slots - running
    assert (idle == 0).any() and (running == 0).any()
    np.testing.assert_array_equal(got, whare_cost_matrix(census, idle, slots, platform=platform))
    np.testing.assert_array_equal(got, ref_w.cost_matrix(census, idle, slots, platform))
    assert got.dtype == np.int32 and got.min() >= 0 and got.max() <= 2000
    # the constants the reference states are the model's
    assert np.array_equal(np.asarray(ref_w.PSI_PRIOR), PSI_PRIOR)
    assert np.array_equal(np.asarray(ref_w.PLATFORM_PRIOR), PLATFORM_PRIOR)
    # an empty machine: ALONE on its platform, less the whole bonus
    empty = np.zeros((3, 4), np.int32)
    alone = np.asarray(whare_device_cost_fn(np.array([6, 12, 24]), np.arange(3))(jnp.asarray(empty)))
    assert alone.tolist() == [[90, 80, 75], [110, 80, 65], [95, 80, 70], [82, 80, 79]]


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


# -- the decode without a [W, M] array, machines that differ ------------------------------------


def _decode_by_comparison(g, rank, grants, pu_free, P):
    """The decode as it stood before PR 55, in numpy: a row's machine by comparing its rank with
    its group's cumulative grants over every machine ([W, M]), its PU by comparing its slot with
    its machine's cumulative room."""
    G, M = grants.shape
    cum = np.cumsum(grants, axis=1)[g]  # [W, M]
    cmp = cum <= rank[:, None]
    machine = cmp.sum(axis=1)
    excl_at = np.where(cmp, cum, 0).max(axis=1)
    offs = (np.cumsum(grants, axis=0) - grants)[g]  # [W, M]
    oh = machine[:, None] == np.arange(M)[None, :]
    slot = np.where(oh, offs, 0).sum(axis=1) + rank - excl_at
    pf2 = pu_free.reshape(M, P)
    grants_pu = np.clip(grants.sum(axis=0)[:, None] - (np.cumsum(pf2, axis=1) - pf2), 0, pf2)
    cg_at = oh.astype(np.int64) @ np.cumsum(grants_pu, axis=1)  # [W, P]
    return machine * P + (cg_at <= slot[:, None]).sum(axis=1)


@pytest.mark.parametrize("width", [7, 256, 4096], ids=["7", "256", "the-table"])
@pytest.mark.parametrize("shape", [(4, 25, 4, 16), (4, 312, 8, 3), (37, 60, 2, 5)],
                         ids=["coco-25x4x16", "three-types-312x8x3", "37-groups"])
def test_the_search_places_a_row_where_the_comparison_over_every_machine_placed_it(shape, width):
    import jax.numpy as jnp

    from ksched_tpu.scheduler.device_bulk import place_by_search

    G, M, P, S = shape
    rng = np.random.default_rng(G * M + width)
    pus = rng.integers(1, P + 1, M) if P == 8 else np.full(M, P)
    pu_free = np.where(np.arange(P)[None, :] < pus[:, None], rng.integers(0, S + 1, (M, P)), 0)
    room = pu_free.sum(axis=1)
    # grants that fit each machine's room, split over the groups; many machines get none
    take = np.minimum(room, rng.integers(0, 2 * S, M) * (rng.random(M) < 0.6))
    grants = np.stack([rng.multinomial(n, np.ones(G) / G) for n in take], axis=1).astype(np.int32)
    quota = grants.sum(axis=1)
    g = rng.integers(0, G, width)
    rank = np.array([rng.integers(0, max(1, quota[k])) for k in g])
    granted = rank < quota[g]
    want = _decode_by_comparison(g, rank, grants.astype(np.int64), pu_free.astype(np.int64), P)
    got = np.asarray(place_by_search(
        jnp.asarray(g, jnp.int32), jnp.asarray(rank, jnp.int32), jnp.asarray(grants),
        jnp.asarray(pu_free.reshape(-1), jnp.int32), P,
    ))
    assert granted.sum() > width // 3
    assert np.array_equal(got[granted], want[granted])
    # a granted row lands on a PU that exists and had room
    assert (pu_free.reshape(-1)[got[granted]] > 0).all()


def _avals(jaxpr):
    """Every array a program's equations produce, sub-programs included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in eqn.params.values():
            for inner in (sub if isinstance(sub, (list, tuple)) else [sub]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


def test_no_program_of_the_full_size_table_holds_an_array_of_rows_by_machines():
    """262,144 rows x 12,500 machines of three types, as `gtrace-12500-wharemap-array` builds
    them: traced, nothing run. One `[Tcap, M]` int32 would be 13.1 GB of a 16 GB chip."""
    import jax

    from ksched_tpu.costmodels import whare
    from ksched_tpu.costmodels.device_costs import whare_device_cost_fn

    M = 12500
    events = cli.fake_node_events(M, 1, 2, types=cli.parse_machine_types(TYPES))
    pus = np.array([n.num_cores * n.pus_per_core for n in events])
    pu_slots = np.where(np.arange(8)[None, :] < pus[:, None], 3, 0).astype(np.int32)
    slots = pu_slots.sum(axis=1)
    assert (np.bincount(pus)[[2, 4, 8]].tolist(), int(slots.sum())) == ([121, 11623, 756], 158346)
    platform = np.array([whare.platform_index(dict(n.labels)) for n in events])
    dev = DeviceBulkCluster(
        num_machines=M, pus_per_machine=8, slots_per_pu=3, pu_slots=pu_slots.reshape(-1), num_jobs=1,
        num_task_classes=4, task_capacity=262144, class_cost_fn=whare_device_cost_fn(slots, platform),
        unsched_cost=whare.UNSCHEDULED_COST, ec_cost=0, supersteps=1 << 17,
    )
    shapes = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), dev.state)
    for width in (256, 4096, None):
        jaxpr = jax.make_jaxpr(
            lambda st: dev._served_round_jit.__wrapped__(st, None, width))(shapes).jaxpr
        sizes = [int(np.prod(a.shape)) for a in _avals(jaxpr) if hasattr(a, "shape")]
        assert len(sizes) > 200
        # the largest array is a few columns of the table, or [rows, classes]; never rows x machines
        assert max(sizes) <= 8 * dev.Tcap, (width, max(sizes))
    # the scaled costs fit: 2,500 x n_scale under the limit, so `cost_overflow` stays 0
    from ksched_tpu.solver.layered import COST_SCALE_LIMIT

    assert dev.n_scale == 16384 and whare.UNSCHEDULED_COST * dev.n_scale < COST_SCALE_LIMIT


@pytest.mark.parametrize("mode", ["pin-on-place", "preemption", "steady-scan"])
def test_every_round_flavour_reads_a_pus_room_from_the_slot_vector(mode):
    """Machines of 1, 2 and 4 PUs padded to 4: no mode of the class may fill a PU that is not there."""
    pu_slots = np.array([[2, 0, 0, 0], [2, 2, 0, 0], [2, 2, 2, 2]] * 4, np.int32)
    dev = DeviceBulkCluster(
        num_machines=12, pus_per_machine=4, slots_per_pu=2, pu_slots=pu_slots.reshape(-1), num_jobs=1,
        num_task_classes=2, task_capacity=256, preemption=mode == "preemption",
        continuation_discount=1, decode_width=64 if mode == "steady-scan" else None,
    )
    dev.add_tasks(80, classes=np.arange(80, dtype=np.int32) % 2)
    if mode == "steady-scan":
        dev.fetch_stats(dev.run_steady_rounds(3, 0.2, 10, seed=1))
    else:
        dev.fetch_stats(dev.round())
        dev.complete_tasks([0, 1, 2])
        dev.fetch_stats(dev.round())
    st = dev.fetch_state()
    running = np.asarray(st["pu_running"])
    assert (running <= pu_slots.reshape(-1)).all() and running.sum() == pu_slots.sum() == 56
    with pytest.raises(ValueError, match="pu_slots must hold 48 entries, each 2"):
        DeviceBulkCluster(num_machines=12, pus_per_machine=4, slots_per_pu=2, num_jobs=1,
                          pu_slots=np.full(48, 3, np.int32))


def test_a_checkpoint_carries_the_slot_vector(tmp_path):
    from ksched_tpu.runtime.checkpoint import load_device_checkpoint, save_device_checkpoint

    pu_slots = np.array([3, 0, 3, 3] * 5, np.int32)
    dev = DeviceBulkCluster(num_machines=10, pus_per_machine=2, slots_per_pu=3, pu_slots=pu_slots,
                            num_jobs=1, task_capacity=64)
    dev.add_tasks(40)
    dev.fetch_stats(dev.round())
    save_device_checkpoint(dev, str(tmp_path / "ck.npz"))
    back = load_device_checkpoint(str(tmp_path / "ck.npz"))
    assert np.array_equal(back.pu_slots, pu_slots)
    back.add_tasks(10)
    back.fetch_stats(back.round())
    assert (np.asarray(back.fetch_state()["pu_running"]) <= pu_slots).all()
    assert back.num_placed_tasks == 45 == pu_slots.sum()
