"""`--cost-model whare` on the served path, on machines of several types
(`--fake-machine-types`): the class x platform x co-runner map of
costmodels/whare.py against the plain reference.

Seeded multi-round streams through `cli.build_service` under `--backend
auto` on clusters that hold all three machine types: every round is
answered by the dense rung; its objective is the sum of cost(c, m) over
its Bindings on the test's own census, the optimum of the round's
transportation problem by the reference (benchmarks/reference_wharemap.py:
one equation and a textbook successive shortest path, nothing of
`ksched_tpu`), and native C++'s; the whole record passes
`check_interference_map`; a model with another PLATFORM_PRIOR, or one that
reads no platform label, fails it. The batch hook prices what the scalar
hook prices; the flag deals what the reference deals and is refused where
it cannot be served.
"""

import numpy as np
import pytest

from benchmarks import reference_wharemap as ref
from benchmarks.client import BenchClusterAPI
from ksched_tpu import cli
from ksched_tpu.cluster.api import NodeEvent, PodEvent
from ksched_tpu.costmodels import whare
from ksched_tpu.costmodels.census import CLASS_ECS
from ksched_tpu.data import PLATFORM_LABEL
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng
from test_k8s_priority import drain

TYPES = "A:1:10,B:2:930,C:4:60"
TABLE = (("A", 1, 10), ("B", 2, 930), ("C", 4, 60))
PUS, PODS_A_PU = 2, 3


def _service(machines, types=TYPES, backend="auto", **kw):
    flags = f" --fake-machine-types {types}" if types else ""
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --pus-per-core {PUS} "
        f"--max-tasks-per-pu {PODS_A_PU}{flags} --cost-model whare --backend {backend}".split()
    )
    api = BenchClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(args, api, **kw)
    api.svc = svc
    svc.init_topology(fake_machines=machines, pus_per_core=PUS)
    return svc, api


def _node(i):
    return f"fake_node_{i}"


class Stream:
    """A seeded stream of arrivals of the four classes and of completions,
    with the test's own books: each node's census, as the reference is
    given it."""

    def __init__(self, machines, seed, **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, **kw)
        self.nodes = [_node(i) for i in range(machines)]
        shapes = [ref.node_shape(n, TABLE, PUS, PODS_A_PU) for n in self.nodes]
        self.platform = np.array([p for p, _s in shapes])
        self.slots = np.array([s for _p, s in shapes])
        self.census = np.zeros((machines, 4), np.int64)
        self.rng = np.random.default_rng(seed)
        self.class_of = {}
        self.bound = {}  # pod -> node index, pods alive and bound
        self.k = 0

    def round(self, classes, completions=0):
        """One served round: a pod of each class of `classes`, after
        `completions` of random bound pods. Returns (the round's objective,
        the cost of its Bindings on the test's census, the reference's
        optimum, native C++'s objective on the round's problem)."""
        gone = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        self.api.complete_later(gone)
        new = []
        for c in classes:
            pod = f"p{self.k}"
            self.k += 1
            self.class_of[pod] = int(c)
            new.append(pod)
            self.api.submit_pod(PodEvent(pod_id=pod, task_class=int(c)))
        # a completed pod counts, and holds its slot, until this round's `deltas` phase
        idle = self.slots - self.census.sum(axis=1)
        cost = ref.cost_matrix(self.census, idle, self.slots, self.platform)
        want = ref.reference_round(
            self.census, idle, self.slots, self.platform, np.bincount(list(classes), minlength=4)
        )
        batch = drain(self.api, len(new))
        assert len(batch) == len(new)
        self.svc.run_round(batch)
        now = self.api.bindings()
        served = 0
        for pod in new:
            at = int(now[pod].rsplit("_", 1)[1])
            served += int(cost[self.class_of[pod], at])
            self.bound[pod] = at
            self.census[at, self.class_of[pod]] += 1
        for pod in gone:
            self.census[self.bound.pop(pod), self.class_of[pod]] -= 1
        solver, timing = self.svc.scheduler.solver, self.svc.scheduler.last_timing
        native = make_backend("native", warm_start=False, fallback=False)
        theirs = int(native.solve(solver.state.problem()).objective)
        return int(timing.objective), served, want, theirs

    def the_record_holds(self):
        return ref.check_interference_map(
            self.api.log, self.class_of, self.nodes, TABLE, PUS, PODS_A_PU,
            admitted=[(t1, n) for _t0, t1, n in self.api.polls if n],
        )


# -- every round is the reference's optimum, on the dense rung -----------------------------


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_every_rounds_bindings_cost_what_the_reference_says(seed):
    s = Stream(24, seed)
    assert sorted(set(s.platform.tolist())) == [0, 1, 2]  # node 0 is an A, 8 and 21 are C
    rung = s.svc.ladder.primary
    out = [s.round(s.rng.integers(0, 4, 240))]  # a fill: 240 of 306 slots, few machines left empty
    for _ in range(12):
        out.append(s.round(s.rng.integers(0, 4, int(s.rng.integers(1, 11))), int(s.rng.integers(0, 11))))
        assert rung.last_path == "dense" and rung.last_refusal == ""
    for objective, served, want, native in out:
        assert objective == served == want == native
    assert all(o[0] > 0 for o in out)  # no round costs nothing: an empty machine costs its platform
    assert s.svc.ladder.degradations_total == 0 and s.svc.noop_rounds == 0
    faults, facts = s.the_record_holds()
    assert faults == []
    assert facts["rounds_compared"] == facts["rounds"] == 13 and facts["rounds_short_of_room"] == 0
    assert facts["served_cost"] == facts["optimum_cost"] == sum(o[0] for o in out)
    assert facts["nodes_by_platform"] == [1, 21, 2] and facts["slots"] == 6 + 21 * 12 + 2 * 24


def test_two_classes_contend_for_the_newest_platform_and_the_more_sensitive_one_gets_it():
    # nine nodes: 0 an A (6 slots), 1-7 Bs (12), 8 a C (24). Turtles take every slot
    s = Stream(9, 11)
    assert s.slots.tolist() == [6] + [12] * 7 + [24]
    objective, served, want, native = s.round([3] * int(s.slots.sum()))
    # every slot is taken, at what its empty machine costs a turtle: 82 / 80 / 79
    assert objective == served == want == native == 6 * 82 + 84 * 80 + 24 * 79 and len(s.bound) == 114
    # one turtle leaves the C and two leave a B. A completed pod holds its slot through the
    # next round's solve, so a turtle that arrives now finds the cluster full, and waits
    gone = [[p for p, at in sorted(s.bound.items()) if at == node][:n] for node, n in ((8, 1), (7, 2))]
    s.api.complete_later(gone[0] + gone[1])
    s.api.submit_pod(PodEvent(pod_id="t", task_class=3))
    s.svc.run_round(drain(s.api, 1))
    assert "t" not in s.api.bindings()
    assert int(s.svc.scheduler.last_timing.objective) == ref.UNSCHEDULED_COST
    for pod in gone[0] + gone[1]:
        s.census[s.bound.pop(pod), 3] -= 1
    idle = s.slots - s.census.sum(axis=1)
    assert idle.tolist() == [0] * 7 + [2, 1]
    # beside turtles a sheep costs 100 on B and 95 on C, a rabbit 101 and 85, a turtle 100
    # and 99; the B's two idle slots of twelve take 3 off, the C's one of 24 nothing
    cost = ref.cost_matrix(s.census, idle, s.slots, s.platform)
    assert cost[:, 7].tolist() == [97, 98, 102, 97] and cost[:, 8].tolist() == [95, 85, 94, 99]
    # a sheep and a rabbit arrive: both are cheaper on the C, which has one slot
    want = ref.reference_round(s.census, idle, s.slots, s.platform, [1, 1, 0, 1])
    s.api.submit_pod(PodEvent(pod_id="sheep", task_class=0))
    s.api.submit_pod(PodEvent(pod_id="rabbit", task_class=1))
    s.svc.run_round(drain(s.api, 2))
    now = s.api.bindings()
    assert (now["rabbit"], now["sheep"], now["t"]) == (_node(8), _node(7), _node(7))
    assert int(s.svc.scheduler.last_timing.objective) == want == 85 + 97 + 97
    assert s.svc.ladder.primary.last_path == "dense" and s.svc.ladder.degradations_total == 0


def test_a_round_with_more_pods_than_slots_leaves_the_dearest_waiting():
    s = Stream(9, 5)
    slots = int(s.slots.sum())  # 6 + 7 x 12 + 24
    classes = s.rng.integers(0, 4, slots - 4)
    objective, served, want, native = s.round(classes)
    assert objective == served == want == native > 65 * (slots - 4)  # an empty cluster costs its platforms
    # 9 pods for 4 slots: the reference prices the 5 that wait at UNSCHEDULED_COST each
    idle = s.slots - s.census.sum(axis=1)
    want = ref.reference_round(s.census, idle, s.slots, s.platform, [3, 2, 2, 2])
    for c in (0, 0, 0, 1, 1, 2, 2, 3, 3):
        s.api.submit_pod(PodEvent(pod_id=f"q{s.k}", task_class=c))
        s.k += 1
    s.svc.run_round(drain(s.api, 9))
    timing = s.svc.scheduler.last_timing
    assert int(timing.objective) == want and want > 5 * ref.UNSCHEDULED_COST
    assert len(s.api.bindings()) == slots and timing.unscheduled_by_rule == 0


def test_a_poll_of_completions_alone_starts_no_round_and_the_pods_count_in_the_next():
    # the reference's rule for a round without Bindings. Between two priced rounds the loop
    # takes four completions in a poll that hands over no pod. No pod waits, so the scheduler
    # finds nothing runnable and returns before its `deltas` phase: the model is not told.
    # On a cluster with every slot taken that shows: two pods that arrive next find no room
    s = Stream(9, 5)
    slots = int(s.slots.sum())
    s.round(s.rng.integers(0, 4, slots))
    s.api.complete_later([str(p) for p in s.rng.permutation(sorted(s.bound))[:4]])
    assert s.api.poll_pod_batch(0.01) == [] and [e[0] for e in s.api.log[-4:]] == ["done"] * 4
    assert s.svc.backlog_dirty
    s.svc.run_round([], solve=s.svc.backlog_dirty)  # what `run` does with a quiet poll
    for i in range(2):
        s.api.submit_pod(PodEvent(pod_id=f"late{i}", task_class=i))
    s.svc.run_round(drain(s.api, 2))
    assert int(s.svc.scheduler.last_timing.objective) == 2 * ref.UNSCHEDULED_COST
    assert len(s.api.bindings()) == slots  # the four held their slots through this solve
    # and the reference follows it: on a cluster with room, the four still count in the books
    # the next batch is priced on (Stream's, and the replay's), and leave after that round
    s = Stream(24, 9)
    s.round(s.rng.integers(0, 4, 280))
    gone = [str(p) for p in s.rng.permutation(sorted(s.bound))[:4]]
    s.api.complete_later(gone)
    assert s.api.poll_pod_batch(0.01) == []
    s.svc.run_round([], solve=s.svc.backlog_dirty)
    assert len(s.api.log) == 284  # no Binding
    held = [s.bound.pop(pod) for pod in gone]  # not to be drawn again as completions
    out = [s.round(s.rng.integers(0, 4, 8))]  # priced with the four on the books
    for pod, at in zip(gone, held):
        s.census[at, s.class_of[pod]] -= 1  # now they have left
    out.append(s.round(s.rng.integers(0, 4, 8), 3))
    for objective, served, want, native in out:
        assert objective == served == want == native > 0
    faults, facts = s.the_record_holds()
    assert faults == [] and facts["rounds_compared"] == facts["rounds"] == 3


# -- a model that is not the reference's fails the check -----------------------------------


def _stream_with(monkeypatch, **patch):
    for name, value in patch.items():
        monkeypatch.setattr(whare, name, value)
    s = Stream(24, 3)
    s.round(s.rng.integers(0, 4, 240))
    for _ in range(12):
        s.round(s.rng.integers(0, 4, int(s.rng.integers(1, 11))), int(s.rng.integers(0, 11)))
    return s.the_record_holds()


def test_a_model_with_a_wrong_platform_prior_fails_the_check(monkeypatch):
    # the newest platform slower and the oldest faster: the columns of the prior swapped
    faults, facts = _stream_with(monkeypatch, PLATFORM_PRIOR=whare.PLATFORM_PRIOR[:, ::-1].copy())
    assert len(faults) == 1 and "by the interference map, the optimum of the round is" in faults[0]
    assert facts["served_cost"] > facts["optimum_cost"]


def test_a_model_that_reads_no_platform_fails_the_check(monkeypatch):
    faults, facts = _stream_with(monkeypatch, platform_index=lambda labels: whare.DEFAULT_PLATFORM)
    assert len(faults) == 1 and "by the interference map, the optimum of the round is" in faults[0]
    assert facts["served_cost"] > facts["optimum_cost"]


# -- the model's own equation ----------------------------------------------------------------


def test_the_map_and_the_matrix_are_the_references():
    assert whare.PLATFORMS == ref.PLATFORMS and whare.DEFAULT_PLATFORM == ref.NEUTRAL
    np.testing.assert_array_equal(whare.psi_prior(), ref.psi())
    np.testing.assert_array_equal(whare.PSI_PRIOR, ref.PSI_PRIOR)
    np.testing.assert_array_equal(whare.PLATFORM_PRIOR, ref.PLATFORM_PRIOR)
    assert (whare.IDLE_BONUS, whare.MAX_COST, whare.UNSCHEDULED_COST) == (
        ref.IDLE_BONUS, ref.MAX_COST, ref.UNSCHEDULED_COST)
    # the neutral platform is the prior the model had before it knew platforms
    np.testing.assert_array_equal(whare.psi_prior()[:, whare.DEFAULT_PLATFORM, :], whare.PSI_PRIOR)
    rng = np.random.default_rng(0)
    slots = rng.choice([6, 12, 24], 200)
    census = rng.integers(0, 3, (200, 4))
    census[census.sum(axis=1) > slots] = 0
    idle = slots - census.sum(axis=1)
    platform = rng.integers(0, 3, 200)
    np.testing.assert_array_equal(
        whare.whare_cost_matrix(census, idle, slots, platform=platform),
        ref.cost_matrix(census, idle, slots, platform),
    )
    # an empty machine costs what its platform does to a lone task, less the whole bonus
    np.testing.assert_array_equal(
        whare.whare_cost_matrix(
            np.zeros((3, 4), np.int64), np.array([6, 12, 24]), np.array([6, 12, 24]), platform=np.arange(3)),
        whare.PLATFORM_PRIOR - whare.IDLE_BONUS,
    )
    assert whare.psi_prior().shape == (4, 3, 5) and (whare.PSI_PRIOR[:, whare.ALONE] == 100).all()


def test_the_batch_hook_prices_what_the_scalar_hook_prices():
    s = Stream(24, 7)
    s.round(s.rng.integers(0, 4, 120))
    for _ in range(4):
        s.round(s.rng.integers(0, 4, 9), 7)
    sched = s.svc.scheduler
    sched.gm.compute_topology_statistics(sched.gm.sink_node)  # as a round's `stats` would
    model = sched.cost_model
    rids = list(model.census.machines)
    assert [whare.platform_index(model.census.machines[r].resource_desc.labels) for r in rids] == (
        s.platform.tolist())
    for ec in CLASS_ECS:
        costs, caps = model.ec_to_resource_batch(ec, rids)
        assert list(zip(costs, caps)) == [model.equiv_class_to_resource_node(ec, r) for r in rids]
        assert all(type(v) is int for v in costs + caps)
    # any other list of the keeper's machines is priced by row, and the span says how many
    some = [rids[i] for i in (17, 2, 2, 9, 23)]
    tracer = SpanTracer().install()
    try:
        costs, caps = model.ec_to_resource_batch(CLASS_ECS[1], rids[::-1])
        few = model.ec_to_resource_batch(CLASS_ECS[1], some)
        model.ec_to_resource_batch(12345, some)  # no class's EC: no arc, and no span
    finally:
        tracer.uninstall()
    assert list(zip(costs, caps)) == [model.equiv_class_to_resource_node(CLASS_ECS[1], r) for r in rids[::-1]]
    assert list(zip(*few)) == [model.equiv_class_to_resource_node(CLASS_ECS[1], r) for r in some]
    priced = [e["args"] for e in tracer.events() if e["name"] == "platform_costs"]
    assert [a["machines"] for a in priced] == [24, 5] and not any("scalar" in a for a in priced)
    # a runtime recorded for (rabbit, C, devil) moves that cell's cost and no other platform's
    before = [model.equiv_class_to_resource_node(CLASS_ECS[1], r)[0] for r in rids]
    for _ in range(20):
        model.record_runtime(1, 2, 2, 900.0)
    after = [model.equiv_class_to_resource_node(CLASS_ECS[1], r)[0] for r in rids]
    moved = [i for i, (a, b) in enumerate(zip(before, after)) if a != b]
    assert moved and all(s.platform[i] == 2 and s.census[i, 2] > 0 for i in moved)
    assert model.ec_to_resource_batch(CLASS_ECS[1], rids)[0] == after


def test_a_machine_without_the_label_or_with_an_unknown_value_is_platform_b():
    assert whare.platform_index({}) == whare.platform_index({PLATFORM_LABEL: "Z"}) == 1
    assert [whare.platform_index({PLATFORM_LABEL: p}) for p in "ABC"] == [0, 1, 2]
    # a service without the flag: no label, every machine neutral, the costs of before
    svc, api = _service(6, types="")
    assert all(PLATFORM_LABEL not in svc.resource_map.find(m).descriptor.labels
               for m in svc.node_to_machine.values())


# -- the counters and the span ------------------------------------------------------------------


def test_the_round_stamps_the_counters_and_opens_the_span():
    seed_rng(2)
    tracer = SpanTracer().install()
    try:
        svc, api = _service(24, tracer=RoundTracer())
        rng = np.random.default_rng(2)
        classes = [rng.integers(0, 4, n).tolist() for n in (150, 8, 5, 6)]
        for k, batch in enumerate(classes):
            for i, c in enumerate(batch):
                api.submit_pod(PodEvent(pod_id=f"r{k}_{i}", task_class=c))
            svc.run_round(drain(api, len(batch)))
    finally:
        tracer.uninstall()
    fill, second, third, fourth = [r for r in svc.tracer.records if r.solver_rung >= 0]
    # the fill: every machine empty, and its platform costs each class its own: four rows
    assert (fill.audit_tasks_grouped, fill.collapse_rows, fill.collapse_cols) == (150, 4, 128)
    # no class EC has listed: each sweeps every machine
    assert fill.census_machines_dirty == 24 and fill.ec_arcs_repriced == 4 * 24
    assert (second.audit_tasks_grouped, second.collapse_cols) == (8, 128) and 1 <= second.collapse_rows <= 4
    # the second round gathers again the machines the fill bound pods on: all of them, which
    # is a sweep's worth, so its ECs sweep, and so does, at its next turn, one that sat that
    # round out; otherwise a class EC the batch reaches prices the machines gathered again
    # since its last turn, and no other
    turned = [set(batch) for batch in classes]
    assert second.census_machines_dirty == 24 and second.ec_arcs_repriced == 24 * len(turned[1])
    assert 0 < third.census_machines_dirty <= 8 and 0 < fourth.census_machines_dirty <= 5
    late3, late4 = turned[2] - turned[1], turned[3] - turned[2] - turned[1]
    assert third.ec_arcs_repriced == 24 * len(late3) + third.census_machines_dirty * len(turned[2] - late3)
    # an EC that sat the third round out owes its machines too
    patched4 = len(turned[3] - late4)
    assert fourth.census_machines_dirty * patched4 <= fourth.ec_arcs_repriced - 24 * len(late4) <= (
        (third.census_machines_dirty + fourth.census_machines_dirty) * patched4)
    for r in (second, third, fourth):
        assert r.ec_arcs_changed <= r.ec_arcs_repriced
    by_sid = {e["args"]["sid"]: e for e in tracer.events() if "sid" in e["args"]}
    spans = [e for e in by_sid.values() if e["name"] == "platform_costs"]
    # one span a class EC that priced a machine, inside that EC's refresh, with the number priced
    assert sum(e["args"]["machines"] for e in spans) == sum(
        r.ec_arcs_repriced for r in (fill, second, third, fourth))
    swept = 4 + len(turned[1]) + len(late3) + len(late4)
    assert {e["args"]["machines"] for e in spans} > {24} and len(spans) > swept
    for e in spans:
        parent = by_sid[e["args"]["parent_sid"]]
        assert parent["name"] == "ec_refresh"
        assert parent["args"]["swept"] == int(e["args"]["machines"] == 24)
        assert parent["ts"] <= e["ts"] and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]
    # every class EC's turn says whether it swept; one that had nothing to price opens no span
    turns = [e["args"]["swept"] for e in by_sid.values() if e["name"] == "ec_refresh"]
    assert turns.count(1) == swept and turns.count(0) >= len(spans) - swept


# -- the flag -------------------------------------------------------------------------------------


def test_the_flag_deals_what_the_reference_deals():
    types = cli.parse_machine_types(TYPES)
    assert types == TABLE
    for i in range(12500):
        assert cli.machine_type_of(i, types) == ref.machine_type(i, TABLE)
    assert cli.fake_cores(12500, 1, types) == 121 * 1 + 11623 * 2 + 756 * 4 == 26391
    assert cli.fake_cores(12500, 3, ()) == 37500
    svc, api = _service(24)
    for i in range(24):
        status = svc.resource_map.find(svc.node_to_machine[_node(i)])
        name, cores, _share = ref.machine_type(i, TABLE)
        assert status.descriptor.labels == {PLATFORM_LABEL: name}
        assert [len(core.children) for core in status.topology_node.children] == [PUS] * cores


@pytest.mark.parametrize("text", ["A:1", "A:1:1000,A:2:0", "A:0:1000", "A:1:500,B:2:400", "A:x:1000", ""])
def test_a_type_table_that_does_not_hold_together_is_refused_by_the_parser(text, capsys):
    with pytest.raises(SystemExit):
        cli.build_arg_parser().parse_args(["--fake-machine-types", text])
    assert "--fake-machine-types" in capsys.readouterr().err


def test_the_flag_is_refused_with_another_cores_per_machine_and_is_read_where_costs_must_fit():
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines 24 --cores-per-machine 2 --fake-machine-types {TYPES}".split()
    )
    with pytest.raises(ValueError, match="--cores-per-machine 2"):
        cli.build_service(args, BenchClusterAPI(pod_chan_size=10))
    # refuse_costs_that_cannot_fit counts the PUs of the type table: 12,500 machines of
    # 4-core types do not fit quincy's largest cost under --backend jax, of 1-core types they do
    fits = f"--fake-machines --num-machines 12500 --max-tasks-per-pu 4 --cost-model quincy --backend jax"
    cli.refuse_costs_that_cannot_fit(cli.build_arg_parser().parse_args(
        (fits + " --fake-machine-types S:1:1000").split()))
    with pytest.raises(ValueError, match="states a largest cost of 1023"):
        cli.refuse_costs_that_cannot_fit(cli.build_arg_parser().parse_args(
            (fits + " --fake-machine-types L:4:1000").split()))


def test_a_node_of_the_cluster_api_brings_its_platform_label():
    svc, api = _service(2, types="")
    svc.add_node(NodeEvent(node_id="n", num_cores=4, pus_per_core=2, labels=((PLATFORM_LABEL, "C"),)))
    model = svc.scheduler.cost_model
    rid = svc.node_to_machine["n"]
    assert whare.platform_index(svc.resource_map.find(rid).descriptor.labels) == 2
    assert whare.platform_index(model.census.machines[rid].resource_desc.labels) == 2
