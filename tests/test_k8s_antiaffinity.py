"""`--cost-model k8s_antiaffinity` on the served path: required hostname
anti-affinity against the pod's own workload (costmodels/k8s_antiaffinity.py).

Seeded multi-round streams through `cli.build_service`: every round's
objective equals the plain reference's (benchmarks/reference_antiaffinity.py:
straight numpy from the equations, no graph manager, no cost model class),
the replay of the Binding log finds no node with two pods of a workload,
the surplus of an over-full workload stays unscheduled and binds when a
machine frees, and the EC -> machine arcs the event-fed refresh keeps are
the arcs a sweep of every (workload, machine) pair would set."""

import numpy as np
import pytest

from benchmarks.client import BenchClusterAPI
from benchmarks.reference_antiaffinity import (
    EC_COST,
    UNSCHEDULED_COST,
    check_anti_affinity,
    reference_round,
)
from ksched_tpu import cli
from ksched_tpu.cluster.api import PodEvent
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, K8sAntiAffinityCostModel
from ksched_tpu.graph.changes import ChangeType
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.graph_collapse import try_collapse
from ksched_tpu.utils import seed_rng
from test_k8s_priority import drain


def _service(machines, slots, backend="native", cost_model="k8s_antiaffinity", **kw):
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --max-tasks-per-pu {slots} "
        f"--cost-model {cost_model} --backend {backend}".split()
    )
    api = BenchClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(args, api, **kw)
    api.svc = svc
    svc.init_topology(fake_machines=machines)
    return svc, api


def _machine(node_id):
    return int(node_id.rsplit("_", 1)[1])


class Stream:
    """A seeded stream of arrivals and completions, with the test's own
    books of who holds which machine: what the reference is given."""

    def __init__(self, machines, slots, groups, seed, backend="native", **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, slots, backend, **kw)
        self.slots = [slots] * machines
        self.groups = groups
        self.rng = np.random.default_rng(seed)
        self.group_of = {}
        self.bound = {}  # pod -> machine index, pods alive and bound
        self.backlog = []  # runnable and unbound
        self.k = 0

    def round(self, arrivals, completions, group=None):
        """One served round: `arrivals` pods of random workloads (of
        `group`, if given; or one pod of each workload in a list) and
        `completions` of random bound pods (or the pods of a list);
        returns (objective, reference objective, pods placed,
        reference's pods placed)."""
        if isinstance(completions, int):
            completions = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        if isinstance(arrivals, int):
            arrivals = [
                int(self.rng.integers(0, self.groups)) if group is None else group
                for _ in range(arrivals)
            ]
        victims = completions
        # a completed pod holds its slot until this round's `deltas` phase
        lingering = [(self.group_of[p], self.bound.pop(p)) for p in victims]
        self.api.complete_later(victims)
        new = []
        for g in arrivals:
            pod = f"p{self.k}"
            self.k += 1
            self.group_of[pod] = g
            new.append(pod)
            self.api.submit_pod(PodEvent(pod_id=pod, task_class=self.group_of[pod]))
        runnable = [(p, self.group_of[p]) for p in self.backlog + new]
        held = [(self.group_of[p], m) for p, m in self.bound.items()] + lingering
        ref_objective, ref_placed = reference_round(runnable, self.slots, held)
        # in as many polls as the debounce takes (7 of 12 pods in one poll, under
        # load, in the driver's run of PR 37): its quiet timer runs on the machine's clock
        batch = drain(self.api, len(arrivals))
        assert len(batch) == len(arrivals)
        self.svc.run_round(batch)
        now = self.api.bindings()
        placed = [p for p, _g in runnable if p in now]
        for p in placed:
            self.bound[p] = _machine(now[p])
        self.backlog = [p for p, _g in runnable if p not in now]
        objective = int(self.svc.scheduler.last_timing.objective)
        return objective, ref_objective, len(placed), ref_placed

    def arcs_are_a_sweeps(self):
        """Every EC's arcs against the rule, pair by pair: but for the
        pairs events touched since its arcs were brought up to date
        (the model's record, which the next refresh works off), an arc
        is there exactly where the rule allows one."""
        sched = self.svc.scheduler
        model, gm = sched.cost_model, sched.gm
        for ec, node in gm.task_ec_to_node.items():
            group = model._ec_group[ec]
            pending = model._changed[group]
            have = {arc.dst_node.resource_id for arc in node.outgoing.values()} - pending
            want = {m for m in model._machines if model._eligible(group, m)} - pending
            assert have == want, f"workload {group}"
            assert all(a.cap_upper == 1 and a.cost == 0 for a in node.outgoing.values())


STREAMS = [
    # machines, slots, groups, seed, backend, rounds, arrivals, completions
    (8, 3, 3, 1, "native", 6, 20, 8),
    (8, 3, 3, 1, "jax", 6, 20, 8),
    (20, 2, 4, 2, "native", 6, 15, 10),
    (20, 2, 4, 2, "auto", 6, 15, 10),
    (16, 110, 16, 3, "native", 8, 40, 12),
    (125, 110, 16, 4, "native", 5, 120, 60),
    (125, 110, 16, 5, "jax", 3, 120, 60),
]


@pytest.mark.parametrize("machines,slots,groups,seed,backend,rounds,arrivals,completions", STREAMS)
def test_every_round_equals_the_reference_and_no_node_holds_two_of_a_workload(
    machines, slots, groups, seed, backend, rounds, arrivals, completions
):
    s = Stream(machines, slots, groups, seed, backend)
    for r in range(rounds):
        objective, ref_objective, placed, ref_placed = s.round(arrivals, completions if r else 0)
        assert (objective, placed) == (ref_objective, ref_placed), f"round {r}"
        s.arcs_are_a_sweeps()
    assert check_anti_affinity(s.api.log, s.group_of) is None
    assert s.svc.noop_rounds == 0 and s.svc.ladder.degradations_total == 0


def test_the_surplus_of_an_over_full_workload_waits_and_binds_when_a_machine_frees():
    s = Stream(8, 4, 2, 11)
    # 11 replicas of workload 0 on 8 machines: exactly 3 are surplus,
    # though 21 slots stay free
    objective, ref_objective, placed, _ = s.round(11, 0, group=0)
    assert placed == 8 and len(s.backlog) == 3
    assert objective == ref_objective == 8 * EC_COST + 3 * UNSCHEDULED_COST
    assert s.svc.scheduler.last_timing.unscheduled_by_rule == 3
    assert sorted(s.bound.values()) == list(range(8))
    # other workloads are not held up by it
    _, _, placed, _ = s.round(5, 0, group=1)
    assert placed == 5 and len(s.backlog) == 3
    # two replicas complete: their machines free one round later (the
    # completed pods leave n in that round's `deltas` phase)
    _, _, placed, _ = s.round(1, [p for p in s.bound if s.group_of[p] == 0][:2], group=1)
    assert placed == 1 and len(s.backlog) == 3
    objective, ref_objective, placed, _ = s.round(1, 0, group=1)
    assert placed == 3 and len(s.backlog) == 1
    assert objective == ref_objective
    assert check_anti_affinity(s.api.log, s.group_of) is None


def test_two_pods_of_a_workload_in_one_batch_never_share_a_machine():
    s = Stream(6, 10, 3, 12, backend="jax")
    for _ in range(3):
        s.round(6, 0)
    by_group = {}
    for pod, m in s.bound.items():
        by_group.setdefault(s.group_of[pod], []).append(m)
    assert sum(len(v) for v in by_group.values()) == len(s.bound)
    for machines in by_group.values():
        assert len(machines) == len(set(machines))


def test_a_full_machine_takes_no_arc_and_gets_them_back_with_a_slot():
    s = Stream(3, 2, 4, 13)
    s.round(6, 0)  # 6 slots, 6 pods of up to 4 workloads: the cluster is full or nearly
    model = s.svc.scheduler.cost_model
    full = [m for m in model._machines if model._load[m] == model._slots[m]]
    assert full
    for group in range(4):
        assert not any(model._eligible(group, m) for m in full)
    s.round(2, 3)
    s.round(2, 0)
    s.arcs_are_a_sweeps()
    assert check_anti_affinity(s.api.log, s.group_of) is None


def test_the_refresh_follows_the_pairs_events_touched_not_a_sweep():
    s = Stream(40, 110, 4, 14)
    s.round(80, 0)
    gm = s.svc.scheduler.gm
    assert gm.ec_arcs_changed == 4 * 40  # the first listing
    s.round([0, 1, 2, 3] * 2, 0)
    # each workload lost the ~20 machines it now occupies; no other pair moved
    assert gm.ec_arcs_changed == 80
    s.round([0, 1, 2, 3] * 2, 4)
    t = s.svc.scheduler.last_timing
    assert t.ec_arcs_changed == 8 and t.ec_nodes == 4
    s.round([0, 1, 2, 3] * 2, 0)
    t = s.svc.scheduler.last_timing
    assert t.ec_arcs_changed == 8 + 4  # last round's binds, and its 4 drops
    assert t.ec_arcs == sum(len(n.outgoing) for n in gm.task_ec_to_node.values())
    s.arcs_are_a_sweeps()


def test_an_ec_goes_after_two_idle_rounds_in_a_row_and_is_listed_anew():
    s = Stream(6, 5, 2, 15)
    s.round(3, 0, group=0)
    gm = s.svc.scheduler.gm
    s.round(1, 0, group=1)  # EC(0) has no runnable task: marked
    assert len(gm.task_ec_to_node) == 2
    s.round(1, 0, group=0)  # in use again: the mark is gone
    s.round(1, 0, group=1)
    assert len(gm.task_ec_to_node) == 2
    s.round(1, 0, group=1)  # idle at two purges in a row: removed
    assert len(gm.task_ec_to_node) == 1
    objective, ref_objective, placed, ref_placed = s.round(1, 0, group=0)  # listed anew
    assert (objective, placed) == (ref_objective, ref_placed) and placed == 1
    s.arcs_are_a_sweeps()


@pytest.mark.parametrize("cost_model,ecs", [("k8s_antiaffinity", 2), ("trivial", 1)])
def test_an_ec_that_tasks_point_at_every_round_is_never_rebuilt(cost_model, ecs):
    """Every placed task is pinned and its EC arc gone by the time of
    the purge, so the EC is unconnected at every purge; the tasks'
    updates in between keep it (the fan-out is added once)."""
    svc, _api = _service(6, 20, cost_model=cost_model)
    gm = svc.scheduler.gm
    added = []
    for r in range(5):
        assert svc.run_round([PodEvent(pod_id=f"r{r}p{i}", task_class=i) for i in range(2)]) == 2
        assert len(gm.task_ec_to_node) == ecs
        added.append(gm.cm.stats.by_type[ChangeType.ADD_ARC_EQUIV_CLASS_TO_RES])
    assert added == [ecs * 6, 0, 0, 0, 0]
    gm.purge_unconnected_equiv_class_nodes()
    assert len(gm.task_ec_to_node) == ecs
    gm.purge_unconnected_equiv_class_nodes()
    assert not gm.task_ec_to_node  # idle at two purges in a row


def test_the_collapse_refuses_it_for_the_capacity_that_binds():
    s = Stream(8, 3, 3, 16)
    s.round(12, 0)
    collapse, reason = try_collapse(s.svc.scheduler.solver.state.problem())
    assert collapse is None
    assert reason.startswith("EC ") and reason.endswith("machine arc cap 1 can bind")


def test_under_auto_the_refused_collapse_goes_to_native_cpp_not_to_the_device():
    """`solver/select.py` gives `AutoSolver` the native C++ solver as its
    general rung on one chip: why the benchmark's configuration names
    `--backend jax` (ROADMAP R2, D5)."""
    from ksched_tpu.solver.native import NativeSolver

    s = Stream(8, 3, 3, 17, backend="auto")
    s.round(12, 0)
    rung = s.svc.ladder.primary
    assert rung.last_path == "csr" and "can bind" in rung.last_refusal
    assert isinstance(rung.csr, NativeSolver)


def test_the_model_is_registered_and_keeps_the_trivial_costs():
    assert MODEL_REGISTRY[CostModelType.K8S_ANTIAFFINITY] is K8sAntiAffinityCostModel
    assert "k8s_antiaffinity" in cli.build_arg_parser().format_help()
    assert (K8sAntiAffinityCostModel.CLUSTER_AGG_COST, K8sAntiAffinityCostModel.UNSCHEDULED_COST) == (
        EC_COST, UNSCHEDULED_COST,
    )
    assert K8sAntiAffinityCostModel.pinned_tasks_are_inert
    doc = K8sAntiAffinityCostModel.__module__
    import importlib

    text = importlib.import_module(doc).__doc__
    for phrase in ("n(g, m) = 0", "capacity 1, cost e", "capacity 1, cost u", "Departures"):
        assert phrase in text


@pytest.mark.parametrize("cost_model,admitted", [("k8s_antiaffinity", True), ("coco", False), ("trivial", False)])
def test_a_pod_of_class_15_is_a_workload_here_and_a_clear_error_elsewhere(cost_model, admitted):
    svc, api = _service(4, 20, cost_model=cost_model)
    pods = [PodEvent(pod_id=f"p{i}", task_class=15) for i in range(3)]
    if admitted:
        assert svc.run_round(pods) == 3
        td = svc.task_map.find(svc.pod_to_task["p0"])
        assert td.workload == 15 and int(td.task_type) == 0
        assert len(set(api.bindings().values())) == 3
        with pytest.raises(ValueError, match="index of a workload"):
            svc.run_round([PodEvent(pod_id="bad", task_class=-1)])
        return
    with pytest.raises(ValueError, match=r"pod p0: task_class 15 is not one of the 4 CoCo classes"):
        svc.run_round(pods)
    assert not svc.pod_to_task  # refused before a task was made
    assert svc.run_round([PodEvent(pod_id="ok", task_class=3)]) == 1


def test_a_redelivered_pod_of_another_workload_is_evicted_and_counted_anew():
    svc, api = _service(2, 5)
    svc.run_round([PodEvent(pod_id="a", task_class=0), PodEvent(pod_id="b", task_class=1)])
    model = svc.scheduler.cost_model
    assert sum(len(h) for h in model._held.values()) == 2
    svc.run_round([PodEvent(pod_id="a", task_class=1)])  # same name, workload 1 now
    assert model._held.get(0, {}) == {}
    nodes = api.bindings()
    assert nodes["a"] != nodes["b"]  # both of workload 1 now


def test_the_counters_ride_the_span_and_the_round_record():
    tracer = SpanTracer(capacity=1 << 14).install()
    try:
        s = Stream(10, 4, 2, 18, tracer=RoundTracer(), span_tracer=tracer)
        s.round(14, 0, group=0)
        events = tracer.events()
    finally:
        tracer.uninstall()
    rec = s.svc.tracer.records[-1]
    assert (rec.ec_nodes, rec.ec_arcs, rec.ec_arcs_changed, rec.unscheduled_by_rule) == (1, 10, 10, 4)
    update = [e for e in events if e["name"] == "graph_update"][-1]
    assert {k: update["args"][k] for k in ("ec_nodes", "ec_arcs", "ec_arcs_changed")} == {
        "ec_nodes": 1, "ec_arcs": 10, "ec_arcs_changed": 10,
    }
    refresh = [e for e in events if e["name"] == "ec_refresh"]
    assert len(refresh) == 1
    assert update["ts"] <= refresh[0]["ts"] and (
        refresh[0]["ts"] + refresh[0]["dur"] <= update["ts"] + update["dur"]
    )
