"""`--cost-model k8s_zonespread` on the served path: a hard zone
topology-spread constraint against the pod's own workload
(costmodels/k8s_zonespread.py).

Seeded multi-round streams through `cli.build_service`: every round's
objective and per-zone placements equal the plain reference's
(benchmarks/reference_zonespread.py: straight numpy from the equations,
no graph manager, no cost model class), the replay of the Binding log
holds every round to the guarantee, the water level and its remainder
come out as worked by hand, the fallback takes over where a zone is short
of room, the EC -> EC arcs are added, re-capacitated and removed, the zone
ECs survive the purge while a workload lives, and node labels reach the
model from `NodeEvent`, from the HTTP watch and from `--fake-zones`."""

import re
import time

import numpy as np
import pytest

from benchmarks.client import BenchClusterAPI
from benchmarks.reference_zonespread import (
    EC_COST,
    UNSCHEDULED_COST,
    allotment,
    check_topology_spread,
    reference_round,
)
from ksched_tpu import cli
from ksched_tpu.cluster import FakeAPIServer
from ksched_tpu.cluster.api import NodeEvent, PodEvent
from ksched_tpu.cluster.http_api import HTTPClusterAPI
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, K8sZoneSpreadCostModel
from ksched_tpu.costmodels.k8s_zonespread import water_level, workload_ec, zone_ec
from ksched_tpu.data import ZONE_LABEL, TaskState
from ksched_tpu.graph.changes import ChangeType
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.graph_collapse import try_collapse
from ksched_tpu.utils import seed_rng
from test_k8s_priority import drain

MAX_SKEW = K8sZoneSpreadCostModel.MAX_SKEW


def _service(machines, slots, zones, backend="native", nodes=None, **kw):
    """`machines` fake nodes dealt over `zones` zones, or the NodeEvents
    of `nodes` as a cluster API would hand them over."""
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --max-tasks-per-pu {slots} "
        f"--fake-zones {zones} --cost-model k8s_zonespread --backend {backend}".split()
    )
    api = BenchClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(args, api, **kw)
    api.svc = svc
    if nodes is None:
        svc.init_topology(fake_machines=machines)
    else:
        for node in nodes:
            svc.add_node(node)
    return svc, api


def _chain_arcs(gm):
    """EC -> EC arcs in the graph."""
    return sum(
        arc.dst_node.equiv_class is not None
        for node in gm.task_ec_to_node.values() for arc in node.outgoing.values()
    )


def _zone_node(i, zone, pus=1):
    return NodeEvent(node_id=f"node_{i}", pus_per_core=pus, labels=((ZONE_LABEL, zone),))


class Stream:
    """A seeded stream of arrivals and completions, with the test's own
    books of who holds which machine: what the reference is given."""

    def __init__(self, machines, slots, zones, groups, seed, backend="native", nodes=None, **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, slots, zones, backend, nodes=nodes, **kw)
        if nodes is None:
            self.slots = [slots] * machines
            self.zone = [i % zones for i in range(machines)]
        else:
            names = sorted({dict(n.labels)[ZONE_LABEL] for n in nodes})
            self.slots = [slots * n.num_cores * n.pus_per_core for n in nodes]
            self.zone = [names.index(dict(n.labels)[ZONE_LABEL]) for n in nodes]
        self.machine_of = {
            node: i for i, node in enumerate(self.svc.node_to_machine)
        }
        self.zone_of = {node: self.zone[i] for node, i in self.machine_of.items()}
        self.groups = groups
        self.rng = np.random.default_rng(seed)
        self.group_of = {}
        self.bound = {}  # pod -> machine index, pods alive and bound
        self.backlog = []  # runnable and unbound
        self.k = 0

    def pods_in(self, group, zone):
        return [p for p, m in self.bound.items() if self.group_of[p] == group and self.zone[m] == zone]

    def counts(self, group):
        return [len(self.pods_in(group, z)) for z in sorted(set(self.zone))]

    def round(self, arrivals, completions, group=None):
        """One served round: `arrivals` pods of random workloads (of
        `group`, if given; or one pod of each workload in a list) and
        `completions` of random bound pods (or the pods of a list).
        Returns (objective, pods placed, {(g, z): placed}) of the
        service and the same of the reference."""
        if isinstance(completions, int):
            completions = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        if isinstance(arrivals, int):
            arrivals = [
                int(self.rng.integers(0, self.groups)) if group is None else group
                for _ in range(arrivals)
            ]
        # a completed pod leaves n now and holds its slot until this
        # round's `deltas` phase
        lingering = [self.bound.pop(p) for p in completions]
        self.api.complete_later(completions)
        new = []
        for g in arrivals:
            pod = f"p{self.k}"
            self.k += 1
            self.group_of[pod] = g
            new.append(pod)
            self.api.submit_pod(PodEvent(pod_id=pod, task_class=g))
        runnable = [(p, self.group_of[p]) for p in self.backlog + new]
        counted = [(self.group_of[p], m) for p, m in self.bound.items()]
        reference = reference_round(
            runnable, self.slots, self.zone, counted,
            list(self.bound.values()) + lingering, MAX_SKEW,
        )
        # in as many polls as the debounce takes (7 of 12 pods in one poll, under
        # load, in the driver's run of PR 37): its quiet timer runs on the machine's clock
        batch = drain(self.api, len(arrivals))
        assert len(batch) == len(arrivals)
        self.svc.run_round(batch)
        now = self.api.bindings()
        into = {}
        for p, g in runnable:
            if p in now:
                self.bound[p] = self.machine_of[now[p]]
                key = (g, self.zone[self.bound[p]])
                into[key] = into.get(key, 0) + 1
        self.backlog = [p for p, _g in runnable if p not in now]
        objective = int(self.svc.scheduler.last_timing.objective)
        return (objective, sum(into.values()), into), reference

    def holds_the_guarantee(self):
        fault, facts = check_topology_spread(self.api.log, self.group_of, self.zone_of, MAX_SKEW)
        assert fault is None, fault
        return facts

    def books_agree(self):
        """The model's n, K, loads and free slots against a recount from
        the scheduler's own maps."""
        sched = self.svc.scheduler
        model = sched.cost_model
        n, load = {}, {}
        for task, pu in sched.task_bindings.items():
            machine = model._pu_machine[pu]
            g = self.svc.task_map.find(task).workload
            zone = model._machine_zone[machine]
            n.setdefault(g, {}).setdefault(zone, 0)
            n[g][zone] += 1
        for pu, machine in model._pu_machine.items():
            rd = self.svc.resource_map.find(pu).descriptor
            load[machine] = load.get(machine, 0) + len(rd.current_running_tasks)
        assert {g: h for g, h in model._n.items() if h} == n
        assert model._load == load
        waiting = {}
        for task, node in sched.gm.task_to_node.items():
            if node.task.state == TaskState.RUNNABLE:
                waiting[task] = node.task.workload
        assert model._waiting == waiting
        assert {g: k for g, k in model._runnable.items() if k} == {
            g: list(waiting.values()).count(g) for g in set(waiting.values())
        }
        for zone, machines in model._zone_machines.items():
            assert model._zone_free[zone] == sum(model._slots[m] - model._load[m] for m in machines)

    def zone_arcs_are_a_sweeps(self):
        """Every zone EC's arcs against the rule, machine by machine, but
        for the machines events touched since its arcs were brought up
        to date (the model's record, which the next refresh works off)."""
        model, gm = self.svc.scheduler.cost_model, self.svc.scheduler.gm
        for ec, node in gm.task_ec_to_node.items():
            zone = model._ec_zone.get(ec)
            if zone is None:
                assert all(a.dst_node.equiv_class is not None for a in node.outgoing.values())
                continue
            pending = model._changed[zone]
            have = {
                a.dst_node.resource_id: a.cap_upper for a in node.outgoing.values()
                if a.dst_node.resource_id not in pending
            }
            want = {
                m: model._slots[m] - model._load[m] for m in model._zone_machines[zone]
                if m not in pending and model._load[m] < model._slots[m]
            }
            assert have == want, f"zone {zone}"


STREAMS = [
    # machines, slots, zones, groups, seed, backend, rounds, arrivals, completions
    (9, 20, 3, 3, 1, "native", 7, 12, 6),
    (9, 20, 3, 3, 1, "jax", 5, 12, 6),
    (12, 30, 3, 4, 2, "auto", 6, 15, 10),
    (10, 40, 2, 5, 3, "native", 6, 9, 7),
    (20, 110, 4, 16, 4, "native", 8, 40, 25),
    (125, 110, 3, 16, 5, "native", 5, 120, 60),
    (125, 110, 3, 16, 6, "jax", 3, 120, 60),
]


@pytest.mark.parametrize(
    "machines,slots,zones,groups,seed,backend,rounds,arrivals,completions", STREAMS
)
def test_every_round_equals_the_reference_zone_by_zone_and_holds_the_guarantee(
    machines, slots, zones, groups, seed, backend, rounds, arrivals, completions
):
    s = Stream(machines, slots, zones, groups, seed, backend)
    for r in range(rounds):
        ours, reference = s.round(arrivals, completions if r else 0)
        assert ours == reference, f"round {r}"
        assert not s.backlog and s.svc.scheduler.last_timing.spread_fallback == 0
        s.books_agree()
        s.zone_arcs_are_a_sweeps()
    facts = s.holds_the_guarantee()
    assert facts["largest_skew"] <= 1 and facts["rounds"] == rounds
    assert s.svc.noop_rounds == 0 and s.svc.ladder.degradations_total == 0


@pytest.mark.parametrize("backend", ["native", "jax"])
def test_zones_that_start_uneven_are_levelled_before_the_high_one_receives(backend):
    s = Stream(6, 50, 3, 2, 7, backend)
    s.round(30, 0, group=0)
    assert s.counts(0) == [10, 10, 10]
    # n = (10, 0, 0): zones 1 and 2 empty out between two rounds
    ours, reference = s.round(4, s.pods_in(0, 1) + s.pods_in(0, 2), group=0)
    assert ours == reference and ours[2] == {(0, 1): 2, (0, 2): 2}
    assert s.counts(0) == [10, 2, 2]
    ours, reference = s.round(25, 0, group=0)  # L = 13: b = (3, 11, 11), no remainder
    assert ours == reference and ours[2] == {(0, 0): 3, (0, 1): 11, (0, 2): 11}
    ours, reference = s.round(2, 0, group=0)  # L = 13, r = 2: the first two zones
    assert ours == reference and ours[2] == {(0, 0): 1, (0, 1): 1}
    # the other workload starts from its own counts
    ours, reference = s.round(5, 0, group=1)
    assert ours == reference and ours[2] == {(1, 0): 2, (1, 1): 2, (1, 2): 1}
    s.books_agree()
    assert s.holds_the_guarantee()["largest_skew"] == 1


def test_n_falls_at_the_completion_not_at_the_drop_a_round_later():
    s = Stream(3, 50, 3, 1, 8)
    s.round(6, 0, group=0)
    assert s.counts(0) == [2, 2, 2]
    # both pods of zone 0 complete; the two arrivals of the same round
    # find n = (0, 2, 2) and go there (a count one round late would read
    # (2, 2, 2) and send them to zones 0 and 1)
    ours, reference = s.round(2, s.pods_in(0, 0), group=0)
    assert ours == reference and ours[2] == {(0, 0): 2}
    model = s.svc.scheduler.cost_model
    machine = next(m for m, z in model._machine_zone.items() if z == "zone-0")
    assert model._n[0] == {"zone-0": 2, "zone-1": 2, "zone-2": 2}
    assert model._load[machine] == 2  # the two that left gave their slots back in `deltas`
    s.books_agree()


@pytest.mark.parametrize("counts,pods,level,allot", [
    ((10, 0, 0), 4, 2, [0, 2, 2]),
    ((10, 0, 0), 25, 11, [2, 12, 11]),  # b = (1, 11, 11), r = 2: zones 0 and 1
    ((3, 3, 3), 1, 3, [1, 0, 0]),
    ((3, 3, 3), 3, 4, [1, 1, 1]),
    ((5, 2, 0), 3, 2, [0, 1, 2]),  # r = 1 skips zone 0, which is above the level
    ((5, 2, 0), 2, 2, [0, 0, 2]),
    ((0, 7), 3, 3, [3, 0]),
    ((4,), 9, 13, [9]),
    ((1, 1, 0, 6), 8, 3, [3, 2, 3, 0]),  # L = 3: b = (2, 2, 3, 0) = 7, r = 1: zone 0
])
def test_the_water_level_and_its_remainder_by_hand(counts, pods, level, allot):
    assert water_level(counts, pods) == (level, allot)
    assert allotment(counts, pods) == allot  # the reference's, by counting up
    assert sum(allot) == pods
    assert all(c + a >= level for c, a in zip(counts, allot))
    assert all(c + a <= level + 1 for c, a in zip(counts, allot) if a)


def test_the_models_water_level_is_the_references_on_random_counts():
    rng = np.random.default_rng(9)
    for _ in range(2000):
        counts = [int(c) for c in rng.integers(0, 15, int(rng.integers(1, 6)))]
        pods = int(rng.integers(1, 50))
        assert water_level(counts, pods)[1] == allotment(counts, pods)


def test_a_zone_short_of_room_takes_the_fallback_and_max_skew_holds_pods_back():
    # zone a: one node of 2 slots; zones b and c: one node of 16 each
    nodes = [_zone_node(0, "a"), _zone_node(1, "b", pus=8), _zone_node(2, "c", pus=8)]
    s = Stream(3, 2, 3, 1, 10, nodes=nodes)
    fell_back = 0
    for _ in range(4):
        ours, reference = s.round(6, 0, group=0)
        assert ours[:2] == reference[:2]  # where the pods go is not unique here
        t = s.svc.scheduler.last_timing
        fell_back += t.spread_fallback
        assert t.unscheduled_by_rule == len(s.backlog)
        s.books_agree()
    assert fell_back == 4
    # zone a is full at 2, so b and c stop at 2 + maxSkew: 16 bound of
    # 24, though b and c keep 9 free slots each
    assert s.counts(0) == [2, 2 + MAX_SKEW, 2 + MAX_SKEW] and len(s.backlog) == 8
    facts = s.holds_the_guarantee()
    assert facts["largest_skew"] == MAX_SKEW
    # a pod of zone a completes: nothing may be placed (its slot is not back,
    # and n(a) = 1 lowers the ceiling); a round later it returns to zone a
    ours, reference = s.round(0, s.pods_in(0, 0)[:1], group=0)
    assert ours[:2] == reference[:2] and ours[1] == 0
    ours, reference = s.round(0, 0, group=0)
    assert ours[:2] == reference[:2] and ours[2] == {(0, 0): 1}
    s.holds_the_guarantee()


def test_with_room_again_the_allotment_returns():
    nodes = [_zone_node(0, "a"), _zone_node(1, "b"), _zone_node(2, "c")]
    s = Stream(3, 6, 3, 2, 11, nodes=nodes)
    s.round(7, 0, group=0)  # R = 7 > F = 6: the fallback, at most maxSkew a zone
    assert s.svc.scheduler.last_timing.spread_fallback == 1 and not s.backlog
    assert max(s.counts(0)) <= MAX_SKEW
    ours, reference = s.round(1, 0, group=1)  # R = 1 <= F(z) for every zone
    assert s.svc.scheduler.last_timing.spread_fallback == 0
    assert ours == reference and ours[2] == {(1, 0): 1}
    s.holds_the_guarantee()


def test_the_collapse_refuses_it_for_the_chain_capacity_that_binds():
    s = Stream(9, 20, 3, 2, 12)
    s.round(12, 0)
    collapse, reason = try_collapse(s.svc.scheduler.solver.state.problem())
    assert collapse is None
    assert re.fullmatch(r"EC \d+ -> EC \d+: chain arc cap \d+ can bind", reason), reason


def test_under_auto_the_refused_collapse_is_solved_on_the_general_rung():
    s = Stream(9, 20, 3, 2, 13, backend="auto")
    ours, reference = s.round(12, 0)
    rung = s.svc.ladder.primary
    assert rung.last_path == "csr" and "chain arc cap" in rung.last_refusal
    assert ours == reference


def test_chain_arcs_are_added_recapacitated_and_removed():
    s = Stream(6, 50, 3, 1, 14)
    gm = s.svc.scheduler.gm
    s.round(6, 0, group=0)
    t = s.svc.scheduler.last_timing
    # 3 chain arcs and the zones' 6 arcs to machines leave the 4 EC nodes
    assert (_chain_arcs(gm), t.ec_chain_arcs_changed, t.ec_nodes, t.ec_arcs) == (3, 3, 4, 9)
    node = gm.task_ec_to_node[workload_ec(0)]

    def caps():
        zone_of = s.svc.scheduler.cost_model._ec_zone
        return {zone_of[a.dst_node.equiv_class]: a.cap_upper for a in node.outgoing.values()}

    assert caps() == {"zone-0": 2, "zone-1": 2, "zone-2": 2}
    s.round(3, 0, group=0)  # another capacity on each
    assert (_chain_arcs(gm), gm.ec_chain_arcs_changed) == (3, 3)
    assert caps() == {"zone-0": 1, "zone-1": 1, "zone-2": 1}
    s.round(3, 0, group=0)  # the same allotment again: nothing to change
    assert (_chain_arcs(gm), gm.ec_chain_arcs_changed) == (3, 0)
    s.round(1, 0, group=0)  # a(0, .) = (1, 0, 0): two arcs go
    assert (_chain_arcs(gm), gm.ec_chain_arcs_changed) == (1, 2)
    assert caps() == {"zone-0": 1}
    s.round(1, 0, group=0)  # the arc moves to zone 1
    assert (_chain_arcs(gm), gm.ec_chain_arcs_changed) == (1, 2)
    assert caps() == {"zone-1": 1}
    stats = gm.cm.stats.by_type
    assert stats[ChangeType.ADD_ARC_BETWEEN_EQUIV_CLASS] == 1
    assert stats[ChangeType.DEL_ARC_BETWEEN_EQUIV_CLASS] == 1
    s.holds_the_guarantee()


def test_zone_ecs_survive_the_purge_while_a_workload_lives_and_go_with_the_last():
    s = Stream(6, 50, 3, 2, 15)
    gm = s.svc.scheduler.gm
    zones = [zone_ec(f"zone-{z}") for z in range(3)]
    s.round(3, 0, group=0)
    listed = gm.cm.stats.by_type[ChangeType.ADD_ARC_EQUIV_CLASS_TO_RES]
    assert listed == 6
    added = 0
    for _ in range(7):
        # one pod a round: one zone has an arc into it, the two others are
        # unconnected at purge after purge, and no task ever points at any
        ours, reference = s.round(1, 0, group=0)
        assert ours == reference
        assert all(z in gm.task_ec_to_node for z in zones)
        added += gm.cm.stats.by_type[ChangeType.ADD_ARC_EQUIV_CLASS_TO_RES]
    assert added == 0  # no zone's fan-out was listed anew
    assert sum(1 for z in zones if not gm.task_ec_to_node[z].incoming) == 2
    # workload 1 takes over; EC(0) is idle at two purges in a row and goes,
    # the zones stay: EC(1) has listed each of them
    for _ in range(3):
        s.round(3, 0, group=1)
    assert workload_ec(0) not in gm.task_ec_to_node
    assert set(gm.task_ec_to_node) == {workload_ec(1), *zones}
    # nothing runnable any more: EC(1) goes at the second idle purge, and
    # the zones, orphaned by it, in the same call
    gm.purge_unconnected_equiv_class_nodes()
    assert len(gm.task_ec_to_node) == 4
    gm.purge_unconnected_equiv_class_nodes()
    assert not gm.task_ec_to_node and not gm._ec_listed_by
    ours, reference = s.round(4, 0)  # listed anew
    assert ours == reference
    s.zone_arcs_are_a_sweeps()
    s.holds_the_guarantee()


def test_the_zone_refresh_follows_the_machines_events_touched():
    s = Stream(30, 110, 3, 4, 16)
    s.round(60, 0)
    gm = s.svc.scheduler.gm
    assert gm.ec_arcs_changed == 30  # the first listing of three zones
    s.round([0, 1, 2, 3], 0)
    # the 60 pods of the first round re-capacitated their machines' arcs
    touched = gm.ec_arcs_changed
    assert 0 < touched <= 30
    s.round([0, 1, 2, 3], 2)
    assert gm.ec_arcs_changed <= 4  # last round's four binds
    s.round([0, 1, 2, 3], 0)
    assert gm.ec_arcs_changed <= 4 + 2  # and the two drops
    s.zone_arcs_are_a_sweeps()


def test_a_machine_that_leaves_takes_its_pods_out_of_n_and_they_bind_again():
    s = Stream(6, 50, 3, 1, 17)
    s.round(12, 0, group=0)
    assert s.counts(0) == [4, 4, 4]
    sched = s.svc.scheduler
    model = sched.cost_model
    gone = s.svc.node_to_machine["fake_node_0"]  # zone 0 keeps fake_node_3
    evicted = sum(1 for m in s.bound.values() if m == 0)
    sched.deregister_resource(s.svc.resource_map.find(gone).topology_node)
    assert model._n[0].get("zone-0", 0) == 4 - evicted and gone not in model._slots
    assert model._runnable[0] == evicted
    s.svc.run_round([PodEvent(pod_id="extra", task_class=0)])
    assert model._n[0] == {"zone-0": 5, "zone-1": 4, "zone-2": 4} and model._runnable[0] == 0
    s.books_agree()


def test_labels_reach_the_model_from_the_node_event_and_from_fake_zones():
    svc, _api = _service(7, 4, 3)
    model = svc.scheduler.cost_model
    for i in range(7):
        machine = svc.node_to_machine[f"fake_node_{i}"]
        labels = svc.resource_map.find(machine).descriptor.labels
        assert labels == {ZONE_LABEL: f"zone-{i % 3}"}
        assert model._machine_zone[machine] == f"zone-{i % 3}"
    assert model._zones == ["zone-0", "zone-1", "zone-2"]
    assert {z: len(m) for z, m in model._zone_machines.items()} == {
        "zone-0": 3, "zone-1": 2, "zone-2": 2,
    }
    # without the flag a fake node has no label, and the model one zone, ""
    plain, _ = _service(4, 4, 0)
    assert plain.resource_map.find(plain.node_to_machine["fake_node_1"]).descriptor.labels == {}
    assert plain.scheduler.cost_model._zones == [""]
    assert plain.run_round([PodEvent(pod_id=f"p{i}", task_class=0) for i in range(5)]) == 5
    # a node event carries whatever labels the control plane has
    event = NodeEvent(node_id="n", labels=(("kubernetes.io/arch", "amd64"), (ZONE_LABEL, "moon-2")))
    assert hash(event) == hash(NodeEvent(node_id="n", labels=event.labels))
    svc.add_node(event)
    rd = svc.resource_map.find(svc.node_to_machine["n"]).descriptor
    assert rd.labels == {"kubernetes.io/arch": "amd64", ZONE_LABEL: "moon-2"}
    assert model._zones == ["moon-2", "zone-0", "zone-1", "zone-2"]
    assert cli.build_arg_parser().parse_args([]).fake_zones == 0


def test_the_http_watch_reads_metadata_labels():
    server = FakeAPIServer().start()
    try:
        server.add_node("node_a", cores=1, pus_per_core=2, labels={ZONE_LABEL: "moon-1", "x": "y"})
        server.add_node("node_b", labels={ZONE_LABEL: "moon-3"})
        server.add_node("node_c")
        api = HTTPClusterAPI(server.base_url, pod_chan_size=16)
        try:
            deadline = time.monotonic() + 10.0
            nodes = []
            while len(nodes) < 3 and time.monotonic() < deadline:
                nodes += api.get_node_batch(0.3)
        finally:
            api.close()
    finally:
        server.stop()
    by_name = {n.node_id: n for n in nodes}
    assert by_name["node_a"].labels == ((ZONE_LABEL, "moon-1"), ("x", "y"))  # sorted pairs
    assert by_name["node_a"].pus_per_core == 2
    assert dict(by_name["node_b"].labels) == {ZONE_LABEL: "moon-3"}
    assert by_name["node_c"].labels == ()


def test_the_model_is_registered_and_its_docstring_holds_the_equations():
    assert MODEL_REGISTRY[CostModelType.K8S_ZONESPREAD] is K8sZoneSpreadCostModel
    assert int(CostModelType.K8S_ZONESPREAD) == 10 and len(MODEL_REGISTRY) == 13
    assert "k8s_zonespread" in cli.build_arg_parser().format_help()
    assert (K8sZoneSpreadCostModel.CLUSTER_AGG_COST, K8sZoneSpreadCostModel.UNSCHEDULED_COST) == (
        EC_COST, UNSCHEDULED_COST,
    )
    assert K8sZoneSpreadCostModel.pinned_tasks_are_inert and MAX_SKEW == 5
    import importlib

    text = importlib.import_module(K8sZoneSpreadCostModel.__module__).__doc__
    for phrase in (
        "sum_z max(0, L - n(g, z)) <= K(g)", "for the first r(g)", "F(z) < R for some z",
        "min_z' n(g, z') + s - n(g, z)", "AT the\ncompletion event", "Departures",
        "stricter than s asks", "Only if:", "capacity 1, cost e", "capacity 1, cost u",
    ):
        assert phrase in text, phrase
    # every model but the two that take a workload index says which do
    from ksched_tpu.costmodels import TrivialCostModel

    with pytest.raises(ValueError, match="k8s_antiaffinity or k8s_zonespread"):
        TrivialCostModel(None, None, set(), 1).task_class_fields(9)
    model = K8sZoneSpreadCostModel(None, None, set(), 1)
    assert model.task_class_fields(9) == {"workload": 9}
    with pytest.raises(ValueError, match="index of a workload"):
        model.task_class_fields(-1)


def test_a_redelivered_pod_of_another_workload_is_counted_under_the_new_one():
    svc, api = _service(3, 5, 3)
    svc.run_round([PodEvent(pod_id="a", task_class=0), PodEvent(pod_id="b", task_class=1)])
    model = svc.scheduler.cost_model
    assert sum(model._n[0].values()) == 1 and sum(model._n[1].values()) == 1
    svc.run_round([PodEvent(pod_id="a", task_class=1)])  # same name, workload 1 now
    assert not model._n[0] and sum(model._n[1].values()) == 2
    assert not model._waiting and not any(model._runnable.values())
    nodes = api.bindings()
    assert nodes["a"] != nodes["b"]  # level over the zones: one node a zone


def test_the_span_and_the_counters_ride_the_round_record():
    tracer = SpanTracer(capacity=1 << 14).install()
    try:
        s = Stream(6, 50, 3, 2, 18, tracer=RoundTracer(), span_tracer=tracer)
        s.round(8, 0, group=0)
        events = tracer.events()
    finally:
        tracer.uninstall()
    rec = s.svc.tracer.records[-1]
    assert (rec.ec_nodes, rec.ec_arcs, rec.ec_chain_arcs_changed) == (4, 9, 3)
    assert (rec.ec_arcs_changed, rec.spread_fallback, rec.unscheduled_by_rule) == (6, 0, 0)
    update = [e for e in events if e["name"] == "graph_update"][-1]
    assert update["args"]["ec_chain_arcs_changed"] == 3
    chain = [e for e in events if e["name"] == "ec_chain_refresh"]
    refresh = [e for e in events if e["name"] == "ec_refresh"]
    assert len(chain) == len(refresh) == 4  # EC(0) and the three zones
    for e in chain + refresh:
        assert update["ts"] <= e["ts"] and e["ts"] + e["dur"] <= update["ts"] + update["dur"]
    # each EC node's chain half comes before its resource half, and they do not overlap
    for c, r in zip(chain, refresh):
        assert c["ts"] + c["dur"] <= r["ts"]
