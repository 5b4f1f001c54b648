"""The class ECs of `--cost-model coco` and `--cost-model whare` re-price
the machines the census gathered again, not every machine
(costmodels/census.py: `ClassCensusKeeper.take_listing_changes`,
`ClassCensusCostModel`).

Two services of one seed take the same pods, completions and nodes: one
as it is built, one whose model answers `equiv_class_pref_arc_changes`
with None, so that every class EC sweeps every machine every round, as
both models did before. After every round of a scripted sequence (a
fill, trickle rounds, a class that sits a round out, a machine that
joins, one that leaves, a round whose statistics pass walks every node,
and for `whare` a recorded runtime) the class ECs' arcs are the same,
arc for arc: target, cost and capacity, an arc of capacity 0 to a full
machine included (the sweep keeps one, so the patch does). The batch hook
prices any list of the keeper's machines as the scalar hook prices each.
"""

import numpy as np
import pytest

from benchmarks.client import BenchClusterAPI
from ksched_tpu import cli
from ksched_tpu.cluster.api import NodeEvent, PodEvent
from ksched_tpu.costmodels import coco
from ksched_tpu.costmodels.base import CostModeler
from ksched_tpu.costmodels.census import CLASS_ECS, ClassCensusCostModel
from ksched_tpu.data import PLATFORM_LABEL
from ksched_tpu.utils import resource_id_from_string, seed_rng
from test_k8s_priority import drain

PUS, PODS_A_PU = 2, 3
FLAGS = {"coco": "", "whare": " --fake-machine-types A:1:10,B:2:930,C:4:60"}
MODELS = sorted(FLAGS)


def _service(model, machines):
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --pus-per-core {PUS} "
        f"--max-tasks-per-pu {PODS_A_PU}{FLAGS[model]} --cost-model {model} --backend auto".split()
    )
    api = BenchClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(args, api)
    api.svc = svc
    svc.init_topology(fake_machines=machines, pus_per_core=PUS)
    return svc, api


def _class_ec_arcs(svc):
    """class EC -> {node name: (cost, capacity)} as the graph has them."""
    gm = svc.scheduler.gm
    return {
        ec: {
            svc.machine_to_node[arc.dst_node.resource_id]: (arc.cost, arc.cap_upper)
            for arc in gm.task_ec_to_node[ec].outgoing.values()
        }
        for ec in CLASS_ECS if ec in gm.task_ec_to_node
    }


class Twins:
    """The service as built (`patched`) and its twin whose class ECs sweep
    (`swept`), fed the same events; `round` holds them to one graph. Ids
    are drawn from one generator of the process, so each side's turn
    starts it again at the same place: the same ids, the same ties."""

    def __init__(self, model, machines, seed):
        self.sides = []
        for sweeps in (False, True):
            seed_rng(seed)
            svc, api = _service(model, machines)
            if sweeps:
                svc.scheduler.cost_model.equiv_class_pref_arc_changes = lambda ec: None
            self.sides.append((svc, api))
        self.seed = seed
        self.turns = 0
        self.rng = np.random.default_rng(seed)
        self.alive = []  # pods bound and not completed
        self.completed = set()
        self.k = 0

    @property
    def patched(self):
        return self.sides[0][0].scheduler

    def each(self, act):
        self.turns += 1
        for svc, _api in self.sides:
            seed_rng(1000 * self.seed + self.turns)
            act(svc)

    def round(self, classes, completions=0):
        gone = [str(p) for p in self.rng.permutation(self.alive)[:completions]]
        new = [(f"p{self.k + i}", int(c)) for i, c in enumerate(classes)]
        self.k += len(new)
        self.turns += 1
        for svc, api in self.sides:
            seed_rng(1000 * self.seed + self.turns)
            api.complete_later(list(gone))
            for pod, c in new:
                api.submit_pod(PodEvent(pod_id=pod, task_class=c))
            svc.run_round(drain(api, len(new)))
        (ours, api), (theirs, their_api) = self.sides
        assert api.bindings() == their_api.bindings()
        assert _class_ec_arcs(ours) == _class_ec_arcs(theirs)
        mine, other = ours.scheduler.last_timing, theirs.scheduler.last_timing
        assert mine.objective == other.objective
        # the same arcs change, and the journal holds the same number of records
        assert (mine.ec_arcs_changed, mine.journal_changes) == (
            other.ec_arcs_changed, other.journal_changes)
        assert mine.census_machines_dirty == other.census_machines_dirty
        self.completed |= set(gone)
        self.alive = sorted(set(api.bindings()) - self.completed)
        return mine, other


@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("model", MODELS)
def test_the_patched_class_ec_arcs_are_the_swept_ones_after_every_round(model, seed):
    t = Twins(model, 24, seed)
    rng = t.rng
    slots = sum(
        len(core.children) * PODS_A_PU
        for m in t.patched.cost_model.census.machines.values() for core in m.children
    )
    every_class = lambda n: [0, 1, 2, 3] + rng.integers(0, 4, n - 4).tolist()
    # the fill: no EC has listed, so each sweeps; most machines end with little room
    mine, other = t.round(every_class(slots - 30))
    assert mine.ec_arcs_repriced == other.ec_arcs_repriced == 4 * 24
    # the fill touched every PU, so the next pass walks every node and prepares every
    # machine: a sweep's worth, and the ECs sweep
    mine, _ = t.round(every_class(5), 4)
    assert mine.stats_full_walk == 1 and mine.ec_arcs_repriced == 4 * 24
    # trickle rounds, every class in each: a class EC prices the machines gathered again
    for _ in range(6):
        mine, other = t.round(every_class(int(rng.integers(4, 9))), int(rng.integers(3, 12)))
        assert mine.stats_full_walk == 0 and 0 < mine.census_machines_dirty < 24
        assert mine.ec_arcs_repriced == 4 * mine.census_machines_dirty
        assert mine.ec_arcs_changed <= mine.ec_arcs_repriced < other.ec_arcs_repriced == 4 * 24
    # some machine is full, and its arc stays, at capacity 0, as a sweep leaves it
    arcs = _class_ec_arcs(t.sides[0][0])
    assert all(len(a) == 24 for a in arcs.values())
    assert any(cap == 0 for a in arcs.values() for _cost, cap in a.values())
    # the turtles' EC sits a round out while turtles complete, and owes that round's machines
    mine, _ = t.round([0, 1, 2, 0, 1], 8)
    assert mine.ec_arcs_repriced == 3 * mine.census_machines_dirty
    owed = mine.census_machines_dirty
    mine, _ = t.round(every_class(6), 4)
    assert 4 * mine.census_machines_dirty <= mine.ec_arcs_repriced <= (
        4 * mine.census_machines_dirty + owed)
    # a machine joins: no listing can be trusted, every EC of the round sweeps and reaches it
    t.each(lambda svc: svc.add_node(NodeEvent(
        node_id="late", num_cores=2, pus_per_core=PUS, labels=((PLATFORM_LABEL, "C"),))))
    mine, _ = t.round(every_class(5), 3)
    assert mine.ec_arcs_repriced == 4 * 25
    assert all("late" in a for a in _class_ec_arcs(t.sides[0][0]).values())
    mine, _ = t.round(every_class(7), 5)
    assert mine.ec_arcs_repriced == 4 * mine.census_machines_dirty < 4 * 25
    # a machine leaves, and its pods with it: they bind again on what is left
    t.each(lambda svc: svc.scheduler.deregister_resource(
        svc.resource_map.find(svc.node_to_machine["fake_node_3"]).topology_node))
    mine, _ = t.round(every_class(5), 2)
    assert mine.ec_arcs_repriced == 4 * 24
    assert all("fake_node_3" not in a for a in _class_ec_arcs(t.sides[0][0]).values())
    t.round(every_class(4), 30)
    # a wave: the next round's statistics pass walks every node, so every machine is on the
    # record and the ECs sweep; the round after that patches again
    t.round(every_class(40), slots // 2)
    mine, _ = t.round(every_class(5), 3)
    assert mine.stats_full_walk == 1 and mine.ec_arcs_repriced == 4 * 24
    mine, other = t.round(every_class(6), 6)
    assert mine.stats_full_walk == 0
    assert mine.ec_arcs_repriced == 4 * mine.census_machines_dirty < other.ec_arcs_repriced
    if model == "whare":
        # the map moves under every arc of the rabbits' EC, on machines no census gathered
        t.each(lambda svc: [svc.scheduler.cost_model.record_runtime(1, 1, 0, 900.0) for _ in range(8)])
        mine, _ = t.round(every_class(6), 4)
        assert mine.ec_arcs_repriced == 4 * 24 and mine.ec_arcs_changed > 24
        mine, _ = t.round(every_class(6), 4)
        assert mine.ec_arcs_repriced == 4 * mine.census_machines_dirty
    for svc, _api in t.sides:
        assert svc.ladder.degradations_total == 0 and svc.noop_rounds == 0
        assert svc.ladder.primary.last_path == "dense"


@pytest.mark.parametrize("model", MODELS)
def test_an_ec_that_never_listed_or_whose_listing_is_void_sweeps(model):
    svc, api = _service(model, 6)
    keeper = svc.scheduler.cost_model.census
    answer = svc.scheduler.cost_model.equiv_class_pref_arc_changes
    assert [answer(ec) for ec in CLASS_ECS] == [None] * 4 and answer(12345) is None
    svc.run_round([PodEvent(pod_id=f"p{i}", task_class=i % 2) for i in range(4)])
    # two classes listed; nothing was gathered since
    assert [answer(ec) for ec in CLASS_ECS] == [[], [], None, None]
    svc.run_round([PodEvent(pod_id="q", task_class=0)])
    # the sheep's EC was answered in the round; the rabbits' sat out and owes the machines
    # the first round's four Bindings dirtied; it is asked once, and the record starts again
    owed = answer(CLASS_ECS[1])
    assert 0 < len(owed) <= 4 and owed == [r for r in keeper.machines if r in owed]  # a listing's order
    assert answer(CLASS_ECS[0]) == answer(CLASS_ECS[1]) == []
    # a machine joins: every listing is void
    svc.add_node(NodeEvent(node_id="late", num_cores=1, pus_per_core=PUS))
    assert [answer(ec) for ec in CLASS_ECS] == [None] * 4
    # a pass that prepared every machine is a sweep's worth: None, and the listing stands
    keeper.start_listing(CLASS_ECS[0])
    gm = svc.scheduler.gm
    for rid in keeper.machines:
        keeper.prepare(gm.resource_to_node[rid])
    assert answer(CLASS_ECS[0]) is None


# -- the batch hook ----------------------------------------------------------------------------


def _a_cluster_with_a_history(model):
    seed_rng(7)
    svc, api = _service(model, 24)
    machines = svc.scheduler.cost_model.census.machines
    for i, rtnd in enumerate(machines.values()):
        # penalties on some machines, before the model first reads them
        s = rtnd.resource_desc.coco_interference_scores
        s.sheep_penalty, s.rabbit_penalty, s.devil_penalty, s.turtle_penalty = (
            (i % 3) * 5, (i % 4) * 7, i % 2, (i % 5) * 600)
    rng = np.random.default_rng(7)
    k = 0
    for n, done in ((150, 0), (9, 7), (9, 7), (9, 7)):
        bound = sorted(api.bindings())
        api.complete_later([bound[i] for i in rng.permutation(len(bound))[:done]])
        for _ in range(n):
            api.submit_pod(PodEvent(pod_id=f"p{k}", task_class=int(rng.integers(0, 4))))
            k += 1
        svc.run_round(drain(api, n))
    sched = svc.scheduler
    sched.gm.compute_topology_statistics(sched.gm.sink_node)  # as a round's `stats` would
    return svc, rng


@pytest.mark.parametrize("model", MODELS)
def test_the_batch_hook_prices_any_list_of_machines_as_the_scalar_hook_prices_each(model):
    svc, rng = _a_cluster_with_a_history(model)
    m = svc.scheduler.cost_model
    assert isinstance(m, ClassCensusCostModel)
    rids = list(m.census.machines)
    lists = [rids, rids[::-1], rids[5:6], [], [rids[i] for i in rng.permutation(24)[:9]], rids[3:4] * 3]
    for ec in CLASS_ECS:
        for some in lists:
            costs, caps = m.ec_to_resource_batch(ec, some)
            assert list(zip(costs, caps)) == [m.equiv_class_to_resource_node(ec, r) for r in some]
            assert all(type(v) is int for v in costs + caps)
    assert len({m.equiv_class_to_resource_node(CLASS_ECS[3], r)[0] for r in rids}) > 3
    # a resource that is no machine of the keeper is asked as the base class asks
    core = next(iter(m.census.machines.values())).children[0].resource_desc
    other = [rids[0], resource_id_from_string(core.uuid)]
    assert m.ec_to_resource_batch(CLASS_ECS[0], other) == CostModeler.ec_to_resource_batch(
        m, CLASS_ECS[0], other)
    # an EC that is no class's has no arc to any machine
    assert m.ec_to_resource_batch(12345, rids[:2]) == ([0, 0], [0, 0])


def test_cocos_batch_is_a_row_of_coco_cost_matrix():
    svc, _rng = _a_cluster_with_a_history("coco")
    m = svc.scheduler.cost_model
    rids, census, _idle, _slots, free = m.census.machine_arrays()
    penalties = np.array(
        [coco.machine_penalty_matrix(m.census.machines[r].resource_desc) for r in rids])
    assert penalties.any() and census.sum() > 100
    matrix = coco.coco_cost_matrix(census, penalties)
    assert matrix.max() == coco.MAX_COST  # some cell is clamped: a penalty of 2,400
    for c, ec in enumerate(CLASS_ECS):
        costs, caps = m.ec_to_resource_batch(ec, rids)
        assert costs == matrix[c].tolist() and caps == free.tolist()
        assert m.ec_to_resource_batch(ec, rids[4:11])[0] == matrix[c, 4:11].tolist()
