"""`--cost-model quincy` on the served path: Quincy's data-locality policy
with its rack tier (costmodels/quincy.py).

Seeded multi-round streams through `cli.build_service` on clusters of a
few racks: every round's Bindings cost what the plain reference's
`reference_round` says (benchmarks/reference_quincy.py: the equations and
a textbook successive shortest path, nothing of `ksched_tpu`), the round's
objective is that number and native C++'s and, where `try_collapse`
answers, the dense rung's; the equations come out as worked by hand; a pin
drops every preference arc; a completed pod's blocks leave the registry;
the wait term stops at its clamp; `build_service` refuses a largest cost
that cannot fit the node bucket; pod inputs and rack labels reach the
model from the cluster API; the scan-CSR rung runs its price update for
this model, without which two pods that contend for one slot settle by
unit relabels."""

import itertools

import numpy as np
import pytest

from benchmarks import reference_quincy as ref
from benchmarks.client import BenchClusterAPI
from ksched_tpu import cli
from ksched_tpu.cluster.api import NodeEvent, PodEvent
from ksched_tpu.costmodels import CLUSTER_AGGREGATOR_EC, QuincyCostModel, TrivialCostModel
from ksched_tpu.costmodels.quincy import rack_ec
from ksched_tpu.data import RACK_LABEL
from ksched_tpu.graph.flowgraph import ArcType
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng
from test_k8s_priority import drain

MB = 1 << 20
BLOCK = 64 * MB


def _service(machines, slots, racks, backend="native", nodes=None, **kw):
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --max-tasks-per-pu {slots} "
        f"--fake-racks {racks} --cost-model quincy --backend {backend}".split()
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


def _node(i):
    return f"fake_node_{i}"


class Stream:
    """A seeded stream of arrivals that read blocks and of completions,
    with the test's own books of who holds which node: what the reference
    is given."""

    def __init__(self, machines, slots, racks, seed, backend="native", **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, slots, racks, backend, **kw)
        self.machines, self.slots, self.racks = machines, slots, racks
        self.rack_of = {_node(i): i % racks for i in range(machines)}
        self.rng = np.random.default_rng(seed)
        self.inputs = {}
        self.bound = {}  # pod -> node, pods alive and bound
        self.lingering = []  # nodes a completed pod still holds a slot of
        self.k = self.b = 0

    def blocks(self, n):
        """`n` fresh blocks, each on three nodes: one anywhere, two in
        another rack."""
        out = []
        for _ in range(n):
            first = int(self.rng.integers(0, self.machines))
            rack = (first % self.racks + 1 + int(self.rng.integers(0, self.racks - 1))) % self.racks
            others = [i for i in range(self.machines) if i % self.racks == rack]
            picks = self.rng.permutation(others)[:2]
            out.append((self.b, BLOCK, (_node(first), *(_node(int(i)) for i in picks))))
            self.b += 1
        return tuple(out)

    def free(self):
        free = {node: self.slots for node in self.rack_of}
        for node in list(self.bound.values()) + self.lingering:
            free[node] -= 1
        return free

    def round(self, arrivals, completions, most_blocks=6, inputs=None):
        """One served round: `arrivals` pods that read 1..`most_blocks`
        fresh blocks each (or the inputs of a list) and `completions` of
        random bound pods. Returns (the round's objective, the sum of
        the route costs of its Bindings, the reference's optimum, native
        C++'s objective on the round's problem)."""
        gone = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        # a completed pod holds its slot until this round's `deltas` phase
        self.lingering = [self.bound.pop(p) for p in gone]
        self.api.complete_later(gone)
        if inputs is None:
            inputs = [self.blocks(int(self.rng.integers(1, most_blocks + 1))) for _ in range(arrivals)]
        new = []
        for blocks in inputs:
            pod = f"p{self.k}"
            self.k += 1
            self.inputs[pod] = blocks
            new.append(pod)
            self.api.submit_pod(PodEvent(pod_id=pod, inputs=blocks))
        routes = [ref.Routes(self.inputs[p], self.rack_of) for p in new]
        want = ref.reference_round(self.free(), routes, self.rack_of)
        batch = drain(self.api, len(new))
        assert len(batch) == len(new)
        self.svc.run_round(batch)
        now = self.api.bindings()
        served = 0
        for pod, route in zip(new, routes):
            self.bound[pod] = now[pod]
            served += route.to_node(now[pod], self.rack_of[now[pod]])
        self.lingering = []
        solver, timing = self.svc.scheduler.solver, self.svc.scheduler.last_timing
        native = make_backend("native", warm_start=False, fallback=False)
        theirs = int(native.solve(solver.state.problem()).objective)
        if timing.plan_refits:
            # the round re-fitted its slot plan and solved once more on the graph it
            # left: that solve is the one `last_result` and `state.problem()` pair for
            assert theirs == int(solver.last_result.objective)
            theirs = int(timing.objective)
        return int(timing.objective), served, want, theirs

    def holds_the_guarantee(self):
        faults, facts = ref.check_data_locality(
            self.api.log, self.inputs, self.rack_of, self.slots,
            admitted=[(t1, n) for _t0, t1, n in self.api.polls if n],
        )
        assert faults == [], faults
        return facts


# -- every round is the reference's optimum ----------------------------------------


@pytest.mark.parametrize("backend", ["native", "jax"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_every_rounds_bindings_cost_what_the_reference_says(seed, backend):
    s = Stream(24, 3, 4, seed, backend=backend)
    for arrivals, completions in [(9, 0), (7, 3), (12, 5), (6, 8), (10, 2), (4, 6)]:
        objective, served, want, native = s.round(arrivals, completions)
        assert objective == served == want == native
    facts = s.holds_the_guarantee()
    assert facts["rounds_compared"] == facts["rounds"] == 6 and facts["pods_reading"] == 48
    assert facts["served_cost"] == facts["optimum_cost"] > 0
    # most pods have machines of their own; some racks only; few only X
    assert facts["bound_via"][0] > facts["bound_via"][2]


@pytest.mark.parametrize("seed", [6, 7])
def test_hot_blocks_make_a_round_contend_and_the_flow_still_finds_the_optimum(seed):
    # twelve pods read the same two blocks: six nodes hold them, three slots each
    s = Stream(24, 3, 4, seed, backend="jax")
    hot = s.blocks(2)
    objective, served, want, native = s.round(0, 0, inputs=[hot] * 12 + [s.blocks(3)] * 4)
    assert objective == served == want == native
    objective, served, want, native = s.round(0, 5, inputs=[hot] * 9)
    assert objective == served == want == native
    s.holds_the_guarantee()


def test_a_round_short_of_room_leaves_the_pods_with_the_cheapest_inputs_waiting():
    s = Stream(4, 1, 2, 8)
    small, large = s.blocks(1), s.blocks(8)
    s.api.submit_pod(PodEvent(pod_id="a", inputs=small))
    for i in range(4):
        s.api.submit_pod(PodEvent(pod_id=f"b{i}", inputs=large))
    s.svc.run_round(drain(s.api, 5))
    # u(t) = alpha(t) + 1: leaving the pod that reads least unscheduled is cheapest
    assert sorted(s.api.bindings()) == ["b0", "b1", "b2", "b3"]


# -- the equations, by hand --------------------------------------------------------


def _model(svc) -> QuincyCostModel:
    return svc.scheduler.cost_model


def _task(svc, pod):
    return svc.pod_to_task[pod]


def test_the_equations_come_out_as_worked_by_hand():
    svc, api = _service(8, 2, 4)  # node i in rack i mod 4
    m = _model(svc)
    rid = svc.node_to_machine
    # three blocks of 64 MiB: b0 on nodes 0, 1, 5 (racks 0, 1, 1); b1 on 0, 2, 6
    # (racks 0, 2, 2); b2 on 3, 1, 5 (racks 3, 1, 1)
    inputs = (
        (10, BLOCK, (_node(0), _node(1), _node(5))),
        (11, BLOCK, (_node(0), _node(2), _node(6))),
        (12, BLOCK, (_node(3), _node(1), _node(5))),
    )
    api.submit_pod(PodEvent(pod_id="t", inputs=inputs))
    svc._admit_pods(drain(api, 1))
    t = _task(svc, "t")
    # total 192 MiB = 12 quanta of 16 MiB; alpha = xi * 12 = 24; u = 25
    assert m.task_to_equiv_class_aggregator(t, CLUSTER_AGGREGATOR_EC) == 24
    assert m.task_to_unscheduled_agg_cost(t) == 25
    # local: node 0 128, nodes 1 and 5 128, nodes 2, 6, 3 64 (all >= 14% of 192);
    # inrack: rack 0 128, rack 1 128, rack 2 64, rack 3 64
    # d(t, 0) = psi * (128 - 128) + xi * (192 - 128) = 128 MiB -> 8
    # d(t, 1) = d(t, 5) = 0 + 2 * 64 -> 8; d(t, 2) = d(t, 6) = 0 + 2 * 128 -> 16 = d(t, 3)
    want = {0: 8, 1: 8, 5: 8, 2: 16, 6: 16, 3: 16}
    assert {n: m.task_to_resource_node_cost(t, rid[_node(n)]) for n in want} == want
    assert m.preferred_machines(t) == [rid[_node(n)] for n in (0, 1, 5, 2, 6, 3)]  # most first, then as met
    # rho(t, 0) = rho(t, 1) = psi * 128 + xi * 64 = 256 MiB -> 16; rho(t, 2) = rho(t, 3) = 64 + 2 * 128 -> 20
    racks = {l: m.task_to_equiv_class_aggregator(t, rack_ec(f"rack-{l}")) for l in range(4)}
    assert racks == {0: 16, 1: 16, 2: 20, 3: 20}
    assert m.get_task_equiv_classes(t) == [CLUSTER_AGGREGATOR_EC] + [rack_ec(f"rack-{l}") for l in (0, 1, 2, 3)]
    # the reference says the same, to every node
    rack_of = {_node(i): i % 4 for i in range(8)}
    routes = ref.Routes(inputs, rack_of)
    assert (routes.alpha, routes.machines, routes.racks) == (
        24, {_node(n): c for n, c in want.items()}, {0: 16, 1: 16, 2: 20, 3: 20},
    )
    assert [ref.route_cost(inputs, _node(n), rack_of) for n in range(8)] == [8, 8, 16, 16, 16, 8, 16, 20]
    assert ref.unscheduled_cost(inputs, rack_of) == 25 and ref.unscheduled_cost(inputs, rack_of, 3) == 55


def test_the_whole_weighted_sum_is_rounded_down_not_its_terms():
    svc, api = _service(4, 1, 2)
    m = _model(svc)
    # 10 MiB on node 0 (rack 0), 10 MiB on node 1 (rack 1): total 20 MiB
    inputs = ((1, 10 * MB, (_node(0),)), (2, 10 * MB, (_node(1),)))
    api.submit_pod(PodEvent(pod_id="t", inputs=inputs))
    svc._admit_pods(drain(api, 1))
    t = _task(svc, "t")
    # alpha = (2 * 20) // 16 = 2; d(t, 0) = (0 + 2 * 10) // 16 = 1; rho(t, 0) = (10 + 2 * 10) // 16 = 1
    assert m.task_to_equiv_class_aggregator(t, CLUSTER_AGGREGATOR_EC) == 2
    assert m.task_to_resource_node_cost(t, svc.node_to_machine[_node(0)]) == 1
    assert m.task_to_equiv_class_aggregator(t, rack_ec("rack-0")) == 1
    routes = ref.Routes(inputs, {_node(i): i % 2 for i in range(4)})
    assert (routes.alpha, routes.machines[_node(0)], routes.racks[0]) == (2, 1, 1)


def test_at_most_seven_machines_and_seven_racks_those_holding_most_ties_to_the_first_met():
    svc, api = _service(40, 1, 20)  # node i in rack i mod 20
    m = _model(svc)
    # seven blocks, block b on nodes b, 7 + b, 27 + b (racks b, 7 + b, 7 + b), but the last
    # on 28, 13, 33: 21 nodes and 14 racks hold a seventh of the input each (14.3% >= 14%),
    # node 28 and its rack 8 two sevenths
    inputs = tuple(
        (b, BLOCK, tuple(_node(n) for n in ((28, 13, 33) if b == 6 else (b, 7 + b, 27 + b))))
        for b in range(7)
    )
    api.submit_pod(PodEvent(pod_id="t", inputs=inputs))
    svc._admit_pods(drain(api, 1))
    t = _task(svc, "t")
    rid = svc.node_to_machine
    machines, racks = (28, 0, 7, 27, 1, 8, 2), (8, 0, 7, 1, 2, 9, 3)  # most first, then as met
    assert m.preferred_machines(t) == [rid[_node(n)] for n in machines]
    assert m.get_task_equiv_classes(t) == [CLUSTER_AGGREGATOR_EC] + [rack_ec(f"rack-{l}") for l in racks]
    routes = ref.Routes(inputs, {_node(i): i % 20 for i in range(40)})
    assert tuple(routes.machines) == tuple(_node(n) for n in machines) and tuple(routes.racks) == racks
    assert [m.task_to_resource_node_cost(t, rid[_node(n)]) for n in machines] == list(routes.machines.values())
    # total 448 MiB = 28 quanta: alpha 56; node 28: (128 - 128 + 2 * 320) // 16 = 40; rack 8: (128 + 640) // 16 = 48
    assert (routes.alpha, routes.machines[_node(28)], routes.racks[8], routes.racks[0]) == (56, 40, 48, 52)


def test_costs_stop_at_the_largest_cost_and_waiting_still_costs_more_than_any_route():
    svc, api = _service(4, 1, 2)
    m = _model(svc)
    inputs = tuple((b, 1024 * MB, (_node(0),)) for b in range(9))  # 9 GiB: alpha would be 1,152
    api.submit_pod(PodEvent(pod_id="t", inputs=inputs))
    svc._admit_pods(drain(api, 1))
    t = _task(svc, "t")
    assert m.largest_cost == 1023 == ref.LARGEST_COST
    assert m.task_to_equiv_class_aggregator(t, CLUSTER_AGGREGATOR_EC) == 1022
    assert m.task_to_unscheduled_agg_cost(t) == 1023
    routes = ref.Routes(inputs, {_node(i): i % 2 for i in range(4)})
    assert routes.alpha == 1022 and ref.unscheduled_cost(inputs, {_node(0): 0}) == 1023


def test_the_constants_are_the_references():
    c = QuincyCostModel
    assert (c.QUANTUM, c.PSI, c.XI, c.DELTA_PCT, c.MAX_PREFS, c.OMEGA, c.largest_cost) == (
        ref.QUANTUM, ref.PSI, ref.XI, ref.DELTA_PCT, ref.MAX_PREFS, ref.OMEGA, ref.LARGEST_COST,
    )
    # the claims the walks of PRs 25-36 need, said by the model itself
    assert c.__dict__["pinned_tasks_are_inert"] is True and c.__dict__["resource_arc_costs_are_fixed"] is True
    assert c.lists_task_preferences and c.routes_differ_in_cost
    assert not TrivialCostModel.lists_task_preferences and not TrivialCostModel.routes_differ_in_cost
    assert TrivialCostModel.largest_cost is None


# -- the graph: arcs of a task's own, and what a pin does to them --------------------


def test_a_pin_drops_every_preference_arc_and_the_counts_follow():
    s = Stream(24, 3, 4, 9)
    gm = s.svc.scheduler.gm
    s.round(8, 0, most_blocks=3)
    t = s.svc.scheduler.last_timing
    # every task is pinned: one arc, its running arc, to its PU
    assert gm.num_pinned == 8 == len(gm.task_to_running_arc)
    for node in gm.task_to_node.values():
        (arc,) = node.outgoing.values()
        assert arc.type == ArcType.RUNNING and (arc.cap_lower, arc.cap_upper) == (1, 1)
    assert gm.pref_arcs_live == 0
    # what the update added the pins took away again: each counted once
    assert t.pref_arcs_live > 8 and t.pref_arcs_changed == 2 * t.pref_arcs_live
    s.round(5, 2, most_blocks=3)
    t = s.svc.scheduler.last_timing
    assert gm.pref_arcs_live == 0 and t.pref_arcs_changed == 2 * t.pref_arcs_live > 0
    assert t.res_nodes_visited == 0 and t.stats_full_walk == 0 and t.graph_tasks_skipped == 6


def test_a_waiting_tasks_arcs_stay_and_a_full_machine_gets_none():
    svc, api = _service(4, 1, 2)
    m, gm = _model(svc), svc.scheduler.gm
    on_0 = ((1, BLOCK, (_node(0),)),)
    api.submit_pod(PodEvent(pod_id="a", inputs=on_0))
    svc.run_round(drain(api, 1))
    assert api.bindings() == {"a": _node(0)}
    # node 0 is full: the next pod that reads the block has no arc to it, only to its rack
    api.submit_pod(PodEvent(pod_id="b", inputs=on_0))
    svc.run_round(drain(api, 1))
    assert api.bindings()["b"] == _node(2)  # the other node of rack 0
    # asked again, the model says node 0 is preferred and lists no arc to it while it is full
    t = _task(svc, "b")
    assert m.preferred_machines(t) == [svc.node_to_machine[_node(0)]] and m.get_task_preference_arcs(t) == []
    assert svc.scheduler.last_timing.pref_arcs_live == 1  # the rack arc alone
    assert (svc.scheduler.last_timing.bound_via_machine, svc.scheduler.last_timing.bound_via_rack) == (0, 1)


def test_chain_arcs_carry_what_lies_below_them_and_never_bind():
    s = Stream(12, 2, 4, 10)
    s.round(5, 0)
    gm, m = s.svc.scheduler.gm, _model(s.svc)
    x = gm.task_ec_to_node[CLUSTER_AGGREGATOR_EC]
    assert len(x.outgoing) == 4 and all(a.dst_node.equiv_class is not None for a in x.outgoing.values())
    s.round(3, 2)  # the update reads the books as the first round left them
    for rack in range(4):
        node = gm.task_ec_to_node[rack_ec(f"rack-{rack}")]
        arc = gm.cm.graph.get_arc(x, node)
        below = {a.dst_node.resource_id: a.cap_upper for a in node.outgoing.values()}
        assert (arc.cost, arc.cap_upper) == (0, sum(below.values()))
        assert all(a.cost == 0 for a in node.outgoing.values())
    # X reaches machines through the racks alone
    assert all(a.dst_node.resource_id == 0 for a in x.outgoing.values())
    assert m.equiv_class_pref_arc_changes(CLUSTER_AGGREGATOR_EC) == []


# -- the registry forgets; the wait term saturates ---------------------------------------


def test_a_completed_pods_blocks_leave_the_registry_and_a_shared_block_stays_for_its_last_reader():
    svc, api = _service(8, 2, 4)
    m = _model(svc)
    shared = (7, BLOCK, (_node(1), _node(2)))
    api.submit_pod(PodEvent(pod_id="a", inputs=(shared, (8, BLOCK, (_node(3),)))))
    api.submit_pod(PodEvent(pod_id="b", inputs=(shared, (9, BLOCK, (_node(4),)))))
    svc.run_round(drain(api, 2))
    assert sorted(b for b in (7, 8, 9) if b in m.blocks) == [7, 8, 9] and len(m.blocks) == 3
    assert svc.complete_pod("a")
    assert 8 not in m.blocks and 7 in m.blocks and 9 in m.blocks
    assert svc.complete_pod("b")
    assert len(m.blocks) == 0 and m._reads == {} and m._inputs == {}
    # a killed task's too
    api.submit_pod(PodEvent(pod_id="c", inputs=((10, BLOCK, (_node(5),)),)))
    svc.run_round(drain(api, 1))
    svc.scheduler.kill_running_task(svc.pod_to_task["c"])
    assert len(m.blocks) == 0


def test_a_thousand_arrivals_and_completions_leave_the_registry_as_small_as_the_live_pods():
    s = Stream(24, 3, 4, 11)
    s.round(20, 0)
    for _ in range(25):
        s.round(8, 8, most_blocks=4)
    m = _model(s.svc)
    live = {b for p in s.bound for b, _size, _nodes in s.inputs[p]}
    assert set(m.blocks._locations) == live and set(m._reads) == {s.svc.pod_to_task[p] for p in s.bound}
    assert m._inputs == {} and m._wait_rounds == {}


def test_the_wait_term_stops_at_its_clamp():
    svc, api = _service(2, 1, 1)
    m = _model(svc)
    for pod in ("a", "b", "c"):
        api.submit_pod(PodEvent(pod_id=pod, inputs=((1, BLOCK, (_node(0),)),)))
    svc.run_round(drain(api, 3))
    (waiting,) = [p for p in ("a", "b", "c") if p not in api.bindings()]
    t = _task(svc, waiting)
    # alpha = 8: u = 9, then + 10 a round waited
    assert m.task_to_unscheduled_agg_cost(t) == 9 + 10
    for _ in range(4):
        svc.run_round([], solve=True)
    assert m.task_to_unscheduled_agg_cost(t) == 9 + 50
    m.note_round([t] * 1)  # one more
    for _ in range(500):
        m.note_round([t])
    assert m.task_to_unscheduled_agg_cost(t) == m.largest_cost == 1023
    assert m._wait_rounds[t] == m.largest_cost // m.OMEGA + 1  # and no further
    arc = next(a for a in svc.scheduler.gm.task_to_node[t].outgoing.values() if a.dst_node.job_id)
    svc.run_round([], solve=True)
    assert arc.cost == 1023


# -- what build_service refuses, and what it asks the rung for -------------------------


def _args(text):
    return cli.build_arg_parser().parse_args(text.split())


def test_build_service_refuses_a_largest_cost_that_cannot_fit_the_node_bucket():
    fits = _args("--fake-machines --num-machines 12500 --max-tasks-per-pu 12 --fake-racks 250 "
                 "--cost-model quincy --backend jax")
    cli.refuse_costs_that_cannot_fit(fits)  # 187,754 nodes -> 262,144: 1,023 x 262,144 < 2^28
    twice = _args("--fake-machines --num-machines 25000 --max-tasks-per-pu 12 --fake-racks 250 "
                  "--cost-model quincy --backend jax")
    with pytest.raises(ValueError, match=r"largest cost of 1023.*bucket of 524288.*is not"):
        cli.build_service(twice, BenchClusterAPI(pod_chan_size=10))
    # the CPU rungs scale nothing; a model that states no largest cost is not held to one
    cli.refuse_costs_that_cannot_fit(_args(
        "--fake-machines --num-machines 25000 --max-tasks-per-pu 12 --cost-model quincy --backend native"))
    cli.refuse_costs_that_cannot_fit(_args(
        "--fake-machines --num-machines 250000 --max-tasks-per-pu 12 --cost-model trivial --backend jax"))
    from ksched_tpu.solver.jax_solver import MAX_SCALED_PATH_COST

    assert MAX_SCALED_PATH_COST == 1 << 28


def test_the_scan_csr_rung_runs_its_price_update_for_this_model_alone():
    svc, _api = _service(4, 1, 2, backend="jax")
    assert svc.ladder.primary.price_update_every == 8
    args = _args("--fake-machines --num-machines 4 --cost-model trivial --backend jax")
    assert cli.build_service(args, BenchClusterAPI(pod_chan_size=10)).ladder.primary.price_update_every == 0


def test_two_pods_that_contend_for_one_slot_settle_in_a_few_supersteps():
    # without the price update the loser's unit crawls between the machine and the
    # task, a unit relabel a time, over the gap to its next route times the node count
    svc, api = _service(6, 1, 3, backend="jax")
    both = ((1, BLOCK, (_node(0),)), (2, BLOCK, (_node(0),)))
    api.submit_pod(PodEvent(pod_id="a", inputs=both))
    api.submit_pod(PodEvent(pod_id="b", inputs=both + ((3, BLOCK, (_node(4),)),)))
    svc.run_round(drain(api, 2))
    rung = svc.ladder.primary
    assert rung.last_supersteps < 64, rung.last_supersteps
    assert sorted(api.bindings().values()) in ([_node(0), _node(3)], [_node(0), _node(4)])
    assert svc.ladder.degradations_total == 0


# -- pod inputs and rack labels on the cluster API ---------------------------------------


def test_inputs_ride_a_hashable_event_and_reach_the_descriptor_and_the_registry():
    ev = PodEvent(pod_id="p", inputs=((5, BLOCK, (_node(1), "elsewhere")),))
    assert hash(ev) == hash(PodEvent(pod_id="p", inputs=((5, BLOCK, (_node(1), "elsewhere")),)))
    assert PodEvent(pod_id="p").inputs == ()
    svc, api = _service(4, 1, 2)
    api.submit_pod(ev)
    svc._admit_pods(drain(api, 1))
    td = svc.task_map.find(svc.pod_to_task["p"])
    assert [(d.id, d.size) for d in td.dependencies] == [(5, BLOCK)]
    # a replica on a node the service does not know is none here
    assert list(_model(svc).blocks.holders(5)) == [svc.node_to_machine[_node(1)]]
    # under a model that reads no input the event's inputs set nothing
    args = _args("--fake-machines --num-machines 2 --cost-model trivial --backend native")
    other = cli.build_service(args, BenchClusterAPI(pod_chan_size=10))
    other.init_topology(fake_machines=2)
    other._admit_pods([ev])
    assert other.task_map.find(other.pod_to_task["p"]).dependencies == []


def test_rack_labels_reach_the_model_from_fake_racks_and_from_node_events():
    svc, _api = _service(6, 1, 3)
    m = _model(svc)
    assert {svc.machine_to_node[k]: v for k, v in m._machine_rack.items()} == {
        _node(i): f"rack-{i % 3}" for i in range(6)
    }
    assert svc.resource_map.find(svc.node_to_machine[_node(4)]).descriptor.labels == {RACK_LABEL: "rack-1"}
    nodes = [NodeEvent(node_id=f"n{i}", labels=((RACK_LABEL, "east" if i < 2 else "west"),)) for i in range(3)]
    svc, _api = _service(0, 2, 0, nodes=nodes + [NodeEvent(node_id="bare")])
    m = _model(svc)
    assert sorted(m._rack_machines) == ["", "east", "west"]  # a node without the label: the rack ""
    assert m._rack_free == {"east": 4, "west": 2, "": 2}
    # --fake-racks beside --fake-zones: both labels, dealt the same way
    args = _args("--fake-machines --num-machines 4 --fake-racks 2 --fake-zones 3 --cost-model quincy")
    both = cli.build_service(args, BenchClusterAPI(pod_chan_size=10))
    both.init_topology(fake_machines=4)
    labels = both.resource_map.find(both.node_to_machine[_node(3)]).descriptor.labels
    assert labels == {RACK_LABEL: "rack-1", "topology.kubernetes.io/zone": "zone-0"}


def test_a_machine_that_leaves_takes_its_replicas_and_its_racks_room_with_it():
    s = Stream(8, 2, 4, 12)
    m = _model(s.svc)
    gone = s.svc.node_to_machine[_node(5)]
    s.api.submit_pod(PodEvent(pod_id="w", inputs=((1, BLOCK, (_node(5), _node(2))),)))
    s.svc._admit_pods(drain(s.api, 1))
    t = s.svc.pod_to_task["w"]
    assert m.preferred_machines(t) == [gone, s.svc.node_to_machine[_node(2)]]
    m.remove_machine(gone)
    assert m.preferred_machines(t) == [s.svc.node_to_machine[_node(2)]]
    assert m._rack_free["rack-1"] == 2 and list(m.blocks.holders(1)) == [s.svc.node_to_machine[_node(2)]]


# -- through try_collapse, the dense rung ---------------------------------------------------


def test_through_try_collapse_the_dense_rung_gives_the_same_objective():
    s = Stream(24, 3, 4, 13, backend="auto")
    rung = s.svc.ladder.primary
    # fewer pods than the emptiest rack has free slots: no chain arc could bind
    objective, served, want, native = s.round(6, 0)
    assert rung.last_path == "dense" and rung.last_refusal == ""
    assert objective == served == want == native
    objective, served, want, native = s.round(5, 2)
    assert rung.last_path == "dense" and objective == served == want == native
    # more pods than a rack has room for: a conservative refusal, and the general rung answers
    objective, served, want, native = s.round(25, 0, most_blocks=2)
    assert rung.last_path == "csr" and "chain arc cap" in rung.last_refusal
    assert objective == served == want == native
    s.holds_the_guarantee()


# -- spans and counters -------------------------------------------------------------------


def test_the_round_record_and_the_span_say_what_the_preference_turn_did():
    tracer, spans = RoundTracer(), SpanTracer().install()
    try:
        s = Stream(24, 3, 4, 14, tracer=tracer, span_tracer=spans)
        s.round(10, 0)
        s.round(6, 4)
    finally:
        spans.uninstall()
    rec = tracer.records[-1]
    t = s.svc.scheduler.last_timing
    assert rec.pref_arcs_live == t.pref_arcs_live > 6 and rec.pref_arcs_changed == 2 * rec.pref_arcs_live
    assert rec.bound_via_machine + rec.bound_via_rack + rec.bound_via_cluster == 6 == rec.num_scheduled
    assert rec.bound_on_preferred_share == 100.0 * (rec.bound_via_machine + rec.bound_via_rack) / 6
    # the bytes the round's pods read from another machine than theirs, of what they read
    read = sum(size for p in list(s.bound)[-6:] for _b, size, _n in s.inputs[p])
    remote = sum(
        size for p in list(s.bound)[-6:] for _b, size, nodes in s.inputs[p] if s.bound[p] not in nodes
    )
    assert rec.remote_bytes_share == pytest.approx(100.0 * remote / read)
    facts = s.holds_the_guarantee()
    assert facts["bound_via"] == [
        sum(getattr(r, f) for r in tracer.records)
        for f in ("bound_via_machine", "bound_via_rack", "bound_via_cluster")
    ]
    events = spans.events()
    turns = [e for e in events if e["name"] == "pref_refresh"]
    runs = [e for e in events if e["name"] == "task_refresh"]
    assert len(turns) == 16  # one a task turn, inside a run of task turns
    for e in turns:
        assert any(r["ts"] <= e["ts"] and e["ts"] + e["dur"] <= r["ts"] + r["dur"] + 1 for r in runs)
    # a model that lists no arc of a task's own opens none
    assert not TrivialCostModel.lists_task_preferences


# -- the reference's own arithmetic -----------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_transport_is_the_brute_force_optimum(seed):
    rng = np.random.default_rng(seed)
    rows, cols = int(rng.integers(2, 6)), int(rng.integers(2, 5))
    cost = rng.integers(0, 30, (rows, cols))
    capacity = rng.integers(0, 3, cols)
    capacity[int(rng.integers(0, cols))] += rows  # room for every row
    best = min(
        sum(int(cost[r, c]) for r, c in enumerate(choice))
        for choice in itertools.product(range(cols), repeat=rows)
        if all(choice.count(c) <= capacity[c] for c in range(cols))
    )
    assert ref.transport(cost, capacity) == best


def test_reference_round_names_machines_aggregates_the_rest_and_says_when_pods_do_not_fit():
    rack_of = {_node(i): i % 2 for i in range(6)}
    on_0 = ref.Routes(((1, BLOCK, (_node(0),)),), rack_of)  # alpha 8, d(0) 0, rho(rack 0) 4
    free = {_node(i): 1 for i in range(6)}
    assert ref.reference_round(free, [on_0], rack_of) == 0
    assert ref.reference_round(free, [on_0, on_0], rack_of) == 4  # the second through the rack
    assert ref.reference_round(free, [on_0] * 4, rack_of) == 0 + 4 + 4 + 8  # rack 0 has three nodes
    assert ref.reference_round({**free, _node(0): 0}, [on_0], rack_of) == 4
    nothing = ref.Routes((), rack_of)
    assert ref.reference_round(free, [nothing] * 6, rack_of) == 0
    assert ref.reference_round(free, [nothing] * 7, rack_of) is None
    # a pod that reads nothing takes what is left: the reader keeps its node
    assert ref.reference_round(free, [nothing] * 5 + [on_0], rack_of) == 0


def test_the_replay_gives_a_completed_pods_slot_to_the_round_after_the_next():
    rack_of = {_node(i): i % 2 for i in range(4)}
    on_0 = ((1, BLOCK, (_node(0),)),)
    inputs = {"a": on_0, "b": on_0, "c": on_0}
    log = [
        ("bind", "a", _node(0), 1.0),
        ("done", "a", "", 1.5),
        ("bind", "b", _node(2), 2.0),  # node 0 still counts as taken: the rack is the optimum
        ("bind", "c", _node(0), 3.0),  # free now
    ]
    faults, facts = ref.check_data_locality(log, inputs, rack_of, 1)
    assert faults == [] and (facts["served_cost"], facts["optimum_cost"]) == (4, 4)
    # the same record with b sent across the core switch though its rack had room
    log[2] = ("bind", "b", _node(1), 2.0)
    faults, _ = ref.check_data_locality(log, inputs, rack_of, 1)
    assert len(faults) == 1 and "cost 8 by their cheapest routes, the optimum of the round is 4" in faults[0]
    # and with a pod left waiting while slots were free
    faults, _ = ref.check_data_locality(log[:1], inputs, rack_of, 1, admitted=[(0.5, 2)])
    assert len(faults) == 1 and "1 pods waited after a round that bound 1 with 4 slots free" in faults[0]
