"""The pin router of `try_collapse` works on arrays (PR 47).

The `audit_pins` pass routes the folded pinned units of every resource
node to the sink before the machines' capacities are read. Until PR 47 it
was one recursive Python walk a node with pins; now the nodes whose every
arc ends at the sink (the PUs of a preemption-off service) are routed all
at once, a cumulative sum over their segments of the interior CSR, and a
pin on any other node falls to the walk, after them. Held here, on seeded
random machine trees: with pins on leaves only, the per-arc pinned flow
and the capacities the pass leaves are those of a plain recursive walk
written below, independent of the module; with pins on interior nodes as
well, the dense rung's objective and task -> machine choices are the
reference solver's; every refusal keeps its sentence; `walked` counts the
nodes that fell to the walk; a problem with no folded pin leaves
`pre_flows` empty.
"""

import random

import numpy as np
import pytest

from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.graph.device_export import FlowProblem
from ksched_tpu.graph.flowgraph import NodeType
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.graph_collapse import AutoSolver, try_collapse
from ksched_tpu.utils import seed_rng

from test_export_forced_supply import plain_fold

T = NodeType
SINK, AGG = 1, 2
#: the node types of a machine's levels, by the depth of its tree
LEVELS = {1: [T.PU], 2: [T.CORE, T.PU], 3: [T.SOCKET, T.CORE, T.PU],
          4: [T.NUMA, T.SOCKET, T.CORE, T.PU]}
RESOURCES = {int(t) for t in (T.MACHINE, T.NUMA, T.SOCKET, T.CACHE, T.CORE, T.PU)}


class _Tree:
    """A problem under construction: node types, arcs and excesses by
    node id (ids from 1), with the children of every resource node."""

    def __init__(self):
        self.types = {SINK: T.SINK, AGG: T.JOB_AGGREGATOR}
        self.arcs = []  # (src, dst, cap, cost)
        self.excess = {}
        self.kids = {}  # resource node -> [(arc index, child or SINK)]

    def node(self, ntype):
        v = len(self.types) + 1
        self.types[v] = ntype
        return v

    def arc(self, s, d, cap, cost=0):
        self.arcs.append((s, d, cap, cost))
        if int(self.types[s]) in RESOURCES:
            self.kids.setdefault(s, []).append((len(self.arcs) - 1, d))
        return len(self.arcs) - 1

    def problem(self):
        n = len(self.types) + 1
        nt = np.full(n, -1, np.int8)
        for v, t in self.types.items():
            nt[v] = int(t)
        ex = np.zeros(n, np.int64)
        for v, e in self.excess.items():
            ex[v] = e
        ex[SINK] = -int(ex.sum())
        cols = list(zip(*self.arcs))
        return FlowProblem(
            num_nodes=n, excess=ex, node_type=nt,
            src=np.array(cols[0], np.int32), dst=np.array(cols[1], np.int32),
            cap=np.array(cols[2], np.int32), cost=np.array(cols[3], np.int32),
            flow_offset=np.zeros(len(self.arcs), np.int32), num_arcs=len(self.arcs),
        )


def _grow(tree, rng, parent, levels, path_cost):
    """The subtree below `parent`, ids in pre-order (an interior node
    before its leaves). A leaf gets one to three sink arcs of differing
    caps; an arc into a subtree either holds all of it or binds.
    Returns what the subtree can pass to the sink."""
    total = 0
    for _ in range(rng.randint(1, 3)):
        v = tree.node(levels[0])
        if len(levels) == 1:
            caps = rng.sample([1, 2, 3, 4], rng.randint(1, 3))
            for c in caps:
                tree.arc(v, SINK, c, path_cost)
            below = sum(caps)
        else:
            below = _grow(tree, rng, v, levels[1:], path_cost)
        cap = below if rng.random() < 0.5 else rng.randint(1, below)
        tree.arc(parent, v, cap)
        total += min(cap, below)
    return total


def _room(tree, cap_res, v):
    """What `v` can still pass to the sink: the exact tree max-flow."""
    if v == SINK:
        return 1 << 30
    return sum(min(cap_res[a], _room(tree, cap_res, d)) for a, d in sorted(tree.kids.get(v, [])))


def _walk(tree, cap_res, flow, v, units):
    """The plain walk: greedy, arcs in ascending order, depth first."""
    routed = 0
    for a, d in sorted(tree.kids.get(v, [])):
        if not units:
            break
        take = min(units, cap_res[a])
        if d != SINK:
            take = _walk(tree, cap_res, flow, d, take)
        cap_res[a] -= take
        flow[a] += take
        units -= take
        routed += take
    return routed


def _cluster(seed, interior_pins):
    """Two to four machines with trees of depth 1-4, pins on most leaves
    (and on some machines and interior nodes if asked), three waiting
    tasks with an arc to every machine. Returns the tree, its machines
    and tasks, and the walk's residual caps and per-arc pinned flow."""
    rng = random.Random(seed)
    tree = _Tree()
    tasks = [tree.node(T.UNSCHEDULED_TASK) for _ in range(3)]
    machines = []
    for _ in range(rng.randint(2, 4)):
        m = tree.node(T.MACHINE)
        machines.append(m)
        _grow(tree, rng, m, LEVELS[rng.randint(1, 4)], path_cost=rng.randint(0, 5))
    tree.arc(AGG, SINK, len(tasks))
    prices = rng.sample(range(1, 5000), len(tasks) * len(machines))
    for t in tasks:
        tree.excess[t] = 1
        tree.arc(t, AGG, 1, 9000)
        for m in machines:
            tree.arc(t, m, 1, prices.pop())
    cap_res = [a[2] for a in tree.arcs]
    flow = [0] * len(tree.arcs)
    nodes = sorted(v for v in tree.kids if v != AGG)
    leaves = [v for v in nodes if all(d == SINK for _a, d in tree.kids[v])]
    for v in leaves:
        if rng.random() < 0.8:
            tree.excess[v] = rng.randint(1, _room(tree, cap_res, v))
            assert _walk(tree, cap_res, flow, v, tree.excess[v]) == tree.excess[v]
    if interior_pins:
        for v in nodes:
            room = _room(tree, cap_res, v)
            wanted = rng.random() < 0.6 or not set(tree.excess) - set(leaves) - set(tasks)
            if v not in leaves and room and wanted:
                tree.excess[v] = rng.randint(1, room)
                assert _walk(tree, cap_res, flow, v, tree.excess[v]) == tree.excess[v]
    return tree, machines, tasks, cap_res, flow


def _audit(problem):
    """try_collapse under a tracer: (collapse, reason, the pins span's args)."""
    with SpanTracer() as tracer:
        gc, reason = try_collapse(problem)
    (pins,) = [e["args"] for e in tracer.events() if e["name"] == "audit_pins"]
    return gc, reason, pins


def _pinned_flow(gc, arcs):
    flow = np.zeros(arcs, np.int64)
    np.add.at(flow, *gc.pre_flows)
    return flow


@pytest.mark.parametrize("seed", range(12))
def test_pins_on_leaves_are_routed_as_the_plain_walk_routes_them(seed):
    tree, machines, _tasks, cap_res, flow = _cluster(seed, interior_pins=False)
    gc, reason, pins = _audit(tree.problem())
    assert gc is not None, reason
    arc_ids, units = gc.pre_flows
    assert arc_ids.dtype == units.dtype == np.int64 and (units > 0).all()
    assert len(set(arc_ids.tolist())) == len(arc_ids)  # a leaf's arcs are its own
    assert _pinned_flow(gc, len(tree.arcs)).tolist() == flow
    # what the pass left of the caps: each machine's exact tree max-flow
    assert gc.machine_node.tolist() == machines
    assert gc.col_cap.tolist() == [_room(tree, cap_res, m) for m in machines]
    assert (pins["pins"], pins["walked"]) == (sum(f > 0 for f in flow), 0)


@pytest.mark.parametrize("seed", range(12))
def test_with_pins_on_interior_nodes_the_dense_rung_answers_as_the_reference(seed):
    tree, machines, tasks, cap_res, _flow = _cluster(100 + seed, interior_pins=True)
    problem = tree.problem()
    leaves = {v for v, kids in tree.kids.items() if all(d == SINK for _a, d in kids)}
    interior = [v for v in tree.excess if v not in leaves and v not in tasks]
    assert interior
    gc, reason, pins = _audit(problem)
    assert gc is not None, reason
    assert pins["walked"] == len(interior)
    # every folded unit reaches the sink, and the machines keep what is left
    pinned = _pinned_flow(gc, len(tree.arcs))
    into_sink = sum(int(pinned[a]) for a, arc in enumerate(tree.arcs) if arc[1] == SINK)
    assert into_sink == sum(e for v, e in tree.excess.items() if v not in tasks)
    assert gc.col_cap.tolist() == [_room(tree, cap_res, m) for m in machines]

    auto = AutoSolver(ReferenceSolver())
    got, want = auto.solve(problem), ReferenceSolver().solve(problem)
    assert auto.last_path == "dense"
    assert int(got.objective) == int(want.objective)

    def bindings(result):
        return {
            t: [arc[1] for a, arc in enumerate(tree.arcs) if arc[0] == t and result.flow[a]]
            for t in tasks
        }

    assert bindings(got) == bindings(want)  # the prices differ, so one optimum


def _two_pus(caps_a, caps_b, excess):
    """One machine over two PUs with the given sink arcs; returns the
    problem and the ids (machine, PU a, PU b)."""
    tree = _Tree()
    t = tree.node(T.UNSCHEDULED_TASK)
    m = tree.node(T.MACHINE)
    a, b = tree.node(T.PU), tree.node(T.PU)
    tree.excess[t] = 1
    tree.arc(t, AGG, 1, 7)
    tree.arc(AGG, SINK, 1)
    tree.arc(t, m, 1, 2)
    for pu, caps in ((a, caps_a), (b, caps_b)):
        tree.arc(m, pu, 8)
        for c in caps:
            tree.arc(pu, SINK, c)
    for v, e in zip((m, a, b), excess):
        if e:
            tree.excess[v] = e
    return tree.problem(), (m, a, b)


REFUSALS = {
    # sink caps of PU a, of PU b, excess of (machine, a, b), who is named
    "a_leaf_over_its_capacity": ([2, 1], [4], (0, 4, 1), "a"),
    "a_pinned_leaf_with_no_live_arc": ([3], [0], (0, 1, 1), "b"),
    "of_two_failing_leaves_the_lower_id": ([1], [1, 1], (0, 2, 3), "a"),
    "an_interior_node_over_what_its_leaves_left": ([2], [2], (3, 1, 1), "m"),
    # leaves are refused before any node is walked, whatever the ids
    "a_failing_leaf_before_a_failing_interior_node": ([2], [2], (9, 1, 3), "b"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_a_pin_that_cannot_be_routed_refuses_with_the_sentence_it_always_had(case):
    caps_a, caps_b, excess, who = REFUSALS[case]
    problem, ids = _two_pus(caps_a, caps_b, excess)
    gc, reason, _pins = _audit(problem)
    named = dict(zip("mab", ids))[who]
    assert gc is None
    assert reason == f"resource {named}: folded pinned units exceed capacity"


def test_a_leaf_fills_its_sink_arcs_in_ascending_order_and_stops_when_served():
    problem, (m, a, b) = _two_pus([2, 0, 3, 5], [4], (0, 4, 0))
    gc, reason, pins = _audit(problem)
    assert gc is not None, reason
    src, cap = problem.src.tolist(), problem.cap.tolist()
    mine = [i for i, s in enumerate(src) if s == a]
    assert [cap[i] for i in mine] == [2, 0, 3, 5]
    # 2 of the first, the dead arc skipped, 2 of the third, none of the last
    assert list(zip(*[x.tolist() for x in gc.pre_flows])) == [(mine[0], 2), (mine[2], 2)]
    assert gc.col_cap.tolist() == [1 + 5 + 4]
    assert (pins["pins"], pins["walked"]) == (2, 0)


def test_a_preemption_on_problem_has_no_folded_pin_and_costs_the_pass_nothing():
    seed_rng(11)
    sched, _rmap, jmap, tmap, _root = build_cluster(
        num_machines=3, num_cores=2, pus_per_core=2, max_tasks_per_pu=2,
        backend=ReferenceSolver(), preemption=True,
    )
    for n in (5, 4):
        add_job(sched, jmap, tmap, num_tasks=n)
        sched.schedule_all_jobs()
    problem = sched.solver.state.problem()
    resources = np.isin(problem.node_type, sorted(RESOURCES))
    assert not (problem.excess[resources] > 0).any()  # running tasks keep their arcs
    gc, reason, pins = _audit(problem)
    assert gc is None and "leaf/keep-mode" in reason  # refused two passes later, as before
    assert (pins["pins"], pins["walked"]) == (0, 0)


def test_a_problem_with_no_running_task_leaves_pre_flows_empty():
    problem, _ids = _two_pus([2], [2], (0, 0, 0))
    gc, reason, pins = _audit(problem)
    assert gc is not None, reason
    assert [x.tolist() for x in gc.pre_flows] == [[], []]
    assert all(x.dtype == np.int64 for x in gc.pre_flows)
    assert (pins["pins"], pins["walked"]) == (0, 0)
    res = AutoSolver(ReferenceSolver()).solve(problem)
    assert int(res.objective) == int(ReferenceSolver().solve(problem).objective) == 2


def test_a_served_cluster_pins_leaves_only_and_binds_as_the_reference_does():
    def run(backend):
        seed_rng(11)
        sched, _rmap, jmap, tmap, _root = build_cluster(
            num_machines=3, num_cores=2, pus_per_core=2, max_tasks_per_pu=2, backend=backend,
        )
        out = []
        for n in (5, 4, 6):
            add_job(sched, jmap, tmap, num_tasks=n)
            busy = len(set(sched.get_task_bindings().values()))  # PUs that hold a pod
            placed, _ = sched.schedule_all_jobs()
            out.append((placed, int(sched.solver.last_result.objective), busy))
        return sched, out

    auto = AutoSolver(ReferenceSolver())
    with SpanTracer() as tracer:
        sched, got = run(auto)
    ref, want = run(ReferenceSolver())
    assert [r[:2] for r in got] == [r[:2] for r in want] and auto.last_path == "dense"
    assert len(sched.get_task_bindings()) == len(ref.get_task_bindings()) == 15
    pins = [e["args"] for e in tracer.events() if e["name"] == "audit_pins"]
    assert [p["walked"] for p in pins] == [0, 0, 0]
    # since PR 52 the export has routed a PU's pins over its one sink arc
    # before the audit looks (`DeviceGraphState.routed`): the audit finds
    # no resource node with excess, and the export's span says how many
    # units it holds, the pods bound so far
    assert [p["pins"] for p in pins] == [0, 0, 0]
    held = [e["args"]["supply_prerouted"] for e in tracer.events() if e["name"] == "graph_export"]
    assert held == [0, 5, 9] and len(sched.get_task_bindings()) == 15
    # the audit routes the same pins, a record a PU that holds pods, where
    # a problem comes with them on the PUs (the fold of lower bounds alone)
    problem = sched.solver.state.problem()
    plain = plain_fold(sched.solver.state)
    # as the last round's export left it: nine pins on their PUs, six pods that wait
    assert (plain.total_supply, problem.total_supply) == (15, 6)
    busy = int(((plain.excess > 0) & (plain.node_type == int(T.PU))).sum())
    assert not ((problem.excess > 0) & (problem.node_type == int(T.PU))).any()
    with SpanTracer() as again:
        collapse, _why = try_collapse(plain)
    assert collapse is not None
    (args,) = [e["args"] for e in again.events() if e["name"] == "audit_pins"]
    assert (args["walked"], args["pins"]) == (0, busy) and busy >= 2

