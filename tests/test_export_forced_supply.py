"""The export routes a leaf's folded supply over the leaf's one arc (PR 52).

A node whose only live out-arc ends at the sink (the PUs of every served
graph) can send the supply the lower-bound fold left on it one way only.
`DeviceGraphState` keeps that amount, up to the arc's room, as a second
folded lower bound of the arc (`routed`): the leaf's folded excess falls by
it, the sink's rises by it, the arc's folded `cap` falls by it and its
`flow_offset` rises by it, so that the solver is handed a problem in which
no PU holds excess. Held here:

- against the parent's view, written below from the raw arrays alone
  (`plain_fold`: arc lower bounds folded, nothing else) and a from-scratch
  count of what is forced (`forced`): over a journal of binds, completions,
  evictions, a PU -> sink arc changing cost and capacity, a slot freed and
  reused, a second way out appearing and going, node and arc growth, the
  incrementally kept `routed` / `fold` / folded `cap` / `flow_offset` equal
  both that and a state rebuilt from the graph, arc for arc;
- a node with two out-arcs, a node whose arc does not end at the sink and
  supply past the arc's capacity keep what is not forced;
- `total_flow`, `objective` and `flow_to_mapping`'s result on the routed
  problem are the plain one's, for `ref`, `native`, `jax` and `auto`, on the
  `PROBLEMS` of tests/test_csr_entry_state.py sent through the export; on
  served rounds of tests/test_k8s_requests_model.py's `Stream`, where many
  machines cost the same and a solver may choose among them otherwise from
  another start, the objective, the flow's cost and feasibility in the graph
  itself, and the number of pods mapped;
- a round that follows a bind, a completion or an eviction is never `warm`,
  and a `warm` round starts balanced at the PUs, on the host path and on
  `--device-resident`, whose mirror passes `parity_check` after every round.
"""

from types import SimpleNamespace as _Row

import numpy as np
import pytest

from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.graph.changes import (
    AddNodeChange, ArcType, ChangeArcChange, NewArcChange, RemoveNodeChange,
)
from ksched_tpu.graph.device_export import DeviceGraphState, DeviceResidentState, FlowProblem
from ksched_tpu.graph.flowgraph import FlowGraph, NodeType
from ksched_tpu.solver.decode import flow_to_mapping
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng

# ---------------------------------------------------------------------------
# the parent's view and what is forced, from the raw arrays alone
# ---------------------------------------------------------------------------


def plain_fold(st):
    """The problem the parent's export made of this state: arc lower
    bounds folded into the excess by a scatter, and nothing else."""
    low = st.low.astype(np.int64)
    excess = st.excess.copy()
    np.subtract.at(excess, st.src, low)
    np.add.at(excess, st.dst, low)
    return FlowProblem(
        num_nodes=st.n_cap, excess=excess, node_type=st.node_type.copy(),
        src=st.src.copy(), dst=st.dst.copy(), cap=st.cap - st.low, cost=st.cost.copy(),
        flow_offset=st.low.copy(), num_arcs=st._num_slots,
    )


def forced(st):
    """int64[m_cap]: what each slot must carry beyond its lower bound,
    counted arc by arc: the plain folded supply of a node whose one live
    out-arc ends at the sink, up to the arc's room."""
    plain = plain_fold(st)
    sinks = np.flatnonzero(st.node_type == int(NodeType.SINK))
    outs = {}
    for (s, _d), slot in st._arc_slot.items():
        outs.setdefault(s, []).append(slot)
    want = np.zeros(st.m_cap, np.int64)
    for node, slots in outs.items():
        if len(slots) == 1 and len(sinks) == 1 and st.dst[slots[0]] == sinks[0]:
            want[slots[0]] = min(max(int(plain.excess[node]), 0), int(plain.cap[slots[0]]))
    return want


def check_view(st):
    """The kept state equals the from-scratch count, the folded view is
    the plain one with `routed` moved, and the supply sums as it did."""
    plain, want = plain_fold(st), forced(st)
    assert np.array_equal(st.routed, want)
    assert st.supply_prerouted == int(want.sum())
    degree = np.bincount([s for s, _d in st._arc_slot], minlength=st.n_cap)
    assert np.array_equal(st.out_deg, degree)
    ones = np.flatnonzero(degree == 1)
    by_src = {s: slot for (s, _d), slot in st._arc_slot.items()}
    assert [int(st.out_sum[v]) for v in ones] == [by_src[int(v)] for v in ones]
    excess = plain.excess.copy()
    np.subtract.at(excess, st.src, want)
    if want.any():
        excess[st.sink] += want.sum()
    problem = st.problem()
    assert np.array_equal(problem.excess, excess)
    assert np.array_equal(st.excess + st.fold, excess)
    assert np.array_equal(problem.cap, plain.cap - want) and (problem.cap >= 0).all()
    assert np.array_equal(problem.flow_offset, plain.flow_offset + want)
    assert problem.flow_offset.dtype == problem.cap.dtype == np.int32
    for name in ("src", "dst", "cost", "node_type"):
        assert np.array_equal(getattr(problem, name), getattr(plain, name)), name
    assert int(problem.excess.sum()) == int(plain.excess.sum()) == int(st.excess.sum())
    return problem, plain


def _by_arc(st, values):
    return {arc: int(values[slot]) for arc, slot in st._arc_slot.items()}


class _GraphOf:
    """What `full_build` reads of a graph, from a state's raw arrays."""

    def __init__(self, st):
        live = sorted(st._arc_slot.items())
        self.max_node_id, self.num_arcs = st.num_nodes, len(live)
        self._nodes = [
            _Row(id=int(v), excess=int(st.excess[v]), type=int(st.node_type[v]))
            for v in np.flatnonzero(st.node_type >= 0)
        ]
        self._arcs = [
            _Row(src=s, dst=d, cap_lower=int(st.low[k]), cap_upper=int(st.cap[k]), cost=int(st.cost[k]))
            for (s, d), k in live
        ]

    def nodes(self):
        return self._nodes

    def arcs(self):
        return self._arcs


def check_against_rebuild(st, graph=None):
    """A state built whole from the graph holds the same folded view, arc
    for arc and node for node (its slots are its own)."""
    rebuilt = DeviceGraphState()
    rebuilt.full_build(graph if graph is not None else _GraphOf(st))
    assert set(rebuilt._arc_slot) == set(st._arc_slot)
    mine, theirs = st.problem(), rebuilt.problem()
    for values, others in (
        (st.routed, rebuilt.routed), (mine.cap, theirs.cap), (mine.flow_offset, theirs.flow_offset),
        (mine.cost, theirs.cost),
    ):
        assert _by_arc(st, values) == _by_arc(rebuilt, others)
    n = min(st.n_cap, rebuilt.n_cap)
    assert np.array_equal(mine.excess[:n], theirs.excess[:n])
    assert not mine.excess[n:].any() and not theirs.excess[n:].any()
    assert np.array_equal(st.fold[:n], rebuilt.fold[:n])
    assert (st.supply_prerouted, st.sink) == (rebuilt.supply_prerouted, rebuilt.sink)


# ---------------------------------------------------------------------------
# a journal, record by record
# ---------------------------------------------------------------------------

SLOTS = 3  # a PU -> sink arc's capacity


def _cluster(machines=3, pus=2, tasks=8):
    """sink <- PUs <- machines <- tasks, as a state and the ids."""
    g = FlowGraph()
    sink = g.add_node()
    sink.type = NodeType.SINK
    ms, ps = [], []
    for _ in range(machines):
        m = g.add_node()
        m.type = NodeType.MACHINE
        ms.append(m.id)
        for _ in range(pus):
            p = g.add_node()
            p.type = NodeType.PU
            g.change_arc(g.add_arc(m, p), 0, SLOTS, 0)
            g.change_arc(g.add_arc(p, sink), 0, SLOTS, 1)
            ps.append(p.id)
    ts = []
    for i in range(tasks):
        t = g.add_node()
        t.type = NodeType.UNSCHEDULED_TASK
        t.excess = 1
        for m in ms:
            g.change_arc(g.add_arc(t, g.node(m)), 0, 1, 2 + (i + m) % 5)
        ts.append(t.id)
    sink.excess = -tasks
    st = DeviceGraphState()
    st.full_build(g)
    return st, sink.id, ms, ps, ts


def _arc(st, s, d, low, cap, cost):
    new = (s, d) not in st._arc_slot
    kind = NewArcChange if new else ChangeArcChange
    args = (s, d, low, cap, cost, ArcType.OTHER) + (() if new else (0,))
    st.apply_changes([kind(*args)])


def _kill(st, s, d):
    st.apply_changes([ChangeArcChange(s, d, 0, 0, 0, ArcType.OTHER, 0)])


def _bind(st, t, pu):
    for (s, d) in [k for k in st._arc_slot if k[0] == t]:
        _kill(st, s, d)
    _arc(st, t, pu, 1, 1, 0)


def _step(st):
    check_view(st)
    check_against_rebuild(st)


def test_the_kept_view_equals_the_count_and_the_rebuild_over_a_journal():
    st, sink, ms, ps, ts = _cluster()
    assert st.sink == sink and st.supply_prerouted == 0
    _step(st)
    # binds: each moves one unit onto its PU's sink arc and touches that
    # slot, the PU and the sink beside the task's own arcs
    st.drain_dirty()
    _bind(st, ts[0], ps[0])
    slots, nodes = st.drain_dirty()
    assert st._arc_slot[(ps[0], sink)] in slots and {ps[0], sink, ts[0]} <= set(nodes.tolist())
    assert st.supply_prerouted == 1
    _step(st)
    for t, p in ((ts[1], ps[0]), (ts[2], ps[0]), (ts[3], ps[3]), (ts[4], ps[5])):
        _bind(st, t, p)
        _step(st)
    assert st.supply_prerouted == 5 and st.routed[st._arc_slot[(ps[0], sink)]] == 3
    assert st.problem().total_supply == 3  # the tasks that wait; no PU holds a unit
    # the arc's cost moves: nothing else does
    before = st.problem()
    _arc(st, ps[0], sink, 0, SLOTS, 7)
    _step(st)
    assert np.array_equal(st.problem().excess, before.excess) and st.supply_prerouted == 5
    # its capacity falls below what is pinned there: the rest stays the PU's
    _arc(st, ps[0], sink, 0, 2, 7)
    _step(st)
    problem = st.problem()
    assert (problem.excess[ps[0]], problem.cap[st._arc_slot[(ps[0], sink)]]) == (1, 0)
    assert st.supply_prerouted == 4
    _arc(st, ps[0], sink, 0, SLOTS, 7)
    _step(st)
    assert st.problem().excess[ps[0]] == 0 and st.supply_prerouted == 5
    # a completion (the pin's arc and the task go) and an eviction (the
    # pin's arc goes, the task's arcs come back)
    _kill(st, ts[1], ps[0])
    st.apply_changes([RemoveNodeChange(ts[1])])
    st.set_excess(sink, -7)
    _step(st)
    _kill(st, ts[3], ps[3])
    for m in ms:
        _arc(st, ts[3], m, 0, 1, 4)
    _step(st)
    assert st.supply_prerouted == 3 and st.routed[st._arc_slot[(ps[3], sink)]] == 0
    # the sink arc is freed and its slot reused by another arc; it comes
    # back in a slot of its own
    slot = st._arc_slot[(ps[5], sink)]
    _kill(st, ps[5], sink)
    assert st.routed[slot] == 0 and st.problem().excess[ps[5]] == 1
    _step(st)
    _arc(st, ts[5], ps[1], 0, 1, 9)
    assert st._arc_slot[(ts[5], ps[1])] == slot and st.routed[slot] == 0
    _step(st)
    _arc(st, ps[5], sink, 0, SLOTS, 1)
    _step(st)
    assert st.problem().excess[ps[5]] == 0 and st.supply_prerouted == 3
    # a second way out of a PU: nothing of its supply is forced; it goes
    # again and the supply is routed again
    _arc(st, ps[0], ps[1], 0, 1, 0)
    _step(st)
    assert st.problem().excess[ps[0]] == 2 and st.supply_prerouted == 1
    _kill(st, ps[0], ps[1])
    _step(st)
    assert st.problem().excess[ps[0]] == 0 and st.supply_prerouted == 3
    # a pin on a machine stays the solver's: two ways out, none the sink
    _bind(st, ts[6], ms[1])
    _step(st)
    assert st.problem().excess[ms[1]] == 1 and st.supply_prerouted == 3
    # nodes and arcs past their buckets: the view is carried
    n_cap, m_cap = st.n_cap, st.m_cap
    grown = []
    node = st.num_nodes
    while st.n_cap == n_cap or st.m_cap == m_cap:
        node += 1
        st.apply_changes([AddNodeChange(node, 1, NodeType.UNSCHEDULED_TASK)])
        for m in ms:
            _arc(st, node, m, 0, 1, 3)
        grown.append(node)
    st.set_excess(sink, -7 - len(grown))
    _step(st)
    assert st.supply_prerouted == 3 and st.n_cap > n_cap and st.m_cap > m_cap
    _bind(st, grown[-1], ps[2])
    _step(st)
    assert st.supply_prerouted == 4 and st.problem().excess[ps[2]] == 0


def test_only_what_is_forced_is_routed():
    """A leaf, a node with two ways out, a node whose one arc ends short of
    the sink, and a leaf over its arc's capacity, side by side."""
    g = FlowGraph()
    sink = g.add_node()
    sink.type = NodeType.SINK
    leaf, fork, inner, over, t = (g.add_node() for _ in range(5))
    for n in (leaf, fork, inner, over):
        n.type = NodeType.PU
    t.type = NodeType.UNSCHEDULED_TASK
    leaf.excess, fork.excess, inner.excess, over.excess, sink.excess = 2, 2, 2, 5, -11
    g.change_arc(g.add_arc(leaf, sink), 0, 4, 1)
    g.change_arc(g.add_arc(fork, sink), 0, 4, 1)
    g.change_arc(g.add_arc(fork, leaf), 0, 1, 0)
    g.change_arc(g.add_arc(inner, leaf), 0, 2, 0)
    g.change_arc(g.add_arc(over, sink), 1, 4, 3)  # one of its four is a lower bound
    st = DeviceGraphState()
    st.full_build(g)
    problem, plain = check_view(st)
    check_against_rebuild(st, g)
    held = _by_arc(st, st.routed)
    assert held == {(leaf.id, sink.id): 2, (fork.id, sink.id): 0, (fork.id, leaf.id): 0,
                    (inner.id, leaf.id): 0, (over.id, sink.id): 3}
    assert [int(problem.excess[n.id]) for n in (leaf, fork, inner, over)] == [0, 2, 2, 1]
    assert int(problem.excess[sink.id]) == -11 + 1 + 5 and st.supply_prerouted == 5
    assert _by_arc(st, problem.flow_offset)[(over.id, sink.id)] == 4
    # with no more on `over` than its arc holds (a feasible problem) the routed problem
    # is the plain one to a solver: the same optimum, the same total flow
    st.set_excess(over.id, 4)
    st.set_excess(sink.id, -10)
    problem, plain = check_view(st)
    assert problem.excess[over.id] == 0 and st.supply_prerouted == 5
    for name in ("ref", "native"):
        a = make_backend(name, warm_start=False, fallback=False).solve(problem)
        b = make_backend(name, warm_start=False, fallback=False).solve(plain)
        assert a.objective == b.objective
        assert np.array_equal(a.total_flow(problem), b.total_flow(plain)), name


def test_a_clean_round_returns_the_cached_problem_and_a_bind_rebuilds_both_sides():
    st, sink, _ms, ps, ts = _cluster()
    first = st.problem()
    assert st.problem() is first
    _arc(st, ps[0], sink, 0, SLOTS, 1)  # what it had: still a write, no routing moved
    again = st.problem()
    assert again.excess is first.excess and again.cap is not first.cap
    _bind(st, ts[0], ps[0])
    after = st.problem()
    assert after.excess is not again.excess and after.cap is not again.cap
    assert after.cap[st._arc_slot[(ps[0], sink)]] == SLOTS - 1


# ---------------------------------------------------------------------------
# every backend: the routed problem's answer is the plain one's
# ---------------------------------------------------------------------------

BACKENDS = ("ref", "native", "jax", "auto")


def _state_of(problem):
    """A plain FlowProblem (its lower bounds folded) sent through the
    export: the graph it came from, node for node and arc for arc, with
    the one node of negative excess its sink."""
    g = FlowGraph()
    nodes = [g.add_node() for _ in range(problem.num_nodes - 1)]  # ids from 1
    excess = problem.excess.astype(np.int64).copy()
    m = problem.num_arcs
    off = problem.flow_offset[:m].astype(np.int64)
    np.add.at(excess, problem.src[:m], off)
    np.subtract.at(excess, problem.dst[:m], off)
    (sink,) = np.flatnonzero(excess < 0).tolist()
    for n in nodes:
        n.excess = int(excess[n.id])
        n.type = NodeType.SINK if n.id == sink else NodeType.PU
    for k in range(m):
        s, d = int(problem.src[k]), int(problem.dst[k])
        if s == d or (problem.cap[k] == 0 and off[k] == 0):
            continue
        arc = g.add_arc(g.node(s), g.node(d))
        g.change_arc(arc, int(off[k]), int(problem.cap[k] + off[k]), int(problem.cost[k]))
    st = DeviceGraphState()
    st.full_build(g)
    return st, sink


def _problem_state(name):
    from test_csr_entry_state import _inputs, _slot_state

    layout, kind = name.split("-", 1)
    if layout == "slot":
        st = _slot_state(kind)
        return st, st.sink
    return _state_of(_inputs(name)[0])


def _problems():
    from test_csr_entry_state import PROBLEMS

    return PROBLEMS


def _solve_both(backend, st):
    problem, plain = check_view(st)
    a = make_backend(backend, warm_start=False, fallback=False).solve(problem)
    b = make_backend(backend, warm_start=False, fallback=False).solve(plain)
    return problem, plain, a, b


def _assert_feasible(plain_problem, total):
    """`total` is a flow of the graph itself: within each arc's bounds,
    every node's raw excess carried away."""
    low, cap = plain_problem.flow_offset, plain_problem.cap + plain_problem.flow_offset
    assert (total >= low).all() and (total <= cap).all()
    net = np.zeros(plain_problem.num_nodes, np.int64)
    np.add.at(net, plain_problem.src, total)
    np.subtract.at(net, plain_problem.dst, total)
    raw = plain_problem.excess.astype(np.int64).copy()
    np.add.at(raw, plain_problem.src, low)
    np.subtract.at(raw, plain_problem.dst, low)
    assert np.array_equal(net, raw)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", _problems())
def test_a_backend_answers_the_routed_problem_as_it_answers_the_plain_one(name, backend):
    st, sink = _problem_state(name)
    problem, plain, a, b = _solve_both(backend, st)
    assert int(a.objective) == int(b.objective)
    total = a.total_flow(problem)
    _assert_feasible(plain, np.asarray(total, np.int64))
    assert np.array_equal(total, b.total_flow(plain))
    assert np.array_equal(a.flow, np.asarray(b.flow) - st.routed)
    tasks = set(np.flatnonzero(plain.excess > 0).tolist())
    leaves = {s for (s, d) in st._arc_slot if d == sink}
    assert flow_to_mapping(problem, total, leaves, sink, tasks) == flow_to_mapping(
        plain, b.total_flow(plain), leaves, sink, tasks
    )


# ---------------------------------------------------------------------------
# served rounds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_every_served_round_is_answered_as_the_plain_problem_would_be(backend):
    """A fill, arrivals and completions of `Stream`: each round's result
    (the service's own rung) against a fresh backend of the same name on
    the parent's view of the same state, and the counter on the record."""
    from ksched_tpu.runtime.trace import RoundTracer
    from test_k8s_requests_model import Stream

    s = Stream(40, 9, backend=backend, tracer=RoundTracer())
    rounds = [(np.zeros(300, int), 0), ([0] * 6, 4), ([0] * 3, 9), ([0], 5), ([0] * 8, 0)]
    held = []
    for sizes, completions in rounds:
        objective, served, want, native = s.round(sizes, completions)
        assert objective == served == want == native
        sched = s.svc.scheduler
        st, gm = sched.solver.state, sched.gm
        record = s.svc.tracer.records[-1]
        held.append(record.supply_prerouted)
        problem, plain = check_view(st)
        assert plain.total_supply - problem.total_supply == st.supply_prerouted
        assert not problem.excess[sorted(gm.leaf_node_ids)].any()
        if sched.last_timing.plan_refits:
            continue  # the state is the graph the round LEFT (PlacementSolver.rehearse)
        assert record.supply_prerouted == st.supply_prerouted == gm.num_pinned - record.num_scheduled
        result = sched.solver.last_result
        theirs = make_backend(backend, warm_start=False, fallback=False).solve(plain)
        assert int(result.objective) == int(theirs.objective) == objective
        total = result.total_flow(problem)
        _assert_feasible(plain, np.asarray(total, np.int64))
        # the same optimum; which of several machines of one cost a pod takes is the
        # solver's to choose, and it may choose otherwise from another start
        assert int((total * plain.cost.astype(np.int64)).sum()) == objective
        waiting = set(np.flatnonzero((plain.excess > 0) & (plain.node_type != int(NodeType.PU))).tolist())
        args = (gm.leaf_node_ids, gm.sink_node.id, waiting)
        mine = flow_to_mapping(problem, total, *args)
        other = flow_to_mapping(plain, theirs.total_flow(plain), *args)
        assert len(mine) == len(other) == record.num_scheduled
    assert held[0] == 0 and held[1] == 300 - 4 and held[-1] == len(s.bound) - 8


# ---------------------------------------------------------------------------
# the warm round, and the device-resident mirror
# ---------------------------------------------------------------------------


def _pu_imbalance(problem, flow0, pus):
    net = problem.excess.astype(np.int64).copy()
    np.subtract.at(net, problem.src, flow0)
    np.add.at(net, problem.dst, flow0)
    return net[sorted(pus)]


@pytest.mark.parametrize("resident", [False, True], ids=["host", "device_resident"])
def test_a_round_after_a_bind_a_completion_or_an_eviction_is_not_warm_and_a_warm_one_is_balanced(
    resident,
):
    """Four slots and a backlog, so that every round solves. A round whose
    journal moved `routed` (a pin applied, a pin removed) re-wires an arc,
    so the solver starts it from zero flow (`fresh`); a round that carries
    flow (`warm`) finds the offsets where its last solve left them, and the
    carried flow leaves no PU with excess or deficit."""
    seed_rng(5)
    rung = JaxSolver()
    sched, rmap, jmap, tmap, _root = build_cluster(
        num_machines=2, num_cores=1, pus_per_core=2, max_tasks_per_pu=1, backend=rung,
    )
    if resident:
        sched.solver.device_resident = True
        sched.solver.resident = DeviceResidentState(sched.solver.state)
    st, gm = sched.solver.state, sched.gm
    add_job(sched, jmap, tmap, num_tasks=7)
    seen = []

    def round_(event):
        held, prev = st.supply_prerouted, None if rung._prev is None else np.array(rung._prev)
        sched.schedule_all_jobs()
        problem, _plain = check_view(st)
        check_against_rebuild(st)  # the export's state, against its own raw arrays
        if resident:
            sched.solver.resident.parity_check()
        scope = rung.last_warm_scope
        seen.append((event, scope, st.supply_prerouted - held))
        if st.supply_prerouted != held:
            assert scope != "warm", seen
        if scope == "warm":
            flow0 = np.minimum(prev, problem.cap)
            assert not _pu_imbalance(problem, flow0, gm.leaf_node_ids).any(), seen
        assert not problem.excess[sorted(gm.leaf_node_ids)].any()
        return scope

    def until_warm():
        """Rounds until one carries flow; what `routed` moved by in each."""
        first = len(seen)
        while round_("after") != "warm":
            assert len(seen) - first < 6, seen
        return [moved for _event, _scope, moved in seen[first:]]

    assert round_("fill") == "cold" and len(sched.get_task_bindings()) == 4
    assert round_("bind") == "fresh"  # the four pins reach the export
    assert seen[-1][2] == 4 and until_warm() == [0]
    sched.handle_task_completion(tmap.find(sorted(sched.get_task_bindings())[0]))
    # the pin goes; the slot is seen free a round later (the statistics lag
    # one round), a pod that waited takes it, and its pin reaches the export
    assert round_("completion") == "fresh" and seen[-1][2] == -1
    assert until_warm() == [0, 1, 0] and len(sched.get_task_bindings()) == 4
    uid, rid = sorted(sched.get_task_bindings().items())[1]
    sched.handle_task_eviction(tmap.find(uid), rmap.find(rid).descriptor)
    assert round_("eviction") == "fresh" and seen[-1][2] == -1
    moves = until_warm()
    assert sum(moves) == 1 and moves[-1] == 0 and len(sched.get_task_bindings()) == 4
    assert [scope for _e, scope, moved in seen if moved] == ["fresh"] * 5
    assert st.supply_prerouted == gm.num_pinned == 4
    # a state built whole from the graph the last round left agrees
    changes = gm.cm.get_optimized_graph_changes()
    st.apply_changes(changes)
    gm.cm.reset_changes()
    st.set_excess(gm.sink_node.id, gm.sink_node.excess)
    check_against_rebuild(st, gm.cm.graph)


def test_the_mirror_of_a_resident_stream_passes_parity_after_every_round():
    from test_k8s_requests_model import Stream

    s = Stream(40, 9, backend="jax --device-resident")  # the word rides into `cli`'s arguments
    res = s.svc.scheduler.solver.resident
    assert res is not None
    for sizes, completions in [(np.zeros(300, int), 0), ([0] * 6, 4), ([0] * 3, 9), ([0], 5),
                               ([0] * 8, 0), ([0] * 2, 2)]:
        objective, served, want, native = s.round(sizes, completions)
        assert objective == served == want == native
        res.parity_check()
        res.plan_parity_check()
        st = s.svc.scheduler.solver.state
        check_view(st)
        assert not np.asarray(res.d_excess)[sorted(s.svc.scheduler.gm.leaf_node_ids)].any()
    assert st.supply_prerouted > 280
