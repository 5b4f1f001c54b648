"""The slot plan re-fits to the graph it holds (graph/slot_plan.py).

A layout built while a fill round's transient arcs were live is sized
for rows that are dead for good a round later. `refit_due` asks, at the
end of every round, whether the graph as it stands would land in a
smaller bucket; `refit` re-lays out there in one step (from the live
degree, not from a half-decayed mark); growth that undoes a re-fit
doubles the wait for the next one; and the round that re-fits runs the
re-fitted shapes once itself, so the next round compiles nothing.
"""

import pickle

import jax
import numpy as np
import pytest
from test_resident_shapes import _COMPILES, ARGV, RESIDENT
from test_resident_shapes import Served as _Served

from ksched_tpu.graph.changes import ArcType, ChangeArcChange, NewArcChange, NodeType
from ksched_tpu.graph.device_export import DeviceGraphState
from ksched_tpu.graph.flowgraph import FlowGraph
from ksched_tpu.graph.slot_plan import SlotPlanState, entry_bucket, refit_bucket
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.utils import seed_rng

# ---------------------------------------------------------------------------
# the plan alone, over a DeviceGraphState
# ---------------------------------------------------------------------------

#: every task keeps one arc to its machine, every machine one to the sink
#: (2,460 arcs); a fill gives PENDING of the tasks one pending arc more:
#: 3,900 arcs at the peak, so m_cap is 4,096 and the peak's 7,801 rows
#: leave less than a sixteenth of 8,192: the build takes 16,384, the way
#: the fill rounds of `trivial-10kx1k` and `k8s-5000-zonespread` do
TASKS, MACHINES, PENDING = 2360, 100, 1440


class Graph:
    """tasks -> machines -> sink in a DeviceGraphState with a built plan."""

    def __init__(self, tasks=TASKS, machines=MACHINES):
        g = FlowGraph()
        sink = g.add_node()
        sink.type = NodeType.SINK
        self.sink = sink.id
        self.machines = [g.add_node().id for _ in range(machines)]
        self.tasks = [g.add_node().id for _ in range(tasks)]
        for m in self.machines:
            g.change_arc(g.add_arc(g.node(m), sink), 0, tasks, 0)
        for i, t in enumerate(self.tasks):
            g.node(t).excess = 1
            g.change_arc(g.add_arc(g.node(t), g.node(self.home(i))), 0, 1, 1 + i % 7)
        sink.excess = -tasks
        self.state = DeviceGraphState()
        self.state.full_build(g)
        self.pending = []

    def home(self, i, k=0):
        return self.machines[(i + k) % len(self.machines)]

    @property
    def plan(self) -> SlotPlanState:
        return self.state.plan

    def fill(self, first=0, count=PENDING, k=1):
        """One pending arc, to the `k`-th machine after its own, for each
        of the `count` tasks from `first` on."""
        for i in range(first, first + count):
            self.pending.append((self.tasks[i], self.home(i, k)))
            self.state.apply_changes(
                [NewArcChange(*self.pending[-1], 0, 1, 9, ArcType.OTHER)]
            )

    def drain(self):
        for src, dst in self.pending:
            self.state.apply_changes([ChangeArcChange(src, dst, 0, 0, 0, ArcType.OTHER, 0)])
        self.pending.clear()

    def retire(self, first, stop):
        """Tasks `first` to `stop` leave: their arc goes, and their unit
        of supply with it."""
        for i in range(first, stop):
            self.state.apply_changes(
                [ChangeArcChange(self.tasks[i], self.home(i), 0, 0, 0, ArcType.OTHER, 0)]
            )
            self.state.set_excess(self.tasks[i], 0)
        self.state.set_excess(self.sink, int(self.state.excess[self.sink]) + stop - first)

    def end_of_round(self) -> bool:
        """What FlowScheduler._refit_plan does with the plan, less the solve."""
        due = self.plan.refit_due(self.plan.rows_live)
        if due:
            self.plan.refit()
        self.plan.ensure_built()
        return due


def _live_rows(plan):
    """The live rows in position order: (owner, arc slot, sign, other end)."""
    args = plan.host_args()
    p_arc, p_sign, p_src, p_dst = (np.asarray(a) for a in args[:4])
    live = np.flatnonzero(p_sign != 0)
    return list(zip(p_src[live], p_arc[live], p_sign[live], p_dst[live]))


def _fresh_plan(state) -> SlotPlanState:
    """A plan built from scratch on the same arrays."""
    plan = SlotPlanState(state)
    plan.ensure_built()
    return plan


def _filled():
    g = Graph()
    g.fill()
    g.plan.ensure_built()
    assert g.state.m_cap == 4096 and g.plan.entry_cap == 16384
    return g


def _marked_twice():
    """The `k8s-5000-zonespread` shape: marks about twice the live degree,
    because the nodes peaked at times of their own (a second fill gives the
    other tasks their pending arc, in regions the first layout had room for)."""
    g = _filled()
    g.drain()
    g.fill(first=TASKS - PENDING)
    g.drain()
    assert not g.plan.needs_rebuild and g.plan.region_relocations == 0
    assert (g.plan._deg_hwm[g.tasks] == 2).all() and (g.plan._occ[g.tasks] == 1).all()
    return g


def test_a_drained_fill_refits_in_one_step_to_the_bucket_a_fresh_build_picks():
    g = _filled()
    assert not g.end_of_round()  # the pending arcs are live: nothing to fit
    g.drain()
    assert g.plan.rows_live == 2 * (TASKS + MACHINES)
    assert g.end_of_round()
    assert g.plan.entry_cap == _fresh_plan(g.state).entry_cap == 8192
    assert (g.plan.refits, g.plan.regrowths) == (1, 0)
    g.plan.check_invariants()
    assert not g.end_of_round()  # and it stays there


def test_marks_twice_the_live_degree_need_one_refit_where_decay_needs_more():
    decayed, refitted = _marked_twice(), _marked_twice()
    # the parent's only way down: a rebuild halves the distance from mark
    # to live degree, and the half-decayed marks still want the larger bucket
    decayed.plan.invalidate()
    decayed.plan.ensure_built()
    assert decayed.plan.entry_cap == 16384
    assert refitted.end_of_round()
    assert refitted.plan.entry_cap == _fresh_plan(refitted.state).entry_cap == 8192
    assert refitted.plan.refits == 1
    assert int(refitted.plan._deg_hwm.sum()) == refitted.plan.rows_live
    refitted.plan.check_invariants()


def test_a_refitted_plan_holds_the_rows_of_one_built_from_scratch_and_solves_alike():
    g = _marked_twice()
    before = g.state.problem()
    old = JaxSolver(warm_start=False).solve(before)
    assert g.end_of_round()
    fresh = _fresh_plan(g.state)
    assert _live_rows(g.plan) == _live_rows(fresh)
    g.plan.check_invariants()  # inv_order, the segments and the split hold
    after = g.state.problem()
    new = JaxSolver(warm_start=False).solve(after)
    ref = ReferenceSolver().solve(after)
    assert new.objective == old.objective == ref.objective
    assert np.array_equal(new.flow, old.flow)
    assert g.plan.entry_cap == 8192 and len(np.asarray(g.plan.host_args()[0])) == 8192


def test_a_refit_goes_below_twice_the_arc_table_only_with_room_to_drift():
    """`2 * m_cap` is what a build allows for, not a floor: a re-fit goes
    below it, but only to a bucket that holds the rows with a quarter more
    and the arena's sixteenth (`refit_bucket`)."""
    g = _filled()
    g.drain()
    # 3,400 rows would fit 4,096 as a build sizes (the arena is left), but
    # would arrive there 83% full: down to twice the arc table and no further
    g.retire(1600, TASKS)
    assert g.plan.rows_live == 3400 and entry_bucket(1 + g.plan.rows_live) == 4096
    assert refit_bucket(1 + g.plan.rows_live) == 8192
    assert g.end_of_round() and g.plan.entry_cap == 2 * g.state.m_cap == 8192
    assert not g.end_of_round()
    # 3,000 rows arrive 73% full: down, below twice the arc table
    g = _filled()
    g.drain()
    g.retire(1400, TASKS)
    assert g.plan.rows_live == 3000 and refit_bucket(1 + g.plan.rows_live) == 4096
    assert g.end_of_round() and g.plan.entry_cap == 4096 == g.state.m_cap
    assert (g.plan.refits, g.plan.regrowths) == (1, 0)
    g.plan.check_invariants()
    assert not g.end_of_round()


def test_rows_that_swing_past_the_margin_go_down_at_a_trough_and_the_back_off_settles_them():
    """`k8s-5000-antiaffinity`'s shape at 1/75: the fill round ends on rows
    that a purge takes two rounds later and arrivals bring back. The margin
    is for rows that hover; a swing wider than it takes the plan down at the
    trough, below `2 * m_cap`, and the peak that returns takes it back to the
    extent it had (a program that has run), which doubles the wait."""
    g = Graph()
    g.plan.ensure_built()
    assert g.plan.entry_cap == 2 * g.state.m_cap == 8192
    g.retire(1400, TASKS)
    g.fill(count=960)  # the fill round ends on 4,920 rows: nothing smaller would do
    assert g.plan.rows_live == 4920 and not g.end_of_round()
    g.drain()  # the trough: 3,000 rows go down to 4,096
    assert g.plan.rows_live == 3000 and refit_bucket(1 + g.plan.rows_live) == 4096
    assert g.end_of_round() and g.plan.entry_cap == 4096 < 2 * g.state.m_cap
    caps = set()
    for _ in range(16):
        g.fill(count=960)  # the peak is back: 4,920 rows do not fit 4,096
        g.plan.ensure_built()
        g.end_of_round()
        caps.add(g.plan.entry_cap)
        g.drain()
        g.end_of_round()
        caps.add(g.plan.entry_cap)
    # between the two extents and nowhere else, a bounded number of times
    assert caps == {4096, 8192} and g.state.m_cap == 4096
    assert 2 <= g.plan.refits <= 5 and g.plan.refits - 1 <= g.plan.regrowths <= g.plan.refits
    assert g.plan._refit_wait == 2 ** g.plan.regrowths >= 8
    g.plan.check_invariants()


def _below(keep):
    """A plan re-fitted below `2 * m_cap`: the fill is over and all but
    `keep` of the tasks have left."""
    g = _filled()
    g.drain()
    g.retire(keep, TASKS)
    assert g.end_of_round()
    assert g.plan.entry_cap == refit_bucket(1 + g.plan.rows_live) < 2 * g.state.m_cap == 8192
    return g


def _objectives(state):
    problem = state.problem()
    return JaxSolver(warm_start=False).solve(problem).objective, ReferenceSolver().solve(problem).objective


@pytest.mark.parametrize("keep, rows", [(1400, 4096), (600, 2048), (150, 1024)])
def test_a_plan_below_twice_the_arc_table_is_a_fresh_build_row_for_row_and_solves(keep, rows):
    g = _below(keep)
    assert g.plan.entry_cap == rows and len(np.asarray(g.plan.host_args()[0])) == rows
    assert len(g.plan.inv_order) == 2 * g.state.m_cap  # the [2m] table is read at E indices
    fresh = _fresh_plan(g.state)
    assert fresh.entry_cap == 2 * g.state.m_cap  # a build keeps the allowance
    assert _live_rows(g.plan) == _live_rows(fresh)
    g.plan.check_invariants()
    ours, reference = _objectives(g.state)
    assert ours == reference
    # incremental churn under it: pending arcs come and half of them go
    before = g.plan.rows_live
    g.fill(count=keep // 8)
    g.pending, kept = g.pending[: keep // 16], g.pending[keep // 16 :]
    g.drain()
    assert g.plan.rows_live == before + 2 * len(kept) > before
    assert not g.plan.needs_rebuild and (g.plan.entry_cap, g.plan.regrowths) == (rows, 0)
    g.plan.check_invariants()
    ours, reference = _objectives(g.state)
    assert ours == reference


@pytest.mark.parametrize("refitted", [True, False], ids=["refitted", "never-refitted"])
def test_an_arena_overflow_keeps_the_extent_the_plan_has(refitted):
    """A rebuild that is no re-fit never lowers `entry_cap`, whatever the
    marks would fit: a smaller bucket met inside a round is a program
    nobody has run. (The parent had `2 * m_cap` to stop at.)"""
    if refitted:
        g = _below(1400)
        g.retire(100, 1400)
    else:
        g = _filled()
        g.drain()
        g.retire(300, TASKS)
    cap = g.plan.entry_cap
    assert cap == (4096 if refitted else 16384)
    for _ in range(3):  # the marks decay halfway at each rebuild
        g.plan._overflow()
        g.plan.ensure_built()
        assert g.plan.entry_cap == cap
    assert entry_bucket(1 + int(g.plan._deg_hwm.sum())) < cap
    assert (g.plan.region_overflows, g.plan.regrowths) == (3, 0)
    g.plan.check_invariants()
    # the way down is the re-fit's, at the end of a round
    assert g.end_of_round() and g.plan.entry_cap == (1024 if refitted else 2048)


@pytest.mark.parametrize(
    "tasks, machines, peak, swing",
    [(414, 20, 200, 60), (1850, 50, 400, 90), (1865, 50, 400, 125)],
    ids=["868-of-1024", "3974-of-4096", "4078-of-4096"],
)
def test_rows_that_hover_about_a_bucket_s_edge_never_take_the_plan_across_it(
    tasks, machines, peak, swing
):
    """The 1/40 rehearsals' shapes: as a build sizes, the rows fit the
    smaller bucket in one round and not in the next. Going down is stricter
    than staying, so the plan does neither."""
    g = Graph(tasks, machines)
    g.fill(count=peak)
    g.plan.ensure_built()
    cap = g.plan.entry_cap
    assert cap == 2 * g.state.m_cap
    low, high = 2 * (tasks + machines), 2 * (tasks + machines + swing)
    assert entry_bucket(1 + low) == cap // 2 and entry_bucket(1 + high) == cap
    for rnd in range(24):
        g.drain()
        if rnd % 2:
            g.fill(first=rnd, count=swing)
        assert g.plan.rows_live == (high if rnd % 2 else low)
        assert not g.end_of_round()
    assert (g.plan.entry_cap, g.plan.refits, g.plan.regrowths) == (cap, 0, 0)
    assert not g.plan.needs_rebuild and g.plan.layout_rebuilds == 1
    g.plan.check_invariants()


def test_growth_of_the_arc_table_after_a_refit_restores_what_a_build_allows_for():
    g = _below(1400)
    assert (g.plan.entry_cap, g.state.m_cap) == (4096, 4096)
    g.fill(count=1400)
    g.fill(count=1400, k=2)  # a second pending arc each: 4,300 arcs
    g.plan.ensure_built()
    assert g.state.m_cap == 8192 and g.plan.entry_cap == 2 * g.state.m_cap
    # growth undid a re-fit: the next one waits twice as long
    assert (g.plan.regrowths, g.plan._refit_wait, g.plan._refit_standing) == (1, 2, False)
    g.plan.check_invariants()
    ours, reference = _objectives(g.state)
    assert ours == reference


def test_a_plan_below_twice_the_arc_table_survives_a_pickle_round_trip():
    g = _below(600)
    state = pickle.loads(pickle.dumps(g.state))
    plan = state.plan
    assert plan.entry_cap == g.plan.entry_cap == 2048 < 2 * state.m_cap
    for ours, theirs in zip(plan.host_args(), g.plan.host_args()):
        assert np.array_equal(ours, theirs)
    plan.check_invariants()
    # the restored plan knows under which caps it was laid out: a rebuild
    # that is no re-fit keeps its extent, and arcs wire as before
    plan._overflow()
    plan.ensure_built()
    assert plan.entry_cap == 2048
    state.apply_changes([NewArcChange(g.tasks[0], g.home(0, 1), 0, 1, 9, ArcType.OTHER)])
    assert not plan.needs_rebuild and plan.rows_live == g.plan.rows_live + 2
    plan.check_invariants()
    ours, reference = _objectives(state)
    assert ours == reference


def test_growth_after_a_refit_doubles_the_wait_and_peaks_that_do_not_fit_settle():
    g = _filled()
    refits = []
    for rnd in range(32):
        if rnd % 2:
            g.fill()  # the peak: it does not fit 8,192 rows
            g.plan.ensure_built()  # the next export's rebuild, if the arena overflowed
        else:
            g.drain()
        wait = g.plan._refit_wait
        if g.end_of_round():
            refits.append(rnd)
        if g.plan._refit_wait != wait:
            assert g.plan._refit_wait == 2 * wait and not refits[-1] == rnd
    assert 2 <= len(refits) <= 5
    assert g.plan.refits == len(refits)
    # every re-fit but the last was undone by growth, and each undoing doubled
    assert g.plan.regrowths >= len(refits) - 1
    assert g.plan._refit_wait == 2 ** g.plan.regrowths
    gaps = np.diff(refits)
    assert (gaps[1:] >= gaps[:-1]).all() and gaps[-1] >= 2 * gaps[0]
    g.plan.check_invariants()


def test_growth_without_a_refit_before_it_is_ordinary_growth():
    g = Graph()
    g.plan.ensure_built()
    assert g.plan.entry_cap == 8192
    g.fill()  # m_cap stays 4,096; the arena overflows and the rebuild grows
    g.plan.ensure_built()
    assert g.plan.entry_cap == 16384
    assert (g.plan.regrowths, g.plan._refit_wait) == (1, 1)


def test_a_refit_that_finds_no_smaller_bucket_backs_off_too():
    """The test reads the graph, the build the arrays: if arcs arrive in
    between, the re-fit lands where it was, and must not be asked for again
    at the end of every round."""
    g = _filled()
    g.drain()
    assert g.plan.refit_due(g.plan.rows_live)
    g.plan.refit()
    g.fill()  # hooks are off until the build: these arcs are just in the arrays
    g.plan.ensure_built()
    assert g.plan.entry_cap == 16384
    assert (g.plan.refits, g.plan.regrowths, g.plan._refit_wait, g.plan._refit_idle) == (0, 0, 2, 0)
    g.plan.check_invariants()


def test_the_sharded_layout_is_left_exactly_as_it_is():
    """Its block extent follows the densest shard (`_rebuild`, D > 1): no
    re-fit test for it yet, so `refit_due` says no whatever the graph."""
    g = Graph()
    g.plan.enable_sharding(2)
    g.fill()
    g.plan.ensure_built()
    cap = g.plan.entry_cap
    g.drain()
    assert not g.plan.refit_due(g.plan.rows_live)
    assert not g.end_of_round() and g.plan.entry_cap == cap
    assert (g.plan.refits, g.plan._refit_idle) == (0, 0)


# ---------------------------------------------------------------------------
# the served round
# ---------------------------------------------------------------------------

#: test_resident_shapes' service: 40 machines x 16 slots. A fill of 280 pods
#: peaks at 1,001 arcs of 1,024, so its plan takes 4,096 rows where the
#: steady graph fits 2,048
FILL = 280
SERVICES = {"plain": ARGV, "pipeline": ARGV + ["--pipeline"], "resident": RESIDENT}
PLAN_FIELDS = ("plan_rows", "plan_rows_live", "plan_refits", "plan_regrowths", "plan_relayouts")


class Served(_Served):
    """The same, under a SpanTracer; a round also says what it compiled."""

    def __init__(self, argv):
        self.spans = SpanTracer(capacity=1 << 16).install()
        super().__init__(argv)

    @property
    def plan(self) -> SlotPlanState:
        return self.svc.scheduler.solver.state.plan

    def round(self, arrivals: int, completions: int = 0):
        mark = len(_COMPILES)
        return super().round(arrivals, completions), len(_COMPILES) - mark

    def close(self):
        self.spans.uninstall()


@pytest.fixture(scope="module", params=sorted(SERVICES))
def served(request):
    jax.clear_caches()  # a program another test ran would hide a compile here
    s = Served(SERVICES[request.param])
    rounds = [s.round(FILL)] + [s.round(k, k) for k in (3, 17, 1, 60, 5)]
    s.close()
    return request.param, s, rounds


def test_the_fill_round_refits_and_every_field_is_stamped(served):
    _name, s, rounds = served
    fill = rounds[0][0]
    assert fill.num_scheduled == FILL
    assert (fill.plan_rows, fill.plan_refits, fill.plan_regrowths) == (4096, 1, 0)
    assert fill.plan_relayouts == 2  # the build and the re-fit
    assert fill.plan_rows_live == 2 * 1001
    for rec, _compiled in rounds[1:]:
        assert rec.plan_rows == s.plan.entry_cap == 2048
        assert 0 < rec.plan_rows_live < rec.plan_rows
        assert (rec.plan_refits, rec.plan_regrowths) == (0, 0)
    assert all(hasattr(rounds[0][0], f) for f in PLAN_FIELDS)


def test_the_round_that_refits_compiles_inside_itself(served):
    """PR 38's failure as a test: a program first met in the round after
    the fill compiled inside the measured window."""
    _name, s, rounds = served
    assert rounds[0][1] >= 2  # the fill's program and the re-fitted one
    assert [compiled for _rec, compiled in rounds[1:]] == [0] * 5
    assert all(r.num_scheduled == k for (r, _c), k in zip(rounds[1:], (3, 17, 1, 60, 5)))
    assert all(r.solver_rung == 0 and not r.noop_round for r, _c in rounds)


def test_the_refit_has_a_span_under_round_and_its_solve_would_place_nothing(served):
    _name, s, _rounds = served
    events = s.spans.events()
    refits = [e for e in events if e["name"] == "plan_refit"]
    assert len(refits) == 1
    args = refits[0]["args"]
    assert (args["rows"], args["rows_after"], args["would_place"]) == (4096, 2048, 0)
    by_sid = {e["args"]["sid"]: e for e in events if "sid" in e["args"]}
    assert args["parent"] == by_sid[args["parent_sid"]]["name"] == "round"
    # the export, the rung and the decode it ran are its children
    children = {e["name"] for e in events if e["args"].get("parent_sid") == args["sid"]}
    assert {"graph_export", "backend_solve", "decode"} <= children


def test_the_answer_does_not_change(served):
    """Every pod bound, none twice, and the same Bindings as a service
    whose plan never re-fits (the layout is not a part of the problem)."""
    name, s, _rounds = served
    bound = s.api.bindings()
    assert len(bound) == FILL + 86
    seed_rng(11)
    control = Served(SERVICES[name])
    control.plan.refit_due = lambda rows: False
    [control.round(FILL)] + [control.round(k, k) for k in (3, 17, 1, 60, 5)]
    control.close()
    assert control.plan.entry_cap == 4096 and control.plan.refits == 0
    assert sorted(bound) == sorted(control.api.bindings())


@pytest.mark.parametrize("served", ["resident"], indirect=True)
def test_resident_the_refit_round_uploads_whole_and_the_next_scatters(served):
    _name, s, rounds = served
    assert rounds[0][0].upload_full == 1
    # the 60-pod round overflows the arena of 2,048 rows: today's path, a
    # re-layout at the unchanged bucket and no compile
    assert [r.upload_full for r, _c in rounds[1:]] == [0, 0, 0, 1, 0]
    assert [r.plan_relayouts for r, _c in rounds[1:]] == [0, 0, 0, 1, 0]
    assert all(r.upload_bytes > 0 for r, _c in rounds)
    res = s.svc.scheduler.solver.resident
    # checks/resident's comparison: the arrays on the chip are the host's
    res.parity_check()
    res.plan_parity_check()
    problem = res.state.problem()
    for name_, dev in (("src", res.d_src), ("dst", res.d_dst), ("cap", res.d_cap)):
        assert np.array_equal(np.asarray(dev), getattr(problem, name_))


def test_a_failed_solve_after_the_refit_warns_and_leaves_the_round_whole(monkeypatch):
    s = Served(ARGV)
    solver = s.svc.scheduler.solver
    real = solver.solve
    calls = []

    def solve():
        calls.append(1)
        if len(calls) == 2:  # the solve that follows the re-fit
            raise RuntimeError("boom")
        return real()

    monkeypatch.setattr(solver, "solve", solve)
    with pytest.warns(RuntimeWarning, match="plan re-fit"):
        rec, _compiled = s.round(FILL)
    s.close()
    assert rec.num_scheduled == FILL and len(s.api.bindings()) == FILL
    assert rec.plan_refits == 0 or s.plan.needs_rebuild or s.plan.entry_cap == 2048


def test_under_preemption_the_graph_grows_after_its_fill_and_nothing_refits():
    """`k8s-5000-preemption`'s shape: no task is pinned, a running task keeps
    its arcs and gains one, so the arrays grow in the round after the fill:
    ordinary growth, counted, with nothing for the back-off to undo."""
    argv = [a if a != "trivial" else "k8s_priority" for a in ARGV] + ["--preemption"]
    s = Served(argv)
    fill, _ = s.round(200)
    after, _ = s.round(3)
    s.close()
    assert (fill.num_scheduled, fill.plan_rows, fill.plan_refits) == (200, 2048, 0)
    assert after.plan_rows == s.plan.entry_cap == 4096 > fill.plan_rows
    assert (after.plan_refits, after.plan_regrowths, after.plan_relayouts) == (0, 1, 1)
    assert (s.plan.refits, s.plan._refit_wait) == (0, 1)
    assert not [e for e in s.spans.events() if e["name"] == "plan_refit"]


# ---------------------------------------------------------------------------
# a fill that peaks at twice the steady arcs: the re-fit lands below
# `2 * m_cap`, in the fill round
# ---------------------------------------------------------------------------

#: a fill of 300 pods peaks at 1,081 arcs, so m_cap is 2,048 and the build
#: takes 4,096 rows; the steady graph's ~1,050 rows fit 2,048 with room to drift
FILL_BELOW = 300
DELTAS = (3, 5, 1, 8, 2)


def _mirror_is_the_host_s(s):
    """The ten plan tensors on the device against the host's, and that the
    mirror is not merely behind (`plan_parity_check` returns early then)."""
    res, plan = s.resident, s.plan
    assert (res._plan_gen, res._plan_ver) == (plan.layout_gen, plan.value_version)
    res.plan_parity_check()
    return res.last_plan_kind


@pytest.fixture(scope="module")
def below():
    out = {}
    for name in ("plain", "resident"):
        jax.clear_caches()
        s = Served(SERVICES[name])
        rounds, kinds = [s.round(FILL_BELOW)], []
        for k in (0,) + DELTAS:
            if k:
                rounds.append(s.round(k, k))
            if name == "resident":
                kinds.append(_mirror_is_the_host_s(s))
        s.close()
        out[name] = (s, rounds, kinds)
    return out


@pytest.mark.parametrize("name", ["plain", "resident"])
def test_a_fill_at_twice_the_steady_arcs_refits_below_twice_the_arc_table(below, name):
    s, rounds, _kinds = below[name]
    fill, compiled = rounds[0]
    m_cap = s.svc.scheduler.solver.state.m_cap
    assert (fill.num_scheduled, m_cap) == (FILL_BELOW, 2048)
    assert (fill.plan_rows, fill.plan_refits, fill.plan_regrowths) == (2 * m_cap, 1, 0)
    assert compiled >= 2  # the build's program and the re-fitted one, both in the fill round
    assert s.plan.entry_cap == 2048 < 2 * m_cap
    for (rec, compiled), k in zip(rounds[1:], DELTAS):
        assert (rec.num_scheduled, compiled) == (k, 0)
        assert (rec.plan_rows, rec.plan_refits, rec.plan_regrowths) == (2048, 0, 0)
        assert 0 < rec.plan_rows_live <= 0.75 * rec.plan_rows
        assert rec.solver_rung == 0 and not rec.noop_round
    s.plan.check_invariants()


def test_below_twice_the_arc_table_resident_and_not_bind_the_same_pods(below):
    plain, resident = below["plain"][0], below["resident"][0]
    assert plain.bindings() == resident.bindings()
    assert len(plain.bindings()) == FILL_BELOW + sum(DELTAS)
    for ours, theirs in zip(plain.plan.host_args(), resident.plan.host_args()):
        assert np.array_equal(ours, theirs)


def test_below_twice_the_arc_table_the_mirror_is_the_host_s_whole_and_scattered(below):
    """After the fill round's full upload of the re-fitted layout, and after
    rounds that scattered records into buffers of `entry_cap` rows."""
    s, rounds, kinds = below["resident"]
    assert rounds[0][0].upload_full == 1 and kinds[0] == "rebuild"
    assert "delta" in kinds[1:]
    scattered = [rec for (rec, _c), kind in zip(rounds[1:], kinds[1:]) if kind == "delta"]
    assert scattered and all(r.upload_full == 0 and r.plan_relayouts == 0 for r in scattered)
    res = s.resident
    assert all(len(np.asarray(t)) == 2048 for t in (res.d_p_arc, res.d_p_sign, res.d_seg))
    assert len(np.asarray(res.d_inv)) == 2 * s.svc.scheduler.solver.state.m_cap


# ---------------------------------------------------------------------------
# the re-fitted bucket of a 5,000-node cluster is 262,144 rows: a gather
# table of that many rows lies in the gap between VMEM and the compiler's
# own switch to a compact tiling, and `_rows` pads it over the gap
# ---------------------------------------------------------------------------


def _padded_to(rows, width):
    """Rows of the table `_rows` gathers from, for `width` columns of `rows`."""
    from ksched_tpu.solver.jax_solver import _rows

    col = jax.ShapeDtypeStruct((rows,), np.int32)
    idx = jax.ShapeDtypeStruct((64,), np.int32)
    jaxpr = jax.make_jaxpr(lambda i, *c: _rows(i, *c))(idx, *[col] * width)
    (gather,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"]
    return gather.invars[0].aval.shape


@pytest.mark.parametrize("width", [1, 2, 3, 4])
@pytest.mark.parametrize(
    "rows, table_rows",
    [(65_536, 65_536), (131_072, 131_072), (196_608, 196_608), (262_144, 278_528),
     (278_528, 278_528), (524_288, 524_288)],
)
def test_a_gather_table_in_the_gap_is_padded_over_it_and_no_other(rows, table_rows, width):
    assert _padded_to(rows, width) == (table_rows, max(width, 2))


def test_the_padded_table_gathers_what_the_columns_hold():
    from ksched_tpu.solver.jax_solver import _rows

    rng = np.random.default_rng(41)
    rows = 262_144
    a, b, c = (rng.integers(-(1 << 30), 1 << 30, rows).astype(np.int32) for _ in range(3))
    idx = np.concatenate(([0, rows - 1], rng.integers(0, rows, 4096))).astype(np.int32)
    for cols in ((a,), (a, b), (a, b, c)):
        got = jax.jit(_rows)(idx, *cols)
        assert len(got) == len(cols)
        for g, col in zip(got, cols):
            assert np.array_equal(np.asarray(g), col[idx])
