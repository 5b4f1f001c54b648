"""The rows pass of `try_collapse` groups tasks before it builds rows.

Until PR 46 the pass built every task's effective cost row, `[T, M]`
int64, and grouped the rows afterwards; a fill of 142,000 tasks over
12,500 machines would have asked 14 GB for it. It now groups the tasks by
what makes their rows differ (escape cost, placement arcs) and builds
`[classes, M]`. Held here: on the problems the served `coco`, `whare` and
`quincy` paths export, the `GraphCollapse` is the one the former pass gave
(that pass, frozen below, over the same audit), flows and objective after
reconstruction included; tasks whose arcs differ but whose rows do not
still share a row; and a fill of 20,000 tasks over 4,096 machines stays
under a stated allocation.

And the dense problem as the transport solves it since PR 46:
`solve_layered_host` merges the columns that cost every row alike into one
(the synchronous push-relabel herds on interchangeable columns: 30,000
supersteps a round on 12,500 machines of which 1,350 were empty and cost
alike) beside its other degenerate cases, spreads the grants back over the
members, and solves a problem with no two such columns as it is;
`AutoSolver` pads the rows to a multiple of four with rows of no supply, so
that rounds of two, three and four classes run one compiled program.
"""

import tracemalloc

import numpy as np
import pytest

from ksched_tpu import cli
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.cluster.api import PodEvent
from ksched_tpu.graph.flowgraph import NodeType
from ksched_tpu.solver import graph_collapse
from ksched_tpu.solver.base import FlowProblem
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver import layered
from ksched_tpu.solver.graph_collapse import _BIG, AutoSolver, _group_tasks_by_arcs, try_collapse
from ksched_tpu.solver.layered import (
    LayeredProblem, LayeredTransportSolver, like_columns, merge_like_columns, split_merged_grants,
)
from ksched_tpu.utils import seed_rng


# -- the former pass, frozen ----------------------------------------------------------------


def rows_by_task(problem, gc):
    """PR 45's rows pass over the audit `gc` carries: every task's own
    row, then the byte-view grouping. Returns (supply, cost_cm,
    row_unsched, rows_tasks)."""
    cost = np.asarray(problem.cost)
    T, M = len(gc.task_ids), len(gc.machine_node)
    # a column's path cost: down the first arc from the machine to the sink
    col_path = np.zeros(M, np.int64)
    for col, v in enumerate(gc.machine_node.tolist()):
        while v != -1:
            lo = np.searchsorted(gc.dec_src, v)
            if lo == len(gc.dec_src) or gc.dec_src[lo] != v:
                break  # a machine with no arc below it
            col_path[col] += cost[gc.dec_arc[lo]]
            v = int(gc.dec_child[lo])
    u_eff = cost[gc.esc1].astype(np.int64) + cost[gc.esc2] if T else np.zeros(0, np.int64)
    crow = np.full((T, M), _BIG, np.int64)
    if len(gc.mac_arc):
        np.minimum.at(crow, (gc.mac_t, gc.mac_col), gc.mac_cost)
    if len(gc.ect_arc):
        o = np.argsort(gc.ect_t, kind="stable")
        owner_t = gc.ect_t[o]
        child = gc.ec_cost_row[gc.ect_ec[o]]
        cand = np.where(child >= _BIG, _BIG, gc.ect_cost[o, None] + child)
        starts = np.nonzero(np.r_[True, np.diff(owner_t) > 0])[0]
        red = np.minimum.reduceat(cand, starts, axis=0)
        rows = owner_t[starts]
        crow[rows] = np.minimum(crow[rows], red)
    crow = np.where(crow >= _BIG, _BIG, crow + col_path[None, :])
    if not T:
        return np.zeros(0, np.int32), np.zeros((0, M), np.int64), np.zeros(0, np.int64), []
    key = np.ascontiguousarray(np.concatenate([crow, u_eff[:, None]], axis=1))
    kv = key.view(np.dtype((np.void, key.shape[1] * key.itemsize))).reshape(T)
    _, first_idx, inv = np.unique(kv, return_index=True, return_inverse=True)
    supply = np.bincount(inv).astype(np.int32)
    order = np.argsort(inv, kind="stable")
    starts = np.nonzero(np.r_[True, np.diff(inv[order]) > 0])[0]
    rows_tasks = np.split(order, starts[1:])
    row_cost, row_u = crow[first_idx], u_eff[first_idx]
    finite = row_cost[row_cost < _BIG]
    disallowed = max(int(finite.max()) if finite.size else 0, int(row_u.max())) + 1
    return supply, np.where(row_cost >= _BIG, disallowed, row_cost), row_u, rows_tasks


def same_as_the_former_pass(problem, gc):
    supply, cost_cm, row_unsched, rows_tasks = rows_by_task(problem, gc)
    np.testing.assert_array_equal(gc.supply, supply)
    assert gc.supply.dtype == np.int32 and gc.cost_cm.dtype == np.int64
    np.testing.assert_array_equal(gc.cost_cm, cost_cm)
    np.testing.assert_array_equal(gc.row_unsched, row_unsched)
    assert len(gc.rows_tasks) == len(rows_tasks)
    for got, want in zip(gc.rows_tasks, rows_tasks):
        np.testing.assert_array_equal(got, want)


# -- the problems the served paths export -----------------------------------------------------


def _served(argv, rounds, seed, make_pod):
    """The problems `try_collapse` took in `rounds` served rounds
    ((arrivals, completions) each) of the service `argv` builds, with
    what it made of each and the flows the rung reconstructed."""
    seed_rng(seed)
    args = cli.build_arg_parser().parse_args(argv.split())
    api = SyntheticClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(args, api)
    svc.init_topology(
        fake_machines=args.num_machines, cores_per_machine=args.cores_per_machine,
        pus_per_core=args.pus_per_core,
    )
    rng = np.random.default_rng(seed)
    taken = []
    inner = graph_collapse.try_collapse

    def spy(problem):
        gc, reason = inner(problem)
        taken.append((problem, gc, reason))
        return gc, reason

    graph_collapse.try_collapse = spy
    try:
        alive, k = [], 0
        for arrivals, completions in rounds:
            for pod in [alive.pop(int(rng.integers(0, len(alive)))) for _ in range(min(completions, len(alive)))]:
                svc.complete_pod(pod)
            pods = [make_pod(f"p{k + i}", rng) for i in range(arrivals)]
            k += arrivals
            svc.run_round(pods)
            alive += [p.pod_id for p in pods if p.pod_id in api.bindings()]
            result = svc.scheduler.solver.last_result
            taken[-1] += (np.asarray(result.flow), int(result.objective))
    finally:
        graph_collapse.try_collapse = inner
    assert svc.ladder.degradations_total == 0
    return taken


def _class_pod(pod_id, rng):
    return PodEvent(pod_id=pod_id, task_class=int(rng.integers(0, 4)))


def _block_pod(machines):
    def make(pod_id, rng):
        holders = tuple(f"fake_node_{int(i)}" for i in rng.permutation(machines)[:3])
        return PodEvent(pod_id=pod_id, inputs=((int(rng.integers(0, 1 << 30)), 64 << 20, holders),))
    return make


ROUNDS = [(150, 0), (9, 4), (1, 6), (14, 2), (40, 30), (3, 0)]
#: fewer pods a round than the emptiest rack has free slots: no chain arc could bind
FEW = [(6, 0), (5, 2), (9, 1), (6, 3), (3, 0), (12, 2)]
FIXTURES = {
    "coco": ("--fake-machines --num-machines 24 --pus-per-core 4 --max-tasks-per-pu 4 "
             "--cost-model coco --backend auto", _class_pod),
    "whare-three-types": ("--fake-machines --num-machines 24 --pus-per-core 2 --max-tasks-per-pu 3 "
                          "--fake-machine-types A:1:10,B:2:930,C:4:60 --cost-model whare --backend auto",
                          _class_pod),
    "quincy": ("--fake-machines --num-machines 24 --max-tasks-per-pu 8 --fake-racks 4 "
               "--cost-model quincy --backend auto", _block_pod(24), FEW),
    "trivial": ("--fake-machines --num-machines 24 --pus-per-core 2 --max-tasks-per-pu 4 "
                "--cost-model trivial --backend auto", _class_pod),
}


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_the_grouped_pass_gives_the_collapse_the_former_pass_gave(name):
    argv, make_pod, *rounds = FIXTURES[name]
    rounds = rounds[0] if rounds else ROUNDS
    taken = _served(argv, rounds, 7, make_pod)
    assert len(taken) == len(rounds)
    for problem, gc, reason, flow, objective in taken:
        assert gc is not None, reason
        same_as_the_former_pass(problem, gc)
        # the flows after reconstruction: every task's unit leaves it, and the objective
        # is the reference solver's on the same problem
        assert objective == int(ReferenceSolver().solve(problem).objective)
        src = np.asarray(problem.src)
        out = np.bincount(src, weights=flow, minlength=len(problem.node_type))
        assert (out[gc.task_ids] == 1).all()
    if name == "quincy":
        # tasks with arcs of their own: more classes of arcs than the census models' four
        assert max(len(gc.supply) for _p, gc, *_rest in taken) > 4


# -- the grouping itself ------------------------------------------------------------------------


def test_the_classes_are_those_of_escape_cost_and_placement_arcs():
    none = np.zeros(0, np.int64)
    cls, reps = _group_tasks_by_arcs(0, none, none, none, none, none, none, none)
    assert len(cls) == len(reps) == 0
    # six tasks: 0 and 3 alike (one EC arc, escape 9); 1 another escape; 2 another EC cost;
    # 4 and 5 hold the same two machine arcs, listed in another order
    u_eff = np.array([9, 7, 9, 9, 9, 9])
    ect_t, ect_ec, ect_cost = np.array([0, 1, 2, 3]), np.array([5, 5, 5, 5]), np.array([0, 0, 1, 0])
    mac_t, mac_col, mac_cost = np.array([4, 4, 5, 5]), np.array([2, 3, 3, 2]), np.array([6, 8, 8, 6])
    cls, reps = _group_tasks_by_arcs(6, u_eff, mac_t, mac_col, mac_cost, ect_t, ect_ec, ect_cost)
    assert cls[0] == cls[3] and cls[4] == cls[5] and len({cls[0], cls[1], cls[2], cls[4]}) == 4
    assert len(reps) == 4 and sorted(cls[reps].tolist()) == [0, 1, 2, 3]
    # a task with an arc twice is another class than one with it once (their rows merge later)
    cls, reps = _group_tasks_by_arcs(
        2, np.array([9, 9]), none, none, none, np.array([0, 0, 1]), np.array([5, 5, 5]), np.array([0, 0, 0]))
    assert cls[0] != cls[1] and len(reps) == 2


def _fill_problem(tasks, machines, classes=4, slots=8, seed=0):
    """A fill as the class-census models export it: `tasks` tasks of
    `classes` classes, each with an arc to its class's EC and one to the
    unscheduled aggregator; an arc from every EC to every machine at a
    cost of its own; a PU under every machine."""
    rng = np.random.default_rng(seed)
    sink, agg = 1, 2
    ec0 = 3
    m0 = ec0 + classes
    p0 = m0 + machines
    t0 = p0 + machines
    n = t0 + tasks
    nt = np.full(n, int(NodeType.UNSCHEDULED_TASK), np.int8)
    nt[0] = -1
    nt[sink], nt[agg] = int(NodeType.SINK), int(NodeType.JOB_AGGREGATOR)
    nt[ec0:m0] = int(NodeType.EQUIV_CLASS)
    nt[m0:p0] = int(NodeType.MACHINE)
    nt[p0:t0] = int(NodeType.PU)
    excess = np.zeros(n, np.int64)
    excess[t0:] = 1
    excess[sink] = -tasks
    cls = rng.integers(0, classes, tasks)
    tid = np.arange(t0, n)
    mach, pu = np.arange(m0, p0), np.arange(p0, t0)
    src = np.concatenate([
        [0], tid, tid, [agg], np.repeat(np.arange(ec0, m0), machines), mach, pu])
    dst = np.concatenate([
        [0], ec0 + cls, np.full(tasks, agg), [sink], np.tile(mach, classes), pu, np.full(machines, sink)])
    cap = np.concatenate([
        [0], np.ones(2 * tasks, np.int64), [tasks], np.full(classes * machines, slots),
        np.full(2 * machines, slots)])
    cost = np.concatenate([
        [0], np.zeros(tasks, np.int64), np.full(tasks, 2500), [0],
        rng.integers(0, 200, classes * machines), np.zeros(2 * machines, np.int64)])
    return FlowProblem(
        num_nodes=n, node_type=nt, excess=excess, src=src.astype(np.int32), dst=dst.astype(np.int32),
        cap=cap.astype(np.int32), cost=cost.astype(np.int32),
        flow_offset=np.zeros(len(src), np.int32), num_arcs=len(src),
    ), cls


def test_a_small_fill_collapses_to_one_row_a_class_and_solves_to_the_references_objective():
    problem, cls = _fill_problem(60, 12)
    gc, reason = try_collapse(problem)
    assert gc is not None, reason
    assert gc.cost_cm.shape == (4, 12)
    assert sorted(gc.supply.tolist()) == sorted(np.bincount(cls, minlength=4).tolist())
    same_as_the_former_pass(problem, gc)
    auto = AutoSolver(ReferenceSolver())
    assert int(auto.solve(problem).objective) == int(ReferenceSolver().solve(problem).objective)
    assert auto.last_path == "dense" and auto.last_collapse_shape == (60, 4, 128)


def test_a_fill_of_20000_tasks_over_4096_machines_stays_under_200_mb():
    # the former pass built [20,000, 4,096] int64 (655 MB) and a copy of it for the grouping
    problem, cls = _fill_problem(20_000, 4_096)
    tracemalloc.start()
    try:
        gc, reason = try_collapse(problem)
        _now, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert gc is not None, reason
    assert peak < 200 * (1 << 20), peak
    assert gc.cost_cm.shape == (4, 4_096) and int(gc.supply.sum()) == 20_000
    assert sorted(gc.supply.tolist()) == sorted(np.bincount(cls, minlength=4).tolist())
    for tasks in gc.rows_tasks:
        assert len(set(cls[tasks].tolist())) == 1  # a row is one class


# -- the dense problem as the transport gets it ---------------------------------------------------


def _layered(cost, cap, supply, escape=2500):
    solver = LayeredTransportSolver(alpha=8, max_supersteps=1 << 17)
    res = solver.solve_layered(LayeredProblem(
        supply=np.asarray(supply, np.int32), col_cap=np.asarray(cap, np.int32),
        cost_cm=np.asarray(cost, np.int32), unsched_cost=0, ec_cost=0,
        row_unsched_cost=np.full(len(supply), escape, np.int64),
    ))
    return res


def _unmerged(monkeypatch, cost, cap, supply):
    """The problem solved as it is: the solver with its merge switched off."""
    with monkeypatch.context() as m:
        m.setattr(layered, "merge_like_columns", lambda cost, cap: None)
        return _layered(cost, cap, supply)


@pytest.mark.parametrize("seed", range(6))
def test_like_columns_merge_and_the_grants_split_back_within_every_capacity(seed, monkeypatch):
    rng = np.random.default_rng(seed)
    G, M = int(rng.integers(2, 5)), int(rng.integers(40, 60))
    cost = rng.integers(0, 2, (G, M)) * 50  # at most 16 distinct columns: most have a twin
    cap = rng.integers(0, 4, M).astype(np.int64)
    supply = rng.integers(0, 9, G)
    merged_cost, merged_cap, members, starts = merge_like_columns(cost, cap)
    D = len(starts) - 1
    assert merged_cost.shape == (G, M) and merged_cap.sum() == cap.sum() and not merged_cap[D:].any()
    assert sorted(members.tolist()) == np.nonzero(cap > 0)[0].tolist()
    assert len({tuple(c) for c in merged_cost[:, :D].T.tolist()}) == D  # no two alike are left
    for k in range(D):
        cols = members[starts[k]:starts[k + 1]]
        assert (cost[:, cols] == merged_cost[:, k:k + 1]).all() and cap[cols].sum() == merged_cap[k]
    assert not merged_cost[:, D:].any()  # the dead columns: as the padding is
    # the solver merges by itself: the same optimum as the problem solved as it is, the
    # grants within every member's capacity
    whole, merged = _unmerged(monkeypatch, cost, cap, supply), _layered(cost, cap, supply)
    assert whole.objective == merged.objective and whole.num_unsched == merged.num_unsched
    y = np.asarray(merged.y)
    assert y.shape == (G, M) and (y >= 0).all() and (y.sum(axis=0) <= cap).all()
    np.testing.assert_array_equal(y.sum(axis=1), np.asarray(whole.y).sum(axis=1))
    assert int((y * cost).sum()) + 2500 * merged.num_unsched == whole.objective
    # and the split of a merged answer by hand
    by_hand = split_merged_grants(np.asarray(_unmerged(monkeypatch, merged_cost, merged_cap, supply).y),
                                  members, starts, cap)
    assert (by_hand.sum(axis=0) <= cap).all() and int((by_hand * cost).sum()) == int((y * cost).sum())


def test_a_problem_with_no_two_like_columns_is_solved_as_it_is():
    cost = np.arange(4 * 9).reshape(4, 9)  # every column its own
    two = np.full(9, 2, np.int64)
    assert merge_like_columns(cost, two) is None
    cost[:, 1] = cost[:, 0]  # one pair of twins
    assert merge_like_columns(cost, two)[3].tolist() == [0, 2, 3, 4, 5, 6, 7, 8, 9]
    cap = np.array([2, 0, 0, 0, 0, 0, 0, 0, 0], np.int64)
    assert merge_like_columns(np.zeros((4, 9), np.int64), cap) is None  # one column with room
    assert merge_like_columns(np.zeros((4, 9), np.int64), np.zeros(9, np.int64)) is None  # none


def test_the_split_spreads_a_merged_columns_grant_over_its_members_by_their_capacities():
    # one merged column of four members (12, 12, 12 and 6 slots) and one of two; three rows
    cap = np.array([12, 5, 12, 12, 6, 5], np.int64)
    members, starts = np.array([0, 2, 3, 4, 1, 5]), np.array([0, 4, 6])
    y_merged = np.zeros((3, 6), np.int64)
    y_merged[:, 0], y_merged[:, 1] = [10, 7, 4], [0, 3, 0]  # 21 of 42 slots, 3 of 10
    y = split_merged_grants(y_merged, members, starts, cap)
    assert y.sum(axis=0).tolist() == [6, 2, 6, 6, 3, 1]  # half of every member; 2 + 1: the remainder first
    np.testing.assert_array_equal(y[:, [0, 2, 3, 4]].sum(axis=1), [10, 7, 4])
    np.testing.assert_array_equal(y[:, [1, 5]].sum(axis=1), [0, 3, 0])
    # a fill: every slot of the merged column granted, every member full
    y_merged[:, 0] = [20, 12, 10]
    assert split_merged_grants(y_merged, members, starts, cap).sum(axis=0)[[0, 2, 3, 4]].tolist() == [12, 12, 12, 6]


def test_like_columns_packed_into_one_integer_or_compared_row_wise_are_the_same_classes():
    rng = np.random.default_rng(3)
    small = rng.integers(0, 3, (4, 200)) * 700  # 2,101 ** 4 fits 62 bits: packed
    big = np.concatenate([small, rng.integers(0, 2, (3, 200)) * (1 << 26)])  # 7 rows of 2^26: not
    for cost in (small, big):
        first, which = like_columns(cost)
        assert len(which) == 200 and sorted(set(which.tolist())) == list(range(len(first)))
        columns = [tuple(c) for c in cost.T.tolist()]
        assert len(first) == len(set(columns))
        for i, k in enumerate(which.tolist()):
            assert columns[first[k]] == columns[i]


def test_a_round_onto_many_empty_machines_ends_in_tens_of_supersteps():
    # the cell's shape at a tenth: 1,250 machines of 12 slots, 135 empty (they cost every
    # class alike, less than any other), 300 with a slot or two and costs by class, the rest
    # full; 74 pods of 4 classes. Every caller of the transport gets the merge.
    rng = np.random.default_rng(1)
    M = 1250
    cap = np.zeros(M, np.int64)
    cost = rng.integers(80, 200, (4, M))
    cap[-135:] = 12
    cost[:, -135:] = 60
    some = rng.choice(M - 135, 300, replace=False)
    cap[some] = rng.integers(1, 3, 300)
    supply = [20, 18, 19, 17]
    res = _layered(cost, cap, supply)
    assert res.objective == 60 * 74 and res.num_unsched == 0
    assert res.supersteps < 200, res.supersteps  # as they are: thousands


def test_rounds_of_two_three_and_four_rows_run_one_transport_shape(monkeypatch):
    import ksched_tpu.ops as ops

    shapes = []
    inner = ops.transport_solve

    def spy(wS, *a, **kw):
        shapes.append(tuple(wS.shape))
        return inner(wS, *a, **kw)

    monkeypatch.setattr(ops, "transport_solve", spy)
    for classes in (1, 2, 3, 4, 5):
        problem, cls = _fill_problem(40, 12, classes=classes, seed=classes)
        auto = AutoSolver(ReferenceSolver())
        assert int(auto.solve(problem).objective) == int(ReferenceSolver().solve(problem).objective)
        assert auto.last_path == "dense" and auto.last_collapse_shape == (40, classes, 128)
    # one row is the closed form; 2, 3 and 4 rows one program of four rows; 5 one of eight
    assert set(shapes) == {(4, 128), (8, 128)} and shapes.count((8, 128)) >= 1
