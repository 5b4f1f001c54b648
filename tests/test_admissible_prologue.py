"""The prologue's prices leave the shortest-path tree admissible (PR 54).

`_solve_mcmf`'s `tighten` counts every residual row one dearer than it
costs. Costs arrive scaled by the node count and a path has fewer hops
than there are nodes, so the distance it finds is d + h: the exact
distance and, among the shortest paths, the fewest hops. Under
p = -(d + h) and the true costs every residual row has reduced cost
>= -1 (the start is 1-optimal) and every row of the tree has exactly
-1: admissible at eps 1 as it stands. Held here:

- the invariant itself, on the prices the prologue hands the loop
  (`max_supersteps=0`), against a Bellman-Ford of the test's own, over
  the graph families of tests/test_solver_oracle.py and
  tests/test_jax_solver.py in both plan layouts, cold and refit;
- what it buys: a conflict-free trickle round of the served path
  relabels nothing and takes as many supersteps as its path has hops;
- that nothing was paid for it: the objective is ReferenceSolver's on
  every family, cold, refit and down a cold ladder (where `saturate`
  runs at every phase change and leaves rows within [-eps, +eps] alone);
- the consequence's counter: `price_updates` on the RoundRecord is
  `steps // price_update_every`, 0 for a round that ends before the
  first update; the per-layer entry `price_updates_p50` equals its file,
  loads in the three cells whose rung runs the update, and its reader
  finds nothing, without raising, in a program that stamps no such field.
"""

import importlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import observe, spec
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver import ReferenceSolver
from ksched_tpu.solver import jax_solver
from ksched_tpu.solver.base import lower_bound_cost
from ksched_tpu.solver.jax_solver import JaxSolver, _solve_mcmf

from test_csr_entry_state import PROBLEMS, _inputs, _run

_BIG_D = jax_solver._BIG_D


def _prologue(inp, flow0, warm_p):
    """(flow, p) as the phase loop would receive them: no superstep runs."""
    out = _solve_mcmf(
        jnp.asarray(inp["cap"]), jnp.asarray(inp["cost"]), jnp.asarray(inp["supply"]),
        jnp.asarray(flow0), jnp.asarray(np.int32(1)), *inp["plan"],
        warm_p=warm_p, use_warm_p=warm_p is not None,
        max_supersteps=0, slot_stable=inp["slot_stable"],
    )
    flow, p, steps = (np.asarray(x) for x in out[:3])
    assert steps == 0
    return flow.astype(np.int64), p.astype(np.int64)


def _carried(inp):
    """(flow, prices) a round carries into the next, whose costs then
    moved: the refit of tests/test_csr_entry_state.py."""
    m, n = len(inp["cap"]), len(inp["supply"])
    before = dict(inp, cost=inp["cost"] + (np.arange(m, dtype=np.int32) % 3) * np.int32(n))
    prev = _run(_solve_mcmf, before, np.zeros(m, np.int32), 1, None, 0)
    assert prev[3], "the round before did not converge"
    return np.minimum(prev[0], inp["cap"]), jnp.asarray(prev[1])


def _rows_of(inp, flow):
    """(src, dst, scaled cost, residual) of every live plan row."""
    s_arc, s_sign, s_src, s_dst = (np.asarray(x).astype(np.int64) for x in inp["plan"][:4])
    cap, cost = inp["cap"].astype(np.int64), inp["cost"].astype(np.int64)
    live = s_sign != 0
    r = np.where(s_sign > 0, cap[s_arc] - flow[s_arc], flow[s_arc])
    return s_src[live], s_dst[live], (s_sign * cost[s_arc])[live], r[live]


def _distance(n, src, dst, cost, r, deficit, hop):
    """Bellman-Ford of the test's own: the least (cost + `hop` a row)
    from every node to a node short of flow over the residual rows;
    None where there is no path."""
    d = [0 if deficit[v] else None for v in range(n)]
    rows = [(int(u), int(w), int(c)) for u, w, c, res in zip(src, dst, cost, r) if res > 0]
    for _ in range(n):
        moved = False
        for u, w, c in rows:
            if d[w] is not None and (d[u] is None or c + hop + d[w] < d[u]):
                d[u], moved = c + hop + d[w], True
        if not moved:
            return d
    raise AssertionError("a negative residual cycle: not a problem of these families")


@pytest.mark.parametrize("name", PROBLEMS)
def test_a_cold_prologue_is_one_optimal_and_every_tree_row_is_admissible(name):
    _problem, inp = _inputs(name)
    n = len(inp["supply"])
    flow, p = _prologue(inp, np.zeros(len(inp["cap"]), np.int32), None)
    assert not flow.any(), "saturate emptied a row of a 1-optimal start"
    src, dst, cost, r = _rows_of(inp, flow)
    rc = cost + p[src] - p[dst]
    want = _distance(n, src, dst, cost, r, inp["supply"] < 0, hop=1)
    exact = _distance(n, src, dst, cost, r, inp["supply"] < 0, hop=0)
    reaches = np.array([d is not None for d in want])
    assert reaches[inp["supply"] > 0].all(), "a supply with no way out tests nothing"
    # the prices are -(distance + hops) wherever a deficit can be reached,
    # far below every one of those elsewhere
    assert [int(-p[v]) for v in np.flatnonzero(reaches)] == [d for d in want if d is not None]
    assert (p[~reaches] < -(_BIG_D // 2)).all()
    # costs come scaled by n and a path has fewer than n hops: one integer
    # holds both, the exact distance first
    assert all(d - d % n == e for d, e in zip(want, exact) if d is not None)
    # 1-optimal: no residual row between nodes that reach a deficit lies below -1
    between = (r > 0) & reaches[src]
    assert between.any() and (rc[between] >= -1).all()
    # and each such node short of no flow holds a row at exactly -1: its tree arc
    tree = np.zeros(n, bool)
    tree[src[(r > 0) & (rc == -1) & reaches[dst]]] = True
    need = reaches & (inp["supply"] >= 0)
    assert need.any() and tree[need].all()


@pytest.mark.parametrize("name", PROBLEMS)
def test_a_refit_prologue_is_one_optimal_and_keeps_the_flow_it_carried(name):
    """The carried state of a round whose costs then moved (the refit
    of tests/test_csr_entry_state.py): the sweeps lower a price only
    where a residual row lies below -1, and `saturate` leaves every row
    within [-1, +1] as it stands."""
    _problem, inp = _inputs(name)
    flow0, warm_p = _carried(inp)
    flow, p = _prologue(inp, flow0, warm_p)
    src, dst, cost, r = _rows_of(inp, flow)
    rc = cost + p[src] - p[dst]
    assert (rc[r > 0] >= -1).all()
    # a row the prologue emptied or filled lay outside [-1, +1] under its prices
    s_arc, s_sign = (np.asarray(x)[np.asarray(inp["plan"][1]) != 0] for x in inp["plan"][:2])
    changed = (flow != flow0)[s_arc] & (s_sign > 0)
    assert (np.abs(rc[changed]) > 1).all()


@pytest.mark.parametrize("start", ["cold", "refit", "ladder"])
@pytest.mark.parametrize("name", PROBLEMS)
def test_the_objective_is_the_references_cold_refit_and_down_a_cold_ladder(name, start):
    problem, inp = _inputs(name)
    flow0, warm_p, eps = np.zeros(len(inp["cap"]), np.int32), None, 1
    if start == "refit":
        flow0, warm_p = _carried(inp)
    if start == "ladder":
        eps = max(1, int(np.abs(inp["cost"]).max()))
    flow, _p, steps, converged, p_overflow = _run(_solve_mcmf, inp, flow0, eps, warm_p, 0)
    assert converged and not p_overflow and (start == "refit" or steps > 0)
    flow = flow[: len(problem.src)]
    objective = int((flow.astype(np.int64) * problem.cost.astype(np.int64)).sum())
    assert objective + lower_bound_cost(problem) == ReferenceSolver().solve(problem).objective


def test_a_chain_moves_a_hop_a_superstep_and_relabels_nothing():
    """One unit down a path of seven arcs: seven supersteps, each a
    push, where exact distances took fourteen (a relabel before each)."""
    from test_solver_oracle import make_problem

    hops = 7
    problem = make_problem(
        hops + 2, {1: 1, hops + 1: -1}, [(v, v + 1, 0, 1, v) for v in range(1, hops + 1)]
    )
    solver = JaxSolver(telemetry=32)
    res = solver.solve(problem)
    assert res.objective == ReferenceSolver().solve(problem).objective
    tel = solver.last_telemetry
    assert solver.last_supersteps == hops
    assert tel.col("pushed").tolist() == [1] * hops and not tel.col("relabels").any()


def test_a_conflict_free_round_of_sized_pods_takes_its_paths_five_hops(monkeypatch):
    """The `k8s_requests` stream of tests/test_active_superstep.py: five
    arrivals of one size on a cluster with room (task -> size EC ->
    machine -> core -> PU -> sink). The parent read `relabels`
    [5, 0, 1, 0, 1, 0, 1, 0, 1, 0] and ten supersteps."""
    monkeypatch.setattr(jax_solver, "_ACTIVE_MIN_PLAN_ROWS", 4_096)
    from test_k8s_requests_model import Stream

    s = Stream(100, 9, backend="jax", tracer=RoundTracer())
    rung = s.svc.ladder.primary
    assert isinstance(rung, JaxSolver) and rung.price_update_every == 8
    rung.telemetry = 64
    s.round(np.zeros(2000, int))
    for _ in range(3):
        objective, served, want, native = s.round([0] * 5, 3)
        assert objective == served == want == native
        tel = rung.last_telemetry
        assert rung.last_supersteps == rung.last_sparse_supersteps == 5
        assert tel.col("relabels").tolist() == [0] * 5
        assert tel.col("pushed").tolist() == [5] * 5
        assert tel.col("active").tolist() == [5, 1, 1, 1, 1]
        rec = s.svc.tracer.records[-1]
        # the round ended before the first update could fire
        assert (rec.solver_work, rec.price_updates, rung.last_price_updates) == (5, 0, 0)


def test_a_conflict_free_round_of_spread_pods_takes_its_paths_six_hops():
    """The zonespread stream of tests/test_k8s_zonespread.py, two EC hops
    deep (task -> workload EC -> zone EC -> machine -> core -> PU ->
    sink): the fill of 60 pods and every trickle round after it."""
    from test_k8s_zonespread import Stream

    s = Stream(30, 8, 3, 2, 7, "jax", tracer=RoundTracer())
    rung = s.svc.ladder.primary
    assert isinstance(rung, JaxSolver) and rung.price_update_every == 0
    rung.telemetry = 64
    for r in range(5):
        arrivals = 60 if r == 0 else 4
        ours, reference = s.round(arrivals, 0 if r == 0 else 3)
        assert ours == reference and not s.backlog
        tel = rung.last_telemetry
        assert rung.last_supersteps == 6
        assert tel.col("relabels").tolist() == [0] * 6
        assert tel.col("pushed").tolist() == [arrivals] * 6
        assert s.svc.tracer.records[-1].price_updates == 0  # the rung runs none


def test_a_round_that_outlasts_the_interval_stamps_the_updates_that_fired():
    """`k8s_requests` says its routes differ in cost, so the rung updates
    its prices every eighth superstep: forty pods of four sizes onto ten
    crowded machines contend, and the round pays an update for every
    eight supersteps it took."""
    from test_k8s_requests_model import Stream

    s = Stream(10, 9, backend="jax", tracer=RoundTracer())
    rung = s.svc.ladder.primary
    assert rung.price_update_every == 8
    s.round(s.rng.integers(0, 4, 60))
    s.round([])
    seen = []
    for _ in range(3):
        objective, served, want, native = s.round(s.rng.integers(0, 4, 40), 10)
        assert objective == served == want == native
        rec = s.svc.tracer.records[-1]
        assert rec.solver_work == rung.last_supersteps
        assert rec.price_updates == rung.last_price_updates == rec.solver_work // 8
        assert s.svc.ladder.last_price_updates == rec.price_updates
        seen.append(rec.price_updates)
    assert max(seen) >= 1, "no round crossed its eighth superstep: the case tests nothing"


def test_under_preemption_the_count_follows_the_supersteps_and_the_round_still_ends():
    """The full cluster of tests/test_k8s_priority.py, where units are
    displaced and not placed: the same prologue, the same rule."""
    from test_k8s_priority import Stream

    s = Stream(30, 4, 2, 12, "jax", tracer=RoundTracer())
    rung = s.svc.ladder.primary
    assert rung.price_update_every == 8
    s.round([0] * 120)
    for arrivals in ([1], [1] * 10, [1] * 40, []):
        ours, reference = s.round(arrivals)
        assert ours == reference
        rec = s.svc.tracer.records[-1]
        assert rec.price_updates == rung.last_supersteps // 8
        assert rung.last_supersteps <= 16 * rung.price_update_every
    assert s.svc.ladder.degradations_total == 0


def test_a_solver_without_the_update_counts_none_and_one_with_it_counts_every_interval():
    from test_solver_oracle import make_problem

    problem = make_problem(
        4, {1: 2, 3: -2}, [(1, 3, 0, 9, 10), (1, 2, 0, 1, 2), (2, 3, 0, 9, 3)]
    )
    plain = JaxSolver()
    plain.solve(problem)
    assert plain.last_supersteps > 0 and plain.last_price_updates == 0
    every = JaxSolver(price_update_every=1)
    res = every.solve(problem)
    assert res.objective == ReferenceSolver().solve(problem).objective
    assert every.last_price_updates == every.last_supersteps > 0


# ---------------------------------------------------------------------------
# the per-layer metric that reads the counter
# ---------------------------------------------------------------------------

BENCH = spec.load_benchmark()
NAME = "price_updates_p50"
PARAMS = {"field": "price_updates", "reduce": "p50"}
CELLS = ["k8s-5000-preemption.rollout", "gtrace-12500-quincy.trickle", "k8s-5000-requests.trickle"]


def _entry(name):
    return next(m for m in BENCH["per_layer"] if m["name"] == name)


def test_the_entry_is_appended_equals_its_file_and_lists_the_cells_whose_rung_updates():
    entry = _entry(NAME)
    with open(os.path.join(spec.ROOT, "benchmarks", "layer_metrics", NAME + ".json")) as f:
        own = json.load(f)
    assert {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (own["reader"], own["params"]) == ("round_field", PARAMS)
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
        "updates", "lower", "program_counter", "bind_p50_ms",
    )
    assert entry["layer"] == _entry("supersteps_p50")["layer"] == "solver rungs"
    assert entry["workloads"] == CELLS
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) > names.index("array_unconverged_rounds")  # after PR 53's last
    assert spec.check_names(BENCH) == [] and len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_loads_the_metric_by_name_if_its_rung_runs_the_update(cell):
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert (NAME in loaded) == (cell in CELLS)
    if cell in CELLS:
        # the configuration is what turns the update on: preemption, or a
        # model whose routes differ in cost
        argv = " ".join(spec.load_cell(cell).config["argv"])
        assert "--preemption" in argv or "quincy" in argv or "k8s_requests" in argv


def test_the_reader_reads_the_records_and_nothing_from_a_program_without_the_field():
    read = importlib.import_module("benchmarks.readers.round_field").read
    records = [
        {"num_scheduled": 150000, "solver_work": 2, "price_updates": 0},
        {"num_scheduled": 75, "solver_work": 40, "price_updates": 5},
        {"num_scheduled": 19, "solver_work": 12, "price_updates": 1},
        {"num_scheduled": 20, "solver_work": 6, "price_updates": 0},
        {"num_scheduled": 0, "solver_work": 9, "price_updates": 1},  # bound nothing: no sample
    ]

    def observation(recs):
        return observe.Observation(
            device_kind="cpu", rounds=[], records=recs, client={}, counters={}, shapes={},
            trace=None, rehearsal=True,
        )

    assert read(PARAMS, observation(records)) == 0.5
    parent = [{k: v for k, v in r.items() if k != "price_updates"} for r in records]
    assert read(PARAMS, observation(parent)) is None
    assert read(PARAMS, observation([])) is None
