"""JAX push-relabel solver: parity vs the exact CPU oracle.

MCMF optima are non-unique, so parity = identical objective cost (the
well-defined invariant); scheduler-level placement parity is asserted in
test_scheduler_backends.py under a deterministic tie-break.

The parity matrices run two rungs: `jax` is JaxSolver alone, `auto` is
the ladder `make_backend("auto")` builds, on problems whose nodes are
untyped, so the collapse audit refuses them and the general-graph rung
answers (`last_path == "csr"`, with the audit's reason).
"""

import functools

import numpy as np
import pytest

from ksched_tpu.graph.device_export import FlowProblem
from ksched_tpu.solver import ReferenceSolver
from ksched_tpu.solver.graph_collapse import AutoSolver
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.solver.select import make_backend

from test_solver_oracle import make_problem

RUNGS = ["jax", "auto"]


def make_rung(rung):
    return JaxSolver() if rung == "jax" else make_backend("auto")


def assert_general_rung_answered(rung, solver):
    if rung == "auto":
        assert solver.last_path == "csr"
        assert solver.last_refusal


def assert_valid_flow(p: FlowProblem, flow: np.ndarray):
    assert (flow >= 0).all() and (flow <= p.cap).all()
    n = p.num_nodes
    out_ = np.zeros(n, np.int64)
    in_ = np.zeros(n, np.int64)
    np.add.at(out_, p.src, flow)
    np.add.at(in_, p.dst, flow)
    assert ((p.excess - out_ + in_) == 0).all()


@pytest.mark.parametrize(
    "case", ["single", "cheap", "split", "assign", "escape", "negative", "lower_bound"]
)
@pytest.mark.parametrize("rung", RUNGS)
def test_small_parity(rung, case):
    problems = {
        "single": make_problem(4, {1: 1, 3: -1}, [(1, 2, 0, 1, 2), (2, 3, 0, 1, 3)]),
        "cheap": make_problem(
            4, {1: 1, 3: -1}, [(1, 3, 0, 1, 10), (1, 2, 0, 1, 2), (2, 3, 0, 1, 3)]
        ),
        "split": make_problem(
            4, {1: 2, 3: -2}, [(1, 3, 0, 9, 10), (1, 2, 0, 1, 2), (2, 3, 0, 9, 3)]
        ),
        "assign": make_problem(
            8,
            {1: 1, 2: 1, 6: -2},
            [
                (1, 3, 0, 1, 2),
                (2, 3, 0, 1, 2),
                (3, 4, 0, 1, 0),
                (3, 5, 0, 1, 4),
                (4, 6, 0, 1, 0),
                (5, 6, 0, 1, 0),
                (1, 7, 0, 1, 50),
                (2, 7, 0, 1, 50),
                (7, 6, 0, 2, 0),
            ],
        ),
        "escape": make_problem(
            8,
            {1: 1, 2: 1, 6: -2},
            [
                (1, 3, 0, 1, 2),
                (2, 3, 0, 1, 2),
                (3, 4, 0, 1, 0),
                (4, 6, 0, 1, 0),
                (1, 7, 0, 1, 5),
                (2, 7, 0, 1, 5),
                (7, 6, 0, 2, 0),
            ],
        ),
        # test_solver_oracle.py's two: a negative cost (optimum 1), and a
        # running arc whose lower bound forces the dearer path (optimum 7)
        "negative": make_problem(
            4, {1: 1, 3: -1}, [(1, 2, 0, 1, -2), (2, 3, 0, 1, 3), (1, 3, 0, 1, 5)]
        ),
        "lower_bound": make_problem(
            4, {1: 1, 3: -1}, [(1, 2, 1, 1, 7), (2, 3, 0, 1, 0), (1, 3, 0, 1, 1)]
        ),
    }
    p = problems[case]
    ref = ReferenceSolver().solve(p)
    solver = make_rung(rung)
    got = solver.solve(p)
    assert_general_rung_answered(rung, solver)
    assert_valid_flow(p, got.flow)
    assert got.objective == ref.objective


def random_scheduling_problem(rng, num_tasks, num_machines, slots_per_machine, num_jobs=3):
    """Build a random quincy-style layered instance directly in arrays:
    tasks -> (unsched | EC) ; EC -> machines ; machine -> PUs ; PU -> sink."""
    # node ids: 1..T tasks, then EC, then machines, PUs, unscheds, sink
    nid = 1
    tasks = list(range(nid, nid + num_tasks)); nid += num_tasks
    ec = nid; nid += 1
    machines = list(range(nid, nid + num_machines)); nid += num_machines
    pus = []
    for _ in range(num_machines):
        pus.append(list(range(nid, nid + slots_per_machine)))
        nid += slots_per_machine
    unscheds = list(range(nid, nid + num_jobs)); nid += num_jobs
    sink = nid; nid += 1

    arcs = []
    excess = {}
    for i, t in enumerate(tasks):
        excess[t] = 1
        job = i % num_jobs
        arcs.append((t, unscheds[job], 0, 1, int(rng.integers(3, 10))))
        arcs.append((t, ec, 0, 1, int(rng.integers(0, 5))))
        # occasional direct preference arc to a machine
        if rng.random() < 0.3:
            m = int(rng.integers(0, num_machines))
            arcs.append((t, machines[m], 0, 1, int(rng.integers(0, 3))))
    for m in range(num_machines):
        arcs.append((ec, machines[m], 0, slots_per_machine, int(rng.integers(0, 4))))
        for pu in pus[m]:
            arcs.append((machines[m], pu, 0, 1, 0))
            arcs.append((pu, sink, 0, 1, 0))
    for u in unscheds:
        arcs.append((u, sink, 0, num_tasks, 0))
    excess[sink] = -num_tasks
    return make_problem(nid, excess, arcs)


def test_random_parity_seed0():
    rng = np.random.default_rng(0)
    for trial in range(8):
        p = random_scheduling_problem(
            rng,
            num_tasks=int(rng.integers(3, 25)),
            num_machines=int(rng.integers(1, 6)),
            slots_per_machine=int(rng.integers(1, 4)),
        )
        ref = ReferenceSolver().solve(p)
        jx = JaxSolver().solve(p)
        assert jx.objective == ref.objective, f"trial {trial}"
        assert_valid_flow(p, jx.flow)


@functools.lru_cache(maxsize=None)
def _seed11_problems():
    rng = np.random.default_rng(11)
    return [
        random_scheduling_problem(
            rng,
            num_tasks=int(rng.integers(3, 40)),
            num_machines=int(rng.integers(1, 6)),
            slots_per_machine=int(rng.integers(1, 4)),
        )
        for _ in range(8)
    ]


@pytest.mark.parametrize("trial", range(8))
@pytest.mark.parametrize("rung", RUNGS)
def test_random_parity(rung, trial):
    p = _seed11_problems()[trial]
    ref = ReferenceSolver().solve(p)
    solver = make_rung(rung)
    got = solver.solve(p)
    assert_general_rung_answered(rung, solver)
    assert got.objective == ref.objective
    assert_valid_flow(p, got.flow)


@pytest.mark.parametrize("rung", RUNGS)
def test_warm_start_incremental(rung):
    rng = np.random.default_rng(1)
    p = random_scheduling_problem(rng, num_tasks=10, num_machines=3, slots_per_machine=2)
    solver = make_rung(rung)
    r1 = solver.solve(p)
    ref1 = ReferenceSolver().solve(p)
    assert r1.objective == ref1.objective
    cold_steps = solver.last_supersteps

    # Perturb: raise one unsched cost and re-solve warm.
    p2 = FlowProblem(
        num_nodes=p.num_nodes,
        excess=p.excess.copy(),
        node_type=p.node_type,
        src=p.src,
        dst=p.dst,
        cap=p.cap.copy(),
        cost=p.cost.copy(),
        flow_offset=p.flow_offset,
        num_arcs=p.num_arcs,
    )
    p2.cost[0] += 2
    r2 = solver.solve(p2)
    ref2 = ReferenceSolver().solve(p2)
    assert r2.objective == ref2.objective
    # warm restart should not be wildly more expensive than cold
    assert solver.last_supersteps <= max(cold_steps * 2, 50)


@pytest.mark.parametrize("ladder", [False, True], ids=["jax", "auto_over_jax"])
def test_cost_whose_scaling_overflows_int32_is_refused_by_name(ladder):
    """A cost of 2^28 on a 4-node graph: scan-CSR scales costs by the
    node count in int32 and says so instead of wrapping; the ladder
    routes the graph to that rung and lets its refusal through."""
    p = make_problem(4, {1: 1, 3: -1}, [(1, 2, 0, 1, 1 << 28), (2, 3, 0, 1, 1)])
    solver = AutoSolver(JaxSolver()) if ladder else JaxSolver()
    with pytest.raises(OverflowError, match="scaled costs overflow int32"):
        solver.solve(p)
    if ladder:
        assert solver.last_path == "csr"
