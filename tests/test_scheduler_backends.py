"""Scheduler-level backend parity: the JAX push-relabel backend must
produce placements equivalent to the exact CPU oracle through the full
event loop (equal placement counts and equal flow objective every round
— MCMF optima are non-unique so individual assignments may differ)."""

import numpy as np

from ksched_tpu.data import TaskState
from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.utils import seed_rng


def drive(backend, seed=123):
    seed_rng(seed)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=3, num_cores=2, pus_per_core=1, max_tasks_per_pu=1, backend=backend
    )
    trace = []
    add_job(sched, jmap, tmap, num_tasks=4)
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    trace.append(("round1", n, len(sched.get_task_bindings())))

    add_job(sched, jmap, tmap, num_tasks=2)
    n, _ = sched.schedule_all_jobs()
    trace.append(("round2", n, len(sched.get_task_bindings())))

    running = sorted(
        (td for td in tmap.unsafe_get().values() if td.state == TaskState.RUNNING),
        key=lambda td: td.uid,
    )[:2]
    for td in running:
        sched.handle_task_completion(td)
    n, _ = sched.schedule_all_jobs()
    trace.append(("round3", n, len(sched.get_task_bindings())))
    n, _ = sched.schedule_all_jobs()
    trace.append(("round4", n, len(sched.get_task_bindings())))
    return trace


def test_jax_backend_matches_oracle_through_scheduler():
    ref_trace = drive(None)  # default ReferenceSolver
    jax_trace = drive(JaxSolver())
    assert ref_trace == jax_trace


def test_jax_backend_incremental_rounds_stay_consistent():
    seed_rng(99)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=4, num_cores=1, pus_per_core=2, max_tasks_per_pu=1, backend=JaxSolver()
    )
    placed_total = 0
    for i in range(6):
        add_job(sched, jmap, tmap, num_tasks=2)
        n, _ = sched.schedule_all_jobs()
        placed_total += n
        live = len(sched.gm.task_to_node)
        assert sched.gm.sink_node.excess == -live
    assert placed_total == 8  # 8 slots, 12 tasks submitted
    assert len(sched.get_task_bindings()) == 8


# ---------------------------------------------------------------------------
# automatic dense-vs-CSR dispatch (solver/graph_collapse.py AutoSolver)
# ---------------------------------------------------------------------------


def drive_obj(backend, seed=123, preemption=False, cost_model_factory=None):
    """drive() plus the per-round solver objective (optimality probe)."""
    from ksched_tpu.drivers import build_cluster

    seed_rng(seed)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=3, num_cores=2, pus_per_core=1, max_tasks_per_pu=1,
        backend=backend, preemption=preemption,
        cost_model_factory=cost_model_factory,
    )
    trace = []
    add_job(sched, jmap, tmap, num_tasks=4)
    n, _ = sched.schedule_all_jobs()
    trace.append((n, len(sched.get_task_bindings()),
                  sched.solver.last_result.objective))
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    trace.append((n, len(sched.get_task_bindings()),
                  sched.solver.last_result.objective))
    running = sorted(
        (td for td in tmap.unsafe_get().values()
         if td.state == TaskState.RUNNING),
        key=lambda td: td.uid,
    )[:2]
    for td in running:
        sched.handle_task_completion(td)
    n, _ = sched.schedule_all_jobs()
    trace.append((n, len(sched.get_task_bindings()),
                  sched.solver.last_result.objective))
    return trace, sched


def test_auto_backend_goes_dense_and_matches_oracle():
    """Collapsible graphs (the trivial model's whole lifecycle,
    including lower-bound-folded pinned tasks) ride the dense transport
    with placements AND objectives identical to the CSR oracle."""
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    ref_trace, _ = drive_obj(None)
    auto = AutoSolver(ReferenceSolver())
    auto_trace, _ = drive_obj(auto)
    assert auto.last_path == "dense", auto.last_refusal
    assert auto_trace == ref_trace


def test_auto_backend_binding_interior_ec_routes_csr():
    """A policy with a BINDING interior EC capacity — the one structure
    the dense collapse cannot express (docs/solver_coverage.md) — must
    route to the CSR backend automatically, with the CSR result's
    optimality intact (same trace as the pure oracle)."""
    from typing import List, Tuple

    from ksched_tpu.costmodels import TrivialCostModel
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    JOB_EC, RACK_EC = 881_001, 881_002

    class BindingChainModel(TrivialCostModel):
        """task -> JOB_EC -> RACK_EC -> machines with a chain arc that
        CAN bind (cap 2 < the job's 4 tasks)."""

        def get_task_equiv_classes(self, task_id: int) -> List[int]:
            return [JOB_EC]

        def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
            return [RACK_EC] if ec == JOB_EC else []

        def equiv_class_to_equiv_class(self, ec1: int, ec2: int):
            return 1, 2  # cost 1, capacity 2: BINDS under 4 tasks

        def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
            return list(self._machines) if ec == RACK_EC else []

        def task_to_equiv_class_aggregator(self, task_id: int, ec: int):
            return 2

    ref_trace, _ = drive_obj(None, cost_model_factory=BindingChainModel)
    auto = AutoSolver(ReferenceSolver())
    auto_trace, _ = drive_obj(auto, cost_model_factory=BindingChainModel)
    assert auto.last_path == "csr"
    assert "bind" in auto.last_refusal, auto.last_refusal
    assert auto_trace == ref_trace

    # the CHAIN-FED variant: ample first hop, binding cap on the
    # downstream EC's machine arcs — the r4 review's counterexample
    # (an inflow bound counting only direct task arcs would see 0 at
    # the chain-fed EC and wave the binding cap through). The audit is
    # per-solve: round 1 (4 tasks vs cap-1 arcs) must refuse; later
    # rounds with a small backlog may legitimately collapse.
    class BindingDownstreamModel(BindingChainModel):
        def equiv_class_to_equiv_class(self, ec1, ec2):
            return 1, 64  # ample chain

        def equiv_class_to_resource_node(self, ec, resource_id):
            return 1, 1  # cap 1 per machine arc: BINDS under 4 tasks

    ref2, _ = drive_obj(None, cost_model_factory=BindingDownstreamModel)
    auto2 = AutoSolver(ReferenceSolver())
    auto2_trace, sched2 = drive_obj(
        auto2, cost_model_factory=BindingDownstreamModel
    )
    assert auto2_trace == ref2
    # replay round 1's shape directly: fresh job, binding caps
    from ksched_tpu.utils import seed_rng as _seed

    _seed(123)
    from ksched_tpu.drivers import build_cluster as _bc

    auto3 = AutoSolver(ReferenceSolver())
    s3, _r, j3, t3, _root = _bc(
        num_machines=3, num_cores=2, pus_per_core=1, max_tasks_per_pu=1,
        backend=auto3, cost_model_factory=BindingDownstreamModel,
    )
    add_job(s3, j3, t3, num_tasks=4)
    s3.schedule_all_jobs()
    assert auto3.last_path == "csr"
    assert "bind" in auto3.last_refusal, auto3.last_refusal


def test_auto_backend_keep_mode_routes_csr():
    """Preemption-on (keep-arcs) graphs carry per-task running arcs to
    leaves — outside the dense shape — and must route to CSR once
    tasks are running."""
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    ref_trace, _ = drive_obj(None, preemption=True)
    auto = AutoSolver(ReferenceSolver())
    auto_trace, _ = drive_obj(auto, preemption=True)
    assert auto.last_path == "csr"
    assert auto_trace == ref_trace


def test_try_collapse_structural_refusals():
    """Direct structural edge cases of the collapse audit: a diamond
    below one machine (double-counted capacity / non-tree), and a
    machine whose two sink paths carry different total costs, must
    both REFUSE — not crash, not collapse."""
    from ksched_tpu.graph.device_export import FlowProblem
    from ksched_tpu.graph.flowgraph import NodeType
    from ksched_tpu.solver.graph_collapse import try_collapse

    def make(node_types, arcs, excesses):
        """node ids start at 1 (row 0 padding)."""
        N = len(node_types) + 1
        nt = np.full(N, -1, np.int8)
        ex = np.zeros(N, np.int64)
        for i, t in enumerate(node_types, start=1):
            nt[i] = int(t)
        for i, e in excesses.items():
            ex[i] = e
        src = np.array([a[0] for a in arcs], np.int32)
        dst = np.array([a[1] for a in arcs], np.int32)
        cap = np.array([a[2] for a in arcs], np.int32)
        cost = np.array([a[3] for a in arcs], np.int32)
        return FlowProblem(
            num_nodes=N, excess=ex, node_type=nt, src=src, dst=dst,
            cap=cap, cost=cost,
            flow_offset=np.zeros(len(arcs), np.int32),
            num_arcs=len(arcs),
        )

    T = NodeType
    # nodes: 1=sink, 2=task, 3=agg, 4=machine, 5=PU-a, 6=PU-b
    base_types = [T.SINK, T.UNSCHEDULED_TASK, T.JOB_AGGREGATOR,
                  T.MACHINE, T.PU, T.PU]

    # diamond: machine -> PU-a twice (two parallel arcs into the same
    # subtree) — capacity must NOT double-count; audit refuses
    p = make(
        base_types,
        [(2, 3, 1, 7), (3, 1, 4, 0), (2, 4, 1, 2),
         (4, 5, 1, 0), (4, 5, 1, 0), (5, 1, 1, 0)],
        {2: 1, 1: -1},
    )
    gc, reason = try_collapse(p)
    assert gc is None and "non-tree" in reason, reason

    # non-uniform path costs: machine -> PU-a (cost 0) -> sink and
    # machine -> PU-b (cost 3) -> sink give the column two different
    # totals; audit refuses
    p = make(
        base_types,
        [(2, 3, 1, 7), (3, 1, 4, 0), (2, 4, 1, 2),
         (4, 5, 1, 0), (4, 6, 1, 3), (5, 1, 1, 0), (6, 1, 1, 0)],
        {2: 1, 1: -1},
    )
    gc, reason = try_collapse(p)
    assert gc is None and "non-uniform" in reason, reason

    # the well-formed twin of the same shape COLLAPSES (sanity: the
    # refusals above are about the defects, not the harness)
    p = make(
        base_types,
        [(2, 3, 1, 7), (3, 1, 4, 0), (2, 4, 1, 2),
         (4, 5, 1, 0), (4, 6, 1, 0), (5, 1, 1, 0), (6, 1, 1, 0)],
        {2: 1, 1: -1},
    )
    gc, reason = try_collapse(p)
    assert gc is not None, reason
    assert gc.col_cap.tolist() == [2]  # two PU slots under one machine
    assert gc.row_unsched.tolist() == [7]


def test_try_collapse_refuses_pathologically_deep_subtree():
    """A machine subtree deeper than the Python recursion limit must
    REFUSE ('graph too deep'), not escape as RecursionError — the
    refusal contract says every unauditable input falls back to CSR."""
    import sys

    from ksched_tpu.graph.device_export import FlowProblem
    from ksched_tpu.graph.flowgraph import NodeType
    from ksched_tpu.solver.graph_collapse import try_collapse

    T = NodeType
    depth = sys.getrecursionlimit() + 200
    # nodes: 1=sink, 2=task, 3=agg, 4=machine, 5..5+depth-1 = PU chain
    node_types = [T.SINK, T.UNSCHEDULED_TASK, T.JOB_AGGREGATOR, T.MACHINE]
    node_types += [T.PU] * depth
    N = len(node_types) + 1
    nt = np.full(N, -1, np.int8)
    for i, t in enumerate(node_types, start=1):
        nt[i] = int(t)
    ex = np.zeros(N, np.int64)
    ex[2], ex[1] = 1, -1
    arcs = [(2, 3, 1, 7), (3, 1, 4, 0), (2, 4, 1, 2), (4, 5, 1, 0)]
    for i in range(depth - 1):
        arcs.append((5 + i, 5 + i + 1, 1, 0))
    arcs.append((5 + depth - 1, 1, 1, 0))
    p = FlowProblem(
        num_nodes=N, excess=ex, node_type=nt,
        src=np.array([a[0] for a in arcs], np.int32),
        dst=np.array([a[1] for a in arcs], np.int32),
        cap=np.array([a[2] for a in arcs], np.int32),
        cost=np.array([a[3] for a in arcs], np.int32),
        flow_offset=np.zeros(len(arcs), np.int32),
        num_arcs=len(arcs),
    )
    gc, reason = try_collapse(p)
    assert gc is None and "too deep" in reason, reason


def test_auto_solver_reports_csr_supersteps_of_zero():
    """A CSR fallback whose solve legitimately took 0 supersteps must
    report 0 — not fall through to a stale last_iterations value."""
    from ksched_tpu.solver.graph_collapse import AutoSolver

    class FakeCsr:
        last_supersteps = 0
        last_iterations = 99  # stale, differently-scaled

        def reset(self):
            pass

        def solve(self, problem):
            return "fake-result"

    from ksched_tpu.graph.device_export import FlowProblem
    from ksched_tpu.graph.flowgraph import NodeType

    # two sinks: the audit refuses instantly, routing to the fake CSR
    nt = np.full(3, -1, np.int8)
    nt[1] = nt[2] = int(NodeType.SINK)
    p = FlowProblem(
        num_nodes=3, excess=np.zeros(3, np.int64), node_type=nt,
        src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
        cap=np.zeros(0, np.int32), cost=np.zeros(0, np.int32),
        flow_offset=np.zeros(0, np.int32), num_arcs=0,
    )
    auto = AutoSolver(FakeCsr())
    assert auto.solve(p) == "fake-result"
    assert auto.last_path == "csr"
    assert auto.last_supersteps == 0
