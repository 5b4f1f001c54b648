"""Pallas MCMF megakernel (ops/mcmf_pallas.py, solver/mega_solver.py):
BIT-parity with the CSR solver, oracle parity, and the dense -> mega ->
scan-CSR dispatch escalation.

The kernel runs the same synchronous push-relabel schedule as
solver/jax_solver.py `_solve_mcmf` over the same sorted-entry order, so
parity here is exact flow equality superstep-for-superstep — stronger
than the objective parity the ELL suite asserts (MCMF optima are
non-unique, but these two implementations must pick the SAME one).
Tests run the kernel under the Pallas interpreter (CPU env); compiled,
the kernel is refused by the TPU's compiler (chip_smoke.py's `general`
phase states the refusal, ops/mcmf_pallas.mega_compiler_refusal).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.graph_collapse import AutoSolver
from ksched_tpu.solver.jax_solver import (
    JaxSolver,
    _solve_mcmf,
    build_csr_plan,
)
from ksched_tpu.solver.mega_solver import MegaSolver, build_mega_plan

from test_jax_solver import assert_valid_flow, random_scheduling_problem
from test_solver_oracle import make_problem


def _plan_pair(problem):
    src = problem.src.astype(np.int32)
    dst = problem.dst.astype(np.int32)
    csr = build_csr_plan(src, dst, problem.num_nodes)
    return csr, build_mega_plan(csr)


def test_mega_plan_structure():
    rng = np.random.default_rng(3)
    p = random_scheduling_problem(
        rng, num_tasks=40, num_machines=4, slots_per_machine=3
    )
    csr, mega = _plan_pair(p)
    m2 = 2 * len(p.src)
    E = mega.R * mega.L
    assert E >= m2 and E % mega.L == 0
    # live region mirrors the CSR ordering
    np.testing.assert_array_equal(mega.e_arc[:m2], csr.s_arc)
    np.testing.assert_array_equal(mega.e_sign[:m2], csr.s_sign)
    assert (mega.e_sign[m2:] == 0).all()
    # the partner permutation is an involution pairing opposite signs
    # of the same arc (and self on pads)
    ppos = mega.e_prow.astype(np.int64) * mega.L + mega.e_pcol
    np.testing.assert_array_equal(ppos[ppos], np.arange(E))
    live = mega.e_sign != 0
    assert (mega.e_arc[ppos[live]] == mega.e_arc[live]).all()
    assert (mega.e_sign[ppos[live]] == -mega.e_sign[live]).all()
    assert (ppos[~live] == np.nonzero(~live)[0]).all()
    # the partner's source is the entry's destination
    np.testing.assert_array_equal(mega.e_src[ppos[:m2]], csr.s_dst)
    # one start and one end per segment, pad segment included
    n_seg = len(np.unique(csr.s_src)) + (1 if E > m2 else 0)
    assert int(mega.e_hs.sum()) == n_seg
    assert int(mega.e_he.sum()) == n_seg
    # fwd_pos addresses exactly the forward entries
    assert (mega.e_sign[mega.fwd_pos] == 1).all()
    np.testing.assert_array_equal(mega.e_arc[mega.fwd_pos], np.arange(len(p.src)))


@pytest.mark.parametrize("lanes", [None, 8])
def test_kernel_bit_parity_vs_csr_64_nodes(lanes):
    """The fast tier-1 kernel check (64-node scheduling graph): the
    megakernel's flows and superstep counts must equal the CSR
    solver's exactly, warm (eps=1) and cold (full eps schedule).
    lanes=8 shrinks the tile width so the entries span R=31 block
    rows — exercising the cross-block segmented-scan carry the
    production 10k x 1k shape (R=256) relies on; lanes=None is the
    default single-row tiling."""
    from ksched_tpu.ops.mcmf_pallas import mcmf_loop_pallas

    rng = np.random.default_rng(7)
    p = random_scheduling_problem(
        rng, num_tasks=40, num_machines=4, slots_per_machine=3
    )
    assert p.num_nodes <= 64
    n = p.num_nodes
    csr = build_csr_plan(
        p.src.astype(np.int32), p.dst.astype(np.int32), n
    )
    mega = build_mega_plan(csr, lanes)
    if lanes is not None:
        assert mega.R > 1  # the cross-block carry path is live
    cap = jnp.asarray(p.cap.astype(np.int32))
    cost = jnp.asarray(p.cost.astype(np.int32) * np.int32(n))
    supply = jnp.asarray(p.excess.astype(np.int32))
    flow0 = jnp.zeros(len(p.src), jnp.int32)
    csr_dev = tuple(
        jnp.asarray(x)
        for x in (
            csr.s_arc, csr.s_sign, csr.s_src, csr.s_dst,
            csr.s_segstart, csr.s_isstart, csr.inv_order,
            csr.node_first, csr.node_last, csr.node_nonempty,
        )
    )
    mega_dev = tuple(
        jnp.asarray(x)
        for x in (
            mega.e_arc, mega.e_sign, mega.e_src, mega.e_hs, mega.e_he,
            mega.e_prow, mega.e_pcol, mega.fwd_pos,
        )
    )
    max_cost = int(np.abs(p.cost).max())
    for eps0 in (1, max(1, max_cost * n)):
        f_c, _p, s_c, conv_c, ovf_c = _solve_mcmf(
            cap, cost, supply, flow0, jnp.asarray(np.int32(eps0)), *csr_dev,
            alpha=8, max_supersteps=50_000,
        )
        f_m, s_m, conv_m, ovf_m = mcmf_loop_pallas(
            cap, cost, supply, flow0, jnp.asarray(np.int32(eps0)), *mega_dev,
            R=mega.R, L=mega.L, alpha=8, max_supersteps=50_000,
            interpret=True,
        )
        assert bool(conv_c) and bool(conv_m), eps0
        assert not bool(ovf_c) and not bool(ovf_m), eps0
        assert int(s_c) == int(s_m), eps0
        np.testing.assert_array_equal(np.asarray(f_c), np.asarray(f_m))


def test_solver_bit_parity_and_warm_start():
    """End-to-end MegaSolver vs JaxSolver across warm-started rounds:
    identical flows every round, oracle-equal objectives."""
    rng = np.random.default_rng(5)
    p = random_scheduling_problem(
        rng, num_tasks=12, num_machines=3, slots_per_machine=2
    )
    jx = JaxSolver()
    mg = MegaSolver(interpret=True)
    r_j = jx.solve(p)
    r_m = mg.solve(p)
    ref = ReferenceSolver().solve(p)
    assert r_m.objective == ref.objective == r_j.objective
    assert mg.last_supersteps == jx.last_supersteps
    np.testing.assert_array_equal(r_j.flow, r_m.flow)
    assert_valid_flow(p, r_m.flow)

    from ksched_tpu.graph.device_export import FlowProblem

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
    r_j2 = jx.solve(p2)
    r_m2 = mg.solve(p2)
    ref2 = ReferenceSolver().solve(p2)
    assert r_m2.objective == ref2.objective == r_j2.objective
    np.testing.assert_array_equal(r_j2.flow, r_m2.flow)
    # the warm re-solve stays incremental, as for the CSR solver
    assert mg.last_supersteps == jx.last_supersteps


def test_autosolver_escalates_dense_mega_csr():
    """The AutoSolver ladder: a non-collapsible graph inside the VMEM
    budget takes the mega rung; an 'oversized' graph (budget shrunk to
    force it) falls through to scan-CSR; a collapsible graph still
    takes the dense transport."""
    # untyped nodes -> the collapse audit refuses -> general path
    p = make_problem(
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
    )
    want = ReferenceSolver().solve(p).objective

    auto = AutoSolver(JaxSolver(), mega=MegaSolver(interpret=True))
    res = auto.solve(p)
    assert auto.last_path == "mega"
    assert res.objective == want

    tiny = AutoSolver(
        JaxSolver(), mega=MegaSolver(interpret=True, vmem_budget_bytes=64)
    )
    res2 = tiny.solve(p)
    assert tiny.last_path == "csr"
    assert "VMEM" in tiny.last_mega_refusal
    assert res2.objective == want

    no_mega = AutoSolver(JaxSolver())
    res3 = no_mega.solve(p)
    assert no_mega.last_path == "csr"
    assert no_mega.last_mega_refusal == "no megakernel attached"
    assert res3.objective == want


def test_autosolver_mega_refuses_overflow_costs():
    """Costs whose node-count scaling overflows int32 are a fits()
    refusal (the ladder stays total and routes to the fallback rung),
    not an OverflowError out of the mega rung."""
    p = make_problem(
        4, {1: 1, 3: -1}, [(1, 2, 0, 1, 1 << 28), (2, 3, 0, 1, 1)]
    )
    want = ReferenceSolver().solve(p).objective
    auto = AutoSolver(ReferenceSolver(), mega=MegaSolver(interpret=True))
    res = auto.solve(p)
    assert auto.last_path == "csr"
    assert "overflow" in auto.last_mega_refusal
    assert res.objective == want


def test_backend_mega_fallback_delegation():
    """--backend mega is total: a graph the kernel refuses (budget
    forced to zero here) delegates to the attached CSR fallback with
    the same result; without a fallback the refusal raises."""
    from ksched_tpu.solver.select import make_backend

    rng = np.random.default_rng(2)
    p = random_scheduling_problem(
        rng, num_tasks=12, num_machines=3, slots_per_machine=2
    )
    want = ReferenceSolver().solve(p).objective

    from ksched_tpu.ops import get_pallas_mode, set_pallas_mode

    prev = get_pallas_mode()
    try:
        set_pallas_mode("interpret")  # by name: never inferred from the backend
        mg = make_backend("mega")
    finally:
        set_pallas_mode(prev)
    assert isinstance(mg, MegaSolver) and mg.fallback is not None
    mg.interpret = True
    assert mg.solve(p).objective == want

    mg.vmem_budget_bytes = 64  # force the delegation path
    assert not mg.fits(p)
    assert mg.solve(p).objective == want

    bare = MegaSolver(interpret=True, vmem_budget_bytes=64)
    with pytest.raises(RuntimeError, match="VMEM"):
        bare.solve(p)


def test_auto_backend_attaches_mega_under_forced_pallas():
    """make_backend('auto') hangs the mega rung on the ladder exactly
    when Pallas dispatch is live (forced interpret here); in plain CPU
    auto mode the ladder is the historical dense -> CSR."""
    from ksched_tpu.ops import get_pallas_mode, set_pallas_mode
    from ksched_tpu.solver.select import make_backend

    prev = get_pallas_mode()
    try:
        set_pallas_mode("interpret")
        auto = make_backend("auto", fallback=True)
        assert isinstance(auto, AutoSolver)
        assert isinstance(auto.mega, MegaSolver)
        set_pallas_mode("off")
        auto2 = make_backend("auto", fallback=True)
        assert auto2.mega is None
    finally:
        set_pallas_mode(prev)
