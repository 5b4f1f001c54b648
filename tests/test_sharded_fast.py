"""Fast (tier-1) sharded coverage on a 2-device virtual mesh.

The original sharded suites (test_sharded_solver / test_sharded_
transport) compile 8-way shard_map programs and are `slow`-marked, so
the default tier-1 wall never exercised shard_map at all. This module
keeps the multi-chip rung inside the wall: small-bucket bit-parity of
the slot-stable sharded solve against the single-chip scan-CSR arm,
delta-sized resident rounds through the per-shard routed plan scatter,
the AutoSolver HBM fitting gate, and ladder degradation off the
sharded rung — all on a 2-device mesh where the compiles stay cheap.
"""

import warnings

import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from test_slot_plan import SCRIPT, _build_graph, _churn_round

from ksched_tpu.graph.device_export import (
    DeviceGraphState,
    DeviceResidentState,
)
from ksched_tpu.parallel.sharded_solver import (
    ShardedJaxSolver,
    csr_working_set_bytes,
    scan_csr_fits_hbm,
    sharded_entry_extent,
    sharded_fits_hbm,
    sharded_shard_bytes,
)
from ksched_tpu.runtime.integrity import FP_PLAN_ARRAYS, host_fingerprint
from ksched_tpu.solver.jax_solver import JaxSolver


@pytest.fixture(scope="module")
def mesh2():
    devs = jax.devices()
    assert len(devs) >= 2, "conftest should provide 8 virtual CPU devices"
    return Mesh(np.array(devs[:2]), ("x",))


def _drive(make_solver, *, resident=False, sharded_resident_mesh=None,
           rounds=6, tasks=24, machines=5):
    g, _sink, machine_ids, task_ids = _build_graph(tasks, machines)
    st = DeviceGraphState()
    st.full_build(g)
    res = None
    if resident:
        res = DeviceResidentState(st)
        if sharded_resident_mesh is not None:
            res.enable_sharded_plan(sharded_resident_mesh, "x")
    solver = make_solver()
    rng = np.random.default_rng(7)
    out, kinds = [], {}
    for rnd in range(rounds + 1):
        if rnd:
            _churn_round(
                st, SCRIPT[(rnd - 1) % len(SCRIPT)], task_ids, machine_ids, rng
            )
        prob = res.refresh() if resident else st.problem()
        r = solver.solve(prob)
        if resident:
            kinds[res.last_plan_kind] = kinds.get(res.last_plan_kind, 0) + 1
        out.append(
            (np.asarray(r.flow).copy(), solver.last_supersteps, r.objective)
        )
        if not st.plan.needs_rebuild:
            st.plan.check_invariants()
    return out, kinds, st, res, solver


def _assert_rounds_equal(a, b):
    for rnd, ((fa, sa, oa), (fb, sb, ob)) in enumerate(zip(a, b)):
        assert oa == ob, (rnd, oa, ob)
        assert np.array_equal(fa, fb), (rnd, "flows diverged")
        assert sa == sb, (rnd, "superstep counts diverged", sa, sb)


def test_slot_stable_parity_with_single_chip(mesh2):
    """Flows, superstep counts, AND objectives bit-identical between
    the single-chip slot-stable solve and the 2-device sharded solve
    over a churn script (cost/rewire/recycle/supply rounds)."""
    a, _, _, _, _ = _drive(lambda: JaxSolver(slot_stable=True, restart_budget=64))
    b, _, _, _, solver = _drive(lambda: ShardedJaxSolver(mesh2))
    _assert_rounds_equal(a, b)
    assert solver.last_path == "slot_stable"


def test_resident_sharded_rounds_are_delta_sized(mesh2):
    """The device-resident sharded arm: after the first layout upload
    every churn round syncs the plan as per-shard routed records
    (kind "delta" / "clean"), the scatter-maintained [D, Es] tensors
    equal the host truth bit-for-bit, and the psum'd per-shard
    fingerprints equal the host twins."""
    a, _, _, _, _ = _drive(
        lambda: JaxSolver(slot_stable=True, restart_budget=64), resident=True
    )
    b, kinds, st, res, _ = _drive(
        lambda: ShardedJaxSolver(mesh2), resident=True,
        sharded_resident_mesh=mesh2,
    )
    _assert_rounds_equal(a, b)
    assert kinds.get("rebuild", 0) == 1, kinds  # the initial layout only
    assert kinds.get("delta", 0) >= 3, kinds
    res.parity_check()
    res.plan_parity_check()
    fps = res.plan_fingerprints()
    for i, name in enumerate(FP_PLAN_ARRAYS):
        assert int(fps[i]) == host_fingerprint(getattr(st.plan, name)), name
    # entry tensors really are stacked per-shard tables
    assert np.asarray(res.d_p_arc).shape == (2, st.plan.block_extent)


def test_single_chip_solver_consumes_sharded_mirror(mesh2):
    """The degradation ladder's jax rung (and AutoSolver's too-big-
    even-per-shard CSR fallback) must be able to solve a problem whose
    resident mirror is in SHARDED plan mode: the [D, Es] entry tensors
    flatten losslessly back to the single-chip layout. Regression for
    the dead-middle-rung bug (ValueError on 2-D d_plan) the r15 review
    caught."""
    a, _, _, _, _ = _drive(
        lambda: JaxSolver(slot_stable=True, restart_budget=64),
        resident=True,
    )
    b, _, _, _, solver = _drive(
        lambda: JaxSolver(slot_stable=True, restart_budget=64),
        resident=True, sharded_resident_mesh=mesh2,
    )
    _assert_rounds_equal(a, b)


def test_autosolver_escalates_by_fitting_gate(mesh2):
    """dense -> csr -> sharded: with a budget between the
    per-shard and single-chip working sets the general-graph solve
    escalates to the sharded rung and stays bit-identical to the CSR
    arm; with the default budget this small bucket never escalates."""
    from ksched_tpu.solver.graph_collapse import AutoSolver

    g, _sink, _m, _t = _build_graph(24, 5)
    st = DeviceGraphState()
    st.full_build(g)
    prob = st.problem()
    n_cap, m_cap = prob.num_nodes, len(prob.src)

    auto = AutoSolver(JaxSolver(slot_stable=True))
    base = auto.solve(prob)
    assert auto.last_path == "csr"  # not collapsible, no sharded attached

    budget = (
        sharded_shard_bytes(n_cap, m_cap, 2)
        + csr_working_set_bytes(n_cap, m_cap)
    ) // 2
    made = []

    def factory():
        made.append(1)
        return ShardedJaxSolver(mesh2)

    auto_sh = AutoSolver(
        JaxSolver(slot_stable=True), sharded=factory,
        hbm_budget_bytes=budget,
    )
    res = auto_sh.solve(st.problem())
    assert auto_sh.last_path == "sharded"
    assert made == [1]  # factory resolved lazily, exactly once
    assert res.objective == base.objective
    assert np.array_equal(np.asarray(res.flow), np.asarray(base.flow))

    auto_default = AutoSolver(JaxSolver(slot_stable=True), sharded=factory)
    auto_default.solve(st.problem())
    assert auto_default.last_path == "csr"  # default budget: fits one chip


def test_sharded_layout_tolerates_empty_shards():
    """ceil-division ownership ranges leave trailing shards EMPTY when
    the shard count approaches (or exceeds) the node bucket — e.g. the
    minimum n_cap=16 bucket on a 5-way mesh, or make_backend("sharded")
    building the mesh over all devices for a tiny problem. An empty
    shard's block is one dead slot plus tail; the rebuild must not
    crash and the invariants must hold. Regression for the r15
    review's empty-shard broadcast crash."""
    g, _sink, _m, _t = _build_graph(8, 3)
    st = DeviceGraphState()
    st.full_build(g)
    for d in (5, 7, st.n_cap + 3):
        st.plan.invalidate()
        st.plan.enable_sharding(d)
        st.plan.ensure_built()
        st.plan.check_invariants()
    # and it still solves (single-chip consumer over the odd layout)
    r = JaxSolver(slot_stable=True).solve(st.problem())
    st.plan.invalidate()
    st.plan.enable_sharding(1)
    st.plan.ensure_built()
    r2 = JaxSolver(slot_stable=True).solve(st.problem())
    assert r.objective == r2.objective


def test_fitting_gate_arithmetic():
    """The estimators are monotone in the graph bucket, per-shard
    strictly below single-chip for D > 1, and a graph that fits nobody
    escalates nowhere (falls back to CSR)."""
    assert csr_working_set_bytes(1 << 10, 1 << 12) < csr_working_set_bytes(
        1 << 10, 1 << 14
    )
    n, m = 1 << 17, 1 << 22
    assert sharded_shard_bytes(n, m, 8) < csr_working_set_bytes(n, m)
    assert scan_csr_fits_hbm(64, 256)  # tiny bucket, default budget
    assert not scan_csr_fits_hbm(n, m, budget_bytes=1 << 20)
    assert not sharded_fits_hbm(n, m, 8, budget_bytes=1 << 20)
    assert sharded_entry_extent(1 << 10, 4) == (1 << 11) // 4


def test_ladder_degrades_sharded_to_jax(mesh2):
    """Chaos containment on the sharded rung: a failing sharded solve
    degrades through the ladder (sharded -> jax -> cpu_ref) and the
    round still lands with the same placements."""
    from ksched_tpu.runtime.degrade import build_degradation_ladder

    class FailingOnce(ShardedJaxSolver):
        fails = 0

        def solve(self, problem):
            if FailingOnce.fails == 0:
                FailingOnce.fails += 1
                raise RuntimeError("injected sharded-rung failure")
            return super().solve(problem)

    g, _sink, _m, _t = _build_graph(16, 4)
    st = DeviceGraphState()
    st.full_build(g)
    ladder = build_degradation_ladder(FailingOnce(mesh2), "sharded")
    assert ladder.rung_names() == ["sharded", "jax", "cpu_ref"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r1 = ladder.solve(st.problem())
    assert ladder.last_rung_name == "jax"
    r2 = ladder.solve(st.problem())
    assert ladder.last_rung_name == "sharded"
    assert r1.objective == r2.objective


#: pinned telemetry-OFF hash of the 2-device slot-stable sharded solve
#: at bucket (20, 100) — the "no cost when off" contract extended to
#: the multi-chip rung (the SOLTEL_OFF_BASELINE_HASHES convention of
#: tests/test_static_analysis.py: normalized jaxpr hash, jax 0.9.0;
#: re-capture in the same commit as any jax upgrade)
SHARDED_SLOT_OFF_HASH_2DEV = "dfaba9760e69eada"


def test_sharded_slot_telemetry_off_hash_pinned():
    from ksched_tpu.analysis import jaxpr_contracts as jc

    got = jc.jaxpr_hash(jc.trace_sharded_slot(20, 100, num_devices=2))
    assert got == SHARDED_SLOT_OFF_HASH_2DEV, (
        "the slot-stable sharded telemetry-OFF trace drifted — "
        "disabled solver telemetry must cost zero traced ops, and an "
        "intentional program change must re-pin this hash "
        f"(got {got})"
    )
