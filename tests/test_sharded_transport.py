"""Sharded layered transport (parallel/sharded_transport.py) on the
virtual 8-device mesh: bit-exact parity with the single-device solve,
and the solve_layered seam against the SSP oracle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

# Compiling ~30 while_loop-heavy shard_map programs for the 8-device
# CPU mesh costs ~9 min — past the budgeted tier-1 wall on its own —
# so this parity file runs in the full/slow suite
# (`pytest tests/` without -m 'not slow'). The sharded CSR solver's
# tier-1 coverage (test_sharded_solver.py) stays in the fast set.
pytestmark = pytest.mark.slow

from ksched_tpu.parallel.sharded_transport import (
    ShardedLayeredSolver,
    sharded_transport_solve,
)
from ksched_tpu.scheduler.bulk import BulkCluster
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.layered import LayeredProblem, _transport_loop


def _mesh(n=8):
    devs = jax.devices()
    assert len(devs) >= n
    return Mesh(np.array(devs[:n]), ("x",))


def _instance(seed, C, M, Mp):
    rng = np.random.default_rng(seed)
    n_scale = 2048
    w = rng.integers(-30, 30, (C, M)).astype(np.int64)
    wS = np.zeros((C, Mp), np.int32)
    wS[:, :M] = w * n_scale
    supply = rng.integers(0, 60, C).astype(np.int32)
    col_cap = np.zeros(Mp, np.int32)
    col_cap[:M] = rng.integers(0, 25, M).astype(np.int32)
    col_cap[-1] = supply.sum()
    return wS, supply, col_cap


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("C,M,Mp", [(2, 30, 1024), (4, 200, 1024), (3, 900, 2048)])
def test_sharded_matches_single_device_exactly(seed, C, M, Mp):
    wS, supply, col_cap = _instance(seed, C, M, Mp)
    eps0 = np.int32(max(1, np.abs(wS).max()))
    mesh = _mesh()
    y_sh, steps_sh, conv_sh = sharded_transport_solve(
        mesh, jnp.asarray(wS), jnp.asarray(supply), jnp.asarray(col_cap),
        jnp.asarray(eps0),
    )
    U = jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :])
    y_1, _z, _pm, steps_1, conv_1 = _transport_loop(
        jnp.asarray(wS), U, jnp.asarray(supply), jnp.asarray(col_cap),
        jnp.asarray(eps0), 8, 1 << 17,
    )
    assert bool(conv_sh) and bool(conv_1)
    assert int(steps_sh) == int(steps_1)
    np.testing.assert_array_equal(np.asarray(y_sh), np.asarray(y_1))


@pytest.mark.parametrize("seed", [0, 4])
def test_sharded_solver_seam_matches_oracle(seed):
    """Through BulkCluster's solve_layered seam: objective parity with
    the exact SSP oracle on the 8-device mesh."""
    rng = np.random.default_rng(seed)
    C, M = 3, 12
    cost = rng.integers(0, 20, (C, M)).astype(np.int32)
    solver = ShardedLayeredSolver(_mesh())
    cluster = BulkCluster(
        num_machines=M, pus_per_machine=2, slots_per_pu=2, num_jobs=3,
        backend=solver, task_capacity=256, num_task_classes=C,
        class_cost_fn=lambda cl: cost, unsched_cost=25,
    )
    n = int(rng.integers(40, 120))
    cluster.add_tasks(
        n, rng.integers(0, 3, n).astype(np.int32), rng.integers(0, C, n).astype(np.int32)
    )
    cluster._refresh_capacities()
    want = ReferenceSolver().solve(cluster._problem()).objective
    unplaced = np.nonzero(cluster.task_live & (cluster.task_pu < 0))[0]
    supply = np.bincount(cluster.task_class[unplaced], minlength=C).astype(np.int32)
    pu_free = cluster.S - cluster.pu_running
    machine_free = pu_free.reshape(cluster.M, cluster.P).sum(axis=1)
    res = solver.solve_layered(
        LayeredProblem(
            supply=supply,
            col_cap=machine_free.astype(np.int32),
            cost_cm=cost,
            unsched_cost=25,
            ec_cost=cluster.ec_cost,
        )
    )
    assert res.objective == want
    assert res.supersteps > 0  # the mesh solve actually ran


def test_degenerate_and_single_class_use_closed_form():
    solver = ShardedLayeredSolver(_mesh())
    res = solver.solve_layered(
        LayeredProblem(
            supply=np.asarray([7, 7], np.int32),
            col_cap=np.full(6, 2, np.int32),
            cost_cm=np.zeros((2, 6), np.int32),
            unsched_cost=25, ec_cost=2,
        )
    )
    assert res.supersteps == 0  # closed form, no mesh solve
    assert res.num_unsched == 2  # 14 supply into 12 slots


def test_sharded_superstep_parity_with_single_device():
    """The dryrun_multichip instance shape (3 classes x 16 machines):
    the mesh solve must take exactly as many supersteps as the
    single-device solve. n_scale derives from the REAL node count
    (pad_geometry), not the padded width, so the 128*devices column
    padding the mesh requires cannot inflate the eps schedule; padded
    columns carry no arcs and are inert in every superstep."""
    from ksched_tpu.solver.layered import LayeredTransportSolver

    rng = np.random.default_rng(1)
    C, M = 3, 16
    lp = LayeredProblem(
        supply=rng.integers(5, 20, C).astype(np.int32),
        col_cap=rng.integers(0, 4, M).astype(np.int32),
        cost_cm=rng.integers(0, 20, (C, M)).astype(np.int32),
        unsched_cost=25,
        ec_cost=2,
    )
    sharded = ShardedLayeredSolver(_mesh())
    single = LayeredTransportSolver()
    res_sh = sharded.solve_layered(lp)
    res_1 = single.solve_layered(lp)
    assert res_sh.objective == res_1.objective
    np.testing.assert_array_equal(res_sh.y, res_1.y)
    assert res_sh.supersteps == res_1.supersteps
    # and the count is the real-node-count, oversubscription-aware
    # schedule (choose_eps0): a couple hundred supersteps on this toy,
    # not the ~1.5k that n_scale-from-Mp + a short eps0 start produced
    # (docs/NOTES.md).
    assert 0 < res_sh.supersteps < 500


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("C,M,Mp", [(2, 30, 1024), (4, 200, 1024)])
def test_sharded_tiered_matches_single_device_exactly(seed, C, M, Mp):
    """The sharded TIERED (preemption) solve must be bit-identical to
    the single-device tiered loop — flows and superstep counts — on
    the virtual 8-device mesh: multi-chip preemption rounds carry the
    same keep-arcs semantics as single-chip ones."""
    from ksched_tpu.parallel.sharded_transport import (
        sharded_transport_solve_tiered,
    )
    from ksched_tpu.solver.layered import _transport_loop_tiered

    wS, supply, col_cap = _instance(seed, C, M, Mp)
    rng = np.random.default_rng(seed + 31)
    n_scale = 2048
    discount = int(rng.integers(1, 10)) * n_scale
    wHi = wS
    wLo = wS.copy()
    wLo[:, :M] -= discount
    R = rng.integers(0, 5, (C, Mp)).astype(np.int32)
    R[:, -1] = 0
    eps0 = np.int32(max(1, np.abs(wHi).max()))
    mesh = _mesh()
    RJ = jnp.minimum(
        jnp.asarray(R),
        jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :]),
    )
    U = jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :])
    # both refinement regimes: refine 0 (the host bit-parity
    # convention) and refine 8 (the production preemption setting)
    for refine in (0, 8):
        y_sh, steps_sh, conv_sh = sharded_transport_solve_tiered(
            mesh, jnp.asarray(wLo), jnp.asarray(wHi), jnp.asarray(R),
            jnp.asarray(supply), jnp.asarray(col_cap), jnp.asarray(eps0),
            refine_waves=refine,
        )
        y_1, _z, _pm, steps_1, conv_1 = _transport_loop_tiered(
            jnp.asarray(wLo), jnp.asarray(wHi), RJ, U,
            jnp.asarray(supply), jnp.asarray(col_cap),
            jnp.asarray(eps0), 8, 1 << 17, refine_waves=refine,
        )
        assert bool(conv_sh) and bool(conv_1), refine
        assert int(steps_sh) == int(steps_1), refine
        np.testing.assert_array_equal(np.asarray(y_sh), np.asarray(y_1))
