"""The fused Pallas transport kernel (ops/transport_pallas.py) must be a
bit-exact twin of the XLA phase loop (solver/layered.py _transport_loop):
both run the same synchronous integer push-relabel schedule, so the
resulting flows — not just objectives — are identical. Tests run the
kernel under the Pallas interpreter (CPU env); the TPU-compiled path is
the same kernel code, exercised on hardware by chip_smoke.py's `array`
and `kernels` phases and by the `coco-50kx1k` benchmark cells.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from ksched_tpu.ops import get_pallas_mode, set_pallas_mode, transport_loop_pallas
from ksched_tpu.scheduler.bulk import BulkCluster
from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.layered import (
    LayeredProblem,
    LayeredTransportSolver,
    _transport_loop,
    pad_geometry,
)


@pytest.fixture
def pallas_interpret():
    prev = get_pallas_mode()
    set_pallas_mode("interpret")
    yield
    set_pallas_mode(prev)


def _random_instance(seed, C, M):
    """A padded transport instance in the exact form the bulk scheduler
    emits: scaled costs with a zero-cost unsched column of capacity
    sum(supply)."""
    rng = np.random.default_rng(seed)
    Mp, n_scale = pad_geometry(M, C)
    w = rng.integers(-30, 30, (C, M)).astype(np.int64)
    wS = np.zeros((C, Mp), np.int32)
    wS[:, :M] = w * n_scale
    supply = rng.integers(0, 60, C).astype(np.int32)
    col_cap = np.zeros(Mp, np.int32)
    col_cap[:M] = rng.integers(0, 25, M).astype(np.int32)
    col_cap[-1] = supply.sum()
    return wS, supply, col_cap, n_scale


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("C,M", [(2, 5), (3, 40), (5, 130), (8, 250)])
def test_kernel_flow_identical_to_xla_loop(seed, C, M):
    wS, supply, col_cap, n_scale = _random_instance(seed, C, M)
    eps0 = np.int32(max(1, np.abs(wS).max()))
    U = jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :])
    y_xla, _z, pm_xla, steps_xla, conv_xla = _transport_loop(
        jnp.asarray(wS), U, jnp.asarray(supply), jnp.asarray(col_cap),
        jnp.asarray(eps0), 8, 20_000,
    )
    y_pl, pm_pl, steps_pl, conv_pl = transport_loop_pallas(
        jnp.asarray(wS), jnp.asarray(supply), jnp.asarray(col_cap),
        jnp.asarray(eps0), alpha=8, max_supersteps=20_000, interpret=True,
    )
    assert bool(conv_xla) and bool(conv_pl)
    assert int(steps_xla) == int(steps_pl)
    np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_pl))
    np.testing.assert_array_equal(np.asarray(pm_xla), np.asarray(pm_pl))


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("C,M", [(2, 5), (4, 40), (6, 130)])
def test_tiered_kernel_identical_to_xla_tiered_loop(seed, C, M):
    """The fused TIERED kernel (preemption pricing: residents at
    wLo = w - discount, the rest at wHi) must match the XLA tiered
    phase loop bit-for-bit — flows, prices, and superstep counts —
    with and without price refinement."""
    from ksched_tpu.ops import transport_loop_pallas_tiered
    from ksched_tpu.solver.layered import _transport_loop_tiered

    wS, supply, col_cap, n_scale = _random_instance(seed, C, M)
    rng = np.random.default_rng(seed + 77)
    discount = int(rng.integers(1, 12)) * n_scale
    wHi = wS
    wLo = wS.copy()
    wLo[:, :M] -= discount
    # resident census: scattered residents under the cell capacities
    R = rng.integers(0, 6, (C, wS.shape[1])).astype(np.int32)
    R[:, -1] = 0
    eps0 = np.int32(max(1, np.abs(wHi).max()))
    RJ = jnp.minimum(
        jnp.asarray(R),
        jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :]),
    )
    U = jnp.minimum(jnp.asarray(supply)[:, None], jnp.asarray(col_cap)[None, :])
    for refine in (0, 8):
        y_xla, _z, pm_xla, steps_xla, conv_xla = _transport_loop_tiered(
            jnp.asarray(wLo), jnp.asarray(wHi), RJ, U,
            jnp.asarray(supply), jnp.asarray(col_cap),
            jnp.asarray(eps0), 8, 50_000, refine_waves=refine,
        )
        y_pl, pm_pl, steps_pl, conv_pl = transport_loop_pallas_tiered(
            jnp.asarray(wLo), jnp.asarray(wHi), jnp.asarray(R),
            jnp.asarray(supply), jnp.asarray(col_cap), jnp.asarray(eps0),
            alpha=8, max_supersteps=50_000, interpret=True,
            refine_waves=refine,
        )
        assert bool(conv_xla) and bool(conv_pl), refine
        assert int(steps_xla) == int(steps_pl), refine
        np.testing.assert_array_equal(np.asarray(y_xla), np.asarray(y_pl))
        np.testing.assert_array_equal(np.asarray(pm_xla), np.asarray(pm_pl))


@pytest.mark.parametrize("seed", range(4))
def test_warm_start_stays_exact(seed):
    """Re-solving a perturbed instance from the previous solve's machine
    prices must stay exactly optimal (same objective as cold). No
    superstep-count guarantee exists — warm prices can be slower (they
    flatten reduced costs; see scheduler/device_bulk.py), which is why
    production solves are cold — but correctness must never depend on
    the start point."""
    C, M = 4, 60
    wS, supply, col_cap, n_scale = _random_instance(seed, C, M)
    eps0 = jnp.asarray(np.int32(n_scale))
    a = (jnp.asarray(wS), jnp.asarray(supply), jnp.asarray(col_cap))
    y0, pm0, s0, c0 = transport_loop_pallas(
        *a, eps0, alpha=8, max_supersteps=50_000, interpret=True
    )
    assert bool(c0)
    # perturb: a few tasks of each class finish, a few arrive
    rng = np.random.default_rng(seed + 100)
    supply2 = np.maximum(0, supply + rng.integers(-3, 4, C)).astype(np.int32)
    cap2 = col_cap.copy()
    cap2[-1] = supply2.sum()
    a2 = (jnp.asarray(wS), jnp.asarray(supply2), jnp.asarray(cap2))
    y_cold, _pm, s_cold, c_cold = transport_loop_pallas(
        *a2, eps0, alpha=8, max_supersteps=50_000, interpret=True
    )
    y_warm, _pm2, s_warm, c_warm = transport_loop_pallas(
        *a2, eps0, pm0, alpha=8, max_supersteps=50_000, interpret=True
    )
    assert bool(c_cold) and bool(c_warm)
    w = wS.astype(np.int64)
    obj_cold = int((np.asarray(y_cold) * w).sum())
    obj_warm = int((np.asarray(y_warm) * w).sum())
    assert obj_warm == obj_cold  # warm start never sacrifices optimality


@pytest.mark.parametrize("seed", [0, 3])
def test_layered_solver_via_pallas_matches_oracle(seed, pallas_interpret):
    """End-to-end through LayeredTransportSolver: objective parity with
    the exact SSP oracle on the full flow graph."""
    rng = np.random.default_rng(seed)
    C, M = 3, 12
    cost = rng.integers(0, 20, (C, M)).astype(np.int32)
    solver = LayeredTransportSolver()
    cluster = BulkCluster(
        num_machines=M,
        pus_per_machine=2,
        slots_per_pu=2,
        num_jobs=3,
        backend=solver,
        task_capacity=256,
        num_task_classes=C,
        class_cost_fn=lambda cl: cost,
        unsched_cost=25,
    )
    n = int(rng.integers(40, 120))
    cluster.add_tasks(
        n,
        rng.integers(0, 3, n).astype(np.int32),
        rng.integers(0, C, n).astype(np.int32),
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
            unsched_cost=cluster.unsched_cost,
            ec_cost=cluster.ec_cost,
        )
    )
    assert res.objective == want


def test_device_bulk_rounds_same_with_and_without_pallas():
    """A multi-class device cluster run (round + churn rounds) must
    produce identical stats under pallas and XLA dispatch."""
    def run():
        rng = np.random.default_rng(0)
        cost = np.asarray([[0, 4, 9], [9, 4, 0]], np.int32)
        dev = DeviceBulkCluster(
            num_machines=3,
            pus_per_machine=2,
            slots_per_pu=2,
            num_jobs=2,
            num_task_classes=2,
            task_capacity=64,
            class_cost_fn=lambda census: jnp.asarray(cost),
        )
        dev.add_tasks(
            20,
            rng.integers(0, 2, 20).astype(np.int32),
            rng.integers(0, 2, 20).astype(np.int32),
        )
        r = dev.fetch_stats(dev.round())
        s = dev.fetch_stats(dev.run_steady_rounds(4, 0.2, 2, seed=5))
        return r, s

    prev = get_pallas_mode()
    try:
        set_pallas_mode("off")
        r_x, s_x = run()
        set_pallas_mode("interpret")
        r_p, s_p = run()
    finally:
        set_pallas_mode(prev)
    for k in r_x:
        np.testing.assert_array_equal(r_x[k], r_p[k], err_msg=f"round stat {k}")
    for k in s_x:
        np.testing.assert_array_equal(s_x[k], s_p[k], err_msg=f"steady stat {k}")
