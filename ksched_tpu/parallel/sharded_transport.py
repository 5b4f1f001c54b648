"""Multi-chip layered transport: the production dense solve sharded
over a device mesh.

This is the BASELINE.json north star's multi-chip sentence made
concrete for the PRODUCTION path: the machine axis of the dense
transport problem (solver/layered.py) — the collapsed resource-topology
subtree — is sharded across chips, and the per-superstep combination of
node potentials rides ICI collectives. Where the sharded CSR solver
(parallel/sharded_solver.py) partitions arbitrary graphs by owner node,
this shards the layered formulation's columns:

- machine columns [C, Mloc] (costs, capacities, flows y, prices pm) are
  device-local; Mp is a multiple of 128 so any pow2 mesh divides it;
- row state (supplies, row prices pr, sink price, eps phase) is
  replicated; each superstep reconciles it with one psum/pmax per
  reduction — tiny [C]-sized payloads over ICI;
- the rows' maximal-push allocation needs a GLOBAL exclusive prefix
  over columns in lane order; it distributes as the classic two-level
  scan: local cumsum + all_gather of the D per-device totals + masked
  offset. Global column order equals the unsharded lane order, so the
  sharded solve is BIT-IDENTICAL to the single-device XLA/Pallas solve
  — tests assert exact flow equality on the virtual 8-device mesh.

The algorithm itself is unchanged (synchronous Goldberg–Tarjan
cost-scaling push-relabel; see solver/layered.py for the derivation and
exactness argument).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..solver.layered import (
    BIG as _BIG,
    BIG_D as _BIG_D,
    LayeredProblem,
    LayeredResult,
    pad_geometry,
    solve_layered_host,
    transport_saturate,
    transport_saturate_tiered,
    validate_alpha,
)

AXIS = "x"



def _pcast_varying(x):
    # loop carries built inside shard_map start replicated; mark them
    # device-varying so the while_loop carry types match its body's
    return lax.pcast(x, (AXIS,), to="varying")


def _global_excl_prefix(local_vals, axis_name):
    """Exclusive prefix (over the global column order) of per-column
    values sharded along axis_name: local exclusive cumsum + the sum of
    every earlier device's total. local_vals: [..., Mloc]."""
    local_cum = jnp.cumsum(local_vals, axis=-1)
    local_excl = local_cum - local_vals
    local_tot = local_cum[..., -1:]
    # [D, ...] totals of every device, gathered over ICI
    all_tot = lax.all_gather(local_tot, axis_name)  # [D, ..., 1]
    me = lax.axis_index(axis_name)
    d = all_tot.shape[0]
    mask = (jnp.arange(d, dtype=jnp.int32) < me).reshape((d,) + (1,) * (all_tot.ndim - 1))
    offset = jnp.sum(jnp.where(mask, all_tot, 0), axis=0)
    return local_excl + offset


def _sharded_transport_fn(wS, supply, col_cap, eps0, alpha, max_supersteps):
    """Runs INSIDE shard_map: wS [C, Mloc], col_cap [Mloc] local;
    supply [C], eps0 scalar replicated. Returns (y_local, steps, conv)."""
    i32 = jnp.int32
    C, Mloc = wS.shape
    U = jnp.minimum(supply[:, None], col_cap[None, :])

    def excesses(y, z):
        e_row = supply - lax.psum(jnp.sum(y, axis=1), AXIS)  # [C] repl
        e_col = jnp.sum(y, axis=0) - z  # [Mloc] local
        e_sink = lax.psum(jnp.sum(z), AXIS) - jnp.sum(supply)  # repl
        return e_row, e_col, e_sink

    # cold tighten (zeros pm): pr = global max over live arcs of -wS
    live = col_cap > 0
    pm0 = jnp.where(live, i32(0), -i32(_BIG_D))
    pr0 = lax.pmax(
        jnp.max(jnp.where(U > 0, pm0[None, :] - wS, -i32(_BIG_D)), axis=1), AXIS
    )
    has_arc = lax.psum(jnp.sum((U > 0).astype(i32), axis=1), AXIS) > 0
    pr0 = jnp.where(has_arc, pr0, i32(0))
    psink0 = lax.pmin(jnp.min(jnp.where(live, pm0, i32(_BIG_D))), AXIS)
    psink0 = jnp.where(
        lax.psum(jnp.sum(live.astype(i32)), AXIS) > 0, psink0, i32(0)
    )

    def saturate(y, z, pr, pm, psink):
        # column-local, no collectives: the single-device rule applies
        # verbatim to the shard's columns
        return transport_saturate(wS, U, col_cap, y, z, pr, pm, psink)

    def superstep(y, z, pr, pm, psink, eps):
        e_row, e_col, e_sink = excesses(y, z)
        rcf = wS + pr[:, None] - pm[None, :]

        # rows push forward: global in-row exclusive prefix (two-level)
        r_fwd = U - y
        r_adm = jnp.where((r_fwd > 0) & (rcf < 0), r_fwd, i32(0))
        excl = _global_excl_prefix(r_adm, AXIS)
        delta_f = jnp.clip(e_row[:, None] - excl, 0, r_adm)

        # columns push: sink entry first, then backward col->row — all
        # column-local given replicated pr/psink
        r_s = col_cap - z
        adm_s = jnp.where((r_s > 0) & (pm - psink < 0), r_s, i32(0))
        rc_b = pm[None, :] - pr[:, None] - wS
        adm_b = jnp.where((y > 0) & (rc_b < 0), y, i32(0))
        excl_b = adm_s[None, :] + (jnp.cumsum(adm_b, axis=0) - adm_b)
        delta_s = jnp.clip(e_col, 0, adm_s)
        delta_b = jnp.clip(e_col[None, :] - excl_b, 0, adm_b)

        # sink pushes back along sharded columns: global prefix again
        zb_adm = jnp.where((z > 0) & (psink - pm < 0), z, i32(0))
        excl_zb = _global_excl_prefix(zb_adm, AXIS)
        delta_zb = jnp.clip(e_sink - excl_zb, 0, zb_adm)

        y2 = y + delta_f - delta_b
        z2 = z + delta_s - delta_zb

        # jump relabels; row/sink candidates combine over the mesh
        pushed_row = lax.psum(jnp.sum(delta_f, axis=1), AXIS)
        best_row = lax.pmax(
            jnp.max(jnp.where(r_fwd > 0, pm[None, :] - wS, -i32(_BIG)), axis=1),
            AXIS,
        )
        pr2 = jnp.where((e_row > 0) & (pushed_row == 0), best_row - eps, pr)

        pushed_col = delta_s + jnp.sum(delta_b, axis=0)
        cand_col = jnp.maximum(
            jnp.max(jnp.where(y > 0, pr[:, None] + wS, -i32(_BIG)), axis=0),
            jnp.where(r_s > 0, psink, -i32(_BIG)),
        )
        pm2 = jnp.where((e_col > 0) & (pushed_col == 0), cand_col - eps, pm)

        pushed_sink = lax.psum(jnp.sum(delta_zb), AXIS)
        cand_sink = lax.pmax(jnp.max(jnp.where(z > 0, pm, -i32(_BIG))), AXIS)
        psink2 = jnp.where(
            (e_sink > 0) & (pushed_sink == 0), cand_sink - eps, psink
        )
        return y2, z2, pr2, pm2, psink2

    def phase_cond(state):
        *_rest, steps, done = state
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        y, z, pr, pm, psink, eps, steps, done = state
        e_row, e_col, e_sink = excesses(y, z)
        any_active = (
            jnp.any(e_row > 0)
            | (lax.psum(jnp.sum((e_col > 0).astype(i32)), AXIS) > 0)
            | (e_sink > 0)
        )

        def do_step(_):
            y2, z2, pr2, pm2, psink2 = superstep(y, z, pr, pm, psink, eps)
            return y2, z2, pr2, pm2, psink2, eps, steps + 1, jnp.bool_(False)

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            y2, z2 = saturate(y, z, pr, pm, psink)
            return (
                jnp.where(finished, y, y2),
                jnp.where(finished, z, z2),
                pr, pm, psink,
                jnp.where(finished, eps, new_eps),
                steps,
                finished,
            )

        return lax.cond(any_active, do_step, next_phase, operand=None)

    # zeros materialized inside the shard body are "unvarying" in
    # shard_map's manual-axes tracking; mark them device-varying so the
    # while carry types match after the first superstep
    y0 = _pcast_varying(jnp.zeros((C, Mloc), i32))
    z0 = _pcast_varying(jnp.zeros((Mloc,), i32))
    state = (y0, z0, pr0, pm0, psink0, eps0, i32(0), jnp.bool_(False))
    y, z, pr, pm, psink, eps, steps, done = lax.while_loop(
        phase_cond, phase_body, state
    )
    e_row, e_col, e_sink = excesses(y, z)
    max_abs = jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(e_row)), jnp.abs(e_sink)),
        lax.pmax(jnp.max(jnp.abs(e_col)), AXIS),
    )
    return y, steps, done & (max_abs == 0)


@functools.partial(jax.jit, static_argnames=("mesh", "alpha", "max_supersteps"))  # kschedlint: disable=unregistered-program -- sharded transport research path, bit-parity gated by tests/test_sharded_transport.py
def sharded_transport_solve(
    mesh: Mesh, wS, supply, col_cap, eps0,
    alpha: int = 8, max_supersteps: int = 1 << 17,
):
    """Solve the padded transport problem with machine columns sharded
    over `mesh`'s '{AXIS}' axis. wS int32[C, Mp], supply int32[C],
    col_cap int32[Mp]; Mp must be divisible by the mesh size.
    Returns (y [C, Mp], steps, converged), bit-identical to the
    single-device solve."""
    fn = jax.shard_map(  # kschedlint: disable=unregistered-program -- sharded transport research path, bit-parity gated by tests/test_sharded_transport.py
        functools.partial(
            _sharded_transport_fn, alpha=alpha, max_supersteps=max_supersteps
        ),
        mesh=mesh,
        in_specs=(P(None, AXIS), P(None), P(AXIS), P()),
        out_specs=(P(None, AXIS), P(), P()),
    )
    return fn(wS, supply, col_cap, eps0)


class ShardedLayeredSolver:
    """Drop-in layered backend (BulkCluster `solve_layered` seam) that
    runs the multi-class solve sharded over a device mesh. Single-class
    and class-degenerate instances use the exact host closed form, as
    the single-device solver does."""

    def __init__(self, mesh: Mesh, alpha: int = 8, max_supersteps: int = 1 << 17):
        assert AXIS in mesh.axis_names, f"mesh must have a {AXIS!r} axis"
        self.mesh = mesh
        self.alpha = validate_alpha(alpha)
        self.max_supersteps = max_supersteps
        self.last_supersteps = 0

    def reset(self) -> None:
        pass

    def _pad_geometry(self, M: int, C: int):
        Mp, n_scale = pad_geometry(M, C)
        d = self.mesh.devices.size
        Mp = -(-Mp // (128 * d)) * 128 * d  # divisible by mesh size
        return Mp, n_scale

    def solve_layered(self, lp: LayeredProblem) -> LayeredResult:
        def solve(wS, sup, cap, eps_init):
            return sharded_transport_solve(
                self.mesh, wS, sup, cap, eps_init,
                alpha=self.alpha, max_supersteps=self.max_supersteps,
            )

        try:
            res = solve_layered_host(
                lp, pad=self._pad_geometry, solve=solve,
                max_supersteps=self.max_supersteps,
            )
        except RuntimeError:
            self.last_supersteps = self.max_supersteps  # budget exhausted
            raise
        self.last_supersteps = res.supersteps
        return res


def _sharded_transport_tiered_fn(wLo, wHi, R, supply, col_cap, eps0,
                                 alpha, max_supersteps, refine_waves=0):
    """Tiered (continuation-priced) twin of _sharded_transport_fn:
    preemption-on rounds over a device mesh. wLo/wHi/R [C, Mloc]
    column-local; supply [C], eps0 replicated. Residual rules are the
    canonical parallel-arc split (solver/layered.py
    _transport_loop_tiered, which this matches BIT-FOR-BIT at equal
    refine_waves); the cross-device structure is identical to the
    plain sharded solve — global in-row prefixes + tiny replicated-row
    reductions over ICI. refine_waves > 0 enables the tiered price
    refinement between eps phases (measured ESSENTIAL at preemption
    scale: 31-58k supersteps/round without it — solver/layered.py
    _transport_loop_tiered docstring); each wave costs two pmin
    reductions. Returns (y_local, steps, conv)."""
    i32 = jnp.int32
    C, Mloc = wLo.shape
    U = jnp.minimum(supply[:, None], col_cap[None, :])
    R = jnp.minimum(R, U)

    def excesses(y, z):
        e_row = supply - lax.psum(jnp.sum(y, axis=1), AXIS)
        e_col = jnp.sum(y, axis=0) - z
        e_sink = lax.psum(jnp.sum(z), AXIS) - jnp.sum(supply)
        return e_row, e_col, e_sink

    # cold tighten against the CHEAP tier (wLo <= wHi cellwise)
    live = col_cap > 0
    pm0 = jnp.where(live, i32(0), -i32(_BIG_D))
    pr0 = lax.pmax(
        jnp.max(jnp.where(U > 0, pm0[None, :] - wLo, -i32(_BIG_D)), axis=1),
        AXIS,
    )
    has_arc = lax.psum(jnp.sum((U > 0).astype(i32), axis=1), AXIS) > 0
    pr0 = jnp.where(has_arc, pr0, i32(0))
    psink0 = lax.pmin(jnp.min(jnp.where(live, pm0, i32(_BIG_D))), AXIS)
    psink0 = jnp.where(
        lax.psum(jnp.sum(live.astype(i32)), AXIS) > 0, psink0, i32(0)
    )

    def saturate(y, z, pr, pm, psink):
        # column-local, no collectives
        return transport_saturate_tiered(
            wLo, wHi, R, U, col_cap, y, z, pr, pm, psink
        )

    def saturate_eps(y, z, pr, pm, psink, eps):
        # column-local (solver/layered.py transport_saturate_eps_tiered)
        rcl = wLo + pr[:, None] - pm[None, :]
        rch = wHi + pr[:, None] - pm[None, :]
        yA = jnp.minimum(y, R)
        yB = y - yA
        yA2 = jnp.where(rcl < -eps, R, jnp.where(rcl > eps, i32(0), yA))
        yB2 = jnp.where(rch < -eps, U - R, jnp.where(rch > eps, i32(0), yB))
        rcs = pm - psink
        z2 = jnp.where(rcs < -eps, col_cap, jnp.where(rcs > eps, i32(0), z))
        return yA2 + yB2, z2

    def price_refine(y, z, pr, pm, psink, eps):
        """_price_refine_tiered over the mesh: bound_m is column-local
        (min over replicated rows), bound_r/bound_s are global column
        minima — one pmin each per wave."""
        big = i32(_BIG)
        big_d = i32(_BIG_D)

        def body(_, state):
            pr, pm, psink = state
            yA = jnp.minimum(y, R)
            yB = y - yA
            bound_m = jnp.minimum(
                jnp.min(jnp.where(R - yA > 0, wLo + pr[:, None] + eps, big),
                        axis=0),
                jnp.min(jnp.where((U - R) - yB > 0, wHi + pr[:, None] + eps,
                                  big), axis=0),
            )
            pm2 = jnp.maximum(jnp.minimum(pm, bound_m), -big_d)
            pm2 = jnp.minimum(pm2, jnp.where(z > 0, psink + eps, big))
            bound_r = lax.pmin(
                jnp.minimum(
                    jnp.min(jnp.where(yA > 0, pm2[None, :] - wLo + eps, big),
                            axis=1),
                    jnp.min(jnp.where(yB > 0, pm2[None, :] - wHi + eps, big),
                            axis=1),
                ),
                AXIS,
            )
            pr2 = jnp.maximum(jnp.minimum(pr, bound_r), -big_d)
            bound_s = lax.pmin(
                jnp.min(jnp.where(col_cap - z > 0, pm2 + eps, big)), AXIS
            )
            psink2 = jnp.maximum(jnp.minimum(psink, bound_s), -big_d)
            return pr2, pm2, psink2

        return lax.fori_loop(0, refine_waves, body, (pr, pm, psink))

    def superstep(y, z, pr, pm, psink, eps):
        e_row, e_col, e_sink = excesses(y, z)
        yA = jnp.minimum(y, R)
        yB = y - yA
        rcl = wLo + pr[:, None] - pm[None, :]
        rch = wHi + pr[:, None] - pm[None, :]

        # rows push forward: both tiers' admissible residuals, one
        # global in-row exclusive prefix
        rA = R - yA
        rB = (U - R) - yB
        r_adm = jnp.where((rA > 0) & (rcl < 0), rA, i32(0)) + jnp.where(
            (rB > 0) & (rch < 0), rB, i32(0)
        )
        excl = _global_excl_prefix(r_adm, AXIS)
        delta_f = jnp.clip(e_row[:, None] - excl, 0, r_adm)

        # columns push: sink entry, then dear-tier returns, then cheap
        # — column-local given replicated pr/psink (same [sink; yB; yA]
        # exclusive-prefix order as the single-device loop)
        r_s = col_cap - z
        adm_s = jnp.where((r_s > 0) & (pm - psink < 0), r_s, i32(0))
        rcb_hi = pm[None, :] - pr[:, None] - wHi
        rcb_lo = pm[None, :] - pr[:, None] - wLo
        adm_bh = jnp.where((yB > 0) & (rcb_hi < 0), yB, i32(0))
        adm_bl = jnp.where((yA > 0) & (rcb_lo < 0), yA, i32(0))
        excl_bh = adm_s[None, :] + (jnp.cumsum(adm_bh, axis=0) - adm_bh)
        excl_bl = (
            adm_s[None, :]
            + jnp.sum(adm_bh, axis=0, keepdims=True)
            + (jnp.cumsum(adm_bl, axis=0) - adm_bl)
        )
        delta_s = jnp.clip(e_col, 0, adm_s)
        delta_bh = jnp.clip(e_col[None, :] - excl_bh, 0, adm_bh)
        delta_bl = jnp.clip(e_col[None, :] - excl_bl, 0, adm_bl)
        delta_b = delta_bh + delta_bl

        # sink pushes back along sharded columns: global prefix
        zb_adm = jnp.where((z > 0) & (psink - pm < 0), z, i32(0))
        excl_zb = _global_excl_prefix(zb_adm, AXIS)
        delta_zb = jnp.clip(e_sink - excl_zb, 0, zb_adm)

        y2 = y + delta_f - delta_b
        z2 = z + delta_s - delta_zb

        # jump relabels: candidates consider both tiers' residuals
        pushed_row = lax.psum(jnp.sum(delta_f, axis=1), AXIS)
        # one pmax: max is associative, so combining the two tiers'
        # LOCAL maxima first is bit-identical and halves the reduction
        cand_row = lax.pmax(
            jnp.maximum(
                jnp.max(jnp.where(rA > 0, pm[None, :] - wLo, -i32(_BIG)),
                        axis=1),
                jnp.max(jnp.where(rB > 0, pm[None, :] - wHi, -i32(_BIG)),
                        axis=1),
            ),
            AXIS,
        )
        pr2 = jnp.where((e_row > 0) & (pushed_row == 0), cand_row - eps, pr)

        pushed_col = delta_s + jnp.sum(delta_b, axis=0)
        cand_col = jnp.maximum(
            jnp.maximum(
                jnp.max(jnp.where(yA > 0, pr[:, None] + wLo, -i32(_BIG)),
                        axis=0),
                jnp.max(jnp.where(yB > 0, pr[:, None] + wHi, -i32(_BIG)),
                        axis=0),
            ),
            jnp.where(r_s > 0, psink, -i32(_BIG)),
        )
        pm2 = jnp.where((e_col > 0) & (pushed_col == 0), cand_col - eps, pm)

        pushed_sink = lax.psum(jnp.sum(delta_zb), AXIS)
        cand_sink = lax.pmax(jnp.max(jnp.where(z > 0, pm, -i32(_BIG))), AXIS)
        psink2 = jnp.where(
            (e_sink > 0) & (pushed_sink == 0), cand_sink - eps, psink
        )
        return y2, z2, pr2, pm2, psink2

    def phase_cond(state):
        *_rest, steps, done = state
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        y, z, pr, pm, psink, eps, steps, done = state
        e_row, e_col, e_sink = excesses(y, z)
        any_active = (
            jnp.any(e_row > 0)
            | (lax.psum(jnp.sum((e_col > 0).astype(i32)), AXIS) > 0)
            | (e_sink > 0)
        )

        def do_step(_):
            y2, z2, pr2, pm2, psink2 = superstep(y, z, pr, pm, psink, eps)
            return y2, z2, pr2, pm2, psink2, eps, steps + 1, jnp.bool_(False)

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            if refine_waves:
                pr2, pm2, psink2 = price_refine(y, z, pr, pm, psink, new_eps)
                y2, z2 = saturate_eps(y, z, pr2, pm2, psink2, new_eps)
            else:
                pr2, pm2, psink2 = pr, pm, psink
                y2, z2 = saturate(y, z, pr, pm, psink)
            return (
                jnp.where(finished, y, y2),
                jnp.where(finished, z, z2),
                jnp.where(finished, pr, pr2),
                jnp.where(finished, pm, pm2),
                jnp.where(finished, psink, psink2),
                jnp.where(finished, eps, new_eps),
                steps,
                finished,
            )

        return lax.cond(any_active, do_step, next_phase, operand=None)

    y0 = _pcast_varying(jnp.zeros((C, Mloc), i32))
    z0 = _pcast_varying(jnp.zeros((Mloc,), i32))
    state = (y0, z0, pr0, pm0, psink0, eps0, i32(0), jnp.bool_(False))
    y, z, pr, pm, psink, eps, steps, done = lax.while_loop(
        phase_cond, phase_body, state
    )
    e_row, e_col, e_sink = excesses(y, z)
    max_abs = jnp.maximum(
        jnp.maximum(jnp.max(jnp.abs(e_row)), jnp.abs(e_sink)),
        lax.pmax(jnp.max(jnp.abs(e_col)), AXIS),
    )
    return y, steps, done & (max_abs == 0)


@functools.partial(
    jax.jit,  # kschedlint: disable=unregistered-program -- sharded transport research path, bit-parity gated by tests/test_sharded_transport.py
    static_argnames=("mesh", "alpha", "max_supersteps", "refine_waves"),
)
def sharded_transport_solve_tiered(
    mesh: Mesh, wLo, wHi, R, supply, col_cap, eps0,
    alpha: int = 8, max_supersteps: int = 1 << 17, refine_waves: int = 0,
):
    """Tiered (preemption-on) transport with machine columns sharded
    over `mesh`'s '{AXIS}' axis — the multi-chip form of the
    keep-arcs re-solve (graph_manager.go:855-888). wLo/wHi/R
    int32[C, Mp]; Mp divisible by the mesh size. Returns
    (y [C, Mp], steps, converged), bit-identical to the single-device
    tiered solve AT EQUAL refine_waves (production single-device
    preemption runs refine_waves=8 — pass it here too for the same
    superstep counts; the host-solver bit-parity convention keeps 0
    the default)."""
    fn = jax.shard_map(  # kschedlint: disable=unregistered-program -- sharded transport research path, bit-parity gated by tests/test_sharded_transport.py
        functools.partial(
            _sharded_transport_tiered_fn,
            alpha=alpha, max_supersteps=max_supersteps,
            refine_waves=refine_waves,
        ),
        mesh=mesh,
        in_specs=(P(None, AXIS), P(None, AXIS), P(None, AXIS), P(None),
                  P(AXIS), P()),
        out_specs=(P(None, AXIS), P(), P()),
    )
    return fn(wLo, wHi, R, supply, col_cap, eps0)
