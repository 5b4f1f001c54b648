"""Bucketed-ELL push-relabel: the CSR fallback without the global scans.

Same algorithm as solver/jax_solver.py (synchronous Goldberg–Tarjan
cost-scaling push-relabel with maximal pushes, price tightening and an
eps=1 warm attempt) — different data layout. The CSR formulation pays
for generality with GLOBAL segmented reductions: every superstep runs
~4 full-length cumsums plus a `lax.associative_scan` segmented max,
each O(log n) passes over the 2M sorted residual entries — measured
gather/scan-bound at ~60 ms/solve for the 10k x 1k graph on TPU v5e
and JAX-CPU alike (docs/NOTES.md). VERDICT r4 weak #6 asked for one
real lever on that number.

The lever is the degree distribution: scheduling flow graphs are
near-bipartite with a handful of aggregator hubs. The 10k x 1k graph
measures deg p99.9 = 5 with exactly 13 nodes over degree 8 (job
aggregators and the sink, up to deg 28755). So bucket:

- SMALL nodes (deg <= w_small, 99.96% of nodes) pack into one dense
  [Ns, w_small] entry block — per-node reductions are per-ROW
  reductions (one pass, no scan), the maximal-push prefix is a
  w_small-wide row cumsum;
- HUB nodes row-split into a [Rh, w_hub] block (standard CSR row
  splitting); per-hub combines run over a tiny [Hn, Kmax] row-index
  matrix (13 x ~57 here) — noise;
- per-node values assemble by GATHER from the block partials
  (node_kind/node_slot), never by scatter (TPU serializes scatters).

Everything the superstep touches is a dense elementwise op, a short
row reduction, or a flat gather; the log-pass global scans are gone.
The entry blocks are ~2.4x the CSR entry count (padding), but every
op over them is single-pass.

Semantics match the CSR solver: any maximal-push allocation is a valid
discharge, so flows/objectives agree with the oracle exactly even
though per-node allocation ORDER (hence superstep counts) may differ.

Reference parity note: this is still the Flowlessly replacement seam
(scheduling/flow/placement/solver.go:60-123) — same FlowProblem in,
same FlowResult out, warm-started across rounds.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..graph.device_export import FlowProblem
from .base import FlowResult, FlowSolver, check_finite_costs, lower_bound_cost

_BIG = jnp.int32(1 << 30)
_BIG_D = 1 << 28
_P_GUARD = 1 << 30


@dataclass
class EllPlan:
    """Host-prebuilt bucketed-ELL layout of the doubled residual entries."""

    # small block [Ns, Ws]: one row per small node
    s_node: np.ndarray  # int32[Ns]
    s_arc: np.ndarray  # int32[Ns, Ws] (0 on pad)
    s_sign: np.ndarray  # int32[Ns, Ws] +1/-1, 0 on pad
    s_peer: np.ndarray  # int32[Ns, Ws] (self on pad)
    # hub block [Rh, Wh]: hub nodes row-split in entry order
    h_node: np.ndarray  # int32[Rh]
    h_arc: np.ndarray  # int32[Rh, Wh]
    h_sign: np.ndarray  # int32[Rh, Wh]
    h_peer: np.ndarray  # int32[Rh, Wh]
    h_rowhub: np.ndarray  # int32[Rh] hub slot of each row
    h_rowk: np.ndarray  # int32[Rh] row's position within its hub
    # per-hub combine [Hn, K]
    hub_rows: np.ndarray  # int32[Hn, K] row indices (clamped on pad)
    hub_rows_valid: np.ndarray  # bool[Hn, K]
    hub_node: np.ndarray  # int32[Hn]
    # per-node assembly
    node_kind: np.ndarray  # int32[N] 0=empty 1=small 2=hub
    node_slot: np.ndarray  # int32[N] small-row index or hub slot
    # flow update: entry position of each arc's fwd/bwd entry in the
    # CONCATENATED flat delta array [Ns*Ws + Rh*Wh]
    fwd_flat: np.ndarray  # int32[M]
    bwd_flat: np.ndarray  # int32[M]
    src: np.ndarray  # int32[M] endpoints the plan was built for
    dst: np.ndarray  # int32[M]


def build_ell_plan(
    src: np.ndarray, dst: np.ndarray, num_nodes: int,
    w_small: int = 8, w_hub: int = 512,
) -> EllPlan:
    n = num_nodes
    m = len(src)
    node = np.concatenate([src, dst]).astype(np.int64)  # kschedlint: host-only (numpy plan build)
    peer = np.concatenate([dst, src]).astype(np.int32)
    arc = np.concatenate([np.arange(m), np.arange(m)]).astype(np.int32)
    sign = np.concatenate(
        [np.ones(m, np.int32), -np.ones(m, np.int32)]
    )
    deg = np.bincount(node, minlength=n)
    # in-node rank of every doubled entry, via stable node sort
    order = np.argsort(node, kind="stable")
    row_ptr = np.zeros(n + 1, np.int64)  # kschedlint: host-only (numpy plan build)
    row_ptr[1:] = np.cumsum(deg)
    rank = np.empty(2 * m, np.int64)  # kschedlint: host-only (numpy plan build)
    rank[order] = np.arange(2 * m) - row_ptr[node[order]]

    is_hub_node = deg > w_small
    small_ids = np.nonzero((deg > 0) & ~is_hub_node)[0]
    hub_ids = np.nonzero(is_hub_node)[0]
    ns = max(len(small_ids), 1)
    hn = max(len(hub_ids), 1)
    small_slot = np.full(n, 0, np.int64)  # kschedlint: host-only (numpy plan build)
    small_slot[small_ids] = np.arange(len(small_ids))
    hub_slot = np.full(n, 0, np.int64)  # kschedlint: host-only (numpy plan build)
    hub_slot[hub_ids] = np.arange(len(hub_ids))

    # hub row allocation: ceil(deg/w_hub) consecutive rows per hub
    hub_deg = deg[hub_ids] if len(hub_ids) else np.zeros(0, np.int64)  # kschedlint: host-only (numpy plan build)
    rows_per_hub = (hub_deg + w_hub - 1) // w_hub
    hub_row_start = np.zeros(len(hub_ids) + 1, np.int64)  # kschedlint: host-only (numpy plan build)
    hub_row_start[1:] = np.cumsum(rows_per_hub)
    rh = max(int(hub_row_start[-1]), 1)
    kmax = max(int(rows_per_hub.max()) if len(rows_per_hub) else 0, 1)

    s_node = np.zeros(ns, np.int32)
    s_node[: len(small_ids)] = small_ids
    s_arc = np.zeros((ns, w_small), np.int32)
    s_sign = np.zeros((ns, w_small), np.int32)
    s_peer = np.tile(s_node[:, None], (1, w_small)).astype(np.int32)
    h_node = np.zeros(rh, np.int32)
    h_rowhub = np.zeros(rh, np.int32)
    h_rowk = np.zeros(rh, np.int32)
    for i, hub in enumerate(hub_ids):
        r0, r1 = hub_row_start[i], hub_row_start[i + 1]
        h_node[r0:r1] = hub
        h_rowhub[r0:r1] = i
        h_rowk[r0:r1] = np.arange(r1 - r0)
    h_arc = np.zeros((rh, w_hub), np.int32)
    h_sign = np.zeros((rh, w_hub), np.int32)
    h_peer = np.tile(h_node[:, None], (1, w_hub)).astype(np.int32)

    # scatter entries into their block cells (host numpy, build-time only)
    e_small = ~is_hub_node[node]
    srow = small_slot[node[e_small]]
    scol = rank[e_small]
    s_arc[srow, scol] = arc[e_small]
    s_sign[srow, scol] = sign[e_small]
    s_peer[srow, scol] = peer[e_small]
    e_hub = ~e_small
    hrow = hub_row_start[hub_slot[node[e_hub]]] + rank[e_hub] // w_hub
    hcol = rank[e_hub] % w_hub
    h_arc[hrow, hcol] = arc[e_hub]
    h_sign[hrow, hcol] = sign[e_hub]
    h_peer[hrow, hcol] = peer[e_hub]

    # flat position of every doubled entry in concat([small, hub]) order
    flat = np.empty(2 * m, np.int64)  # kschedlint: host-only (numpy plan build)
    flat[e_small] = srow * w_small + scol
    flat[e_hub] = ns * w_small + hrow * w_hub + hcol

    hub_rows = np.zeros((hn, kmax), np.int32)
    hub_rows_valid = np.zeros((hn, kmax), bool)
    for i in range(len(hub_ids)):
        k = int(rows_per_hub[i])
        hub_rows[i, :k] = np.arange(hub_row_start[i], hub_row_start[i + 1])
        hub_rows_valid[i, :k] = True
    hub_node = np.zeros(hn, np.int32)
    hub_node[: len(hub_ids)] = hub_ids

    node_kind = np.where(
        deg == 0, 0, np.where(is_hub_node, 2, 1)
    ).astype(np.int32)
    node_slot = np.where(is_hub_node, hub_slot, small_slot).astype(np.int32)

    return EllPlan(
        s_node=s_node, s_arc=s_arc, s_sign=s_sign, s_peer=s_peer,
        h_node=h_node, h_arc=h_arc, h_sign=h_sign, h_peer=h_peer,
        h_rowhub=h_rowhub, h_rowk=h_rowk,
        hub_rows=hub_rows, hub_rows_valid=hub_rows_valid,
        hub_node=hub_node,
        node_kind=node_kind, node_slot=node_slot,
        fwd_flat=flat[:m].astype(np.int32),
        bwd_flat=flat[m:].astype(np.int32),
        src=src.copy(), dst=dst.copy(),
    )



def _g2(table, idx2):
    """2D-indexed gather. Measured equivalent to a flat gather of the
    same element count on TPU (~2.0 ms per 262k int32 elements, i.e.
    ~7.6 ns/element, timed with REAL carried dependencies; an earlier
    flat+optimization_barrier+reshape variant that appeared 13x faster
    was a dead-code artifact). Kept as a helper so the gather cost
    model has one grep-able seam."""
    return table[idx2]

@functools.partial(
    jax.jit, static_argnames=("alpha", "max_supersteps", "tighten_sweeps", "telemetry_cap")  # kschedlint: program=ell_solve
)
def _solve_mcmf_ell(
    cap, cost, supply, flow0, eps_init,
    s_node, s_arc, s_sign, s_peer,
    h_node, h_arc, h_sign, h_peer, h_rowhub, h_rowk,
    hub_rows, hub_rows_valid, hub_node, node_kind, node_slot,
    fwd_flat, bwd_flat, a_src, a_dst,
    alpha: int = 8,
    max_supersteps: int = 50_000,
    tighten_sweeps: int = 32,
    telemetry_cap: int = 0,
):
    """telemetry_cap > 0 appends the superstep-indexed telemetry ring
    (obs/soltel.py layout) to the returned tuple — same contract as
    solver/jax_solver.py `_solve_mcmf`; cap=0 traces the exact
    pre-telemetry jaxpr."""
    from ..obs.soltel import SOLTEL_WIDTH

    i32 = jnp.int32
    kmax = hub_rows.shape[1]

    # entry-block constants (costs/caps don't change during a solve)
    sc_s = s_sign * _g2(cost, s_arc)  # signed cost per small entry
    sc_h = h_sign * _g2(cost, h_arc)
    cap_s = _g2(cap, s_arc)
    cap_h = _g2(cap, h_arc)

    def per_node(part_s, part_h_row, combine, identity):
        """Assemble a per-node [N] value from block partials by gather.
        `combine` reduces a hub's row partials (axis=1)."""
        hub_part = combine(
            jnp.where(
                hub_rows_valid, part_h_row[hub_rows], identity
            ),
            axis=1,
        )
        v = jnp.where(
            node_kind == 2, hub_part[node_slot], part_s[node_slot]
        )
        return jnp.where(node_kind == 0, identity, v)

    def residuals(flow):
        f_s = _g2(flow, s_arc)
        f_h = _g2(flow, h_arc)
        r_s = jnp.where(
            s_sign > 0, cap_s - f_s,
            jnp.where(s_sign < 0, f_s, i32(0)),
        )
        r_h = jnp.where(
            h_sign > 0, cap_h - f_h,
            jnp.where(h_sign < 0, f_h, i32(0)),
        )
        return r_s, r_h

    def excess_of(flow):
        out_s = jnp.sum(s_sign * _g2(flow, s_arc), axis=1)
        out_h = jnp.sum(h_sign * _g2(flow, h_arc), axis=1)
        return supply - per_node(out_s, out_h, jnp.sum, i32(0))

    def saturate(flow, p):
        rc_fwd = cost + p[a_src] - p[a_dst]
        return jnp.where(rc_fwd < 0, cap, jnp.where(rc_fwd > 0, i32(0), flow))

    def tighten(flow):
        excess0 = excess_of(flow)
        r_s, r_h = residuals(flow)
        d0 = jnp.where(excess0 < 0, i32(0), i32(_BIG_D))

        def t_cond(state):
            _d, changed, it = state
            return changed & (it < tighten_sweeps)

        def t_body(state):
            d, _, it = state
            cand_s = jnp.where(r_s > 0, sc_s + _g2(d, s_peer), i32(_BIG_D))
            cand_h = jnp.where(r_h > 0, sc_h + _g2(d, h_peer), i32(_BIG_D))
            best = per_node(
                jnp.min(cand_s, axis=1), jnp.min(cand_h, axis=1),
                jnp.min, i32(_BIG_D),
            )
            d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
            return d2, jnp.any(d2 != d), it + 1

        d, _, _ = lax.while_loop(t_cond, t_body, (d0, jnp.bool_(True), i32(0)))
        return -jnp.minimum(d, i32(_BIG_D))

    def superstep(flow, p, eps, excess):
        r_s, r_h = residuals(flow)
        pp_s = _g2(p, s_peer)
        pp_h = _g2(p, h_peer)
        rc_s = sc_s + p[s_node][:, None] - pp_s
        rc_h = sc_h + p[h_node][:, None] - pp_h
        e_s = excess[s_node]
        e_h = excess[h_node]
        adm_s = (r_s > 0) & (rc_s < 0) & (e_s[:, None] > 0)
        adm_h = (r_h > 0) & (rc_h < 0) & (e_h[:, None] > 0)
        ra_s = jnp.where(adm_s, r_s, i32(0))
        ra_h = jnp.where(adm_h, r_h, i32(0))

        # maximal push: allocate each node's excess across admissible
        # entries in block order via exclusive prefix sums — per-row
        # cumsum for smalls; hubs add a cross-row offset (per-hub
        # exclusive cumsum of row totals over the tiny [Hn, K] matrix)
        pre_s = jnp.cumsum(ra_s, axis=1) - ra_s
        row_tot = jnp.sum(ra_h, axis=1)
        hub_row_tot = jnp.where(hub_rows_valid, row_tot[hub_rows], i32(0))
        hub_excl = jnp.cumsum(hub_row_tot, axis=1) - hub_row_tot
        row_off = hub_excl.reshape(-1)[h_rowhub * kmax + h_rowk]
        pre_h = (jnp.cumsum(ra_h, axis=1) - ra_h) + row_off[:, None]

        d_s = jnp.clip(e_s[:, None] - pre_s, 0, ra_s)
        d_h = jnp.clip(e_h[:, None] - pre_h, 0, ra_h)

        delta_flat = jnp.concatenate([d_s.reshape(-1), d_h.reshape(-1)])
        new_flow = flow + delta_flat[fwd_flat] - delta_flat[bwd_flat]

        pushed = per_node(
            jnp.sum(d_s, axis=1), jnp.sum(d_h, axis=1), jnp.sum, i32(0)
        )
        sum_r = per_node(
            jnp.sum(r_s, axis=1), jnp.sum(r_h, axis=1), jnp.sum, i32(0)
        )
        cand_s = jnp.where(r_s > 0, pp_s - sc_s, -_BIG)
        cand_h = jnp.where(r_h > 0, pp_h - sc_h, -_BIG)
        best = per_node(
            jnp.max(cand_s, axis=1), jnp.max(cand_h, axis=1),
            jnp.max, -_BIG,
        )
        relabel = (excess > 0) & (pushed == 0) & (sum_r > 0)
        new_p = jnp.where(relabel, best - eps, p)
        if not telemetry_cap:
            return new_flow, new_p, ()
        aux = (
            jnp.sum(pushed),
            jnp.sum(relabel.astype(i32)),
            # flow == cap <=> forward residual 0 (zero-cap arcs count:
            # their residual is zero) — matches the CSR/mega counters
            jnp.sum((flow >= cap).astype(i32)),
            jnp.sum(adm_s.astype(i32)) + jnp.sum(adm_h.astype(i32)),
        )
        return new_flow, new_p, aux

    if telemetry_cap:
        from ..obs import soltel as _soltel

        _tel_rows_iota = _soltel.device_rows_iota(telemetry_cap)

    def tel_row(eps, excess, aux):
        return _soltel.device_row(
            eps,
            jnp.sum((excess > 0).astype(i32)),
            jnp.sum(jnp.maximum(excess, 0)),
            *aux,
        )

    def tel_write(tel, steps, row):
        return _soltel.device_ring_write(
            tel, steps, row, telemetry_cap, _tel_rows_iota
        )

    def phase_cond(state):
        steps, done = state[3], state[4]
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        if telemetry_cap:
            flow, p, eps, steps, done, tel = state
        else:
            flow, p, eps, steps, done = state
        excess = excess_of(flow)
        any_active = jnp.any(excess > 0)

        def do_superstep(_):
            f2, p2, aux = superstep(flow, p, eps, excess)
            if not telemetry_cap:
                return f2, p2, eps, steps + 1, jnp.bool_(False)
            tel2 = tel_write(tel, steps, tel_row(eps, excess, aux))
            return f2, p2, eps, steps + 1, jnp.bool_(False), tel2

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            f2 = jnp.where(finished, flow, saturate(flow, p))
            out = (f2, p, jnp.where(finished, eps, new_eps), steps, finished)
            return out + ((tel,) if telemetry_cap else ())

        return lax.cond(any_active, do_superstep, next_phase, operand=None)

    p0 = tighten(flow0)
    flow1 = saturate(flow0, p0)
    state = (flow1, p0, eps_init, i32(0), jnp.bool_(False))
    if telemetry_cap:
        state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
        flow, p, eps, steps, done, tel = lax.while_loop(
            phase_cond, phase_body, state
        )
    else:
        flow, p, eps, steps, done = lax.while_loop(phase_cond, phase_body, state)
    converged = done & (jnp.max(jnp.abs(excess_of(flow))) == 0)
    p_overflow = jnp.max(jnp.abs(p)) >= _P_GUARD
    if telemetry_cap:
        return flow, p, steps, converged, p_overflow, tel
    return flow, p, steps, converged, p_overflow


def _plan_args(plan: EllPlan) -> tuple:
    return tuple(
        jnp.asarray(x)
        for x in (
            plan.s_node, plan.s_arc, plan.s_sign, plan.s_peer,
            plan.h_node, plan.h_arc, plan.h_sign, plan.h_peer,
            plan.h_rowhub, plan.h_rowk,
            plan.hub_rows, plan.hub_rows_valid, plan.hub_node,
            plan.node_kind, plan.node_slot,
            plan.fwd_flat, plan.bwd_flat,
            plan.src.astype(np.int32), plan.dst.astype(np.int32),
        )
    )


class EllSolver(FlowSolver):
    """Bucketed-ELL cost-scaling push-relabel, warm-started across
    rounds — drop-in for JaxSolver with the scan-free layout."""

    def __init__(
        self, alpha: int = 8, max_supersteps: int = 50_000,
        warm_start: bool = True, w_small: int = 8, w_hub: int = 512,
        telemetry: Optional[int] = None,
    ):
        from .layered import validate_alpha

        self.alpha = validate_alpha(alpha)
        self.max_supersteps = max_supersteps
        self.warm_start = warm_start
        self.w_small = w_small
        self.w_hub = w_hub
        self.telemetry = telemetry
        self._prev: Optional[np.ndarray] = None
        self._prev_dev = None  # warm flow as a device array (no re-upload)
        # endpoints at the LAST SUCCESSFUL SOLVE (see jax_solver: the
        # warm mask must not use a failed round's refresh endpoints)
        self._prev_src_dev = None
        self._prev_dst_dev = None
        self._plan: Optional[EllPlan] = None
        self._plan_dev: Optional[tuple] = None
        #: endpoint-generation key of the cached plan (FlowProblem.
        #: plan_key): equal keys skip the O(M) endpoint scans entirely
        self._plan_key = None
        self.last_supersteps = 0
        self.last_telemetry = None

    def reset(self) -> None:
        self._prev = None
        self._prev_dev = None
        self._prev_src_dev = None
        self._prev_dst_dev = None

    def _plan_for(self, src, dst, n, plan_key=None) -> tuple:
        plan = self._plan
        if plan_key is not None and self._plan_key == plan_key and plan is not None:
            return self._plan_dev  # generation key match: no scans at all
        if plan is None or len(plan.src) != len(src) or len(
            plan.node_kind
        ) != n or plan_key is not None or not (
            np.array_equal(plan.src, src) and np.array_equal(plan.dst, dst)
        ):
            plan = build_ell_plan(
                src, dst, n, w_small=self.w_small, w_hub=self.w_hub
            )
            self._plan = plan
            self._plan_dev = _plan_args(plan)
        self._plan_key = plan_key
        return self._plan_dev

    def solve_async(self, problem: FlowProblem):
        n = problem.num_nodes
        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            if (problem.excess > 0).any():
                raise RuntimeError("infeasible flow problem: supply but no arcs")
            return (problem, None, None, None)
        check_finite_costs(problem)
        src = problem.src.astype(np.int32)
        dst = problem.dst.astype(np.int32)
        max_cost = int(np.abs(problem.cost).max()) if m else 0
        if max_cost * n >= (1 << 30):
            raise OverflowError(
                f"scaled costs overflow int32: max|cost|={max_cost} at {n} nodes"
            )

        prev_plan = self._plan
        plan_dev = self._plan_for(
            src, dst, n, plan_key=getattr(problem, "plan_key", None)
        )

        from ..obs import soltel

        tel_cap = soltel.resolve_cap(self.telemetry)
        resident = getattr(problem, "d_cap", None) is not None
        if resident:
            # device-resident problem handle: persistent buffers in,
            # device-carried warm flow — no per-round array re-uploads
            # (see solver/jax_solver.py; same contract)
            from ..graph.device_export import resident_solver_inputs

            dev_args, flow0_dev, _warm = resident_solver_inputs(
                problem, self._prev_dev, self._prev_src_dev,
                self._prev_dst_dev, self.warm_start,
            )
        else:
            cap = problem.cap.astype(np.int32)
            supply = problem.excess.astype(np.int32)
            cost = problem.cost.astype(np.int32) * np.int32(n)
            dev_args = (jnp.asarray(cap), jnp.asarray(cost), jnp.asarray(supply))
            flow0 = np.zeros(m, dtype=np.int32)
            if self.warm_start and self._prev is not None:
                f_prev = self._prev
                if len(f_prev) == m and prev_plan is not None and len(prev_plan.src) == m:
                    same = (prev_plan.src == src) & (prev_plan.dst == dst)
                    flow0 = np.where(same, np.minimum(f_prev, cap), 0).astype(np.int32)
            flow0_dev = jnp.asarray(flow0)
        fut = _solve_mcmf_ell(
            *dev_args,
            flow0_dev,
            jnp.asarray(np.int32(1)),
            *plan_dev,
            alpha=self.alpha,
            max_supersteps=min(4096, self.max_supersteps),
            telemetry_cap=tel_cap,
        )
        cold = (np.zeros(m, dtype=np.int32), max(1, max_cost * n))
        return (problem, fut, (dev_args, plan_dev, cold, tel_cap), resident)

    def complete(self, pending) -> FlowResult:
        from ..obs import soltel

        problem, fut, rest, resident = pending
        if fut is None:
            self.last_telemetry = None
            return FlowResult(
                flow=np.zeros(len(problem.src), dtype=np.int64),  # kschedlint: host-only (FlowResult contract is int64)
                objective=0, iterations=0,
            )
        dev_args, plan_dev, (f0_cold, eps_cold), tel_cap = rest
        tel_buf = None
        if tel_cap:
            flow, p, steps, converged, p_overflow, tel_buf = fut
        else:
            flow, p, steps, converged, p_overflow = fut
        if not (bool(converged) and not bool(p_overflow)):
            out = _solve_mcmf_ell(
                *dev_args,
                jnp.asarray(f0_cold),
                jnp.asarray(np.int32(eps_cold)),
                *plan_dev,
                alpha=self.alpha,
                max_supersteps=self.max_supersteps,
                telemetry_cap=tel_cap,
            )
            if tel_cap:
                flow, p, steps, converged, p_overflow, tel_buf = out
            else:
                flow, p, steps, converged, p_overflow = out
        self.last_supersteps = int(steps)
        # budget = the SOLVER's budget, not the warm attempt's 4096 cap
        # (see jax_solver.complete)
        self.last_telemetry = (
            soltel.decode(
                tel_buf, int(steps), tel_cap, "ell", self.max_supersteps,
                converged=bool(converged) and not bool(p_overflow),
                nodes=problem.num_nodes, arcs=len(problem.src),
            )
            if tel_buf is not None
            else None
        )
        if bool(p_overflow) or not bool(converged):
            self.reset()
        if bool(p_overflow):
            raise OverflowError("push-relabel potentials approached int32 range")
        if not bool(converged):
            tel = self.last_telemetry
            raise soltel.SolverStallError(
                f"push-relabel did not converge within {self.max_supersteps} "
                "supersteps; the flow problem may be infeasible",
                reason=soltel.detect_stall(tel) if tel is not None else None,
                telemetry=tel,
            )
        flow_np = np.asarray(flow)
        if self.warm_start:
            self._prev = flow_np.astype(np.int32)
            self._prev_dev = flow if resident else None
            self._prev_src_dev = problem.d_src if resident else None
            self._prev_dst_dev = problem.d_dst if resident else None
        objective = int(
            (flow_np.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
        ) + lower_bound_cost(problem)
        return FlowResult(
            flow=flow_np.astype(np.int64), objective=objective,  # kschedlint: host-only (FlowResult contract is int64)
            iterations=int(steps),
        )

    def solve(self, problem: FlowProblem) -> FlowResult:
        return self.complete(self.solve_async(problem))


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(__name__, "ell_solve")
