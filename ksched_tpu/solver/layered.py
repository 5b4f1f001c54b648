"""Dense layered MCMF: the TPU fast path for the aggregate topology.

The quincy-style scheduling graph the bulk scheduler builds
(scheduler/bulk.py; reference: trivial_cost_modeler.go:101-110 +
graph_manager.go:931-1010) is layered and aggregate:

    task --(u)--> unsched[job] --> sink
    task --(e)--> EC[class]
    EC[c] --(cost[c,m], cap free_m)--> machine_m --> PU --> sink

Tasks of one class are interchangeable (identical arc costs u and e for
every job — trivial_cost_modeler.go:41-43,69-74), the PU layer never
binds tighter than its machine (machine free capacity IS the sum of its
PU free capacities), and the per-job unscheduled aggregators always have
enough escape capacity. So the min-cost flow collapses EXACTLY to a
transportation problem over a dense [C, M+1] matrix:

    minimize    sum_{c,m} y[c,m] * w[c,m]
    subject to  sum_m y[c,m] == supply[c]          (every task routed)
                sum_c y[c,m] <= col_cap[m]         (machine free slots)

with w[c,m] = cost[c,m] + e - u for real machines and w[c,M] = 0 for the
"unscheduled" column (cap = total supply, so the problem is always
feasible — the unscheduled-aggregator invariant, graph_manager.go:
1270-1305). The full 10k-task solve becomes a ~[4, 1024] dense problem.

Why this is the TPU formulation: the general CSR push-relabel
(solver/jax_solver.py) is correct for arbitrary graphs but spends
milliseconds per superstep in random gathers — TPU serializes them.
Here every push/relabel superstep is ~20 fused dense ops on one
[C, M+1] tile (row/col reductions, axis cumsums, elementwise masks):
microseconds on the VPU, no gathers, no scatters, one compiled
executable reused across rounds.

The kernel is the same synchronous Goldberg-Tarjan cost-scaling
push-relabel as the general solver (costs pre-scaled so eps=1 is exact;
maximal pushes via in-row exclusive prefix sums; jump relabels), plus a
Bellman-Ford price-tightening prelude which is EXACT here (the residual
graph of the zero flow has diameter 2), so the eps=1 discharge follows
shortest paths from the start.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

#: sentinel magnitudes shared with parallel/sharded_transport.py — the
#: sharded solve's bit-identity contract depends on matching fills
BIG = 1 << 30
BIG_D = 1 << 28
_BIG = np.int32(BIG)
_BIG_D = np.int32(BIG_D)


def validate_job_unsched_cost(job_unsched_cost, num_jobs: int):
    """Normalize/validate the per-job unsched-cost knob (None passes
    through). One definition shared by BulkCluster, DeviceBulkCluster,
    and tests so the three call sites cannot drift."""
    if job_unsched_cost is None:
        return None
    out = np.asarray(job_unsched_cost, np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    if out.shape != (num_jobs,):
        raise ValueError(
            f"job_unsched_cost must have shape ({num_jobs},), got {out.shape}"
        )
    # Values at or beyond COST_SCALE_LIMIT are guaranteed to overflow
    # once scaled — and the device path casts to int32 BEFORE its
    # in-graph guard, so an unchecked huge cost would silently wrap to
    # a strongly-negative escape instead of raising like the host path.
    if out.size and int(np.abs(out).max()) >= COST_SCALE_LIMIT:
        raise OverflowError(
            f"job_unsched_cost magnitude {int(np.abs(out).max())} exceeds "
            f"the scaled-cost limit {COST_SCALE_LIMIT}"
        )
    return out


def validate_alpha(alpha: int) -> int:
    """alpha < 2 would make the eps phase schedule a fixed point and
    hang the solve loop; one guard shared by every constructor that
    accepts the knob."""
    if alpha < 2:
        raise ValueError(f"alpha must be >= 2 (got {alpha}): the eps "
                         "phase schedule would never shrink")
    return int(alpha)


@dataclass
class LayeredProblem:
    """The aggregate scheduling round, in row-by-machine form. A row is
    a commodity of interchangeable tasks: a task class in the basic
    shape, or a (job, class) group when per-job unscheduled costs
    differentiate jobs (the reference's per-job unsched aggregators,
    graph_manager.go:1291-1305 — each job's escape arc has its own
    cost, so tasks of one class but different jobs are distinct
    commodities)."""

    supply: np.ndarray  # int32[C] unplaced live tasks per row
    col_cap: np.ndarray  # int32[M] free slots per machine
    cost_cm: np.ndarray  # int32[C, M] EC->machine arc cost per row
    unsched_cost: int  # u: task->unsched arc cost
    ec_cost: int  # e: task->EC arc cost
    #: optional per-row unsched costs overriding the scalar (int[C]);
    #: row r's escape then costs row_unsched_cost[r]
    row_unsched_cost: Optional[np.ndarray] = None


@dataclass
class LayeredResult:
    y: np.ndarray  # int64[C, M] tasks of class c placed on machine m
    num_unsched: int
    objective: int  # in full-graph units: u*unplaced + sum((e+cost)*y)
    supersteps: int


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, dtype=x.dtype)
    out[: len(x)] = x
    return out


def pad_geometry(num_machines: int, num_classes: int) -> Tuple[int, int]:
    """(Mp, n_scale) for the padded transport problem — shared by the
    host path (solve_layered) and the device-resident path
    (scheduler/device_bulk.py) so the two cannot drift.

    Mp pads the machine axis to a lane-friendly multiple of 128 with
    room for the unsched column; n_scale is the cost multiplier that
    makes eps=1 termination exact: smallest pow2 > the REAL node count
    C + (M+1) + 1 (rows + live columns + sink). Padded columns have no
    arcs (cap 0), so residual cycles traverse only live nodes and the
    exactness bound is independent of Mp — deriving n_scale from Mp
    would inflate the scaled-cost range (and with it the price ground
    the eps=1 phase must cover, i.e. supersteps) by the pad factor; the
    mesh-sharded solver pads Mp to a multiple of 128*devices, where
    that inflation was measured at ~50x supersteps on small instances."""
    Mp = ((num_machines + 1 + 127) // 128) * 128
    n_scale = 1
    while n_scale < num_classes + num_machines + 3:
        n_scale <<= 1
    return Mp, n_scale


#: scaled costs must stay below 2^29 for int32 arithmetic headroom:
#: with |wS| < 2^29 and pm clamped to ±2^28 (transport_tighten), the
#: derived row prices satisfy |pr| <= 2^28 + 2^29, so any reduced cost
#: rcf = wS + pr - pm is bounded by 2^29 + (2^28 + 2^29) + 2^28 =
#: 1.5 * 2^30 < 2^31 - 1, wrap-free. (At 2^30 a worst-case pair of
#: near-limit arcs of opposite sign could overflow the guard.)
COST_SCALE_LIMIT = 1 << 29


def default_eps0(n_scale: int) -> int:
    """The tuned eps-schedule start for iterative transport solves:
    n_scale/4 — a quarter of one original cost unit. Valid for any
    value — tightened potentials make the zero flow 0-optimal
    regardless; callers keep a full-range fallback. One definition so
    the three solve sites cannot drift.

    Measured (round-3 tail study, tools/tail_repro.py on captured
    steady-state whare + coco tail rounds): deeply sub-quantum starts
    are the tail's CAUSE — at eps << one cost unit the synchronous
    maximal pushes circulate flow around admissible cycles whose total
    reduced cost sits between -len*eps and 0, with prices inching down
    one eps per failed push (traced: 7k steps with excess sloshing
    rows<->cols through 1-3 active columns and near-zero relabels).
    The old n_scale/16 start burned 2.5-7k supersteps per contended
    round; the superstep count is invariant to n_scale at a FIXED
    eps0/n_scale ratio (measured: 64x n_scale change, identical
    counts), so the ratio is the knob. The landscape is jagged and
    regime-dependent (whare tails prefer 1.0: mean 934; coco tails
    prefer 1/4: mean 419), but 1/4 has the best combined worst case —
    max 1756 supersteps over every captured tail instance vs 3270 for
    1.0 and 7136 for 1/16 — and is alpha-insensitive (a4 == a8 within
    noise). Objectives identical across all starts, as theory demands.

    Only correct for instances that are NOT oversubscribed: when total
    supply exceeds real machine capacity, prices must descend deep on
    the unsched column and the short start pays for the descent in
    eps-sized relabels (measured 1387 vs 284 supersteps on a 3x16 toy
    at 1.25x oversubscription). Use choose_eps0 where supply/capacity
    are at hand."""
    return max(1, n_scale // 4)


def choose_eps0(n_scale: int, eps_full, supply_total, real_cap_total,
                short=None):
    """Adaptive eps-schedule start: the tuned short start for the
    common regime (supply fits real machine capacity — steady-state
    backlogs vs free slots), the classic full-range start when the
    instance is oversubscribed. Works on Python ints or traced scalars
    (returns a traced scalar if any input is traced). `short` overrides
    the default_eps0 start for regimes with their own tuning (the
    grouped locality solve uses n_scale — see device_bulk)."""
    if short is None:
        short = default_eps0(n_scale)
    if isinstance(supply_total, (int, np.integer)) and isinstance(
        real_cap_total, (int, np.integer)
    ):
        return eps_full if supply_total > real_cap_total else short
    return jnp.where(
        supply_total > real_cap_total,
        jnp.int32(eps_full),
        jnp.int32(short),
    )


def _excesses(supply, y, z):
    e_row = supply - jnp.sum(y, axis=1)
    e_col = jnp.sum(y, axis=0) - z
    e_sink = jnp.sum(z) - jnp.sum(supply)
    return e_row, e_col, e_sink


def transport_tighten(wS, U, col_cap, pm0=None):
    """Potentials making the ZERO flow 0-optimal, from optional carried
    machine prices pm0 (warm start across rounds).

    pm = pm0 on live columns (cap>0), sunk for dead ones; row prices are
    re-derived as pr[c] = max_{U>0}(pm - wS) so every forward residual
    arc has reduced cost >= 0, and psink = min_{cap>0} pm likewise. Any
    pm0 is VALID (it is clamped to ±_BIG_D, then optimality of the
    start point is re-established by construction — without the clamp a
    price vector carried over many rounds drifts monotonically negative
    until pm0 - wS wraps int32) — a good pm0 just makes the discharge
    shorter. With pm0 = None/zeros this reduces exactly to shortest
    residual-cost distances for the zero flow (the all-forward residual
    graph has diameter 2), i.e. the cold start."""
    i32 = jnp.int32
    big_d = jnp.int32(_BIG_D)
    if pm0 is None:
        pm0 = jnp.zeros_like(col_cap)
    live = col_cap > 0
    pm = jnp.where(live, jnp.clip(pm0, -big_d, big_d), -big_d)
    has_arc = U > 0
    pr = jnp.max(jnp.where(has_arc, pm[None, :] - wS, -big_d), axis=1)
    pr = jnp.where(jnp.any(has_arc, axis=1), pr, i32(0))
    psink = jnp.min(jnp.where(live, pm, big_d))
    psink = jnp.where(jnp.any(live), psink, i32(0))
    return pr, pm, psink


def transport_saturate(wS, U, col_cap, y, z, pr, pm, psink):
    i32 = jnp.int32
    rcf = wS + pr[:, None] - pm[None, :]
    y2 = jnp.where(rcf < 0, U, jnp.where(rcf > 0, i32(0), y))
    rcs = pm - psink
    z2 = jnp.where(rcs < 0, col_cap, jnp.where(rcs > 0, i32(0), z))
    return y2, z2


def transport_saturate_eps(wS, U, col_cap, y, z, pr, pm, psink, eps):
    """Partial saturate: reset ONLY the arcs violating eps-optimality
    (|reduced cost| beyond eps on a residual direction), keeping the
    rest of the flow. With eps=0 this is transport_saturate. Used with
    price refinement, where most of the converged flow already
    satisfies the next phase's eps and re-flooding it would re-fight
    every contended column from scratch."""
    i32 = jnp.int32
    rcf = wS + pr[:, None] - pm[None, :]
    y2 = jnp.where(rcf < -eps, U, jnp.where(rcf > eps, i32(0), y))
    rcs = pm - psink
    z2 = jnp.where(rcs < -eps, col_cap, jnp.where(rcs > eps, i32(0), z))
    return y2, z2


def _price_refine(wS, U, col_cap, y, z, pr, pm, psink, eps, waves: int):
    """Price refinement (the classic cost-scaling speedup, cf. CS2's
    price updates): `waves` synchronous Bellman-Ford relaxations that
    LOWER potentials toward eps-optimality of the CURRENT flow before
    the next phase. Each eps-optimality constraint has the form
    potential <= other + slack over a residual arc; relaxing monotonely
    downward converges in graph-diameter waves on this shallow layered
    structure. The wave count is bounded (a residual cycle more
    negative than the slack would otherwise descend forever — possible
    while eps shrinks); whatever violations remain are cleaned by
    transport_saturate_eps, so optimality never depends on the refit
    finishing."""
    big = jnp.int32(_BIG)
    big_d = jnp.int32(_BIG_D)

    def body(_, state):
        pr, pm, psink = state
        # fwd residual row->col (U-y>0): pm <= wS + pr + eps
        bound_m = jnp.min(
            jnp.where(U - y > 0, wS + pr[:, None] + eps, big), axis=0
        )
        pm2 = jnp.maximum(jnp.minimum(pm, bound_m), -big_d)
        # sink->col residual (z>0): pm <= psink + eps
        pm2 = jnp.minimum(pm2, jnp.where(z > 0, psink + eps, big))
        # bwd residual col->row (y>0): pr <= pm - wS + eps
        bound_r = jnp.min(
            jnp.where(y > 0, pm2[None, :] - wS + eps, big), axis=1
        )
        pr2 = jnp.maximum(jnp.minimum(pr, bound_r), -big_d)
        # col->sink residual (cap-z>0): psink <= pm + eps
        bound_s = jnp.min(jnp.where(col_cap - z > 0, pm2 + eps, big))
        psink2 = jnp.maximum(jnp.minimum(psink, bound_s), -big_d)
        return pr2, pm2, psink2

    return lax.fori_loop(0, waves, body, (pr, pm, psink))


def transport_superstep(wS, U, supply, col_cap, y, z, pr, pm, psink, eps,
                        with_stats: bool = False):
    """One synchronous push/relabel wave over the dense bipartite
    residual graph. A fixed point once no node has positive excess, so
    it is safe to run under a fixed trip count (lax.fori_loop).

    with_stats=True additionally returns the soltel counter tuple
    (pushed, relabels, saturated, work) computed from this wave's own
    intermediates (obs/soltel.py cols 3..6) — observational only,
    never fed back, so flows are bit-identical either way."""
    i32 = jnp.int32
    big = jnp.int32(_BIG)
    e_row, e_col, e_sink = _excesses(supply, y, z)
    rcf = wS + pr[:, None] - pm[None, :]

    # --- rows push forward along admissible arcs (maximal push via
    # in-row exclusive prefix sums) ---
    r_fwd = U - y
    adm_f = (r_fwd > 0) & (rcf < 0)
    r_adm = jnp.where(adm_f, r_fwd, i32(0))
    excl = jnp.cumsum(r_adm, axis=1) - r_adm
    delta_f = jnp.clip(e_row[:, None] - excl, 0, r_adm)

    # --- columns push: entry 0 = col->sink, entries 1..C = backward
    # col->row (returning flow) ---
    r_s = col_cap - z
    rc_s = pm - psink
    r_b = y  # backward residual col->row
    rc_b = pm[None, :] - pr[:, None] - wS  # cost of bwd arc is -wS
    colA = jnp.concatenate(
        [
            jnp.where((r_s > 0) & (rc_s < 0), r_s, i32(0))[None, :],
            jnp.where((r_b > 0) & (rc_b < 0), r_b, i32(0)),
        ],
        axis=0,
    )  # [1+C, Mp1], allocation order: sink first, then rows
    exclA = jnp.cumsum(colA, axis=0) - colA
    deltaA = jnp.clip(e_col[None, :] - exclA, 0, colA)
    delta_s = deltaA[0]
    delta_b = deltaA[1:]

    # --- sink pushes back (transient positive excess after a
    # saturate): backward sink->col arcs, residual z, cost 0 ---
    r_zb = z
    rc_zb = psink - pm
    zb_adm = jnp.where((r_zb > 0) & (rc_zb < 0), r_zb, i32(0))
    excl_zb = jnp.cumsum(zb_adm) - zb_adm
    delta_zb = jnp.clip(e_sink - excl_zb, 0, zb_adm)

    y2 = y + delta_f - delta_b
    z2 = z + delta_s - delta_zb

    # --- jump relabels for active nodes that pushed nothing ---
    pushed_row = jnp.sum(delta_f, axis=1)
    cand_row = jnp.where(r_fwd > 0, pm[None, :] - wS, -big)
    best_row = jnp.max(cand_row, axis=1)
    relabel_row = (e_row > 0) & (pushed_row == 0)
    pr2 = jnp.where(relabel_row, best_row - eps, pr)

    pushed_col = delta_s + jnp.sum(delta_b, axis=0)
    cand_col = jnp.maximum(
        jnp.max(jnp.where(y > 0, pr[:, None] + wS, -big), axis=0),
        jnp.where(r_s > 0, psink, -big),
    )
    relabel_col = (e_col > 0) & (pushed_col == 0)
    pm2 = jnp.where(relabel_col, cand_col - eps, pm)

    pushed_sink = jnp.sum(delta_zb)
    cand_sink = jnp.max(jnp.where(z > 0, pm, -big))
    relabel_sink = (e_sink > 0) & (pushed_sink == 0)
    psink2 = jnp.where(relabel_sink, cand_sink - eps, psink)
    if not with_stats:
        return y2, z2, pr2, pm2, psink2
    stats = (
        jnp.sum(delta_f) + jnp.sum(deltaA) + jnp.sum(delta_zb),
        jnp.sum(relabel_row.astype(i32))
        + jnp.sum(relabel_col.astype(i32))
        + relabel_sink.astype(i32),
        jnp.sum(((U > 0) & (y >= U)).astype(i32))
        + jnp.sum(((col_cap > 0) & (z >= col_cap)).astype(i32)),
        jnp.sum((r_adm > 0).astype(i32))
        + jnp.sum((colA > 0).astype(i32))
        + jnp.sum((zb_adm > 0).astype(i32)),
    )
    return y2, z2, pr2, pm2, psink2, stats


# ---------------------------------------------------------------------------
# Tiered (continuation-priced) transport: the preemption-on formulation
# ---------------------------------------------------------------------------
#
# With preemption on (graph_manager.go:855-888), placed tasks re-enter
# every round's solve: machine capacity is total slots (the capacity
# rule flips, :662-667) and a task's CURRENT machine offers a cheaper
# "continuation" price than a fresh placement (TaskContinuationCost vs
# TaskToResourceNodeCost, costmodel/interface.go:75-79). In aggregate
# form each cell (row g, machine m) prices its first R[g,m] units (the
# residents) at wLo = w - discount and the rest at w. A per-cell convex
# two-tier cost is exactly a pair of parallel arcs, so cost-scaling
# push-relabel remains exact: every residual/relabel rule below is the
# parallel-arc rule with the canonical cheapest-first split
# yA = min(y, R), yB = y - yA.


def transport_saturate_tiered(wLo, wHi, R, U, col_cap, y, z, pr, pm, psink):
    """Phase-start saturation, per tier (wLo <= wHi cellwise, so a
    saturated cheap tier is implied by a saturated dear one)."""
    i32 = jnp.int32
    rcl = wLo + pr[:, None] - pm[None, :]
    rch = wHi + pr[:, None] - pm[None, :]
    yA = jnp.minimum(y, R)
    yB = y - yA
    yA2 = jnp.where(rcl < 0, R, jnp.where(rcl > 0, i32(0), yA))
    yB2 = jnp.where(rch < 0, U - R, jnp.where(rch > 0, i32(0), yB))
    rcs = pm - psink
    z2 = jnp.where(rcs < 0, col_cap, jnp.where(rcs > 0, i32(0), z))
    return yA2 + yB2, z2


def transport_saturate_eps_tiered(
    wLo, wHi, R, U, col_cap, y, z, pr, pm, psink, eps
):
    """Tiered twin of transport_saturate_eps: reset ONLY tiers whose
    reduced cost violates eps-optimality, keeping the rest of the
    carried flow (price-refinement phase starts)."""
    i32 = jnp.int32
    rcl = wLo + pr[:, None] - pm[None, :]
    rch = wHi + pr[:, None] - pm[None, :]
    yA = jnp.minimum(y, R)
    yB = y - yA
    yA2 = jnp.where(rcl < -eps, R, jnp.where(rcl > eps, i32(0), yA))
    yB2 = jnp.where(rch < -eps, U - R, jnp.where(rch > eps, i32(0), yB))
    rcs = pm - psink
    z2 = jnp.where(rcs < -eps, col_cap, jnp.where(rcs > eps, i32(0), z))
    return yA2 + yB2, z2


def _price_refine_tiered(
    wLo, wHi, R, U, col_cap, y, z, pr, pm, psink, eps, waves: int
):
    """Tiered twin of _price_refine: synchronous Bellman-Ford
    relaxations lowering potentials toward eps-optimality of the
    CURRENT flow, with each tier's residuals contributing its own
    constraints (fwd tier A at wLo while R-yA>0, fwd tier B at wHi
    while (U-R)-yB>0; bwd with the signs flipped)."""
    big = jnp.int32(_BIG)
    big_d = jnp.int32(_BIG_D)

    def body(_, state):
        pr, pm, psink = state
        yA = jnp.minimum(y, R)
        yB = y - yA
        bound_m = jnp.minimum(
            jnp.min(jnp.where(R - yA > 0, wLo + pr[:, None] + eps, big),
                    axis=0),
            jnp.min(jnp.where((U - R) - yB > 0, wHi + pr[:, None] + eps, big),
                    axis=0),
        )
        pm2 = jnp.maximum(jnp.minimum(pm, bound_m), -big_d)
        pm2 = jnp.minimum(pm2, jnp.where(z > 0, psink + eps, big))
        bound_r = jnp.minimum(
            jnp.min(jnp.where(yA > 0, pm2[None, :] - wLo + eps, big), axis=1),
            jnp.min(jnp.where(yB > 0, pm2[None, :] - wHi + eps, big), axis=1),
        )
        pr2 = jnp.maximum(jnp.minimum(pr, bound_r), -big_d)
        bound_s = jnp.min(jnp.where(col_cap - z > 0, pm2 + eps, big))
        psink2 = jnp.maximum(jnp.minimum(psink, bound_s), -big_d)
        return pr2, pm2, psink2

    return lax.fori_loop(0, waves, body, (pr, pm, psink))


def transport_superstep_tiered(
    wLo, wHi, R, U, supply, col_cap, y, z, pr, pm, psink, eps
):
    """One synchronous push/relabel wave over the two-tier residual
    graph. Identical structure to transport_superstep, with forward and
    backward residuals split by tier (cheap tier fills first, dear tier
    empties first — the canonical split of a convex arc cost)."""
    i32 = jnp.int32
    big = jnp.int32(_BIG)
    e_row, e_col, e_sink = _excesses(supply, y, z)
    yA = jnp.minimum(y, R)
    yB = y - yA
    rcl = wLo + pr[:, None] - pm[None, :]
    rch = wHi + pr[:, None] - pm[None, :]

    # --- rows push forward: tier-A residual at wLo, tier-B at wHi ---
    rA = R - yA
    rB = (U - R) - yB
    r_adm = jnp.where((rA > 0) & (rcl < 0), rA, i32(0)) + jnp.where(
        (rB > 0) & (rch < 0), rB, i32(0)
    )
    excl = jnp.cumsum(r_adm, axis=1) - r_adm
    delta_f = jnp.clip(e_row[:, None] - excl, 0, r_adm)

    # --- columns push: sink first, then dear-tier returns, then cheap ---
    r_s = col_cap - z
    rc_s = pm - psink
    rcb_hi = pm[None, :] - pr[:, None] - wHi  # backward tier B (cost -wHi)
    rcb_lo = pm[None, :] - pr[:, None] - wLo  # backward tier A
    colA = jnp.concatenate(
        [
            jnp.where((r_s > 0) & (rc_s < 0), r_s, i32(0))[None, :],
            jnp.where((yB > 0) & (rcb_hi < 0), yB, i32(0)),
            jnp.where((yA > 0) & (rcb_lo < 0), yA, i32(0)),
        ],
        axis=0,
    )  # [1 + 2C, Mp1]
    C = y.shape[0]
    exclA = jnp.cumsum(colA, axis=0) - colA
    deltaA = jnp.clip(e_col[None, :] - exclA, 0, colA)
    delta_s = deltaA[0]
    delta_b = deltaA[1 : 1 + C] + deltaA[1 + C :]

    # --- sink pushes back (tier-less, as before) ---
    r_zb = z
    rc_zb = psink - pm
    zb_adm = jnp.where((r_zb > 0) & (rc_zb < 0), r_zb, i32(0))
    excl_zb = jnp.cumsum(zb_adm) - zb_adm
    delta_zb = jnp.clip(e_sink - excl_zb, 0, zb_adm)

    y2 = y + delta_f - delta_b
    z2 = z + delta_s - delta_zb

    # --- jump relabels (candidates consider both tiers' residuals) ---
    pushed_row = jnp.sum(delta_f, axis=1)
    cand_row = jnp.maximum(
        jnp.max(jnp.where(rA > 0, pm[None, :] - wLo, -big), axis=1),
        jnp.max(jnp.where(rB > 0, pm[None, :] - wHi, -big), axis=1),
    )
    relabel_row = (e_row > 0) & (pushed_row == 0)
    pr2 = jnp.where(relabel_row, cand_row - eps, pr)

    pushed_col = delta_s + jnp.sum(delta_b, axis=0)
    cand_col = jnp.maximum(
        jnp.maximum(
            jnp.max(jnp.where(yA > 0, pr[:, None] + wLo, -big), axis=0),
            jnp.max(jnp.where(yB > 0, pr[:, None] + wHi, -big), axis=0),
        ),
        jnp.where(r_s > 0, psink, -big),
    )
    relabel_col = (e_col > 0) & (pushed_col == 0)
    pm2 = jnp.where(relabel_col, cand_col - eps, pm)

    pushed_sink = jnp.sum(delta_zb)
    cand_sink = jnp.max(jnp.where(z > 0, pm, -big))
    relabel_sink = (e_sink > 0) & (pushed_sink == 0)
    psink2 = jnp.where(relabel_sink, cand_sink - eps, psink)
    return y2, z2, pr2, pm2, psink2


def _transport_loop_tiered(wLo, wHi, R, U, supply, col_cap, eps_init, alpha,
                           max_supersteps, refine_waves: int = 0):
    """Tiered twin of _transport_loop (cold start: tightening against
    the cheap tier makes the zero flow 0-optimal, since wLo <= wHi).
    refine_waves enables the tiered price refinement between phases —
    measured essential at scale (the preemption-on 50k round burned
    31-58k supersteps/round without it)."""
    i32 = jnp.int32

    def phase_cond(state):
        *_rest, steps, done = state
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        y, z, pr, pm, psink, eps, steps, done = state
        e_row, e_col, e_sink = _excesses(supply, y, z)
        any_active = jnp.any(e_row > 0) | jnp.any(e_col > 0) | (e_sink > 0)

        def do_step(_):
            y2, z2, pr2, pm2, psink2 = transport_superstep_tiered(
                wLo, wHi, R, U, supply, col_cap, y, z, pr, pm, psink, eps
            )
            return y2, z2, pr2, pm2, psink2, eps, steps + 1, jnp.bool_(False)

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            if refine_waves:
                pr2, pm2, psink2 = _price_refine_tiered(
                    wLo, wHi, R, U, col_cap, y, z, pr, pm, psink, new_eps,
                    refine_waves,
                )
                y2, z2 = transport_saturate_eps_tiered(
                    wLo, wHi, R, U, col_cap, y, z, pr2, pm2, psink2, new_eps
                )
            else:
                pr2, pm2, psink2 = pr, pm, psink
                y2, z2 = transport_saturate_tiered(
                    wLo, wHi, R, U, col_cap, y, z, pr, pm, psink
                )
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

    C, Mp1 = wLo.shape
    pr0, pm0, psink0 = transport_tighten(wLo, U, col_cap, None)
    y0 = jnp.zeros((C, Mp1), jnp.int32)
    z0 = jnp.zeros((Mp1,), jnp.int32)
    state = (y0, z0, pr0, pm0, psink0, eps_init, jnp.int32(0), jnp.bool_(False))
    y, z, pr, pm, psink, eps, steps, done = lax.while_loop(
        phase_cond, phase_body, state
    )
    e_row, e_col, e_sink = _excesses(supply, y, z)
    max_abs = jnp.maximum(
        jnp.max(jnp.abs(e_row)), jnp.maximum(jnp.max(jnp.abs(e_col)), jnp.abs(e_sink))
    )
    return y, z, pm, steps, done & (max_abs == 0)


def solve_single_class_tiered(wLo, wHi, R, supply, col_cap):
    """EXACT closed form for one tiered row: expand each column into a
    cheap tier (cap min(R, col_cap), cost wLo) and a base tier (the
    rest at wHi), then greedy-fill strictly-profitable capacity by
    sorted marginal cost — valid because the per-cell cost is convex
    (the cheap tier always fills first). Returns y int32[Mp1] (tier
    totals per column)."""
    i32 = jnp.int32
    Mp1 = wLo.shape[0]
    Reff = jnp.minimum(R, col_cap)
    w2 = jnp.concatenate([wLo, wHi])
    cap2 = jnp.concatenate([Reff, col_cap - Reff])
    take = jnp.where(w2 < 0, cap2, i32(0))
    order = jnp.argsort(w2)
    take_s = take[order]
    excl = jnp.cumsum(take_s) - take_s
    y_s = jnp.clip(supply - excl, 0, take_s)
    inv = jnp.argsort(order)
    y2 = y_s[inv]
    return y2[:Mp1] + y2[Mp1:]


def _solve_transport_tiered(wLo, wHi, R, supply, col_cap, eps_init,
                            alpha: int = 8, max_supersteps: int = 20_000,
                            refine_waves: int = 0):
    """XLA form of the tiered solve behind ops.transport_solve_tiered
    (the fused kernel is ops/transport_pallas.py
    transport_loop_pallas_tiered — bit-identical)."""
    R = jnp.minimum(R, jnp.minimum(supply[:, None], col_cap[None, :]))
    U = jnp.minimum(supply[:, None], col_cap[None, :])
    y, _z, pm, steps, conv = _transport_loop_tiered(
        wLo, wHi, R, U, supply, col_cap, eps_init, alpha, max_supersteps,
        refine_waves=refine_waves,
    )
    return y, pm, steps, conv


def transport_fori_tiered(wLo, wHi, R, supply, col_cap, num_supersteps: int,
                          alpha: int = 8, eps0: Optional[int] = None,
                          refine_waves: int = 0):
    """Bounded tiered transport solve, embeddable in jitted programs —
    the preemption-on twin of transport_fori. Dispatches through
    ops.transport_solve_tiered: the fused tiered Pallas kernel on TPU
    (~a handful of us/superstep, VMEM-resident), the XLA phase loop
    elsewhere — bit-identical either way. Single-row instances take
    the exact closed form. Returns (y, pm, steps, converged)."""
    C, Mp1 = wLo.shape
    i32 = jnp.int32
    if C == 1:
        R1 = jnp.minimum(
            R, jnp.minimum(supply[:, None], col_cap[None, :])
        )
        y = solve_single_class_tiered(
            wLo[0], wHi[0], R1[0], supply[0], col_cap
        )
        return y[None, :], jnp.zeros_like(col_cap), i32(0), jnp.bool_(True)

    from ..ops import transport_solve_tiered

    eps_full = jnp.maximum(jnp.max(jnp.abs(wHi)), i32(1))

    def run(eps_init):
        return transport_solve_tiered(
            wLo, wHi, R, supply, col_cap, eps_init,
            alpha=alpha, max_supersteps=num_supersteps,
            refine_waves=refine_waves,
        )

    if eps0 is None:
        return run(eps_full)
    y1, pm1, s1, conv1 = run(i32(eps0))

    def keep(_):
        return y1, pm1, s1, conv1

    def retry(_):
        y2, pm2, s2, conv2 = run(eps_full)
        return y2, pm2, s1 + s2, conv2

    # plain `conv1` on purpose — see the note in transport_fori
    return lax.cond(conv1, keep, retry, operand=None)


def solve_row_constant(v, supply, col_cap):
    """EXACT closed form when every row's cost is machine-uniform:
    w[g, m] = v[g] for all real columns m (the per-job-unsched shape
    with no class cost model — each (job, class) row's shifted cost is
    e - u_job everywhere). The objective sum_g v_g * placed_g is linear
    in per-row placement totals, so the optimum is the fractional-
    knapsack greedy: rows in ascending v (most profitable first), rows
    with v >= 0 place nothing (ties at 0 left unscheduled, matching
    solve_single_class), machine split arbitrary — assigned in
    (row-order, machine-order) interval overlaps, mirroring
    split_grants_by_class. Generalizes the class-degenerate collapse
    (all rows equal) to rows equal only WITHIN themselves; the
    iterative solve herds pathologically on such instances (a trivially
    easy 12.5k-machine per-job instance blew a 20k-superstep budget —
    docs/NOTES.md).

    v int32[G]; supply int32[G]; col_cap int32[Mp1] (last = escape).
    Returns y int32[G, Mp1] with the escape column filled.
    """
    i32 = jnp.int32
    cap_real = col_cap[:-1]
    cap_total = jnp.sum(cap_real)
    order = jnp.argsort(v)
    v_s = v[order]
    sup_s = supply[order]
    take_s = jnp.where(v_s < 0, sup_s, i32(0))
    excl = jnp.cumsum(take_s) - take_s
    q_s = jnp.clip(cap_total - excl, 0, take_s)  # placed per sorted row
    Q = jnp.cumsum(q_s)
    starts = Q - q_s
    cum_m = jnp.cumsum(cap_real)
    lo = jnp.maximum((cum_m - cap_real)[None, :], starts[:, None])
    hi = jnp.minimum(cum_m[None, :], Q[:, None])
    y_s = jnp.maximum(hi - lo, 0).astype(i32)  # [G, M] sorted rows
    inv = jnp.argsort(order)
    y_real = y_s[inv]
    esc = (supply - jnp.sum(y_real, axis=1)).astype(i32)
    return jnp.concatenate([y_real, esc[:, None]], axis=1)


def solve_row_constant_np(v, supply, col_cap):
    """Host (numpy) twin of solve_row_constant."""
    cap_real = col_cap[:-1].astype(np.int64)  # kschedlint: host-only (host greedy decode)
    cap_total = int(cap_real.sum())
    order = np.argsort(v, kind="stable")
    sup_s = supply[order].astype(np.int64)  # kschedlint: host-only (host greedy decode)
    take_s = np.where(v[order] < 0, sup_s, 0)
    excl = np.cumsum(take_s) - take_s
    q_s = np.clip(cap_total - excl, 0, take_s)
    Q = np.cumsum(q_s)
    starts = Q - q_s
    cum_m = np.cumsum(cap_real)
    lo = np.maximum((cum_m - cap_real)[None, :], starts[:, None])
    hi = np.minimum(cum_m[None, :], Q[:, None])
    y_s = np.maximum(hi - lo, 0)
    y_real = np.empty_like(y_s)
    y_real[order] = y_s
    esc = supply.astype(np.int64) - y_real.sum(axis=1)  # kschedlint: host-only (host greedy decode)
    return np.concatenate([y_real, esc[:, None]], axis=1)


def solve_single_class(w, supply, col_cap):
    """EXACT closed form for the C=1 transportation row (the trivial
    cost model's shape, and the Google-trace / quincy-base shape): sort
    columns by cost and greedily fill strictly-profitable capacity.

    Exchange argument: any optimal solution places exactly
    min(supply, sum of capacity at w<0) units, on the cheapest such
    capacity; ties at w==0 are objective-neutral (left unscheduled).
    One sort + one cumsum — no iterations, no convergence concerns.

    w, col_cap: int32[Mp1]; returns y int32[Mp1].
    """
    i32 = jnp.int32
    take = jnp.where(w < 0, col_cap, i32(0))
    order = jnp.argsort(w)
    take_s = take[order]
    excl = jnp.cumsum(take_s) - take_s
    y_s = jnp.clip(supply - excl, 0, take_s)
    inv = jnp.argsort(order)
    return y_s[inv]


def solve_single_class_np(w: np.ndarray, supply: int, col_cap: np.ndarray) -> np.ndarray:
    """Host (numpy) twin of solve_single_class."""
    take = np.where(w < 0, col_cap, 0).astype(np.int64)  # kschedlint: host-only (host closed-form decode)
    order = np.argsort(w, kind="stable")
    take_s = take[order]
    excl = np.cumsum(take_s) - take_s
    y_s = np.clip(supply - excl, 0, take_s)
    y = np.empty_like(y_s)
    y[order] = y_s
    return y


def split_grants_by_class(y_tot, supply):
    """Split single-class machine grants y_tot[Mp] among C classes with
    per-class supplies [C] — any split is cost-equal when every class
    has the same cost row (the class-degenerate case), so grant units
    are handed out in (machine-order, class-order): y[c,m] = overlap of
    the class's supply interval with the machine's grant interval.
    Works on numpy or jnp arrays (pure elementwise/broadcast math)."""
    xp = np if isinstance(y_tot, np.ndarray) else jnp
    cum_s = xp.cumsum(supply)
    excl_s = (cum_s - supply)[:, None]  # [C, 1] class interval starts
    cum_m = xp.cumsum(y_tot)[None, :]  # [1, Mp] machine interval ends
    lo = xp.maximum(cum_m - y_tot[None, :], excl_s)
    hi = xp.minimum(cum_m, excl_s + supply[:, None])
    return xp.maximum(hi - lo, 0).astype(y_tot.dtype)


def like_columns(cost):
    """The columns of `cost` [G, n] that are alike in every row: (a
    first column of every distinct one, which distinct one every column
    is). A column's rows are packed into one integer where they fit 62
    bits (one sort of integers), compared row-wise otherwise."""
    G = cost.shape[0]
    lo = int(cost.min())
    span = int(cost.max()) - lo + 1
    if span ** G < 1 << 62:
        key = ((cost - lo) * (span ** np.arange(G, dtype=np.int64))[:, None]).sum(axis=0)  # kschedlint: host-only (host cost prep)
        _, first, which = np.unique(key, return_index=True, return_inverse=True)
    else:
        _, first, which = np.unique(cost.T, axis=0, return_index=True, return_inverse=True)
    return first, which.reshape(-1)


def merge_like_columns(cost, col_cap):
    """The transport problem with the columns that cost every row alike
    merged into one column of their summed capacity, in the same [G, M]
    shape: the merged columns first, the rest dead (no capacity, cost
    0, as the padding is). Interchangeable columns are what the
    synchronous push-relabel herds on: 74 units over 1,350 empty
    machines that all cost alike took 30,000 supersteps, and take 28 as
    one column. Returns None where no two columns with capacity are
    alike (the problem is solved as it is), else (cost [G, M], capacity
    [M], the member columns in merged order, where each merged column's
    members start)."""
    has = np.nonzero(col_cap > 0)[0]
    if len(has) < 2:
        return None
    first, which = like_columns(cost[:, has])
    D = len(first)
    if D == len(has):
        return None
    merged_cost = np.zeros_like(cost)
    merged_cost[:, :D] = cost[:, has[first]]
    merged_cap = np.zeros_like(col_cap)
    merged_cap[:D] = np.bincount(which, weights=col_cap[has], minlength=D)
    members = has[np.argsort(which, kind="stable")]
    starts = np.concatenate([[0], np.cumsum(np.bincount(which, minlength=D))])
    return merged_cost, merged_cap, members, starts


def split_merged_grants(y_merged, members, starts, col_cap):
    """The grants of the merged problem handed to the member columns.
    Any split is optimal (the members cost every row alike); this one
    spreads a merged column's grant over its members in proportion to
    their capacities, so that of the round's optimal placements the
    one that co-locates least is taken (a cost model that prices a
    machine by who runs there has said that it prefers it; packing the
    members one after another left a fill with a pool of empty
    machines and the rest full). Each row then takes its units off the
    members' shares laid end to end. Returns y [G, M]."""
    y = np.zeros((y_merged.shape[0], len(col_cap)), np.int64)  # kschedlint: host-only (LayeredResult contract is int64)
    D = len(starts) - 1
    for k in np.nonzero(y_merged[:, :D].sum(axis=0) > 0)[0].tolist():
        cols = members[starts[k]:starts[k + 1]]
        cap = col_cap[cols]
        granted = int(y_merged[:, k].sum())
        share = granted * cap // int(cap.sum())
        share[: granted - int(share.sum())] += 1  # the remainder: fewer units than members
        share_end = np.cumsum(share)
        row_end = np.cumsum(y_merged[:, k])
        lo = np.maximum((row_end - y_merged[:, k])[:, None], (share_end - share)[None, :])
        hi = np.minimum(row_end[:, None], share_end[None, :])
        y[:, cols] = np.maximum(hi - lo, 0)
    return y


def _transport_loop(wS, U, supply, col_cap, eps_init, alpha, max_supersteps,
                    pm_init=None, refine_waves: int = 0,
                    telemetry_cap: int = 0):
    """The cost-scaling phase schedule as a bounded lax.while_loop:
    each iteration either runs a superstep (while active nodes exist)
    or advances the eps phase; exits as soon as the eps=1 phase drains
    (early exit matters — a converged multi-class solve typically takes
    tens of supersteps against a bound of thousands). Legal inside jit
    and inside lax.scan bodies. pm_init optionally warm-starts the
    machine prices (see transport_tighten). Returns
    (y, z, pm, steps, converged) — pm is the final machine-price vector,
    for carrying into the next round. telemetry_cap > 0 appends the
    superstep-indexed soltel ring (obs/soltel.py) to the returned
    tuple; cap=0 traces the exact pre-telemetry jaxpr."""
    from ..obs.soltel import SOLTEL_WIDTH

    i32 = jnp.int32

    def phase_cond(state):
        steps, done = state[6], state[7]
        return ~done & (steps < max_supersteps)

    if telemetry_cap:
        from ..obs import soltel as _soltel

        _tel_rows_iota = _soltel.device_rows_iota(telemetry_cap)

    def tel_row(eps, e_row, e_col, e_sink, stats):
        active = (
            jnp.sum((e_row > 0).astype(i32))
            + jnp.sum((e_col > 0).astype(i32))
            + (e_sink > 0).astype(i32)
        )
        exc_pos = (
            jnp.sum(jnp.maximum(e_row, 0))
            + jnp.sum(jnp.maximum(e_col, 0))
            + jnp.maximum(e_sink, 0)
        )
        return _soltel.device_row(eps, active, exc_pos, *stats)

    def tel_write(tel, steps, row):
        return _soltel.device_ring_write(
            tel, steps, row, telemetry_cap, _tel_rows_iota
        )

    def phase_body(state):
        if telemetry_cap:
            y, z, pr, pm, psink, eps, steps, done, tel = state
        else:
            y, z, pr, pm, psink, eps, steps, done = state
        e_row, e_col, e_sink = _excesses(supply, y, z)
        any_active = jnp.any(e_row > 0) | jnp.any(e_col > 0) | (e_sink > 0)

        def do_step(_):
            out = transport_superstep(
                wS, U, supply, col_cap, y, z, pr, pm, psink, eps,
                with_stats=bool(telemetry_cap),
            )
            if not telemetry_cap:
                y2, z2, pr2, pm2, psink2 = out
                return y2, z2, pr2, pm2, psink2, eps, steps + 1, jnp.bool_(False)
            y2, z2, pr2, pm2, psink2, stats = out
            tel2 = tel_write(
                tel, steps, tel_row(eps, e_row, e_col, e_sink, stats)
            )
            return (
                y2, z2, pr2, pm2, psink2, eps, steps + 1, jnp.bool_(False),
                tel2,
            )

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            if refine_waves:
                # price refinement: tighten potentials for the CURRENT
                # converged flow at the next eps, then reset only the
                # arcs still violating it — instead of re-flooding
                # every negative arc and re-fighting each contended
                # column from scratch every phase.
                pr2, pm2, psink2 = _price_refine(
                    wS, U, col_cap, y, z, pr, pm, psink, new_eps,
                    refine_waves,
                )
                y2, z2 = transport_saturate_eps(
                    wS, U, col_cap, y, z, pr2, pm2, psink2, new_eps
                )
            else:
                pr2, pm2, psink2 = pr, pm, psink
                y2, z2 = transport_saturate(
                    wS, U, col_cap, y, z, pr, pm, psink
                )
            out = (
                jnp.where(finished, y, y2),
                jnp.where(finished, z, z2),
                jnp.where(finished, pr, pr2),
                jnp.where(finished, pm, pm2),
                jnp.where(finished, psink, psink2),
                jnp.where(finished, eps, new_eps),
                steps,
                finished,
            )
            return out + ((tel,) if telemetry_cap else ())

        return lax.cond(any_active, do_step, next_phase, operand=None)

    C, Mp1 = wS.shape
    pr0, pm0, psink0 = transport_tighten(wS, U, col_cap, pm_init)
    y0 = jnp.zeros((C, Mp1), i32)
    z0 = jnp.zeros((Mp1,), i32)
    state = (y0, z0, pr0, pm0, psink0, eps_init, i32(0), jnp.bool_(False))
    if telemetry_cap:
        state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
        y, z, pr, pm, psink, eps, steps, done, tel = lax.while_loop(
            phase_cond, phase_body, state
        )
    else:
        y, z, pr, pm, psink, eps, steps, done = lax.while_loop(
            phase_cond, phase_body, state
        )
    e_row, e_col, e_sink = _excesses(supply, y, z)
    max_abs = jnp.maximum(
        jnp.max(jnp.abs(e_row)), jnp.maximum(jnp.max(jnp.abs(e_col)), jnp.abs(e_sink))
    )
    base = (y, z, pm, steps, done & (max_abs == 0))
    if telemetry_cap:
        return base + (tel,)
    return base


def transport_fori(wS, supply, col_cap, num_supersteps: int, alpha: int = 8,
                   eps0: Optional[int] = None, class_degenerate: bool = False,
                   pm0=None, eps0_budget: Optional[int] = None,
                   refine_waves: int = 0, eps0_retry: bool = True):
    """Bounded transport solve, embeddable in larger jitted programs.

    C == 1: the exact closed form (solve_single_class) — O(sort(M)).
    C >= 2: the cost-scaling phase schedule, exiting as soon as it
    converges, bounded by num_supersteps — as the fused Pallas kernel
    (ops/transport_pallas.py, one kernel launch with all state in VMEM)
    when the ambient backend is TPU, else the XLA `_transport_loop`.

    eps0: optional static eps-schedule start. Passing the problem's
    n_scale (one original cost unit) cuts supersteps ~20x on contended
    instances — valid for any start since tightened potentials make the
    zero flow 0-optimal; if the short schedule stalls within the budget,
    an in-graph lax.cond falls back to the full range, so convergence
    never regresses.

    class_degenerate: static flag asserting every class has the SAME
    cost row (e.g. no class cost model wired in). Classes are then
    interchangeable and the iterative multi-class solve — which herds
    badly on identical costs (all classes chase the same columns in
    lockstep) — collapses to the exact C=1 closed form plus an
    arbitrary-but-feasible split of grants among classes.

    pm0: optional carried machine prices [Mp1] (previous round's pm)
    warm-starting the solve; any value is valid, a near-optimal one
    makes the discharge a handful of supersteps.

    Returns (y, pm, steps, converged) — pm is the final machine-price
    vector and steps the executed superstep count (both zero on the
    closed-form paths, where no iterations run).
    """
    C, Mp1 = wS.shape
    i32 = jnp.int32
    if C == 1:
        y = solve_single_class(wS[0], supply[0], col_cap)[None, :]
        return y, jnp.zeros_like(col_cap), i32(0), jnp.bool_(True)
    if class_degenerate:
        y_tot = solve_single_class(wS[0], jnp.sum(supply), col_cap)
        return (
            split_grants_by_class(y_tot, supply),
            jnp.zeros_like(col_cap),
            i32(0),
            jnp.bool_(True),
        )

    eps_full = jnp.maximum(jnp.max(jnp.abs(wS)), i32(1))
    from ..ops import transport_solve

    if eps0 is None:
        return transport_solve(
            wS, supply, col_cap, eps_full, pm0,
            alpha=alpha, max_supersteps=num_supersteps,
            refine_waves=refine_waves,
        )

    # eps0_budget bounds ONLY the short first attempt: when the short
    # schedule is instance-dependent (great on some shapes, a stall on
    # others), a small budget caps the damage before the full-range
    # retry — instead of burning the whole num_supersteps first.
    y1, pm1, s1, conv1 = transport_solve(
        wS, supply, col_cap, i32(eps0), pm0,
        alpha=alpha,
        max_supersteps=min(eps0_budget or num_supersteps, num_supersteps),
        refine_waves=refine_waves,
    )
    if not eps0_retry:
        # caller owns the fallback: return the bounded attempt as-is
        # (conv flag honest) — used by the grouped two-stage solve,
        # whose stall recovery is a DIFFERENT instance (the original
        # cost matrix), not a full-range retry on this one
        return y1, pm1, s1, conv1

    def keep(_):
        return y1, pm1, s1, conv1

    def retry(_):
        # Cold restart: full eps range, no carried prices.
        y2, pm2, s2, conv2 = transport_solve(
            wS, supply, col_cap, eps_full, None,
            alpha=alpha, max_supersteps=num_supersteps,
            refine_waves=refine_waves,
        )
        return y2, pm2, s1 + s2, conv2

    # NOTE: the retry predicate stays plain `conv1`. Gating it with
    # `conv1 | (i32(eps0) >= eps_full)` (to skip an identical retry
    # when choose_eps0 already picked the full range) crashed the TPU
    # worker under an earlier libtpu whenever it ran inside a scanned
    # round, and has not been re-tried on libtpu 0.0.34. The
    # duplicated full-range retry only fires on a non-converged
    # oversubscribed solve, a rare path worth the waste.
    return lax.cond(conv1, keep, retry, operand=None)


@functools.partial(
    jax.jit, static_argnames=("alpha", "max_supersteps", "refine_waves", "telemetry_cap")  # kschedlint: program=layered_solve
)
def _solve_transport(
    wS,  # int32[C, Mp1] scaled costs (column Mp1-1 = unsched, 0)
    supply,  # int32[C]
    col_cap,  # int32[Mp1]
    eps_init,  # int32 scalar
    pm0=None,  # optional int32[Mp1] carried machine prices
    alpha: int = 8,
    max_supersteps: int = 20_000,
    refine_waves: int = 0,
    telemetry_cap: int = 0,
):
    U = jnp.minimum(supply[:, None], col_cap[None, :])  # fwd arc capacity
    out = _transport_loop(
        wS, U, supply, col_cap, eps_init, alpha, max_supersteps, pm_init=pm0,
        refine_waves=refine_waves, telemetry_cap=telemetry_cap,
    )
    y, z, pm, steps, converged = out[:5]
    if telemetry_cap:
        return y, pm, steps, converged, out[5]
    return y, pm, steps, converged


def solve_layered_host(lp: LayeredProblem, *, pad, solve,
                       max_supersteps: int) -> LayeredResult:
    """The shared host harness around a device transport solve: cost
    shift (subtract the unsched cost so the escape column is 0), padded
    geometry, int32 overflow guard, closed-form dispatch for C==1 and
    class-degenerate instances, like columns merged into one for the
    iterative solve, the short-then-full eps attempts loop,
    and objective reconstruction. One definition so the single-device
    and mesh-sharded solvers cannot drift.

    pad(M, C) -> (Mp, n_scale); solve(wS, supply, col_cap, eps_init)
    -> (y, steps, converged) on device arrays."""
    C, M = lp.cost_cm.shape
    supply = lp.supply.astype(np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    total = int(supply.sum())
    if total == 0:
        return LayeredResult(
            y=np.zeros((C, M), np.int64), num_unsched=0, objective=0, supersteps=0  # kschedlint: host-only (LayeredResult contract is int64)
        )
    # Shifted per-unit cost: placing costs (e + cost[c,m]), leaving
    # unscheduled costs u (per row when row_unsched_cost is set);
    # subtract u so the unsched column is 0 for every row.
    if lp.row_unsched_cost is not None:
        u_row = np.asarray(lp.row_unsched_cost, np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
        assert u_row.shape == (C,), f"row_unsched_cost must be [{C}]"
    else:
        u_row = np.full(C, int(lp.unsched_cost), np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    w = lp.cost_cm.astype(np.int64) + int(lp.ec_cost) - u_row[:, None]  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    Mp, n_scale = pad(M, C)
    wP = np.zeros((C, Mp), np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    wP[:, :M] = w
    col_cap = np.zeros(Mp, np.int64)  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
    col_cap[:M] = lp.col_cap
    col_cap[-1] = total

    max_w = int(np.abs(wP).max())
    if max_w * n_scale >= COST_SCALE_LIMIT:
        raise OverflowError(
            f"scaled layered costs overflow int32: max|w|={max_w} * {n_scale}"
        )

    if C == 1:
        y_np = solve_single_class_np(wP[0], total, col_cap)[None, :]
        steps_taken = 0
    elif (wP == wP[0]).all():
        # Class-degenerate (all cost rows equal): exact closed form on
        # the total supply, grants split arbitrarily by class — the
        # iterative solve herds pathologically on identical costs.
        y_tot = solve_single_class_np(wP[0], total, col_cap)
        y_np = split_grants_by_class(y_tot, supply)
        steps_taken = 0
    elif (w == w[:, :1]).all():
        # Row-constant (each row machine-uniform, rows differ — the
        # per-job-unsched shape with no class cost model): the
        # fractional-knapsack closed form.
        y_np = solve_row_constant_np(
            w[:, 0].astype(np.int32), supply.astype(np.int32),
            col_cap.astype(np.int32),
        )
        steps_taken = 0
    else:
        # Columns that cost every row alike: one column of their summed
        # capacity (the iterative solve herds on interchangeable
        # columns as it does on interchangeable rows); grants split back
        # over the members below.
        merged = merge_like_columns(w, lp.col_cap.astype(np.int64))  # kschedlint: host-only (host cost prep; overflow-guarded before the i32 cast)
        if merged is not None:
            wP[:, :M], col_cap[:M] = merged[0], merged[1]
            # the exactness bound counts the live nodes, and the merged
            # problem has as many live columns as distinct ones: a smaller
            # multiplier, fewer eps phases
            n_scale = pad(len(merged[3]) - 1, C)[1]
        wS = jnp.asarray((wP * n_scale).astype(np.int32))
        sup = jnp.asarray(supply.astype(np.int32))
        cap = jnp.asarray(col_cap.astype(np.int32))
        eps_full = int(max(1, max_w * n_scale))
        eps0 = int(
            choose_eps0(n_scale, eps_full, total, int(lp.col_cap.sum()))
        )
        attempts = [np.int32(eps0)]
        if eps0 != eps_full:
            attempts.append(np.int32(eps_full))
        y = None
        converged = False
        # supersteps accumulate ACROSS attempts (matching the in-graph
        # retry in transport_fori, which reports s1 + s2)
        steps_taken = 0
        for eps_init in attempts:
            y, steps, converged = solve(wS, sup, cap, jnp.asarray(eps_init))
            steps_taken += int(steps)
            if bool(converged):
                break
        if not bool(converged):
            raise RuntimeError(
                f"layered transport solve did not converge in "
                f"{max_supersteps} supersteps"
            )
        y_np = np.asarray(y).astype(np.int64)  # kschedlint: host-only (host decode of device results)
        if merged is not None:
            y_np = split_merged_grants(
                y_np, merged[2], merged[3], lp.col_cap.astype(np.int64)  # kschedlint: host-only (host decode of device results)
            )
    y_real = y_np[:, :M]
    placed = int(y_real.sum())
    unplaced_row = supply - y_real.sum(axis=1)
    objective = int((u_row * unplaced_row).sum()) + int(
        ((lp.cost_cm.astype(np.int64) + int(lp.ec_cost)) * y_real).sum()  # kschedlint: host-only (int64 objective math on host)
    )
    return LayeredResult(
        y=y_real,
        num_unsched=total - placed,
        objective=objective,
        supersteps=steps_taken,
    )


class LayeredTransportSolver:
    """The bulk scheduler's production TPU backend.

    Not a generic FlowSolver: it understands only the aggregate layered
    topology (which is the one BulkCluster builds) and is dispatched via
    ``solve_layered`` — BulkCluster picks this fast path whenever its
    backend provides the method, and otherwise falls back to the generic
    FlowProblem seam (the same graceful dispatch the reference has
    between full and incremental solver modes, placement/solver.go:60-90).
    """

    def __init__(self, alpha: int = 8, max_supersteps: int = 20_000,
                 telemetry: Optional[int] = None):
        self.alpha = validate_alpha(alpha)
        self.max_supersteps = max_supersteps
        #: soltel ring capacity override; None = module default (see
        #: obs/soltel.resolve_cap). The fused Pallas transport kernel
        #: carries no telemetry ring, so telemetry is only collected
        #: where the XLA `_solve_transport` loop runs ANYWAY (CPU, or
        #: a forced non-Pallas mode) — it must never silently swap the
        #: TPU hot path off the fused kernel. Flows are bit-identical
        #: either way by the kernel's parity contract.
        self.telemetry = telemetry
        self.last_supersteps = 0
        self.last_telemetry = None

    def reset(self) -> None:
        pass

    def solve_layered(self, lp: LayeredProblem) -> LayeredResult:
        from ..obs import soltel
        from ..ops import resolve_pallas, transport_solve

        tel_cap = soltel.resolve_cap(self.telemetry)
        if tel_cap and resolve_pallas()[0]:
            # Pallas dispatch is live (TPU or forced on): keep the
            # fused kernel and skip interior telemetry rather than
            # silently demoting the hot path to the XLA loop. The
            # superstep COUNT still reaches the registry via
            # solve_traced/solve_layered consumers.
            tel_cap = 0
        captured = []  # (tel_buf, steps, converged) of the last attempt

        def solve(wS, sup, cap, eps_init):
            if tel_cap:
                y, _pm, steps, converged, tel = _solve_transport(
                    wS, sup, cap, eps_init,
                    alpha=self.alpha, max_supersteps=self.max_supersteps,
                    telemetry_cap=tel_cap,
                )
                captured.append((tel, steps, converged))
            else:
                y, _pm, steps, converged = transport_solve(
                    wS, sup, cap, eps_init,
                    alpha=self.alpha, max_supersteps=self.max_supersteps,
                )
            return y, steps, converged

        def decode_last(converged_override=None):
            if not captured:
                return None
            tel, steps, converged = captured[-1]
            return soltel.decode(
                tel, int(steps), tel_cap, "layered", self.max_supersteps,
                converged=(
                    bool(converged)
                    if converged_override is None
                    else converged_override
                ),
                nodes=int(lp.supply.shape[0]),
                arcs=int(lp.cost_cm.size),
            )

        self.last_telemetry = None
        try:
            res = solve_layered_host(
                lp, pad=pad_geometry, solve=solve,
                max_supersteps=self.max_supersteps,
            )
        except RuntimeError as e:
            self.last_supersteps = self.max_supersteps  # budget exhausted
            tel = decode_last(converged_override=False)
            self.last_telemetry = tel
            if tel is not None and not isinstance(e, soltel.SolverStallError):
                raise soltel.SolverStallError(
                    str(e),
                    reason=soltel.detect_stall(tel),
                    telemetry=tel,
                ) from e
            raise
        self.last_supersteps = res.supersteps
        self.last_telemetry = decode_last()
        return res


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(__name__, "layered_solve")
