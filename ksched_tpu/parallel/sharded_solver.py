"""Multi-chip MCMF: the push-relabel solve sharded over a device mesh.

The reference scales by incremental re-solves in one external process
(SURVEY §2.5); the TPU rebuild scales across chips: residual entries are
partitioned by the OWNER of their source node (so every node's outgoing
entries — the unit of push/relabel work — live on exactly one shard),
while flow and potentials are replicated and combined with
`jax.lax.psum` over the mesh axis each superstep. ICI traffic per
superstep is one [N] node-vector and one [M] arc-vector reduction.

Design invariants (mirroring solver/jax_solver.py, which documents the
algorithm):
- no scatters: per-shard segment reductions use the same CSR-sorted
  cumsum/gather + associative-scan machinery; cross-shard combination is
  psum of owner-masked dense vectors (each node/arc has exactly one
  contributing shard, so psum implements "select the owner's value");
- pushes and relabels for a node are computed entirely on its owner
  shard from replicated state, so the single-chip eps-optimality
  argument carries over unchanged;
- price tightening (Bellman-Ford sweeps) distributes the same way: the
  per-node min over outgoing entries is owner-local, then psum-combined.

Built for `jax.sharding.Mesh` + `shard_map`; exercised on a virtual
8-device CPU mesh in tests and by __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..graph.device_export import FlowProblem
from ..solver.base import FlowResult, FlowSolver
from ..solver.layered import validate_alpha

_BIG = jnp.int32(1 << 30)
_BIG_D = 1 << 28


@dataclass
class ShardedPlan:
    """Host-prebuilt per-shard CSR data, stacked on a leading shard axis."""

    # [D, E] per-shard sorted entries (E = padded per-shard entry count)
    s_arc: np.ndarray
    s_sign: np.ndarray
    s_src: np.ndarray
    s_dst: np.ndarray
    s_segstart: np.ndarray  # local sorted index of entry's segment start
    s_isstart: np.ndarray
    s_valid: np.ndarray  # bool, padding mask
    # [D, N] per-node segment boundaries within the shard's local entries
    node_first: np.ndarray
    node_last: np.ndarray
    node_nonempty: np.ndarray
    owned: np.ndarray  # bool [D, N]: shard owns this node
    # [D, M] position of arc j's fwd/bwd entry in this shard (E = zero pad)
    pos_fwd: np.ndarray
    pos_bwd: np.ndarray
    src: np.ndarray  # [M] the endpoints this plan was built for
    dst: np.ndarray


def node_owner(node_ids: np.ndarray, num_nodes: int, num_shards: int) -> np.ndarray:
    """Owner shard per node: contiguous range partition, so resource
    subtrees laid out contiguously stay on one shard. Delegates to
    graph/slot_plan.shard_owner — the slot-stable sharded layout and
    this legacy plan builder must agree on ownership, or a maintained
    layout and a from-scratch build would route the same node's
    entries to different chips."""
    from ..graph.slot_plan import shard_owner

    return shard_owner(node_ids, num_nodes, num_shards)


def build_sharded_plan(src: np.ndarray, dst: np.ndarray, num_nodes: int, num_shards: int) -> ShardedPlan:
    m = len(src)
    esrc = np.concatenate([src, dst])
    edst = np.concatenate([dst, src])
    earc = np.concatenate([np.arange(m), np.arange(m)]).astype(np.int32)
    esign = np.concatenate([np.ones(m), -np.ones(m)]).astype(np.int32)
    owner = node_owner(esrc, num_nodes, num_shards)

    per_shard = [np.nonzero(owner == d)[0] for d in range(num_shards)]
    e_max = max((len(ix) for ix in per_shard), default=1)
    # One spare slot past the densest shard: pos_fwd/pos_bwd default
    # there, and it is invalid on every shard, so padded gathers read 0.
    e_pad = e_max + 1

    def stack(fill, dtype):
        return np.full((num_shards, e_pad), fill, dtype=dtype)

    s_arc = stack(0, np.int32)
    s_sign = stack(1, np.int32)
    s_src = stack(0, np.int32)
    s_dst = stack(0, np.int32)
    s_segstart = stack(0, np.int32)
    s_isstart = np.zeros((num_shards, e_pad), bool)
    s_valid = np.zeros((num_shards, e_pad), bool)
    node_first = np.zeros((num_shards, num_nodes), np.int32)
    node_last = np.zeros((num_shards, num_nodes), np.int32)
    node_nonempty = np.zeros((num_shards, num_nodes), bool)
    owned = np.zeros((num_shards, num_nodes), bool)
    pos_fwd = np.full((num_shards, m), e_pad - 1, np.int32)
    pos_bwd = np.full((num_shards, m), e_pad - 1, np.int32)

    node_ids = np.arange(num_nodes)
    node_owner_arr = node_owner(node_ids, num_nodes, num_shards)
    for d in range(num_shards):
        ix = per_shard[d]
        k = len(ix)
        order = np.argsort(esrc[ix], kind="stable")
        lsrc = esrc[ix][order]
        s_src[d, :k] = lsrc
        s_dst[d, :k] = edst[ix][order]
        s_arc[d, :k] = earc[ix][order]
        s_sign[d, :k] = esign[ix][order]
        s_valid[d, :k] = True
        counts = np.bincount(lsrc, minlength=num_nodes)
        row_ptr = np.zeros(num_nodes + 1, np.int64)  # kschedlint: host-only (numpy plan build)
        row_ptr[1:] = np.cumsum(counts)
        s_segstart[d, :k] = row_ptr[lsrc]
        starts = np.unique(row_ptr[lsrc]).astype(np.int64)  # kschedlint: host-only (numpy plan build)
        s_isstart[d, starts] = True
        node_first[d] = np.minimum(row_ptr[:-1], max(e_pad - 1, 0))
        node_last[d] = np.maximum(row_ptr[1:] - 1, 0)
        node_nonempty[d] = row_ptr[1:] > row_ptr[:-1]
        owned[d] = node_owner_arr == d
        # Map arc -> local entry position (padding position reads delta 0
        # because padded entries are never admissible).
        local_pos = np.empty(k, np.int64)  # kschedlint: host-only (numpy plan build)
        local_pos[:] = np.arange(k)
        glob = ix[order]
        is_fwd = glob < m
        pos_fwd[d, earc[ix][order][is_fwd]] = local_pos[is_fwd]
        pos_bwd[d, earc[ix][order][~is_fwd]] = local_pos[~is_fwd]
    return ShardedPlan(
        s_arc=s_arc,
        s_sign=s_sign,
        s_src=s_src,
        s_dst=s_dst,
        s_segstart=s_segstart,
        s_isstart=s_isstart,
        s_valid=s_valid,
        node_first=node_first,
        node_last=node_last,
        node_nonempty=node_nonempty,
        owned=owned,
        pos_fwd=pos_fwd,
        pos_bwd=pos_bwd,
        src=src.copy(),
        dst=dst.copy(),
    )


def _seg_sum_local(vals, node_first, node_last, node_nonempty):
    """Per-node sum over a sorted-entry array: cumsum + boundary gathers."""
    c = jnp.cumsum(vals)
    excl_first = c[node_first] - vals[node_first]
    seg = c[node_last] - excl_first
    return jnp.where(node_nonempty, seg, 0)


def _seg_scan(vals, isstart, combine_val):
    def combine(a, b):
        f1, v1 = a
        f2, v2 = b
        return f1 | f2, jnp.where(f2, v2, combine_val(v1, v2))

    _, scanned = lax.associative_scan(combine, (isstart, vals))
    return scanned


def make_sharded_solver(mesh: Mesh, axis: str, alpha: int, max_supersteps: int, tighten_sweeps: int = 32, telemetry_cap: int = 0):
    """Build the jitted sharded solve fn over the given mesh axis. The
    per-shard plan arrays arrive as call arguments (sharded on their
    leading axis); nothing is baked into the compiled function besides
    shapes. telemetry_cap > 0 appends the replicated soltel ring
    (obs/soltel.py) to the outputs: per-shard counter contributions are
    psum-combined, so the rows are GLOBAL — identical on every shard —
    and cap=0 traces the exact pre-telemetry program."""
    from ..obs.soltel import SOLTEL_WIDTH
    spec_sharded = P(axis)
    spec_repl = P()

    def solve_shard(
        cap, cost, supply, flow0, eps_init, step_cap,
        s_arc, s_sign, s_src, s_dst, s_segstart, s_isstart, s_valid,
        node_first, node_last, node_nonempty, owned, pos_fwd, pos_bwd,
    ):
        # Inside shard_map: leading shard axis is stripped; arrays are
        # the local shard's slices. cap/cost/supply/flow0 replicated.
        i32 = jnp.int32
        (s_arc, s_sign, s_src, s_dst, s_segstart, s_isstart, s_valid,
         node_first, node_last, node_nonempty, owned, pos_fwd, pos_bwd) = (
            x[0] for x in (s_arc, s_sign, s_src, s_dst, s_segstart, s_isstart, s_valid,
                           node_first, node_last, node_nonempty, owned, pos_fwd, pos_bwd)
        )
        s_cost = s_sign * cost[s_arc]

        def residual(flow):
            a_flow = flow[s_arc]
            r = jnp.where(s_sign > 0, cap[s_arc] - a_flow, a_flow)
            return jnp.where(s_valid, r, i32(0))

        def excess_of(flow):
            contrib = _seg_sum_local(
                jnp.where(s_valid, s_sign * flow[s_arc], i32(0)),
                node_first, node_last, node_nonempty,
            )
            contrib = jnp.where(owned, contrib, i32(0))
            total = lax.psum(contrib, axis)
            return supply - total

        def tighten(flow):
            r = residual(flow)
            excess0 = excess_of(flow)
            d0 = jnp.where(excess0 < 0, i32(0), i32(_BIG_D))

            def t_cond(state):
                _d, changed, it = state
                return changed & (it < tighten_sweeps)

            def t_body(state):
                d, _, it = state
                cand = jnp.where(r > 0, s_cost + 1 + d[s_dst], i32(_BIG_D))
                scanned = _seg_scan(cand, s_isstart, jnp.minimum)
                best = jnp.where(node_nonempty, scanned[node_last], i32(_BIG_D))
                best = jnp.where(owned, best, i32(_BIG_D))
                best = lax.pmin(best, axis)
                # clamp below: transient negative-cost residual cycles
                # must not run d toward int32 wraparound
                d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
                return d2, jnp.any(d2 != d), it + 1

            d, _, _ = lax.while_loop(t_cond, t_body, (d0, jnp.bool_(True), i32(0)))
            return -jnp.minimum(d, i32(_BIG_D))

        # pos_fwd/pos_bwd point either at the arc's real local entry or
        # at the spare padded slot (invalid on every shard), so gathers
        # through them read 0 after the s_valid mask.
        def arc_delta(delta):
            dz = jnp.where(s_valid, delta, i32(0))
            return lax.psum(dz[pos_fwd] - dz[pos_bwd], axis)

        def superstep(flow, p, eps, excess):
            r = residual(flow)
            rc = s_cost + p[s_src] - p[s_dst]
            e_at = excess[s_src]
            admissible = (r > 0) & (rc < 0) & (e_at > 0) & s_valid
            r_adm = jnp.where(admissible, r, i32(0))
            cum = jnp.cumsum(r_adm)
            excl = cum - r_adm
            prefix_before = excl - excl[s_segstart]
            delta = jnp.clip(e_at - prefix_before, 0, r_adm)
            new_flow = flow + arc_delta(delta)

            pushed = _seg_sum_local(delta, node_first, node_last, node_nonempty)
            sum_r = _seg_sum_local(r, node_first, node_last, node_nonempty)
            cand = jnp.where(r > 0, p[s_dst] - s_cost, -_BIG)
            scanned = _seg_scan(cand, s_isstart, jnp.maximum)
            best = jnp.where(node_nonempty, scanned[node_last], -_BIG)
            relabel = (excess > 0) & (pushed == 0) & (sum_r > 0) & owned
            p_local = jnp.where(relabel, best - eps, jnp.where(owned, p, i32(0)))
            new_p = lax.psum(jnp.where(owned, p_local, i32(0)), axis)
            if not telemetry_cap:
                return new_flow, new_p, ()
            # soltel cols 3..6: per-shard contributions psum'd to global
            # counts (each entry/owned node contributes on one shard)
            aux = (
                lax.psum(jnp.sum(jnp.where(s_valid, delta, i32(0))), axis),
                lax.psum(jnp.sum(relabel.astype(i32)), axis),
                lax.psum(
                    jnp.sum(((s_sign > 0) & s_valid & (r == 0)).astype(i32)),
                    axis,
                ),
                lax.psum(jnp.sum(admissible.astype(i32)), axis),
            )
            return new_flow, new_p, aux

        def sat_full(flow, p, eps):
            # rows within [-eps, +eps] stay as they stand: `tighten`
            # leaves the shortest-path tree at -1 (solver/jax_solver.py)
            rc = s_cost + p[s_src] - p[s_dst]
            want = jnp.where((rc < -eps) & s_valid & (s_sign > 0), cap[s_arc], i32(-1))
            want = jnp.where((rc < -eps) & s_valid & (s_sign < 0), i32(0), want)
            # translate per-entry wishes to per-arc flow targets
            wz = jnp.where(s_valid, want, i32(-1))
            tgt_f = wz[pos_fwd]
            tgt_b = wz[pos_bwd]
            tgt = jnp.maximum(lax.pmax(tgt_f, axis), lax.pmax(tgt_b, axis))
            return jnp.where(tgt >= 0, tgt, flow)

        if telemetry_cap:
            from ..obs import soltel as _soltel

            _tel_rows_iota = _soltel.device_rows_iota(telemetry_cap)

        def tel_row(eps, excess, aux):
            # excess is already the psum-combined global [N] vector,
            # identical on every shard — no further combine needed
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
            return ~done & (steps < step_cap)

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
                f2 = jnp.where(finished, flow, sat_full(flow, p, new_eps))
                out = (
                    f2, p, jnp.where(finished, eps, new_eps), steps, finished
                )
                return out + ((tel,) if telemetry_cap else ())

            return lax.cond(any_active, do_superstep, next_phase, operand=None)

        p0 = tighten(flow0)
        flow1 = sat_full(flow0, p0, eps_init)
        state = (flow1, p0, eps_init, i32(0), jnp.bool_(False))
        if telemetry_cap:
            state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
            flow, p, eps, steps, done, tel = lax.while_loop(
                phase_cond, phase_body, state
            )
        else:
            flow, p, eps, steps, done = lax.while_loop(
                phase_cond, phase_body, state
            )
        converged = done & (jnp.max(jnp.abs(excess_of(flow))) == 0)
        p_overflow = jnp.max(jnp.abs(p)) >= (1 << 30)
        base = (flow, steps, converged, p_overflow)
        if telemetry_cap:
            return base + (tel,)
        return base

    in_specs = (
        spec_repl, spec_repl, spec_repl, spec_repl, spec_repl, spec_repl,
        spec_sharded, spec_sharded, spec_sharded, spec_sharded, spec_sharded,
        spec_sharded, spec_sharded, spec_sharded, spec_sharded, spec_sharded,
        spec_sharded, spec_sharded, spec_sharded,
    )
    out_specs = (spec_repl, spec_repl, spec_repl, spec_repl)
    if telemetry_cap:
        out_specs = out_specs + (spec_repl,)
    fn = jax.shard_map(  # kschedlint: program=sharded_solve
        solve_shard, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )
    return jax.jit(fn)  # kschedlint: program=sharded_solve


# ---------------------------------------------------------------------------
# Slot-stable sharded solve: the maintained-layout multi-chip rung (r15)
# ---------------------------------------------------------------------------
#
# The legacy path above rebuilds a ShardedPlan (host argsort) whenever
# endpoints change. The slot-stable path consumes the SAME ten
# maintained plan tensors as the single-chip scan-CSR solver
# (graph/slot_plan.SlotPlanState in sharded layout mode): the
# entry-shaped tensors reshape losslessly to [D, Es] per-shard stacked
# tables (each shard block holds exactly the segments of the nodes it
# owns), liveness rides the sign column (a dead row's residual is
# forced to 0, no mask tensor), and endpoint churn ships as per-shard
# routed records through one donated shard_map scatter — no
# build_sharded_plan host rebuild on the event path.


def sharded_entry_extent(m_pad: int, num_shards: int) -> int:
    """Per-shard entry-block extent of the slot-stable sharded layout
    in the COMMON case: the (2*m_cap)/D floor slot_plan's sharded
    sizing applies (graph/slot_plan.SlotPlanState._rebuild) — a pure
    function of the pow2 arc bucket and the shard count, never the raw
    size, which is what makes the shard-count-bucket jaxpr hash pins
    non-vacuous (tests/test_static_analysis.py)."""
    return max((2 * m_pad) // num_shards, 16)


#: Explicit PartitionSpec rules for the slot-stable sharded solve, the
#: mesh-layout contract of docs/sharding.md (the match_partition_rules
#: pattern of SNIPPETS.md [1]/[3], specialized to the plan pytree):
#: entry-shaped tensors are stacked [D, Es] and partitioned by the
#: source-node OWNER along the mesh axis (contiguous node ranges —
#: graph/slot_plan.shard_owner — so resource subtrees stay
#: shard-local); every node-/arc-space vector (problem arrays, warm
#: state, positions, boundary statics) is replicated and combined with
#: psum/pmin/pmax over ICI each superstep.
SHARDED_PARTITION_RULES = (
    (r"^(p_arc|p_sign|p_src|p_dst|seg_start|is_start)$", "sharded"),
    (r"^(cap|cost|supply|flow0|eps|steps|warm_p)$", "replicated"),
    (r"^(inv_order|node_first|node_last|node_nonempty)$", "replicated"),
)


def match_partition_rules(names, axis: str):
    """PartitionSpec per named tensor from SHARDED_PARTITION_RULES —
    first matching rule wins, unknown names are an error (a new tensor
    must be placed deliberately, not silently replicated)."""
    import re

    from jax.sharding import PartitionSpec as P  # noqa: F811

    specs = []
    for name in names:
        for rule, kind in SHARDED_PARTITION_RULES:
            if re.search(rule, name):
                specs.append(P(axis) if kind == "sharded" else P())
                break
        else:
            raise ValueError(f"no partition rule for tensor {name!r}")
    return tuple(specs)


def place_sharded_plan(mesh: Mesh, axis: str, host_tensors, num_shards: int, block_extent: int) -> Tuple:
    """Device placement of the ten maintained plan tensors
    (SlotPlanState.host_args order) per SHARDED_PARTITION_RULES: the
    six entry-shaped tensors reshape [D, Es] and partition on the mesh
    axis, the rest replicate. The ONE placement implementation — the
    sharded solver's full-upload cache and the resident mirror's
    rebuild/repair path both call it, so the entry-vs-replicated split
    can never drift between them."""
    from jax.sharding import NamedSharding

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    return tuple(
        jax.device_put(
            np.ascontiguousarray(
                np.reshape(x, (num_shards, block_extent))
            ),
            shard,
        )
        if i < 6
        else jax.device_put(np.asarray(x), repl)
        for i, x in enumerate(host_tensors)
    )


#: argument names of the slot-stable sharded solve, in positional
#: order (warm_p appended by the use_warm_p variant)
_SLOT_SOLVE_ARGS = (
    "cap", "cost", "supply", "flow0", "eps", "steps",
    "p_arc", "p_sign", "p_src", "p_dst", "seg_start", "is_start",
    "inv_order", "node_first", "node_last", "node_nonempty",
)


def make_sharded_slot_solver(
    mesh: Mesh,
    axis: str,
    alpha: int,
    max_supersteps: int,
    tighten_sweeps: int = 32,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
):
    """The jitted slot-stable sharded solve over the given mesh axis.

    Same algorithm and superstep structure as the single-chip
    slot-stable `_solve_mcmf` (solver/jax_solver.py) — same residual
    masking through the sign column, same prefix-sum push allocation,
    same tightening sweeps (and, with ``use_warm_p``, the same
    dirty-frontier price REFIT seeded from the carried potentials) —
    so flows, potentials, and superstep counts are bit-identical to
    the single-chip solve of the same problem over the same layout.
    Per-shard contributions combine through exactly three collective
    shapes per superstep: one [N] psum for the excess/potential
    vectors, one [M] psum for the arc deltas, and the pmin/pmax
    segment combines (telemetry adds scalar psums, off by default).

    ICI traffic per superstep is therefore one [N] node-vector and one
    [M] arc-vector reduction (the PR-1 brief's "allreduce node
    potentials over ICI each superstep"), countable from the traced
    program (analysis/jaxpr_contracts.count_collectives)."""
    from ..obs.soltel import SOLTEL_WIDTH
    D = int(mesh.shape[axis])

    def solve_shard(*args):
        if use_warm_p:
            (cap, cost, supply, flow0, eps_init, step_cap,
             p_arc, p_sign, p_src, p_dst, seg_g, isstart,
             inv, node_first_g, node_last_g, node_nonempty, warm_p) = args
        else:
            (cap, cost, supply, flow0, eps_init, step_cap,
             p_arc, p_sign, p_src, p_dst, seg_g, isstart,
             inv, node_first_g, node_last_g, node_nonempty) = args
            warm_p = None
        i32 = jnp.int32
        # entry-shaped operands arrive [1, Es] (their shard slice);
        # strip the leading mesh dim
        s_arc, s_sign, s_src, s_dst, seg_g, isstart = (
            x[0] for x in (p_arc, p_sign, p_src, p_dst, seg_g, isstart)
        )
        Es = s_arc.shape[0]
        n = supply.shape[0]
        m = cap.shape[0]
        me = lax.axis_index(axis)
        base = me * i32(Es)
        # ownership re-derived from iota — the same contiguous-range
        # arithmetic as graph/slot_plan.shard_owner, so the kernel and
        # the host layout can never disagree on who owns a node
        per = -(-n // D)
        owned = jnp.minimum(lax.iota(i32, n) // i32(per), i32(D - 1)) == me
        # boundary statics are GLOBAL positions; translate into the
        # local block (owned nodes' regions live in this block by
        # construction; non-owned rows are masked everywhere they feed)
        node_first = jnp.clip(node_first_g - base, 0, i32(Es - 1))
        node_last = jnp.clip(node_last_g - base, 0, i32(Es - 1))
        nonempty = node_nonempty & owned
        seg_local = jnp.clip(seg_g - base, 0, i32(Es - 1))
        # per-arc entry positions: the fwd/bwd halves of inv_order.
        # A position outside this block (or a freed slot's parked 0)
        # maps to the block's reserved dead local slot 0, whose sign
        # is 0 — it can never carry flow, wants, or deltas.
        pf_g = inv[:m]
        pb_g = inv[m:]
        pf = jnp.where(pf_g // i32(Es) == me, pf_g - base, i32(0))
        pb = jnp.where(pb_g // i32(Es) == me, pb_g - base, i32(0))
        s_cost = s_sign * cost[s_arc]

        def residual(flow):
            a_flow = flow[s_arc]
            return jnp.where(
                s_sign > 0, cap[s_arc] - a_flow,
                jnp.where(s_sign < 0, a_flow, i32(0)),
            )

        def excess_of(flow):
            contrib = _seg_sum_local(
                s_sign * flow[s_arc], node_first, node_last, nonempty
            )
            contrib = jnp.where(owned, contrib, i32(0))
            return supply - lax.psum(contrib, axis)

        def tighten(flow, d0=None):
            r = residual(flow)
            if d0 is None:
                excess0 = excess_of(flow)
                d0 = jnp.where(excess0 < 0, i32(0), i32(_BIG_D))

            def t_cond(state):
                _d, changed, it = state
                return changed & (it < tighten_sweeps)

            def t_body(state):
                d, _, it = state
                cand = jnp.where(r > 0, s_cost + 1 + d[s_dst], i32(_BIG_D))
                scanned = _seg_scan(cand, isstart, jnp.minimum)
                best = jnp.where(nonempty, scanned[node_last], i32(_BIG_D))
                best = jnp.where(owned, best, i32(_BIG_D))
                best = lax.pmin(best, axis)
                d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
                return d2, jnp.any(d2 != d), it + 1

            d, _, _ = lax.while_loop(t_cond, t_body, (d0, jnp.bool_(True), i32(0)))
            return -jnp.minimum(d, i32(_BIG_D))

        def arc_delta(delta):
            return lax.psum(delta[pf] - delta[pb], axis)

        def superstep(flow, p, eps, excess):
            r = residual(flow)
            rc = s_cost + p[s_src] - p[s_dst]
            e_at = excess[s_src]
            admissible = (r > 0) & (rc < 0) & (e_at > 0)
            r_adm = jnp.where(admissible, r, i32(0))
            cum = jnp.cumsum(r_adm)
            excl = cum - r_adm
            prefix_before = excl - excl[seg_local]
            delta = jnp.clip(e_at - prefix_before, 0, r_adm)
            new_flow = flow + arc_delta(delta)

            pushed = _seg_sum_local(delta, node_first, node_last, nonempty)
            sum_r = _seg_sum_local(r, node_first, node_last, nonempty)
            cand = jnp.where(r > 0, p[s_dst] - s_cost, -_BIG)
            scanned = _seg_scan(cand, isstart, jnp.maximum)
            best = jnp.where(nonempty, scanned[node_last], -_BIG)
            relabel = (excess > 0) & (pushed == 0) & (sum_r > 0) & owned
            p_local = jnp.where(relabel, best - eps, jnp.where(owned, p, i32(0)))
            new_p = lax.psum(jnp.where(owned, p_local, i32(0)), axis)
            if not telemetry_cap:
                return new_flow, new_p, ()
            aux = (
                lax.psum(jnp.sum(pushed), axis),
                lax.psum(jnp.sum(relabel.astype(i32)), axis),
                lax.psum(jnp.sum(((s_sign > 0) & (r == 0)).astype(i32)), axis),
                lax.psum(jnp.sum((r_adm > 0).astype(i32)), axis),
            )
            return new_flow, new_p, aux

        def sat_full(flow, p, eps):
            # as the single-chip `saturate`: rows within [-eps, +eps] stay
            rc = s_cost + p[s_src] - p[s_dst]
            want = jnp.where((rc < -eps) & (s_sign > 0), cap[s_arc], i32(-1))
            want = jnp.where((rc < -eps) & (s_sign < 0), i32(0), want)
            tgt = jnp.maximum(
                lax.pmax(want[pf], axis), lax.pmax(want[pb], axis)
            )
            return jnp.where(tgt >= 0, tgt, flow)

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
            return ~done & (steps < step_cap)

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
                f2 = jnp.where(finished, flow, sat_full(flow, p, new_eps))
                out = (
                    f2, p, jnp.where(finished, eps, new_eps), steps, finished
                )
                return out + ((tel,) if telemetry_cap else ())

            return lax.cond(any_active, do_superstep, next_phase, operand=None)

        if use_warm_p:
            # dirty-frontier refit: the Bellman sweeps seeded from the
            # carried prices, exactly the single-chip use_warm_p path
            p0 = tighten(
                flow0, d0=jnp.clip(-warm_p, -i32(_BIG_D), i32(_BIG_D))
            )
        else:
            p0 = tighten(flow0)
        flow1 = sat_full(flow0, p0, eps_init)
        state = (flow1, p0, eps_init, i32(0), jnp.bool_(False))
        if telemetry_cap:
            state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
            flow, p, eps, steps, done, tel = lax.while_loop(
                phase_cond, phase_body, state
            )
        else:
            flow, p, eps, steps, done = lax.while_loop(
                phase_cond, phase_body, state
            )
        converged = done & (jnp.max(jnp.abs(excess_of(flow))) == 0)
        p_overflow = jnp.max(jnp.abs(p)) >= (1 << 30)
        base_out = (flow, p, steps, converged, p_overflow)
        if telemetry_cap:
            return base_out + (tel,)
        return base_out

    names = _SLOT_SOLVE_ARGS + (("warm_p",) if use_warm_p else ())
    in_specs = match_partition_rules(names, axis)
    out_specs = (P(), P(), P(), P(), P())
    if telemetry_cap:
        out_specs = out_specs + (P(),)
    fn = jax.shard_map(  # kschedlint: program=sharded_slot_solve
        solve_shard, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )
    return jax.jit(fn)  # kschedlint: program=sharded_slot_solve


# ---------------------------------------------------------------------------
# HBM fitting gate: when does a graph escalate off the single chip?
# ---------------------------------------------------------------------------

#: int32 entry-space vectors the scan-CSR solve holds live across a
#: superstep: the 6 resident entry tables (arc/sign/src/dst/segstart/
#: isstart) plus ~8 superstep temporaries (a_flow, residual, signed
#: cost, reduced cost, per-entry excess, admissible residual, the
#: prefix cumsum and its exclusive form)
_CSR_LIVE_EVECS = 14
#: [N] node-space vectors live per superstep (supply, excess, p,
#: relabel candidates, boundary statics)
_CSR_LIVE_NVECS = 8
#: [M] arc-space vectors live per solve (cap, cost, flow, flow0,
#: inv_order's two halves)
_CSR_LIVE_MVECS = 6

#: default per-chip working-set budget for ONE solver's buffers. This
#: is deliberately far below a v5e's 16 GB HBM: the budget covers the
#: solver working set only, and the serving stack holds the rest of
#: the chip — double-buffered rounds keep two problem generations
#: live, warm state and telemetry rings persist, and the multi-tenant
#: service packs many cells per chip (docs/sharding.md derives the
#: number). Overridable per AutoSolver.
DEFAULT_HBM_BUDGET_BYTES = 1 << 30


def csr_working_set_bytes(n_cap: int, m_cap: int) -> int:
    """Estimated bytes of the single-chip scan-CSR live set for a
    padded (n_cap, m_cap) bucket — the slot-stable entry extent is
    2*m_cap in the common case (analysis/jaxpr_contracts.
    slot_stable_entry_cap)."""
    e = 2 * m_cap
    return 4 * (
        _CSR_LIVE_EVECS * e + _CSR_LIVE_NVECS * n_cap + _CSR_LIVE_MVECS * m_cap
    )


def scan_csr_fits_hbm(
    n_cap: int, m_cap: int, budget_bytes: int = DEFAULT_HBM_BUDGET_BYTES
) -> bool:
    """Whether one chip's budget holds the scan-CSR working set
    (`csr_working_set_bytes`: 4 bytes times the live entry, node and
    arc vectors). False is what escalates dispatch to the sharded
    rung (solver/graph_collapse.AutoSolver)."""
    return csr_working_set_bytes(n_cap, m_cap) <= budget_bytes


def sharded_shard_bytes(n_cap: int, m_cap: int, num_shards: int) -> int:
    """Estimated per-shard bytes of the slot-stable sharded working
    set: the entry tables shrink to the per-shard block extent, while
    the replicated node/arc vectors (the PartitionSpec rules above)
    are paid in full on every shard."""
    es = sharded_entry_extent(m_cap, num_shards)
    return 4 * (
        _CSR_LIVE_EVECS * es + _CSR_LIVE_NVECS * n_cap + _CSR_LIVE_MVECS * m_cap
    )


def sharded_fits_hbm(
    n_cap: int,
    m_cap: int,
    num_shards: int,
    budget_bytes: int = DEFAULT_HBM_BUDGET_BYTES,
) -> bool:
    """Whether the PER-SHARD working set fits the per-chip budget."""
    return sharded_shard_bytes(n_cap, m_cap, num_shards) <= budget_bytes


# ---------------------------------------------------------------------------
# Sharded plan maintenance programs (the device-resident mirror's
# sharded mode — graph/device_export.DeviceResidentState)
# ---------------------------------------------------------------------------

_SHARDED_PLAN_APPLY: dict = {}


def sharded_plan_apply_fn(mesh: Mesh, axis: str):
    """The per-shard routed plan scatter: the THIRD (and last) scoped
    scatter exemption of the solver stack, the sharded sibling of
    `graph/slot_plan.plan_apply_fn`. A round's dirty plan rows and
    relocated segment statics arrive pre-routed to their owner shards
    (``SlotPlanState.drain_records_sharded`` — positions block-local,
    one shared pow2 record bucket per stream, idempotent dead-slot
    pads), and every shard applies ITS records to ITS block of the
    donated entry tensors — O(records/shard) per shard, zero
    cross-shard traffic (no collectives in the traced program: the
    routing already happened on host). Pinned by the jaxpr contracts:
    non-vacuous (really scatters), 32-bit, pow2-record-bucket
    hash-stable (tests/test_static_analysis.py)."""
    key = (mesh, axis)
    fn = _SHARDED_PLAN_APPLY.get(key)
    if fn is None:
        def body(p_arc, p_sign, p_src, p_dst, seg, isstart, row_rec, seg_rec):
            (p_arc, p_sign, p_src, p_dst, seg, isstart, row_rec, seg_rec) = (
                x[0] for x in (p_arc, p_sign, p_src, p_dst, seg, isstart, row_rec, seg_rec)
            )
            pos = row_rec[:, 0]
            p_arc = p_arc.at[pos].set(row_rec[:, 1])
            p_sign = p_sign.at[pos].set(row_rec[:, 2])
            p_src = p_src.at[pos].set(row_rec[:, 3])
            p_dst = p_dst.at[pos].set(row_rec[:, 4])
            spos = seg_rec[:, 0]
            seg = seg.at[spos].set(seg_rec[:, 1])
            isstart = isstart.at[spos].set(seg_rec[:, 2] != 0)
            return tuple(
                x[None] for x in (p_arc, p_sign, p_src, p_dst, seg, isstart)
            )

        inner = jax.shard_map(  # kschedlint: program=sharded_plan_apply
            body, mesh=mesh,
            in_specs=(P(axis),) * 8, out_specs=(P(axis),) * 6,
        )
        fn = jax.jit(inner, donate_argnums=(0, 1, 2, 3, 4, 5))  # kschedlint: program=sharded_plan_apply
        _SHARDED_PLAN_APPLY[key] = fn
    return fn


_REPL_PLAN_APPLY = None


def replicated_plan_apply_fn():
    """The replicated remainder of a sharded plan sync: inv-order and
    node-boundary records scatter into the REPLICATED plan tensors
    (the partition rules keep them whole on every shard), donated in
    place. Same record scheme as plan_apply_fn's inv/node streams."""
    global _REPL_PLAN_APPLY
    if _REPL_PLAN_APPLY is None:
        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))  # kschedlint: program=replicated_plan_apply
        def _apply(inv, first, last, nonempty, inv_rec, node_rec):
            inv = inv.at[inv_rec[:, 0]].set(inv_rec[:, 1])
            nid = node_rec[:, 0]
            first = first.at[nid].set(node_rec[:, 1])
            last = last.at[nid].set(node_rec[:, 2])
            nonempty = nonempty.at[nid].set(node_rec[:, 3] != 0)
            return inv, first, last, nonempty

        _REPL_PLAN_APPLY = _apply
    return _REPL_PLAN_APPLY


_SHARDED_PLAN_FP: dict = {}


def sharded_plan_fingerprint_fn(mesh: Mesh, axis: str):
    """Per-shard fingerprints psum'd to ONE comparable checksum (the
    PR 14 integrity audit, sharded): each shard computes the weighted
    partial sum of its block with GLOBAL-index weights (global position
    = shard * Es + local iota, the same w[i] = (i*MUL + ADD) | 1 as
    `runtime/integrity.host_fingerprint`), and the psum over the mesh
    axis equals the host twin of the full tensor bit-for-bit — so a
    sharded mirror audits against the SAME host fingerprints as a
    single-chip one, no sharded-specific host math. Returns int32[10]
    in FP_PLAN_ARRAYS order."""
    key = (mesh, axis)
    fn = _SHARDED_PLAN_FP.get(key)
    if fn is None:
        from ..runtime.integrity import _FP_ADD, _FP_MUL, _device_fp1
        i32 = jnp.int32

        def body(p_arc, p_sign, p_src, p_dst, seg, isstart):
            outs = []
            me = lax.axis_index(axis)
            for t in (p_arc, p_sign, p_src, p_dst, seg, isstart):
                v = t[0]
                es = v.shape[0]
                i = lax.iota(i32, es) + me * i32(es)
                w = (i * i32(_FP_MUL) + i32(_FP_ADD)) | i32(1)
                outs.append(lax.psum(jnp.sum(v.astype(i32) * w), axis))
            return jnp.stack(outs)

        entry_fp = jax.shard_map(  # kschedlint: program=sharded_plan_fingerprint
            body, mesh=mesh, in_specs=(P(axis),) * 6, out_specs=P(),
        )

        def _fp(p_arc, p_sign, p_src, p_dst, inv, seg, isstart, first, last, nonempty):
            ent = entry_fp(p_arc, p_sign, p_src, p_dst, seg, isstart)
            rep = [_device_fp1(x) for x in (inv, first, last, nonempty)]
            # FP_PLAN_ARRAYS order: p_arc, p_sign, p_src, p_dst,
            # inv_order, seg_start, is_start, node_first, node_last,
            # node_nonempty
            return jnp.stack([
                ent[0], ent[1], ent[2], ent[3], rep[0],
                ent[4], ent[5], rep[1], rep[2], rep[3],
            ])

        fn = jax.jit(_fp)  # kschedlint: program=sharded_plan_fingerprint
        _SHARDED_PLAN_FP[key] = fn
    return fn


class ShardedJaxSolver(FlowSolver):
    """Push-relabel MCMF sharded over a jax Mesh axis.

    Two dispatch paths, chosen per problem:

    - **slot-stable** (``slot_stable=True`` and the problem carries a
      slot-plan handle — every DeviceGraphState problem): the plan is
      switched into sharded layout mode (graph/slot_plan.
      enable_sharding) and the solve consumes the SAME ten maintained
      tensors as the single-chip scan-CSR rung, entry tables stacked
      [D, Es] by owner shard. Endpoint churn never rebuilds a
      ShardedPlan: the per-round records ride the sharded plan
      scatter (device-resident mirror) or the plan's cached full
      upload. Warm flow and potentials stay device-resident between
      rounds under the SAME journal-scoped policy as JaxSolver
      (carried flow only on endpoint-clean rounds, prices refit via
      the dirty-frontier Bellman seed, budgeted warm attempt escaping
      to the fresh-restart program, cost-scaling as the backstop) —
      so sharded placements stay bit-identical to the single-chip
      arm's.
    - **legacy** (plain array problems — tests, ad-hoc solves): the
      r7 build_sharded_plan argsort path, unchanged.
    """

    def __init__(self, mesh: Mesh, axis: str = "x", alpha: int = 8, max_supersteps: int = 50_000, warm_start: bool = True, telemetry: Optional[int] = None, warm_potentials: bool = True, restart_budget: Optional[int] = 64, slot_stable: bool = True, journal_scoped_warm: bool = True):
        self.mesh = mesh
        self.axis = axis
        self.alpha = validate_alpha(alpha)
        self.max_supersteps = max_supersteps
        self.warm_start = warm_start
        self.telemetry = telemetry
        self.warm_potentials = warm_potentials
        self.restart_budget = restart_budget
        self.slot_stable = slot_stable
        self.journal_scoped_warm = journal_scoped_warm
        self._plan: Optional[ShardedPlan] = None
        self._plan_dev = None
        self._solve_fn = None
        self._solve_fn_cap = 0  # telemetry_cap the cached fn was built for
        self._prev: Optional[np.ndarray] = None
        # ---- slot-stable path state ----------------------------------
        self._slot_fns = {}  # (telemetry_cap, use_warm_p) -> jitted fn
        self._splan_cache = None  # (layout_gen, value_version, tensors)
        self._prev_dev = None  # carried flow, device-resident
        self._prev_p = None  # carried potentials, device-resident
        self._prev_src_dev = None  # endpoint buffers at the last success
        self._prev_dst_dev = None
        self._prev_src_host = None  # endpoints at the last SUCCESSFUL solve
        self._prev_dst_host = None
        self._key_solved = None  # plan_key at the last successful solve
        self.last_supersteps = 0
        self.last_telemetry = None
        self.last_warm_scope = "cold"  # warm | fresh | cold
        self.last_path = "legacy"  # legacy | slot_stable (per solve)

    def reset(self) -> None:
        self._prev = None
        self._prev_dev = None
        self._prev_p = None
        self._prev_src_dev = None
        self._prev_dst_dev = None
        self._prev_src_host = None
        self._prev_dst_host = None
        self._key_solved = None

    @property
    def num_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names if a == self.axis]))

    # -- slot-stable dispatch ----------------------------------------------

    def _slot_fn(self, tel_cap: int, use_warm_p: bool):
        key = (tel_cap, use_warm_p)
        fn = self._slot_fns.get(key)
        if fn is None:
            fn = make_sharded_slot_solver(
                self.mesh, self.axis, self.alpha, self.max_supersteps,
                telemetry_cap=tel_cap, use_warm_p=use_warm_p,
            )
            self._slot_fns[key] = fn
        return fn

    def _sharded_plan_args(self, plan_state) -> Tuple:
        """The maintained plan as sharded device tensors (the
        non-resident full-upload path, cached per (layout_gen,
        value_version) like SlotPlanState.device_args): entry-shaped
        tensors reshaped [D, Es] and placed by the partition rules,
        the rest replicated."""
        key = (plan_state.layout_gen, plan_state.value_version)
        if self._splan_cache is None or self._splan_cache[0] != key:
            self._splan_cache = (
                key,
                place_sharded_plan(
                    self.mesh, self.axis, plan_state.host_args(),
                    self.num_shards, plan_state.block_extent,
                ),
            )
        return self._splan_cache[1]

    def _solve_slot_stable(self, problem: FlowProblem, plan_state) -> FlowResult:
        from ..graph.device_export import resident_solver_inputs
        from ..obs import soltel
        from ..solver.base import check_finite_costs, lower_bound_cost

        n = problem.num_nodes
        m = len(problem.src)
        check_finite_costs(problem)
        max_cost = int(np.abs(problem.cost).max()) if m else 0
        if max_cost * n >= (1 << 30):
            raise OverflowError("scaled costs overflow int32")
        D = self.num_shards
        plan_state.enable_sharding(D)
        plan_state.ensure_built()
        tel_cap = soltel.resolve_cap(self.telemetry)
        self.last_path = "slot_stable"

        # device plan tensors: the sharded device-resident mirror's
        # scatter-maintained buffers when the handle carries them
        # ([D, Es]-shaped), else the plan's cached full upload
        d_plan = getattr(problem, "d_plan", None)
        if d_plan is not None and getattr(d_plan[0], "ndim", 1) == 2:
            plan_dev = d_plan
        else:
            plan_dev = self._sharded_plan_args(plan_state)

        # journal-scoped warm policy — verbatim JaxSolver semantics:
        # carried FLOW only when this round's journal re-wired no
        # endpoints (plan_key match against the last successful solve)
        plan_key = getattr(problem, "plan_key", None)
        keep_flow = True
        if self.journal_scoped_warm and plan_key is not None:
            keep_flow = (
                self._key_solved is not None and plan_key == self._key_solved
            )
        resident = getattr(problem, "d_cap", None) is not None
        if resident:
            dev_args, flow0_dev, warm = resident_solver_inputs(
                problem, self._prev_dev, self._prev_src_dev,
                self._prev_dst_dev, self.warm_start and keep_flow,
            )
        else:
            cap = problem.cap.astype(np.int32)
            supply = problem.excess.astype(np.int32)
            cost = problem.cost.astype(np.int32) * np.int32(n)
            dev_args = (
                jnp.asarray(cap), jnp.asarray(cost), jnp.asarray(supply),
            )
            warm = (
                self.warm_start
                and keep_flow
                and self._prev is not None
                and len(self._prev) == m
                and self._prev_src_host is not None
                and len(self._prev_src_host) == m
            )
            flow0 = np.zeros(m, dtype=np.int32)
            if warm:
                same = (self._prev_src_host == problem.src) & (
                    self._prev_dst_host == problem.dst
                )
                if self.journal_scoped_warm and plan_key is None and not same.all():
                    warm = False
                else:
                    flow0 = np.where(
                        same, np.minimum(self._prev, cap), 0
                    ).astype(np.int32)
            flow0_dev = jnp.asarray(flow0)
        had_state = self._prev is not None or self._prev_dev is not None
        self.last_warm_scope = (
            "warm" if warm else ("fresh" if had_state else "cold")
        )

        warm_p_ok = (
            self.warm_potentials
            and warm
            and self._prev_p is not None
            and self._prev_p.shape[0] == n
        )
        attempt1_budget = min(4096, self.max_supersteps)
        if warm and self.restart_budget is not None:
            attempt1_budget = min(attempt1_budget, self.restart_budget)
        zeros = jnp.zeros(m, jnp.int32)
        # attempt ladder (the JaxSolver.complete ladder, synchronous):
        # warm (budgeted) -> fresh restart (eps=1, zero flow) ->
        # cost scaling from max|cost|*n
        attempts = [(
            flow0_dev, 1, attempt1_budget, warm_p_ok,
        )]
        if warm:
            attempts.append((zeros, 1, min(4096, self.max_supersteps), False))
        attempts.append(
            (zeros, max(1, max_cost * n), self.max_supersteps, False)
        )
        flow = p = steps = tel_buf = None
        converged = p_overflow = False
        spent = 0
        for ai, (f0, eps_init, cap_steps, use_wp) in enumerate(attempts):
            fn = self._slot_fn(tel_cap, use_wp)
            args = dev_args + (
                f0, jnp.asarray(np.int32(eps_init)),
                jnp.asarray(np.int32(cap_steps)),
            ) + tuple(plan_dev)
            if use_wp:
                args = args + (self._prev_p,)
            out = fn(*args)
            if tel_cap:
                flow, p, steps, converged, p_overflow, tel_buf = out
            else:
                flow, p, steps, converged, p_overflow = out
            spent += int(steps)
            ok = bool(converged) and not bool(p_overflow)
            if ai == 0 and warm and not ok and not bool(converged):
                soltel.warm_price_war(
                    "sharded",
                    supersteps=int(steps),
                    budget=attempt1_budget,
                    escaped_to="fresh_restart",
                    tel=(
                        soltel.decode(
                            tel_buf, int(steps), tel_cap, "sharded",
                            attempt1_budget, converged=False,
                            nodes=n, arcs=m,
                        )
                        if tel_buf is not None
                        else None
                    ),
                )
            if ok:
                break
        self.last_supersteps = spent
        # the telemetry budget is the SOLVER's budget, not the warm
        # attempt's internal cap: a budgeted warm attempt that escapes
        # is escalated, not failed, and cap-proximity against the warm
        # cap would be a spurious stall event (JaxSolver.complete's
        # convention; the warm_price_war event above already carries
        # the attempt-local budget)
        self.last_telemetry = (
            soltel.decode(
                tel_buf, int(steps), tel_cap, "sharded", self.max_supersteps,
                converged=bool(converged) and not bool(p_overflow),
                nodes=n, arcs=m,
            )
            if tel_buf is not None
            else None
        )
        if bool(p_overflow) or not bool(converged):
            self.reset()
        if bool(p_overflow):
            raise OverflowError(
                "sharded push-relabel potentials approached int32 range"
            )
        if not bool(converged):
            tel = self.last_telemetry
            raise soltel.SolverStallError(
                f"sharded push-relabel did not converge within "
                f"{self.max_supersteps} supersteps; infeasible?",
                reason=soltel.detect_stall(tel) if tel is not None else None,
                telemetry=tel,
            )
        flow_np = np.asarray(flow)
        if self.warm_start:
            self._prev = flow_np.astype(np.int32)
            self._prev_dev = flow if resident else None
            self._prev_src_dev = problem.d_src if resident else None
            self._prev_dst_dev = problem.d_dst if resident else None
            self._prev_src_host = np.asarray(problem.src, np.int32)
            self._prev_dst_host = np.asarray(problem.dst, np.int32)
            self._key_solved = plan_key
            self._prev_p = p
        objective = int(
            (flow_np.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
        ) + lower_bound_cost(problem)
        return FlowResult(flow=flow_np.astype(np.int64), objective=objective, iterations=spent)  # kschedlint: host-only (FlowResult contract is int64)

    def solve(self, problem: FlowProblem) -> FlowResult:
        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            if (problem.excess > 0).any():
                raise RuntimeError("infeasible flow problem: supply but no arcs")
            self.last_telemetry = None
            return FlowResult(flow=np.zeros(m, dtype=np.int64), objective=0, iterations=0)  # kschedlint: host-only (FlowResult contract is int64)
        plan_state = getattr(problem, "plan", None) if self.slot_stable else None
        if plan_state is not None:
            return self._solve_slot_stable(problem, plan_state)
        return self._solve_legacy(problem)

    def _solve_legacy(self, problem: FlowProblem) -> FlowResult:
        from ..obs import soltel

        self.last_path = "legacy"
        n = problem.num_nodes
        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            if (problem.excess > 0).any():
                raise RuntimeError("infeasible flow problem: supply but no arcs")
            self.last_telemetry = None
            return FlowResult(flow=np.zeros(m, dtype=np.int64), objective=0, iterations=0)  # kschedlint: host-only (FlowResult contract is int64)
        src = problem.src.astype(np.int32)
        dst = problem.dst.astype(np.int32)
        cap = problem.cap.astype(np.int32)
        supply = problem.excess.astype(np.int32)
        max_cost = int(np.abs(problem.cost).max()) if m else 0
        if max_cost * n >= (1 << 30):
            raise OverflowError("scaled costs overflow int32")
        cost = problem.cost.astype(np.int32) * np.int32(n)

        tel_cap = soltel.resolve_cap(self.telemetry)
        prev_plan = self._plan
        plan = prev_plan
        if plan is None or len(plan.src) != m or plan.node_first.shape[1] != n or not (
            np.array_equal(plan.src, src) and np.array_equal(plan.dst, dst)
        ):
            plan = build_sharded_plan(src, dst, n, self.num_shards)
            self._plan = plan
            self._plan_dev = tuple(
                jnp.asarray(x)
                for x in (
                    plan.s_arc, plan.s_sign, plan.s_src, plan.s_dst,
                    plan.s_segstart, plan.s_isstart, plan.s_valid,
                    plan.node_first, plan.node_last, plan.node_nonempty,
                    plan.owned, plan.pos_fwd, plan.pos_bwd,
                )
            )
            self._solve_fn = None
        if self._solve_fn is None or self._solve_fn_cap != tel_cap:
            self._solve_fn = make_sharded_solver(
                self.mesh, self.axis, self.alpha, self.max_supersteps,
                telemetry_cap=tel_cap,
            )
            self._solve_fn_cap = tel_cap

        flow0 = np.zeros(m, dtype=np.int32)
        if (
            self.warm_start
            and self._prev is not None
            and len(self._prev) == m
            and prev_plan is not None
            and len(prev_plan.src) == m
        ):
            # Compare against the endpoints the previous flow was solved
            # for (prev_plan), not the freshly rebuilt plan.
            same = (prev_plan.src == src) & (prev_plan.dst == dst)
            flow0 = np.where(same, np.minimum(self._prev, cap), 0).astype(np.int32)

        attempts = [
            (flow0, 1, min(4096, self.max_supersteps)),
            (np.zeros(m, dtype=np.int32), max(1, max_cost * n), self.max_supersteps),
        ]
        flow = steps = None
        tel_buf = None
        budget = self.max_supersteps
        converged = p_overflow = False
        for f0, eps_init, cap_steps in attempts:
            out = self._solve_fn(
                jnp.asarray(cap), jnp.asarray(cost), jnp.asarray(supply),
                jnp.asarray(f0), jnp.asarray(np.int32(eps_init)),
                jnp.asarray(np.int32(cap_steps)),
                *self._plan_dev,
            )
            if tel_cap:
                flow, steps, converged, p_overflow, tel_buf = out
            else:
                flow, steps, converged, p_overflow = out
            budget = cap_steps
            if bool(converged) and not bool(p_overflow):
                break
        self.last_supersteps = int(steps)
        self.last_telemetry = (
            soltel.decode(
                tel_buf, int(steps), tel_cap, "sharded", budget,
                converged=bool(converged) and not bool(p_overflow),
                nodes=n, arcs=m,
            )
            if tel_buf is not None
            else None
        )
        if bool(p_overflow) or not bool(converged):
            self._prev = None
        if bool(p_overflow):
            raise OverflowError("sharded push-relabel potentials approached int32 range")
        if not bool(converged):
            tel = self.last_telemetry
            raise soltel.SolverStallError(
                "sharded push-relabel did not converge; infeasible?",
                reason=soltel.detect_stall(tel) if tel is not None else None,
                telemetry=tel,
            )
        flow_np = np.asarray(flow)
        if self.warm_start:
            self._prev = flow_np.astype(np.int32)
        objective = int(
            (flow_np.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
            + (problem.flow_offset.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
        )
        return FlowResult(flow=flow_np.astype(np.int64), objective=objective, iterations=int(steps))  # kschedlint: host-only (FlowResult contract is int64)


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(
    __name__,
    "sharded_solve", "sharded_slot_solve", "sharded_slot_solve_warmp",
    "sharded_plan_apply", "replicated_plan_apply", "sharded_plan_fingerprint",
)
