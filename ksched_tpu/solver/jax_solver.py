"""The TPU MCMF backend: cost-scaling push-relabel in JAX.

This is the centerpiece of the rebuild — the replacement for the
reference's external Flowlessly C++ solver (invoked over DIMACS pipes at
scheduling/flow/placement/solver.go:92-123). The flow network arrives as
flat arrays (graph/device_export.py), lives in device memory, and is
solved by a synchronous Goldberg–Tarjan cost-scaling push-relabel:

- arcs are doubled into residual entries (forward + backward);
- each superstep, every active node (excess > 0) pushes along ALL its
  admissible arcs at once via an in-segment prefix-sum allocation
  (maximal push), and active nodes with no admissible arc relabel;
- simultaneous pushes/relabels preserve eps-optimality: a relabel only
  lowers its own potential (reduced costs of in-arcs rise, and out-arc
  bounds were computed against neighbor potentials that only decrease),
  and opposite-direction pushes on one arc are mutually exclusive;
- phases shrink eps by alpha until eps = 1 on costs pre-scaled by the
  node count, at which point the flow is exactly optimal;
- the prologue's prices are 1-optimal with the shortest-path tree
  ADMISSIBLE (PR 54). `tighten` runs its Bellman sweeps on every row's
  cost + 1, so p = -(d + h): the exact distance to a node short of
  flow plus the fewest hops among the shortest paths, one integer
  (costs are multiples of the node count, a path has fewer hops than
  that). Under p and the true costs a tree arc has reduced cost -1 and
  every other residual arc at least -1: a unit moves a hop in every
  superstep from the first, where exact distances (0 on the tree, not
  admissible) cost each node on its path a superstep of lowering its
  price by eps before it pushed, ten supersteps for five hops. The
  optimum is as exact as before (the discharge needs a 1-optimal
  start, no more). `saturate` therefore leaves a row within
  [-eps, +eps] as it stands, at the prologue and at a phase change: a
  test of `rc < 0` would saturate the whole tree.

TPU-shaped implementation notes:

- Scatters only where they are few. A DENSE superstep has none: all
  its segment reductions are expressed over a host-prebuilt CSR
  ordering of the residual entries as cumsum + gather
  (diff-at-row-boundaries) and a segmented max via
  lax.associative_scan. The rule came from a round-5 reading ("TPU
  serializes scatter-adds": a 64k segment_sum ~68 ms, ~1 us an
  update) on a chip and a jax that are gone. Read again on a v5e under
  jax 0.9.0 (2026-10-02, PERF.md section 6, PR 50): `.at[idx].add`
  into s32[524288] or s32[262144] costs ~9 ns an update (4,096
  updates 37 us, 16,384 ~130 us; 64 are lost in the launch), `.set`
  ~5 ns; `unique_indices` / `indices_are_sorted` change nothing, and
  updates sent out of range under `mode="drop"` cost no more than the
  rest. What stays dear is a scatter of as many updates as a table
  has rows (`jnp.nonzero(size=K)`'s bincount: 1.85 ms over 262,144
  nodes, whatever K). So the ACTIVE-SET superstep (below) writes its
  few changed rows back with four scatter-adds, and finds its nodes
  with a `top_k`, not a `nonzero`.
- The superstep has two forms, chosen per superstep inside the loop
  from what the loop state shows (`active_set_fits`). A served round
  re-wires an arc, restarts fresh, and then moves a handful of units
  down the tree: after the first two supersteps one node holds excess
  at a time (the class node, then a machine, its core, its PU), and
  the dense form sweeps every plan row for it: 524,288 rows ten times
  to move 28 pods. `active_superstep` computes the same integers over
  the compacted rows of the nodes that hold excess (the regions of at
  most `_ACTIVE_NODES` nodes, within `1 / _ACTIVE_ROWS_SHARE` of the
  plan), so its cost follows the work and not the plan. A superstep
  outside the caps (a fill, the two bulk supersteps of a trickle round
  on a large cluster) runs the dense form, op for op what it was.
  `JaxSolver` asks for both forms (`active_set_caps`); the stacked
  lanes (`stacked_solve_fn`: under vmap a `cond` is a select and both
  would run), the sharded program and every direct caller keep the one.
- Gathers are the price (78% of the device time before PR 29), so the
  loop does as few as the algorithm needs and does each as a gather of
  ROWS. Measured on a v5e (PERF.md section 6, PR 29): XLA's gather of
  scalars out of a 1-D table costs ~7 ns per OUTPUT element whatever
  the table and the order of the indices (0.94-1.13 ms into the
  131,072 plan rows of the 10k x 1k cluster, where a streaming fusion
  of that size is 12 us); a gather of rows two to eight wide costs
  ~1.8 ns a row (0.23 ms). Therefore:
  * the phase loop CARRIES its state in the sorted entry space, where
    the superstep reads it — the residual of every row and the excess
    of every node — and never the per-arc flow. A push leaves its row
    and arrives at its partner (the row of the same arc's opposite
    entry), so a superstep's update is r' = r - delta + delta[partner]
    and excess' = excess - pushed + seg_sum(delta[partner]). The flow is
    read back once, after the loop (an arc's flow is its backward
    row's residual);
  * what no loop changes is LIFTED and gathered once a solve: each
    row's capacity, its signed cost, its partner;
  * every gather goes through `_rows`: the tables that share an index
    are stacked into one table of rows, a lone table is doubled. A
    superstep gathers into the plan rows four times (p and excess at
    the row's own node, p at its far end, the segment's prefix
    base, the partner's push) and into the nodes twice (every
    per-node sum and the relabel candidate, read at the nodes' first
    and last rows), where the arc-space loop state took eight, one
    over the arcs and thirteen, all of scalars: 11.7 ms a superstep
    became 2.3.
- The CSR ordering depends only on arc endpoints. For plain array
  problems it is cached and rebuilt on the host (numpy argsort) when
  the structure changes; problems that carry a slot-stable plan
  (graph/slot_plan.py — every DeviceGraphState problem) skip the host
  rebuild entirely: endpoint churn mutates O(1) maintained plan rows,
  shipped as packed records through one jit'd scatter (a node that
  out-churns its region relocates to a tail-pool span the same way),
  and the argsort survives only on full_build / pow2 growth /
  tail-pool exhaustion.
- Everything is int32: TPU v5e has no native int64 (emulation trips XLA
  scoped-vmem issues and is slow). Scaled costs |c|*N must fit int32
  (checked on entry); potentials are guarded against overflow.
- Shapes are static per padded generation (power-of-two growth in
  DeviceGraphState), so repeated rounds reuse one compiled executable.

Incremental warm start (the property Flowlessly's daemon mode
provides), JOURNAL-SCOPED since r12: the change journal decides which
warm state each round may carry. Node potentials always carry — on
rounds whose journal holds only cap/cost/excess changes, the warm
prologue REFITS them (the tightening Bellman sweep seeded with the
carried prices moves only the journal-dirty frontier) and the carried
flow discharges at eps=1 in a handful of supersteps. Carried FLOW,
however, is kept only when the journal re-wired NO arc endpoints:
an endpoint-churn round's optimum displaces carried flow, and
discharging displaced excess is the measured unit-relabel price war
(600-4,000 supersteps at 1% churn — and measured NOT fixable by
price quality: exact entry prices, deeper Bellman budgets, warm eps
ladders, and periodic global relabels all leave or worsen it, see
_solve_mcmf). Those rounds dispatch the fresh-restart program up
front — zero flow, tightened prices, eps=1, a superstep a hop on these
graphs — the same program the old `restart_budget` escape reached
only after burning a doomed warm attempt. Cost-scaling from max-cost
remains the final fallback, and `restart_budget` still backstops the
kept-flow warm attempts (a budget blow is reported as a structured
`warm_price_war` soltel event).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..graph.device_export import FlowProblem
from ..obs.spans import span
from .base import FlowResult, FlowSolver, check_finite_costs, lower_bound_cost

_BIG = jnp.int32(1 << 30)
_P_GUARD = 1 << 30  # potential magnitude beyond this risks int32 overflow


@dataclass
class CsrPlan:
    """Host-prebuilt ordering of the doubled residual entries by source
    node, with everything the device needs for segment reductions."""

    s_arc: np.ndarray  # int32[2M] arc slot per sorted entry
    s_sign: np.ndarray  # int32[2M] +1 forward, -1 backward
    s_src: np.ndarray  # int32[2M]
    s_dst: np.ndarray  # int32[2M]
    s_segstart: np.ndarray  # int32[2M] sorted index of the entry's segment start
    s_isstart: np.ndarray  # bool[2M] segment-start flags
    inv_order: np.ndarray  # int32[2M] sorted position of original entry j
    node_first: np.ndarray  # int32[N] row_ptr[:-1] clamped
    node_last: np.ndarray  # int32[N] row_ptr[1:]-1 clamped
    node_nonempty: np.ndarray  # bool[N]
    src: np.ndarray  # int32[M] the endpoints this plan was built for
    dst: np.ndarray  # int32[M]


def build_csr_plan(src: np.ndarray, dst: np.ndarray, num_nodes: int) -> CsrPlan:
    m = len(src)
    esrc = np.concatenate([src, dst])
    order = np.argsort(esrc, kind="stable").astype(np.int32)
    s_src = esrc[order]
    s_dst = np.concatenate([dst, src])[order]
    s_arc = np.where(order < m, order, order - m).astype(np.int32)
    s_sign = np.where(order < m, 1, -1).astype(np.int32)
    inv_order = np.empty(2 * m, dtype=np.int32)
    inv_order[order] = np.arange(2 * m, dtype=np.int32)
    row_ptr = np.zeros(num_nodes + 1, dtype=np.int64)  # kschedlint: host-only (numpy plan build (row_ptr of 2M entries can exceed int32 in principle))
    counts = np.bincount(s_src, minlength=num_nodes)
    row_ptr[1:] = np.cumsum(counts)
    s_segstart = row_ptr[s_src].astype(np.int32)
    s_isstart = np.zeros(2 * m, dtype=bool)
    s_isstart[np.unique(s_segstart)] = True
    node_first = np.minimum(row_ptr[:-1], 2 * m - 1).astype(np.int32)
    node_last = np.maximum(row_ptr[1:] - 1, 0).astype(np.int32)
    node_nonempty = (row_ptr[1:] > row_ptr[:-1])
    return CsrPlan(
        s_arc=s_arc,
        s_sign=s_sign,
        s_src=s_src.astype(np.int32),
        s_dst=s_dst.astype(np.int32),
        s_segstart=s_segstart,
        s_isstart=s_isstart,
        inv_order=inv_order,
        node_first=node_first,
        node_last=node_last,
        node_nonempty=node_nonempty,
        src=src.copy(),
        dst=dst.copy(),
    )


#: rows of a gather table (`_rows`) that XLA compiled into VMEM with
#: its rows padded to the lane width (196,608 x 512 B = 96 MiB; at
#: 229,376 part of it spills), and the fewest from which it tiles
#: every table of two to four columns compactly (266,240 still pads,
#: 274,432 does not). Read off programs compiled ahead of time for a
#: v5e under jax 0.9.0, no chip; the ns a row are chip readings
_TABLE_ROWS_IN_VMEM = 196_608
_TABLE_ROWS_COMPACT = 278_528


def _rows(idx, *cols):
    """``tuple(c[idx] for c in cols)`` for 1-D tables of one length,
    done as ONE gather of rows two or more wide (a lone column is
    doubled). The shape is the point: XLA's TPU gather of scalars out
    of a 1-D table goes element by element, ~7 ns an output element on
    a v5e whatever the table's size or the order of the indices, while
    a gather of rows moves a tile row an index, ~1.8 ns a row up to at
    least 8 columns (PERF.md section 6, PR 29: 0.955 ms against 0.23
    ms for the 131,072 plan rows). The rows come back padded to the
    lane width, which is the temporary memory this costs (64 MB a
    gather into the plan rows of the 10k x 1k cluster).

    The TABLE is padded to the lane width too while XLA's layout
    assignment sees fit, and gathers that fast only while the padded
    table (512 B a row) fits the v5e's 128 MiB of VMEM. Above
    `_TABLE_ROWS_COMPACT` the compiler tiles a table compactly
    instead (4.2 ns a gathered row); in between it still pads, the
    table lies in HBM, and every table a superstep builds costs a
    padded copy of the plan on top (12.2 ns a row at 262,144 rows: a
    plan of 262,144 rows solved slower than the same graph on
    524,288). A table in that gap is given dead rows, which no index
    reads, up to where the compiler switches (PERF.md section 6, PR
    41). The tables a plan meets have pow2 rows: 262,144 is the one."""
    table = jnp.stack(cols * 2 if len(cols) == 1 else cols, axis=1)
    rows = table.shape[0]
    if _TABLE_ROWS_IN_VMEM < rows < _TABLE_ROWS_COMPACT:
        table = jnp.pad(table, ((0, _TABLE_ROWS_COMPACT - rows), (0, 0)))
    got = table[idx]
    return tuple(got[:, i] for i in range(len(cols)))


def _seg_scan(pick, vals, isstart):
    """Running ``pick`` (jnp.maximum / jnp.minimum) within each segment
    of a sorted-entry array, via a segmented associative scan: at a
    segment's last row stands the segment's reduction."""

    def combine(a, b):
        f1, v1 = a
        f2, v2 = b
        return f1 | f2, jnp.where(f2, v2, pick(v1, v2))

    return lax.associative_scan(combine, (isstart, vals))[1]


def _seg_ends(node_first, node_last, node_nonempty, vals, cums, *at_last):
    """Per-node sums of the sorted-entry arrays ``vals`` (0 for an
    empty node) from their cumsums ``cums``, followed by the further
    arrays ``at_last`` as they stand at each node's last row (the
    caller masks empty nodes): every boundary read of a pass through
    two row gathers."""
    last = _rows(node_last, *cums, *at_last)
    first = _rows(node_first, *(c - v for c, v in zip(cums, vals)))
    sums = tuple(jnp.where(node_nonempty, l - f, 0) for l, f in zip(last, first))
    return sums + last[len(sums):]


_BIG_D = 1 << 28  # "unreachable" distance sentinel for price tightening
#: so a path's cost times the node count, and its hops on top (`tighten`
#: counts a row one dearer), has to stay below it: a tightened
#: distance of _BIG_D reads as unreachable (the refusal inside a round,
#: `max|cost| * nodes >= 2^30`, is of one arc and comes later). A cost model
#: that states its largest cost is held to this before the service exists
#: (cli.refuse_costs_that_cannot_fit)
MAX_SCALED_PATH_COST = _BIG_D

#: a service with preemption (--preemption --backend jax): the discharge
#: lowers its prices to the residual graph's exact ones after every this
#: many supersteps (JaxSolver.price_update_every). Any value ends the round
#: (prices only fall); an update costs about as many sweeps as a path has
#: hops (arrival -> EC -> machine -> core -> PU -> back along a running
#: arc -> unscheduled aggregator -> sink: 7), so a smaller value spends
#: its time in updates and a larger one in unit relabels between them:
#: on a full 125-machine cluster a round takes 14-61 supersteps at 4,
#: 26-42 at 8, 20-91 at 16 (CPU runs, PR 38)
PREEMPTION_PRICE_UPDATE_EVERY = 8

#: the caps of the active-set superstep (`_solve_mcmf`, `active_set`),
#: from readings on a v5e under jax 0.9.0 (PERF.md section 6, PR 50;
#: one node active a superstep, 200 supersteps a solve, ms a superstep,
#: dense against sparse): 11.0 / 2.1 at 524,288 plan rows, 5.5 / 1.25
#: at 262,144, 1.2 / 0.6 at 65,536. A sparse superstep costs ~0.45 ms
#: whatever the plan (a `top_k` over the nodes, a dozen launches) and
#: then follows its caps:
#: the most nodes that may hold excess (+0.1 ms from 4,096 to 8,192 at
#: 524,288 rows: their reads, span marks and write-back). Since PR 52
#: no PU holds excess when a round starts (the export routes a pinned
#: pod's unit PU -> sink itself, `DeviceGraphState.routed`), so a
#: trickle round's supersteps hold its arrivals, tens of nodes, and
#: 8,192 is room for a wave of thousands, or for the PUs of a problem
#: that comes with its pins unrouted (a plain array-built one)
_ACTIVE_NODES = 8_192
#: and the most plan rows their regions may span, as a share of the
#: plan: ~55 ns a compacted row (one gather of the row's four values at
#: 4.3 ns, the far end's price, two reads by owner, three scatter
#: updates at ~7 ns each), so a sixteenth of the plan costs a fifth of
#: a dense superstep and an eighth would cost a third. At 524,288 rows
#: it is 32,768: `gtrace-12500-quincy`'s cluster aggregator (12,500
#: arcs and a quarter of slack) with room to spare
_ACTIVE_ROWS_SHARE = 16
#: plans below this many rows keep the one form: the two costs cross
#: near 30,000 rows (a dense superstep ~18 ns a plan row, read down from
#: 65,536; nothing smaller was read on the chip)
_ACTIVE_MIN_PLAN_ROWS = 32_768


def active_set_caps(plan_rows: int) -> Optional[Tuple[int, int]]:
    """`_solve_mcmf`'s `active_set` for a plan of this many rows: the
    caps within which a superstep takes the sparse form, or None where
    the plan is too small for one to pay."""
    if plan_rows < _ACTIVE_MIN_PLAN_ROWS:
        return None
    return _ACTIVE_NODES, plan_rows // _ACTIVE_ROWS_SHARE


@functools.partial(jax.jit, static_argnames=("alpha", "max_supersteps", "tighten_sweeps", "telemetry_cap", "use_warm_p", "slot_stable", "price_update_every", "active_set"))  # kschedlint: program=csr_solve
def _solve_mcmf(
    cap, cost, supply, flow0, eps_init,
    s_arc, s_sign, s_src, s_dst, s_segstart, s_isstart, inv_order,
    node_first, node_last, node_nonempty,
    warm_p=None,
    alpha: int = 8,
    max_supersteps: int = 50_000,
    tighten_sweeps: int = 32,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
    slot_stable: bool = False,
    price_update_every: int = 0,
    active_set: Optional[Tuple[int, int]] = None,
):
    """telemetry_cap > 0 appends a superstep-indexed int32 telemetry
    ring [telemetry_cap, SOLTEL_WIDTH] to the returned tuple (row
    layout: obs/soltel.py), written at `step % cap` so the final
    supersteps always survive. The counters read state each superstep
    already computes — flows are bit-identical on/off, and with cap=0
    no telemetry op is traced (no cost when off; the telemetry-off
    trace is pinned by the jaxpr contracts).

    What the loop carries and what it lifts (module docstring): the
    state is (r, excess, p, eps, steps, done): the residual of every
    sorted entry, the excess of every node, the potentials. `s_cap`,
    `s_cost` and `s_partner` are gathered once, before any loop, from
    the tensors this function is handed (no plan tensor is added), and
    serve `tighten`, `saturate` and the superstep alike. `saturate` is
    stated on rows (a row with negative reduced cost is emptied, its
    partner, whose reduced cost is the negative, takes the capacity), so
    neither the prologue nor a phase change of a cold ladder converts
    to arc space. `tighten` and `saturate` are the prologue of PR 54
    (module docstring: 1-optimal prices, the tree admissible, rows
    within [-eps, +eps] kept). Held bit for bit to the arc-state
    program it replaced
    (flow, p, steps, converged, p_overflow, every soltel row) by
    tests/test_csr_entry_state.py.

    use_warm_p=True REFITS the caller-supplied ``warm_p`` potentials
    (the previous round's device-resident prices) instead of running
    the from-scratch tightening pass: the same Bellman sweep loop is
    seeded with d0 = -warm_p, so the first sweep only moves nodes with
    a violated residual out-arc — exactly the journal-touched dirty
    frontier — and later sweeps expand that frontier until the prices
    are consistent again (or the sweep budget runs out; the saturate
    step then restores eps-optimality regardless, so the result is an
    exact optimum either way). Because last round's converged prices
    certify last round's flow, violations exist only around the churn,
    which is what kills the warm-start price war: the discharge starts
    eps-optimal-ish and drains in fresh-restart-like superstep counts
    instead of unit-relabel wars. With the defaults (None, False)
    warm_p contributes no invars to the traced program.

    slot_stable=True consumes a scatter-maintained slot-stable plan
    (graph/slot_plan.py): entry rows live in fixed per-node regions
    with slack, and liveness is encoded in the sign column (s_sign in
    {+1, -1, 0}) — a dead row's capacity, cost and residual are forced
    to 0, which makes it inert in every reduction (no separate mask
    tensor), and what would arrive at it through its stale partner is
    masked. The default (False) is the tightly-packed build_csr_plan
    layout, where every row is live; both layouts run the same code.

    price_update_every=G > 0 is the global price update for graphs whose
    running tasks keep their arcs (`--preemption`): in the eps=1
    discharge, after every G-th superstep every potential is lowered to
    what `tighten` gives on the residual graph as it then stands (minus
    the distance, a hop counted one, to the nodes still short of flow:
    at eps=1, on costs scaled by the node count, no residual cycle is
    negative), where that is lower than what the node has. Without it
    such a round
    does not end: a full cluster hands out more units than PU -> sink
    takes, the zero-flow prices of the prologue cannot know, and the units left over at the
    PUs sink the whole plateau of machines, PUs and running tasks one
    unit relabel at a time until a task's arc to its unscheduled
    aggregator turns admissible (cost x nodes relabels for each node:
    45,617 supersteps for ONE arrival on a full 125-machine cluster,
    25,514 of them in one phase of the cold ladder with one unit of
    excess wandering; CHANGES.md, PR 38). The default (0) traces no op
    of it: the program of every service without preemption is the one
    it was.

    active_set=(K, R) traces the superstep's second form beside the
    first (module docstring; `active_set_caps`): a superstep whose
    nodes with excess > 0 number at most K and whose regions span at
    most R plan rows runs `active_superstep`, any other `superstep`.
    The state, the results and every soltel row are what they were,
    bit for bit (tests/test_active_superstep.py); the supersteps that
    took the sparse form are counted and returned sixth, before the
    telemetry ring. The default (None) traces no op of it and returns
    the five outputs it always did: the program of the stacked lanes,
    of `__graft_entry__` and of every caller that is not `JaxSolver`.

    Discharging DISPLACED excess through carried flow is structurally
    slow here, and no price seeding fixes it (measured, r12): with the
    prologue tighten CONVERGED (exact prices — raising its sweep cap
    changes nothing), a churn round's warm attempt still drains its
    bulk excess in ~20 supersteps and then strands the last displaced
    units in a unit-relabel crawl for hundreds-to-thousands of steps —
    the displacement chains are discovered one eps-relabel at a time,
    and a periodic mid-discharge global relabel makes it WORSE (10x,
    measured: re-tightening un-does the relabel progress that IS the
    chain discovery). That is why JaxSolver keeps carried flow only on
    journal-rounds with no endpoint churn (see its docstring)."""
    from ..obs.soltel import SOLTEL_WIDTH

    m = cap.shape[0]
    i32 = jnp.int32
    seg = (node_first, node_last, node_nonempty)

    # Lifted: what no loop changes, gathered once a solve, and the
    # residual per sorted entry of the warm flow. A dead row of the
    # slot-stable layout (sign 0) gets capacity, cost and residual 0
    # and keeps them through every formula below; its partner points
    # anywhere and is masked where it is read.
    s_cap, a_cost, a_flow0 = _rows(s_arc, cap, cost, flow0)
    s_cost = s_sign * a_cost
    (s_partner,) = _rows(jnp.where(s_sign > 0, s_arc + m, s_arc), inv_order)
    r0 = jnp.where(s_sign > 0, s_cap - a_flow0, a_flow0)
    if slot_stable:
        live = s_sign != 0
        s_cap, r0 = jnp.where(live, s_cap, i32(0)), jnp.where(live, r0, i32(0))

    def excess_of(r):
        """Node excess of the pseudoflow the rows hold: a forward row
        carries flow s_cap - r out of its node, a backward row r into
        it (a dead row 0)."""
        signed = jnp.where(s_sign > 0, s_cap - r, -r)
        (out,) = _seg_ends(*seg, (signed,), (jnp.cumsum(signed),))
        return supply - out

    def saturate(r, p, eps):
        """Refine step: empty every residual entry whose reduced cost
        lies below -eps (its partner, whose reduced cost is the negative,
        takes the whole capacity), making the pseudoflow eps-optimal for
        the phase. A row within [-eps, +eps] stays as it stands: `tighten`
        leaves the shortest-path tree at -1 (module docstring)."""
        (p_src,), (p_dst,) = _rows(s_src, p), _rows(s_dst, p)
        rc = s_cost + p_src - p_dst
        return jnp.where(rc < -eps, i32(0), jnp.where(rc > eps, s_cap, r))

    def tighten(r, d0=None):
        """Price tightening: p = -(shortest residual-cost distance to a
        demand node, every row counted ONE DEARER than it costs), via
        synchronous Bellman-Ford sweeps over the sorted entries: the
        exact distance plus the fewest hops among the shortest paths
        (module docstring). Afterwards every residual arc between
        reachable nodes has reduced cost >= -1 and every arc of the
        shortest-path tree exactly -1, so the discharge can run at eps=1
        regardless of how flows/capacities changed since the last round
        (this is what makes warm restarts cheap and drift-free) and moves
        a unit a hop in its first superstep. `largest cost x nodes <
        _BIG_D` leaves the room the hops take: a pow2 node bucket divides
        _BIG_D, so the product stays a bucket below it.

        With an explicit ``d0`` this is the warm-prologue REFIT instead:
        seeded from the carried prices, the relaxation only moves nodes
        whose residual out-arcs are violated (the dirty frontier), and
        the `changed` early-exit stops as soon as the frontier drains —
        a bounded Bellman sweep over the journal-touched subgraph,
        expressed data-parallel."""
        if d0 is None:
            d0 = jnp.where(excess_of(r) < 0, i32(0), i32(_BIG_D))

        def t_cond(state):
            _d, changed, it = state
            return changed & (it < tighten_sweeps)

        def t_body(state):
            d, _, it = state
            (d_dst,) = _rows(s_dst, d)
            cand = jnp.where(r > 0, s_cost + 1 + d_dst, i32(_BIG_D))
            (best,) = _rows(node_last, _seg_scan(jnp.minimum, cand, s_isstart))
            best = jnp.where(node_nonempty, best, i32(_BIG_D))
            # Clamp from below: a negative-cost residual cycle (possible
            # transiently with warm flows + changed costs) must not run d
            # toward int32 wraparound; the discharge handles the rest.
            d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
            return d2, jnp.any(d2 != d), it + 1

        d, _, _ = lax.while_loop(t_cond, t_body, (d0, jnp.bool_(True), i32(0)))
        return -jnp.minimum(d, i32(_BIG_D))

    def superstep(r, excess, p, eps):
        (p_dst,) = _rows(s_dst, p)
        p_src, e_at = _rows(s_src, p, excess)
        rc = s_cost + p_src - p_dst
        admissible = (r > 0) & (rc < 0) & (e_at > 0)

        # Maximal push: allocate each node's excess across its admissible
        # entries front-to-back via an in-segment exclusive prefix sum.
        r_adm = jnp.where(admissible, r, i32(0))
        cum = jnp.cumsum(r_adm)
        excl = cum - r_adm
        (base,) = _rows(s_segstart, excl)
        delta = jnp.clip(e_at - (excl - base), 0, r_adm)

        # A push leaves its own row and arrives at the partner row (the
        # same arc's opposite entry), whose node receives it.
        (arrived,) = _rows(s_partner, delta)
        if slot_stable:
            arrived = jnp.where(live, arrived, i32(0))
        new_r = r - delta + arrived

        # Per node, from one pair of boundary reads: what its segment
        # could take (an active node pushes min(excess, that): the
        # allocation above fills front to back), what arrived, whether a
        # residual entry is left, and the relabel candidate.
        cand = jnp.where(r > 0, p_dst - s_cost, -_BIG)
        adm, arrived_sum, sum_r, best = _seg_ends(
            *seg, (r_adm, arrived, r), (cum, jnp.cumsum(arrived), jnp.cumsum(r)),
            _seg_scan(jnp.maximum, cand, s_isstart),
        )
        pushed = jnp.clip(excess, 0, adm)
        new_excess = excess - pushed + arrived_sum

        # Relabel nodes that were active but pushed nothing (maximal push
        # guarantees active nodes with an admissible entry push >= 1).
        best = jnp.where(node_nonempty, best, -_BIG)
        relabel = (excess > 0) & (pushed == 0) & (sum_r > 0)
        new_p = jnp.where(relabel, best - eps, p)
        if not telemetry_cap:
            return new_r, new_excess, new_p, ()
        # counters over state this superstep already computed (soltel
        # row cols 3..6); purely observational, never fed back.
        # Cost discipline: `pushed` is the already-reduced [N] per-node
        # push total (sum == sum(delta) since segments partition the
        # entries), and the saturated mask reuses r/s_sign — the only
        # NEW entry-space passes are two compare+sum sweeps, no
        # gathers (a zero-capacity arc counts as saturated: its
        # residual is zero, which is what the counter means).
        aux = (
            jnp.sum(pushed),
            jnp.sum(relabel.astype(i32)),
            jnp.sum(((s_sign > 0) & (r == 0)).astype(i32)),
            # r_adm > 0 <=> admissible (admissibility requires r > 0),
            # and r_adm is already materialized for the prefix cumsum
            jnp.sum((r_adm > 0).astype(i32)),
        )
        return new_r, new_excess, new_p, aux

    if active_set:
        k_cap, r_cap = min(active_set[0], supply.shape[0]), active_set[1]

        def active_set_fits(excess):
            """Whether this superstep's work lies within the caps: the
            nodes that hold excess, and the plan rows of their regions
            (dead rows counted: they ride along, inert, as in `superstep`).
            Two sums over the nodes."""
            act = excess > 0
            extent = jnp.where(act & node_nonempty, node_last - node_first + 1, i32(0))
            return (jnp.sum(act.astype(i32)) <= k_cap) & (jnp.sum(extent) <= r_cap)

        def active_superstep(r, excess, p, eps):
            """`superstep` over the rows of the nodes that hold excess and
            nothing else: the same integers by the same formulas, read at
            `r_cap` compacted rows and `k_cap` nodes and written back where
            they change (only called where `active_set_fits`). A region's
            rows keep their plan order and the regions follow each other in
            node order without a gap, so the compacted rows are a packed
            plan of their own: segment k starts where k - 1 ended, and the
            front-to-back allocation gives each row the `delta` it had."""
            n, e = excess.shape[0], r.shape[0]

            # the active nodes in node order (the k_cap lowest ids: top_k of
            # a key that falls with the id), and where each one's region
            # lands among the compacted rows
            key, _ = lax.top_k(
                jnp.where(excess > 0, n - jnp.arange(n, dtype=i32), i32(0)), k_cap
            )
            held = key > 0
            ids = jnp.where(held, n - key, i32(0))
            first_k, last_k, nonempty_k, p_k, e_k = _rows(
                ids, node_first, node_last, node_nonempty.astype(i32), p, excess
            )
            nonempty_k = nonempty_k != 0
            ext = jnp.where(held & nonempty_k, last_k - first_k + 1, i32(0))
            end = jnp.cumsum(ext)
            start = end - ext

            # slot s belongs to the node whose span holds it: the nodes
            # whose span ends at or before s, counted
            slot = jnp.arange(r_cap, dtype=i32)
            owner = jnp.cumsum(jnp.zeros(r_cap, i32).at[end].add(1, mode="drop"))
            owner = jnp.minimum(owner, k_cap - 1)
            used = slot < end[-1]
            shift, seg0, p_src, e_at = _rows(owner, first_k - start, start, p_k, e_k)
            row = jnp.where(used, slot + shift, i32(0))
            cost_r, dst_r, partner_r, r_at = _rows(row, s_cost, s_dst, s_partner, r)
            r_at = jnp.where(used, r_at, i32(0))
            (p_dst,) = _rows(dst_r, p)

            # `superstep`'s allocation, relabel candidate and per-node sums
            rc = cost_r + p_src - p_dst
            r_adm = jnp.where((r_at > 0) & (rc < 0), r_at, i32(0))
            cum = jnp.cumsum(r_adm)
            cand = jnp.where(r_at > 0, p_dst - cost_r, -_BIG)
            adm, sum_r, best = _seg_ends(
                jnp.minimum(start, r_cap - 1), jnp.clip(end - 1, 0, r_cap - 1), ext > 0,
                (r_adm, r_at), (cum, jnp.cumsum(r_at)),
                _seg_scan(jnp.maximum, cand, slot == seg0),
            )
            (base,) = _rows(owner, jnp.cumsum(adm) - adm)
            delta = jnp.clip(e_at - (cum - r_adm - base), 0, r_adm)
            pushed = jnp.where(held, jnp.clip(e_k, 0, adm), i32(0))
            best = jnp.where(ext > 0, best, -_BIG)
            relabel = held & (pushed == 0) & (sum_r > 0)

            # write back: a push leaves its row and arrives at its partner
            # and at its far end (far ends repeat); an active node gives
            # what it pushed and, relabelled, takes its new price. A row
            # that pushed nothing goes out of range and is dropped.
            moved = delta > 0
            new_r = r.at[
                jnp.concatenate([jnp.where(moved, row, i32(e)), jnp.where(moved, partner_r, i32(e))])
            ].add(jnp.concatenate([-delta, delta]), mode="drop")
            new_excess = excess.at[
                jnp.concatenate([jnp.where(pushed > 0, ids, i32(n)), jnp.where(moved, dst_r, i32(n))])
            ].add(jnp.concatenate([-pushed, delta]), mode="drop")
            new_p = p.at[jnp.where(relabel, ids, i32(n))].add(best - eps - p_k, mode="drop")
            if not telemetry_cap:
                return new_r, new_excess, new_p, ()
            aux = (
                jnp.sum(pushed),
                jnp.sum(relabel.astype(i32)),
                jnp.sum(((s_sign > 0) & (r == 0)).astype(i32)),
                jnp.sum((r_adm > 0).astype(i32)),
            )
            return new_r, new_excess, new_p, aux

    if telemetry_cap:
        from ..obs import soltel as _soltel

        _tel_rows_iota = _soltel.device_rows_iota(telemetry_cap)

    def tel_row(eps, excess, aux):
        active = jnp.sum((excess > 0).astype(i32))
        exc_pos = jnp.sum(jnp.maximum(excess, 0))
        return _soltel.device_row(eps, active, exc_pos, *aux)

    def tel_write(tel, steps, row):
        return _soltel.device_ring_write(
            tel, steps, row, telemetry_cap, _tel_rows_iota
        )

    # loop state: (r, excess, p, eps, steps, done[, sparse][, tel])
    def phase_cond(state):
        steps, done = state[4], state[5]
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        r, excess, p, eps, steps, done = state[:6]
        sparse, tel = (state[6:7], state[7:]) if active_set else ((), state[6:])
        any_active = jnp.any(excess > 0)

        def do_superstep(_):
            if active_set:
                # per superstep, from the loop state: one solve may hold
                # both kinds (a wave's bulk supersteps, then its stragglers')
                fits = active_set_fits(excess)
                r2, e2, p2, aux = lax.cond(
                    fits, active_superstep, superstep, r, excess, p, eps
                )
            else:
                r2, e2, p2, aux = superstep(r, excess, p, eps)
            if price_update_every:
                # the lower of the two prices at every node: both keep
                # every residual arc's reduced cost >= -eps, so their
                # minimum does (c + p(v) - p'(w) >= c + p(v) - p(w) for
                # p' <= p), and prices still only fall, which is what
                # ends the discharge: an update that could raise a price
                # forgets where a unit came from, and a unit then walks
                # a plateau of zero reduced cost for ever
                p2 = lax.cond(
                    (eps == 1) & ((steps + 1) % price_update_every == 0),
                    lambda: jnp.minimum(
                        p2, tighten(r2, d0=jnp.where(e2 < 0, i32(0), i32(_BIG_D)))
                    ),
                    lambda: p2,
                )
            out = (r2, e2, p2, eps, steps + 1, jnp.bool_(False))
            if active_set:
                out = out + (sparse[0] + fits.astype(i32),)
            if not telemetry_cap:
                return out
            return out + (tel_write(tel[0], steps, tel_row(eps, excess, aux)),)

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            r2 = jnp.where(finished, r, saturate(r, p, new_eps))
            out = (r2, excess_of(r2), p, jnp.where(finished, eps, new_eps), steps, finished)
            return out + sparse + tel

        return lax.cond(any_active, do_superstep, next_phase, operand=None)

    if use_warm_p:
        # dirty-frontier refit: Bellman sweeps seeded from the carried
        # prices (clipped into tighten's distance range so the relax
        # arithmetic cannot overflow int32)
        p0 = tighten(r0, d0=jnp.clip(-warm_p, -i32(_BIG_D), i32(_BIG_D)))
    else:
        p0 = tighten(r0)
    r1 = saturate(r0, p0, eps_init)  # mop up any residual violations
    state = (r1, excess_of(r1), p0, eps_init, i32(0), jnp.bool_(False))
    if active_set:
        state = state + (i32(0),)
    if telemetry_cap:
        state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
    r, excess, p, _eps, steps, done, *rest = lax.while_loop(
        phase_cond, phase_body, state
    )
    # the flow, read back once: an arc's flow is its backward row's residual
    (flow,) = _rows(inv_order[m:], r)
    converged = done & (jnp.max(jnp.abs(excess)) == 0)
    p_overflow = jnp.max(jnp.abs(p)) >= _P_GUARD
    # with `active_set`, the supersteps that took the sparse form come
    # sixth, before the telemetry ring
    return (flow, p, steps, converged, p_overflow, *rest)


# ---------------------------------------------------------------------------
# Stacked-CSR batched entry: one compiled program for a whole shape
# bucket of tenant lanes (ksched_tpu/tenancy — multi-tenant service)
# ---------------------------------------------------------------------------

#: lane counts pad to pow2 buckets (repeating a real lane, which is
#: idempotent: a duplicate lane computes the same solve and its outputs
#: are ignored), so tenants joining/leaving re-use executables instead
#: of recompiling per lane-count — same policy as the record buckets in
#: graph/device_export.pad_record_count
MIN_LANE_BUCKET = 1


def pad_lane_count(k: int) -> int:
    from ..utils import next_pow2

    return max(next_pow2(max(k, 1)), MIN_LANE_BUCKET)


_STACKED_SOLVES: dict = {}


def stacked_solve_fn(
    *,
    alpha: int = 8,
    max_supersteps: int = 4096,
    tighten_sweeps: int = 32,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
):
    """The batched (block-diagonal stacked-CSR) solve program: same-
    bucket tenant lanes solved through ONE compiled executable.

    Independent flow components in a block-diagonal stack never
    interact, so batching them is semantically free; the lane axis is
    the leading dimension of every argument (the flat offset-id stack
    reshaped [L, ...] — lane i's node ids are its local ids plus
    i*n_cap in the flat view, see tenancy/batch.py). The program is
    ``jit(vmap(_solve_mcmf))`` with the statics bound, which gives the
    two properties the multi-tenant acceptance demands by
    construction:

    - **per-lane convergence masks**: jax's while-loop batching runs
      the loop until every lane's own condition is false and freezes
      finished lanes via select — a slow tenant cannot change another
      lane's state, and each lane's superstep count (carried in its
      own lane of the loop state) stops the moment IT converges;
    - **bit-identical per-lane solves**: each lane's carry evolves
      through exactly the ops the single-lane `_solve_mcmf` applies to
      the same int32 data, so flows, potentials, superstep counts, and
      telemetry rows equal the lane solved alone (asserted exhaustively
      by tests/test_tenancy.py).

    A lane that exhausts ``max_supersteps`` freezes unconverged
    (its ``converged`` output stays False) without extending the other
    lanes' superstep counts; wall-clock for the whole program is
    bounded by the slowest lane's budget, which is why the tenancy
    layer batches only budget-capped attempts and escalates per lane
    (tenancy/batch.py). Returns per-lane tuples shaped
    ``(flow [L, m], p [L, n], steps [L], converged [L],
    p_overflow [L][, telemetry [L, cap, W]])``.

    Cached per statics tuple: with pow2 lane-count and shape buckets
    the warm service re-uses one executable per (bucket, policy), the
    compile-cache amortization the ROADMAP's multi-tenant story names.
    The jaxpr contracts pin this program scatter-free, 32-bit, and
    hash-stable across raw sizes in a bucket and lane counts in a lane
    bucket (tests/test_static_analysis.py)."""
    key = (alpha, max_supersteps, tighten_sweeps, telemetry_cap, use_warm_p)
    fn = _STACKED_SOLVES.get(key)
    if fn is None:
        statics = dict(
            alpha=alpha,
            max_supersteps=max_supersteps,
            tighten_sweeps=tighten_sweeps,
            telemetry_cap=telemetry_cap,
            slot_stable=False,
        )
        if use_warm_p:

            def lane(cap, cost, supply, flow0, eps, warm_p, *plan):
                return _solve_mcmf(
                    cap, cost, supply, flow0, eps, *plan,
                    warm_p=warm_p, use_warm_p=True, **statics,
                )

        else:

            def lane(cap, cost, supply, flow0, eps, *plan):
                return _solve_mcmf(cap, cost, supply, flow0, eps, *plan, **statics)

        fn = jax.jit(jax.vmap(lane))  # kschedlint: program=stacked_solve
        _STACKED_SOLVES[key] = fn
    return fn


class JaxSolver(FlowSolver):
    """Cost-scaling push-relabel on device, warm-started across rounds.

    Handed a DeviceResidentProblem (graph/device_export.py), the solve
    reads the persistent device buffers directly — no device_put of
    unchanged arrays — and the warm flow is carried BETWEEN rounds as a
    device array (masked against the pre-delta endpoints by the
    scatter-free ``device_warm_flow_fn`` program), bit-identical to the
    host warm path. Node potentials are likewise kept device-resident;
    with ``warm_potentials=True`` (default) a kept-flow warm attempt
    REFITS the carried prices around the journal-touched subgraph
    instead of re-deriving them from scratch — an exact solve either
    way. ``journal_scoped_warm=True`` (default) decides PER ROUND
    whether the carried flow itself is reusable: only when the round's
    journal re-wired no endpoints (see the module docstring for the
    measured price-war evidence behind that rule). Every loop mode /
    export arm shares the same policy, so the bit-for-bit
    placement-parity suites still hold.

    ``slot_stable=True`` (default) consumes the scatter-maintained
    slot-stable plan when the problem carries one
    (graph/slot_plan.py): endpoint churn then never costs a host
    argsort or a full plan re-upload — the plan deltas ride the same
    dirty-slot journal as the problem deltas. Plain array problems
    (no plan handle) keep the legacy host-built CsrPlan."""

    def __init__(self, alpha: int = 8, max_supersteps: int = 50_000, warm_start: bool = True, telemetry: Optional[int] = None, warm_potentials: bool = True, restart_budget: Optional[int] = None, slot_stable: bool = True, journal_scoped_warm: bool = True, price_update_every: int = 0):
        from .layered import validate_alpha

        self.alpha = validate_alpha(alpha)
        #: global price update of the eps=1 discharge, every this many
        #: supersteps (0: never; _solve_mcmf's docstring): for graphs
        #: whose running tasks keep their arcs, which hand out more
        #: units than the cluster takes in every round
        self.price_update_every = price_update_every
        self.max_supersteps = max_supersteps
        self.warm_start = warm_start
        self.warm_potentials = warm_potentials
        self.slot_stable = slot_stable
        #: journal-scoped warm restart (default): the change journal
        #: decides WHICH warm state each round may carry. Prices are
        #: always reusable — the refit repairs them around whatever
        #: the journal touched — but carried FLOW is kept only when
        #: the journal holds no endpoint changes (plan_key match).
        #: An endpoint-churn round deletes/rewires arcs, so its
        #: optimum displaces carried flow, and discharging displaced
        #: excess is the measured unit-relabel price war (600-4,000
        #: supersteps at 1% churn; exact entry prices, deeper Bellman
        #: budgets, eps ladders, and periodic global relabels all
        #: measured NOT to fix it — see _solve_mcmf's docstring).
        #: Those rounds dispatch the fresh-restart program up front
        #: (zero flow, tightened prices, eps=1: a superstep a hop on
        #: these graphs) instead of burning a doomed warm attempt.
        #: False restores the r11 policy (always attempt the carried
        #: flow; rely on restart_budget to escape).
        self.journal_scoped_warm = journal_scoped_warm
        #: superstep budget for the WARM attempt before escaping to a
        #: fresh-restart solve (flow0=0, tightened prices, eps=1 — the
        #: few-superstep machine on these graphs) instead of burning
        #: the full 4096-step attempt-1 budget. None keeps the original
        #: two-attempt ladder. Since the dirty-frontier refit landed
        #: this is a BACKSTOP, not the fix: refitted warm attempts
        #: converge in fresh-restart-like superstep counts, and a
        #: budget blow is reported as a structured `warm_price_war`
        #: soltel event before escaping.
        self.restart_budget = restart_budget
        #: telemetry ring capacity override; None = the soltel module
        #: default (0 when KSCHED_SOLTEL=0 — telemetry off, identical
        #: traced program), resolved per solve
        self.telemetry = telemetry
        self._prev: Optional[np.ndarray] = None  # previous round's flow
        self._prev_dev = None  # same flow as a device array (no re-upload)
        self._prev_p = None  # previous round's potentials, device-resident
        #: endpoint buffers AT THE LAST SUCCESSFUL SOLVE — the warm
        #: mask must compare against these, not the pre-delta buffers
        #: of the latest refresh: a failed/degraded round still
        #: refreshes the mirror, and masking against its endpoints
        #: would miss changes from the round the solver never saw
        self._prev_src_dev = None
        self._prev_dst_dev = None
        #: same endpoints as host arrays (the non-resident warm mask)
        self._prev_src_host = None
        self._prev_dst_host = None
        self._plan: Optional[CsrPlan] = None
        self._plan_dev: Optional[tuple] = None
        #: endpoint-generation key of the cached plan
        #: (FlowProblem.plan_key) — equal keys skip the O(M) endpoint
        #: scans entirely on clean rounds
        self._plan_key = None
        #: endpoint key AT THE LAST SUCCESSFUL SOLVE — the journal-
        #: scoped warm policy keeps carried flow only when the current
        #: problem's key matches (no endpoint churn since that solve)
        self._key_solved = None
        self.last_supersteps = 0
        #: of those, the supersteps that worked on the rows of the
        #: active nodes alone (`active_superstep`), over every attempt
        self.last_sparse_supersteps = 0
        #: the global price updates that fired in the last solve: host
        #: arithmetic, `steps // price_update_every` of each attempt that
        #: ran at eps 1 from its first superstep (attempt 1 and the
        #: restart escape; a cold ladder reaches eps 1 at a step the host
        #: does not see, and its updates are not counted)
        self.last_price_updates = 0
        self.last_telemetry = None  # SolveTelemetry of the last solve
        self.last_warm_scope = "cold"  # warm | fresh | cold (see solve_async)
        #: exact bytes of the last solve's own transfers, every attempt:
        #: what solve_async and a retry handed to `jnp.asarray` (problem
        #: arrays, warm flow, eps, the plan's re-ship; a resident problem
        #: sends the eps scalar alone), and what complete() fetched (the
        #: attempts' scalars, the telemetry ring, the flow; prices stay
        #: on the device)
        self.last_h2d_bytes = 0
        self.last_d2h_bytes = 0

    def reset(self) -> None:
        self._prev = None
        self._prev_dev = None
        self._prev_p = None
        self._prev_src_dev = None
        self._prev_dst_dev = None
        self._prev_src_host = None
        self._prev_dst_host = None
        self._key_solved = None

    # -- warm-state checkpointing (runtime/checkpoint.save_warm_manifest) --

    def export_warm_state(self) -> Optional[dict]:
        """The carried warm state as host arrays, or None when cold —
        what a warm crash restore needs to make its first solve
        bit-identical to the never-killed process's. One D2H fetch of
        the potentials (the flow already has a host copy)."""
        if self._prev is None:
            return None
        return {
            "prev": np.asarray(self._prev, np.int32),
            "prev_p": (
                np.asarray(self._prev_p, np.int32)
                if self._prev_p is not None else None
            ),
            "prev_src": (
                np.asarray(self._prev_src_host, np.int32)
                if self._prev_src_host is not None else None
            ),
            "prev_dst": (
                np.asarray(self._prev_dst_host, np.int32)
                if self._prev_dst_host is not None else None
            ),
            "key_solved": self._key_solved,
        }

    def import_warm_state(
        self, state: dict, key_solved=None, resident: bool = False
    ) -> None:
        """Adopt an export_warm_state payload. `key_solved` is the
        endpoint key REMAPPED onto the restored DeviceGraphState (its
        uid changes across processes; the checkpoint loader owns the
        remap). With `resident`, the warm flow and the last-solve
        endpoint masks are re-uploaded so a device-resident loop's
        first post-restore warm attempt consumes the exact buffers the
        killed process carried."""
        self._prev = np.asarray(state["prev"], np.int32)
        self._prev_p = (
            jnp.asarray(state["prev_p"]) if state.get("prev_p") is not None else None
        )
        self._prev_src_host = (
            np.asarray(state["prev_src"], np.int32)
            if state.get("prev_src") is not None else None
        )
        self._prev_dst_host = (
            np.asarray(state["prev_dst"], np.int32)
            if state.get("prev_dst") is not None else None
        )
        self._key_solved = key_solved if key_solved is not None else state.get("key_solved")
        if resident and self._prev_src_host is not None:
            self._prev_dev = jnp.asarray(self._prev)
            self._prev_src_dev = jnp.asarray(self._prev_src_host)
            self._prev_dst_dev = jnp.asarray(self._prev_dst_host)
        else:
            self._prev_dev = None
            self._prev_src_dev = None
            self._prev_dst_dev = None

    def _plan_for(self, src: np.ndarray, dst: np.ndarray, n: int, plan_key=None) -> tuple:
        """The host-built CsrPlan of a plain array problem on the
        device, and the bytes this call shipped for it (0 when the
        cached upload stands)."""
        plan = self._plan
        shipped = 0
        if plan_key is not None and self._plan_key == plan_key and plan is not None:
            return self._plan_dev, 0  # generation key match: no scans at all
        if plan is None or len(plan.src) != len(src) or len(plan.node_first) != n or plan_key is not None or not (
            np.array_equal(plan.src, src) and np.array_equal(plan.dst, dst)
        ):
            with span("plan_upload", kind="csr_build") as sp:
                plan = build_csr_plan(src, dst, n)
                self._plan = plan
                host = (
                    plan.s_arc, plan.s_sign, plan.s_src, plan.s_dst,
                    plan.s_segstart, plan.s_isstart, plan.inv_order,
                    plan.node_first, plan.node_last, plan.node_nonempty,
                )
                self._plan_dev = tuple(jnp.asarray(x) for x in host)
                shipped = sum(x.nbytes for x in host)
                sp.set("bytes", shipped)
            # Structure changed: stale flows are only reusable per-slot if
            # endpoints match, checked in solve().
        self._plan_key = plan_key
        return self._plan_dev, shipped

    def solve_async(self, problem: FlowProblem):
        """Dispatch the warm attempt WITHOUT synchronizing and return an
        opaque pending token for complete(). The device works while the
        host is free to build the next round's graph — the pipelining
        seam the reference's daemon-mode solver implies
        (placement/solver.go:60-90): its subprocess crunches DIMACS
        concurrently with the Go process, and here the asynchronous
        dispatch gives the same overlap in-process.

        Three leaf spans, in order, each with the size it worked on:
        `solve_prepare` (the host's checks, scaling, casts and warm-flow
        mask), `problem_upload` (every `jnp.asarray` of the dispatch,
        the plan's re-ship as its child `plan_upload`) and
        `solve_launch` (the call of `_solve_mcmf` until it returns its
        future). `last_h2d_bytes` is the exact `nbytes` handed to
        `jnp.asarray` here and by a retry in complete()."""
        self.last_h2d_bytes = 0
        self.last_d2h_bytes = 0
        n = problem.num_nodes
        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            if (problem.excess > 0).any():
                raise RuntimeError("infeasible flow problem: supply but no arcs")
            return (problem, None, None, None)
        from ..obs import soltel

        resident = getattr(problem, "d_cap", None) is not None
        plan_key = getattr(problem, "plan_key", None)
        with span("solve_prepare", arcs=m, nodes=n) as sp:
            check_finite_costs(problem)
            src = np.asarray(problem.src, np.int32)
            dst = np.asarray(problem.dst, np.int32)

            # Pre-scale costs by the node count so eps = 1 implies exactness;
            # the scaled range must fit int32 comfortably.
            max_cost = int(np.abs(problem.cost).max()) if m else 0
            if max_cost * n >= (1 << 30):
                raise OverflowError(
                    f"scaled costs overflow int32: max|cost|={max_cost} at {n} nodes; "
                    "rescale cost-model outputs or shrink the graph padding"
                )
            # Journal-scoped warm restart: the endpoint generation key says
            # whether this round's journal re-wired any arc. If it did, the
            # optimum displaces carried flow and the warm discharge is the
            # measured unit-relabel price war — dispatch the fresh-restart
            # program (a superstep a hop) up front instead. Carried PRICES
            # survive either way (the refit repairs them on clean rounds).
            keep_flow = True
            if self.journal_scoped_warm and plan_key is not None:
                keep_flow = (
                    self._key_solved is not None and plan_key == self._key_solved
                )
            if resident:
                # Device-resident problem: the folded arrays are already on
                # device (only this round's delta records crossed the
                # boundary); the warm flow is last round's device output,
                # masked ON the device against the last successful solve's
                # endpoints — the same values the host mask below computes,
                # without the flow round-trip.
                from ..graph.device_export import resident_solver_inputs

                dev_args, flow0_dev, warm = resident_solver_inputs(
                    problem, self._prev_dev, self._prev_src_dev,
                    self._prev_dst_dev, self.warm_start and keep_flow,
                )
            else:
                cap = problem.cap.astype(np.int32)
                supply = problem.excess.astype(np.int32)
                cost = problem.cost.astype(np.int32) * np.int32(n)
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
                    # Reuse prior flow where the arc endpoints are unchanged
                    # since the last SUCCESSFUL solve; the refit/tighten
                    # prologue inside the solve restores consistent prices.
                    # (With a matched plan_key the mask is all-ones by
                    # construction; plain-array problems carry no key, so
                    # the journal-scoped policy falls back to this compare.)
                    same = (self._prev_src_host == src) & (self._prev_dst_host == dst)
                    if self.journal_scoped_warm and plan_key is None and not same.all():
                        warm = False
                    else:
                        flow0 = np.where(same, np.minimum(self._prev, cap), 0).astype(np.int32)
            had_state = self._prev is not None or self._prev_dev is not None
            #: per-solve warm scope, for bench/obs accounting: "warm" =
            #: carried flow + refit prices, "fresh" = journal-scoped
            #: restart (endpoint churn; zero flow, tightened prices),
            #: "cold" = no carried state at all (first round / post-reset)
            self.last_warm_scope = (
                "warm" if warm else ("fresh" if had_state else "cold")
            )
            sp.set("warm", self.last_warm_scope)
            tel_cap = soltel.resolve_cap(self.telemetry)
            eps1 = np.int32(1)

        plan_state = getattr(problem, "plan", None) if self.slot_stable else None
        slot_stable = plan_state is not None
        with span("problem_upload") as sp:
            sent = eps1.nbytes
            if slot_stable:
                # slot-stable plan: endpoint churn was already folded into
                # the maintained layout — no argsort, no endpoint scans.
                # Prefer the device-resident scatter-maintained mirror;
                # otherwise the plan's own cached full upload (re-shipped
                # only when its value_version moved: `plan_upload`).
                d_plan = getattr(problem, "d_plan", None)
                if d_plan is not None and getattr(d_plan[0], "ndim", 1) == 2:
                    # sharded-mode mirror: the entry tensors are [D, Es]
                    # stacked per-shard tables. The stacking is a lossless
                    # reshape of the global layout (graph/slot_plan.py
                    # sharded block form), so flattening them recovers the
                    # exact single-chip tensors — this is the degradation
                    # ladder's jax rung (and AutoSolver's too-big-even-
                    # per-shard CSR fallback) consuming a sharded mirror.
                    # On a real mesh the reshape gathers the shards; a
                    # degraded round may pay that once.
                    d_plan = tuple(
                        x.reshape(-1) if getattr(x, "ndim", 1) == 2 else x
                        for x in d_plan
                    )
                if d_plan is not None:
                    plan_dev = d_plan
                else:
                    plan_dev = plan_state.device_args()
                    sent += plan_state.last_ship_bytes
            else:
                plan_dev, shipped = self._plan_for(src, dst, n, plan_key=plan_key)
                sent += shipped
            if not resident:
                dev_args = (
                    jnp.asarray(cap), jnp.asarray(cost), jnp.asarray(supply),
                )
                flow0_dev = jnp.asarray(flow0)
                sent += cap.nbytes + cost.nbytes + supply.nbytes + flow0.nbytes
            eps_dev = jnp.asarray(eps1)
            sp.set("bytes", sent)
        self.last_h2d_bytes = sent

        # Attempt 1: warm flow, tightened prices (or, with
        # warm_potentials, the previous round's device-resident prices)
        # + eps=1 discharge. Attempt 2: genuinely cold — zero flow and
        # full cost-scaling — so a poisoned warm state can always
        # recover. Only attempt 1 is dispatched here; the cold fallback
        # runs synchronously in complete() if needed (rare).
        warm_p_ok = (
            self.warm_potentials
            and warm
            and self._prev_p is not None
            and self._prev_p.shape[0] == n
        )
        attempt1_budget = min(4096, self.max_supersteps)
        active_set = active_set_caps(plan_dev[0].shape[0])
        if warm and self.restart_budget is not None:
            # budgeted warm attempt: a price-war round escapes to the
            # fresh-restart attempt in complete() instead of burning
            # the full attempt-1 budget first
            attempt1_budget = min(attempt1_budget, self.restart_budget)
        with span(
            "solve_launch", attempt="1", rows=int(plan_dev[0].shape[0])
        ):
            fut = _solve_mcmf(
                *dev_args,
                flow0_dev,
                eps_dev,
                *plan_dev,
                warm_p=self._prev_p if warm_p_ok else None,
                alpha=self.alpha,
                max_supersteps=attempt1_budget,
                telemetry_cap=tel_cap,
                use_warm_p=warm_p_ok,
                slot_stable=slot_stable,
                price_update_every=self.price_update_every,
                active_set=active_set,
            )
        cold = (np.zeros(m, dtype=np.int32), max(1, max_cost * n))
        rest = (dev_args, plan_dev, cold, tel_cap, warm, slot_stable, attempt1_budget, active_set)
        return (problem, fut, rest, resident)

    def _retry(self, attempt: str, rest, eps: int, budget: int):
        """One more attempt from zero flow, launched and awaited: a
        second `solve_launch` (the zero flow and eps go up inside it)
        and `solve_wait` pair, `attempt` in their args."""
        dev_args, plan_dev, (f0_cold, _eps_cold), tel_cap, _warm, slot_stable, _b, active_set = rest
        eps_host = np.int32(eps)
        with span("solve_launch", attempt=attempt, rows=int(plan_dev[0].shape[0])):
            self.last_h2d_bytes += f0_cold.nbytes + eps_host.nbytes
            out = _solve_mcmf(
                *dev_args,
                jnp.asarray(f0_cold),
                jnp.asarray(eps_host),
                *plan_dev,
                alpha=self.alpha,
                max_supersteps=budget,
                telemetry_cap=tel_cap,
                slot_stable=slot_stable,
                price_update_every=self.price_update_every,
                active_set=active_set,
            )
        return self._await(out, attempt, active_set)

    def _updates_fired(self, steps: int) -> int:
        """Global price updates of an attempt that ran `steps` supersteps
        at eps 1 from its first: one after every `price_update_every`-th."""
        return steps // self.price_update_every if self.price_update_every else 0

    def _await(self, out, attempt: str, active_set):
        """Block until one attempt's scalars are on the host
        (`solve_wait`: from here on the device has finished, so with
        `solve_launch`'s start it brackets the device's time). Returns
        (flow, p, steps, ok, p_overflow, sparse supersteps, telemetry
        ring); the supersteps that took the sparse form (0 where the
        plan is too small for one) and the ring are there or not."""
        with span("solve_wait", attempt=attempt) as sp:
            flow, p, steps, converged, p_overflow = out[:5]
            tail = list(out[5:])
            took = tail.pop(0) if active_set else None
            scalars = [np.asarray(x) for x in (steps, converged, p_overflow)]
            if took is not None:
                scalars.append(np.asarray(took))
            self.last_d2h_bytes += sum(x.nbytes for x in scalars)
            steps_i = int(scalars[0])
            sp.set("supersteps", steps_i)
        return (
            flow, p, steps_i, bool(scalars[1]), bool(scalars[2]),
            int(scalars[3]) if took is not None else 0, tail[0] if tail else None,
        )

    def complete(self, pending) -> FlowResult:
        """Synchronize a solve_async dispatch into a FlowResult. Inside
        the caller's `backend_solve`: `solve_wait` (and a `solve_launch`
        / `solve_wait` pair for each retry), `result_readback` (the
        telemetry ring and the flow come down: `last_d2h_bytes`),
        `result_unpack` (warm-state copies, objective, FlowResult)."""
        from ..obs import soltel

        problem, fut, rest, resident = pending
        if fut is None:
            self.last_telemetry = None
            self.last_sparse_supersteps = 0
            self.last_price_updates = 0
            return FlowResult(
                flow=np.zeros(len(problem.src), dtype=np.int64),  # kschedlint: host-only (FlowResult contract is int64)
                objective=0, iterations=0,
            )
        _dev_args, _plan_dev, (_f0_cold, eps_cold), tel_cap, warm, _slot_stable, attempt1_budget, active_set = rest

        flow, p, steps, converged, p_overflow, took, tel_buf = self._await(fut, "1", active_set)
        spent = steps  # device work across ALL attempts this solve
        spent_sparse = took
        updates = self._updates_fired(steps)
        warm_failed = warm and not (converged and not p_overflow)
        if warm_failed and not converged:
            # A warm attempt that exhausted its budget is a price war,
            # not a hard instance (the fresh restart below converges in
            # a superstep a hop): report it as a structured soltel event so
            # flight dumps distinguish it from genuine non-convergence.
            # A CONVERGED attempt that tripped the potential-overflow
            # guard still escapes below, but is NOT a price war — and
            # must not masquerade as one on the stall ring.
            soltel.warm_price_war(
                "jax",
                supersteps=steps,
                budget=attempt1_budget,
                escaped_to=(
                    "fresh_restart" if self.restart_budget is not None
                    else "cost_scaling"
                ),
                tel=(
                    soltel.decode(  # not counted in last_d2h_bytes: a failure's evidence
                        tel_buf, steps, tel_cap, "jax", attempt1_budget,
                        converged=False,
                        nodes=problem.num_nodes, arcs=len(problem.src),
                    )
                    if tel_buf is not None
                    else None
                ),
            )
        if warm_failed and self.restart_budget is not None:
            # Attempt 1b (restart escape): a warm attempt that blew its
            # budget re-solves FRESH — zero flow, tightened prices,
            # eps=1 — the few-superstep path on these graphs, instead
            # of the ~20k-superstep full cost-scaling below. Exact
            # either way; the cost-scaling attempt remains the backstop
            # for genuinely hard instances.
            flow, p, steps, converged, p_overflow, took, tel_buf = self._retry(
                "1b", rest, 1, min(4096, self.max_supersteps)
            )
            spent += steps
            spent_sparse += took
            updates += self._updates_fired(steps)
        if not (converged and not p_overflow):
            flow, p, steps, converged, p_overflow, took, tel_buf = self._retry(
                "cold", rest, eps_cold, self.max_supersteps
            )
            spent += steps
            spent_sparse += took
        # work accounting covers every attempt (a budget-blown warm
        # attempt's burn included) — the supersteps the DEVICE ran this
        # round, not just the attempt that won; telemetry decode below
        # stays attempt-local (the ring indexes the final attempt)
        self.last_supersteps = spent
        self.last_sparse_supersteps = spent_sparse
        self.last_price_updates = updates
        ok = converged and not p_overflow
        with span("result_readback") as sp:
            # the telemetry budget is the SOLVER's budget (max_supersteps),
            # not the warm attempt's internal 4096 cap: a warm solve that
            # converges near 4096 steps is escalated to the cold fallback,
            # not failed, so cap-proximity against the warm cap would be a
            # spurious stall event (and would spam the flight ring)
            self.last_telemetry = (
                soltel.decode(
                    tel_buf, steps, tel_cap, "jax", self.max_supersteps,
                    converged=ok,
                    nodes=problem.num_nodes, arcs=len(problem.src),
                )
                if tel_buf is not None
                else None
            )
            flow_np = np.asarray(flow) if ok else None  # fetched ONCE, for the decode
            down = (tel_buf.nbytes if tel_buf is not None else 0) + (
                flow_np.nbytes if ok else 0
            )
            self.last_d2h_bytes += down
            sp.set("bytes", down)
        if not ok:
            self.reset()  # never reuse the state that failed
        if p_overflow:
            raise OverflowError("push-relabel potentials approached int32 range")
        if not converged:
            # non-convergence now carries its interior evidence: the
            # stall detector's structured reason + the decoded ring
            # (the degradation ladder forwards both to flight dumps)
            tel = self.last_telemetry
            raise soltel.SolverStallError(
                f"push-relabel did not converge within {self.max_supersteps} supersteps; "
                "the flow problem may be infeasible (missing unscheduled-aggregator arcs?)",
                reason=soltel.detect_stall(tel) if tel is not None else None,
                telemetry=tel,
            )
        with span("result_unpack", arcs=len(flow_np)):
            if self.warm_start:
                self._prev = flow_np.astype(np.int32)
                # flow and potentials stay device-resident between rounds:
                # the next warm attempt consumes the handles directly
                # instead of re-uploading what the device just produced,
                # masked against THIS solve's endpoint buffers
                self._prev_dev = flow if resident else None
                self._prev_src_dev = problem.d_src if resident else None
                self._prev_dst_dev = problem.d_dst if resident else None
                # host-side endpoints at this (successful) solve, for the
                # non-resident warm mask; problem arrays are snapshots
                self._prev_src_host = np.asarray(problem.src, np.int32)
                self._prev_dst_host = np.asarray(problem.dst, np.int32)
                # endpoint key at this solve: the journal-scoped warm
                # policy compares the next round's key against it
                self._key_solved = getattr(problem, "plan_key", None)
                self._prev_p = p
            objective = int(
                (flow_np.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
            ) + lower_bound_cost(problem)
            return FlowResult(flow=flow_np.astype(np.int64), objective=objective, iterations=spent)  # kschedlint: host-only (FlowResult contract is int64)

    def solve(self, problem: FlowProblem) -> FlowResult:
        return self.complete(self.solve_async(problem))


# Level-3 registry ownership: the programs this module compiles
# (ksched_tpu/analysis/program_registry.py; audited by analysis/engine.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(
    __name__,
    "csr_solve", "csr_solve_warmp", "csr_solve_slot", "csr_refit_slot",
    "csr_solve_active",
    "stacked_solve", "stacked_solve_warmp",
)
