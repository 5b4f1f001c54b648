"""Slot-stable CSR plan: scatter-maintained entry layout for scan-CSR.

The scan-CSR solver (solver/jax_solver.py) needs its doubled residual
entries grouped per source node so segment reductions stay in
cumsum/gather/associative-scan form (no scatters on the solve path).
The original `build_csr_plan` derives that grouping by argsorting the
2M entries by endpoint every time any arc ENDPOINT changes — an
O(M log M) host pass plus a full plan re-upload per endpoint-churn
round, the last O(graph) cost on the event path after r11 made the
problem arrays delta-sized.

This module replaces the per-round rebuild with a MAINTAINED layout,
the same move `scheduler/bulk.py` makes by pre-wiring arc endpoints:

- every node owns a contiguous REGION of the entry table, sized to
  its degree high-water mark plus slack; segment-boundary tensors
  (`seg_start`/`is_start`/`node_first`/`node_last`/`node_nonempty`)
  therefore change only when a region MOVES (relocation, below) —
  ordinary endpoint churn never touches them;
- each live arc slot owns two plan rows (forward entry in its src's
  region, backward in its dst's region), assigned when the slot's
  endpoints are set and freed when the arc is removed. Within a
  region, forward rows fill from the FRONT and backward rows from
  the BACK — a load-bearing invariant, not bookkeeping taste: the
  discharge allocates each node's excess over its admissible entries
  front-to-back, and backward rows ahead of forward ones soak pushes
  into bounce-back moves (measured: interleaved wiring order drove
  fresh-restart supersteps 10 → 17-23 within six churn rounds;
  restoring the split restores ~10). Liveness is encoded in the sign
  column (`p_sign` in {+1, -1, 0}): a dead row has sign 0 and the
  solver's slot-stable residual formula makes it contribute nothing
  to any reduction — no separate mask tensor, no extra gathers;
- an endpoint change within existing slots (slot recycle — the churn
  workload's task-completion/arrival dance) mutates O(1) plan rows,
  journaled as dirty positions and shipped as pow2-padded int32
  records applied by ONE jit'd scatter (`plan_apply_fn`, the second
  and last scoped scatter exemption after the problem-delta apply);
- the host mirror of the plan tensors is maintained in place, so the
  "full-rebuild" path is a straight re-upload of the same values the
  scatter path maintains incrementally — which is what makes
  scatter-vs-rebuild parity assertable bit-for-bit (flows,
  supersteps, telemetry rows), and what keeps the sync / pipelined /
  device-resident service loops placement-identical;
- host argsort + full plan re-upload survive ONLY on `full_build`
  (slot table reassigned), pow2 bucket growth (n_cap/m_cap), and
  tail-pool exhaustion — all counted on `layout_rebuilds`;
- regions are sized by a per-node-id degree HIGH-WATER MARK that
  persists across layouts, not by the instantaneous degree. Node ids
  are recycled (flowgraph.py free-list), and the recycled id's new
  tenant routinely needs more rows than the old one held at layout
  time — a completed (bound) task carries ~2 arcs while the arriving
  task that inherits its id wires a full preference set. Sizing by
  current degree alone makes that mismatch overflow a region EVERY
  churn round (measured: 24/24 bench rounds degenerated to layout
  rebuilds); with the high-water mark each id overflows at most when
  it sets a new degree record;
- on top of the high-water mark, active nodes get slack headroom
  (+2 rows plus 25% of the mark, granted whole in descending
  churn × region-size order — the weight is the expected relocation
  cost saved), funded strictly from the pow2 surplus the entry
  table already carries — `entry_cap` never grows past the bare-hwm
  sizing, so solver cost is untouched. This matters because
  aggregator occupancy (EQUIV_CLASS / PU / machine nodes)
  random-walks under churn: somewhere in the fleet a node beats its
  record by +1 nearly every round (measured: bare-hwm sizing still
  rebuilt every other round, one fresh record-setter per rebuild),
  and exact-mark regions turn every record into a rebuild. The mark
  DECAYS toward the instantaneous degree at each rebuild (halving
  the excess), so one fill-time spike cannot inflate the entry
  budget forever;
- a node that out-churns its region anyway is RELOCATED, not
  rebuilt around: the surplus left after slack grants stays past
  the packed spans as a shared TAIL POOL, and `_relocate` moves the
  node's live rows into a grown (1.25x) region — best-fit from the
  dead-span list (returned spans coalesce with neighbours and the
  tail frontier, and loose fits split, so churn cannot shred the
  arena), else fresh tail — in O(degree) host writes, journaled
  through the same per-round scatter as ordinary endpoint churn
  (the segment-boundary tensors gain their own record stream:
  relocation rewires `seg_start`/`is_start` rows for the new span
  and the node's `node_first`/`node_last`/`node_nonempty` entries;
  the abandoned span keeps its — now all-dead — segment structure,
  which no reduction ever samples);
- fresh regions (an id with no history: node ids are recycled, so
  the per-round EPHEMERAL aggregators — born, grown to full size,
  drained, freed — reappear under a different id every round) are
  sized by the node TYPE's degree record (reset per rebuild — the
  fill-time giants must not ghost-poison it), capped by pool health,
  so they claim one right-sized span instead of laddering 4→8→…→64
  through the pool; a node that empties returns a BIG span to the
  pool (the dying aggregator funds its successor) while small spans
  stay attached to the id as recycle insurance — the next tenant of
  a completed task's id refills in place, zero relocations, zero
  journal bytes. A full layout rebuild therefore survives ONLY
  full_build, pow2 bucket growth, and tail-pool exhaustion
  (`region_overflows`, the rare compaction case);
- the entry budget RE-FITS to the graph it holds. A layout built
  while a fill round's transient arcs were live (every admitted pod
  carries two or three pending arcs; a round later it is pinned and
  keeps one) is sized for rows that are dead for good once the round
  has been applied, and every superstep gathers and scans dead rows
  like live ones. At the END of each round the owner asks
  `refit_due()` (microseconds: the rows in use are twice the live
  arcs): is there a smaller bucket that holds the graph as it stands
  with room to drift, a quarter more rows and the arena's sixteenth
  (`refit_bucket`)? `refit()` then re-lays out from the LIVE degree
  (the marks are set to it, not decayed halfway: the spike is over),
  slack and arena funded from the bucket's surplus as in any rebuild.
  `2 * m_cap` is what a BUILD allows for (a first build, and one that
  `m_cap` / `n_cap` growth forced: room for every slot the arc table
  can hand out, so a fill grows the plan only with the table), not a
  floor: the arc table never shrinks, and a re-fit goes below twice
  it when the fill that sized it is over (nothing in the solve
  relates E to m: the `[2m]` `inv_order` is read at E indices, the
  `[E]` tables at m indices). Going down is stricter than staying: a
  plan grows when less than a sixteenth of it is left (93.75% full)
  and goes down only to a bucket it reaches at most ~75% full, so
  rows that hover about a bucket's edge take it across neither way.
  Rows that SWING by more than that margin (an equivalence class
  purged in one round, re-listed rounds later) do take it down at a
  trough, and back up if the peak returns: that is the back-off's
  case below, and both extents' programs exist by then.
  A rebuild that is no re-fit (an arena overflow) never lowers
  `entry_cap`: a smaller bucket met inside a round is a program
  nobody has run. A re-fit that growth undoes (an overflow or
  `m_cap` growth that raises `entry_cap` again) doubles the rounds
  the next one waits, so a workload whose peaks do not fit the
  smaller bucket pays a bounded number of re-layouts and settles at
  the larger. The lifecycle is
  build -> re-fit -> (relocate | overflow | grow).

Entry position 0 is permanently reserved and dead: freed slots'
`inv_order` rows are parked there, so a stale slot can never alias a
live row's push allocation.

SHARDED layout mode (``enable_sharding(D)``, the multi-chip rung —
parallel/sharded_solver.py): the table is laid out as D equal-extent
per-shard BLOCKS, block d holding exactly the regions of the nodes
shard d owns (``shard_owner``'s contiguous id ranges — and regions
were ALWAYS allocated in node-id order, so this is the same layout
with per-block bases). Each block reserves its local position 0 as a
per-shard dead slot and keeps its own tail arena + dead-span list, so
relocation traffic stays owner-local and the maintained entry tensors
reshape losslessly to ``[D, E/D]`` stacked per-shard tables — the
sharded solver's plan IS the reshaped global plan, no second
allocator, no drift. Entry order within every node's region is
unchanged, so a single-chip consumer of the same plan (the jax
ladder rung below the sharded one) solves bit-identically to the
unsharded layout.
"""

from __future__ import annotations

import functools
import heapq
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs.spans import span
from ..utils import next_pow2

#: int32 columns of one packed plan-row record:
#: (position, arc slot, sign, src, dst)
PLAN_RECORD_COLS = 5
#: int32 columns of one packed inv-order record: (entry index, position)
INV_RECORD_COLS = 2
#: int32 columns of one packed segment-static record (relocations):
#: (position, seg_start value, is_start flag)
SEG_RECORD_COLS = 3
#: int32 columns of one packed node-static record (relocations):
#: (node, node_first, node_last, node_nonempty flag)
NODE_RECORD_COLS = 4
#: the record streams of `plan_apply_fn`, in argument order
PLAN_STREAM_COLS = (
    PLAN_RECORD_COLS, INV_RECORD_COLS, SEG_RECORD_COLS, NODE_RECORD_COLS,
)


def _pad_records(*counts: int) -> int:
    from .device_export import pad_record_count

    return pad_record_count(*counts)


def shard_owner(node_ids, num_nodes: int, num_shards: int) -> np.ndarray:
    """Owner shard per node id: contiguous range partition, so resource
    subtrees laid out contiguously stay on one shard. The SAME
    arithmetic the sharded solve kernel re-derives from iota on device
    (parallel/sharded_solver.py re-exports this as ``node_owner``) —
    one source of truth for who owns what."""
    per = -(-num_nodes // max(num_shards, 1))
    return np.minimum(np.asarray(node_ids) // per, num_shards - 1)


def entry_bucket(need: int, floor: int = 0) -> int:
    """The entry-table extent of an unsharded layout that must house
    `need` rows: the pow2 above it, at least `floor`, and one bucket
    more when less than a sixteenth of it (the relocation arena) would
    be left. The floor is the caller's (`SlotPlanState._rebuild`):
    `2 * m_cap` for a first build and for one that `m_cap` / `n_cap`
    growth forced, the extent the plan has for any later rebuild, none
    for the bucket a re-fit goes down to (`refit_bucket`)."""
    cap = max(floor, next_pow2(need))
    if cap - need < max(64, cap >> 4):
        cap = max(floor, next_pow2(need + max(64, cap >> 4)))
    return cap


def refit_bucket(need: int) -> int:
    """The extent a re-fit may take a plan of `need` live rows DOWN to:
    the bucket that houses them with a quarter more (the drift room a
    node's region is granted, `hwm >> 2`) and the arena's sixteenth, so
    at most three quarters full on arrival, where a plan grows at
    fifteen sixteenths. Going down is stricter than staying: rows that
    hover about a bucket's edge never take the plan across it twice."""
    return entry_bucket(need + (need >> 2))


_PLAN_APPLY = None


def plan_apply_fn():
    """The SECOND (and last) scoped scatter exemption of the solver
    stack: applies a round's packed plan-row + inv-order + segment-
    static + node-static records to the persistent device plan
    tensors. Like the problem-delta apply
    (graph/device_export.delta_apply_fn) it is O(records), runs once
    per round, and is pinned by the jaxpr contracts: the exemption is
    non-vacuous (it really scatters), 32-bit, and hash-stable within a
    pow2 record bucket. Records are padded by repeating a real row
    (idempotent duplicates), and the host coalesces multiple writes to
    one position before packing, so scatter ordering can never matter.

    The segment/node statics ride the same program (not a third
    exemption): on ordinary endpoint-churn rounds their record
    streams are empty pads (an idempotent rewrite of the permanently
    dead position 0 / node 0's current meta); they carry real dirt
    only when a region RELOCATION moved a node's rows into the tail
    pool (module docstring).
    """
    global _PLAN_APPLY
    if _PLAN_APPLY is None:
        import jax

        # All ten plan tensors are DONATED: the scatter updates the
        # persistent buffers in place.
        @functools.partial(
            jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9)  # kschedlint: program=plan_apply
        )
        def _apply_plan(
            p_arc, p_sign, p_src, p_dst, inv_order,
            seg_start, is_start, node_first, node_last, node_nonempty,
            row_rec, inv_rec, seg_rec, node_rec,
        ):
            pos = row_rec[:, 0]
            p_arc = p_arc.at[pos].set(row_rec[:, 1])
            p_sign = p_sign.at[pos].set(row_rec[:, 2])
            p_src = p_src.at[pos].set(row_rec[:, 3])
            p_dst = p_dst.at[pos].set(row_rec[:, 4])
            inv_order = inv_order.at[inv_rec[:, 0]].set(inv_rec[:, 1])
            spos = seg_rec[:, 0]
            seg_start = seg_start.at[spos].set(seg_rec[:, 1])
            is_start = is_start.at[spos].set(seg_rec[:, 2] != 0)
            nid = node_rec[:, 0]
            node_first = node_first.at[nid].set(node_rec[:, 1])
            node_last = node_last.at[nid].set(node_rec[:, 2])
            node_nonempty = node_nonempty.at[nid].set(node_rec[:, 3] != 0)
            return (
                p_arc, p_sign, p_src, p_dst, inv_order,
                seg_start, is_start, node_first, node_last, node_nonempty,
            )

        _PLAN_APPLY = _apply_plan
    return _PLAN_APPLY


class SlotPlanState:
    """Maintained slot-stable plan over a DeviceGraphState's arc slots.

    Created as an inert shell on every DeviceGraphState; it costs
    nothing until a slot-stable consumer (JaxSolver) calls
    ``ensure_built()``, which flips ``enabled`` and builds the first
    layout. From then on the DeviceGraphState's ``_set_arc`` hooks
    keep it in sync per mutation (O(1) each), and the device-resident
    mirror drains ``drain_records()`` once per round.
    """

    def __init__(self, state) -> None:
        self.state = state  # owning DeviceGraphState
        self.enabled = False
        self.needs_rebuild = True
        self.layout_gen = 0  # bumped per layout (re)build
        self.value_version = 0  # bumped per mutation batch and rebuild
        self.static_version = 0  # bumped per relocation and rebuild
        self.layout_rebuilds = 0  # full rebuilds (telemetry)
        self.region_overflows = 0  # rebuilds forced by tail-pool exhaustion
        self.region_relocations = 0  # regions moved to the tail pool
        self.refits = 0  # rebuilds that took a smaller bucket (refit)
        self.regrowths = 0  # rebuilds that raised entry_cap
        #: end-of-round tests a re-fit waits for, and those seen since
        #: the layout was built or grew back; the wait doubles each
        #: time growth undoes a re-fit (`_refit_standing`)
        self._refit_wait = 1
        self._refit_idle = 0
        self._refit_standing = False
        self._refit_asked = False  # the next build is a re-fit (refit)
        # ---- layout (static per layout_gen) --------------------------
        self.entry_cap = 0  # E: padded entry-table extent
        self.region_start: Optional[np.ndarray] = None  # int32[n_cap]
        self.region_cap: Optional[np.ndarray] = None  # int32[n_cap]
        self.seg_start: Optional[np.ndarray] = None  # int32[E]
        self.is_start: Optional[np.ndarray] = None  # bool[E]
        self.node_first: Optional[np.ndarray] = None  # int32[n_cap]
        self.node_last: Optional[np.ndarray] = None  # int32[n_cap]
        self.node_nonempty: Optional[np.ndarray] = None  # bool[n_cap]
        # ---- values (scatter-maintained) -----------------------------
        self.p_arc: Optional[np.ndarray] = None  # int32[E]
        self.p_sign: Optional[np.ndarray] = None  # int32[E] {+1,-1,0}
        self.p_src: Optional[np.ndarray] = None  # int32[E]
        self.p_dst: Optional[np.ndarray] = None  # int32[E]
        self.inv_order: Optional[np.ndarray] = None  # int32[2*m_cap]
        self.pos_fwd: Optional[np.ndarray] = None  # int32[m_cap], -1 unassigned
        self.pos_bwd: Optional[np.ndarray] = None  # int32[m_cap]
        # ---- allocation state ----------------------------------------
        #: forward-row frontier (ascends from region start) and
        #: backward-row frontier (descends from region end) — forward
        #: rows fill the front, backward rows the back (load-bearing;
        #: see _rebuild)
        self._next_seq: Optional[np.ndarray] = None  # int64[n_cap]
        self._next_back: Optional[np.ndarray] = None  # int64[n_cap]
        self._freed_f: Dict[int, List[int]] = {}  # node -> min-heap (fwd side)
        self._freed_b: Dict[int, List[int]] = {}  # node -> max-heap, negated (bwd side)
        #: live rows currently in each node's region, and the max ever
        #: seen per node id (region sizing input — survives rebuilds;
        #: see the module docstring's recycled-id rationale)
        self._occ: Optional[np.ndarray] = None  # int64[n_cap]
        self._deg_hwm = np.zeros(0, np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        #: max degree ever seen per node TYPE — sizes the first span of
        #: a fresh region, where the id has no history (see _rebuild)
        self._type_hwm: Dict[int, int] = {}
        #: cumulative alloc/release events per node id — the slack
        #: rationing weight (churn-hot nodes get headroom first);
        #: persists across rebuilds like the high-water mark
        self._churn_ct = np.zeros(0, np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        #: first unassigned tail-pool position PER SHARD BLOCK
        #: (relocation arena; one block covering the whole table in
        #: the default single-shard layout)
        self._tail_next = np.zeros(1, np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        #: abandoned (start, cap) spans per shard block — relocation
        #: reuses them best-fit before carving fresh tail, so moves
        #: don't leak
        self._dead_spans: List[List[Tuple[int, int]]] = [[]]
        #: sharded layout mode (enable_sharding): block count, equal
        #: per-block extent (== entry_cap when unsharded), and the
        #: node -> owner-shard map of the current layout
        self._num_shards = 1
        self.block_extent = 0
        self._owner = np.zeros(0, np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        # ---- dirty journal (for the device scatter) ------------------
        self._dirty_pos: set = set()
        self._dirty_inv: set = set()
        self._dirty_seg: set = set()  # relocated segment statics
        self._dirty_node: set = set()  # relocated node statics
        # ---- device caches (non-resident full-upload path) -----------
        self._static_dev: Optional[Tuple] = None  # (layout_gen, tensors)
        self._values_dev: Optional[Tuple] = None  # (layout_gen, version, tensors)
        #: exact bytes the last device_args() shipped: the value tensors
        #: and, after a relocation, the boundary tensors; 0 on a clean
        #: round that re-used both cached uploads
        self.last_ship_bytes = 0

    # -- pickling (the warm-restore manifest, runtime/checkpoint.py) -------

    def __getstate__(self):
        # the device caches hold live jax buffers; they are rebuilt on
        # first use in the restored process
        state = dict(self.__dict__)
        state["_static_dev"] = None
        state["_values_dev"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)

    # -- lifecycle ---------------------------------------------------------

    def invalidate(self) -> None:
        """Layout is stale (full_build / pow2 growth / region
        overflow): the next consumer rebuilds from the arrays.
        Mutation hooks no-op until then — the rebuild reads final
        state, so per-entry dirt in between is noise."""
        self.needs_rebuild = True
        self._dirty_pos.clear()
        self._dirty_inv.clear()
        self._dirty_seg.clear()
        self._dirty_node.clear()

    def ensure_built(self) -> None:
        self.enabled = True
        if self.needs_rebuild:
            self._rebuild()

    def enable_sharding(self, num_shards: int) -> None:
        """Switch every FUTURE layout to the per-shard block form (see
        the module docstring): block d holds the regions of exactly
        the nodes shard d owns, with a per-block reserved dead slot
        and a shard-local tail arena. Idempotent; a shard-count change
        invalidates the layout (the sharded solver owns exactly one
        mesh, so this fires once per process in practice)."""
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if num_shards != self._num_shards:
            self._num_shards = num_shards
            self.invalidate()

    # -- layout build ------------------------------------------------------

    def _rebuild(self) -> None:
        """Re-derive regions and entry placement from the current
        arrays (vectorized; the moral equivalent of build_csr_plan's
        argsort, run only on full_build / growth / overflow / re-fit;
        a re-fit sizes from the live degree alone: see `refit`)."""
        st = self.state
        n_cap, m_cap = st.n_cap, st.m_cap
        cap_before = self.entry_cap
        refit, self._refit_asked = self._refit_asked, False
        slots = np.fromiter(st._arc_slot.values(), np.int64, len(st._arc_slot))  # kschedlint: host-only (host layout build)
        slots.sort()
        src_l = st.src[slots].astype(np.int64)  # kschedlint: host-only (host layout build)
        dst_l = st.dst[slots].astype(np.int64)  # kschedlint: host-only (host layout build)
        deg = np.bincount(src_l, minlength=n_cap) + np.bincount(dst_l, minlength=n_cap)
        # region sizing: the per-id degree high-water mark (so a
        # recycled id can re-house its historical max — see the module
        # docstring), + 1 slack row for every node that ever held rows,
        # then the surplus up to the pow2 entry budget distributed
        # proportionally to degree (hubs absorb churn; pos 0 reserved)
        if len(self._deg_hwm) < n_cap:
            self._deg_hwm = np.concatenate([
                self._deg_hwm,
                np.zeros(n_cap - len(self._deg_hwm), np.int64),  # kschedlint: host-only (host allocation bookkeeping)
            ])
        if len(self._churn_ct) < n_cap:
            self._churn_ct = np.concatenate([
                self._churn_ct,
                np.zeros(n_cap - len(self._churn_ct), np.int64),  # kschedlint: host-only (host allocation bookkeeping)
            ])
        # decay the mark halfway toward the instantaneous degree (the
        # fill-time spike of a since-bound task, or a recycled id's
        # past big tenant, must not inflate the entry budget forever);
        # the type-hinted relocation path catches whoever decays too
        # far. A re-fit has decided that the spike is over: it drops
        # the marks to the live degree at once
        hwm = deg.copy() if refit else np.maximum(deg, (self._deg_hwm[:n_cap] + deg + 1) // 2)
        self._deg_hwm = hwm
        # RESET the per-TYPE degree records to the live peak (fresh-
        # region sizing hints: an id never predicts its next tenant —
        # ephemeral aggregators are reborn each round under a recycled
        # id — but the TYPE's record does). Reset, not accumulate: the
        # fill-time cluster aggregator leaves a ~N-degree ghost record
        # on its type that would poison every later fresh claim
        nt = self.state.node_type[:n_cap].astype(np.int64)  # kschedlint: host-only (host layout build)
        self._type_hwm = {
            int(t): int(deg[nt == t].max()) for t in np.unique(nt[deg > 0])
        }
        self._occ = deg.astype(np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        # regions are sized to the mark EXACTLY: a node allocating past
        # its historical max is the record-setter case, and relocation
        # (not a pre-paid spare row for every node in the cluster — a
        # ~25%-of-table tax at production fill) is the designed path
        base = hwm.copy()
        churn = self._churn_ct[:n_cap]
        active = hwm > 0
        # slack headroom (module docstring): an active node wants a
        # flat +2 (the ±2 occupancy jump a task binding makes in one
        # round) plus 25% of its mark (drift room for the big
        # aggregators), granted whole from the pow2 surplus in
        # descending churn × region-size order — the weight is the
        # expected relocation COST saved, so a slowly-growing hub
        # outranks a small id that recycles often. A tail-pool FLOOR
        # is reserved before any grant: whatever the grants leave (and
        # at least the floor) stays contiguous past the packed spans
        # as the relocation arena (per shard block in sharded mode).
        want = np.where(active, 2 + (hwm >> 2), 0)
        D = self._num_shards
        if D == 1:
            owner = np.zeros(n_cap, np.int64)  # kschedlint: host-only (host layout build)
            need = 1 + int(base.sum())
            # the pow2 above `need`, with the relocation arena
            # guaranteed (entry_bucket): at least `2 * m_cap` for a
            # first build and one that `m_cap` / `n_cap` growth forced
            # (the plan's own arrays say under which caps it was laid
            # out), at least the extent it has for any later rebuild;
            # only a re-fit goes down (module docstring)
            grew = (
                self.inv_order is None
                or len(self.inv_order) != 2 * m_cap
                or len(self.region_start) != n_cap
            )
            self.entry_cap = entry_bucket(need, 2 * m_cap if grew else cap_before)
            if refit and not grew:
                self.entry_cap = min(self.entry_cap, refit_bucket(need))
            surplus = self.entry_cap - need
            grantable = max(surplus - max(64, self.entry_cap >> 4), 0)
            slack = want
            if int(want.sum()) > grantable:
                order = np.argsort(-(churn * (hwm + 1)), kind="stable")
                fits = np.cumsum(want[order]) <= grantable
                slack = np.zeros_like(want)
                slack[order[fits]] = want[order[fits]]
            self.block_extent = self.entry_cap
        else:
            # sharded layout: equal-extent per-shard blocks, each with
            # its own reserved dead slot (local 0), packed regions, and
            # tail arena. The block extent is sized for the DENSEST
            # shard with full slack wants, floored at (2*m_cap)/D —
            # the pow2-bucket common case the jaxpr contracts pin
            # (sharded_entry_extent in parallel/sharded_solver.py)
            owner = shard_owner(np.arange(n_cap), n_cap, D)
            full = (base + want).astype(np.int64)  # kschedlint: host-only (host layout build)
            shard_need = np.bincount(owner, weights=full, minlength=D).astype(np.int64) + 1  # kschedlint: host-only (host layout build)
            max_need = int(shard_need.max())
            Es = next_pow2(max_need)
            if Es - max_need < max(64, Es >> 4):
                Es = next_pow2(max_need + max(64, Es >> 4))
            if (2 * m_cap) % D == 0:
                Es = max(Es, (2 * m_cap) // D)
            self.block_extent = Es
            self.entry_cap = D * Es
            base_sum = np.bincount(owner, weights=base.astype(np.float64), minlength=D).astype(np.int64)  # kschedlint: host-only (host layout build)
            slack = np.zeros_like(want)
            for d in range(D):
                sel = np.flatnonzero(owner == d)
                grantable = max(
                    int(Es - 1 - base_sum[d] - max(64, Es >> 4)), 0
                )
                wd = want[sel]
                if int(wd.sum()) <= grantable:
                    slack[sel] = wd
                else:
                    order = np.argsort(
                        -(churn[sel] * (hwm[sel] + 1)), kind="stable"
                    )
                    fits = np.cumsum(wd[order]) <= grantable
                    slack[sel[order[fits]]] = wd[order[fits]]
        caps = base + slack
        E = self.entry_cap
        Es = self.block_extent
        self._owner = owner
        start = np.empty(n_cap, np.int64)  # kschedlint: host-only (host layout build)
        seg = np.zeros(E, np.int32)
        isstart = np.zeros(E, bool)
        tail0 = np.zeros(D, np.int64)  # kschedlint: host-only (host allocation bookkeeping)
        for d in range(D):
            sel = np.flatnonzero(owner == d) if D > 1 else np.arange(n_cap)
            # each block's local position 0 is its reserved dead slot:
            # its own one-row segment, never allocated (global position
            # 0 keeps the historical reserved role on shard 0)
            seg[d * Es] = d * Es
            isstart[d * Es] = True
            if len(sel) == 0:
                # a shard can legitimately own zero nodes (D close to
                # or above n_cap: ceil-division ranges leave trailing
                # shards empty); its block is one dead slot + tail
                tail0[d] = d * Es + 1
                continue
            cd = caps[sel]
            sd = d * Es + 1 + np.concatenate(([0], np.cumsum(cd[:-1])))
            start[sel] = sd
            used_d = int(cd.sum())
            seg[d * Es + 1 : d * Es + 1 + used_d] = np.repeat(sd, cd).astype(np.int32)
            isstart[sd[cd > 0]] = True
            tail0[d] = d * Es + 1 + used_d
        self.region_start = start.astype(np.int32)
        self.region_cap = caps.astype(np.int32)
        self.node_first = np.minimum(start, E - 1).astype(np.int32)
        self.node_last = np.minimum(start + caps - 1, E - 1).astype(np.int32)
        self.node_nonempty = caps > 0
        self.seg_start = seg
        self.is_start = isstart
        # entry placement: within a region, forward entries (slot
        # ascending) at the FRONT and backward entries (slot
        # ascending) at the BACK, slack between. Live-row order
        # matches the stable argsort's fwd-then-bwd order (dead slack
        # rows between are inert), so the first layout after a build
        # is allocation-order identical to the legacy plan. The
        # fwd-front/bwd-back split is LOAD-BEARING for solve speed,
        # not cosmetics: the discharge allocates a node's excess over
        # its admissible entries front-to-back, and backward rows
        # sitting in front of forward ones soak pushes into
        # bounce-back moves (measured: interleaved wiring order drove
        # fresh-restart supersteps 10 -> 17-23 within six churn
        # rounds; separating the sides restores ~10, so the incre-
        # mentally maintained layout must preserve the split)
        counts_f = np.bincount(src_l, minlength=n_cap)
        cum_f = np.concatenate(([0], np.cumsum(counts_f)[:-1]))
        order_f = np.argsort(src_l, kind="stable")
        gsrc = src_l[order_f]
        rank_f = np.arange(len(slots), dtype=np.int64) - cum_f[gsrc]  # kschedlint: host-only (host layout build)
        pos_f = start[gsrc] + rank_f
        counts_b = np.bincount(dst_l, minlength=n_cap)
        cum_b = np.concatenate(([0], np.cumsum(counts_b)[:-1]))
        order_b = np.argsort(dst_l, kind="stable")
        gdst = dst_l[order_b]
        rank_b = np.arange(len(slots), dtype=np.int64) - cum_b[gdst]  # kschedlint: host-only (host layout build)
        pos_b = start[gdst] + caps[gdst] - counts_b[gdst] + rank_b
        self.p_arc = np.zeros(E, np.int32)
        self.p_sign = np.zeros(E, np.int32)
        self.p_src = np.zeros(E, np.int32)
        self.p_dst = np.zeros(E, np.int32)
        pf = np.full(m_cap, -1, np.int32)
        pb = np.full(m_cap, -1, np.int32)
        sf = slots[order_f]
        sb = slots[order_b]
        pf[sf] = pos_f
        pb[sb] = pos_b
        self.pos_fwd = pf
        self.pos_bwd = pb
        self.p_arc[pos_f] = sf
        self.p_sign[pos_f] = 1
        self.p_src[pos_f] = gsrc
        self.p_dst[pos_f] = st.dst[sf]
        self.p_arc[pos_b] = sb
        self.p_sign[pos_b] = -1
        self.p_src[pos_b] = gdst
        self.p_dst[pos_b] = st.src[sb]
        inv = np.zeros(2 * m_cap, np.int32)
        inv[sf] = pos_f
        inv[m_cap + sb] = pos_b
        self.inv_order = inv
        self._next_seq = start + counts_f
        self._next_back = start + caps - counts_b - 1
        self._freed_f = {}
        self._freed_b = {}
        self._tail_next = tail0
        self._dead_spans = [[] for _ in range(D)]
        self._dirty_pos.clear()
        self._dirty_inv.clear()
        self._dirty_seg.clear()
        self._dirty_node.clear()
        self.layout_gen += 1
        self.value_version += 1
        self.static_version += 1
        self.layout_rebuilds += 1
        self.needs_rebuild = False
        if refit and self.entry_cap < cap_before:
            self.refits += 1
            self._refit_standing = True
            return
        if cap_before and self.entry_cap > cap_before:
            self.regrowths += 1
        if refit or (self.entry_cap > cap_before and self._refit_standing):
            # growth undid a re-fit (the workload's peaks do not fit
            # the smaller bucket), or a re-fit found no smaller bucket
            # after all (the arc table grew under it): the next one
            # waits twice as long
            self._refit_standing = False
            self._refit_wait *= 2
            self._refit_idle = 0

    # -- re-fit (module docstring) -----------------------------------------

    @property
    def rows_live(self) -> int:
        """Plan rows in use: two for each arc the arrays hold
        (`_occ.sum()` of a built layout)."""
        return 2 * len(self.state._arc_slot)

    def refit_due(self, rows_live: int) -> bool:
        """The end-of-round test: is there a smaller bucket than
        `entry_cap` that holds a graph with `rows_live` rows in use (two
        for each arc of the graph as the round left it: the journal
        `apply` wrote is not in the arrays yet) with room to drift
        (`refit_bucket`)? One call a round (it is also the back-off's
        clock). The sharded layout is left as it is: its block extent
        follows the densest shard."""
        if not self.enabled or not self.entry_cap or self._num_shards != 1:
            return False
        self._refit_idle += 1
        if self._refit_idle < self._refit_wait:
            return False
        return refit_bucket(1 + rows_live) < self.entry_cap

    def refit(self) -> None:
        """Ask for a re-layout at the bucket `refit_due` found, in ONE
        step: the next build (the next export, once the journal is in
        the arrays) sizes its regions from the live degree, with the
        slack and the arena any rebuild grants from the bucket's
        surplus. A node that outgrows its region is relocated, as after
        any rebuild. A new `entry_cap` is a new solve program and new
        upload shapes: the caller runs them once before it lets the
        round go (FlowScheduler._refit_plan)."""
        self._refit_asked = True
        self.invalidate()

    # -- per-mutation hooks (called by DeviceGraphState._set_arc) ----------

    def _alloc(self, node: int, sign: int) -> int:
        """A free position in `node`'s region for a row of `sign` —
        forward rows fill from the region FRONT, backward rows from
        the BACK (the load-bearing split; see _rebuild). -1 when the
        region is full and the tail pool can't house a relocated
        one."""
        self._churn_ct[node] += 1  # failed attempts weigh in too
        nf = int(self._next_seq[node])
        nb = int(self._next_back[node])
        if sign > 0:
            h = self._freed_f.get(node)
            if h and (nf > nb or h[0] < nf):
                pos = heapq.heappop(h)
            elif nf <= nb:
                self._next_seq[node] = nf + 1
                pos = nf
            else:
                if not self._relocate(node):
                    return -1
                return self._alloc(node, sign)
        else:
            h = self._freed_b.get(node)
            if h and (nb < nf or -h[0] > nb):
                pos = -heapq.heappop(h)
            elif nb >= nf:
                self._next_back[node] = nb - 1
                pos = nb
            else:
                if not self._relocate(node):
                    return -1
                return self._alloc(node, sign)
        occ = int(self._occ[node]) + 1
        self._occ[node] = occ
        if occ > self._deg_hwm[node]:
            self._deg_hwm[node] = occ
        t = int(self.state.node_type[node])
        if occ > self._type_hwm.get(t, 0):
            self._type_hwm[t] = occ
        return pos

    def _release(self, node: int, pos: int, sign: int) -> None:
        occ = int(self._occ[node]) - 1
        self._occ[node] = occ
        self._churn_ct[node] += 1
        if occ == 0:
            # an emptied node returns a BIG span to the pool: the
            # per-round ephemeral aggregators (born, grown to full
            # size, and drained under a different recycled id every
            # round) would otherwise strand a full-size region per
            # round and bleed the pool dry. SMALL spans stay attached
            # to the id as recycle insurance — the next tenant of a
            # completed task's id refills a task-shaped arc set in
            # place, costing zero relocations and zero journal bytes
            start = int(self.region_start[node])
            cap = int(self.region_cap[node])
            if cap > 16:
                self._return_span(start, cap)
                self.region_cap[node] = 0
                self._next_seq[node] = start
                self._next_back[node] = start - 1
                self._freed_f.pop(node, None)
                self._freed_b.pop(node, None)
                if self.node_nonempty[node]:
                    self.node_nonempty[node] = False
                    self._dirty_node.add(node)
                self.value_version += 1
                self.static_version += 1
            else:
                # keep the span; reset the frontiers once empty so the
                # next tenant fills it front/back from scratch
                self._next_seq[node] = start
                self._next_back[node] = start + cap - 1
                self._freed_f.pop(node, None)
                self._freed_b.pop(node, None)
        elif sign > 0:
            heapq.heappush(self._freed_f.setdefault(node, []), pos)
        else:
            heapq.heappush(self._freed_b.setdefault(node, []), -pos)

    def _return_span(self, start: int, cap: int) -> None:
        """Give a span back to its owner block's arena, coalescing with
        adjacent dead spans and with the tail frontier — relocation
        churn must not shred the pool into unusable slivers (measured:
        ~90 abandoned 2-4 row fragments starving 6-row claims). A span
        never straddles a block boundary by construction."""
        d = start // self.block_extent if self.block_extent else 0
        spans = self._dead_spans[d]
        merged = True
        while merged:
            merged = False
            for i, (s0, c0) in enumerate(spans):
                if s0 + c0 == start:
                    start, cap = s0, c0 + cap
                    spans.pop(i)
                    merged = True
                    break
                if start + cap == s0:
                    cap += c0
                    spans.pop(i)
                    merged = True
                    break
        if start + cap == self._tail_next[d]:
            self._tail_next[d] = start
        else:
            spans.append((start, cap))

    def _claim_span(self, k: int, shard: int = 0) -> Optional[Tuple[int, int]]:
        """A (start, cap) span of >= k rows in `shard`'s block for a
        relocated region: best-fit from the block's dead-span list
        (split when the fit is loose — the remainder stays claimable),
        else fresh tail. None when neither fits."""
        spans = self._dead_spans[shard]
        best = -1
        for i, (_s0, c0) in enumerate(spans):
            if c0 >= k and (best < 0 or c0 < spans[best][1]):
                best = i
        if best >= 0:
            s0, c0 = spans.pop(best)
            if c0 - k >= 8:
                spans.append((s0 + k, c0 - k))
                return (s0, k)
            return (s0, c0)
        limit = (shard + 1) * self.block_extent
        if self._tail_next[shard] + k <= limit:
            s0 = int(self._tail_next[shard])
            self._tail_next[shard] += k
            return (s0, k)
        return None

    def _relocate(self, node: int) -> bool:
        """Move `node`'s live rows into a doubled region carved from
        the tail pool, preserving their relative order. O(degree) host
        writes, all journaled: the moved value rows and freshly dead
        old rows ride the ordinary row records, the new span's segment
        statics and the node's boundary statics ride the seg/node
        record streams. The abandoned span keeps its (all-dead)
        segment structure — no reduction samples a span outside every
        node's `node_first..node_last`. False iff the pool is spent."""
        old_start = int(self.region_start[node])
        old_cap = int(self.region_cap[node])
        occ = int(self._occ[node])
        shard = int(self._owner[node]) if len(self._owner) > node else 0
        # 1.25x growth: big aggregator regions dominate pool traffic,
        # and doubling a 70-row region for a +1 record wastes half the
        # arena; a quarter-step still amortizes the move count
        want = max(old_cap + max(old_cap >> 2, 2), occ + 2, 4)
        if old_cap == 0:
            # fresh region: the id's TYPE already names the NEW tenant
            # (nodes are typed before arcs wire), so its degree record
            # sizes the span — an ephemeral aggregator reborn on a
            # recycled task id claims its full span at once instead of
            # laddering 4→8→…→64 through the pool. The id's own mark
            # folds in as a floor, and the whole hint is capped by
            # pool health so a poisoned type record (types can mix
            # giants with minnows) can't let a few fresh claims drain
            # the arena.
            pool_left = int(
                (shard + 1) * self.block_extent - self._tail_next[shard]
            ) + sum(c for _, c in self._dead_spans[shard])
            rec = max(
                self._type_hwm.get(int(self.state.node_type[node]), 0),
                int(self._deg_hwm[node]),
            )
            hint = rec + max(2, rec >> 3)  # drift margin atop the record
            want = max(want, min(hint, max(pool_left >> 1, 8)))
        placed = self._claim_span(want, shard)
        if placed is None:
            # doubling doesn't fit — a minimal region still beats a
            # full layout rebuild
            placed = self._claim_span(max(occ + 2, 4), shard)
        if placed is None:
            return False
        new_start, new_cap = placed
        if old_cap > 0:
            self._return_span(old_start, old_cap)
        m_cap = self.state.m_cap
        # forward rows (relative order kept) to the FRONT of the new
        # span, backward rows to the BACK — the load-bearing split
        # (see _rebuild) survives every move
        rows = [
            (pos, int(self.p_sign[pos]))
            for pos in range(old_start, old_start + old_cap)
            if self.p_sign[pos] != 0
        ]
        n_bwd = sum(1 for _, sign in rows if sign < 0)
        wf = new_start
        wb = new_start + new_cap - n_bwd
        for pos, sign in rows:
            slot = int(self.p_arc[pos])
            if sign > 0:
                w = wf
                wf += 1
            else:
                w = wb
                wb += 1
            self._write_row(
                w, slot, sign, int(self.p_src[pos]), int(self.p_dst[pos])
            )
            if sign > 0:
                self.pos_fwd[slot] = w
                self.inv_order[slot] = w
                self._dirty_inv.add(slot)
            else:
                self.pos_bwd[slot] = w
                self.inv_order[m_cap + slot] = w
                self._dirty_inv.add(m_cap + slot)
            self._write_row(pos, 0, 0, 0, 0)
        self.region_start[node] = new_start
        self.region_cap[node] = new_cap
        self.node_first[node] = new_start
        self.node_last[node] = new_start + new_cap - 1
        self.node_nonempty[node] = True
        self._dirty_node.add(node)
        for pos in range(new_start, new_start + new_cap):
            self.seg_start[pos] = new_start
            self.is_start[pos] = pos == new_start
            self._dirty_seg.add(pos)
        self._next_seq[node] = wf
        self._next_back[node] = new_start + new_cap - n_bwd - 1
        self._freed_f[node] = []
        self._freed_b[node] = []
        self.value_version += 1
        self.static_version += 1
        self.region_relocations += 1
        return True

    def _overflow(self) -> None:
        self.region_overflows += 1
        self.invalidate()

    def _write_row(self, pos: int, arc: int, sign: int, src: int, dst: int) -> None:
        self.p_arc[pos] = arc
        self.p_sign[pos] = sign
        self.p_src[pos] = src
        self.p_dst[pos] = dst
        self._dirty_pos.add(pos)

    def slot_assigned(self, slot: int, src: int, dst: int) -> None:
        """A slot gained endpoints (new arc, or a recycled slot re-wired
        to a different (src, dst)): wire its two plan rows."""
        if not self.enabled or self.needs_rebuild:
            return
        pf = self._alloc(src, 1)
        if pf < 0:
            self._overflow()
            return
        pb = self._alloc(dst, -1)
        if pb < 0:
            self._release(src, pf, 1)
            self._overflow()
            return
        m_cap = self.state.m_cap
        self._write_row(pf, slot, 1, src, dst)
        self._write_row(pb, slot, -1, dst, src)
        self.pos_fwd[slot] = pf
        self.pos_bwd[slot] = pb
        self.inv_order[slot] = pf
        self.inv_order[m_cap + slot] = pb
        self._dirty_inv.add(slot)
        self._dirty_inv.add(m_cap + slot)
        self.value_version += 1

    def slot_freed(self, slot: int, src: int, dst: int) -> None:
        """The arc in `slot` was removed: kill its plan rows (sign 0 ⇒
        inert in every reduction) and park its inv entries on the
        reserved dead position 0 so a later recycling of the row can
        never alias this slot's flow update."""
        if not self.enabled or self.needs_rebuild:
            return
        pf = int(self.pos_fwd[slot])
        pb = int(self.pos_bwd[slot])
        if pf < 0:  # pragma: no cover - defensive (never assigned)
            return
        m_cap = self.state.m_cap
        self._write_row(pf, 0, 0, 0, 0)
        self._write_row(pb, 0, 0, 0, 0)
        self._release(src, pf, 1)
        self._release(dst, pb, -1)
        self.pos_fwd[slot] = -1
        self.pos_bwd[slot] = -1
        self.inv_order[slot] = 0
        self.inv_order[m_cap + slot] = 0
        self._dirty_inv.add(slot)
        self._dirty_inv.add(m_cap + slot)
        self.value_version += 1

    # -- record packing (device-resident scatter path) ---------------------

    @property
    def has_pending(self) -> bool:
        return bool(
            self._dirty_pos or self._dirty_inv
            or self._dirty_seg or self._dirty_node
        )

    def record_bucket(self) -> int:
        """The joint pow2 bucket `drain_records` would pad the pending
        dirt to: the one number that picks `plan_apply_fn`'s shape."""
        return _pad_records(
            len(self._dirty_pos), len(self._dirty_inv),
            len(self._dirty_seg), len(self._dirty_node),
        )

    def drain_records(
        self,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Pack the dirty plan rows / inv entries / relocated segment
        and node statics into int32 records, all four streams padded
        to the one `record_bucket`, and clear the
        journal. Positions are coalesced (a position written twice
        this round ships once, final value) and sorted, so the packed
        records are deterministic and duplicate-free — scatter
        ordering can never matter. Empty streams pad with an
        idempotent rewrite of the permanently dead position 0 (rows /
        segment statics) or node 0's current boundary meta."""
        pos = np.sort(np.fromiter(self._dirty_pos, np.int32, len(self._dirty_pos)))
        ents = np.sort(np.fromiter(self._dirty_inv, np.int32, len(self._dirty_inv)))
        segs = np.sort(np.fromiter(self._dirty_seg, np.int32, len(self._dirty_seg)))
        nids = np.sort(np.fromiter(self._dirty_node, np.int32, len(self._dirty_node)))
        kp, ki, ks, kn = len(pos), len(ents), len(segs), len(nids)
        bucket = self.record_bucket()
        row_rec = np.zeros((bucket, PLAN_RECORD_COLS), np.int32)
        if kp:
            row_rec[:kp, 0] = pos
            row_rec[:kp, 1] = self.p_arc[pos]
            row_rec[:kp, 2] = self.p_sign[pos]
            row_rec[:kp, 3] = self.p_src[pos]
            row_rec[:kp, 4] = self.p_dst[pos]
            row_rec[kp:] = row_rec[0]
        # else: all-zero rows rewrite the reserved dead position 0 with
        # its permanent (0, 0, 0, 0) values — idempotent by invariant
        inv_rec = np.zeros((bucket, INV_RECORD_COLS), np.int32)
        if ki:
            inv_rec[:ki, 0] = ents
            inv_rec[:ki, 1] = self.inv_order[ents]
            inv_rec[ki:] = inv_rec[0]
        else:
            inv_rec[:, 1] = self.inv_order[0]  # rewrite entry 0 as-is
        seg_rec = np.zeros((bucket, SEG_RECORD_COLS), np.int32)
        if ks:
            seg_rec[:ks, 0] = segs
            seg_rec[:ks, 1] = self.seg_start[segs]
            seg_rec[:ks, 2] = self.is_start[segs]
            seg_rec[ks:] = seg_rec[0]
        else:
            seg_rec[:, 1] = self.seg_start[0]
            seg_rec[:, 2] = self.is_start[0]
        node_rec = np.zeros((bucket, NODE_RECORD_COLS), np.int32)
        if kn:
            node_rec[:kn, 0] = nids
            node_rec[:kn, 1] = self.node_first[nids]
            node_rec[:kn, 2] = self.node_last[nids]
            node_rec[:kn, 3] = self.node_nonempty[nids]
            node_rec[kn:] = node_rec[0]
        else:
            node_rec[:, 1] = self.node_first[0]
            node_rec[:, 2] = self.node_last[0]
            node_rec[:, 3] = self.node_nonempty[0]
        self.clear_pending()
        return row_rec, inv_rec, seg_rec, node_rec

    def drain_records_sharded(
        self,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Per-shard routed form of ``drain_records`` (requires sharded
        layout mode): dirty plan rows and relocated segment statics are
        grouped by OWNER SHARD (position // block_extent) with
        block-local positions, stacked ``[D, K, cols]`` and padded to
        one shared pow2 record bucket per stream — a shard with fewer
        (or zero) records pads idempotently by rewriting its own
        reserved dead local slot 0 (rows: zeros; segment statics: the
        dead slot's permanent meta). The inv-order and node-boundary
        records stay in the global replicated form (those tensors are
        replicated on device by the partition rules). Returns
        ``(row [D, Kp, 5], seg [D, Ks, 3], inv [Ki, 2], node [Kn, 4])``
        and clears the journal."""
        D = self._num_shards
        Es = self.block_extent
        pos = np.sort(np.fromiter(self._dirty_pos, np.int64, len(self._dirty_pos)))  # kschedlint: host-only (host record packing)
        segs = np.sort(np.fromiter(self._dirty_seg, np.int64, len(self._dirty_seg)))  # kschedlint: host-only (host record packing)
        ents = np.sort(np.fromiter(self._dirty_inv, np.int32, len(self._dirty_inv)))
        nids = np.sort(np.fromiter(self._dirty_node, np.int32, len(self._dirty_node)))

        def route(idx, cols, fill):
            """[D, K, cols] per-shard records from global positions."""
            owner = idx // Es
            counts = np.bincount(owner, minlength=D)
            k = _pad_records(int(counts.max()) if len(idx) else 0)
            rec = np.zeros((D, k, cols), np.int32)
            for d in range(D):
                rec[d] = fill(d)  # idempotent dead-slot pad, whole block
                mine = idx[owner == d]
                kd = len(mine)
                if kd:
                    rec[d, :kd, 0] = (mine - d * Es).astype(np.int32)
                    rec[d, :kd, 1:] = self._row_values(mine, cols)
                    rec[d, kd:] = rec[d, 0]
            return rec

        row_rec = route(
            pos, PLAN_RECORD_COLS,
            lambda d: np.zeros(PLAN_RECORD_COLS, np.int32),
        )
        seg_rec = route(
            segs, SEG_RECORD_COLS,
            lambda d: np.array([0, d * Es, 1], np.int32),
        )
        ki, kn = len(ents), len(nids)
        inv_rec = np.zeros((_pad_records(ki), INV_RECORD_COLS), np.int32)
        if ki:
            inv_rec[:ki, 0] = ents
            inv_rec[:ki, 1] = self.inv_order[ents]
            inv_rec[ki:] = inv_rec[0]
        else:
            inv_rec[:, 1] = self.inv_order[0]
        node_rec = np.zeros((_pad_records(kn), NODE_RECORD_COLS), np.int32)
        if kn:
            node_rec[:kn, 0] = nids
            node_rec[:kn, 1] = self.node_first[nids]
            node_rec[:kn, 2] = self.node_last[nids]
            node_rec[:kn, 3] = self.node_nonempty[nids]
            node_rec[kn:] = node_rec[0]
        else:
            node_rec[:, 1] = self.node_first[0]
            node_rec[:, 2] = self.node_last[0]
            node_rec[:, 3] = self.node_nonempty[0]
        self.clear_pending()
        return row_rec, seg_rec, inv_rec, node_rec

    def _row_values(self, idx: np.ndarray, cols: int) -> np.ndarray:
        """Value columns for routed records at global positions `idx`
        (row records carry the four plan-row values, segment records
        the (seg_start, is_start) pair)."""
        if cols == PLAN_RECORD_COLS:
            return np.stack(
                [self.p_arc[idx], self.p_sign[idx], self.p_src[idx], self.p_dst[idx]],
                axis=1,
            )
        return np.stack(
            [self.seg_start[idx], self.is_start[idx].astype(np.int32)], axis=1
        )

    def clear_pending(self) -> None:
        self._dirty_pos.clear()
        self._dirty_inv.clear()
        self._dirty_seg.clear()
        self._dirty_node.clear()

    # -- materialization ---------------------------------------------------

    def host_args(self) -> Tuple:
        """The plan tensors as host arrays, in `_solve_mcmf` positional
        order — the full-rebuild/full-ship materialization the scatter
        path must match bit-for-bit."""
        self.ensure_built()
        return (
            self.p_arc, self.p_sign, self.p_src, self.p_dst,
            self.seg_start, self.is_start, self.inv_order,
            self.node_first, self.node_last, self.node_nonempty,
        )

    def device_static(self) -> Tuple:
        """The segment/node boundary tensors on device, cached per
        (layout_gen, static_version) — uploaded once per layout and
        re-shipped only when a relocation moved a region (ordinary
        endpoint churn never touches them)."""
        self.ensure_built()
        key = (self.layout_gen, self.static_version)
        if self._static_dev is None or self._static_dev[0] != key:
            import jax.numpy as jnp

            self._static_dev = (
                key,
                tuple(
                    jnp.asarray(x)
                    for x in (
                        self.seg_start, self.is_start,
                        self.node_first, self.node_last, self.node_nonempty,
                    )
                ),
            )
        return self._static_dev[1]

    def static_nbytes(self) -> int:
        return int(
            self.seg_start.nbytes + self.is_start.nbytes
            + self.node_first.nbytes + self.node_last.nbytes
            + self.node_nonempty.nbytes
        )

    def values_nbytes(self) -> int:
        return int(
            self.p_arc.nbytes + self.p_sign.nbytes
            + self.p_src.nbytes + self.p_dst.nbytes + self.inv_order.nbytes
        )

    def device_args(self) -> Tuple:
        """The full plan as device tensors in `_solve_mcmf` order,
        cached by (layout_gen, value_version): a clean round re-uses
        the previous upload outright; a dirty round re-ships the
        maintained host arrays wholesale (the non-resident path — the
        device-resident mirror scatters records instead). What it ships
        goes up inside a `plan_upload` span, the name the mirror's
        scatter has, `bytes` exact (`last_ship_bytes`); a clean round
        opens none."""
        self.ensure_built()
        key = (self.layout_gen, self.value_version)
        static_key = (self.layout_gen, self.static_version)
        ship_values = self._values_dev is None or self._values_dev[:2] != key
        ship_static = self._static_dev is None or self._static_dev[0] != static_key
        self.last_ship_bytes = 0
        if ship_values or ship_static:
            import jax.numpy as jnp

            self.last_ship_bytes = (self.values_nbytes() if ship_values else 0) + (
                self.static_nbytes() if ship_static else 0
            )
            with span(
                "plan_upload", kind="static_and_values" if ship_static else "values",
                bytes=self.last_ship_bytes, rows=self.entry_cap,
            ):
                if ship_values:
                    self._values_dev = key + (
                        tuple(
                            jnp.asarray(x)
                            for x in (self.p_arc, self.p_sign, self.p_src, self.p_dst)
                        ),
                        jnp.asarray(self.inv_order),
                    )
                seg, isstart, first, last, nonempty = self.device_static()
        else:
            seg, isstart, first, last, nonempty = self._static_dev[1]
        values, inv = self._values_dev[2], self._values_dev[3]
        return values + (seg, isstart, inv, first, last, nonempty)

    # -- invariants (tests / debug) ----------------------------------------

    def check_invariants(self) -> None:
        """Verify the maintained layout is internally consistent with
        the owning DeviceGraphState (O(E)). Raises a structured
        `runtime.integrity.IntegrityError` (an AssertionError subclass,
        so bare-assert-era consumers keep working) — promoted from a
        test helper to the `--audit-every` service audit surface."""
        try:
            self._check_invariants_impl()
        except AssertionError as e:
            from ..runtime.integrity import IntegrityError

            if isinstance(e, IntegrityError):
                raise
            raise IntegrityError(f"slot-plan invariant violated: {e}", array="slot_plan") from e

    def _check_invariants_impl(self) -> None:
        st = self.state
        assert not self.needs_rebuild, "plan not built"
        live = sorted(st._arc_slot.values())
        seen = set()
        for slot in live:
            pf, pb = int(self.pos_fwd[slot]), int(self.pos_bwd[slot])
            s, d = int(st.src[slot]), int(st.dst[slot])
            assert pf > 0 and pb > 0, f"slot {slot} unassigned"
            assert pf not in seen and pb not in seen, f"slot {slot} aliases a row"
            seen.update((pf, pb))
            rs, rc = int(self.region_start[s]), int(self.region_cap[s])
            assert rs <= pf < rs + rc, f"fwd row of slot {slot} outside src region"
            assert int(self.seg_start[pf]) == rs, (
                f"fwd row of slot {slot} carries a stale segment start"
            )
            rs, rc = int(self.region_start[d]), int(self.region_cap[d])
            assert rs <= pb < rs + rc, f"bwd row of slot {slot} outside dst region"
            assert int(self.seg_start[pb]) == rs, (
                f"bwd row of slot {slot} carries a stale segment start"
            )
            assert (
                self.p_arc[pf] == slot and self.p_sign[pf] == 1
                and self.p_src[pf] == s and self.p_dst[pf] == d
            ), f"fwd row of slot {slot} stale"
            assert (
                self.p_arc[pb] == slot and self.p_sign[pb] == -1
                and self.p_src[pb] == d and self.p_dst[pb] == s
            ), f"bwd row of slot {slot} stale"
            assert int(self.inv_order[slot]) == pf
            assert int(self.inv_order[st.m_cap + slot]) == pb
        n_live_rows = int((self.p_sign != 0).sum())
        assert n_live_rows == 2 * len(live), (
            f"{n_live_rows} live plan rows for {len(live)} live slots"
        )
        assert self.p_sign[0] == 0, "reserved position 0 must stay dead"
        occ = np.bincount(
            self.p_src[self.p_sign != 0], minlength=st.n_cap
        )
        if not np.array_equal(occ, self._occ[: st.n_cap]):
            from ..runtime.integrity import bounded_diff

            # raised AS the structured error: check_invariants passes
            # IntegrityError through unwrapped, keeping the
            # machine-readable indices/expected/found fields
            raise bounded_diff("plan_occupancy", self._occ[: st.n_cap], occ)
        assert (self._deg_hwm[: st.n_cap] >= occ).all(), (
            "degree high-water mark fell below live occupancy"
        )
        block_limits = (np.arange(self._num_shards, dtype=np.int64) + 1) * self.block_extent  # kschedlint: host-only (test-only invariant check)
        assert (self._tail_next <= block_limits).all(), (
            "a tail pool overran its shard block"
        )
        # the load-bearing fwd-front/bwd-back split within every region
        fpos = np.flatnonzero(self.p_sign == 1).astype(np.int64)  # kschedlint: host-only (test-only invariant check)
        bpos = np.flatnonzero(self.p_sign == -1).astype(np.int64)  # kschedlint: host-only (test-only invariant check)
        maxf = np.full(st.n_cap, -1, np.int64)  # kschedlint: host-only (test-only invariant check)
        np.maximum.at(maxf, self.p_src[fpos], fpos)
        minb = np.full(st.n_cap, self.entry_cap, np.int64)  # kschedlint: host-only (test-only invariant check)
        np.minimum.at(minb, self.p_src[bpos], bpos)
        assert (maxf < minb).all(), (
            "a backward row precedes a forward row in its region"
        )
        # current regions (original spans and relocated tail spans
        # alike) must be pairwise disjoint and inside [1, tail)
        starts = self.region_start.astype(np.int64)  # kschedlint: host-only (test-only invariant check)
        caps64 = self.region_cap.astype(np.int64)  # kschedlint: host-only (test-only invariant check)
        held = caps64 > 0
        order = np.argsort(starts[held], kind="stable")
        lo = starts[held][order]
        hi = lo + caps64[held][order]
        if lo.size:
            assert (hi[:-1] <= lo[1:]).all(), "regions overlap"
            # every held region lives inside its OWNER's block, past
            # the block's reserved dead slot and under its tail
            # frontier (one block == the whole table when unsharded)
            own = self._owner[np.flatnonzero(held)]
            s_h = starts[held]
            e_h = s_h + caps64[held]
            assert (s_h >= own * self.block_extent + 1).all(), (
                "a region precedes its block's reserved dead slot"
            )
            assert (e_h <= self._tail_next[own]).all(), (
                "a region lies outside the packed/tail extent"
            )
        for node in np.flatnonzero(held):
            assert int(self.node_first[node]) == int(starts[node])
            assert int(self.node_last[node]) == int(starts[node] + caps64[node] - 1)


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(__name__, "plan_apply")
