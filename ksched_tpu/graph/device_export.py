"""Device-array graph state: the TPU-native replacement for the DIMACS wire.

Where the reference streams DIMACS text to a solver subprocess
(scheduling/flow/placement/solver.go:92-123), the TPU build keeps the
flow network as flat structure-of-arrays buffers whose row indices ARE
the flow-graph node ids (dense + recycled, see graph/flowgraph.py). A
full build converts the host graph once; afterwards the per-round change
journal (graph/changes.py) is scattered into the arrays in place, so the
cost of preparing a round's solve tracks the delta, not the graph — the
same property the reference gets from Flowlessly's incremental daemon
mode.

Arrays are padded to power-of-two extents so repeated jit solves reuse
the same compiled executable as the cluster grows (XLA static shapes).

Two consumers read the per-round mutations:

- ``problem()`` materializes the lower-bound-folded host FlowProblem,
  rebuilding only the array groups a journal entry actually touched
  since the last materialize (clean rounds return the cached object);
- ``DeviceResidentState`` mirrors the folded arrays as PERSISTENT
  device buffers: the round's dirty slots/nodes are packed on host
  into flat int32 delta records and applied by ONE jit'd scatter
  (`delta_apply_fn`), so after the initial full upload only
  delta-sized records cross the host/device boundary. The mirror is
  rebuilt only when a pow2 bucket grows or `full_build` reassigns the
  slot table — the recompile/reupload boundary the reference pays as
  a full DIMACS re-export.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from .changes import AddNodeChange, Change, ChangeArcChange, NewArcChange, RemoveNodeChange
from .flowgraph import FlowGraph, NodeType
from ..utils import next_pow2


@dataclass
class FlowProblem:
    """A min-cost max-flow instance in flat arrays.

    Row 0 of the node arrays is a padding row (graph node ids start at 1).
    Arc lower bounds are already folded into ``excess`` via the standard
    transformation; ``flow_offset`` holds the folded lower bound per arc so
    decoded flows can be restored (decoded_flow = solver_flow + flow_offset).
    It holds a second thing on the one arc of a leaf (a node whose only
    live out-arc ends at the sink, the PUs of a served graph): the
    supply the fold left on that node, which every feasible flow sends
    over that arc, so it is a lower bound of the arc too and is folded
    like one (``DeviceGraphState.routed``): the leaf holds no excess,
    the sink that much more, ``cap`` that much less.
    """

    num_nodes: int  # dense extent including padding row
    excess: np.ndarray  # int64[N] supply(+)/demand(-) after lower-bound fold
    node_type: np.ndarray  # int8[N] NodeType, -1 for invalid rows
    src: np.ndarray  # int32[M]
    dst: np.ndarray  # int32[M]
    cap: np.ndarray  # int32[M] residual upper bound after lower-bound fold
    cost: np.ndarray  # int32[M]
    flow_offset: np.ndarray  # int32[M] folded lower bounds
    num_arcs: int  # live arc slots (<= len(src))
    #: slot-stable CSR plan handle (graph/slot_plan.SlotPlanState) when
    #: the problem came from a DeviceGraphState; None for plain
    #: array-built problems (bulk, tests) — consumers that don't know
    #: about it (cpu_ref, native, sharded) just ignore it
    plan: object = None
    #: cheap endpoint-structure generation key
    #: (state uid, rebuild_count, n_cap, m_cap, endpoint_gen): two
    #: problems with equal keys have identical arc endpoints, so
    #: solver plan caches can skip their O(M) endpoint scans entirely
    #: on clean rounds (None = unknown, fall back to comparing arrays)
    plan_key: object = None

    @property
    def total_supply(self) -> int:
        return int(self.excess[self.excess > 0].sum())


def pad_problem(problem: FlowProblem, n_cap: int, m_cap: int) -> FlowProblem:
    """Zero-pad a FlowProblem into a LARGER pow2 shape bucket (the
    multi-tenant lane-alignment helper, tenancy/batch.py).

    Padding rows are inert by construction: pad nodes carry zero excess
    and node_type -1, pad arc slots are (0, 0) self-loops at node 0
    with zero cap/cost, whose forward AND backward residuals are zero —
    they can never push, relabel, or absorb prefix allocation, so the
    real prefix of the solved flow is unchanged by the padding.

    One caveat the tenancy layer documents and tests: the general-graph
    solvers pre-scale costs by ``num_nodes`` for eps=1 exactness, so a
    padded problem is a DIFFERENT (equally exact) solve than the
    unpadded one — bit-parity holds between runs that pad identically
    (a lane vs the same lane solved alone at the same bucket), not
    between a padded and an unpadded solve. Bucket assignment is
    therefore a per-tenant property (its own caps + a static floor),
    never a function of which co-tenants happen to share the process.
    """
    n0, m0 = problem.num_nodes, len(problem.src)
    if n_cap < n0 or m_cap < m0:
        raise ValueError(
            f"pad_problem cannot shrink: ({n0}, {m0}) -> ({n_cap}, {m_cap})"
        )
    if n_cap == n0 and m_cap == m0:
        return problem

    def pad_to(arr, size, fill=0):
        out = np.full(size, fill, dtype=arr.dtype)
        out[: len(arr)] = arr
        return out

    return FlowProblem(
        num_nodes=n_cap,
        excess=pad_to(problem.excess, n_cap),
        node_type=pad_to(problem.node_type, n_cap, fill=-1),
        src=pad_to(problem.src, m_cap),
        dst=pad_to(problem.dst, m_cap),
        cap=pad_to(problem.cap, m_cap),
        cost=pad_to(problem.cost, m_cap),
        flow_offset=pad_to(problem.flow_offset, m_cap),
        num_arcs=problem.num_arcs,
        plan=None,  # slot-stable plans do not survive re-padding
        plan_key=(
            ("padded", problem.plan_key, n_cap, m_cap)
            if problem.plan_key is not None
            else None
        ),
    )


_STATE_UIDS = itertools.count()


class DeviceGraphState:
    """Maintains the padded flat arrays + the (src, dst) → arc-slot map.

    ``full_build`` constructs arrays from a host FlowGraph; ``apply_changes``
    scatters a change journal into them. Freed arc slots are recycled.
    """

    def __init__(self) -> None:
        self.n_cap = 0  # padded node extent
        self.m_cap = 0  # padded arc extent
        self.excess: Optional[np.ndarray] = None
        self.node_type: Optional[np.ndarray] = None
        self.src: Optional[np.ndarray] = None
        self.dst: Optional[np.ndarray] = None
        self.cap: Optional[np.ndarray] = None
        self.low: Optional[np.ndarray] = None
        self.cost: Optional[np.ndarray] = None
        #: per-node lower-bound fold contribution, maintained
        #: incrementally as arc lows change: folded excess ==
        #: ``excess + fold`` (replaces the O(M) scatter fold the old
        #: problem() ran every round)
        self.fold: Optional[np.ndarray] = None
        #: per-slot forced supply, folded once more: on the one live
        #: out-arc of a leaf (a node whose only out-arc ends at the
        #: sink) the folded supply of the leaf, up to the arc's room.
        #: It is a lower bound of that arc in every feasible flow, so
        #: the folded view treats it as one: ``fold`` carries it from
        #: the leaf to the sink, the folded cap is ``cap - low -
        #: routed``, ``flow_offset`` is ``low + routed``. Kept by
        #: `_route` wherever `_set_arc` moves a fold, an out-arc or a
        #: capacity; ``supply_prerouted`` is its sum
        self.routed: Optional[np.ndarray] = None
        self.supply_prerouted = 0
        #: live out-arcs per node and the sum of their slots: the one
        #: out-arc of a node of out-degree 1 is slot ``out_sum[node]``
        self.out_deg: Optional[np.ndarray] = None
        self.out_sum: Optional[np.ndarray] = None
        self.sink = -1  # the SINK node's id, once a node says it is one
        self._arc_slot: Dict[Tuple[int, int], int] = {}
        self._free_slots: List[int] = []
        self._num_slots = 0
        self.num_nodes = 0
        self.generation = 0  # bumped when padded extents change (recompile signal)
        #: bumped by full_build only: the slot table was reassigned, so
        #: any device mirror of the arc arrays is wholesale invalid
        #: (growth keeps slots stable and is signaled by n_cap/m_cap)
        self.rebuild_count = 0
        #: bumped whenever some slot's (src, dst) actually changes —
        #: cap/cost-only journals leave it alone, so solver plan caches
        #: keyed on plan_key() skip their endpoint scans on clean rounds
        self.endpoint_gen = 0
        self._uid = next(_STATE_UIDS)
        #: slot-stable CSR plan (graph/slot_plan.py): an inert shell
        #: until a slot-stable consumer calls plan.ensure_built();
        #: after that the _set_arc hooks below keep it in sync per
        #: endpoint change, O(1) each
        from .slot_plan import SlotPlanState

        self.plan = SlotPlanState(self)
        # -- mutation tracking ------------------------------------------
        # Two consumers, two mechanisms: the problem() cache needs only
        # "did anything in this group change" booleans; the device-
        # resident mirror needs the exact touched slots/nodes to pack
        # delta records from. drain_dirty() empties the sets without
        # touching the cache flags, and vice versa.
        self._dirty_slots: Set[int] = set()
        self._dirty_nodes: Set[int] = set()
        self._cache: Optional[FlowProblem] = None
        self._cache_nodes_ok = False
        self._cache_arcs_ok = False

    # -- mutation bookkeeping ---------------------------------------------

    def _touch_slot(self, slot: int) -> None:
        self._dirty_slots.add(slot)
        self._cache_arcs_ok = False

    def _touch_node(self, node: int) -> None:
        self._dirty_nodes.add(node)
        self._cache_nodes_ok = False

    def _reset_tracking(self) -> None:
        """After a full (re)build every consumer must resync from the
        arrays wholesale; per-entry dirt from the build is noise."""
        self._dirty_slots.clear()
        self._dirty_nodes.clear()
        self._cache = None
        self._cache_nodes_ok = False
        self._cache_arcs_ok = False

    def drain_dirty(self) -> Tuple[np.ndarray, np.ndarray]:
        """The slots/nodes touched since the last drain, sorted (set
        order is not deterministic; packed records must be), and clear
        them. Consumed by DeviceResidentState.refresh()."""
        slots = np.sort(np.fromiter(self._dirty_slots, np.int32, len(self._dirty_slots)))
        nodes = np.sort(np.fromiter(self._dirty_nodes, np.int32, len(self._dirty_nodes)))
        self._dirty_slots.clear()
        self._dirty_nodes.clear()
        return slots, nodes

    # -- construction -----------------------------------------------------

    def _alloc(self, n: int, m: int) -> None:
        self.n_cap = max(next_pow2(n), 16)
        self.m_cap = max(next_pow2(m), 16)
        self.excess = np.zeros(self.n_cap, dtype=np.int64)  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.node_type = np.full(self.n_cap, -1, dtype=np.int8)
        self.src = np.zeros(self.m_cap, dtype=np.int32)
        self.dst = np.zeros(self.m_cap, dtype=np.int32)
        self.cap = np.zeros(self.m_cap, dtype=np.int32)
        self.low = np.zeros(self.m_cap, dtype=np.int32)
        self.cost = np.zeros(self.m_cap, dtype=np.int32)
        self.fold = np.zeros(self.n_cap, dtype=np.int64)  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.routed = np.zeros(self.m_cap, dtype=np.int32)
        self.supply_prerouted = 0
        self.out_deg = np.zeros(self.n_cap, dtype=np.int32)
        self.out_sum = np.zeros(self.n_cap, dtype=np.int64)  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.sink = -1
        self.generation += 1
        self.plan.invalidate()

    def plan_key(self) -> Tuple:
        """Endpoint-structure generation key for this state's current
        arrays (see FlowProblem.plan_key)."""
        return (self._uid, self.rebuild_count, self.n_cap, self.m_cap, self.endpoint_gen)

    def full_build(self, graph: FlowGraph) -> None:
        n = graph.max_node_id
        m = graph.num_arcs
        self._alloc(n, m)
        self._arc_slot.clear()
        self._free_slots.clear()
        self._num_slots = 0
        self.num_nodes = n
        for node in graph.nodes():
            self.excess[node.id] = node.excess
            self._set_node_type(node.id, int(node.type))
        for arc in graph.arcs():
            self._set_arc(arc.src, arc.dst, arc.cap_lower, arc.cap_upper, arc.cost)
        self.rebuild_count += 1  # slot table reassigned: device mirrors resync
        self._reset_tracking()

    # -- incremental updates ----------------------------------------------

    def _grow_nodes(self, need: int) -> None:
        new_cap = next_pow2(need)
        if new_cap <= self.n_cap:
            return
        self.excess = np.concatenate([self.excess, np.zeros(new_cap - self.n_cap, np.int64)])  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.node_type = np.concatenate(
            [self.node_type, np.full(new_cap - self.n_cap, -1, np.int8)]
        )
        self.fold = np.concatenate([self.fold, np.zeros(new_cap - self.n_cap, np.int64)])  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.out_deg = np.concatenate([self.out_deg, np.zeros(new_cap - self.n_cap, np.int32)])
        self.out_sum = np.concatenate([self.out_sum, np.zeros(new_cap - self.n_cap, np.int64)])  # kschedlint: host-only (host graph arrays; the device mirror is int32)
        self.n_cap = new_cap
        self.generation += 1
        self.plan.invalidate()  # regions must cover the new rows
        # shapes changed: every cached materialization is stale
        self._cache = None
        self._cache_nodes_ok = False
        self._cache_arcs_ok = False

    def _grow_arcs(self, need: int) -> None:
        new_cap = next_pow2(need)
        if new_cap <= self.m_cap:
            return
        pad = new_cap - self.m_cap
        for name in ("src", "dst", "cap", "low", "cost", "routed"):
            arr = getattr(self, name)
            setattr(self, name, np.concatenate([arr, np.zeros(pad, arr.dtype)]))
        self.m_cap = new_cap
        self.generation += 1
        self.plan.invalidate()  # entry budget + inv_order extent stale
        self._cache = None
        self._cache_nodes_ok = False
        self._cache_arcs_ok = False

    def _take_slot(self) -> int:
        if self._free_slots:
            return self._free_slots.pop()
        slot = self._num_slots
        self._grow_arcs(slot + 1)
        self._num_slots += 1
        return slot

    def _set_node_type(self, node: int, node_type: int) -> None:
        self.node_type[node] = node_type
        if node_type == int(NodeType.SINK):
            self.sink = node

    def _hold(self, slot: int, node: int, units: int) -> None:
        """Set what the leaf `node` holds routed on its one arc `slot`:
        the folded view moves the difference from the node to the sink
        and from the arc's cap to its offset."""
        moved = units - int(self.routed[slot])
        self.routed[slot] = units
        self.fold[node] -= moved
        self.fold[self.sink] += moved
        self.supply_prerouted += moved
        self._touch_slot(slot)
        self._touch_node(node)
        self._touch_node(self.sink)

    def _route(self, node: int) -> None:
        """Keep `routed` at what is forced of `node`: if its only live
        out-arc ends at the sink, the supply the fold leaves on it (its
        excess and the lower bounds of the arcs into it) up to the
        arc's room; a node with another way out, or supply past the
        arc's capacity, keeps it for the solver. Called after `_set_arc`
        (or an excess write) moved the node's fold, out-arcs or the
        arc's capacity; a no-op where nothing of that changed."""
        if self.out_deg[node] != 1:
            return
        slot = int(self.out_sum[node])
        if self.dst[slot] != self.sink:
            return
        held = int(self.routed[slot])
        # `fold` already carries `held` away from the node
        supply = int(self.excess[node]) + int(self.fold[node]) + held
        room = int(self.cap[slot]) - int(self.low[slot])
        units = max(min(supply, room), 0)
        if units != held:
            self._hold(slot, node, units)

    def _set_arc(self, src: int, dst: int, low: int, cap: int, cost: int) -> None:
        key = (src, dst)
        slot = self._arc_slot.get(key)
        low0 = int(self.low[slot]) if slot is not None else 0
        if cap == 0 and low == 0:
            if slot is not None:
                if self.routed[slot]:
                    self._hold(slot, src, 0)
                self.out_deg[src] -= 1
                self.out_sum[src] -= slot
                self.plan.slot_freed(slot, src, dst)
                self.endpoint_gen += 1
                self.cap[slot] = 0
                self.low[slot] = 0
                self.cost[slot] = 0
                self.src[slot] = 0
                self.dst[slot] = 0
                del self._arc_slot[key]
                self._free_slots.append(slot)
                self._touch_slot(slot)
                if low0:
                    self.fold[src] += low0
                    self.fold[dst] -= low0
                    self._touch_node(src)
                    self._touch_node(dst)
                    self._route(dst)
                self._route(src)  # the arc it has left may be its one
            return
        if slot is None:
            if self.out_deg[src] == 1:
                # a second way out: nothing is forced any more
                only = int(self.out_sum[src])
                if self.routed[only]:
                    self._hold(only, src, 0)
            slot = self._take_slot()
            self._arc_slot[key] = slot
            self.out_deg[src] += 1
            self.out_sum[src] += slot
            self.plan.slot_assigned(slot, src, dst)
            self.endpoint_gen += 1
        if low != low0:
            # fold delta: an arc (src, dst) with lower bound L
            # contributes -L to src's folded excess and +L to dst's
            self.fold[src] += low0 - low
            self.fold[dst] += low - low0
            self._touch_node(src)
            self._touch_node(dst)
        self.src[slot] = src
        self.dst[slot] = dst
        self.cap[slot] = cap
        self.low[slot] = low
        self.cost[slot] = cost
        self._touch_slot(slot)
        self._route(src)
        if low != low0:
            self._route(dst)

    def apply_changes(self, changes: List[Change]) -> None:
        for ch in changes:
            if isinstance(ch, AddNodeChange):
                self._grow_nodes(ch.node_id + 1)
                self.excess[ch.node_id] = ch.excess
                self._set_node_type(ch.node_id, int(ch.node_type))
                self.num_nodes = max(self.num_nodes, ch.node_id + 1)
                self._touch_node(ch.node_id)
                self._route(ch.node_id)
            elif isinstance(ch, RemoveNodeChange):
                self.excess[ch.node_id] = 0
                self.node_type[ch.node_id] = -1
                self._touch_node(ch.node_id)
                self._route(ch.node_id)
            elif isinstance(ch, (NewArcChange, ChangeArcChange)):
                self._set_arc(ch.src, ch.dst, ch.cap_lower, ch.cap_upper, ch.cost)
            else:  # pragma: no cover
                raise TypeError(f"unknown change record: {ch!r}")

    def set_excess(self, node_id: int, excess: int) -> None:
        """Sink-excess bookkeeping happens outside the journal in the
        reference (graph_manager.go:636-640); mirror of that path. A
        no-op write stays invisible to the dirty tracking, so the
        every-round sink sync does not invalidate a clean cache."""
        if int(self.excess[node_id]) != excess:
            self.excess[node_id] = excess
            self._touch_node(node_id)
            self._route(node_id)

    # -- solver view ------------------------------------------------------

    def folded_offset(self, slots=slice(None)) -> np.ndarray:
        """What the folded view takes off each arc's ``cap`` and hands
        back as ``flow_offset``: the arc's lower bound and, on a leaf's
        one arc, the supply routed over it."""
        return self.low[slots] + self.routed[slots]

    def problem(self) -> FlowProblem:
        """Materialize the lower-bound-folded FlowProblem view.

        Copies the arrays (never aliases them) so a solver can keep its
        snapshot while further host mutations accumulate — but only the
        array GROUPS a journal entry touched since the last materialize
        are re-copied/refolded: the node side (excess, node_type) and
        the arc side (src/dst/cap/cost/flow_offset) invalidate
        independently, and a mutation-free round returns the cached
        FlowProblem outright. The lower-bound fold is the incrementally
        maintained ``fold`` array (one vector add), not a scatter pass,
        and so is the leaves' forced supply (``routed``).
        """
        cache = self._cache
        if cache is not None and self._cache_nodes_ok and self._cache_arcs_ok:
            return cache
        m = self.m_cap
        if cache is not None and self._cache_arcs_ok:
            src, dst, cap = cache.src, cache.dst, cache.cap
            cost, flow_offset = cache.cost, cache.flow_offset
        else:
            flow_offset = self.folded_offset()  # new array
            src = self.src[:m].copy()
            dst = self.dst[:m].copy()
            cap = self.cap[:m] - flow_offset  # folded residual bound (new array)
            cost = self.cost[:m].copy()
        if cache is not None and self._cache_nodes_ok:
            excess, node_type = cache.excess, cache.node_type
        else:
            excess = self.excess + self.fold  # folded supply (new array)
            node_type = self.node_type.copy()
        self._cache = FlowProblem(
            num_nodes=self.n_cap,
            excess=excess,
            node_type=node_type,
            src=src,
            dst=dst,
            cap=cap,
            cost=cost,
            flow_offset=flow_offset,
            num_arcs=self._num_slots,
            plan=self.plan,
            plan_key=self.plan_key(),
        )
        self._cache_nodes_ok = True
        self._cache_arcs_ok = True
        return self._cache


# ---------------------------------------------------------------------------
# Device-resident mirror: persistent buffers + packed-record delta scatter
# ---------------------------------------------------------------------------

#: int32 columns of one packed arc delta record:
#: (slot, src, dst, folded cap, cost). flow_offset stays host-only —
#: no solver reads it on device (decode adds it back on host), so
#: shipping it would pad every record by a sixth for nothing.
ARC_RECORD_COLS = 5
#: int32 columns of one packed node delta record: (node, folded excess)
NODE_RECORD_COLS = 2
#: smallest padded record count — one compiled scatter program per pow2
#: record bucket, so tiny deltas share one executable
MIN_RECORD_BUCKET = 8
#: a delta whose bucket would pass this share of the buffer's rows goes
#: up whole instead: a record is 20 B against 16 B of arrays per slot,
#: so the next bucket up would ship more bytes than the arrays and
#: still need the serialized scatter
FULL_UPLOAD_SHARE = 2


def pad_record_count(*counts: int) -> int:
    """The ONE pow2 bucket all of a scatter program's record streams
    are padded to (>= 1 so an empty delta still has a well-formed —
    idempotent — record to ship). One joint bucket per program, not
    one per stream: the shapes a program can be called with are then
    a short list (`record_buckets`), not a product of lists."""
    return max(next_pow2(max(max(counts), 1)), MIN_RECORD_BUCKET)


def record_buckets(extent: int) -> Tuple[int, ...]:
    """The closed set of record buckets of a scatter program over a
    buffer of `extent` rows: the pow2s from MIN_RECORD_BUCKET up to
    extent / FULL_UPLOAD_SHARE. A delta past the last one goes up
    whole on the full-upload path."""
    top = max(extent // FULL_UPLOAD_SHARE, MIN_RECORD_BUCKET)
    return tuple(
        MIN_RECORD_BUCKET << i
        for i in range((top // MIN_RECORD_BUCKET).bit_length())
    )


#: (program, buffer shapes) -> {bucket: compiled executable}
_CLOSED_SETS: Dict[Tuple, Dict[int, object]] = {}


def closed_set(program, buffers, record_cols, extent: int) -> Dict[int, object]:
    """`program` compiled ahead of time for every bucket of
    `record_buckets(extent)`, keyed by bucket. Called where the
    persistent buffers are (re)allocated — first full upload, pow2
    growth, layout rebuild — so that no later round meets a shape that
    has not been compiled: a served round that compiles makes its pods
    wait tenths of a second to seconds on a TPU. Shared by every mirror
    of the same buffer shapes in the process, like a jit cache."""
    import jax

    shapes = tuple(jax.ShapeDtypeStruct(b.shape, b.dtype) for b in buffers)
    key = (program, tuple((b.shape, b.dtype.name) for b in shapes))
    programs = _CLOSED_SETS.get(key)
    if programs is None:
        programs = _CLOSED_SETS[key] = {
            k: program.lower(
                *shapes,
                *(jax.ShapeDtypeStruct((k, c), np.int32) for c in record_cols),
            ).compile()
            for k in record_buckets(extent)
        }
    return programs


_DELTA_APPLY = None


def delta_apply_fn():
    """The ONE jit'd scatter program of the solver stack: applies a
    round's packed delta records to the persistent device buffers.

    TPU serializes scatters, which is why every solver program is
    scatter-free (the zero-scatter jaxpr contract) — but the delta
    apply is O(records), not O(graph), and runs once per round, so a
    serialized scatter of ~churn-sized records is exactly the right
    tool. The jaxpr contracts grant this program a SCOPED exemption
    from the zero-scatter rule and pin its pow2-bucket hash stability
    (analysis/jaxpr_contracts.py).

    Records are pow2-padded by REPEATING a real record (or, for an
    empty delta, re-writing slot/node 0 with its current values):
    duplicate scatter updates carry identical values, so the result is
    deterministic regardless of XLA's scatter ordering.
    """
    global _DELTA_APPLY
    if _DELTA_APPLY is None:
        import jax

        # excess/cap/cost are DONATED: XLA scatters into the existing
        # buffers instead of copying the whole mirror first (measured
        # 498 -> 8.7 us/apply at 256k rows on CPU XLA; donation is
        # honored on CPU and TPU alike). src/dst are NOT donated — the
        # pre-delta endpoint buffers stay alive as the warm-flow masks
        # (device_warm_flow_fn) and the solvers' last-solve endpoint
        # handles; donating them would tear the buffers out from under
        # those references.
        @functools.partial(jax.jit, donate_argnums=(0, 3, 4))  # kschedlint: program=delta_apply
        def _apply_delta(excess, src, dst, cap, cost, arc_rec, node_rec):
            nid = node_rec[:, 0]
            excess = excess.at[nid].set(node_rec[:, 1])
            slot = arc_rec[:, 0]
            src = src.at[slot].set(arc_rec[:, 1])
            dst = dst.at[slot].set(arc_rec[:, 2])
            cap = cap.at[slot].set(arc_rec[:, 3])
            cost = cost.at[slot].set(arc_rec[:, 4])
            return excess, src, dst, cap, cost

        _DELTA_APPLY = _apply_delta
    return _DELTA_APPLY


_WARM_FLOW = None


def device_warm_flow_fn():
    """Scatter-free warm-flow carry: the previous round's device flow,
    kept where the arc endpoints are unchanged (compared against the
    PRE-delta endpoint buffers, which jax's immutability keeps alive
    for free) and clipped to the new capacities. Bit-identical to the
    host path's ``np.where(same, minimum(prev, cap), 0)``, so a
    device-resident loop decodes the same placements as a host loop.
    """
    global _WARM_FLOW
    if _WARM_FLOW is None:
        import jax
        import jax.numpy as jnp

        @jax.jit  # kschedlint: program=warm_flow
        def _warm_flow(prev_flow, src_prev, dst_prev, src, dst, cap):
            same = (src_prev == src) & (dst_prev == dst)
            return jnp.where(same, jnp.minimum(prev_flow, cap), jnp.int32(0))

        _WARM_FLOW = _warm_flow
    return _WARM_FLOW


_SCALE_COST = None


def _scale_cost_fn():
    global _SCALE_COST
    if _SCALE_COST is None:
        import jax

        @jax.jit  # kschedlint: program=scale_cost
        def _scale(cost, n):
            return cost * n

        _SCALE_COST = _scale
    return _SCALE_COST


@dataclass
class DeviceResidentProblem(FlowProblem):
    """A FlowProblem whose folded arrays ALSO live as persistent device
    buffers. The host arrays stay populated (decode, the cpu_ref/native
    ladder rungs, and the objective math read them), so every existing
    consumer keeps working; device-aware solvers read the ``d_*``
    handles instead of re-uploading.

    The warm-flow masks deliberately compare against endpoint buffers
    each solver captured at its own last SUCCESSFUL solve (not this
    refresh's pre-delta buffers): a failed/degraded round still
    refreshes the mirror, and masking against its endpoints would miss
    changes from the round the solver never saw — see
    ``resident_solver_inputs``.
    """

    d_excess: object = None  # jax int32[n_cap] folded supply
    d_src: object = None  # jax int32[m_cap]
    d_dst: object = None  # jax int32[m_cap]
    d_cap: object = None  # jax int32[m_cap] folded residual bound
    d_cost: object = None  # jax int32[m_cap] UNSCALED costs
    #: scatter-maintained slot-stable plan tensors in _solve_mcmf
    #: order (graph/slot_plan.py), or None until the mirror's first
    #: plan sync (the solver then full-uploads via the plan handle)
    d_plan: object = None
    resident: object = None  # owning DeviceResidentState
    version: int = 0

    def device_scaled_cost(self):
        """Costs pre-scaled by the node count (the general-graph
        solvers' exactness convention), computed on device once per
        refresh and cached on the owning resident state."""
        return self.resident.scaled_cost(self)


def resident_solver_inputs(problem, prev_flow, prev_src, prev_dst, warm_start):
    """The device-resident solve prologue of the general-graph
    backend (jax): the dispatch args read straight from the
    persistent buffers, and the warm flow is derived ON DEVICE from the
    solver's previous flow, masked against the endpoint buffers the
    solver captured at its last successful solve. Returns
    ``(dev_args, flow0, warm)`` where dev_args is
    (cap, scaled cost, supply). One implementation so the warm-gate
    rule can never silently diverge between backends."""
    import jax.numpy as jnp

    m = problem.d_cap.shape[0]
    dev_args = (
        problem.d_cap,
        problem.device_scaled_cost(),
        problem.d_excess,
    )
    warm = (
        warm_start
        and prev_flow is not None
        and prev_flow.shape[0] == m
        and prev_src is not None
        and prev_src.shape[0] == m
    )
    if warm:
        flow0 = device_warm_flow_fn()(
            prev_flow, prev_src, prev_dst,
            problem.d_src, problem.d_dst, problem.d_cap,
        )
    else:
        flow0 = jnp.zeros(m, jnp.int32)
    return dev_args, flow0, warm


class DeviceResidentState:
    """Persistent device mirror of a DeviceGraphState's folded problem
    arrays.

    ``refresh()`` (once per round, after the journal is applied on
    host) packs the touched slots/nodes into flat int32 records, ships
    ONLY those bytes, and applies them with the one jit'd scatter. The
    mirror is rebuilt wholesale only when:

    - ``full_build`` reassigned the slot table (rebuild_count moved),
    - the arc pow2 bucket grew (m_cap changed — slot values survive but
      the buffer shape is stale), or
    - the node pow2 bucket grew (n_cap; node side only — the arc
      buffers and the warm-flow geometry survive, as they do on host).

    ``last_upload_bytes``/``last_upload_kind`` expose the EXACT nbytes
    of what crossed the host→device boundary this refresh — the
    devprof h2d accounting reads them instead of estimating from
    ChangeStats.
    """

    def __init__(self, state: DeviceGraphState) -> None:
        self.state = state
        self.d_excess = None
        self.d_src = None
        self.d_dst = None
        self.d_cap = None
        self.d_cost = None
        self._rebuild_count = -1
        self._n_cap = -1
        self._m_cap = -1
        self.version = 0
        self.last_upload_bytes = 0
        self.last_upload_kind = "full_build"
        self.last_arc_records = 0
        self.last_node_records = 0
        #: the joint record bucket of the last delta (0: went up whole)
        self.last_record_bucket = 0
        #: bucket -> compiled delta apply for the buffers as allocated
        #: (closed_set): a delta whose bucket is not here goes up whole
        self._delta_set: Dict[int, object] = {}
        self._scaled = None  # (version, jax scaled-cost buffer)
        # ---- slot-stable plan mirror (graph/slot_plan.py) ------------
        self.d_p_arc = None
        self.d_p_sign = None
        self.d_p_src = None
        self.d_p_dst = None
        self.d_inv = None
        #: boundary statics — mirror-OWNED copies (they are donated to
        #: the plan scatter when a relocation rewires them, so they
        #: must never alias the plan's own full-upload cache)
        self.d_seg = None
        self.d_isstart = None
        self.d_first = None
        self.d_last = None
        self.d_nonempty = None
        self._plan_gen = -1  # layout_gen mirrored
        self._plan_ver = -1  # value_version mirrored
        self.last_plan_kind = "none"  # none | rebuild | delta | clean
        self.last_plan_bytes = 0
        self.last_plan_records = 0
        self.last_plan_bucket = 0  # joint record bucket of a plan delta
        #: regions the plan relocated since the previous sync
        self.last_plan_relocations = 0
        self._relocations_seen = 0
        #: bucket -> compiled plan apply for the layout as uploaded
        self._plan_set: Dict[int, object] = {}
        #: sharded plan mirror mode (enable_sharded_plan): the entry-
        #: shaped plan tensors are maintained as [D, Es] stacked
        #: per-shard tables and the round's records route to their
        #: owner shards — None = single-chip mirror (the default)
        self._shard = None  # (mesh, axis, num_shards)

    # -- packing -----------------------------------------------------------

    def _pack_arcs(self, slots: np.ndarray, bucket: int) -> np.ndarray:
        st = self.state
        ka = len(slots)
        rec = np.zeros((bucket, ARC_RECORD_COLS), np.int32)
        if ka:
            rec[:ka, 0] = slots
            rec[:ka, 1] = st.src[slots]
            rec[:ka, 2] = st.dst[slots]
            rec[:ka, 3] = st.cap[slots] - st.folded_offset(slots)
            rec[:ka, 4] = st.cost[slots]
            rec[ka:] = rec[0]  # idempotent pad: repeat a real record
        else:
            rec[:, 1] = st.src[0]
            rec[:, 2] = st.dst[0]
            rec[:, 3] = st.cap[0] - st.folded_offset(0)
            rec[:, 4] = st.cost[0]
        return rec

    def _pack_nodes(self, nodes: np.ndarray, bucket: int) -> np.ndarray:
        st = self.state
        kn = len(nodes)
        rec = np.zeros((bucket, NODE_RECORD_COLS), np.int32)
        folded0 = st.excess[nodes] + st.fold[nodes] if kn else None
        if kn:
            rec[:kn, 0] = nodes
            rec[:kn, 1] = folded0.astype(np.int32)
            rec[kn:] = rec[0]
        else:
            rec[:, 1] = np.int32(int(st.excess[0]) + int(st.fold[0]))
        return rec

    # -- refresh -----------------------------------------------------------

    def _full_upload(self, problem: FlowProblem, arcs_too: bool) -> int:
        import jax.numpy as jnp

        nbytes = 0
        self.d_excess = jnp.asarray(problem.excess.astype(np.int32))
        nbytes += self.d_excess.nbytes
        if arcs_too:
            self.d_src = jnp.asarray(problem.src)
            self.d_dst = jnp.asarray(problem.dst)
            self.d_cap = jnp.asarray(problem.cap)
            self.d_cost = jnp.asarray(problem.cost.astype(np.int32))
            nbytes += (
                self.d_src.nbytes + self.d_dst.nbytes
                + self.d_cap.nbytes + self.d_cost.nbytes
            )
        return nbytes

    def ship_records(self, slots: np.ndarray, nodes: np.ndarray) -> int:
        """Bring the given arc slots and nodes of the mirror to the
        host's values and return the bytes that took: packed records
        through the scatter where their joint bucket is in the closed
        set (`delta_pack`, `delta_upload` spans), the arrays whole
        where it is past the largest one (`last_record_bucket` 0).
        A delta round's export, and the integrity ladder's re-scatter
        rung."""
        import jax.numpy as jnp

        from ..obs.spans import span

        bucket = pad_record_count(len(slots), len(nodes))
        apply_delta = self._delta_set.get(bucket)
        if apply_delta is None:
            # slots are stable, so the solvers' warm flow survives
            self.last_record_bucket = 0
            with span("delta_upload", kind="oversize_delta"):
                return self._full_upload(self.state.problem(), arcs_too=True)
        self.last_record_bucket = bucket
        with span("delta_pack", arcs=len(slots), nodes=len(nodes)):
            arc_rec = self._pack_arcs(slots, bucket)
            node_rec = self._pack_nodes(nodes, bucket)
        nbytes = arc_rec.nbytes + node_rec.nbytes
        with span("delta_upload", bytes=nbytes, bucket=bucket):
            (
                self.d_excess, self.d_src, self.d_dst, self.d_cap, self.d_cost,
            ) = apply_delta(
                self.d_excess, self.d_src, self.d_dst, self.d_cap, self.d_cost,
                jnp.asarray(arc_rec), jnp.asarray(node_rec),
            )
        return nbytes

    def refresh(self) -> DeviceResidentProblem:
        """Sync the mirror with the host state and return the
        device-resident problem handle for this round's solve."""
        from ..obs.spans import span

        st = self.state
        with span("problem_snapshot"):
            problem = st.problem()
        slots, nodes = st.drain_dirty()
        rebuilt = self._rebuild_count != st.rebuild_count
        arcs_stale = rebuilt or self._m_cap != st.m_cap or self.d_src is None
        nodes_stale = rebuilt or self._n_cap != st.n_cap or self.d_excess is None
        self.last_arc_records = len(slots)
        self.last_node_records = len(nodes)
        nbytes = 0
        if arcs_stale or nodes_stale:
            with span(
                "delta_upload",
                kind="full_build" if arcs_stale else "node_rebuild",
            ):
                nbytes = self._full_upload(problem, arcs_too=arcs_stale)
                # the buffers were (re)allocated: compile, now, every
                # shape a later delta can have
                self._delta_set = closed_set(
                    delta_apply_fn(),
                    (self.d_excess, self.d_src, self.d_dst, self.d_cap, self.d_cost),
                    (ARC_RECORD_COLS, NODE_RECORD_COLS),
                    st.m_cap,
                )
            self.last_record_bucket = 0
            nodes = nodes[:0]  # every node went up
        if not arcs_stale:
            # the arc side is delta-sized also when the node bucket
            # grew: the endpoint geometry survives, so warm flow does
            nbytes += self.ship_records(slots, nodes)
        whole = arcs_stale or nodes_stale or not self.last_record_bucket
        self.last_upload_kind = "full_build" if whole else "delta"
        self.last_upload_bytes = nbytes
        self._rebuild_count = st.rebuild_count
        self._n_cap = st.n_cap
        self._m_cap = st.m_cap
        self.version += 1
        d_plan = self._sync_plan()
        return DeviceResidentProblem(
            num_nodes=problem.num_nodes,
            excess=problem.excess,
            node_type=problem.node_type,
            src=problem.src,
            dst=problem.dst,
            cap=problem.cap,
            cost=problem.cost,
            flow_offset=problem.flow_offset,
            num_arcs=problem.num_arcs,
            d_excess=self.d_excess,
            d_src=self.d_src,
            d_dst=self.d_dst,
            d_cap=self.d_cap,
            d_cost=self.d_cost,
            d_plan=d_plan,
            resident=self,
            version=self.version,
            plan=st.plan,
            plan_key=st.plan_key(),
        )

    def enable_sharded_plan(self, mesh, axis: str = "x") -> None:
        """Maintain the slot-plan mirror in SHARDED form for the
        multi-chip rung (parallel/sharded_solver.py): the owning
        SlotPlanState switches to per-shard block layout, the
        entry-shaped device tensors become [D, Es] stacked tables
        placed by the partition rules (entry tables partitioned on the
        mesh axis, everything else replicated), and each round's dirty
        rows/segment statics ship as per-shard routed records through
        the donated shard_map scatter. Idempotent per (mesh, axis)."""
        D = int(mesh.shape[axis])
        if self._shard is not None and self._shard[0] is mesh and self._shard[1] == axis:
            return
        self._shard = (mesh, axis, D)
        self.state.plan.enable_sharding(D)
        self._plan_gen = -1  # mode flip: next sync re-uploads wholesale

    def _upload_plan_full(self, plan) -> None:
        """Fresh plan buffers from the host truth — the rebuild path
        AND the integrity ladder's reupload rung. In sharded mode the
        entry-shaped tensors are placed as [D, Es] stacked tables on
        the mesh; the rest replicate."""
        import jax.numpy as jnp

        if self._shard is None:
            self.d_p_arc = jnp.asarray(plan.p_arc)
            self.d_p_sign = jnp.asarray(plan.p_sign)
            self.d_p_src = jnp.asarray(plan.p_src)
            self.d_p_dst = jnp.asarray(plan.p_dst)
            self.d_inv = jnp.asarray(plan.inv_order)
            self.d_seg = jnp.asarray(plan.seg_start)
            self.d_isstart = jnp.asarray(plan.is_start)
            self.d_first = jnp.asarray(plan.node_first)
            self.d_last = jnp.asarray(plan.node_last)
            self.d_nonempty = jnp.asarray(plan.node_nonempty)
            return
        from ..parallel.sharded_solver import place_sharded_plan

        mesh, axis, D = self._shard
        (
            self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
            self.d_seg, self.d_isstart, self.d_inv,
            self.d_first, self.d_last, self.d_nonempty,
        ) = place_sharded_plan(
            mesh, axis, plan.host_args(), D, plan.block_extent
        )

    def _scatter_plan_delta(self, plan) -> Tuple[int, int]:
        """Apply a round's dirty plan records; (bytes, records)."""
        import jax.numpy as jnp

        from ..obs.spans import span

        if self._shard is None:
            row_rec, inv_rec, seg_rec, node_rec = plan.drain_records()
            rec_bytes = (
                row_rec.nbytes + inv_rec.nbytes
                + seg_rec.nbytes + node_rec.nbytes
            )
            self.last_plan_bucket = len(row_rec)
            with span(
                "plan_upload", kind="delta", bytes=rec_bytes,
                bucket=len(row_rec),
            ):
                (
                    self.d_p_arc, self.d_p_sign, self.d_p_src,
                    self.d_p_dst, self.d_inv,
                    self.d_seg, self.d_isstart,
                    self.d_first, self.d_last, self.d_nonempty,
                ) = self._plan_set[len(row_rec)](
                    *self._plan_buffers(),
                    jnp.asarray(row_rec), jnp.asarray(inv_rec),
                    jnp.asarray(seg_rec), jnp.asarray(node_rec),
                )
            return rec_bytes, 4 * len(row_rec)
        from ..parallel.sharded_solver import (
            replicated_plan_apply_fn,
            sharded_plan_apply_fn,
        )

        mesh, axis, D = self._shard
        row_rec, seg_rec, inv_rec, node_rec = plan.drain_records_sharded()
        rec_bytes = (
            row_rec.nbytes + seg_rec.nbytes
            + inv_rec.nbytes + node_rec.nbytes
        )
        with span(
            "plan_upload", kind="sharded_delta", bytes=rec_bytes, shards=D
        ):
            (
                self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
                self.d_seg, self.d_isstart,
            ) = sharded_plan_apply_fn(mesh, axis)(
                self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
                self.d_seg, self.d_isstart,
                jnp.asarray(row_rec), jnp.asarray(seg_rec),
            )
            (
                self.d_inv, self.d_first, self.d_last, self.d_nonempty,
            ) = replicated_plan_apply_fn()(
                self.d_inv, self.d_first, self.d_last, self.d_nonempty,
                jnp.asarray(inv_rec), jnp.asarray(node_rec),
            )
        records = (
            int(np.prod(row_rec.shape[:2])) + int(np.prod(seg_rec.shape[:2]))
            + len(inv_rec) + len(node_rec)
        )
        return rec_bytes, records

    def _plan_buffers(self) -> Tuple:
        """The ten mirrored plan tensors in `plan_apply_fn`'s argument
        order (FP_PLAN_ARRAYS order)."""
        return (
            self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
            self.d_inv, self.d_seg, self.d_isstart,
            self.d_first, self.d_last, self.d_nonempty,
        )

    def plan_fingerprints(self) -> np.ndarray:
        """uint32 checksum per mirrored plan tensor, FP_PLAN_ARRAYS
        order — the sharded mirror psums per-shard partials with
        global-index weights, so both modes compare against the SAME
        host twins (runtime/integrity.StateAuditor)."""
        bufs = self._plan_buffers()
        if self._shard is None:
            from ..runtime.integrity import device_fingerprints

            return device_fingerprints(bufs)
        from ..parallel.sharded_solver import sharded_plan_fingerprint_fn

        mesh, axis, _D = self._shard
        fps = sharded_plan_fingerprint_fn(mesh, axis)(*bufs)
        return np.asarray(fps).astype(np.int32).view(np.uint32)

    def _sync_plan(self):
        """Mirror the slot-stable plan (graph/slot_plan.py) as
        persistent device tensors. Inactive until a slot-stable
        consumer enables the plan (so non-jax backends pay nothing);
        afterwards each round ships only the dirty plan rows / inv
        entries through the ONE jit'd plan scatter (per-shard routed
        in sharded mode), and the full re-upload survives only on
        layout rebuilds (full_build, pow2 bucket growth, region
        overflow). Returns the plan tensors in `_solve_mcmf` order
        (entry-shaped ones stacked [D, Es] in sharded mode), or None
        while inactive."""
        from ..obs.spans import span

        plan = self.state.plan
        self.last_plan_kind = "none"
        self.last_plan_bytes = 0
        self.last_plan_records = 0
        if plan is None or not plan.enabled:
            return None
        plan.ensure_built()
        self.last_plan_relocations = plan.region_relocations - self._relocations_seen
        self._relocations_seen = plan.region_relocations
        self.last_plan_bucket = 0
        rebuilt = self._plan_gen != plan.layout_gen
        if rebuilt or (
            # a delta past the largest compiled bucket goes up whole
            self._shard is None and plan.record_bucket() not in self._plan_set
        ):
            # layout rebuilt: fresh buffers all around (they will be
            # donated by later scatters, so never share the plan's own
            # full-upload cache)
            with span("plan_upload", kind="rebuild" if rebuilt else "oversize_delta"):
                self._upload_plan_full(plan)
                if rebuilt and self._shard is None:
                    # new buffers: compile, now, every shape a later
                    # plan delta can have
                    from .slot_plan import PLAN_STREAM_COLS, plan_apply_fn

                    self._plan_set = closed_set(
                        plan_apply_fn(), self._plan_buffers(),
                        PLAN_STREAM_COLS, plan.entry_cap,
                    )
            plan.clear_pending()
            self._plan_gen = plan.layout_gen
            self._plan_ver = plan.value_version
            self.last_plan_kind = "rebuild"
            self.last_plan_bytes = plan.values_nbytes() + plan.static_nbytes()
            self.last_upload_bytes += self.last_plan_bytes
        elif plan.value_version != self._plan_ver or plan.has_pending:
            rec_bytes, records = self._scatter_plan_delta(plan)
            self._plan_ver = plan.value_version
            self.last_plan_kind = "delta"
            self.last_plan_bytes = rec_bytes
            self.last_plan_records = records
            self.last_upload_bytes += self.last_plan_bytes
        else:
            self.last_plan_kind = "clean"
        return (
            self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
            self.d_seg, self.d_isstart, self.d_inv,
            self.d_first, self.d_last, self.d_nonempty,
        )

    def scaled_cost(self, problem: DeviceResidentProblem):
        """d_cost * num_nodes, computed on device, cached per refresh."""
        if self._scaled is None or self._scaled[0] != problem.version:
            import jax.numpy as jnp

            scaled = _scale_cost_fn()(
                problem.d_cost, jnp.int32(problem.num_nodes)
            )
            self._scaled = (problem.version, scaled)
        return self._scaled[1]

    def rebind(self, problem: "DeviceResidentProblem") -> None:
        """Re-point a problem handle at the mirror's CURRENT buffers.
        Required after any out-of-band buffer replacement (divergence
        repair, injected corruption): the repair/poison scatters
        produce new buffers (and repairs may donate the old ones), so
        a handle built at refresh time would read dead or stale
        arrays."""
        problem.d_excess = self.d_excess
        problem.d_src = self.d_src
        problem.d_dst = self.d_dst
        problem.d_cap = self.d_cap
        problem.d_cost = self.d_cost
        if problem.d_plan is not None and self._plan_gen >= 0:
            problem.d_plan = (
                self.d_p_arc, self.d_p_sign, self.d_p_src, self.d_p_dst,
                self.d_seg, self.d_isstart, self.d_inv,
                self.d_first, self.d_last, self.d_nonempty,
            )
        self._scaled = None

    def parity_check(self) -> None:
        """Verify the device mirror equals the host folded view
        bit-for-bit (fetches the buffers; audit/debug — the cheap
        per-round path is the fingerprint audit in
        runtime/integrity.py). Raises a structured IntegrityError
        carrying a bounded diff (first-k mismatching indices,
        expected vs found)."""
        from ..runtime.integrity import bounded_diff

        problem = self.state.problem()
        pairs = (
            (self.d_excess, problem.excess.astype(np.int32)),
            (self.d_src, problem.src),
            (self.d_dst, problem.dst),
            (self.d_cap, problem.cap),
            (self.d_cost, problem.cost.astype(np.int32)),
        )
        names = ("excess", "src", "dst", "cap", "cost")
        for name, (dev, host) in zip(names, pairs):
            got = np.asarray(dev)
            if not np.array_equal(got, host):
                raise bounded_diff(f"device mirror {name}", got, host)

    def plan_parity_check(self) -> None:
        """Assert the scatter-maintained device plan tensors equal the
        host-maintained plan arrays bit-for-bit (the full-rebuild
        materialization; test/debug only)."""
        plan = self.state.plan
        if plan is None or not plan.enabled or self._plan_gen < 0:
            return
        if plan.needs_rebuild or self._plan_gen != plan.layout_gen or (
            self._plan_ver != plan.value_version
        ):
            return  # mirror legitimately behind (mutations since refresh)
        pairs = (
            ("p_arc", self.d_p_arc, plan.p_arc),
            ("p_sign", self.d_p_sign, plan.p_sign),
            ("p_src", self.d_p_src, plan.p_src),
            ("p_dst", self.d_p_dst, plan.p_dst),
            ("inv_order", self.d_inv, plan.inv_order),
            ("seg_start", self.d_seg, plan.seg_start),
            ("is_start", self.d_isstart, plan.is_start),
            ("node_first", self.d_first, plan.node_first),
            ("node_last", self.d_last, plan.node_last),
            ("node_nonempty", self.d_nonempty, plan.node_nonempty),
        )
        from ..runtime.integrity import bounded_diff

        for name, dev, host in pairs:
            got = np.asarray(dev)
            if got.ndim > 1:  # sharded [D, Es] stacking of the [E] host tensor
                got = got.reshape(-1)
            if not np.array_equal(got, host):
                raise bounded_diff(f"device plan mirror {name}", got, host)


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(__name__, "delta_apply", "warm_flow", "scale_cost")
