"""Device-resident bulk scheduling: the end-to-end TPU round.

scheduler/bulk.py keeps cluster state in host numpy and ships a problem
to the solver every round. That design pays a host<->device round trip
per scheduling round, which on real deployments can dominate the
actual solve. This module is the next step
of the same design: the ENTIRE cluster state — task table, placements,
per-PU occupancy, machine membership — lives in device arrays, and one
scheduling round (capacity refresh -> class census -> transport solve ->
flow decode -> placement apply) is a single jitted program. Rounds chain
on device with no host synchronization; bindings are fetched
asynchronously outside the round, exactly where the reference's round
timer stops (the reference times ScheduleAllJobs and pushes Bindings to
the API server after the timed region — cmd/k8sscheduler/scheduler.go:
146-187).

The solve is the dense layered transport kernel — dispatched via
ops.transport_solve: the fused Pallas kernel on TPU, the XLA phase loop
elsewhere; both exit on convergence under a safety bound (`supersteps`),
and each round reports a `converged` flag that callers assert on fetch. The decode is fully vectorized:
placed tasks are rank-matched to machine grants by a binary search of
each row's rank in its group's cumulative grants, and to a PU by a
search in that machine's cumulative room (`place_by_search`): O(W log M)
for a window of W rows, and no array of [W, M], which at 262,144 rows x
12,500 machines would be 13 GB.

Machines may differ in size: every machine is padded to `pus_per_machine`
PUs and `pu_slots` says what each PU holds (`slots_per_pu`, or 0 for a
PU its machine does not have). PU p belongs to machine p // P either way.

Graph semantics are identical to BulkCluster (same aggregate topology,
same pin-on-place preemption-off accounting, same unscheduled-escape
policy); tests drive both against the same scenario and require equal
placement counts and objectives.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..solver.layered import (
    COST_SCALE_LIMIT,
    choose_eps0,
    pad_geometry,
    solve_row_constant,
    split_grants_by_class,
    transport_fori,
    transport_fori_tiered,
    validate_alpha,
    validate_job_unsched_cost,
)


class DeviceClusterState(NamedTuple):
    live: jnp.ndarray  # bool[Tcap]
    cls: jnp.ndarray  # int32[Tcap]
    job: jnp.ndarray  # int32[Tcap]
    pu: jnp.ndarray  # int32[Tcap]; PU index or -1
    pu_running: jnp.ndarray  # int32[num_pus]
    machine_enabled: jnp.ndarray  # bool[M]
    #: interchangeability group per task (group mode; all-zero otherwise)
    grp: jnp.ndarray  # int32[Tcap]


class GroupSpec(NamedTuple):
    """Device-resident group metadata (group mode): row g of the
    transport is one interchangeability class of tasks — same task
    class, same escape cost, same per-machine cost profile. This is how
    per-task preference arcs (graph_manager.go:1229-1264,
    costmodel/interface.go:105-110 GetTaskPreferenceArcs) ride the
    dense fast path: tasks sharing a preference signature share a row,
    and the signature's preferred machines become per-row cost
    overrides (pref_w) min'd into the class cost row. Arrays live on
    device and are passed as traced args, so the host can update them
    (new signatures, wait-cost aging) without recompiling the round."""

    cls: jnp.ndarray  # int32[G] class of each group (census/cost row)
    job: jnp.ndarray  # int32[G] job of each group (bookkeeping)
    e: jnp.ndarray  # int32[G] task->EC route base cost (per group)
    u: jnp.ndarray  # int32[G] escape (unsched) cost per group
    pref_w: jnp.ndarray  # int32[G, M] absolute route cost overrides;
    #                      PREF_NONE where the group has no preference


#: the scalars of a round in the order `serve_round`'s summary holds them
SERVED_SUMMARY = (
    "placed", "unscheduled", "converged", "cost_overflow", "supersteps", "live", "objective",
)

#: pref_w fill for "no preference": large enough to never win the min
#: against any guarded route cost, small enough that min() arithmetic
#: cannot overflow int32
PREF_NONE = 1 << 30


def count_le(table, start, length: int, x):
    """For each of the W rows: how many of the `length` (static) entries
    of `table` from `start[t]` on are <= `x[t]`. Every such segment is
    nondecreasing, so this is a binary search of ceil(log2(length + 1))
    steps, each one gather of W scalars. (`jnp.searchsorted` searches one
    sorted array: the segments would have to be keyed apart, group x the
    largest count, which overflows int32 at thousands of groups.)"""
    lo = jnp.zeros_like(x)
    hi = jnp.full_like(x, length)
    for _ in range(int(length).bit_length()):
        mid = (lo + hi) // 2
        at = table[start + jnp.minimum(mid, length - 1)]
        right = (lo < hi) & (at <= x)
        lo, hi = jnp.where(right, mid + 1, lo), jnp.where(right, hi, jnp.minimum(mid, hi))
    return lo


def place_by_search(g, rank, grants_gm, pu_free, pus_per_machine: int):
    """The PU of the row that is the `rank`-th (0-based, i32[W]) of group
    `g` (i32[W], within [0, G)) under the solver's grants `grants_gm`
    i32[G, M], over PUs with `pu_free` i32[M * P] slots to give: its
    machine by a search of the rank in its group's cumulative grants, its
    slot there after the groups before its own, its PU by a search of the
    slot in that machine's cumulative room. Returns pu_abs i32[W]; a row
    whose rank is beyond its group's grants gets an index that means
    nothing (the caller masks it). O(W log M), and no [W, M] array: at a
    table of 262,144 rows over 12,500 machines one would be 13 GB."""
    i32 = jnp.int32
    M, P = grants_gm.shape[1], int(pus_per_machine)
    flat_cum = jnp.cumsum(grants_gm, axis=1).reshape(-1)  # each group's row nondecreasing
    row0 = g * i32(M)
    machine = count_le(flat_cum, row0, M, rank)  # the grant's machine; M when beyond
    m_at = jnp.minimum(machine, i32(M - 1))
    excl_at = jnp.where(machine > 0, flat_cum[row0 + jnp.maximum(machine, 1) - 1], i32(0))
    offs = (jnp.cumsum(grants_gm, axis=0) - grants_gm).reshape(-1)
    slot = offs[row0 + m_at] + (rank - excl_at)  # within-machine slot

    # split each machine's grant across its PUs in slot order
    t_m = jnp.sum(grants_gm, axis=0)
    pf2 = pu_free.reshape(M, P)
    exclg = jnp.cumsum(pf2, axis=1) - pf2
    grants_pu = jnp.clip(t_m[:, None] - exclg, 0, pf2)
    cumg = jnp.cumsum(grants_pu, axis=1).reshape(-1)
    return machine * i32(P) + count_le(cumg, m_at * i32(P), P, slot)


class DeviceBulkCluster:
    """Flat device-array cluster; one jitted program per scheduling round."""

    def __init__(
        self,
        num_machines: int,
        pus_per_machine: int,
        slots_per_pu: int,
        num_jobs: int,
        num_task_classes: int = 1,
        task_capacity: int = 2048,
        unsched_cost: int = 5,
        ec_cost: int = 2,
        class_cost_fn: Optional[Callable] = None,  # census[M,C] -> int32[C,M], traceable
        supersteps: Optional[int] = None,
        decode_width: Optional[int] = None,  # steady-round decode window
        alpha: int = 8,  # eps-schedule divisor for iterative solves
        job_unsched_cost: Optional[np.ndarray] = None,
        preemption: bool = False,
        continuation_discount: int = 1,
        preempt_every: int = 1,
        preempt_drift: int = 0,
        preempt_global_every: int = 0,
        preempt_scope_tau: int = 1,
        preempt_scoped_width: Optional[int] = None,
        preempt_incr_budget: Optional[int] = None,
        track_realized_cost: bool = False,
        num_groups: int = 0,
        active_groups_cap: int = 256,
        refine_waves: int = 8,
        two_stage_eps0: str = "one",
        pu_slots: Optional[np.ndarray] = None,  # int[num_pus]: slots_per_pu, or 0 (no such PU)
    ) -> None:
        self.M = num_machines
        self.P = pus_per_machine
        self.S = slots_per_pu
        self.J = num_jobs
        self.C = num_task_classes
        self.num_pus = num_machines * pus_per_machine
        # Machines that differ in size share one table: each is padded to
        # `pus_per_machine` PUs, and a PU its machine does not have holds
        # 0 slots. Every program reads a PU's or a machine's room from
        # this vector, never from the scalar.
        if pu_slots is None:
            pu_slots = np.full(self.num_pus, slots_per_pu, np.int32)
        pu_slots = np.asarray(pu_slots, np.int32)
        if pu_slots.shape != (self.num_pus,) or not np.isin(pu_slots, (0, slots_per_pu)).all():
            raise ValueError(
                f"pu_slots must hold {self.num_pus} entries, each {slots_per_pu} (a PU) "
                "or 0 (a PU its machine does not have)"
            )
        self.pu_slots = pu_slots
        self.Tcap = int(task_capacity)
        self.unsched_cost = int(unsched_cost)
        self.ec_cost = int(ec_cost)
        self.class_cost_fn = class_cost_fn
        self.alpha = validate_alpha(alpha)
        # Per-job unsched costs (graph_manager.go:1291-1305: each job's
        # unsched aggregator has its own cost). When set, (job, class)
        # pairs become distinct transport commodities: the solve's row
        # axis expands from C classes to G = J*C groups, g = j*C + c.
        # Intended for moderate J (tens to low hundreds): the dense
        # transport carries [G, M] state and the decode a [W, G]
        # one-hot, both linear in G — at thousands of jobs the CSR
        # graph path (per-task unsched arcs) is the right tool.
        self.job_unsched_cost = validate_job_unsched_cost(
            job_unsched_cost, num_jobs
        )
        job_unsched_cost = self.job_unsched_cost  # normalized array/None
        self.per_job = job_unsched_cost is not None
        self.G = num_jobs * num_task_classes if self.per_job else num_task_classes
        # Group mode: rows are caller-defined interchangeability groups
        # (see GroupSpec) instead of classes / (job, class) pairs. The
        # group axis is static (capacity num_groups); metadata arrives
        # as traced device arrays so signatures can be registered and
        # escape costs aged between rounds without recompiling.
        self.grouped = num_groups > 0
        if self.grouped:
            if self.per_job:
                raise ValueError(
                    "num_groups and job_unsched_cost are exclusive: group "
                    "escape costs (GroupSpec.u) subsume per-job unsched costs"
                )
            self.G = int(num_groups)
        # rows the COMPACTED grouped solve can hold. An int is one
        # compaction width; a sequence is a LADDER of widths — the
        # round picks the smallest width that fits the live active-row
        # count (nested lax.cond, each width compiled once), so
        # diversity-pressure configs whose active set exceeds the first
        # cap degrade to a mid-width solve instead of jumping straight
        # to full G width (VERDICT r3 #2: the multiblock tail was
        # full-width 512-row solves past a single 256-row cap).
        if isinstance(active_groups_cap, (int, np.integer)):
            caps = (int(active_groups_cap),)
        else:
            caps = tuple(int(c) for c in active_groups_cap)
        if not caps or any(c < 1 for c in caps):
            raise ValueError("active_groups_cap entries must be >= 1")
        caps = tuple(sorted({min(c, max(self.G, 1)) for c in caps}))
        self.active_groups_caps = caps
        #: largest ladder width (back-compat scalar view; == the single
        #: cap when an int was passed)
        self.active_groups_cap = caps[-1]
        # Price refinement between eps phases (solver/layered.py
        # _price_refine) for the iterative solves. Default ON for the
        # device path: measured 2.2x fewer supersteps on contended
        # CoCo-50k steady rounds (mean 2013 -> 925) and 6-12x on
        # grouped locality instances. The HOST solvers
        # (LayeredTransportSolver, ShardedLayeredSolver) keep
        # refine_waves=0 — their cross-backend bit-identity contracts
        # compare superstep-for-superstep.
        self.refine_waves = int(refine_waves)
        # Stage-1 eps schedule of the grouped two-stage solve — REGIME-
        # DEPENDENT (docs/NOTES.md): "one" (eps0=1, budget 256) wins on
        # near-uniform discounts (single-block Quincy: tens of waves
        # when pref capacity suffices); "quarter" (n_scale/4, budget
        # 1024) wins on heavy-tailed discounts (multi-block: captured
        # tail rounds 3580 -> 51 supersteps — the eps=1 schedule pays
        # for ~190-unit discount descents in unit bounces, r4 sweep
        # via tools/tail_repro.py replay-grouped).
        if two_stage_eps0 not in ("one", "quarter"):
            raise ValueError("two_stage_eps0 must be 'one' or 'quarter'")
        self.two_stage_eps0 = two_stage_eps0
        # Preemption (keep-arcs semantics, graph_manager.go:855-888):
        # every round's solve reconsiders PLACED tasks too — staying on
        # the current machine is discounted by `continuation_discount`
        # (the aggregate TaskContinuationCost, interface.go:75-79),
        # moving pays full price, escaping pays the unsched cost (the
        # aggregate TaskPreemptionCost). Machine capacity counts total
        # slots, not free ones (the :662-667 rule flips). The round
        # emits PLACE / MIGRATE / PREEMPT counts; the solve is the
        # tiered transport (solver/layered.py transport_fori_tiered).
        self.preemption = bool(preemption)
        self.continuation_discount = int(continuation_discount)
        # Stability-aware (incremental) preemption: the reference keeps
        # round cost proportional to the DELTA even with preemption on
        # (placement/solver.go:60-90 — running tasks keep their arcs,
        # the incremental solver re-prices only changes). The TPU form:
        # scanned rounds run the cheap incremental core (residents
        # pinned, bounded backlog decode) and a FULL tiered re-solve
        # fires every `preempt_every` rounds OR when the running-class
        # census has drifted by more than `preempt_drift` task
        # positions since the last full solve (L1 distance, device-
        # computed) — so migration opportunities accumulate bounded
        # staleness instead of being re-derived from scratch every
        # round. preempt_every=1 (default) is the pure per-round
        # re-solve; preempt_drift=0 disables the drift trigger.
        self.preempt_every = int(preempt_every)
        self.preempt_drift = int(preempt_drift)
        # Three-tier stability (VERDICT r4 #2): with this knob on,
        # cadence/drift re-solves become SCOPED (drifted columns +
        # backlog re-solve; out-of-scope residents pinned) and a truly
        # GLOBAL tiered re-solve fires only every preempt_global_every
        # rounds — rare enough to sit outside p99 while bounding how
        # long scoping can defer multi-hop migration chains.
        self.preempt_global_every = int(preempt_global_every)
        # Scope membership threshold: a machine joins a scoped
        # re-solve when the L1 distance between its running-class
        # census and the drift reference reaches tau. Measured at the
        # coco50k shape (docs/NOTES.md round-5): after 12 incremental
        # rounds 807/1000 machines have SOME drift (scope-on-any-change
        # is a full solve in disguise) but tau=12 concentrates 53% of
        # the total L1 on 144 machines — the thresholded scope is what
        # makes scoped rounds small.
        self.preempt_scope_tau = int(preempt_scope_tau)
        # Mover-decode window for scoped rounds (None = Tcap-wide).
        # Must comfortably exceed the plausible scoped mover count:
        # a binding window PARKS displaced residents (pu=-1) and the
        # resulting backlog craters the census — measured 2.8M -> 14M
        # realized cost on the toy config when scope-everything met a
        # 4096 window.
        self.preempt_scoped_width = (
            None if preempt_scoped_width is None
            else int(preempt_scoped_width)
        )
        # Incremental-round superstep budget (three-tier only): the
        # backlog-admission solve of an incremental round occasionally
        # hits the eps-slosh regime against drifted census costs —
        # measured monsters of 42.7k and 62.3k supersteps (~1-in-40k
        # rounds, top_rounds forensics r5; drift value at the monster
        # is 4-10k, i.e. NOT predicted by the drift trigger, and
        # lowering the trigger measured WORSE). With a budget set, the
        # incremental attempt is bounded and a non-converged attempt
        # ESCALATES the round to the scoped tier (discarding the
        # attempt, re-pricing the drifted columns) — the incremental
        # tail becomes min(monster, budget + scoped-round cost) by
        # construction. None disables (bit-identical legacy rounds).
        self.preempt_incr_budget = (
            None if preempt_incr_budget is None else int(preempt_incr_budget)
        )
        if self.preempt_every < 1:
            raise ValueError("preempt_every must be >= 1")
        if self.preempt_drift < 0:
            raise ValueError("preempt_drift must be >= 0")
        if self.preempt_global_every < 0:
            raise ValueError("preempt_global_every must be >= 0")
        if self.preempt_scope_tau < 1:
            raise ValueError("preempt_scope_tau must be >= 1")
        self.hybrid_preempt = self.preemption and (
            self.preempt_every > 1 or self.preempt_drift > 0
        )
        if self.preempt_global_every > 0 and not self.hybrid_preempt:
            raise ValueError(
                "preempt_global_every requires stability-aware "
                "preemption (preempt_every > 1 or preempt_drift > 0)"
            )
        if self.preempt_incr_budget is not None:
            if self.preempt_incr_budget < 1:
                raise ValueError("preempt_incr_budget must be >= 1")
            if self.preempt_global_every <= 0:
                raise ValueError(
                    "preempt_incr_budget requires the three-tier scheme "
                    "(preempt_global_every > 0) — escalation targets the "
                    "scoped tier"
                )
        # Opt-in quality metric: pricing the whole assignment costs an
        # extra cost_fn + Tcap gather per round INSIDE the timed scan —
        # the parity tests turn it on; benches leave it off so the
        # metric cannot inflate the latencies it exists to defend.
        self.track_realized_cost = bool(track_realized_cost)
        if self.preemption:
            if continuation_discount < 0:
                raise ValueError("continuation_discount must be >= 0")
            # decode_width in preemption mode bounds the MOVER decode
            # (stays keep their PU without decoding) — see
            # round_core_preempt
        if decode_width is not None:
            if decode_width <= 0:
                raise ValueError(
                    f"decode_width must be positive, got {decode_width}"
                )
            if decode_width >= task_capacity:
                decode_width = None  # wider than the pool = the full path
        self.decode_width = None if decode_width is None else int(decode_width)
        # Degenerate = every group shares one cost row (no class cost
        # model, and no per-job cost spread): the solve collapses to
        # the exact closed form regardless of G. Group mode is assumed
        # heterogeneous (preference overrides differentiate rows).
        self.class_degenerate = (
            not self.grouped
            and class_cost_fn is None
            and (
                job_unsched_cost is None
                or bool((job_unsched_cost == job_unsched_cost[0]).all())
            )
        )
        # Row-constant: each (job, class) row's cost is machine-uniform
        # (per-job unsched costs but no class cost model) — rows differ
        # from each other, so the class-degenerate collapse doesn't
        # apply, but the fractional-knapsack closed form
        # (solver/layered.py solve_row_constant) is exact. Without it
        # the iterative solve herds pathologically at scale (the
        # 12.5k-machine livelock of docs/NOTES.md, per-job flavor).
        self.row_constant = (
            not self.grouped and self.per_job and class_cost_fn is None
        )
        # A positive continuation discount makes cells residency-
        # dependent, so the degenerate collapse only applies to
        # preemption mode at discount 0 (where the tiers coincide and
        # the ordinary solve serves).
        if self.preemption and self.continuation_discount > 0:
            self.class_degenerate = False
            self.row_constant = False
        # Closed-form solves (G == 1 or degenerate) take no iterations;
        # otherwise the cost-scaling schedule runs under a
        # lax.while_loop that exits on convergence — this is only the
        # safety bound, not the cost.
        self.supersteps = int(
            supersteps if supersteps is not None
            else (
                1
                if (self.G == 1 or self.class_degenerate or self.row_constant)
                else 16384
            )
        )

        # Padded transport columns: [machines | zero-cap padding | unsched]
        self.Mp, self.n_scale = pad_geometry(num_machines, self.G)

        self.state = DeviceClusterState(
            live=jnp.zeros(self.Tcap, jnp.bool_),
            cls=jnp.zeros(self.Tcap, jnp.int32),
            job=jnp.zeros(self.Tcap, jnp.int32),
            pu=jnp.full(self.Tcap, -1, jnp.int32),
            pu_running=jnp.zeros(self.num_pus, jnp.int32),
            machine_enabled=jnp.ones(self.M, jnp.bool_),
            grp=jnp.zeros(self.Tcap, jnp.int32),
        )
        # stability-aware preemption bookkeeping (see preempt_every):
        # the running-class census at the last FULL re-solve and the
        # rounds elapsed since. k starts saturated so the first scanned
        # round is a full solve (host mutations before it are unseen
        # drift).
        self._hyb_census = jnp.zeros((self.M, self.C), jnp.int32)
        self._hyb_k = jnp.int32(self.preempt_every - 1)
        # rounds since the last GLOBAL re-solve; starts saturated so
        # the first fired re-solve of a scan is global (host mutations
        # before it are unseen drift for EVERY column)
        self._hyb_kg = jnp.int32(
            max(self.preempt_global_every - 1, 0)
        )
        # Benign defaults until set_groups: every group is class 0 /
        # job 0 at the scalar costs with no preferences.
        self.groups = GroupSpec(
            cls=jnp.zeros(self.G, jnp.int32),
            job=jnp.zeros(self.G, jnp.int32),
            e=jnp.full(self.G, self.ec_cost, jnp.int32),
            u=jnp.full(self.G, self.unsched_cost, jnp.int32),
            pref_w=jnp.full((self.G, self.M), PREF_NONE, jnp.int32),
        ) if self.grouped else None
        # Host mirror of GroupSpec.cls so group-only admissions can
        # derive per-task classes without a device fetch (a host sync
        # in the middle of a chain of rounds).
        self._groups_cls_host = (
            np.zeros(self.G, np.int32) if self.grouped else None
        )
        #: steady-round arrival group draw map (see set_arrival_groups)
        self._arrival_map = jnp.arange(max(self.G, 1), dtype=jnp.int32)
        self._arrival_n = jnp.int32(max(self.G, 1))
        self._zeros_by_width: dict = {}
        self._build_programs()
        self.last_stats: Optional[dict] = None
        self.last_admitted = None  # device i32 from the latest add_tasks

    # ------------------------------------------------------------------
    # jitted programs (closures over the static geometry)
    # ------------------------------------------------------------------

    def _build_programs(self) -> None:
        M, P, C, Tcap, Mp = self.M, self.P, self.C, self.Tcap, self.Mp
        num_pus, J = self.num_pus, self.J
        pu_slots = jnp.asarray(self.pu_slots)  # [num_pus]: what each PU holds
        machine_slots = jnp.asarray(self.pu_slots.reshape(M, P).sum(axis=1))  # [M]
        u_cost, e_cost = self.unsched_cost, self.ec_cost
        n_scale = self.n_scale
        supersteps = self.supersteps
        cost_fn = self.class_cost_fn
        alpha = self.alpha
        steady_decode_width = self.decode_width
        i32 = jnp.int32
        per_job, Gn = self.per_job, self.G
        grouped = self.grouped
        # The one-hot decode's [W, Gn] x [Gn, M] matmuls scale as
        # W*Gn*M MACs; beyond ~2M Gn*M cells the sort+row-gather decode
        # wins regardless of mode (e.g. per-job rows at trace scale:
        # 256 groups x 12.5k machines). Static choice per geometry.
        use_sorted_decode = grouped or (Gn * M >= (1 << 21))
        active_caps = self.active_groups_caps
        class_degenerate = self.class_degenerate
        row_constant = self.row_constant
        preempt, discount = self.preemption, self.continuation_discount
        stage1_quarter = self.two_stage_eps0 == "quarter"
        hybrid = self.hybrid_preempt
        preempt_every = self.preempt_every
        preempt_drift = self.preempt_drift
        global_every = self.preempt_global_every
        scope_tau = self.preempt_scope_tau
        scoped_width = self.preempt_scoped_width
        incr_budget = self.preempt_incr_budget
        track_realized = self.track_realized_cost
        refine_waves = self.refine_waves
        # Per-row (group) escape costs: row g = j*C + c escapes at job
        # j's unsched cost; without per-job costs every row uses the
        # scalar. Closure constant — baked into the compiled round.
        u_row = jnp.asarray(
            np.repeat(self.job_unsched_cost, C).astype(np.int32)
            if per_job
            else np.full(Gn, u_cost, np.int32)
        )

        def census_of(state: DeviceClusterState):
            """Per-machine running-class census [M, C] (the vectorized
            WhareMapStats, whare_map_stats.proto:12-18)."""
            placed = state.live & (state.pu >= 0)
            machine = jnp.clip(state.pu, 0, num_pus - 1) // P
            idx = jnp.where(placed, machine * C + state.cls, M * C)
            flat = jnp.zeros(M * C + 1, i32).at[idx].add(1)
            return flat[: M * C].reshape(M, C)

        def rank_match_decode(g_safe, grants_gm, pu_free):
            """Rank-match participant rows to machine grants — the
            shared decode of both round flavors. g_safe [W] holds each
            row's group (sentinel Gn = not participating), grants_gm
            [Gn, M] the solver's per-group machine grants, pu_free
            [num_pus] the slots these grants may occupy. Returns
            (granted bool[W], pu_abs i32[W]).

            In-group ranks come from one one-hot [W, Gn] cumsum — no
            per-group Python loop. precision=HIGHEST: TPU f32 matmuls
            default to bf16 passes, whose 8-bit mantissa corrupts counts
            beyond 256; all counts here are < 2^24, so f32 at HIGHEST is
            exact. The machine and the PU of a rank: `place_by_search`."""
            hi = jax.lax.Precision.HIGHEST
            part = g_safe < i32(Gn)
            onehot = (
                g_safe[:, None] == jnp.arange(Gn, dtype=i32)[None, :]
            ).astype(jnp.float32)  # [W, Gn]; sentinel rows hit no column
            cum_oh = jnp.cumsum(onehot, axis=0)
            rank_f = jnp.sum((cum_oh - onehot) * onehot, axis=1)  # excl rank
            quota = jnp.einsum(
                "tg,g->t", onehot,
                jnp.sum(grants_gm, axis=1).astype(jnp.float32), precision=hi,
            )
            granted = part & (rank_f < quota)
            pu_abs = place_by_search(
                jnp.minimum(g_safe, i32(Gn - 1)), rank_f.astype(i32), grants_gm, pu_free, P
            )
            return granted, pu_abs

        def rank_match_decode_grouped(g_safe, grants_gm, pu_free):
            """Group-mode twin of rank_match_decode for LARGE group
            counts: the one-hot path's [W, Gn] arrays scale as W*Gn —
            prohibitive at thousands of groups. This variant computes
            in-group ranks with ONE stable sort. Same output contract as
            rank_match_decode: (granted bool[W], pu_abs i32[W])."""
            W = g_safe.shape[0]
            part = g_safe < i32(Gn)
            # in-group exclusive rank via one stable sort (same trick
            # as the preempt decode's per-cell resident ranks)
            order = jnp.argsort(g_safe, stable=True)
            counts = jnp.zeros(Gn + 1, i32).at[g_safe].add(1)
            starts = jnp.cumsum(counts) - counts
            rank_sorted = jnp.arange(W, dtype=i32) - starts[g_safe[order]]
            rank = jnp.zeros(W, i32).at[order].set(rank_sorted)
            quota = jnp.sum(grants_gm, axis=1)  # [Gn]
            quota_t = jnp.concatenate([quota, jnp.zeros(1, i32)])[g_safe]
            granted = part & (rank < quota_t)
            pu_abs = place_by_search(jnp.clip(g_safe, 0, Gn - 1), rank, grants_gm, pu_free, P)
            return granted, pu_abs

        def group_costs(gspec: GroupSpec, cost_cm):
            """[G, M] effective per-unit place cost and shifted solve
            matrix for group mode. Route via the class EC costs
            e_g + cost[cls_g, m]; a preference override (pref_w) wins
            where cheaper — exactly min(EC route, preference arc), the
            two parallel paths a task has in the reference graph
            (updateTaskNode wiring, graph_manager.go:1183-1264)."""
            if cost_fn is None:
                route = jnp.broadcast_to(gspec.e[:, None], (Gn, M))
            else:
                # exact integer row gather — costs are NOT counts, so
                # the one-hot f32 matmul trick (which silently rounds
                # values >= 2^24 even at HIGHEST) is not usable here;
                # G row gathers from a [C, M] table are cheap
                cost_gm = cost_cm[jnp.clip(gspec.cls, 0, C - 1)]
                route = cost_gm + gspec.e[:, None]
            cost_eff = jnp.minimum(route, gspec.pref_w)
            w = cost_eff - gspec.u[:, None]
            return cost_eff, w

        def round_core(state: DeviceClusterState, gspec=None,
                       decode_width=None, window_offset=None,
                       supersteps_cap=None):
            """One scheduling round. decode_width (static) bounds the
            decode to a compacted window of that many unplaced rows —
            the admission-batch bound (the reference bounds per-round
            work the same way via pod batching, k8sclient/client.go:
            153-193): tasks beyond the window stay pending for a later
            round. window_offset (traced scalar) rotates which backlog
            ranks the window covers; steady rounds pass a random offset
            so solver-escaped tasks parked in low rows cannot occupy
            the window forever and starve placeable tasks behind them.
            With decode_width=None the decode spans all Tcap rows (the
            fill path). Bounding matters at 50k+ tasks: the decode's
            [width, M] passes dominate the non-solve round cost.

            supersteps_cap (static) bounds this round's TOTAL
            transport budget below the cluster-wide `supersteps`
            safety bound — on the grouped two-stage path the cap is
            split across attempts (the stage-1 spend is subtracted
            from the full-solve fallback's budget), so a
            budget-exhausted stage 1 plus its fallback stay within
            the documented escalated-tail bound. A capped solve may
            return converged=False, which the three-tier hybrid uses
            as its escalation signal (the caller discards the
            attempt)."""
            ss_budget = (
                supersteps
                if supersteps_cap is None
                else min(int(supersteps_cap), supersteps)
            )
            pu_free = jnp.where(
                jnp.repeat(state.machine_enabled, P),
                pu_slots - state.pu_running,
                i32(0),
            )
            machine_free = pu_free.reshape(M, P).sum(axis=1)

            unplaced = state.live & (state.pu < 0)
            if decode_width is None:
                backlog = jnp.sum(unplaced, dtype=i32)
                W = Tcap
                idx = None  # identity window
                valid = unplaced
                cls_w = state.cls
                job_w = state.job
                grp_w = state.grp
            else:
                W = int(decode_width)
                # compact W unplaced rows into the window: select the
                # cyclic rank interval [off, off+W) of the backlog and
                # find each rank's row by binary search in the running
                # count (scatter-free; the [W] gathers that follow are
                # cheap at W << Tcap). Ranks within the valid prefix are
                # distinct, so no row enters the window twice.
                cum_act = jnp.cumsum(unplaced.astype(i32))
                backlog = cum_act[-1]  # one reduction serves window + stats
                num_active = jnp.minimum(backlog, i32(W))
                off = i32(0) if window_offset is None else window_offset
                # rotate only when the window binds: a non-binding
                # window covers the whole backlog anyway, and keeping
                # row order makes the bounded path bit-identical to the
                # full path in that regime
                off = jnp.where(backlog > i32(W), off, i32(0))
                denom = jnp.maximum(i32(1), backlog)
                target = (off % denom + jnp.arange(W, dtype=i32)) % denom
                idx = jnp.searchsorted(cum_act, target + 1).astype(i32)
                valid = jnp.arange(W, dtype=i32) < num_active
                idx = jnp.where(valid, jnp.clip(idx, 0, Tcap - 1), Tcap)
                cls_w = jnp.where(
                    valid, state.cls[jnp.clip(idx, 0, Tcap - 1)], i32(C)
                )
                job_w = jnp.where(
                    valid, state.job[jnp.clip(idx, 0, Tcap - 1)], i32(0)
                )
                grp_w = jnp.where(
                    valid, state.grp[jnp.clip(idx, 0, Tcap - 1)], i32(Gn)
                )
            # group index per window row; sentinel Gn for invalid rows
            if grouped:
                g_w = grp_w
            else:
                g_w = (job_w * i32(C) + cls_w) if per_job else cls_w
            g_safe = jnp.where(valid, g_w, i32(Gn))
            supply = jnp.zeros(Gn + 1, i32).at[g_safe].add(1)[:Gn]
            total = jnp.sum(supply)

            if cost_fn is not None:
                cost_cm = cost_fn(census_of(state)).astype(i32)
            else:
                cost_cm = jnp.zeros((C, M), i32)
            if grouped:
                cost_eff, w = group_costs(gspec, cost_cm)
            else:
                # group rows: g = j*C + c carries class c's cost row and
                # job j's escape cost (the per-job unsched differentiation)
                cost_gm = jnp.tile(cost_cm, (J, 1)) if per_job else cost_cm
                cost_eff = cost_gm + i32(e_cost)
                w = cost_eff - u_row[:, None]
            # int32 headroom guard: the host solver raises OverflowError
            # for the same condition (solver/layered.py solve_layered);
            # in a jitted round we can only flag it — surfaced in stats
            # and asserted by fetch_stats.
            cost_overflow = jnp.max(jnp.abs(w)) >= i32(
                COST_SCALE_LIMIT // n_scale
            )

            wS = jnp.zeros((Gn, Mp), i32).at[:, :M].set(w * i32(n_scale))
            col_cap = (
                jnp.zeros(Mp, i32).at[:M].set(machine_free).at[Mp - 1].set(total)
            )
            # With no class cost model the cost matrix is statically
            # uniform across classes — the degenerate collapse avoids
            # the iterative solve entirely (closed form + class split).
            # Deliberately COLD-started every round (pm0=None): carrying
            # the previous round's near-optimal machine prices flattens
            # reduced costs to ~0 across thousands of machines, which
            # destroys the cost discrimination the synchronous maximal
            # push relies on and recreates the identical-cost herding
            # pathology — measured 20x SLOWER (9ms -> 197ms/round on the
            # CoCo 50k config) than cold tightening, which re-derives
            # prices from the cost structure each round.
            if row_constant:
                # machine-uniform rows (per-job unsched, no cost model):
                # the fractional-knapsack closed form — no iterations
                y = solve_row_constant(w[:, 0], supply, col_cap)
                solve_steps, converged = i32(0), jnp.bool_(True)
            elif not grouped:
                # eps0 from choose_eps0 (n_scale/4 — see the round-3
                # tail study in default_eps0's docstring: deeply
                # sub-quantum starts cause multi-thousand-superstep
                # tail rounds; exactly optimal for any start, with the
                # in-graph fallback to the full schedule covering
                # pathologies). Oversubscribed rounds (backlog > free
                # slots) switch to the full-range start — choose_eps0.
                eps_full = jnp.maximum(jnp.max(jnp.abs(wS)), i32(1))
                y, _pm, solve_steps, converged = transport_fori(
                    wS, supply, col_cap, ss_budget,
                    alpha=alpha,
                    eps0=choose_eps0(
                        n_scale, eps_full, total, jnp.sum(machine_free)
                    ),
                    class_degenerate=class_degenerate,
                    refine_waves=refine_waves,
                )
            else:
                # Grouped solves: (a) EXACT two-stage decomposition for
                # the locality structure (row-constant ground + sparse
                # preference overrides — cost_fn None): with every
                # row's ground profitable and the round not
                # oversubscribed, all units place, so total cost =
                # sum(ground_g * supply_g) (a constant) minus the
                # discount recovered on pref cells; stage 1 maximizes
                # discounts on the SPARSE pref cells alone, stage 2
                # spreads leftovers in closed form. The one-shot dense
                # solve herds on the uniform ground cells instead —
                # measured 27k-43k supersteps on real steady rounds.
                # (b) Row COMPACTION: steady backlogs touch ~a hundred
                # of the G groups; compacting to the active rows cuts
                # per-superstep cost ~G/active and keeps the instance
                # inside the fused kernel's VMEM budget.
                # (c) alpha=2 + price refinement: fine phases whose
                # flows carry over (only violations re-flood) resolve
                # the pref-contention price fights in ~2.7k supersteps
                # where coarse re-flooding phases took ~35k.
                ground = gspec.e - gspec.u  # [G] route - escape
                can_two_stage = cost_fn is None
                if can_two_stage:
                    D = jnp.maximum(ground[:, None] - w, i32(0))  # [G, M]
                    w1 = jnp.where(D > 0, -D, i32(1))
                    wS1 = jnp.zeros((Gn, Mp), i32).at[:, :M].set(
                        w1 * i32(n_scale)
                    )
                else:
                    wS1 = wS  # unused

                def grouped_solve(wS_x, wS1_x, supply_x, ground_x):
                    """Solve one grouped instance (row count from the
                    input shapes); returns (y, steps, converged)."""
                    total_x = jnp.sum(supply_x)
                    eps_full_x = jnp.maximum(jnp.max(jnp.abs(wS_x)), i32(1))

                    def solve_full(_, budget=ss_budget):
                        # eps0 = n_scale for grouped instances (not the
                        # global n_scale/4 default): the round-3 tail
                        # study's grouped replay shows blocked quincy
                        # rounds at 1.0-3.3k supersteps from the
                        # full-unit start vs 7.2-13.3k from n/4 and
                        # ~134k from eps0=1 — the sparse strong
                        # discounts over uniform ground want full-unit
                        # price-war steps (tools/tail_repro.py
                        # replay-grouped).
                        y_f, _pmf, s_f, c_f = transport_fori(
                            wS_x, supply_x, col_cap, budget,
                            alpha=2, refine_waves=8,
                            eps0=choose_eps0(
                                n_scale, eps_full_x, total_x,
                                jnp.sum(machine_free),
                                short=n_scale,
                            ),
                        )
                        return y_f, s_f, c_f

                    if not can_two_stage:
                        return solve_full(None)

                    def solve_two_stage(_):
                        # Stage-1 schedule per two_stage_eps0 (see
                        # __init__): "one" finishes the sparse matching
                        # in tens of waves when discounts are near-
                        # uniform but pays deep descents in unit
                        # bounces on heavy-tailed discounts; "quarter"
                        # flips that trade. Bounded HONESTLY either way
                        # (eps0_retry=False: no internal full-range
                        # retry on the discount matrix) with the
                        # refined full solve of the ORIGINAL matrix as
                        # the fallback.
                        if stage1_quarter:
                            s1_eps0 = jnp.maximum(i32(1), i32(n_scale // 4))
                            # 2048, not 1024: the multiblock max-tail
                            # round was a pure budget exhaustion — the
                            # captured monster needed ~1286 stage-1
                            # supersteps, got cut at 1024, and paid a
                            # ~3350-superstep full fallback on top
                            # (4374 total, ~30 ms; 16-instance r5
                            # replay sweep, tools/tail_repro.py
                            # replay-grouped). Typical rounds converge
                            # far below either bound, so the extra
                            # headroom costs nothing except on
                            # instances that would blow BOTH budgets,
                            # which the capture population does not
                            # contain.
                            s1_budget = 2048
                        else:
                            s1_eps0 = i32(1)
                            s1_budget = 256
                        y1, _pm1, s1, conv1 = transport_fori(
                            wS1_x, supply_x, col_cap, ss_budget,
                            alpha=2, refine_waves=8,
                            eps0=s1_eps0, eps0_budget=s1_budget,
                            eps0_retry=False,
                        )

                        def finish_two_stage(_):
                            y1r = y1[:, :M]
                            left = supply_x - jnp.sum(y1r, axis=1).astype(i32)
                            rem = machine_free - jnp.sum(y1r, axis=0).astype(
                                i32
                            )
                            excl = jnp.cumsum(rem) - rem
                            grants_m = jnp.clip(jnp.sum(left) - excl, 0, rem)
                            y2 = split_grants_by_class(grants_m, left)
                            y_out = y1.at[:, :M].add(y2.astype(i32))
                            # escape column: anything beyond real capacity
                            y_out = y_out.at[:, Mp - 1].set(
                                supply_x
                                - jnp.sum(y_out[:, :M], axis=1).astype(i32)
                            )
                            return y_out, s1, conv1

                        def fall_back(_):
                            # round-total budget (ADVICE r5 #2): the
                            # exhausted stage 1 spent up to s1_budget
                            # of the cap, so the fallback gets the
                            # remainder — the two attempts together
                            # honor supersteps_cap instead of each
                            # claiming it. A cap at or below s1_budget
                            # leaves no remainder: return the failed
                            # attempt as-is (conv1 is False on this
                            # branch; the caller's escalation discards
                            # it) instead of a futile token solve.
                            fb_budget = ss_budget - min(s1_budget, ss_budget)
                            if fb_budget <= 0:
                                return y1, s1, conv1
                            y_f, s_f, c_f = solve_full(
                                None, budget=fb_budget
                            )
                            return y_f, s1 + s_f, c_f

                        return lax.cond(
                            conv1, finish_two_stage, fall_back, operand=None
                        )

                    two_stage_ok = (
                        (total_x <= jnp.sum(machine_free))
                        & jnp.all((ground_x < 0) | (supply_x == 0))
                    )
                    return lax.cond(
                        two_stage_ok, solve_two_stage, solve_full,
                        operand=None,
                    )

                caps = tuple(c for c in active_caps if c < Gn)
                n_active_rows = jnp.sum((supply > 0).astype(i32))
                if caps:
                    act = supply > 0
                    order = jnp.argsort(~act, stable=True)
                    n_act = n_active_rows

                    def compact_at(Gc):
                        sel = order[:Gc]
                        valid_c = act[sel]

                        def path(_):
                            sup_c = jnp.where(valid_c, supply[sel], i32(0))
                            y_c, s_c, c_c = grouped_solve(
                                wS[sel], wS1[sel], sup_c, ground[sel]
                            )
                            y_f = jnp.zeros((Gn, Mp), i32).at[sel].add(
                                jnp.where(valid_c[:, None], y_c, i32(0))
                            )
                            return y_f, s_c, c_c

                        return path

                    def full_path(_):
                        return grouped_solve(wS, wS1, supply, ground)

                    # ladder: smallest width that fits n_act wins; the
                    # widths are static (one compiled solve each), the
                    # choice is dynamic — no recompile as the live
                    # signature count drifts between maintenance points
                    def make_rung(Gc, wider):
                        def rung(_):
                            return lax.cond(
                                n_act <= i32(Gc), compact_at(Gc), wider,
                                operand=None,
                            )

                        return rung

                    branch = full_path
                    for Gc in reversed(caps):
                        branch = make_rung(Gc, branch)
                    y, solve_steps, converged = branch(None)
                else:
                    y, solve_steps, converged = grouped_solve(
                        wS, wS1, supply, ground
                    )
            y_real = y[:, :M]

            # ---- decode: rank-match placed tasks to machine grants ----
            decode = (rank_match_decode_grouped if use_sorted_decode
                      else rank_match_decode)
            placed_w, pu_abs = decode(g_safe, y_real, pu_free)

            if idx is None:
                # identity window: elementwise select, no scatter
                new_pu = jnp.where(placed_w, pu_abs, state.pu)
                pr_idx = jnp.where(placed_w, pu_abs, num_pus)
            else:
                # compacted window: scatter the W placements back (rows
                # beyond Tcap — invalid/unplaced — are dropped)
                tgt = jnp.where(placed_w, idx, Tcap)
                new_pu = state.pu.at[tgt].set(pu_abs, mode="drop")
                pr_idx = jnp.where(placed_w, pu_abs, num_pus)
            pu_running = (
                jnp.zeros(num_pus + 1, i32)
                .at[pr_idx].add(1)[:num_pus]
                + state.pu_running
            )
            placed_count = jnp.sum(placed_w, dtype=i32)
            # unscheduled counts the WHOLE backlog left pending (solver
            # escapes + rows beyond the decode window) — matches the
            # host BulkCluster's num_unsched accounting
            if per_job or grouped:
                # per-group escape pricing needs the whole-pool backlog
                # split by group, not just the window's
                g_all = (
                    state.grp if grouped
                    else state.job * i32(C) + state.cls
                )
                g_all_safe = jnp.where(unplaced, g_all, i32(Gn))
                backlog_g = jnp.zeros(Gn + 1, i32).at[g_all_safe].add(1)[:Gn]
                placed_g = jnp.sum(y_real, axis=1).astype(i32)
                u_g = gspec.u if grouped else u_row
                objective = jnp.sum(u_g * (backlog_g - placed_g)) + jnp.sum(
                    cost_eff * y_real
                )
            else:
                objective = i32(u_cost) * (backlog - placed_count) + jnp.sum(
                    cost_eff * y_real
                )
            stats = {
                "placed": placed_count,
                "unscheduled": backlog - placed_count,
                "converged": converged,
                "cost_overflow": cost_overflow,
                "objective": objective,
                "live": jnp.sum(state.live, dtype=i32),
                # solver supersteps this round (0 on closed-form paths)
                # — the observability the reference parses and discards
                # (placement/solver.go:169-170)
                "supersteps": solve_steps,
            }
            if grouped:
                # which compaction rung carried the solve (ladder tuning)
                stats["active_groups"] = n_active_rows
            return state._replace(pu=new_pu, pu_running=pu_running), stats

        def round_core_preempt(state: DeviceClusterState, gspec=None,
                               decode_width=None, window_offset=None,
                               scope_m=None):
            """Preemption-on round (keep-arcs semantics, graph_manager.
            go:855-888): every live task re-solves. Staying on the
            current machine is discounted, moving pays full price,
            escaping pays the group's unsched cost; machine capacity is
            TOTAL slots (the :662-667 capacity rule with preemption
            on). Decode: per cell (group, machine), min(grant,
            residents) residents are retained in row order; remaining
            grants go to "movers" (displaced residents + backlog),
            yielding MIGRATE for re-granted residents, PLACE for fresh
            tasks, PREEMPT for residents left without a grant. A
            displaced resident can never be re-granted its own machine
            (rem[g,m] > 0 forces retained[g,m] = R[g,m]), so the three
            delta kinds are disjoint by construction.

            decode_width (static) bounds the MOVER decode to a
            compacted window, as round_core's does for the backlog:
            stays need no decode (they keep their PU), and steady-state
            movers are ~churn-sized, so the [W, M] decode passes shrink
            from Tcap-wide (the 21 ms fixed floor measured at
            Tcap=65536 on coco50k-preempt) to window-wide. Movers
            beyond a binding window keep pu=-1 this round and re-enter
            the next solve — the same pending semantics as the bounded
            backlog window; window_offset rotates coverage so none
            starves.

            scope_m (traced bool[M] or None) is the SCOPED re-solve of
            the three-tier stability scheme (VERDICT r4 #2): residents
            on out-of-scope machines are pinned in place (they stay,
            consume capacity, and pay their discounted cost in the
            objective) and only residents on in-scope machines plus the
            backlog re-solve. Soundness (per-interval bound): cost
            columns are census-determined, and an out-of-scope machine
            moved < preempt_scope_tau (L1) SINCE THE LAST FIRED ROUND
            — so within one interval its cost column moved < tau times
            the cost model's census Lipschitz constant and pinning it
            is an eps-bounded approximation. The bound is per
            interval, not cumulative: the drift reference re-bases
            globally at every fired round (the per-machine variant ran
            away — see hybrid_round), so sub-tau-per-interval drift
            can accumulate unpriced until the GLOBAL re-solve
            (preempt_global_every) re-prices every column. That global
            backstop, plus the measured realized-cost parity tests,
            is the quality contract. Multi-hop chains through
            out-of-scope machines are deferred the same way — as the
            reference's delta-proportional incremental rounds defer
            them (placement/solver.go:60-90)."""
            enabled_pu = jnp.repeat(state.machine_enabled, P)
            live = state.live
            placed = live & (state.pu >= 0)
            cur_pu = jnp.clip(state.pu, 0, num_pus - 1)
            cur_m = jnp.where(placed, cur_pu // P, i32(M))  # sentinel M
            if grouped:
                g_t = state.grp
            else:
                g_t = (state.job * i32(C) + state.cls) if per_job else state.cls

            if scope_m is None:
                in_scope_res = placed
                forced = jnp.zeros_like(placed)
            else:
                scope_pad = jnp.concatenate(
                    [scope_m, jnp.zeros(1, jnp.bool_)]
                )
                in_scope_res = placed & scope_pad[cur_m]
                forced = placed & ~in_scope_res
            solve_live = live & (~placed | in_scope_res)
            g_safe = jnp.where(solve_live, g_t, i32(Gn))
            supply = jnp.zeros(Gn + 1, i32).at[g_safe].add(1)[:Gn]
            total = jnp.sum(supply)

            # forced (out-of-scope) stays consume machine capacity
            forced_m = jnp.where(forced, cur_m, i32(M))
            F_m = jnp.zeros(M + 1, i32).at[forced_m].add(1)[:M]
            col_cap_m = jnp.where(
                state.machine_enabled, machine_slots - F_m, i32(0)
            )

            if cost_fn is not None:
                cost_cm = cost_fn(census_of(state)).astype(i32)
            else:
                cost_cm = jnp.zeros((C, M), i32)
            if grouped:
                cost_eff, w = group_costs(gspec, cost_cm)
            else:
                cost_gm = jnp.tile(cost_cm, (J, 1)) if per_job else cost_cm
                cost_eff = cost_gm + i32(e_cost)
                w = cost_eff - u_row[:, None]
            cost_overflow = (
                jnp.max(jnp.abs(w)) + i32(discount)
            ) >= i32(COST_SCALE_LIMIT // n_scale)

            # resident census per cell [Gn, M] (in-scope placed tasks)
            cell = jnp.where(
                in_scope_res, g_t * i32(M) + cur_m, i32(Gn * M)
            )
            R_real = (
                jnp.zeros(Gn * M + 1, i32).at[cell].add(1)[: Gn * M]
            ).reshape(Gn, M)

            wS_hi = jnp.zeros((Gn, Mp), i32).at[:, :M].set(w * i32(n_scale))
            wS_lo = wS_hi.at[:, :M].add(-i32(discount * n_scale))
            R_pad = jnp.zeros((Gn, Mp), i32).at[:, :M].set(R_real)
            col_cap = (
                jnp.zeros(Mp, i32).at[:M].set(col_cap_m).at[Mp - 1].set(total)
            )
            eps_full = jnp.maximum(jnp.max(jnp.abs(wS_hi)), i32(1))
            # full-unit start for the tiered re-solve (short=n_scale):
            # the round-3 tiered replay sweep measured it 2-6x under
            # the global n/4 default on captured preemption rounds
            # (22.6k -> 8.5k supersteps worst), refinement on
            eps0 = choose_eps0(
                n_scale, eps_full, total, jnp.sum(col_cap_m),
                short=n_scale,
            )
            if discount == 0 and row_constant:
                # tiers coincide AND rows are machine-uniform: the
                # fractional-knapsack closed form on the all-live supply
                y = solve_row_constant(w[:, 0], supply, col_cap)
                solve_steps, converged = i32(0), jnp.bool_(True)
            elif discount == 0:
                # tiers coincide: the ordinary solve (incl. the
                # degenerate collapse) is exact on the all-live supply
                y, _pm, solve_steps, converged = transport_fori(
                    wS_hi, supply, col_cap, supersteps, alpha=alpha,
                    eps0=eps0,
                    class_degenerate=class_degenerate,
                    refine_waves=refine_waves,
                )
            else:
                y, _pm, solve_steps, converged = transport_fori_tiered(
                    wS_lo, wS_hi, R_pad, supply, col_cap, supersteps,
                    alpha=alpha, eps0=eps0, refine_waves=refine_waves,
                )
            y_real = y[:, :M]

            # ---- decode ----
            retained = jnp.minimum(y_real, R_real)  # residents kept
            rem = y_real - retained  # grants for movers

            # per-cell resident ranks (row order) via one stable sort
            order = jnp.argsort(cell, stable=True)
            counts = jnp.zeros(Gn * M + 1, i32).at[cell].add(1)
            starts = jnp.cumsum(counts) - counts
            rank_sorted = jnp.arange(Tcap, dtype=i32) - starts[cell[order]]
            rank_cell = jnp.zeros(Tcap, i32).at[order].set(rank_sorted)
            ret_flat = jnp.concatenate([retained.reshape(-1), jnp.zeros(1, i32)])
            stay = forced | (
                in_scope_res
                & (rank_cell < ret_flat[jnp.clip(cell, 0, Gn * M)])
            )

            # movers: every live task not staying; their grants fill
            # the slots left after stays
            mover = live & ~stay
            stay_pu = jnp.where(stay, cur_pu, num_pus)
            pu_stay = jnp.zeros(num_pus + 1, i32).at[stay_pu].add(1)[:num_pus]
            pu_free_mv = jnp.where(enabled_pu, pu_slots - pu_stay, i32(0))
            decode = (rank_match_decode_grouped if use_sorted_decode
                      else rank_match_decode)
            if decode_width is None:
                g_mv = jnp.where(mover, g_t, i32(Gn))
                granted, pu_abs = decode(g_mv, rem, pu_free_mv)
                new_pu = jnp.where(
                    stay, state.pu, jnp.where(granted, pu_abs, i32(-1))
                )
                granted_full = granted & mover
            else:
                Wm = int(decode_width)
                cum_mv = jnp.cumsum(mover.astype(i32))
                n_mv = cum_mv[-1]
                off = i32(0) if window_offset is None else window_offset
                off = jnp.where(n_mv > i32(Wm), off, i32(0))
                denom = jnp.maximum(i32(1), n_mv)
                target = (off % denom + jnp.arange(Wm, dtype=i32)) % denom
                idx = jnp.searchsorted(cum_mv, target + 1).astype(i32)
                valid = jnp.arange(Wm, dtype=i32) < jnp.minimum(n_mv, i32(Wm))
                idx = jnp.where(valid, jnp.clip(idx, 0, Tcap - 1), Tcap)
                g_mv_w = jnp.where(
                    valid, g_t[jnp.clip(idx, 0, Tcap - 1)], i32(Gn)
                )
                granted_w, pu_abs_w = decode(g_mv_w, rem, pu_free_mv)
                tgt = jnp.where(granted_w, idx, Tcap)
                base_pu = jnp.where(stay, state.pu, i32(-1))
                new_pu = base_pu.at[tgt].set(pu_abs_w, mode="drop")
                granted_full = (
                    jnp.zeros(Tcap + 1, jnp.bool_)
                    .at[tgt].set(True, mode="drop")[:Tcap]
                )
            final_on = live & (new_pu >= 0)
            pu_idx = jnp.where(final_on, new_pu, num_pus)
            pu_running = jnp.zeros(num_pus + 1, i32).at[pu_idx].add(1)[:num_pus]

            placed_total = jnp.sum(y_real, dtype=i32)
            # objective: placements at the effective route cost,
            # retained residents rebated by the discount, escapes at
            # the group unsched cost
            u_g = gspec.u if grouped else u_row
            objective = (
                jnp.sum(cost_eff * y_real)
                - i32(discount) * jnp.sum(retained)
                + jnp.sum(u_g * (supply - jnp.sum(y_real, axis=1)))
            )
            if scope_m is not None:
                # forced (out-of-scope) stays pay their discounted cost
                # so scoped and global objectives price the same pool
                F_gm = (
                    jnp.zeros(Gn * M + 1, i32)
                    .at[jnp.where(forced, g_t * i32(M) + cur_m, i32(Gn * M))]
                    .add(1)[: Gn * M]
                ).reshape(Gn, M)
                objective = (
                    objective + jnp.sum(cost_eff * F_gm)
                    - i32(discount) * jnp.sum(F_gm, dtype=i32)
                )
            stats = {
                "placed": jnp.sum(granted_full & ~placed, dtype=i32),
                "migrated": jnp.sum(granted_full & placed, dtype=i32),
                "preempted": jnp.sum(
                    placed & ~stay & ~granted_full, dtype=i32
                ),
                "unscheduled": total - placed_total,
                "converged": converged,
                "cost_overflow": cost_overflow,
                "objective": objective,
                "live": jnp.sum(live, dtype=i32),
                "supersteps": solve_steps,
            }
            return state._replace(pu=new_pu, pu_running=pu_running), stats

        def realized_cluster_cost(state: DeviceClusterState, gspec):
            """Price the CURRENT assignment at this state's census:
            every placed task pays its group's effective route cost on
            its machine, every unplaced live task pays its group's
            escape cost. One number both preemption regimes share, so
            the stability-aware scheme's objective drift vs the
            full-re-solve-every-round regime is directly measurable
            (the parity contract of VERDICT r3 #1)."""
            if cost_fn is not None:
                cost_cm = cost_fn(census_of(state)).astype(i32)
            else:
                cost_cm = jnp.zeros((C, M), i32)
            if grouped:
                cost_eff, _ = group_costs(gspec, cost_cm)
            else:
                cost_gm = jnp.tile(cost_cm, (J, 1)) if per_job else cost_cm
                cost_eff = cost_gm + i32(e_cost)
            if grouped:
                g_t = state.grp
            else:
                g_t = (state.job * i32(C) + state.cls) if per_job else state.cls
            on = state.live & (state.pu >= 0)
            m_t = jnp.clip(state.pu, 0, num_pus - 1) // P
            g_c = jnp.clip(g_t, 0, Gn - 1)
            c_task = cost_eff[g_c, m_t]
            u_g = gspec.u if grouped else u_row
            esc = u_g[g_c]
            # int32 is ample: Tcap * max cost stays well under 2^31 for
            # every wired model (costs clamp at ~2.5k, escape costs a
            # few units above)
            return (
                jnp.sum(jnp.where(on, c_task, i32(0)), dtype=i32)
                + jnp.sum(jnp.where(state.live & ~on, esc, i32(0)), dtype=i32)
            )

        def hybrid_round(state, census_ref, k_since, kg_since, gspec,
                         window_offset):
            """Stability-aware preemption round (see preempt_every /
            preempt_drift in __init__): the cheap incremental core
            (residents pinned, bounded backlog decode) serves steady
            rounds; the full tiered re-solve fires on schedule or when
            the running census drifts past the threshold. Both cores
            live under one lax.cond — only the taken branch executes,
            so round cost tracks the delta, as the reference's
            incremental solver does (placement/solver.go:60-90)."""
            cen = census_of(state)
            drift = jnp.sum(jnp.abs(cen - census_ref), dtype=i32)
            do_full = k_since + 1 >= i32(preempt_every)
            if preempt_drift > 0:
                do_full = do_full | (drift >= i32(preempt_drift))
            # three-tier scheme (preempt_global_every > 0): cadence /
            # drift rounds run the SCOPED re-solve over drifted columns
            # + backlog; a rare GLOBAL re-solve catches the multi-hop
            # chains scoping defers. With the knob off every full round
            # is global (round-4 behavior, bit-preserved).
            do_global = (
                kg_since + 1 >= i32(global_every)
                if global_every > 0 else do_full
            )

            def full_branch(_):
                s2, st = round_core_preempt(
                    state, gspec, decode_width=None, window_offset=None
                )
                return s2, census_of(s2), st

            def scoped_branch(_):
                scope = (
                    jnp.sum(jnp.abs(cen - census_ref), axis=1)
                    >= i32(scope_tau)
                )
                s2, st = round_core_preempt(
                    state, gspec,
                    decode_width=scoped_width,
                    window_offset=window_offset,
                    scope_m=scope,
                )
                # the reference re-bases GLOBALLY here, exactly like a
                # full round — deliberately. The per-machine variant
                # (advance only in-scope refs so sub-tau drifters
                # accumulate toward tau) was measured and REVERTED:
                # each scoped round's ~10k migration landings add ~10
                # L1 to machines outside the scope, so under per-
                # machine refs nearly every machine crosses tau within
                # one interval and both the scope and the drift trigger
                # run away (149/160 rounds fired, scoped supersteps
                # back at full-solve size — docs/NOTES.md round-5).
                # The price of global re-basing: a machine drifting
                # < tau per interval is re-based every fired round and
                # never enters scope; its stale pricing is corrected
                # only by the preempt_global_every backstop.
                return s2, census_of(s2), st

            def incr_branch(_):
                s2, st = round_core(
                    state, gspec,
                    decode_width=steady_decode_width,
                    window_offset=window_offset,
                    supersteps_cap=incr_budget,
                )
                st = dict(st)
                st.pop("active_groups", None)  # preempt core has none
                st["migrated"] = i32(0)
                st["preempted"] = i32(0)
                st["escalated"] = jnp.bool_(False)
                if incr_budget is None:
                    return s2, census_ref, st

                # Escalation (three-tier only, enforced in __init__): a
                # budget-exhausted incremental attempt is DISCARDED and
                # the round re-runs as a scoped re-solve from the same
                # pre-round state — re-pricing the drifted columns is
                # exactly what the sloshing admission solve was missing.
                # The attempt's supersteps stay in the round's count
                # (real work the round paid for).
                def keep(_):
                    return s2, census_ref, st

                def escalate(_):
                    s3, cen3, st3 = scoped_branch(None)
                    st3 = dict(st3)
                    st3["escalated"] = jnp.bool_(True)
                    st3["supersteps"] = st3["supersteps"] + st["supersteps"]
                    return s3, cen3, st3

                return lax.cond(st["converged"], keep, escalate, operand=None)

            if global_every > 0:
                def resolve_branch(_):
                    s2, cen2, st = lax.cond(
                        do_global, full_branch, scoped_branch, operand=None
                    )
                    st = dict(st)
                    st["escalated"] = jnp.bool_(False)
                    return s2, cen2, st

                state2, census_ref2, stats = lax.cond(
                    do_full | do_global, resolve_branch, incr_branch,
                    operand=None,
                )
                stats = dict(stats)
                escalated = stats.pop("escalated")
                # an escalated round IS a fired (scoped) round: census
                # re-based, cadence counter reset, scope forensics
                # attribute it to the scoped tier
                fired = do_full | do_global | escalated
                kg_since2 = jnp.where(do_global, i32(0), kg_since + 1)
            else:
                def full_branch_tagged(_):
                    s2, cen2, st = full_branch(None)
                    st = dict(st)
                    st["escalated"] = jnp.bool_(False)
                    return s2, cen2, st

                state2, census_ref2, stats = lax.cond(
                    do_full, full_branch_tagged, incr_branch, operand=None
                )
                stats = dict(stats)
                escalated = stats.pop("escalated")
                fired = do_full
                kg_since2 = kg_since
            k_since2 = jnp.where(fired, i32(0), k_since + 1)
            stats["full_round"] = fired
            stats["global_round"] = do_global if global_every > 0 else fired
            stats["escalated_round"] = escalated
            stats["census_drift"] = drift
            if track_realized:
                stats["realized_cost"] = realized_cluster_cost(state2, gspec)
            return state2, census_ref2, k_since2, kg_since2, stats

        def admit(state: DeviceClusterState, jobs, classes, groups, count):
            """Occupy the first `count` free rows with the first `count`
            entries of (jobs, classes, groups), which are as wide as the
            caller shipped them (Tcap, or a served batch's bucket).
            Returns (state, admitted): admitted < count when the task
            pool is exhausted — the host BulkCluster raises for this;
            here the shortfall is reported so add_tasks can check it
            after fetch."""
            free_rank = jnp.cumsum(~state.live) - 1  # rank among free rows
            newmask = ~state.live & (free_rank < count)
            src_idx = jnp.clip(free_rank, 0, classes.shape[0] - 1)
            admitted = jnp.sum(newmask, dtype=i32)
            return state._replace(
                live=state.live | newmask,
                cls=jnp.where(newmask, classes[src_idx].astype(i32), state.cls),
                job=jnp.where(newmask, jobs[src_idx].astype(i32), state.job),
                grp=jnp.where(newmask, groups[src_idx].astype(i32), state.grp),
                pu=jnp.where(newmask, i32(-1), state.pu),
            ), admitted

        def complete(state: DeviceClusterState, rows, count):
            """Retire `count` task rows (first `count` entries of `rows`,
            which is as wide as the caller shipped it)."""
            k = jnp.arange(rows.shape[0], dtype=jnp.int32)
            sel = k < count
            idx = jnp.where(sel, rows, Tcap)
            done = jnp.zeros(Tcap + 1, jnp.bool_).at[idx].set(True)[:Tcap]
            done = done & state.live
            pu_idx = jnp.where(done & (state.pu >= 0), state.pu, num_pus)
            pu_running = (
                jnp.zeros(num_pus + 1, i32).at[pu_idx].add(1)[:num_pus]
            )
            return state._replace(
                live=state.live & ~done,
                pu=jnp.where(done, i32(-1), state.pu),
                pu_running=state.pu_running - pu_running,
            )

        def set_machine(state: DeviceClusterState, machine_index, enabled):
            """Elastic membership (RegisterResource/DeregisterResource,
            flowscheduler/scheduler.go:134-210): disabling evicts the
            machine's tasks back to the unscheduled pool."""
            me = state.machine_enabled.at[machine_index].set(enabled)
            on_machine = (
                state.live
                & (state.pu >= 0)
                & ((jnp.clip(state.pu, 0, num_pus - 1) // P) == machine_index)
            )
            disabled = jnp.bool_(not enabled)
            evict = on_machine & disabled
            pu_mask = (jnp.arange(num_pus, dtype=i32) // P) == machine_index
            pu_running = jnp.where(
                pu_mask & disabled, i32(0), state.pu_running
            )
            return state._replace(
                machine_enabled=me,
                pu=jnp.where(evict, i32(-1), state.pu),
                pu_running=pu_running,
            )

        def steady_round(carry, gspec, key, churn_prob,
                         arrivals, arrival_map, arrival_n):
            """One benchmark round: complete ~churn_prob of running
            tasks, admit `arrivals` new ones (random job/class — or a
            random GROUP in group mode, drawn uniformly over the first
            `arrival_n` entries of `arrival_map` [Gn] so the host can
            restrict arrivals to REGISTERED signatures when the table
            churns under LRU eviction — exactly uniform over the
            registered set, no tiling skew; class
            and job gathered from the group metadata), then schedule.
            Entirely on device so rounds chain without host sync — the
            incremental re-solve regime Flowlessly's daemon mode serves
            in the reference (placement/solver.go:60-90)."""
            if hybrid:
                state, census_ref, k_since, kg_since = carry
            else:
                state = carry
            k1, k2, k3, k4 = jax.random.split(key, 4)
            placed = state.live & (state.pu >= 0)
            done = placed & (
                jax.random.uniform(k1, (Tcap,)) < churn_prob
            )
            pu_idx = jnp.where(done, state.pu, num_pus)
            dec = jnp.zeros(num_pus + 1, i32).at[pu_idx].add(1)[:num_pus]
            state = state._replace(
                live=state.live & ~done,
                pu=jnp.where(done, i32(-1), state.pu),
                pu_running=state.pu_running - dec,
            )
            free_rank = jnp.cumsum(~state.live) - 1
            newmask = ~state.live & (free_rank < arrivals)
            if grouped:
                new_grp = arrival_map[
                    jax.random.randint(k2, (Tcap,), 0, arrival_n)
                ]
                new_cls = gspec.cls[new_grp]
                new_job = gspec.job[new_grp]
            else:
                new_grp = jnp.zeros(Tcap, i32)
                new_cls = jax.random.randint(k2, (Tcap,), 0, C)
                new_job = jax.random.randint(k3, (Tcap,), 0, J)
            state = state._replace(
                live=state.live | newmask,
                cls=jnp.where(newmask, new_cls, state.cls),
                job=jnp.where(newmask, new_job, state.job),
                grp=jnp.where(newmask, new_grp, state.grp),
                pu=jnp.where(newmask, i32(-1), state.pu),
            )
            admitted = jnp.sum(newmask, dtype=i32)
            # steady rounds bound the decode to the configured window;
            # the one-shot round() keeps the full width (fill path).
            # The random offset rotates the window over the backlog so
            # no pending task can be starved by earlier-row escapees.
            # Preemption mode bounds its MOVER decode the same way
            # (stays need no decode; movers are ~churn-sized).
            if hybrid:
                state, census_ref, k_since, kg_since, stats = hybrid_round(
                    state, census_ref, k_since, kg_since, gspec,
                    jax.random.randint(k4, (), 0, 1 << 30),
                )
            elif preempt:
                state, stats = round_core_preempt(
                    state, gspec,
                    decode_width=steady_decode_width,
                    window_offset=jax.random.randint(k4, (), 0, 1 << 30),
                )
            else:
                state, stats = round_core(
                    state,
                    gspec,
                    decode_width=steady_decode_width,
                    window_offset=jax.random.randint(k4, (), 0, 1 << 30),
                )
            stats["completed"] = jnp.sum(done, dtype=i32)
            stats["admitted"] = admitted
            out = (
                (state, census_ref, k_since, kg_since)
                if hybrid else state
            )
            return out, stats

        def replay_round(carry, gspec, xs):
            """One trace-replay round: machine toggles (with evictions),
            completions, admissions, then the scheduling round — the
            whole round's events pre-staged as fixed-width device
            arrays so a windowed trace replays as ONE scanned program
            (the TPU-idiomatic form of the reference's event loop,
            cmd/k8sscheduler/scheduler.go:120-188: host batches events
            into windows ahead of time, device consumes them without
            per-round host round-trips)."""
            if hybrid:
                state, census_ref, k_since, kg_since = carry
            else:
                state = carry
            aj, ac, ag, an, dr, dn, ti, ton, tn, key = xs
            Emax = ti.shape[0]
            Dmax = dr.shape[0]
            Amax = aj.shape[0]

            # --- machine toggles + evictions (set_machine, batched;
            # the host stager dedups per-window toggles keep-last, so
            # duplicate scatter indices cannot race) ---
            valid_t = jnp.arange(Emax, dtype=i32) < tn
            idx_t = jnp.where(valid_t, ti, i32(M))
            me = state.machine_enabled.at[idx_t].set(ton, mode="drop")
            on = state.live & (state.pu >= 0)
            machine_of = jnp.clip(state.pu, 0, num_pus - 1) // P
            evict = on & ~me[machine_of]
            pu2 = jnp.where(evict, i32(-1), state.pu)
            on2 = state.live & (pu2 >= 0)
            pu_idx = jnp.where(on2, pu2, num_pus)
            pu_running = jnp.zeros(num_pus + 1, i32).at[pu_idx].add(1)[:num_pus]
            state = state._replace(
                machine_enabled=me, pu=pu2, pu_running=pu_running
            )
            evicted = jnp.sum(evict, dtype=i32)

            # --- completions (complete(), in-scan form) ---
            kk = jnp.arange(Dmax, dtype=i32)
            idx_d = jnp.where(kk < dn, dr, i32(Tcap))
            done = (
                jnp.zeros(Tcap + 1, jnp.bool_).at[idx_d].set(True)[:Tcap]
                & state.live
            )
            pu_idx = jnp.where(done & (state.pu >= 0), state.pu, num_pus)
            dec = jnp.zeros(num_pus + 1, i32).at[pu_idx].add(1)[:num_pus]
            state = state._replace(
                live=state.live & ~done,
                pu=jnp.where(done, i32(-1), state.pu),
                pu_running=state.pu_running - dec,
            )

            # --- admissions (admit(), [Amax]-wide sources; the host
            # mirror predicts the same first-free-rows assignment) ---
            free_rank = jnp.cumsum(~state.live) - 1
            newmask = ~state.live & (free_rank < an)
            src = jnp.clip(free_rank, 0, Amax - 1)
            state = state._replace(
                live=state.live | newmask,
                cls=jnp.where(newmask, ac[src], state.cls),
                job=jnp.where(newmask, aj[src], state.job),
                grp=jnp.where(newmask, ag[src], state.grp),
                pu=jnp.where(newmask, i32(-1), state.pu),
            )
            admitted = jnp.sum(newmask, dtype=i32)

            if hybrid:
                state, census_ref, k_since, kg_since, stats = hybrid_round(
                    state, census_ref, k_since, kg_since, gspec,
                    jax.random.randint(key, (), 0, 1 << 30),
                )
            elif preempt:
                state, stats = round_core_preempt(
                    state, gspec,
                    decode_width=steady_decode_width,
                    window_offset=jax.random.randint(key, (), 0, 1 << 30),
                )
            else:
                state, stats = round_core(
                    state, gspec,
                    decode_width=steady_decode_width,
                    window_offset=jax.random.randint(key, (), 0, 1 << 30),
                )
            stats["evicted"] = evicted
            stats["admitted"] = admitted
            stats["completed"] = jnp.sum(done, dtype=i32)
            out = (
                (state, census_ref, k_since, kg_since)
                if hybrid else state
            )
            return out, stats

        def replay_scan(carry, gspec, aj, ac, ag, an, dr, dn, ti, ton, tn,
                        key0):
            keys = jax.random.split(key0, aj.shape[0])

            def body(s, xs):
                return replay_round(s, gspec, xs)

            return lax.scan(
                body, carry, (aj, ac, ag, an, dr, dn, ti, ton, tn, keys)
            )

        self._replay_scan_jit = jax.jit(replay_scan)  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py

        core = round_core_preempt if preempt else round_core
        self._round_jit = jax.jit(core)  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py
        self._admit_jit = jax.jit(admit)  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py
        self._complete_jit = jax.jit(complete)  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py
        self._set_machine_jit = jax.jit(set_machine, static_argnums=(2,))  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py
        self._census_jit = jax.jit(census_of)  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py

        def served_round(state: DeviceClusterState, gspec, decode_width):
            """The one-shot round as a service runs it, with what it
            moved: `round()`'s program over a decode window of
            `decode_width` unplaced rows (static; None: every row. The
            caller takes a window that holds the whole backlog, where
            the bounded decode is bit-identical to the full one), then
            the rows it placed, in row order: `rows` i32[K] and the PU
            of each, `pus` i32[K], K the window's width, compacted to the
            front with Tcap past the last one; over every row (K = Tcap)
            row r stands at r, Tcap where it did not move: the caller
            keeps `rows < Tcap` either way. The round's scalars come as ONE array,
            `summary` i32[len(SERVED_SUMMARY)], so that the host waits
            for one transfer. What a service reads back a round is sized
            to its batch, not to the table."""
            new, stats = round_core(state, gspec, decode_width=decode_width)
            moved = state.live & (state.pu < 0) & (new.pu >= 0)
            if decode_width is None:
                # every row is in the window: nothing to compact
                rows = jnp.where(moved, jnp.arange(Tcap, dtype=i32), i32(Tcap))
                pus = new.pu
            else:
                # the k-th row that moved, by binary search in the running
                # count, as the decode window finds its rows: K searches
                # where `jnp.nonzero(size=K)` scatters an update a row of
                # the table (PERF.md section 6, PR 50: ~7 ns a row, 0.46 ms
                # at 65,536 rows whatever K)
                ranks = jnp.arange(1, int(decode_width) + 1, dtype=i32)
                rows = jnp.searchsorted(jnp.cumsum(moved.astype(i32)), ranks).astype(i32)
                pus = new.pu[jnp.clip(rows, 0, Tcap - 1)]
            summary = jnp.stack([stats[k].astype(i32) for k in SERVED_SUMMARY])
            return new, summary, rows, pus

        self._served_round_jit = jax.jit(  # kschedlint: program=array_served_round
            served_round, static_argnames=("decode_width",)
        )

        def steady_scan(carry, gspec, key0, churn_prob, arrivals, num_rounds,
                        arrival_map, arrival_n):
            keys = jax.random.split(key0, num_rounds)

            def body(s, k):
                return steady_round(s, gspec, k, churn_prob, arrivals,
                                    arrival_map, arrival_n)

            return lax.scan(body, carry, keys)

        self._steady_scan_jit = jax.jit(steady_scan, static_argnums=(4, 5))  # kschedlint: disable=unregistered-program -- device-bulk replay machinery, bit-parity gated by tests/test_device_bulk.py

    # ------------------------------------------------------------------
    # host API
    # ------------------------------------------------------------------

    def add_tasks(self, count, job_ids=None, classes=None, groups=None,
                  width: Optional[int] = None) -> None:
        """Admit up to `count` tasks. The admitted count is kept on
        device in ``last_admitted`` (fetching it mid-run would sync the
        host into the chain of rounds); callers that
        need the host BulkCluster's pool-exhausted error should check
        ``int(jax.device_get(self.last_admitted)) == count`` at a safe
        point. In group mode, `groups` assigns each task its
        interchangeability group (see GroupSpec / set_groups).

        `width` is how wide the sources go up (Tcap when None): a
        service that admits a handful of pods a round ships arrays of
        its batch's bucket, one compiled program a width. A source that
        was not given (`job_ids`, `groups`) is then a zero array kept on
        the device and is not shipped at all."""
        resident_zeros = width is not None
        width = self.Tcap if width is None else int(width)
        if count > width:
            raise ValueError(f"{count} tasks do not fit sources {width} wide")
        jobs = np.zeros(width, np.int32)
        cls = np.zeros(width, np.int32)
        grp = np.zeros(width, np.int32)
        if job_ids is not None:
            jobs[: len(job_ids)] = job_ids
        if classes is not None:
            cls[: len(classes)] = classes
        if groups is not None:
            if not self.grouped:
                raise ValueError("groups requires num_groups > 0")
            g = np.asarray(groups, np.int32)
            if ((g < 0) | (g >= self.G)).any():
                raise ValueError(
                    f"task group out of range [0, {self.G}): "
                    f"{g.min()}..{g.max()}"
                )
            grp[: len(g)] = g
            # round_core's census feeds cost_fn from per-task cls, so
            # grouped admissions must carry classes consistent with the
            # group table: derive them when omitted, validate otherwise.
            derived = self._groups_cls_host[g]
            if classes is None:
                cls[: len(g)] = derived
            else:
                got = np.asarray(classes, np.int32)
                if len(got) < len(g):
                    raise ValueError(
                        f"classes ({len(got)}) shorter than groups "
                        f"({len(g)}): every grouped task needs both"
                    )
                got = got[: len(g)]
                if (got != derived).any():
                    bad = int(np.nonzero(got != derived)[0][0])
                    raise ValueError(
                        f"task {bad}: class {got[bad]} inconsistent with "
                        f"group {g[bad]}'s class {derived[bad]}"
                    )
        self.state, self.last_admitted = self._admit_jit(
            self.state,
            self._zeros(width) if resident_zeros and job_ids is None else jnp.asarray(jobs),
            jnp.asarray(cls),
            self._zeros(width) if resident_zeros and groups is None else jnp.asarray(grp),
            jnp.int32(count),
        )

    def _zeros(self, width: int):
        """int32 zeros [width], made once a width and kept on the device."""
        if width not in self._zeros_by_width:
            self._zeros_by_width[width] = jnp.zeros(width, jnp.int32)
        return self._zeros_by_width[width]

    def set_groups(
        self, cls=None, job=None, e=None, u=None, pref_w=None
    ) -> None:
        """Upload group metadata (group mode). Each argument updates
        the corresponding GroupSpec field ([G] arrays; pref_w [G, M],
        PREF_NONE = no preference); omitted fields keep their current
        values. Host -> device only — no recompilation (the arrays are
        traced arguments of the round programs)."""
        if not self.grouped:
            raise ValueError("set_groups requires num_groups > 0")
        limit = COST_SCALE_LIMIT // self.n_scale

        def _vec(name, val, cur, index_range=None):
            if val is None:
                return cur
            a = np.asarray(val, np.int64)  # kschedlint: host-only (host staging; cast at the jit boundary)
            if a.shape != (self.G,):
                raise ValueError(f"{name} must have shape ({self.G},), got {a.shape}")
            if index_range is not None:
                if a.size and ((a < 0) | (a >= index_range)).any():
                    raise ValueError(
                        f"{name} out of range [0, {index_range}): "
                        f"{a.min()}..{a.max()}"
                    )
            elif a.size and np.abs(a).max() >= limit:
                raise OverflowError(
                    f"{name} magnitude {np.abs(a).max()} exceeds the "
                    f"scaled-cost limit {limit}"
                )
            return jnp.asarray(a.astype(np.int32))

        pw = self.groups.pref_w
        if pref_w is not None:
            a = np.asarray(pref_w, np.int64)  # kschedlint: host-only (host staging; cast at the jit boundary)
            if a.shape != (self.G, self.M):
                raise ValueError(
                    f"pref_w must have shape ({self.G}, {self.M}), got {a.shape}"
                )
            real = a[a < PREF_NONE]
            if real.size and np.abs(real).max() >= limit:
                raise OverflowError(
                    f"pref_w magnitude {np.abs(real).max()} exceeds the "
                    f"scaled-cost limit {limit}"
                )
            pw = jnp.asarray(np.minimum(a, PREF_NONE).astype(np.int32))
        self.groups = GroupSpec(
            cls=_vec("cls", cls, self.groups.cls, index_range=self.C),
            job=_vec("job", job, self.groups.job, index_range=self.J),
            e=_vec("e", e, self.groups.e),
            u=_vec("u", u, self.groups.u),
            pref_w=pw,
        )
        if cls is not None:
            self._groups_cls_host = np.asarray(cls, np.int32).copy()

    def complete_tasks(self, rows, width: Optional[int] = None) -> None:
        """Retire the tasks in `rows`, shipped `width` wide (Tcap when
        None; a served round's bucket otherwise)."""
        pad = np.full(self.Tcap if width is None else int(width), self.Tcap, np.int32)
        pad[: len(rows)] = rows
        self.state = self._complete_jit(
            self.state, jnp.asarray(pad), jnp.int32(len(rows))
        )

    def set_machine_enabled(self, machine_index: int, enabled: bool) -> None:
        self.state = self._set_machine_jit(
            self.state, jnp.int32(machine_index), bool(enabled)
        )

    def _scan_carry(self):
        """Scan carry: bare state, or (state, census_ref, k_since,
        kg_since) in stability-aware preemption mode."""
        if self.hybrid_preempt:
            return (
                self.state, self._hyb_census, self._hyb_k, self._hyb_kg
            )
        return self.state

    def _store_carry(self, carry):
        if self.hybrid_preempt:
            (self.state, self._hyb_census, self._hyb_k,
             self._hyb_kg) = carry
        else:
            self.state = carry

    def round(self) -> dict:
        """One scheduling round; returns un-fetched device stats (call
        fetch_stats() to materialize — the analogue of the reference's
        binding push AFTER the timed region). In stability-aware
        preemption mode this one-shot round is always a FULL tiered
        re-solve and resets the drift reference."""
        self.state, stats = self._round_jit(self.state, self.groups)
        if self.hybrid_preempt:
            self._hyb_census = self._census_jit(self.state)
            self._hyb_k = jnp.int32(0)
            self._hyb_kg = jnp.int32(0)
        self.last_stats = stats
        return stats

    def serve_round(self, decode_width: Optional[int] = None):
        """`round()` for a service: the same round over a decode window
        of `decode_width` unplaced rows (None: all Tcap), and the rows
        it placed. Returns un-fetched (summary, rows, pus): `rows` i32[K],
        the rows newly placed in row order among entries that read Tcap
        (keep `rows < Tcap`), and `pus` i32[K], the PU of each, K the
        window's width; `summary`
        i32[7], `round()`'s stats in the order of SERVED_SUMMARY (the
        flags as 0 / 1). A window that holds every unplaced row
        decodes exactly what the full width does, so the caller, who
        admitted them, takes the smallest of its widths that does (one
        compiled program a width). Reading back costs 8 K bytes and the
        scalars, not the table."""
        if self.preemption:
            raise ValueError("serve_round is the pin-on-place round: preemption is not served")
        self.state, summary, rows, pus = self._served_round_jit(
            self.state, self.groups, decode_width=decode_width
        )
        return summary, rows, pus

    def run_steady_rounds(
        self, num_rounds: int, churn_prob: float, arrivals: int, seed: int = 0
    ):
        """`num_rounds` chained churn rounds fully on device. Returns
        stacked stats (device arrays, un-fetched). In group mode,
        arrivals draw their group through the arrival map (identity by
        default; see set_arrival_groups)."""
        carry, stats = self._steady_scan_jit(
            self._scan_carry(),
            self.groups,
            jax.random.PRNGKey(seed),
            jnp.float32(churn_prob),
            int(arrivals),
            int(num_rounds),
            self._arrival_map,
            self._arrival_n,
        )
        self._store_carry(carry)
        self.last_stats = stats
        return stats

    def set_arrival_groups(self, gids) -> None:
        """Restrict on-device steady-round arrivals to these group ids:
        with LRU signature eviction the table has FREED rows between
        maintenance points, and uniform draws over [0, G) would admit
        tasks into them — zero-signature rows the real policy never
        populates. Draws are EXACTLY uniform over the registered set:
        the map is padded to [G] but the device draw indexes only its
        first len(gids) entries (no tiling skew toward low-indexed
        groups). Host -> device upload only; recompile-free (the map
        and count are traced args)."""
        if not self.grouped:
            raise ValueError("set_arrival_groups requires group mode")
        g = np.asarray(gids, np.int32)
        if g.size == 0 or ((g < 0) | (g >= self.G)).any():
            raise ValueError("gids must be non-empty, within [0, G)")
        if g.size > self.G:
            raise ValueError("more arrival gids than groups")
        self._arrival_map = jnp.asarray(np.resize(g, self.G))
        self._arrival_n = jnp.int32(g.size)

    def run_replay_rounds(self, schedule, seed: int = 0):
        """Replay `schedule` (a staged window schedule — see
        drivers/trace_replay.py DeviceTraceReplayDriver.stage) as one
        scanned device program: K rounds of machine toggles +
        completions + admissions + solve chained without host sync.
        Returns stacked stats (device arrays, un-fetched)."""
        carry, stats = self._replay_scan_jit(
            self._scan_carry(),
            self.groups,
            jnp.asarray(schedule["adm_job"]),
            jnp.asarray(schedule["adm_cls"]),
            jnp.asarray(schedule["adm_grp"]),
            jnp.asarray(schedule["adm_n"]),
            jnp.asarray(schedule["done_rows"]),
            jnp.asarray(schedule["done_n"]),
            jnp.asarray(schedule["tog_idx"]),
            jnp.asarray(schedule["tog_on"]),
            jnp.asarray(schedule["tog_n"]),
            jax.random.PRNGKey(seed),
        )
        self._store_carry(carry)
        self.last_stats = stats
        return stats

    def fetch_stats(self, stats=None) -> dict:
        got = jax.device_get(stats if stats is not None else self.last_stats)
        out = {k: np.asarray(v) for k, v in got.items()}
        if "cost_overflow" in out and bool(np.any(out["cost_overflow"])):
            raise OverflowError(
                "scaled layered costs overflow int32 in a device round "
                "(class_cost_fn values too large for "
                f"n_scale={self.n_scale}); the solve result is invalid"
            )
        return out

    def fetch_state(self) -> dict:
        got = jax.device_get(self.state)
        return got._asdict()

    # convenience for tests
    @property
    def num_live_tasks(self) -> int:
        return int(jax.device_get(jnp.sum(self.state.live)))

    @property
    def num_placed_tasks(self) -> int:
        return int(jax.device_get(jnp.sum(self.state.live & (self.state.pu >= 0))))


# -- Level-3 registry hook: the program this module owns (the other jit
# sites are the scanned replays' machinery, waived where they stand) -------
from ..analysis.program_registry import declare_programs as _declare_programs  # noqa: E402

_declare_programs(__name__, "array_served_round")
