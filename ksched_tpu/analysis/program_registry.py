"""Level-3 registry: every compiled program in the tree, declared once.

Levels 1/2 (ast_rules, jaxpr_contracts) grew ~30 hand-written test
functions asserting per-program invariants — scoped scatter exemptions,
ICI psum budgets, donated in-place buffers, pow2-bucket hash pins —
with nothing proving the NEXT jit'd program gets audited at all. Level
3 closes that hole with two pieces:

- **This registry**: a declarative table (`PROGRAMS`) where every
  compiled program registers once — name, abstract tracer (a factory
  in `jaxpr_contracts`), shape-bucket calls, and a contract spec
  (scatter policy, collective budget, 32-bit dtype policy, donation
  spec, telemetry-off hash pin, hash-stability class). A generic
  engine (`analysis/engine.py`) enforces every spec uniformly via
  `jax.make_jaxpr` and AOT ``.lower().compile()`` — one code path, no
  copy-pasted per-program assertions.
- **The sweep** (`ast_rules.rule_unregistered_program`, surfaced by
  ``tools/kschedlint.py --coverage``): every `jax.jit` /
  `pl.pallas_call` / `shard_map` call site under `ksched_tpu/` must
  carry ``# kschedlint: program=<registered-name>`` or an inline
  waiver with a rationale — program coverage is a ratchet, not an
  honor system.

This module is import-light on purpose (stdlib only — NO jax, NO
numpy): the lint CLI reads the registry in environments without the
jax_graft toolchain. Tracers are named by string and resolved lazily
by the engine.

Program-owning modules confirm ownership with a one-line hook::

    from ..analysis.program_registry import declare_programs
    declare_programs(__name__, "delta_apply", "warm_flow", "scale_cost")

`declare_programs` validates names eagerly (a typo fails at import
time), and the engine cross-checks that every spec's owning module
really declares it — so the registry, the source annotations, and the
modules can never drift apart silently.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Mapping, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# spec vocabulary
# ---------------------------------------------------------------------------

#: scatter policies a program may declare (docs/static_analysis.md):
#: - "forbidden": zero scatter-family primitives anywhere (every solve
#:   and audit program — TPU serializes scatter-adds).
#: - "scoped-exempt": the program MUST scatter (a vacuous exemption is
#:   an error): O(churn)-sized once-per-round maintenance outside any
#:   solve. Exactly the delta/plan/sharded/replicated appliers.
#: - "chaos-only": allowed to scatter, never dispatched in production
#:   (the corruption-injection poison used to prove the fingerprint
#:   audit catches bit flips).
#: - "active-set": a solve program that holds scan-CSR's active-set
#:   superstep (solver/jax_solver.py `active_superstep`): exactly
#:   `ProgramSpec.scatters` scatter-adds, every one inside the branch a
#:   superstep takes when the nodes that hold excess and their rows fit
#:   the caps, where an update costs ~9 ns on a v5e (PERF.md section 6,
#:   PR 50) and they are the whole write-back; that branch holds no
#:   gather, cumsum or top_k over the plan's rows.
SCATTER_POLICIES = ("forbidden", "scoped-exempt", "chaos-only", "active-set")

#: hash-stability classes:
#: - "pow2-bucket": raw sizes sharing a pow2 padding bucket trace
#:   byte-identical jaxprs (the recompile-hazard detector).
#: - "record-bucket": same, over pow2-padded delta-record counts.
#: - "shard-bucket": same, per (bucket, shard count) — each mesh size
#:   is its own executable.
#: - "exempt": traced shapes depend on graph structure (degree
#:   buckets / per-shard maxima); the recompile unit is the plan
#:   rebuild, which the plan tests cover. `reason` is mandatory.
HASH_STABILITY_KINDS = ("pow2-bucket", "record-bucket", "shard-bucket", "exempt")


@dataclass(frozen=True)
class TraceCall:
    """One concrete invocation of a spec's tracer: (args, kwargs)."""

    args: Tuple = ()
    kwargs: Tuple = ()  # sorted (key, value) pairs — hashable

    def as_kwargs(self) -> Dict:
        return dict(self.kwargs)


def call(*args, **kwargs) -> TraceCall:
    return TraceCall(args=tuple(args), kwargs=tuple(sorted(kwargs.items())))


@dataclass(frozen=True)
class HashStability:
    """Which tracer calls must (and must not) collide."""

    kind: str
    #: pairs of TraceCalls that MUST trace byte-identical jaxprs
    same: Tuple[Tuple[TraceCall, TraceCall], ...] = ()
    #: pairs that MUST differ (keeps the stability check non-vacuous)
    cross: Tuple[Tuple[TraceCall, TraceCall], ...] = ()
    reason: str = ""  # mandatory when kind == "exempt"

    def __post_init__(self):
        if self.kind not in HASH_STABILITY_KINDS:
            raise ValueError(f"unknown hash-stability kind {self.kind!r}")
        if self.kind == "exempt" and not self.reason:
            raise ValueError("exempt hash stability requires a reason")


@dataclass(frozen=True)
class DonationSpec:
    """Declared in-place buffers, audited on the COMPILED executable.

    XLA silently falls back to a copy when a donated input cannot
    alias an output (dtype/shape/layout mismatch) — doubling HBM for
    the delta/plan/sharded scatters with no error anywhere. The engine
    AOT-lowers `builder`'s callable (``.lower().compile()`` on CPU)
    and asserts every argnum in `donate_argnums` appears in the
    executable's ``input_output_alias`` config, with zero
    donation-unusable warnings."""

    donate_argnums: Tuple[int, ...]
    #: name of a ``jaxpr_contracts`` function returning
    #: ``(jitted_callable, abstract_args)`` for AOT lowering
    builder: str


@dataclass(frozen=True)
class CollectiveBudget:
    """The ICI traffic contract of a (sharded) program.

    `loop` pins exact per-superstep counts (eqns inside while/scan
    bodies); `total` pins exact whole-program counts; `forbidden`
    names primitive families that must not appear anywhere. Counts are
    occurrences in the traced program (a loop body counts once)."""

    loop: Tuple[Tuple[str, int], ...] = ()
    total: Tuple[Tuple[str, int], ...] = ()
    forbidden: Tuple[str, ...] = ()
    #: telemetry-ON variant must add loop psums (the soltel counters)
    knob_adds_loop_psum: bool = False


@dataclass(frozen=True)
class GatherBudget:
    """HBM gather-traffic contract (None = unchecked)."""

    hbm_loop: Optional[int] = None  # exact gathers in loop bodies, off-kernel
    kernel: Optional[int] = None  # exact gathers inside pallas_call bodies
    oneshot: Optional[int] = None  # exact per-solve (outside loops) gathers


@dataclass(frozen=True)
class ProgramSpec:
    """One registered compiled program and its full contract."""

    name: str
    module: str  # dotted module owning the jit/pallas/shard_map site
    kind: str  # "solve" | "maintenance" | "audit" | "chaos"
    tracer: str  # factory name in analysis/jaxpr_contracts
    trace: TraceCall = field(default_factory=TraceCall)
    #: extra shape buckets the dtype/scatter/gather checks also sweep
    extra: Tuple[TraceCall, ...] = ()
    scatter_policy: str = "forbidden"
    #: under "active-set": the exact number of scatter primitives
    scatters: Optional[int] = None
    dtype_policy: str = "int32"  # the only policy: no 64-bit anywhere
    collectives: Optional[CollectiveBudget] = None
    donation: Optional[DonationSpec] = None
    #: pinned normalized jaxpr hash of the DEFAULT (telemetry-off)
    #: trace — "disabled telemetry costs zero traced ops", held
    #: byte-identically across PRs (re-pin only with a jax upgrade)
    telemetry_off_hash: Optional[str] = None
    #: tracer kwarg enabling solver telemetry; the engine asserts
    #: knob=512 traces a DIFFERENT program and knob=0 the default one
    telemetry_knob: Optional[str] = None
    hash_stability: Optional[HashStability] = None
    gathers: Optional[GatherBudget] = None
    #: names of other registered programs whose default trace must
    #: hash differently (variant non-vacuity)
    distinct_from: Tuple[str, ...] = ()
    #: annotation name used at the call site (several variant specs
    #: share one physical jit site); defaults to `name`
    site: Optional[str] = None
    notes: str = ""

    def __post_init__(self):
        if self.scatter_policy not in SCATTER_POLICIES:
            raise ValueError(f"{self.name}: bad scatter policy {self.scatter_policy!r}")
        if self.dtype_policy != "int32":
            raise ValueError(f"{self.name}: bad dtype policy {self.dtype_policy!r}")
        if (self.scatter_policy == "active-set") != (self.scatters is not None):
            raise ValueError(f"{self.name}: `scatters` goes with the active-set policy")

    @property
    def site_name(self) -> str:
        return self.site or self.name


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

#: the three representative shape buckets the contract sweeps trace
#: (mirrored from the historical SHAPE_BUCKETS of the Level-2 suite)
_BUCKETS = ((12, 40), (20, 100), (40, 220))

#: pow2-bucket pairs per tracer family (same bucket -> same jaxpr)
_CSR_SAME = (
    (call(12, 40), call(15, 60)),
    (call(20, 100), call(30, 70)),
    (call(40, 220), call(60, 200)),
)
_CSR_CROSS = ((call(12, 40), call(12, 200)),)
_LAYERED_SAME = (
    (call(4, 40), call(4, 100)),
    (call(4, 130), call(4, 250)),
    (call(8, 300), call(8, 370)),
)
_LAYERED_CROSS = ((call(4, 40), call(4, 300)),)
_RECORD_SAME = ((call(3, 2), call(7, 5)),)
_RECORD_CROSS = ((call(3, 2), call(100, 2)),)
_RECORD_GRAPH_SAME = ((call(3, 2, n_raw=20, m_raw=100), call(3, 2, n_raw=24, m_raw=110)),)
_RECORD_GRAPH_CROSS = ((call(3, 2, n_raw=20, m_raw=100), call(3, 2, n_raw=20, m_raw=300)),)

#: what the scan-CSR solve gathers (solver/jax_solver.py `_solve_mcmf`,
#: both layouts; every gather a row gather, `_rows`). In loop bodies:
#: the superstep's six (four over the plan rows: a row's head-node
#: values, its tail node's potential, the prefix base, the partner's
#: push; two over the nodes: their first and last rows), the phase
#: change's four (`saturate`'s two potentials, the excess's two
#: boundary reads) and `tighten`'s two a sweep. Once a solve: the rows'
#: capacity/cost/warm flow, the partner rows, the prologue's saturate
#: and its two excess evaluations (the refit seeds `tighten` from the
#: carried prices and skips one), the flow read back. PR 28's program
#: had 24 / 15 (11 for the refit), all of scalars.
_CSR_GATHERS = GatherBudget(hbm_loop=12, oneshot=9)
_CSR_REFIT_GATHERS = GatherBudget(hbm_loop=12, oneshot=7)
#: with the active-set superstep traced beside the dense one (its
#: branch of the superstep's `cond`): seven more in the loop, none of
#: them over the plan's rows (at the active nodes their regions, price
#: and excess; at the compacted rows their node's values, the row's
#: cost / far end / partner / residual, the far end's price, the
#: prefix base; at the nodes' first and last compacted rows)
_CSR_ACTIVE_GATHERS = GatherBudget(hbm_loop=19, oneshot=9)

#: every collective family jaxpr_contracts counts — "forbid all"
_ALL_COLLECTIVES = ("psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute")

_SPECS = (
    # -- solver programs (solver/select.py rungs + variants) ------------
    ProgramSpec(
        name="csr_solve", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_jax", trace=call(20, 100),
        extra=(call(12, 40), call(40, 220)),
        telemetry_off_hash="81f926532b54d878", telemetry_knob="telemetry_cap",
        hash_stability=HashStability("pow2-bucket", same=_CSR_SAME, cross=_CSR_CROSS),
        gathers=_CSR_GATHERS,
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="scan-CSR push-relabel, state carried in entry space (PR 29); the "
        "exact loop-gather count is the budget (a gather put back into the "
        "loop fails here, on the CPU) and, being > 0, the gather-classifier "
        "canary as well (CSR pays per-superstep HBM gathers by design)",
    ),
    ProgramSpec(
        name="csr_solve_warmp", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_jax_warmp", trace=call(20, 100), site="csr_solve",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        distinct_from=("csr_solve",),
        gathers=_CSR_REFIT_GATHERS,
        notes="dirty-frontier warm-price refit; that the DEFAULT trace takes "
        "no warm_p invar is csr_solve's telemetry_off_hash",
    ),
    ProgramSpec(
        name="csr_solve_slot", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_jax_slot_stable", trace=call(20, 100), site="csr_solve",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        distinct_from=("csr_solve",),
        gathers=_CSR_GATHERS,
        notes="slot-stable layout: dead rows masked through the sign column",
    ),
    ProgramSpec(
        name="csr_refit_slot", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_jax_warmp", trace=call(20, 100, slot_stable=True),
        site="csr_solve", distinct_from=("csr_solve_warmp",),
        gathers=_CSR_REFIT_GATHERS,
        notes="the production event-path program: refit ON TOP of the "
        "slot-stable plan",
    ),
    ProgramSpec(
        name="csr_solve_active", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_jax_active", trace=call(20, 100), site="csr_solve",
        extra=(call(12, 40), call(40, 220)),
        telemetry_knob="telemetry_cap",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        distinct_from=("csr_solve_slot",),
        scatter_policy="active-set", scatters=4,
        gathers=_CSR_ACTIVE_GATHERS,
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="what JaxSolver dispatches (PR 50): the slot-stable program with "
        "the active-set superstep beside the dense one, chosen per superstep "
        "from the loop state; its four scatter-adds (the span marks, r, "
        "excess, p) are the chip reading's, and the dense branch is "
        "csr_solve_slot's superstep",
    ),
    ProgramSpec(
        name="stacked_solve", module="ksched_tpu.solver.jax_solver", kind="solve",
        tracer="trace_stacked", trace=call(4, 20, 100),
        telemetry_knob="telemetry_cap",
        hash_stability=HashStability(
            "pow2-bucket",
            same=((call(3, 20, 100), call(4, 24, 110)),),
            cross=(
                (call(3, 20, 100), call(8, 20, 100)),  # lane bucket
                (call(3, 20, 100), call(4, 20, 300)),  # shape bucket
            ),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="multi-tenant jit(vmap) batched solve; lane-count AND shape "
        "bucket stable (tenant churn must not recompile)",
    ),
    ProgramSpec(
        name="stacked_solve_warmp", module="ksched_tpu.solver.jax_solver",
        kind="solve", tracer="trace_stacked",
        trace=call(4, 20, 100, use_warm_p=True), site="stacked_solve",
        distinct_from=("stacked_solve",),
        notes="lane-batched dirty-frontier refit (the warm seed is a real invar)",
    ),
    ProgramSpec(
        name="layered_solve", module="ksched_tpu.solver.layered", kind="solve",
        tracer="trace_layered", trace=call(20, 100),
        extra=(call(12, 40), call(40, 220)),
        telemetry_off_hash="d9971a009af01491", telemetry_knob="telemetry_cap",
        hash_stability=HashStability(
            "pow2-bucket", same=_LAYERED_SAME, cross=_LAYERED_CROSS
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
    ),
    ProgramSpec(
        name="sharded_solve", module="ksched_tpu.parallel.sharded_solver",
        kind="solve", tracer="trace_sharded", trace=call(20, 100),
        extra=(call(12, 40), call(40, 220)),
        telemetry_off_hash="5fe7fb5d9c0a5982", telemetry_knob="telemetry_cap",
        hash_stability=HashStability(
            "exempt",
            reason="legacy ShardedPlan shapes depend on per-shard maxima; "
            "the recompile unit is build_sharded_plan (superseded by "
            "sharded_slot_solve on the event path)",
        ),
        notes="hash pin is mesh-size-dependent (conftest's 8-device "
        "virtual CPU mesh)",
    ),
    ProgramSpec(
        name="sharded_slot_solve", module="ksched_tpu.parallel.sharded_solver",
        kind="solve", tracer="trace_sharded_slot",
        trace=call(20, 100, num_devices=2), telemetry_knob="telemetry_cap",
        hash_stability=HashStability(
            "shard-bucket",
            same=tuple(
                (call(20, 100, num_devices=d), call(24, 110, num_devices=d))
                for d in (2, 4, 8)
            ),
            cross=(
                (call(20, 100, num_devices=2), call(20, 100, num_devices=4)),
                (call(20, 100, num_devices=4), call(20, 100, num_devices=8)),
                (call(20, 100, num_devices=2), call(20, 100, num_devices=8)),
            ),
        ),
        collectives=CollectiveBudget(
            loop=(("psum", 3), ("pmin", 1), ("pmax", 2)),
            forbidden=("all_gather", "all_to_all", "ppermute"),
            knob_adds_loop_psum=True,
        ),
        notes="exactly 3 vector psums cross ICI per superstep (the [N] "
        "excess, [M] arc-delta, [N] potential combines); pmin = tighten "
        "sweep, pmax = sat_full's fwd/bwd phase-boundary combines",
    ),
    ProgramSpec(
        name="sharded_slot_solve_warmp",
        module="ksched_tpu.parallel.sharded_solver", kind="solve",
        tracer="trace_sharded_slot",
        trace=call(20, 100, num_devices=2, use_warm_p=True),
        site="sharded_slot_solve", distinct_from=("sharded_slot_solve",),
    ),
    # -- maintenance programs (the scoped scatter exemptions) -----------
    ProgramSpec(
        name="delta_apply", module="ksched_tpu.graph.device_export",
        kind="maintenance", tracer="trace_delta_apply", trace=call(5, 3),
        scatter_policy="scoped-exempt",
        donation=DonationSpec(donate_argnums=(0, 3, 4), builder="aot_delta_apply"),
        hash_stability=HashStability(
            "record-bucket",
            same=_RECORD_SAME + _RECORD_GRAPH_SAME,
            cross=_RECORD_CROSS + _RECORD_GRAPH_CROSS,
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="O(churn) once-per-round problem-delta scatter; excess/cap/"
        "cost donated in place (measured 498 -> 8.7 us/apply at 256k rows); "
        "arc and node records share one pow2 bucket, and the buckets of a "
        "mirror (graph/device_export.record_buckets) are compiled where its "
        "buffers are allocated",
    ),
    ProgramSpec(
        name="plan_apply", module="ksched_tpu.graph.slot_plan",
        kind="maintenance", tracer="trace_plan_apply", trace=call(5, 3),
        scatter_policy="scoped-exempt",
        donation=DonationSpec(
            donate_argnums=tuple(range(10)), builder="aot_plan_apply"
        ),
        hash_stability=HashStability(
            "record-bucket",
            same=_RECORD_SAME + _RECORD_GRAPH_SAME,
            cross=_RECORD_CROSS + _RECORD_GRAPH_CROSS,
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="slot-stable plan-row + boundary-static apply; all ten plan "
        "tensors donated; the four record streams share one pow2 bucket of "
        "the same closed set",
    ),
    ProgramSpec(
        name="sharded_plan_apply", module="ksched_tpu.parallel.sharded_solver",
        kind="maintenance", tracer="trace_sharded_plan_apply", trace=call(5, 3),
        scatter_policy="scoped-exempt",
        donation=DonationSpec(
            donate_argnums=(0, 1, 2, 3, 4, 5), builder="aot_sharded_plan_apply"
        ),
        hash_stability=HashStability(
            "record-bucket", same=_RECORD_SAME, cross=_RECORD_CROSS
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="per-shard routed plan scatter; zero collectives (routing "
        "happened on host), six entry tensors donated",
    ),
    ProgramSpec(
        name="replicated_plan_apply",
        module="ksched_tpu.parallel.sharded_solver", kind="maintenance",
        tracer="trace_replicated_plan_apply", trace=call(5, 3),
        scatter_policy="scoped-exempt",
        donation=DonationSpec(
            donate_argnums=(0, 1, 2, 3), builder="aot_replicated_plan_apply"
        ),
        hash_stability=HashStability(
            "record-bucket", same=_RECORD_SAME, cross=_RECORD_CROSS
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="the replicated remainder of a sharded plan sync (inv-order "
        "+ node boundaries). Shipped UNAUDITED in r15 — the registry "
        "sweep is what surfaced it; the fourth (and last) scoped "
        "scatter exemption",
    ),
    ProgramSpec(
        name="warm_flow", module="ksched_tpu.graph.device_export",
        kind="maintenance", tracer="trace_warm_flow",
        gathers=GatherBudget(hbm_loop=0, kernel=0, oneshot=0),
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="pure elementwise warm-flow carry: scatter- AND gather-free",
    ),
    ProgramSpec(
        name="scale_cost", module="ksched_tpu.graph.device_export",
        kind="maintenance", tracer="trace_scale_cost",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="cost pre-scaling (cost * n) before a device solve",
    ),
    ProgramSpec(
        name="array_served_round", module="ksched_tpu.scheduler.device_bulk",
        kind="solve", tracer="trace_array_served_round", trace=call(12, 1024),
        extra=(call(12, 1024, decode_width=256),),
        scatter_policy="scoped-exempt",
        hash_stability=HashStability(
            "exempt", reason="one table, one set of decode widths a service: "
            "the shapes are fixed when the cluster is built and no size is bucketed",
        ),
        distinct_from=(),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="the array round as `--array-round` serves it (PR 53): the class "
        "census, CoCo's costs, the dense transport and the rank-match decode over "
        "a window of the table's unplaced rows, then the rows it placed, "
        "compacted. It scatters by design, once a round and outside the "
        "transport's loop: the census and the supply are scatter-adds over the "
        "table's rows, the window's placements a scatter back",
    ),
    # -- audit programs (integrity fingerprints — normal round cadence,
    #    so NO scatter exemption) ---------------------------------------
    ProgramSpec(
        name="state_fingerprint", module="ksched_tpu.runtime.integrity",
        kind="audit", tracer="trace_state_fingerprint",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
    ),
    ProgramSpec(
        name="plan_fingerprint", module="ksched_tpu.runtime.integrity",
        kind="audit", tracer="trace_plan_fingerprint",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
    ),
    ProgramSpec(
        name="buffer_fingerprint", module="ksched_tpu.runtime.integrity",
        kind="audit", tracer="trace_buffer_fingerprint",
        hash_stability=HashStability(
            "pow2-bucket", same=((call(20, 100), call(24, 110)),),
            cross=((call(20, 100), call(20, 300)),),
        ),
        collectives=CollectiveBudget(forbidden=_ALL_COLLECTIVES),
        notes="single-buffer checksum (the warm-flow audit's _FP_ONE)",
    ),
    ProgramSpec(
        name="sharded_plan_fingerprint",
        module="ksched_tpu.parallel.sharded_solver", kind="audit",
        tracer="trace_sharded_plan_fingerprint", trace=call(),
        collectives=CollectiveBudget(
            total=(("psum", 6),),
            forbidden=("pmin", "pmax", "all_gather", "all_to_all", "ppermute"),
        ),
        notes="per-shard partials psum'd to one comparable checksum — "
        "exactly 6 psums (the entry-shaped tensors), nothing else",
    ),
    # -- chaos programs --------------------------------------------------
    ProgramSpec(
        name="corrupt_flip", module="ksched_tpu.runtime.integrity",
        kind="chaos", tracer="trace_corrupt_flip",
        scatter_policy="chaos-only",
        notes="the seeded poison scatter: flips one bit of one element "
        "to prove the fingerprint audit detects it; never dispatched in "
        "production",
    ),
)

PROGRAMS: Dict[str, ProgramSpec] = {s.name: s for s in _SPECS}
if len(PROGRAMS) != len(_SPECS):  # duplicate name = table bug
    raise RuntimeError("duplicate program name in registry")

#: annotation names valid at call sites (variant specs share a site)
SITE_NAMES: frozenset = frozenset(s.site_name for s in _SPECS)


def registered_names() -> frozenset:
    return frozenset(PROGRAMS)


def donating_programs() -> Tuple[ProgramSpec, ...]:
    return tuple(s for s in _SPECS if s.donation is not None)


def specs_for_site(site_name: str) -> Tuple[ProgramSpec, ...]:
    return tuple(s for s in _SPECS if s.site_name == site_name)


# ---------------------------------------------------------------------------
# ownership declarations
# ---------------------------------------------------------------------------

#: module -> names it declared (owners and consumers both appear here)
DECLARED: Dict[str, Set[str]] = {}


def declare_programs(module: str, *names: str) -> None:
    """Registration hook for program-owning (and consuming) modules.

    Validates eagerly: an unregistered name raises at the owning
    module's import — a typo can never silently declare nothing."""
    unknown = [n for n in names if n not in PROGRAMS]
    if unknown:
        raise ValueError(
            f"{module} declares unregistered program(s) {unknown}; "
            "register them in ksched_tpu/analysis/program_registry.py"
        )
    DECLARED.setdefault(module, set()).update(names)
