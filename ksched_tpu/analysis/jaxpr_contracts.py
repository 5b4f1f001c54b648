"""Level-2 contracts: invariants checked on the TRACED program.

Every registered solver backend (solver/select.py: jax, layered,
plus parallel/sharded_*) is traced abstractly with
`jax.make_jaxpr` over `ShapeDtypeStruct`s — no device arrays, no
compile, CPU-safe — and the resulting jaxpr is walked recursively
(pjit / while / cond / scan / pallas_call sub-jaxprs included) to
assert:

- **no-64bit**: no `convert_element_type` (or iota/constant aval) with
  a 64-bit dtype anywhere. "Everything is int32" (solver/jax_solver.py
  header: TPU v5e has no native int64) holds in the traced program,
  not just in the source text the AST lint sees.
- **no-scatter**: zero scatter-family primitives in any backend's
  solve. TPU serializes scatter-adds (~68 ms for a 64k segment_sum,
  jax_solver.py header); every segment reduction must stay in
  cumsum/gather/associative-scan form. Exactly THREE programs hold
  scoped exemptions, all O(churn)-sized once-per-round maintenance
  scatters that run OUTSIDE every solve: the device-resident problem
  delta apply (graph/device_export.delta_apply_fn, pinned by
  `trace_delta_apply`), the slot-stable plan-row apply
  (graph/slot_plan.plan_apply_fn, pinned by `trace_plan_apply`), and
  the per-shard routed sharded plan apply (parallel/sharded_solver.
  sharded_plan_apply_fn, pinned by `trace_sharded_plan_apply`). Each
  pin asserts the exemption is real (the program actually scatters),
  stays 32-bit, and hashes stably within a pow2 record bucket; every
  solver program stays at zero — including the slot-stable solve
  variant (`trace_jax_slot_stable`), the dirty-frontier warm-price
  refit (`trace_jax_warmp`), and the slot-stable SHARDED solve
  (`trace_sharded_slot`, additionally hash-stable per shard-count
  bucket at 2/4/8 devices).
- **pow2-bucket stability** (recompile-hazard detector): two raw
  problem sizes sharing a pow2 padding bucket must produce
  byte-identical jaxprs — if a raw size leaks into a static argument
  or a host-derived shape, the hash splits and the gate names the
  recompile before a production cluster discovers it as a per-round
  compile stall.

The sharded backends build entry tables whose SHAPES depend on graph
structure (per-shard maxima), not only on (n, m); they get the
dtype/scatter contracts via plans built from a deterministic
generator graph, and are exempt from the bucket-hash contract (their
recompile unit is the plan rebuild, which existing tests cover). See
docs/static_analysis.md.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

#: the backend names this suite traces, mirroring solver/select.py
#: ("native" is C++, "ref" is pure numpy, "auto" composes the others)
REGISTERED_BACKENDS = ("jax", "layered", "sharded")

#: backends whose traced shapes are a function of the padded (n, m)
#: alone — the pow2-bucket hash contract applies to exactly these
HASH_STABLE_BACKENDS = ("jax", "layered")

_64BIT = frozenset({"int64", "uint64", "float64", "complex128"})


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> Iterable:
    for val in eqn.params.values():
        for sub in val if isinstance(val, (list, tuple)) else [val]:
            core = getattr(sub, "jaxpr", sub)
            if hasattr(core, "eqns"):
                yield core


def walk_eqns(jaxpr, in_pallas: bool = False, in_loop: bool = False):
    """Yield (eqn, in_pallas, in_loop) over the whole nested jaxpr.
    `in_loop` marks bodies whose eqns run per loop iteration (while /
    scan); `in_pallas` marks the kernel body, where every operand is
    on-chip by BlockSpec construction."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield eqn, in_pallas, in_loop
        child_pallas = in_pallas or name == "pallas_call"
        child_loop = in_loop or name in ("while", "scan")
        for sub in _sub_jaxprs(eqn):
            yield from walk_eqns(sub, child_pallas, child_loop)


def _aval_dtypes(eqn) -> Iterable[str]:
    for var in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(var, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield str(dtype)


# ---------------------------------------------------------------------------
# contracts
# ---------------------------------------------------------------------------


@dataclass
class ContractReport:
    backend: str
    shape_key: Tuple
    num_eqns: int
    violations_64bit: List[str]
    scatter_eqns: List[str]
    hbm_loop_gathers: int  # gathers outside pallas_call, inside loop bodies
    kernel_gathers: int  # gathers inside a pallas_call body (VMEM reads)
    oneshot_gathers: int  # gathers outside any loop (per-solve, not per-step)
    jaxpr_hash: str

    @property
    def ok_64bit(self) -> bool:
        return not self.violations_64bit

    @property
    def ok_scatter(self) -> bool:
        return not self.scatter_eqns


_SRC_INFO_RE = None


def _normalize_jaxpr_str(text: str) -> str:
    """Strip trace metadata that varies without the PROGRAM changing:
    `origin='...'` operand labels and `... at /path/file.py:NN` source
    infos (pallas embeds both in its jaxpr params — a comment edit
    above a kernel would otherwise split the hash)."""
    global _SRC_INFO_RE
    if _SRC_INFO_RE is None:
        import re

        _SRC_INFO_RE = (
            re.compile(r"origin='[^']*'"),
            re.compile(r" at [^\s,)]+\.py:\d+"),
        )
    for pat in _SRC_INFO_RE:
        text = pat.sub("", text)
    return text


def jaxpr_hash(closed) -> str:
    return hashlib.sha256(
        _normalize_jaxpr_str(str(closed)).encode()
    ).hexdigest()[:16]


def check_jaxpr(backend: str, closed, shape_key: Tuple = ()) -> ContractReport:
    violations_64bit: List[str] = []
    scatter_eqns: List[str] = []
    hbm_loop = kernel = oneshot = 0
    num_eqns = 0
    for eqn, in_pallas, in_loop in walk_eqns(closed.jaxpr):
        num_eqns += 1
        name = eqn.primitive.name
        if name == "convert_element_type":
            new = str(eqn.params.get("new_dtype"))
            if new in _64BIT:
                violations_64bit.append(f"convert_element_type -> {new}")
        for dtype in _aval_dtypes(eqn):
            if dtype in _64BIT:
                violations_64bit.append(f"{name}: {dtype} aval")
        if name.startswith("scatter"):
            scatter_eqns.append(name)
        elif name == "gather":
            if in_pallas:
                kernel += 1
            elif in_loop:
                hbm_loop += 1
            else:
                oneshot += 1
    return ContractReport(
        backend=backend,
        shape_key=shape_key,
        num_eqns=num_eqns,
        violations_64bit=violations_64bit,
        scatter_eqns=scatter_eqns,
        hbm_loop_gathers=hbm_loop,
        kernel_gathers=kernel,
        oneshot_gathers=oneshot,
        jaxpr_hash=jaxpr_hash(closed),
    )


# ---------------------------------------------------------------------------
# per-backend abstract tracing
# ---------------------------------------------------------------------------


def bucketed_sizes(n_raw: int, m_raw: int) -> Tuple[int, int]:
    """(Np, Mp): the padded extents DeviceGraphState hands every
    solver (graph/device_export.py full_build) — the pow2 bucket."""
    from ..utils import next_pow2

    return max(next_pow2(n_raw), 16), max(next_pow2(m_raw), 16)


def _sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _generator_graph(n: int, m: int, seed: int = 0):
    """Deterministic connected-ish multigraph with skewed degrees (node
    0 is a hub: a third of the arcs leave it)."""
    rng = np.random.default_rng(seed)
    src = np.where(
        np.arange(m) % 3 == 0, 0, rng.integers(0, n, m)
    ).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n - 1, m)) % n).astype(np.int32)
    return src, dst


def trace_jax(n_raw: int, m_raw: int, seed: int = 0, telemetry_cap: int = 0):
    from ..solver.jax_solver import _solve_mcmf

    n, m = bucketed_sizes(n_raw, m_raw)
    fn = functools.partial(
        _solve_mcmf, alpha=8, max_supersteps=4096, tighten_sweeps=32,
        telemetry_cap=telemetry_cap,
    )
    e = 2 * m
    return jax.make_jaxpr(fn)(
        _sds((m,)), _sds((m,)), _sds((n,)), _sds((m,)), _sds(()),
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)),
        _sds((e,), jnp.bool_), _sds((e,)),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
    )


def trace_layered(n_raw: int, m_raw: int, seed: int = 0, telemetry_cap: int = 0):
    """(n_raw, m_raw) doubles as (num_classes, num_machines): the
    layered backend's problem geometry."""
    from ..solver.layered import _solve_transport, pad_geometry

    C = max(1, n_raw)
    Mp, _n_scale = pad_geometry(m_raw, C)
    fn = functools.partial(
        _solve_transport, alpha=8, max_supersteps=4096, refine_waves=0,
        telemetry_cap=telemetry_cap,
    )
    return jax.make_jaxpr(fn)(
        _sds((C, Mp)), _sds((C,)), _sds((Mp,)), _sds(()), _sds((Mp,))
    )


def trace_sharded(n_raw: int, m_raw: int, seed: int = 0, telemetry_cap: int = 0):
    from jax.sharding import Mesh

    from ..parallel.sharded_solver import build_sharded_plan, make_sharded_solver

    n, m = bucketed_sizes(n_raw, m_raw)
    src, dst = _generator_graph(n, m, seed)
    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("x",))
    plan = build_sharded_plan(src, dst, n, len(devices))
    fn = make_sharded_solver(
        mesh, "x", alpha=8, max_supersteps=4096, telemetry_cap=telemetry_cap
    )
    plan_sds = tuple(
        _sds(np.shape(x), np.asarray(x).dtype)
        for x in (
            plan.s_arc, plan.s_sign, plan.s_src, plan.s_dst,
            plan.s_segstart, plan.s_isstart, plan.s_valid,
            plan.node_first, plan.node_last, plan.node_nonempty,
            plan.owned, plan.pos_fwd, plan.pos_bwd,
        )
    )
    return jax.make_jaxpr(fn)(
        _sds((m,)), _sds((m,)), _sds((n,)), _sds((m,)), _sds(()), _sds(()),
        *plan_sds,
    )


def _mesh_of(num_devices: int):
    from jax.sharding import Mesh

    devices = jax.devices()
    assert len(devices) >= num_devices, (
        f"need {num_devices} devices for the sharded contracts "
        "(conftest forces an 8-device virtual CPU mesh)"
    )
    return Mesh(np.array(devices[:num_devices]), ("x",))


def trace_sharded_slot(
    n_raw: int,
    m_raw: int,
    num_devices: int = 2,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
):
    """Abstract trace of the slot-stable SHARDED solve
    (parallel/sharded_solver.make_sharded_slot_solver): entry tensors
    stacked [D, Es] with Es the pow2 per-shard block extent — a
    function of the (m-bucket, shard count) alone, never the raw size,
    which is what the shard-count-bucket hash pins assert."""
    from ..parallel.sharded_solver import (
        make_sharded_slot_solver,
        sharded_entry_extent,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    D = num_devices
    es = sharded_entry_extent(m, D)
    mesh = _mesh_of(D)
    fn = make_sharded_slot_solver(
        mesh, "x", alpha=8, max_supersteps=4096,
        telemetry_cap=telemetry_cap, use_warm_p=use_warm_p,
    )
    args = [
        _sds((m,)), _sds((m,)), _sds((n,)), _sds((m,)), _sds(()), _sds(()),
        _sds((D, es)), _sds((D, es)), _sds((D, es)), _sds((D, es)),
        _sds((D, es)), _sds((D, es), jnp.bool_),
        _sds((2 * m,)), _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
    ]
    if use_warm_p:
        args.append(_sds((n,)))
    return jax.make_jaxpr(fn)(*args)


def trace_sharded_plan_apply(
    kp_raw: int, ks_raw: int, num_devices: int = 2,
    n_raw: int = 20, m_raw: int = 100,
):
    """Abstract trace of the THIRD scatter-exempt program: the
    per-shard routed plan-row + segment-static apply
    (parallel/sharded_solver.sharded_plan_apply_fn) over pow2-bucketed
    per-shard record counts."""
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import PLAN_RECORD_COLS, SEG_RECORD_COLS
    from ..parallel.sharded_solver import (
        sharded_entry_extent,
        sharded_plan_apply_fn,
    )

    _n, m = bucketed_sizes(n_raw, m_raw)
    D = num_devices
    es = sharded_entry_extent(m, D)
    kp = pad_record_count(kp_raw)
    ks = pad_record_count(ks_raw)
    fn = sharded_plan_apply_fn(_mesh_of(D), "x")
    return jax.make_jaxpr(fn)(
        _sds((D, es)), _sds((D, es)), _sds((D, es)), _sds((D, es)),
        _sds((D, es)), _sds((D, es), jnp.bool_),
        _sds((D, kp, PLAN_RECORD_COLS)), _sds((D, ks, SEG_RECORD_COLS)),
    )


def trace_sharded_plan_fingerprint(num_devices: int = 2, n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the sharded plan fingerprint (per-shard
    global-weight partials psum'd to one comparable checksum) — an
    audit program on the normal round cadence, so NO scatter
    exemption."""
    from ..parallel.sharded_solver import (
        sharded_entry_extent,
        sharded_plan_fingerprint_fn,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    D = num_devices
    es = sharded_entry_extent(m, D)
    fn = sharded_plan_fingerprint_fn(_mesh_of(D), "x")
    return jax.make_jaxpr(fn)(
        _sds((D, es)), _sds((D, es)), _sds((D, es)), _sds((D, es)),
        _sds((2 * m,)), _sds((D, es)), _sds((D, es), jnp.bool_),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
    )


#: collective primitive families counted by count_collectives — the
#: ICI traffic classes of a sharded program
_COLLECTIVE_PRIMS = ("psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute")


def count_collectives(closed, loop_only: bool = False) -> Dict[str, int]:
    """Occurrences of each collective primitive in the traced program
    (loop bodies count ONCE — multiply by superstep counts for traffic
    totals). With ``loop_only`` only eqns inside while/scan bodies
    count — the per-superstep ICI reduction budget of a sharded solve
    (prologue/one-shot collectives excluded). The sharded programs'
    ICI-reduction contracts (analysis/engine.py) read both views."""
    counts: Dict[str, int] = {}
    for eqn, _p, in_loop in walk_eqns(closed.jaxpr):
        if loop_only and not in_loop:
            continue
        name = eqn.primitive.name
        for prim in _COLLECTIVE_PRIMS:
            if name == prim or name.startswith(prim + "_"):
                counts[prim] = counts.get(prim, 0) + 1
    return counts


def count_superstep_collectives(closed) -> Dict[str, int]:
    """Loop-body-only view of :func:`count_collectives`."""
    return count_collectives(closed, loop_only=True)


def trace_jax_warmp(n_raw: int, m_raw: int, seed: int = 0, telemetry_cap: int = 0,
                    slot_stable: bool = False):
    """The warm-potentials variant of the CSR solve — since the
    dirty-frontier refit landed, use_warm_p=True SEEDS the tightening
    Bellman sweep with the previous round's device-resident prices
    (clipped), so the relaxation touches only the journal-dirty
    frontier. The refit is plain data-parallel relaxation: it must
    stay scatter-free like every solve program. A distinct traced
    program — the default (warm_p=None, use_warm_p=False) trace takes
    no warm_p invar and stays on csr_solve's pinned hash."""
    from ..solver.jax_solver import _solve_mcmf

    n, m = bucketed_sizes(n_raw, m_raw)
    fn = functools.partial(
        _solve_mcmf, alpha=8, max_supersteps=4096, tighten_sweeps=32,
        telemetry_cap=telemetry_cap, use_warm_p=True,
        slot_stable=slot_stable,
    )
    e = 2 * m
    return jax.make_jaxpr(fn)(
        _sds((m,)), _sds((m,)), _sds((n,)), _sds((m,)), _sds(()),
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)),
        _sds((e,), jnp.bool_), _sds((e,)),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
        _sds((n,)),  # warm_p
    )


def slot_stable_entry_cap(m_pad: int) -> int:
    """The entry-table extent the slot-stable layout pads to for an
    m_pad-arc bucket at BUILD time (graph/slot_plan.SlotPlanState
    ._rebuild, a first build or one that m_cap / n_cap growth forced:
    max(2*m_cap, next_pow2(need)) — need exceeds 2*m_cap only when
    per-node slack rows outgrow the doubled entries, which next_pow2
    then absorbs; either way a pow2 of the bucket, never the raw
    size). A re-fit may take a plan to a smaller pow2 once a fill
    round's transient arcs are gone (SlotPlanState.refit,
    refit_bucket): the same program at another E, which nothing here
    relates to m."""
    return 2 * m_pad


def trace_jax_slot_stable(n_raw: int, m_raw: int, seed: int = 0,
                          telemetry_cap: int = 0, active_rows_share: int = 0):
    """The slot-stable variant of the CSR solve: entry rows live in
    fixed per-node regions with slack and liveness rides the sign
    column (graph/slot_plan.py), so the residual formula masks dead
    rows to zero. Still a solve program: zero scatters, no 64-bit,
    pow2-bucket hash stable (the entry extent is a function of the
    m-bucket alone)."""
    from ..solver.jax_solver import _solve_mcmf

    n, m = bucketed_sizes(n_raw, m_raw)
    e = slot_stable_entry_cap(m)
    fn = functools.partial(
        _solve_mcmf, alpha=8, max_supersteps=4096, tighten_sweeps=32,
        telemetry_cap=telemetry_cap, slot_stable=True,
        # `trace_jax_active`: caps of 4 nodes and a share of the rows
        active_set=(4, e // active_rows_share) if active_rows_share else None,
    )
    return jax.make_jaxpr(fn)(
        _sds((m,)), _sds((m,)), _sds((n,)), _sds((m,)), _sds(()),
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)),
        _sds((e,), jnp.bool_), _sds((2 * m,)),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
    )


def trace_jax_active(n_raw: int, m_raw: int, seed: int = 0,
                     telemetry_cap: int = 0):
    """The program `JaxSolver` dispatches on a slot-stable plan since
    PR 50: `trace_jax_slot_stable`'s with the active-set superstep
    traced beside the dense one (`active_set`: caps of 4 nodes and a
    sixteenth of the rows here, where `active_set_caps` would find the
    plan too small to bother). Its scatter-adds are admitted by a chip
    reading and counted (program_registry, "active-set")."""
    from ..solver.jax_solver import _ACTIVE_ROWS_SHARE

    return trace_jax_slot_stable(
        n_raw, m_raw, seed, telemetry_cap, active_rows_share=_ACTIVE_ROWS_SHARE
    )


def active_set_branches(closed):
    """(dense, sparse): the two branches of the `cond` by which a
    superstep of an `active_set` program chooses its form, found as the
    one `cond` nested in a branch of the phase loop's `cond`; None for
    a program that holds no such choice."""
    for eqn, _in_pallas, in_loop in walk_eqns(closed.jaxpr):
        if not in_loop or eqn.primitive.name != "cond":
            continue
        for branch in eqn.params["branches"]:
            inner = [e for e in branch.jaxpr.eqns if e.primitive.name == "cond"]
            if len(inner) == 1 and len(inner[0].params["branches"]) == 2:
                dense, sparse = (b.jaxpr for b in inner[0].params["branches"])
                return dense, sparse
    return None


def active_set_faults(closed) -> List[str]:
    """What breaks the "active-set" scatter policy in a traced program:
    a scatter outside the sparse branch, or a gather, prefix sum or
    top_k in it whose result is as long as the plan (the longest array
    the dense branch gathers)."""
    found = active_set_branches(closed)
    if found is None:
        return ["no superstep chooses between two forms"]
    dense, sparse = found


    def eqns(jaxpr):
        return [e for e, _p, _l in walk_eqns(jaxpr)]

    faults = []
    inside = sum(e.primitive.name.startswith("scatter") for e in eqns(sparse))
    total = sum(e.primitive.name.startswith("scatter") for e in eqns(closed.jaxpr))
    if inside != total:
        faults.append(f"{total - inside} scatter(s) outside the sparse branch")
    rows = max(
        e.outvars[0].aval.shape[0] for e in eqns(dense) if e.primitive.name == "gather"
    )
    for e in eqns(sparse):
        name = e.primitive.name
        if name in ("gather", "cumsum", "cummax", "cummin", "top_k") or name.startswith(
            "reduce_window"
        ):
            if e.outvars[0].aval.shape and e.outvars[0].aval.shape[0] >= rows:
                faults.append(f"{name} over {e.outvars[0].aval.shape} in the sparse branch")
    return faults


def trace_plan_apply(
    kp_raw: int, ki_raw: int, n_raw: int = 20, m_raw: int = 100,
    ks_raw: int = 0, kn_raw: int = 0,
):
    """Abstract trace of the SECOND (and last) scatter-exempt program:
    the slot-stable plan-row + boundary-static apply
    (graph/slot_plan.plan_apply_fn). Its four record streams share ONE
    pow2 bucket (the largest stream's), so the shapes it can be called
    with are the closed set `graph/device_export.record_buckets` lists;
    the seg/node static streams carry real dirt only on
    region-relocation rounds and are idempotent pads otherwise."""
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import (
        INV_RECORD_COLS,
        NODE_RECORD_COLS,
        PLAN_RECORD_COLS,
        SEG_RECORD_COLS,
        plan_apply_fn,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    e = slot_stable_entry_cap(m)
    k = pad_record_count(kp_raw, ki_raw, ks_raw, kn_raw)
    return jax.make_jaxpr(plan_apply_fn())(
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((2 * m,)),
        _sds((e,)), _sds((e,), jnp.bool_),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
        _sds((k, PLAN_RECORD_COLS)), _sds((k, INV_RECORD_COLS)),
        _sds((k, SEG_RECORD_COLS)), _sds((k, NODE_RECORD_COLS)),
    )


def trace_stacked(
    lanes_raw: int,
    n_raw: int,
    m_raw: int,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
):
    """Abstract trace of the multi-tenant stacked-CSR batched solve
    (solver/jax_solver.stacked_solve_fn): same-bucket tenant lanes
    through one program, lane axis leading. Contracts pin it
    scatter-free (vmap's while-loop batching masks converged lanes
    with selects, never scatters), 32-bit, and hash-stable across raw
    sizes within a pow2 shape bucket AND raw lane counts within a pow2
    lane bucket — tenants joining/leaving must reuse executables."""
    from ..solver.jax_solver import pad_lane_count, stacked_solve_fn

    n, m = bucketed_sizes(n_raw, m_raw)
    L = pad_lane_count(lanes_raw)
    e = 2 * m
    fn = stacked_solve_fn(
        alpha=8, max_supersteps=4096, tighten_sweeps=32,
        telemetry_cap=telemetry_cap, use_warm_p=use_warm_p,
    )
    args = [
        _sds((L, m)), _sds((L, m)), _sds((L, n)), _sds((L, m)), _sds((L,)),
    ]
    if use_warm_p:
        args.append(_sds((L, n)))
    args += [
        _sds((L, e)), _sds((L, e)), _sds((L, e)), _sds((L, e)), _sds((L, e)),
        _sds((L, e), jnp.bool_), _sds((L, e)),
        _sds((L, n)), _sds((L, n)), _sds((L, n), jnp.bool_),
    ]
    return jax.make_jaxpr(fn)(*args)


def trace_delta_apply(ka_raw: int, kn_raw: int, n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the FIRST scatter-exempt program: the
    device-resident delta apply (graph/device_export.delta_apply_fn),
    its arc and node records padded to ONE joint pow2 bucket."""
    from ..graph.device_export import (
        ARC_RECORD_COLS,
        NODE_RECORD_COLS,
        delta_apply_fn,
        pad_record_count,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    k = pad_record_count(ka_raw, kn_raw)
    return jax.make_jaxpr(delta_apply_fn())(
        _sds((n,)), _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,)),
        _sds((k, ARC_RECORD_COLS)), _sds((k, NODE_RECORD_COLS)),
    )


def trace_state_fingerprint(n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the device-state fingerprint program
    (runtime/integrity.state_fingerprint_fn): per-buffer weighted
    checksums of the five persistent problem buffers. Must stay
    scatter-free and 32-bit — the integrity audit rides the normal
    solve cadence and gets no scatter exemption."""
    from ..runtime.integrity import state_fingerprint_fn

    n, m = bucketed_sizes(n_raw, m_raw)
    return jax.make_jaxpr(state_fingerprint_fn())(
        _sds((n,)), _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,)),
    )


def trace_plan_fingerprint(n_raw: int = 20, m_raw: int = 100, e_raw: int = 256):
    """Abstract trace of the slot-plan fingerprint program
    (runtime/integrity.plan_fingerprint_fn) over the ten maintained
    plan tensors."""
    from ..runtime.integrity import plan_fingerprint_fn
    from ..utils import next_pow2

    n, m = bucketed_sizes(n_raw, m_raw)
    e = max(next_pow2(e_raw), 2 * m)
    return jax.make_jaxpr(plan_fingerprint_fn())(
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((2 * m,)),
        _sds((e,)), _sds((e,), jnp.bool_), _sds((n,)), _sds((n,)),
        _sds((n,), jnp.bool_),
    )


def trace_warm_flow(n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the device warm-flow carry
    (graph/device_export.device_warm_flow_fn) — elementwise only, so
    it must stay scatter- AND gather-free."""
    from ..graph.device_export import device_warm_flow_fn

    _n, m = bucketed_sizes(n_raw, m_raw)
    return jax.make_jaxpr(device_warm_flow_fn())(
        _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,))
    )


def trace_replicated_plan_apply(
    ki_raw: int, kn_raw: int, n_raw: int = 20, m_raw: int = 100
):
    """Abstract trace of the FOURTH (and last) scatter-exempt program:
    the replicated remainder of a sharded plan sync — inv-order and
    node-boundary records scattered into the replicated plan tensors
    (parallel/sharded_solver.replicated_plan_apply_fn). Shipped
    unaudited in PR 15; the Level-3 registry sweep is what surfaced
    it."""
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import INV_RECORD_COLS, NODE_RECORD_COLS
    from ..parallel.sharded_solver import replicated_plan_apply_fn

    n, m = bucketed_sizes(n_raw, m_raw)
    ki = pad_record_count(ki_raw)
    kn = pad_record_count(kn_raw)
    return jax.make_jaxpr(replicated_plan_apply_fn())(
        _sds((2 * m,)), _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
        _sds((ki, INV_RECORD_COLS)), _sds((kn, NODE_RECORD_COLS)),
    )


def trace_scale_cost(n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the cost pre-scaling program
    (graph/device_export._scale_cost_fn) — cost * n ahead of a device
    solve."""
    from ..graph.device_export import _scale_cost_fn

    _n, m = bucketed_sizes(n_raw, m_raw)
    return jax.make_jaxpr(_scale_cost_fn())(_sds((m,)), _sds(()))


def trace_buffer_fingerprint(n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the single-buffer checksum (the warm-flow
    audit's runtime/integrity._FP_ONE program)."""
    from ..runtime.integrity import _device_fp1

    _n, m = bucketed_sizes(n_raw, m_raw)
    return jax.make_jaxpr(_device_fp1)(_sds((m,)))


def trace_corrupt_flip(n_raw: int = 20, m_raw: int = 100):
    """Abstract trace of the chaos-only poison scatter
    (runtime/integrity.corrupt_fn): flip one bit of one element. The
    only registered program with a chaos-only scatter policy."""
    from ..runtime.integrity import corrupt_fn

    _n, m = bucketed_sizes(n_raw, m_raw)
    return jax.make_jaxpr(corrupt_fn())(_sds((m,)), _sds(()), _sds(()))


def trace_array_served_round(machines: int, rows: int, decode_width=None):
    """Abstract trace of the served array round
    (scheduler/device_bulk.py `served_round`): CoCo's cost function over
    `machines` machines of 4 PUs x 16 slots and a table of `rows` task
    rows, the decode `decode_width` rows wide (None: every row)."""
    from ..costmodels import coco
    from ..costmodels.device_costs import coco_device_cost_fn
    from ..scheduler.device_bulk import DeviceBulkCluster

    dev = DeviceBulkCluster(
        num_machines=machines, pus_per_machine=4, slots_per_pu=16, num_jobs=1,
        num_task_classes=4, task_capacity=rows, class_cost_fn=coco_device_cost_fn(),
        unsched_cost=coco.UNSCHEDULED_COST, ec_cost=0,
    )
    return jax.make_jaxpr(
        dev._served_round_jit.__wrapped__, static_argnums=(2,)
    )(dev.state, dev.groups, decode_width)


TRACERS = {
    "jax": trace_jax,
    "layered": trace_layered,
    "sharded": trace_sharded,
}


# ---------------------------------------------------------------------------
# AOT builders for the donation/aliasing audit
# ---------------------------------------------------------------------------
#
# Each returns (jitted_callable, abstract_args) for the engine's
# compiled-executable donation audit: the callable is the REAL cached
# program factory's output (donate_argnums already applied at the jit
# site), and the args are the same ShapeDtypeStructs its tracer uses —
# so `.lower(*args).compile()` exercises exactly the production
# donation configuration.


def aot_delta_apply(ka_raw: int = 5, kn_raw: int = 3, n_raw: int = 20, m_raw: int = 100):
    from ..graph.device_export import (
        ARC_RECORD_COLS,
        NODE_RECORD_COLS,
        delta_apply_fn,
        pad_record_count,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    k = pad_record_count(ka_raw, kn_raw)
    return delta_apply_fn(), (
        _sds((n,)), _sds((m,)), _sds((m,)), _sds((m,)), _sds((m,)),
        _sds((k, ARC_RECORD_COLS)), _sds((k, NODE_RECORD_COLS)),
    )


def aot_plan_apply(kp_raw: int = 5, ki_raw: int = 3, n_raw: int = 20, m_raw: int = 100):
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import (
        INV_RECORD_COLS,
        NODE_RECORD_COLS,
        PLAN_RECORD_COLS,
        SEG_RECORD_COLS,
        plan_apply_fn,
    )

    n, m = bucketed_sizes(n_raw, m_raw)
    e = slot_stable_entry_cap(m)
    k = pad_record_count(kp_raw, ki_raw)
    return plan_apply_fn(), (
        _sds((e,)), _sds((e,)), _sds((e,)), _sds((e,)), _sds((2 * m,)),
        _sds((e,)), _sds((e,), jnp.bool_),
        _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
        _sds((k, PLAN_RECORD_COLS)), _sds((k, INV_RECORD_COLS)),
        _sds((k, SEG_RECORD_COLS)), _sds((k, NODE_RECORD_COLS)),
    )


def aot_sharded_plan_apply(
    kp_raw: int = 5, ks_raw: int = 3, num_devices: int = 2,
    n_raw: int = 20, m_raw: int = 100,
):
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import PLAN_RECORD_COLS, SEG_RECORD_COLS
    from ..parallel.sharded_solver import (
        sharded_entry_extent,
        sharded_plan_apply_fn,
    )

    _n, m = bucketed_sizes(n_raw, m_raw)
    D = num_devices
    es = sharded_entry_extent(m, D)
    kp = pad_record_count(kp_raw)
    ks = pad_record_count(ks_raw)
    return sharded_plan_apply_fn(_mesh_of(D), "x"), (
        _sds((D, es)), _sds((D, es)), _sds((D, es)), _sds((D, es)),
        _sds((D, es)), _sds((D, es), jnp.bool_),
        _sds((D, kp, PLAN_RECORD_COLS)), _sds((D, ks, SEG_RECORD_COLS)),
    )


def aot_replicated_plan_apply(
    ki_raw: int = 5, kn_raw: int = 3, n_raw: int = 20, m_raw: int = 100
):
    from ..graph.device_export import pad_record_count
    from ..graph.slot_plan import INV_RECORD_COLS, NODE_RECORD_COLS
    from ..parallel.sharded_solver import replicated_plan_apply_fn

    n, m = bucketed_sizes(n_raw, m_raw)
    ki = pad_record_count(ki_raw)
    kn = pad_record_count(kn_raw)
    return replicated_plan_apply_fn(), (
        _sds((2 * m,)), _sds((n,)), _sds((n,)), _sds((n,), jnp.bool_),
        _sds((ki, INV_RECORD_COLS)), _sds((kn, NODE_RECORD_COLS)),
    )


@functools.lru_cache(maxsize=64)
def traced(backend: str, n_raw: int, m_raw: int, seed: int = 0,
           telemetry_cap: int = 0):
    """Cached abstract trace: the contract tests revisit the same
    (backend, bucket) pairs, and tracing dominates the suite's tier-1
    cost. telemetry_cap traces the
    solver-telemetry-ON program (obs/soltel.py); 0 is the baseline
    pre-telemetry program."""
    return TRACERS[backend](n_raw, m_raw, seed, telemetry_cap=telemetry_cap)


def backend_report(backend: str, n_raw: int, m_raw: int, seed: int = 0) -> ContractReport:
    closed = traced(backend, n_raw, m_raw, seed)
    return check_jaxpr(backend, closed, shape_key=(n_raw, m_raw))


def recompile_hazard(
    backend: str, raw_a: Tuple[int, int], raw_b: Tuple[int, int], seed: int = 0
) -> Tuple[str, str]:
    """Jaxpr hashes for two raw sizes; equal hashes = one executable
    serves both (no recompile inside the bucket)."""
    return (
        jaxpr_hash(traced(backend, *raw_a, seed)),
        jaxpr_hash(traced(backend, *raw_b, seed)),
    )
