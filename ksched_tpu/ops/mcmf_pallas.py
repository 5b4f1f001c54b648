"""Pallas TPU megakernel: the general-graph CSR MCMF solve, fused.

The scan-based CSR/ELL backends (solver/jax_solver.py, ell_solver.py)
pay ~6 full-entry HBM gathers plus 3 global scans per push-relabel
superstep — measured gather-bound at ~60 ms/solve for the 10k x 1k
general graph on TPU v5e and CPU alike, with CSR and ELL tying because
the layouts change nothing about the HBM round-trips (the arithmetic,
docs/solver_coverage.md: ~7.6 ns/element per gather pass, 6-10 ms per
superstep). The identified lever, built here, is a megakernel:
the ENTIRE superstep loop — Bellman-Ford price tightening, the
cost-scaling phase schedule, every push/relabel superstep — runs inside
one `pl.pallas_call` with the sorted-entry tables pinned in VMEM for the
whole solve, following the pattern proven by ops/transport_pallas.py
for the dense layered transport.

Two representation changes make the CSR algorithm VMEM-shaped:

- PER-ENTRY state instead of per-node/per-arc state. Each of the 2M
  doubled residual entries carries its arc's flow and its SOURCE node's
  potential. The one cross-segment access the algorithm needs — the
  destination node's potential / tightening distance — is the PARTNER
  entry's source value, because arc (u, v)'s backward entry is exactly
  (v, u): a single fixed permutation (prow/pcol index pair, VMEM-
  resident, built once per graph structure) replaces every p[s_dst],
  excess[s_src] and delta[inv_order] gather of the HBM formulation.
- Per-node segment reductions (excess, maximal-push prefix, relabel
  bound) become SEGMENTED Hillis-Steele scans with head flags —
  log-step `pltpu.roll` + iota-masked combines, the construction the
  transport kernel already uses for plain cumsum (jnp.cumsum and
  lax.associative_scan do not lower on Pallas TPU). The entry tables
  are tiled into VMEM-friendly [R, L] blocks (row-major flattening of
  the sorted order); an intra-block scan plus a cross-block carry
  propagation over the R block rows yields the global segmented scan.

Semantics are the same synchronous Goldberg-Tarjan cost-scaling
push-relabel as solver/jax_solver.py `_solve_mcmf` — identical entry
order, identical maximal-push prefix allocation, identical jump
relabels and tightening sweeps — so the kernel's flows are
BIT-IDENTICAL to the CSR solver's, superstep for superstep (tests
assert exact flow equality, not just objective parity). Integer
arithmetic only.

Capacity: everything must fit VMEM (~16 MB/core). The live set is
~_MEGA_LIVE_TILES int32 entry tables, so graphs beyond
`mega_fits_vmem` route to the scan-based CSR fallback via the
dispatch seams (solver/select.py --backend mega, AutoSolver
escalation). The 10k x 1k headline graph is 131072 entries — ~9 MB
of live tables — comfortably resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Python ints (not jnp scalars): jnp constants captured by the kernel
# closure trip pallas_call's "captures constants" check.
_BIG = 1 << 30
_BIG_D = 1 << 28
_P_GUARD = 1 << 30

#: live int32 [R, L] tiles across a superstep (9 input tables + flow/
#: potential state + scan temporaries), used by the VMEM dispatch gate
_MEGA_LIVE_TILES = 18
_MEGA_VMEM_BUDGET_BYTES = 15 << 20

#: lane width of the entry tiling ([R, L] row-major); 512 keeps the
#: intra-row scan at 9 roll steps and the row counts small
MEGA_LANES = 512


def mega_entry_rows(num_entries: int, lanes: int = MEGA_LANES) -> int:
    """Block rows R for a 2M-entry table tiled [R, lanes]."""
    return max(1, -(-num_entries // lanes))


def mega_fits_vmem(
    num_entries: int,
    lanes: int = MEGA_LANES,
    budget_bytes: int = _MEGA_VMEM_BUDGET_BYTES,
    telemetry: bool = False,
) -> bool:
    """Whether the whole-solve live set stays VMEM-resident. With
    solver telemetry on, the budget charges one extra tile: the
    telemetry ring is clamped to at most one [R, L] tile of int32
    (`mega_telemetry_cap`), so +1 tile is exact, not an estimate."""
    padded = mega_entry_rows(num_entries, lanes) * lanes
    tiles = _MEGA_LIVE_TILES + (1 if telemetry else 0)
    return tiles * padded * 4 <= budget_bytes


def mega_telemetry_cap(R: int, L: int, cap: int) -> int:
    """Clamp a telemetry ring capacity so the [cap, SOLTEL_WIDTH]
    buffer never exceeds one [R, L] entry tile of VMEM — the +1-tile
    budget `mega_fits_vmem(telemetry=True)` charges. Small graphs get
    a shorter ring (their solves are short too); the ring keeps the
    FINAL supersteps either way."""
    from ..obs.soltel import SOLTEL_WIDTH

    return max(1, min(int(cap), (R * L) // SOLTEL_WIDTH))


def _mcmf_kernel(
    sign_ref, cap_ref, sc_ref, sup_ref, hs_ref, he_ref,
    prow_ref, pcol_ref, f0_ref, eps_ref,
    fout_ref, steps_ref, conv_ref, povf_ref,
    *tel_refs,
    R: int, L: int, alpha: int, max_supersteps: int,
    tighten_sweeps: int, telemetry_cap: int = 0,
):
    i32 = jnp.int32
    sign = sign_ref[:]       # [R, L] +1 fwd / -1 bwd / 0 pad
    cap = cap_ref[:]         # [R, L] arc capacity per entry
    sc = sc_ref[:]           # [R, L] signed scaled cost per entry
    sup = sup_ref[:]         # [R, L] source-node supply per entry
    hs = hs_ref[:]           # [R, L] segment-start flags (0/1 int32)
    he = he_ref[:]           # [R, L] segment-end flags (0/1 int32)
    prow = prow_ref[:]       # [R, L] partner block row
    pcol = pcol_ref[:]       # [R, L] partner lane
    eps0 = eps_ref[0]

    col = lax.broadcasted_iota(i32, (R, L), 1)
    row = lax.broadcasted_iota(i32, (R, 1), 0)

    def perm(x):
        """The partner permutation: entry (u, v) <-> entry (v, u) of
        the same arc. The ONLY non-elementwise data movement in the
        solve, and it reads VMEM."""
        return x[prow, pcol]

    def seg_scan(v, combine, rev: bool = False):
        """Inclusive segmented scan of v over the row-major [R, L]
        flattening (forward from segment starts, or reverse from
        segment ends): flag-carrying Hillis-Steele — at each log step
        an element absorbs its 2^t-neighbor unless its covered
        interval already reaches its segment head. Flags ride as 0/1
        int32 vectors (only int32 goes through pltpu.roll, matching
        the transport kernel's proven lowerings)."""
        f = he if rev else hs
        k = 1
        while k < L:
            if rev:
                pv = pltpu.roll(v, shift=L - k, axis=1)
                pf = pltpu.roll(f, shift=L - k, axis=1)
                ok = col < (L - k)
            else:
                pv = pltpu.roll(v, shift=k, axis=1)
                pf = pltpu.roll(f, shift=k, axis=1)
                ok = col >= k
            v = jnp.where(ok & (f == 0), combine(pv, v), v)
            f = jnp.maximum(f, jnp.where(ok, pf, i32(0)))
            k <<= 1
        if R > 1:
            # cross-block carry: pair-scan the per-row summaries, then
            # fold the exclusive carry into rows whose prefix never hit
            # a segment head — the "fori over blocks" of the global scan
            if rev:
                sv, sf = v[:, 0:1], f[:, 0:1]
            else:
                sv, sf = v[:, L - 1:L], f[:, L - 1:L]
            k = 1
            while k < R:
                if rev:
                    pv = pltpu.roll(sv, shift=R - k, axis=0)
                    pf = pltpu.roll(sf, shift=R - k, axis=0)
                    ok = row < (R - k)
                else:
                    pv = pltpu.roll(sv, shift=k, axis=0)
                    pf = pltpu.roll(sf, shift=k, axis=0)
                    ok = row >= k
                sv = jnp.where(ok & (sf == 0), combine(pv, sv), sv)
                sf = jnp.maximum(sf, jnp.where(ok, pf, i32(0)))
                k <<= 1
            if rev:
                cv = pltpu.roll(sv, shift=R - 1, axis=0)
                has = row < (R - 1)
            else:
                cv = pltpu.roll(sv, shift=1, axis=0)
                has = row >= 1
            v = jnp.where((f == 0) & has, combine(cv, v), v)
        return v

    def add(a, b):
        return a + b

    def seg_sum(v):
        """Segment total, broadcast to every entry of the segment."""
        return seg_scan(v, add) + seg_scan(v, add, rev=True) - v

    def seg_max(v):
        return jnp.maximum(
            seg_scan(v, jnp.maximum), seg_scan(v, jnp.maximum, rev=True)
        )

    def seg_min(v):
        return jnp.minimum(
            seg_scan(v, jnp.minimum), seg_scan(v, jnp.minimum, rev=True)
        )

    def seg_excl(v):
        """In-segment exclusive prefix sum (the maximal-push order)."""
        return seg_scan(v, add) - v

    def resid(f):
        return jnp.where(sign > 0, cap - f, jnp.where(sign < 0, f, i32(0)))

    def excess_of(f):
        return sup - seg_sum(sign * f)

    def saturate(f, p):
        # per-arc refine expressed per entry: rc_fwd(arc) = sign * rc
        rcf = sign * (sc + p - perm(p))
        return jnp.where(rcf < 0, cap, jnp.where(rcf > 0, i32(0), f))

    def tighten(f):
        """Price tightening: synchronous Bellman-Ford over residual
        reduced costs, exactly solver/jax_solver.py tighten — d lives
        broadcast per segment; d[s_dst] is the partner's value."""
        exc0 = excess_of(f)
        r = resid(f)
        d0 = jnp.where(exc0 < 0, i32(0), i32(_BIG_D))

        def t_cond(state):
            _d, changed, it = state
            return changed & (it < tighten_sweeps)

        def t_body(state):
            d, _, it = state
            cand = jnp.where(r > 0, sc + perm(d), i32(_BIG_D))
            best = seg_min(cand)
            d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
            return d2, jnp.any(d2 != d), it + 1

        d, _, _ = lax.while_loop(
            t_cond, t_body, (d0, jnp.bool_(True), i32(0))
        )
        return -jnp.minimum(d, i32(_BIG_D))

    def superstep(f, p, eps, exc):
        r = resid(f)
        rc = sc + p - perm(p)
        adm = (r > 0) & (rc < 0) & (exc > 0)
        r_adm = jnp.where(adm, r, i32(0))
        # maximal push: allocate each node's excess across admissible
        # entries front-to-back (same sorted order as the CSR solver)
        delta = jnp.clip(exc - seg_excl(r_adm), 0, r_adm)
        new_f = f + sign * (delta - perm(delta))

        pushed = seg_sum(delta)
        sum_r = seg_sum(r)
        cand = jnp.where(r > 0, perm(p) - sc, -i32(_BIG))
        best = seg_max(cand)
        relabel = (exc > 0) & (pushed == 0) & (sum_r > 0)
        new_p = jnp.where(relabel, best - eps, p)
        if not telemetry_cap:
            return new_f, new_p, ()
        # soltel counters (cols 3..6) from state this superstep already
        # holds in VMEM — pure reductions and masks, no new gathers, so
        # the kernel's MEGA_KERNEL_PERM_GATHERS budget is unchanged.
        # Per-node quantities (relabels) are counted at segment heads;
        # delta counts each pushed unit once (per-entry amounts).
        aux = (
            jnp.sum(delta),
            jnp.sum(jnp.where((hs == 1) & relabel, i32(1), i32(0))),
            jnp.sum(jnp.where((sign > 0) & (r == 0), i32(1), i32(0))),
            jnp.sum(adm.astype(i32)),
        )
        return new_f, new_p, aux

    if telemetry_cap:
        tel_rows_iota = lax.broadcasted_iota(i32, (telemetry_cap, 1), 0)
        tel_cols_iota = lax.broadcasted_iota(i32, (1, 8), 1)

    def tel_update(tel, steps, eps, exc, aux):
        """Write one soltel row at steps % cap — a masked elementwise
        select over the [cap, 8] ring (dynamic-index stores don't
        lower on Pallas TPU; this does, and the ring is small)."""
        pushed_n, relabels, saturated, work = aux
        active = jnp.sum(jnp.where((hs == 1) & (exc > 0), i32(1), i32(0)))
        exc_pos = jnp.sum(jnp.where(hs == 1, jnp.maximum(exc, 0), i32(0)))
        vals = (eps, active, exc_pos, pushed_n, relabels, saturated, work)
        row = i32(0)
        for j, v in enumerate(vals):
            row = jnp.where(tel_cols_iota == j, v, row)
        idx = jnp.remainder(steps, i32(telemetry_cap))
        return jnp.where(tel_rows_iota == idx, row, tel)

    def phase_cond(state):
        steps, done = state[3], state[4]
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        if telemetry_cap:
            f, p, eps, steps, done, tel = state
        else:
            f, p, eps, steps, done = state
        exc = excess_of(f)
        any_active = jnp.any(exc > 0)

        def do_step(_):
            f2, p2, aux = superstep(f, p, eps, exc)
            if not telemetry_cap:
                return f2, p2, eps, steps + 1, jnp.bool_(False)
            tel2 = tel_update(tel, steps, eps, exc, aux)
            return f2, p2, eps, steps + 1, jnp.bool_(False), tel2

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            f2 = jnp.where(finished, f, saturate(f, p))
            out = (f2, p, jnp.where(finished, eps, new_eps), steps, finished)
            return out + ((tel,) if telemetry_cap else ())

        return lax.cond(any_active, do_step, next_phase, operand=None)

    f0 = f0_ref[:]
    p0 = tighten(f0)
    f1 = saturate(f0, p0)  # mop up any residual violations
    state = (f1, p0, eps0, i32(0), jnp.bool_(False))
    if telemetry_cap:
        state = state + (jnp.zeros((telemetry_cap, 8), i32),)
        f, p, eps, steps, done, tel = lax.while_loop(
            phase_cond, phase_body, state
        )
        tel_refs[0][:] = tel
    else:
        f, p, eps, steps, done = lax.while_loop(phase_cond, phase_body, state)
    exc = excess_of(f)
    fout_ref[:] = f
    steps_ref[0] = steps
    conv_ref[0] = (done & (jnp.max(jnp.abs(exc)) == 0)).astype(i32)
    povf_ref[0] = (jnp.max(jnp.abs(p)) >= i32(_P_GUARD)).astype(i32)


@functools.partial(
    jax.jit,  # kschedlint: program=mega_solve
    static_argnames=(
        "R", "L", "alpha", "max_supersteps", "tighten_sweeps", "interpret",
        "telemetry_cap",
    ),
)
def mcmf_loop_pallas(
    cap, cost, supply, flow0, eps_init,
    e_arc, e_sign, e_src, e_hs, e_he, e_prow, e_pcol, fwd_pos,
    R: int, L: int,
    alpha: int = 8,
    max_supersteps: int = 50_000,
    tighten_sweeps: int = 32,
    interpret: bool = False,
    telemetry_cap: int = 0,
):
    """One fused kernel per general-graph MCMF solve.

    cap/cost/flow0: int32[M] per arc (cost pre-scaled by the node
    count); supply: int32[N]; eps_init: int32 scalar. e_*: the padded
    [R*L] entry tables of a MegaPlan (solver/mega_solver.py), built
    from the cached `build_csr_plan` ordering; fwd_pos: int32[M] flat
    position of each arc's forward entry. Returns
    (flow[M], steps, converged, p_overflow) matching `_solve_mcmf`'s
    public result bit-for-bit (+ the [telemetry_cap, 8] soltel ring
    when telemetry_cap > 0 — written from inside the pallas_call to a
    dedicated VMEM output, clamped by `mega_telemetry_cap` to one
    entry tile so the VMEM budget grows by exactly +1 tile). The
    per-solve entry materialization (cap/cost/supply/flow gathered to
    entry order) runs as plain XLA ONCE per solve — the kernel itself
    never touches HBM between supersteps."""
    i32 = jnp.int32
    if telemetry_cap:
        telemetry_cap = mega_telemetry_cap(R, L, telemetry_cap)
    live = e_sign != 0
    arc = jnp.clip(e_arc, 0, cap.shape[0] - 1)
    src = jnp.clip(e_src, 0, supply.shape[0] - 1)
    sign2 = e_sign.astype(i32).reshape(R, L)
    cap2 = jnp.where(live, cap[arc], 0).astype(i32).reshape(R, L)
    sc2 = jnp.where(live, e_sign * cost[arc], 0).astype(i32).reshape(R, L)
    sup2 = jnp.where(live, supply[src], 0).astype(i32).reshape(R, L)
    f02 = jnp.where(live, flow0[arc], 0).astype(i32).reshape(R, L)

    out_shape = [
        jax.ShapeDtypeStruct((R, L), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec(memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    if telemetry_cap:
        out_shape.append(jax.ShapeDtypeStruct((telemetry_cap, 8), jnp.int32))
        out_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
    outs = pl.pallas_call(  # kschedlint: program=mega_solve
        functools.partial(
            _mcmf_kernel,
            R=R, L=L, alpha=alpha, max_supersteps=max_supersteps,
            tighten_sweeps=tighten_sweeps, telemetry_cap=telemetry_cap,
        ),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_specs,
        interpret=interpret,
    )(
        sign2,
        cap2,
        sc2,
        sup2,
        e_hs.astype(i32).reshape(R, L),
        e_he.astype(i32).reshape(R, L),
        e_prow.astype(i32).reshape(R, L),
        e_pcol.astype(i32).reshape(R, L),
        f02,
        eps_init.astype(i32).reshape(1),
    )
    f_out, steps, conv, povf = outs[:4]
    flow = f_out.reshape(-1)[fwd_pos]
    base = (flow, steps[0], conv[0] != 0, povf[0] != 0)
    if telemetry_cap:
        return base + (outs[4],)
    return base


@functools.lru_cache(maxsize=None)
def mega_compiler_refusal() -> str:
    """'' when the Pallas TPU compiler takes the kernel, else its words.

    Lowers one minimal instance ([1, MEGA_LANES] entry tiling) for the
    TPU platform — Pallas lowers kernel bodies to Mosaic MLIR at this
    stage, on any host — and, when a TPU backs JAX, compiles it too, so
    Mosaic's own refusals are seen. The dispatch seams consult this
    before attaching the compiled kernel (solver/select.py): a kernel
    the compiler refuses is reported and detached, never handed to the
    interpreter or to scan-CSR behind the caller's back."""
    import traceback

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    m, n, e = 256, 256, MEGA_LANES
    try:
        lowered = mcmf_loop_pallas.trace(
            sds(m), sds(m), sds(n), sds(m), sds(),
            *(sds(e) for _ in range(7)), sds(m),
            R=1, L=MEGA_LANES,
        ).lower(lowering_platforms=("tpu",))
        if jax.default_backend() == "tpu":
            lowered.compile()
    except Exception as err:  # the refusal IS the result: callers raise or print it
        where = traceback.extract_tb(err.__traceback__)[-1]
        text = str(err).strip().split("\n\nThe MLIR operation involved")[0]
        return (
            f"{type(err).__name__}: {text or where.line} "
            f"({where.filename.split('site-packages/')[-1]}:{where.lineno} "
            f"in {where.name})"
        )
    return ""


# Level-3 registry ownership (ksched_tpu/analysis/program_registry.py)
from ..analysis.program_registry import declare_programs as _declare_programs

_declare_programs(__name__, "mega_solve")
