"""Level-3 enforcement engine: one code path checks every registered
program's contract spec (analysis/program_registry.py).

Where Level 2 grew one hand-written test function per program, the
engine interprets `ProgramSpec` fields generically:

- **contracts**: trace the program abstractly (`jax.make_jaxpr` over
  ShapeDtypeStructs — CPU-safe, no compile) at the default call plus
  every extra shape bucket, then assert the 32-bit dtype policy, the
  scatter policy (forbidden / scoped-exempt-and-NON-VACUOUS /
  chaos-only), the gather budget, and the collective budget.
- **hash pin**: the telemetry-off normalized-jaxpr hash equals the
  pinned value byte-for-byte ("disabled telemetry costs zero traced
  ops" can never silently rot).
- **hash stability**: every `same` pair of tracer calls collides,
  every `cross` pair splits — the recompile-hazard detector.
- **telemetry knob**: knob=0 IS the default program, knob=512 is a
  DIFFERENT one that still satisfies dtype/scatter/gather budgets
  (and, for pow2-stable programs, still bucket-collides).
- **donation audit** (the genuinely new analysis): AOT-lower the real
  jitted callable (``.lower().compile()`` on CPU) and assert every
  declared donated input actually aliases an output in the compiled
  executable's ``input_output_alias`` config, with zero XLA
  "donated buffers were not usable" warnings. XLA silently copies
  when donation fails — doubling HBM for the delta/plan/sharded
  scatters — and before this audit nothing would have noticed.

All checks raise :class:`ContractError` (an AssertionError) with the
offending program named, so registry-driven parametrized tests get
readable failures and negative tests can assert the engine flags
seeded violations.

Import cost: this module lazily imports `jaxpr_contracts` (and hence
jax) on first use — the registry itself stays stdlib-only for the
lint CLI.
"""

from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .program_registry import PROGRAMS, ProgramSpec, TraceCall


class ContractError(AssertionError):
    """A registered program violates its declared contract."""


# ---------------------------------------------------------------------------
# tracing (memoized — the parametrized suite revisits default calls)
# ---------------------------------------------------------------------------

_TRACE_CACHE: Dict[Tuple, object] = {}


def _contracts():
    from . import jaxpr_contracts

    return jaxpr_contracts


def resolve_tracer(name: str):
    jc = _contracts()
    fn = getattr(jc, name, None)
    if fn is None or not callable(fn):
        raise ContractError(
            f"tracer {name!r} does not exist in analysis/jaxpr_contracts.py"
        )
    return fn


def trace_call(spec: ProgramSpec, tc: Optional[TraceCall] = None, **overrides):
    """Trace `spec` at `tc` (default: its registered default call),
    with optional kwarg overrides (the telemetry knob)."""
    tc = tc or spec.trace
    key = (spec.tracer, tc, tuple(sorted(overrides.items())))
    closed = _TRACE_CACHE.get(key)
    if closed is None:
        # Hash pins depend on the pretty-printed jaxpr, and the printer
        # hoists sub-jaxprs shared BY OBJECT IDENTITY (`let _where.. =`
        # blocks in pp_toplevel_jaxpr). Whether two call sites share one
        # traced Jaxpr object depends on jax's process-global tracing
        # caches — i.e. on whatever traced earlier in the process, which
        # makes str(jaxpr) order-dependent mid-suite. The pins were
        # derived in fresh processes (empty caches); clearing before
        # each fresh trace reproduces that state exactly, so the
        # normalized string is byte-stable no matter what ran before.
        import jax

        jax.clear_caches()
        kwargs = tc.as_kwargs()
        kwargs.update(overrides)
        closed = resolve_tracer(spec.tracer)(*tc.args, **kwargs)
        _TRACE_CACHE[key] = closed
    return closed


def report(spec: ProgramSpec, tc: Optional[TraceCall] = None, **overrides):
    jc = _contracts()
    closed = trace_call(spec, tc, **overrides)
    return jc.check_jaxpr(spec.name, closed, shape_key=(tc or spec.trace).args)


def program_hash(spec: ProgramSpec, tc: Optional[TraceCall] = None, **overrides) -> str:
    jc = _contracts()
    return jc.jaxpr_hash(trace_call(spec, tc, **overrides))


# ---------------------------------------------------------------------------
# contract checks
# ---------------------------------------------------------------------------


def _fail(spec: ProgramSpec, msg: str):
    raise ContractError(f"program {spec.name!r}: {msg}")


def _check_one(spec: ProgramSpec, tc: TraceCall, exact_collectives: bool = True,
               **overrides):
    jc = _contracts()
    rep = report(spec, tc, **overrides)
    where = f"at {tc.args}{dict(tc.kwargs) or ''}"
    if not rep.ok_64bit:
        _fail(spec, f"64-bit dtypes in traced program {where}: {rep.violations_64bit}")
    if spec.scatter_policy == "forbidden":
        if rep.scatter_eqns:
            _fail(spec, f"scatter primitives {rep.scatter_eqns} {where} but policy "
                        "is 'forbidden' (TPU serializes scatter-adds)")
    elif spec.scatter_policy == "active-set":
        if len(rep.scatter_eqns) != spec.scatters:
            _fail(spec, f"{len(rep.scatter_eqns)} scatter primitives {where}, the "
                        f"active-set superstep was admitted {spec.scatters}")
        faults = jc.active_set_faults(trace_call(spec, tc, **overrides))
        if faults:
            _fail(spec, f"active-set policy broken {where}: {'; '.join(faults)}")
    else:  # scoped-exempt / chaos-only must actually scatter
        if not rep.scatter_eqns:
            _fail(spec, f"scatter policy {spec.scatter_policy!r} is VACUOUS {where}: "
                        "the program never scatters — drop the exemption")
    g = spec.gathers
    if g is not None:
        got = (rep.hbm_loop_gathers, rep.kernel_gathers, rep.oneshot_gathers)
        for label, want, have in (
            ("hbm_loop", g.hbm_loop, got[0]),
            ("kernel", g.kernel, got[1]),
            ("oneshot", g.oneshot, got[2]),
        ):
            if want is not None and have != want:
                _fail(spec, f"{label} gathers {where}: expected {want}, traced {have}")
    if spec.collectives is not None:
        _check_collectives(
            spec, trace_call(spec, tc, **overrides), where, exact_collectives
        )


def _check_collectives(spec: ProgramSpec, closed, where: str, exact: bool = True):
    jc = _contracts()
    budget = spec.collectives
    loop = jc.count_collectives(closed, loop_only=True)
    total = jc.count_collectives(closed)
    if exact:  # exact counts pin the TELEMETRY-OFF program only — the
        # soltel counters legitimately add loop psums when enabled
        for prim, want in budget.loop:
            if loop.get(prim, 0) != want:
                _fail(spec, f"loop-body {prim} count {where}: expected {want}, "
                            f"traced {loop.get(prim, 0)} (per-superstep ICI budget)")
        for prim, want in budget.total:
            if total.get(prim, 0) != want:
                _fail(spec, f"total {prim} count {where}: expected {want}, "
                            f"traced {total.get(prim, 0)}")
    for prim in budget.forbidden:
        if total.get(prim, 0):
            _fail(spec, f"forbidden collective {prim} appears {total[prim]}x {where}")


def check_contracts(spec: ProgramSpec):
    """Dtype / scatter / gather / collective contracts at the default
    call and every extra shape bucket."""
    for tc in (spec.trace,) + spec.extra:
        _check_one(spec, tc)


def check_hash_pin(spec: ProgramSpec):
    if spec.telemetry_off_hash is None:
        return
    got = program_hash(spec)
    if got != spec.telemetry_off_hash:
        import os
        if os.environ.get("KSCHED_DEBUG_HASH_DUMP"):
            jc = _contracts()
            with open(f"/tmp/ksched_bad_jaxpr_{spec.name}.txt", "w") as f:
                f.write(jc._normalize_jaxpr_str(str(trace_call(spec))))
        _fail(spec, f"telemetry-off jaxpr hash {got} != pinned "
                    f"{spec.telemetry_off_hash} — the traced program CHANGED. "
                    "If intentional, re-derive and re-pin in program_registry.py")


def check_hash_stability(spec: ProgramSpec):
    hs = spec.hash_stability
    if hs is None or hs.kind == "exempt":
        return
    for a, b in hs.same:
        ha, hb = program_hash(spec, a), program_hash(spec, b)
        if ha != hb:
            _fail(spec, f"{hs.kind} hash split inside one bucket: "
                        f"{a.args}{dict(a.kwargs) or ''}={ha} vs "
                        f"{b.args}{dict(b.kwargs) or ''}={hb} — a raw size "
                        "leaked into the traced program (recompile hazard)")
    for a, b in hs.cross:
        ha, hb = program_hash(spec, a), program_hash(spec, b)
        if ha == hb:
            _fail(spec, f"cross-bucket calls {a.args} and {b.args} collide "
                        f"({ha}) — the stability check is vacuous")


def check_telemetry_knob(spec: ProgramSpec):
    if spec.telemetry_knob is None:
        return
    knob = spec.telemetry_knob
    # knob=0 must BE the default program. The tracers take the knob as
    # a keyword with default 0, so asserting the signature default is
    # equivalent to re-tracing with an explicit 0 — without paying a
    # second full solver trace per program.
    import inspect

    params = inspect.signature(resolve_tracer(spec.tracer)).parameters
    if knob not in params or params[knob].default != 0:
        _fail(spec, f"tracer {spec.tracer!r} does not default {knob}=0 — "
                    "the pinned hash would not be the telemetry-OFF program")
    off = default = program_hash(spec)
    on = program_hash(spec, **{knob: 512})
    if on == off:
        _fail(spec, f"{knob}=512 traces the SAME program as {knob}=0 — "
                    "the telemetry knob is dead")
    # the telemetry-ON program must hold the same structural contracts
    # (forbidden collectives included; exact counts are off-only)
    _check_one(spec, spec.trace, exact_collectives=False, **{knob: 512})
    if spec.collectives is not None and spec.collectives.knob_adds_loop_psum:
        jc = _contracts()
        loop_off = jc.count_collectives(trace_call(spec), loop_only=True)
        loop_on = jc.count_collectives(
            trace_call(spec, **{knob: 512}), loop_only=True
        )
        if loop_on.get("psum", 0) <= loop_off.get("psum", 0):
            _fail(spec, "telemetry-ON trace does not add loop psums (the "
                        "soltel counters ride the superstep reductions)")
    hs = spec.hash_stability
    if hs is not None and hs.kind != "exempt" and hs.same:
        a, b = hs.same[0]
        ha = program_hash(spec, a, **{knob: 512})
        hb = program_hash(spec, b, **{knob: 512})
        if ha != hb:
            _fail(spec, f"telemetry-ON trace splits the {hs.kind} hash "
                        f"({a.args} vs {b.args}) — the knob leaks a raw size")


def check_distinct(spec: ProgramSpec):
    if not spec.distinct_from:
        return
    mine = program_hash(spec)
    for other_name in spec.distinct_from:
        other = PROGRAMS[other_name]
        if mine == program_hash(other):
            _fail(spec, f"default trace collides with {other_name!r} — the "
                        "variant is vacuous (its distinguishing input is dead)")


def check_declared(spec: ProgramSpec):
    """The owning module's `declare_programs` hook names this spec."""
    import importlib

    from .program_registry import DECLARED

    importlib.import_module(spec.module)
    declared = DECLARED.get(spec.module, set())
    if spec.name not in declared:
        _fail(spec, f"owning module {spec.module} does not declare_programs() "
                    f"it (declared: {sorted(declared) or 'nothing'})")


# ---------------------------------------------------------------------------
# the donation/aliasing audit
# ---------------------------------------------------------------------------

#: substring XLA puts in its donation-fallback warning
_UNUSABLE = "donated buffers were not usable"

_ALIAS_BLOCK_RE = re.compile(
    # the alias config nests one brace level: { {out}: (param, {}, kind), ... }
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}"
)
_ALIAS_PARAM_RE = re.compile(r":\s*\((\d+),")


@dataclass
class DonationReport:
    aliased_params: Tuple[int, ...]
    missing: Tuple[int, ...] = ()
    unusable_warnings: Tuple[str, ...] = ()
    header: str = ""

    @property
    def ok(self) -> bool:
        return not self.missing and not self.unusable_warnings


def audit_donation(fn, args: Sequence, donate_argnums: Sequence[int]) -> DonationReport:
    """AOT-lower `fn` (already jitted WITH its donation config) and
    read the compiled executable's ``input_output_alias``: every
    argnum in `donate_argnums` must appear as an aliased parameter.
    For the registered appliers every argument is a flat array, so HLO
    parameter numbers equal positional argnums. Also captures XLA's
    donation-unusable warning — either signal alone means a silent
    full-buffer copy in production."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = fn.lower(*args).compile()
    unusable = tuple(
        str(w.message) for w in caught if _UNUSABLE in str(w.message)
    )
    header = ""
    aliased: List[int] = []
    for line in compiled.as_text().splitlines():
        if line.startswith("HloModule"):
            header = line
            m = _ALIAS_BLOCK_RE.search(line)
            if m:
                aliased = sorted(
                    int(p) for p in _ALIAS_PARAM_RE.findall(m.group(1))
                )
            break
    missing = tuple(a for a in donate_argnums if a not in aliased)
    return DonationReport(
        aliased_params=tuple(aliased),
        missing=missing,
        unusable_warnings=unusable,
        header=header,
    )


def check_donation(spec: ProgramSpec):
    if spec.donation is None:
        return
    jc = _contracts()
    builder = getattr(jc, spec.donation.builder, None)
    if builder is None:
        _fail(spec, f"donation builder {spec.donation.builder!r} missing "
                    "from analysis/jaxpr_contracts.py")
    fn, args = builder()
    rep = audit_donation(fn, args, spec.donation.donate_argnums)
    if rep.unusable_warnings:
        _fail(spec, "XLA reports unusable donated buffers (silent copy in "
                    f"production): {rep.unusable_warnings}")
    if rep.missing:
        _fail(spec, f"donated argnums {rep.missing} are NOT aliased in the "
                    f"compiled executable (aliased: {rep.aliased_params}; "
                    f"header: {rep.header!r}) — XLA fell back to a copy")


# ---------------------------------------------------------------------------
# check registry (drives the parametrized suite)
# ---------------------------------------------------------------------------

CHECKS = {
    "contracts": check_contracts,
    "hash_pin": check_hash_pin,
    "stability": check_hash_stability,
    "telemetry_knob": check_telemetry_knob,
    "distinct": check_distinct,
    "donation": check_donation,
    "declared": check_declared,
}


def applicable_checks(spec: ProgramSpec) -> Tuple[str, ...]:
    """Which CHECKS are non-trivial for this spec (the suite
    parametrizes over exactly these, so skipped work is visible as
    absent test ids, not silently-passing ones)."""
    names = ["contracts", "declared"]
    if spec.telemetry_off_hash is not None:
        names.append("hash_pin")
    hs = spec.hash_stability
    if hs is not None and hs.kind != "exempt" and (hs.same or hs.cross):
        names.append("stability")
    if spec.telemetry_knob is not None:
        names.append("telemetry_knob")
    if spec.distinct_from:
        names.append("distinct")
    if spec.donation is not None:
        names.append("donation")
    return tuple(names)


def run_all(spec: ProgramSpec):
    for name in applicable_checks(spec):
        CHECKS[name](spec)
