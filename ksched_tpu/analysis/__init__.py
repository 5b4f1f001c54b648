"""Static-analysis suite: the codebase's TPU invariants, machine-checked.

Three levels (docs/static_analysis.md has the full rule catalog):

- Level 1, `ast_rules`: AST lint over the whole tree (driven by
  `tools/kschedlint.py`, gated by `tests/test_static_analysis.py`).
  Catches the invariants that live in *source text* — 64-bit dtypes in
  device-bound modules, dtype-less jnp array creation, `jax.jit` calls
  whose scalar knobs are missing from `static_argnames`, Python
  control flow on traced values, mutable default args, bare excepts,
  raw `print` in library code.
- Level 2, `jaxpr_contracts`: abstract traces (`jax.make_jaxpr` over
  `ShapeDtypeStruct`s — no device, no compile) of every registered
  solver backend, asserting the invariants that live in the *traced
  program* — no 64-bit `convert_element_type` anywhere, no scatter
  in a solve, exact gather budgets, and jaxpr-hash stability across
  raw sizes sharing a pow2 padding bucket (the recompile-hazard
  detector).
- Level 3, `program_registry` + `engine`: a declarative registry where
  every compiled program in the tree registers once with its full
  contract spec (scatter policy, collective budget, dtype policy,
  donation spec, telemetry-off hash pin, hash-stability class), a
  generic engine enforcing every spec uniformly (including an AOT
  ``.lower().compile()`` donation/aliasing audit — XLA silently copies
  when a donated buffer is unusable), and an unaudited-program sweep
  (`unregistered-program` rule) that fails lint for any
  `jax.jit`/`pallas_call`/`shard_map` call site that is neither
  registered nor waived with a rationale.

The split mirrors what each level can see: the AST rules catch hazards
before a trace exists (and in code that never traces), the jaxpr
contracts catch what only the traced program knows (a float64 sneaking
in through promotion has no grep-able source form), and the registry
makes the per-program contracts declarative data instead of copy-pasted
assertions — so coverage is a checkable property, not a convention.
"""

from .ast_rules import (
    RULES,
    Directive,
    ProgramSite,
    Violation,
    collect_program_sites,
    iter_directives,
    lint_file,
    lint_paths,
    parse_directive,
    program_coverage,
)
from .baseline import fingerprint, load_baseline, split_by_baseline, write_baseline
from .program_registry import (
    PROGRAMS,
    SITE_NAMES,
    CollectiveBudget,
    DonationSpec,
    GatherBudget,
    HashStability,
    ProgramSpec,
    declare_programs,
    donating_programs,
    registered_names,
    specs_for_site,
)

__all__ = [
    "RULES",
    "Directive",
    "ProgramSite",
    "Violation",
    "collect_program_sites",
    "iter_directives",
    "lint_file",
    "lint_paths",
    "parse_directive",
    "program_coverage",
    "fingerprint",
    "load_baseline",
    "split_by_baseline",
    "write_baseline",
    "PROGRAMS",
    "SITE_NAMES",
    "CollectiveBudget",
    "DonationSpec",
    "GatherBudget",
    "HashStability",
    "ProgramSpec",
    "declare_programs",
    "donating_programs",
    "registered_names",
    "specs_for_site",
]
