"""Tier-1 gate for the static-analysis suite (ksched_tpu/analysis/).

Level 1: the AST lint must be clean over the whole tree (zero
unsuppressed, unbaselined violations), and every rule must actually
fire on a seeded bad snippet — a lint that silently stopped matching
is worse than no lint.

Level 2: generic trace-level machinery (jaxpr_contracts) plus negative
tests proving each analysis detects a seeded violation.

Level 3 (ISSUE 18): the declarative program registry drives the whole
per-program sweep — one parametrized test runs every applicable check
(dtype/scatter/gather/collective contracts, telemetry-off hash pin,
pow2-bucket hash stability, telemetry-knob semantics, variant
distinctness, the compiled donation/aliasing audit, module
ownership) for every registered program. The hand-written
per-program test functions this replaces live on as registry data.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

from ksched_tpu.analysis import (
    RULES,
    lint_paths,
    load_baseline,
    program_coverage,
    split_by_baseline,
)
from ksched_tpu.analysis.ast_rules import collect_program_sites, build_context, lint_source
from ksched_tpu.analysis import jaxpr_contracts as jc
from ksched_tpu.analysis import engine
from ksched_tpu.analysis.program_registry import (
    PROGRAMS,
    SITE_NAMES,
    CollectiveBudget,
    DonationSpec,
    HashStability,
    call,
    donating_programs,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_TARGETS = ["ksched_tpu", "tools"]
BASELINE = os.path.join(REPO_ROOT, "tools", "kschedlint_baseline.json")


# ---------------------------------------------------------------------------
# Level 1: the repo is lint-clean
# ---------------------------------------------------------------------------


def test_repo_is_lint_clean():
    violations = lint_paths(LINT_TARGETS, repo_root=REPO_ROOT)
    baseline = load_baseline(BASELINE)
    new, _old, stale = split_by_baseline(violations, baseline)
    assert not new, "new kschedlint violations:\n" + "\n".join(
        v.render() for v in new
    )
    assert not stale, f"stale baseline entries (fixed debt): {dict(stale)}"


def test_baseline_is_empty():
    """The ratchet starts clean: every seed violation was fixed or
    suppressed inline with a rationale (ISSUE 3 acceptance)."""
    with open(BASELINE) as fh:
        data = json.load(fh)
    assert data["violations"] == []


def test_cli_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kschedlint", "ksched_tpu", "tools"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# Level 1+3: every rule fires on a seeded bad snippet
# ---------------------------------------------------------------------------

BAD_SNIPPETS = {
    "dtype64": """
        import numpy as np
        import jax.numpy as jnp

        def prep(n):
            a = np.zeros(n, dtype=np.int64)
            b = a.astype("float64")
            return jnp.asarray(a), b
    """,
    "implicit-dtype": """
        import jax.numpy as jnp

        def build(n):
            return jnp.zeros(n), jnp.arange(n), jnp.full((n,), 3)
    """,
    "jit-static": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("alpha",))
        def solve(x, alpha: int = 8, max_steps: int = 100):
            return x * alpha
    """,
    "traced-branch": """
        import jax

        @jax.jit
        def f(x, flag):
            if flag > 0:
                return x + 1
            while x:
                x = x - 1
            return x
    """,
    "mutable-default": """
        def accumulate(item, acc=[]):
            acc.append(item)
            return acc
    """,
    "bare-except": """
        def risky():
            try:
                return 1
            except:
                return 0
    """,
    "raw-print": """
        def report(msg):
            print(msg)
    """,
    "unregistered-program": """
        import jax

        fn = jax.jit(lambda x: x + 1)
    """,
    "stale-waiver": """
        import jax

        x = 1  # kschedlint: disable=raw-print -- nothing here prints
    """,
    "bad-waiver": """
        import jax

        y = 2  # kschedlint: disable=raw-pirnt -- typo'd rule name
    """,
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_rule_fires_on_bad_snippet(rule):
    source = textwrap.dedent(BAD_SNIPPETS[rule])
    # lint under a library path so library-scoped rules apply
    violations = lint_source(f"ksched_tpu/_snippet_{rule.replace('-', '_')}.py", source)
    assert any(v.rule == rule for v in violations), (
        f"rule {rule} did not fire; got {[v.rule for v in violations]}"
    )


def test_suppression_comment_silences_rule():
    source = (
        "import numpy as np\nimport jax\n"
        "x = np.zeros(4, dtype=np.int64)  # kschedlint: host-only (test)\n"
        "print('hi')  # kschedlint: disable=raw-print -- test\n"
    )
    assert lint_source("ksched_tpu/_snippet_suppress.py", source) == []


def test_suppression_does_not_leak_to_other_rules():
    source = (
        "import numpy as np\nimport jax\n"
        "x = np.zeros(4, dtype=np.int64)  "
        "# kschedlint: disable=raw-print -- wrong rule on purpose\n"
    )
    rules = [v.rule for v in lint_source("ksched_tpu/_s.py", source)]
    # the dtype64 violation survives; the raw-print waiver is dead on
    # this line, so the staleness audit also fires
    assert "dtype64" in rules and "stale-waiver" in rules


def test_baseline_is_a_multiset():
    """One baselined entry waives ONE occurrence: copy-pasting an
    accepted bad line elsewhere in the file still fails the gate."""
    from collections import Counter

    from ksched_tpu.analysis.baseline import fingerprint as fp

    source = (
        "import numpy as np\nimport jax\n"
        "a = np.zeros(4, dtype=np.int64)\n"
        "b = np.zeros(4, dtype=np.int64)\n"
    )
    violations = lint_source("ksched_tpu/_dup.py", source)
    assert len(violations) == 2
    e = fp(violations[0])
    baseline = Counter([(e["path"], e["rule"], e["hash"])])
    new, old, stale = split_by_baseline(violations, baseline)
    assert len(old) == 1 and len(new) == 1 and not stale


def test_unparsable_file_reports_syntax_error_violation():
    """A half-written .py must fail the gate with a clean diagnostic,
    not an ast.parse traceback."""
    violations = lint_source("ksched_tpu/_broken.py", "def f(:\n")
    assert [v.rule for v in violations] == ["syntax-error"]
    assert "does not parse" in violations[0].message


def test_is_none_branch_is_not_flagged():
    source = textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, pm0=None):
            if pm0 is None:
                pm0 = jnp.zeros_like(x)
            return x + pm0
    """)
    assert not any(
        v.rule == "traced-branch"
        for v in lint_source("ksched_tpu/_s.py", source)
    )


# ---------------------------------------------------------------------------
# Level 3: the unaudited-program sweep
# ---------------------------------------------------------------------------


def _sweep(source):
    return [
        v for v in lint_source("ksched_tpu/_sweep.py", textwrap.dedent(source))
        if v.rule == "unregistered-program"
    ]


def test_sweep_finds_every_compile_entry_point():
    hits = _sweep("""
        import functools
        import jax
        from jax.experimental import pallas as pl
        from jax import shard_map

        f1 = jax.jit(lambda x: x)

        @jax.jit
        def f2(x):
            return x

        @functools.partial(jax.jit, static_argnames=("k",))
        def f3(x, k: int = 2):
            return x * k

        def f4(x):
            return pl.pallas_call(lambda ref, o: None, out_shape=x)(x)

        def f5(fn, mesh, spec):
            return shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)
    """)
    assert len(hits) == 5, [(v.line, v.message) for v in hits]


def test_sweep_accepts_registered_annotation_and_waiver():
    assert not _sweep("""
        import jax

        f1 = jax.jit(lambda x: x)  # kschedlint: program=csr_solve
        f2 = jax.jit(lambda x: x)  # kschedlint: disable=unregistered-program -- test scaffolding
    """)


def test_sweep_rejects_unknown_program_name():
    hits = _sweep("""
        import jax

        f1 = jax.jit(lambda x: x)  # kschedlint: program=no_such_program
    """)
    assert len(hits) == 1 and "names no registered program" in hits[0].message


def test_sweep_annotation_found_across_multiline_span():
    """A decorator like @functools.partial(jax.jit, donate_argnums=...)
    spans lines; the annotation rides whichever line is natural."""
    assert not _sweep("""
        import functools
        import jax

        @functools.partial(
            jax.jit,  # kschedlint: program=delta_apply
            donate_argnums=(0,),
        )
        def apply(buf):
            return buf + 1
    """)


def test_sweep_ignores_non_library_and_method_names():
    # outside ksched_tpu/: no sweep
    src = "import jax\nfn = jax.jit(lambda x: x)\n"
    assert not [
        v for v in lint_source("tools/_t.py", src)
        if v.rule == "unregistered-program"
    ]
    # a method merely NAMED like the wrapped callable is not a site
    assert not _sweep("""
        class Cell:
            def _round_jit(self, x):
                return x

            def step(self, x):
                return self._round_jit(x)
    """)


def test_unregistered_program_waiver_requires_rationale():
    hits = [
        v for v in lint_source("ksched_tpu/_w.py", textwrap.dedent("""
            import jax

            f1 = jax.jit(lambda x: x)  # kschedlint: disable=unregistered-program
        """))
        if v.rule == "bad-waiver"
    ]
    assert len(hits) == 1 and "rationale" in hits[0].message


def test_stale_program_annotation_is_flagged():
    hits = [
        v for v in lint_source("ksched_tpu/_w.py", textwrap.dedent("""
            import jax

            x = 1  # kschedlint: program=csr_solve
        """))
        if v.rule == "stale-waiver"
    ]
    assert len(hits) == 1 and "no jit/pallas_call/shard_map" in hits[0].message


def test_stale_host_only_waiver_is_flagged():
    hits = [
        v for v in lint_source("ksched_tpu/_w.py", textwrap.dedent("""
            import numpy as np
            import jax

            x = np.zeros(4, dtype=np.int32)  # kschedlint: host-only (nothing 64-bit here)
        """))
        if v.rule == "stale-waiver"
    ]
    assert len(hits) == 1 and "host-only" in hits[0].message


def test_live_waivers_are_not_stale():
    source = (
        "import numpy as np\nimport jax\n"
        "x = np.zeros(4, dtype=np.int64)  # kschedlint: host-only (test)\n"
    )
    assert not any(
        v.rule == "stale-waiver" for v in lint_source("ksched_tpu/_w.py", source)
    )


def test_bad_waiver_catches_unknown_directive_and_empty_disable():
    src = textwrap.dedent("""
        import jax

        a = 1  # kschedlint: supress=raw-print
        b = 2  # kschedlint: disable= -- nothing named
    """)
    rules = [v.rule for v in lint_source("ksched_tpu/_w.py", src)]
    assert rules.count("bad-waiver") == 2


def test_repo_coverage_is_total():
    """The ISSUE 18 acceptance: 100% call-site coverage — every
    jit/pallas_call/shard_map site in the library is annotated with a
    registered program or waived with a rationale, and every registered
    site name is annotated somewhere."""
    cov = program_coverage(LINT_TARGETS, repo_root=REPO_ROOT)
    assert cov["unaudited"] == [], cov["unaudited"]
    assert cov["unannotated_registered"] == []
    assert cov["sites"] == len(cov["annotated"]) + len(cov["waived"])
    assert len(cov["annotated"]) >= len(SITE_NAMES)


def test_collect_program_sites_classifies_kinds():
    ctx = build_context("ksched_tpu/_k.py", textwrap.dedent("""
        import jax
        from jax.experimental import pallas as pl

        a = jax.jit(lambda x: x)  # kschedlint: program=csr_solve
        b = pl.pallas_call(lambda r, o: None)  # kschedlint: program=layered_solve
    """))
    kinds = {s.kind for s in collect_program_sites(ctx)}
    assert kinds == {"jit", "pallas_call"}


# ---------------------------------------------------------------------------
# Level 3: registry sanity
# ---------------------------------------------------------------------------


def test_registry_matches_select():
    """The registry must cover what select.py can hand out: every
    in-process array backend rung has a registered solve program."""
    with open(os.path.join(REPO_ROOT, "ksched_tpu", "solver", "select.py")) as fh:
        select_src = fh.read()
    for rung, program in (
        ("jax", "csr_solve"), ("layered", "layered_solve"),
    ):
        assert f'name == "{rung}"' in select_src
        assert program in PROGRAMS
    assert "sharded_solve" in PROGRAMS  # parallel/ rung


def test_registry_policies_are_coherent():
    """Solve and audit programs never scatter, but for the one that
    holds scan-CSR's active-set superstep, whose four scatter-adds a
    chip reading admitted (PR 50) and the engine counts and confines to
    the sparse branch, and for the served array round (PR 53), which
    keeps the cluster's TABLE on the device and counts its census and
    its supply by scatter-adds over the table's rows, once a round and
    outside the transport's loop; every other scoped exemption is a
    maintenance program; chaos programs are never donation-audited (they
    are never dispatched in production)."""
    for spec in PROGRAMS.values():
        if spec.name == "csr_solve_active":
            assert (spec.kind, spec.scatter_policy, spec.scatters) == ("solve", "active-set", 4)
        elif spec.name == "array_served_round":
            assert (spec.kind, spec.scatter_policy) == ("solve", "scoped-exempt")
        elif spec.kind in ("solve", "audit"):
            assert spec.scatter_policy == "forbidden", spec.name
        elif spec.scatter_policy == "scoped-exempt":
            assert spec.kind == "maintenance", spec.name
        if spec.kind == "chaos":
            assert spec.donation is None, spec.name
    assert len(donating_programs()) == 4
    assert {s.name for s in donating_programs()} == {
        "delta_apply", "plan_apply", "sharded_plan_apply", "replicated_plan_apply",
    }


def test_registry_pins_are_the_pretelemetry_baselines():
    """The three telemetry-off hash pins (telemetry-off traces, derived
    on jax 0.9.0) live in the registry; this literal copy guards
    against an accidental registry edit re-pinning them.
    A jax upgrade that changes jaxpr printing re-pins BOTH in the same
    commit (verify the off-trace is otherwise unchanged first), and so
    does a PR that changes a traced program on purpose (PR 29:
    csr_solve, whose telemetry-off trace still holds no telemetry op,
    the engine's `telemetry` check)."""
    assert {
        n: s.telemetry_off_hash
        for n, s in PROGRAMS.items() if s.telemetry_off_hash
    } == {
        "csr_solve": "81f926532b54d878",  # PR 54: the prologue leaves the tree admissible (PR 29: state carried in entry space)
        # sharded traces over the conftest 8-virtual-device mesh; its
        # hash is mesh-size-dependent (the others' are not)
        "sharded_solve": "5fe7fb5d9c0a5982",
        "layered_solve": "d9971a009af01491",
    }


# ---------------------------------------------------------------------------
# Level 3: the engine enforces every registered program's contract.
# One test id per (program, applicable check) — skipped work would be
# visible as absent ids, not silently-passing ones.
# ---------------------------------------------------------------------------

REGISTRY_CASES = [
    (name, check)
    for name in sorted(PROGRAMS)
    for check in engine.applicable_checks(PROGRAMS[name])
]


@pytest.mark.parametrize(
    "program,check", REGISTRY_CASES, ids=[f"{p}-{c}" for p, c in REGISTRY_CASES]
)
def test_program_contract(program, check):
    engine.CHECKS[check](PROGRAMS[program])


def test_every_program_gets_contract_and_ownership_checks():
    for spec in PROGRAMS.values():
        checks = engine.applicable_checks(spec)
        assert "contracts" in checks and "declared" in checks, spec.name


# ---------------------------------------------------------------------------
# Level 2/3 bespoke: checks the generic engine cannot express
# ---------------------------------------------------------------------------


def test_csr_backend_shows_the_contrast():
    """The scan-CSR backend pays per-superstep HBM gathers (they are
    most of a superstep on the v5e) — if this ever reads 0 the
    gather classifier is broken, not the solver fixed. (The registry
    pins csr_solve's exact count, 12 since PR 29; asserted directly
    here so a GatherBudget refactor can't drop it.)"""
    report = engine.report(PROGRAMS["csr_solve"])
    assert report.hbm_loop_gathers > 0
    assert PROGRAMS["csr_solve"].gathers.hbm_loop == report.hbm_loop_gathers


# ---------------------------------------------------------------------------
# Level 2: negative tests — the generic analyses detect seeded violations
# ---------------------------------------------------------------------------


def _make_jaxpr(fn, *shapes):
    import jax
    import jax.numpy as jnp

    return jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes)
    )


def test_contract_catches_64bit_convert():
    import jax
    import jax.numpy as jnp

    def bad(x):
        return x.astype(jnp.float64).sum()

    # without x64, jax downcasts the seeded violation to f32 before the
    # checker could see it — exactly why the contract exists: if anyone
    # flips x64 on, 64-bit types flow silently
    with jax.enable_x64(True):
        closed = _make_jaxpr(bad, (8,))
    report = jc.check_jaxpr("bad", closed)
    assert not report.ok_64bit


def test_contract_catches_scatter():
    def bad(x, idx):
        return x.at[idx].add(1)

    report = jc.check_jaxpr("bad", _make_jaxpr(bad, (8,), (3,)))
    assert not report.ok_scatter


def test_contract_catches_loop_gather():
    import jax.numpy as jnp
    from jax import lax

    def bad(x, idx):
        def body(_, carry):
            return carry + x[idx].sum()

        return lax.fori_loop(0, 4, body, jnp.int32(0))

    report = jc.check_jaxpr("bad", _make_jaxpr(bad, (8,), (3,)))
    assert report.hbm_loop_gathers > 0


def test_contract_catches_bucket_leak():
    """A raw size leaking into a static arg splits the jaxpr hash —
    the exact failure mode of a forgotten pow2 pad."""
    import functools

    def leaky(x, scale: int = 1):
        return x * scale

    def trace(m_raw):
        fn = functools.partial(leaky, scale=m_raw)  # raw size as static
        return _make_jaxpr(fn, (64,))

    assert jc.jaxpr_hash(trace(40)) != jc.jaxpr_hash(trace(60))


# ---------------------------------------------------------------------------
# Level 3 negatives: the engine flags a seeded violation of each spec field
# ---------------------------------------------------------------------------


def test_donation_audit_catches_broken_donation():
    """The analysis the registry exists to host: a donated input whose
    every output needs a different dtype/shape cannot alias — XLA
    SILENTLY copies (a UserWarning at best), and only the compiled
    executable's input_output_alias tells the truth."""
    import jax
    import jax.numpy as jnp

    sds = (
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.int32),
    )

    def broken(a, b):
        # no output has donated `a`'s byte size (16 B and a scalar vs
        # 32 B), so the donation is unusable. Dtype alone no longer
        # breaks it: jaxlib 0.9.0 aliases i32[8] into an f32[8] output
        return (a.astype(jnp.float32) * 2.0)[:4], b.sum()

    rep = engine.audit_donation(jax.jit(broken, donate_argnums=(0,)), sds, (0,))
    assert not rep.ok
    assert 0 in rep.missing

    def good(a, b):
        return a + 1, b.sum()

    rep = engine.audit_donation(jax.jit(good, donate_argnums=(0,)), sds, (0,))
    assert rep.ok, (rep.missing, rep.unusable_warnings, rep.header)
    assert 0 in rep.aliased_params


def test_donation_check_fails_on_undeclared_argnum():
    """Auditing MORE argnums than the program donates must fail — the
    registry can't claim in-place behavior the executable lacks."""
    spec = dataclasses.replace(
        PROGRAMS["delta_apply"],
        donation=DonationSpec(donate_argnums=(0, 1, 2, 3, 4), builder="aot_delta_apply"),
    )
    with pytest.raises(engine.ContractError, match="NOT aliased"):
        engine.check_donation(spec)


def test_donation_check_fails_on_missing_builder():
    spec = dataclasses.replace(
        PROGRAMS["delta_apply"],
        donation=DonationSpec(donate_argnums=(0,), builder="aot_no_such_builder"),
    )
    with pytest.raises(engine.ContractError, match="builder"):
        engine.check_donation(spec)


def test_engine_flags_forbidden_scatter():
    spec = dataclasses.replace(PROGRAMS["delta_apply"], scatter_policy="forbidden")
    with pytest.raises(engine.ContractError, match="forbidden"):
        engine.check_contracts(spec)


def test_engine_flags_vacuous_scatter_exemption():
    spec = dataclasses.replace(
        PROGRAMS["warm_flow"], kind="maintenance", scatter_policy="scoped-exempt"
    )
    with pytest.raises(engine.ContractError, match="VACUOUS"):
        engine.check_contracts(spec)


def test_engine_flags_collective_budget_mismatch():
    spec = dataclasses.replace(
        PROGRAMS["sharded_slot_solve"],
        collectives=CollectiveBudget(loop=(("psum", 99),)),
    )
    with pytest.raises(engine.ContractError, match="psum count"):
        engine.check_contracts(spec)


def test_engine_flags_forbidden_collective():
    spec = dataclasses.replace(
        PROGRAMS["sharded_slot_solve"],
        collectives=CollectiveBudget(forbidden=("psum",)),
    )
    with pytest.raises(engine.ContractError, match="forbidden collective"):
        engine.check_contracts(spec)


def test_engine_flags_hash_pin_mismatch():
    spec = dataclasses.replace(
        PROGRAMS["csr_solve"], telemetry_off_hash="0000000000000000"
    )
    with pytest.raises(engine.ContractError, match="pinned"):
        engine.check_hash_pin(spec)


def test_engine_flags_cross_bucket_hash_split():
    """A `same` pair straddling two buckets must fail (and proves the
    stability check isn't comparing a hash to itself)."""
    spec = dataclasses.replace(
        PROGRAMS["csr_solve"],
        hash_stability=HashStability(
            "pow2-bucket", same=((call(12, 40), call(12, 200)),)
        ),
    )
    with pytest.raises(engine.ContractError, match="recompile hazard"):
        engine.check_hash_stability(spec)


def test_engine_flags_vacuous_cross_pair():
    spec = dataclasses.replace(
        PROGRAMS["csr_solve"],
        hash_stability=HashStability(
            "pow2-bucket", cross=((call(12, 40), call(15, 60)),)
        ),
    )
    with pytest.raises(engine.ContractError, match="vacuous"):
        engine.check_hash_stability(spec)


def test_engine_flags_vacuous_distinct_variant():
    spec = dataclasses.replace(PROGRAMS["csr_solve"], distinct_from=("csr_solve",))
    with pytest.raises(engine.ContractError, match="collides"):
        engine.check_distinct(spec)


def test_engine_flags_undeclared_ownership():
    spec = dataclasses.replace(PROGRAMS["csr_solve"], module="ksched_tpu.solver.base")
    with pytest.raises(engine.ContractError, match="declare_programs"):
        engine.check_declared(spec)


def test_engine_counts_the_active_set_scatters_and_confines_them():
    """One scatter more or fewer than the chip reading admitted fails;
    so does the policy on a program with no sparse branch (its tracer
    swapped for the slot-stable one's, which never scatters)."""
    active = PROGRAMS["csr_solve_active"]
    with pytest.raises(engine.ContractError, match="was admitted 3"):
        engine.check_contracts(dataclasses.replace(active, scatters=3))
    with pytest.raises(engine.ContractError, match="was admitted 4"):
        engine.check_contracts(
            dataclasses.replace(active, tracer="trace_jax_slot_stable", gathers=None)
        )
    with pytest.raises(engine.ContractError, match="'forbidden'"):
        engine.check_contracts(
            dataclasses.replace(active, scatter_policy="forbidden", scatters=None)
        )
    with pytest.raises(ValueError, match="active-set policy"):
        dataclasses.replace(PROGRAMS["csr_solve"], scatters=4)


def test_engine_flags_missing_tracer():
    spec = dataclasses.replace(PROGRAMS["csr_solve"], tracer="trace_no_such_thing")
    with pytest.raises(engine.ContractError, match="does not exist"):
        engine.check_contracts(spec)


def test_registry_rejects_bad_vocabulary():
    with pytest.raises(ValueError, match="scatter policy"):
        dataclasses.replace(PROGRAMS["csr_solve"], scatter_policy="whatever")
    with pytest.raises(ValueError, match="dtype policy"):
        dataclasses.replace(PROGRAMS["csr_solve"], dtype_policy="int64")
    with pytest.raises(ValueError, match="reason"):
        HashStability("exempt")
    with pytest.raises(ValueError, match="kind"):
        HashStability("no-such-kind")


def test_declare_programs_rejects_typo_eagerly():
    from ksched_tpu.analysis.program_registry import declare_programs

    with pytest.raises(ValueError, match="unregistered program"):
        declare_programs("tests._fake_module", "csr_slove")


# ---------------------------------------------------------------------------
# Level 3 satellites: CLI flags
# ---------------------------------------------------------------------------


def _run_cli(*argv, timeout=120):
    """Drive the CLI in-process (argparse + real repo walk, no
    interpreter spawn — the end-to-end subprocess path is covered once
    by test_cli_exits_zero)."""
    import contextlib
    import io

    from tools import kschedlint

    out, err = io.StringIO(), io.StringIO()
    cwd = os.getcwd()
    os.chdir(REPO_ROOT)
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            try:
                rc = kschedlint.main(list(argv))
            except SystemExit as e:
                rc = e.code if isinstance(e.code, int) else 2
    finally:
        os.chdir(cwd)
    return subprocess.CompletedProcess(argv, rc, out.getvalue(), err.getvalue())


def test_cli_unknown_rule_exits_2():
    proc = _run_cli("--rules", "dtype64,no-such-rule", "tools")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


def test_cli_rules_subset_runs():
    proc = _run_cli("--rules", "dtype64,raw-print", "tools")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "2 rules" in proc.stderr


def test_cli_coverage_summary_line():
    proc = _run_cli("--coverage", "ksched_tpu", "tools")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert f"kschedlint L3: {len(PROGRAMS)} programs registered" in proc.stderr
    assert "0 unaudited" in proc.stderr


def test_cli_json_mode(tmp_path):
    proc = _run_cli("--json", "--coverage", "ksched_tpu", "tools")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True
    assert payload["new"] == [] and payload["stale_baseline"] == []
    cov = payload["coverage"]
    assert cov["unaudited"] == [] and cov["unannotated_registered"] == []
    assert cov["programs_registered"] == len(PROGRAMS)
    assert cov["sites"] == len(cov["annotated"]) + len(cov["waived"])


def test_cli_stale_baseline_fails_and_prune_sheds(tmp_path):
    """The shrink-only ratchet: a baseline entry matching no current
    violation is an ERROR (the debt was paid; the entry would silently
    excuse a regression), and --prune-baseline sheds exactly those
    entries without admitting anything new."""
    tree = tmp_path / "pkg"
    tree.mkdir()
    (tree / "clean.py").write_text("def f():\n    return 1\n")
    stale = tmp_path / "baseline.json"
    stale.write_text(json.dumps({
        "violations": [
            {"path": "pkg/gone.py", "rule": "dtype64", "hash": "0" * 16}
        ]
    }))
    proc = _run_cli("--baseline", str(stale), str(tree))
    assert proc.returncode == 1
    assert "stale baseline" in proc.stderr
    proc = _run_cli("--prune-baseline", "--baseline", str(stale), str(tree))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(stale.read_text())["violations"] == []
