"""Level-1 lint: AST rules for the repo's TPU invariants.

Each rule is a function `(ctx: FileContext) -> Iterable[Violation]`
registered in `RULES`. Rules are pure AST + comment-token analysis: no
imports of the linted code, so the linter can check files that need a
TPU (or a C++ toolchain) to import.

Suppressions are line-level comments on the line of the flagged node:

    x = np.zeros(n + 1, dtype=np.int64)  # kschedlint: host-only (why)
    y = risky()  # kschedlint: disable=bare-except,raw-print -- why

`host-only` silences only the `dtype64` rule (it is a semantic claim:
this 64-bit value never crosses the jit boundary); `disable=` silences
the named rules. Both forms should carry a rationale — the lint does
not parse it, reviewers do.

Level 3 adds two directive-audit rules and the compiled-program sweep:

- `unregistered-program`: every `jax.jit` / `pl.pallas_call` /
  `shard_map` call site in the library must carry
  `# kschedlint: program=<name>` naming a program registered in
  `program_registry.py`, or a `disable=unregistered-program` waiver
  WITH a `-- rationale`.
- `stale-waiver`: a directive that suppresses nothing (and a
  `program=` annotation attached to no call site) is itself an error —
  waivers can only shrink.
- `bad-waiver`: an unparseable directive, a `disable=` naming an
  unknown rule (the classic typo that silently checks nothing), or an
  `unregistered-program` waiver without a rationale.

Scoping (see docs/static_analysis.md):

- `dtype64` applies to *device-bound* modules: files under the library
  root that import `jax`. Pure-numpy host modules (graph codecs, cost
  models, the CPU reference solver) legitimately compute in int64.
- `raw-print` applies to library modules except CLI entry points
  (`cli.py`, `__main__.py`); tools print by design.
- Everything else applies to every linted file.
"""

from __future__ import annotations

import ast
import io
import tokenize
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .program_registry import SITE_NAMES

#: module names whose import marks a file device-bound for `dtype64`
_JAX_MODULES = ("jax",)

#: attribute / dtype-string names the `dtype64` rule flags
_DTYPE64_NAMES = frozenset({"int64", "float64", "uint64"})

#: jnp constructors that must name their dtype, with the positional
#: index at which the dtype argument may appear instead of `dtype=`
_IMPLICIT_DTYPE_FUNCS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2, "arange": 3}

#: annotations / default types that mark a jit parameter
#: "obviously static" for the `jit-static` rule
_STATIC_ANNOTATIONS = frozenset({"int", "bool", "str"})


@dataclass(frozen=True)
class Violation:
    path: str  # repo-relative, forward slashes
    rule: str
    line: int
    col: int
    message: str
    line_text: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


@dataclass
class FileContext:
    path: str  # repo-relative
    source: str
    tree: ast.Module
    lines: List[str]
    comments: Dict[int, str]  # line -> comment text (without '#')
    device_bound: bool  # imports jax -> dtype64 applies
    in_library: bool  # under the library package root
    is_cli: bool  # CLI entry point (print allowed)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def suppressed(self, lineno: int, rule: str) -> bool:
        comment = self.comments.get(lineno, "")
        marker = comment.find("kschedlint:")
        if marker < 0:
            return False
        directive = comment[marker + len("kschedlint:"):].strip()
        if directive.startswith("host-only"):
            return rule == "dtype64"
        if directive.startswith("disable="):
            names = directive[len("disable="):].split("--")[0].split("(")[0]
            return rule in {n.strip() for n in names.split(",")}
        return False


def _collect_comments(source: str) -> Dict[int, str]:
    comments: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                comments[tok.start[0]] = tok.string.lstrip("#").strip()
    except (tokenize.TokenError, IndentationError):  # half-written file: lint what parsed
        pass
    return comments


def _imports_jax(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] in _JAX_MODULES for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] in _JAX_MODULES:
                return True
    return False


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute(Name('jax'), 'jit'); '' when not a plain
    dotted path."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# ---------------------------------------------------------------------------
# dtype64: no 64-bit dtypes in device-bound code
# ---------------------------------------------------------------------------


def rule_dtype64(ctx: FileContext) -> Iterable[Violation]:
    """TPU v5e has no native int64 (solver/jax_solver.py header): a
    64-bit array reaching a jit boundary either downcasts silently
    (x64 off) or trips slow XLA emulation (x64 on). Host-side prep
    that never crosses the boundary carries `# kschedlint: host-only`
    with a rationale."""
    if not (ctx.in_library and ctx.device_bound):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and node.attr in _DTYPE64_NAMES:
            yield Violation(
                ctx.path, "dtype64", node.lineno, node.col_offset,
                f"64-bit dtype `{_dotted(node) or node.attr}` in a device-bound "
                "module; use int32/float32, or mark the line "
                "`# kschedlint: host-only` with a rationale",
                ctx.line_text(node.lineno),
            )
        elif isinstance(node, ast.Call):
            # dtype="int64" / astype("float64") / np.dtype("int64")
            callee = _dotted(node.func)
            is_astype = isinstance(node.func, ast.Attribute) and node.func.attr == "astype"
            is_dtype_ctor = callee.endswith(".dtype")
            for kw in node.keywords:
                if kw.arg == "dtype" and isinstance(kw.value, ast.Constant) \
                        and kw.value.value in _DTYPE64_NAMES:
                    yield Violation(
                        ctx.path, "dtype64", kw.value.lineno, kw.value.col_offset,
                        f'64-bit dtype string "{kw.value.value}" in a device-bound module',
                        ctx.line_text(kw.value.lineno),
                    )
            if (is_astype or is_dtype_ctor) and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Constant) and a0.value in _DTYPE64_NAMES:
                    yield Violation(
                        ctx.path, "dtype64", a0.lineno, a0.col_offset,
                        f'64-bit dtype string "{a0.value}" in a device-bound module',
                        ctx.line_text(a0.lineno),
                    )


# ---------------------------------------------------------------------------
# implicit-dtype: jnp array creation must name its dtype
# ---------------------------------------------------------------------------


def rule_implicit_dtype(ctx: FileContext) -> Iterable[Violation]:
    """`jnp.zeros(n)` materializes float32 (or float64 under x64) where
    the solvers need int32 — every jnp constructor names its dtype, as
    a positional argument or `dtype=`."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr in _IMPLICIT_DTYPE_FUNCS):
            continue
        base = _dotted(func.value)
        if base not in ("jnp", "jax.numpy"):
            continue
        dtype_pos = _IMPLICIT_DTYPE_FUNCS[func.attr]
        has_dtype = any(kw.arg == "dtype" for kw in node.keywords) or (
            len(node.args) > dtype_pos
            and not any(isinstance(a, ast.Starred) for a in node.args)
        )
        if not has_dtype:
            yield Violation(
                ctx.path, "implicit-dtype", node.lineno, node.col_offset,
                f"`{base}.{func.attr}(...)` without an explicit dtype",
                ctx.line_text(node.lineno),
            )


# ---------------------------------------------------------------------------
# jit-static / traced-branch: jit boundary hygiene
# ---------------------------------------------------------------------------


def _jit_decoration(node: ast.AST) -> Optional[Tuple[Set[str], ast.AST]]:
    """When `node` is a jit decorator, return (static_argnames, site).

    Recognized forms: `jax.jit`, `jit`, `jax.jit(...)`,
    `functools.partial(jax.jit, static_argnames=(...))`,
    `partial(jit, ...)`. static_argnums is resolved by the caller
    (needs the parameter list)."""
    target = node
    statics: Set[str] = set()
    if isinstance(node, ast.Call):
        callee = _dotted(node.func)
        if callee in ("functools.partial", "partial"):
            if not node.args or _dotted(node.args[0]) not in ("jax.jit", "jit"):
                return None
        elif callee not in ("jax.jit", "jit"):
            return None
        for kw in node.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                vals = kw.value.elts if isinstance(kw.value, (ast.Tuple, ast.List)) \
                    else [kw.value]
                for v in vals:
                    if isinstance(v, ast.Constant):
                        statics.add(v.value)  # str names and int nums mixed
        return statics, target
    if _dotted(node) in ("jax.jit", "jit"):
        return statics, target
    return None


def _params_of(fn: ast.FunctionDef) -> List[ast.arg]:
    return list(fn.args.posonlyargs) + list(fn.args.args) + list(fn.args.kwonlyargs)


def _static_param_names(fn: ast.FunctionDef, statics: Set) -> Set[str]:
    params = _params_of(fn)
    names = {s for s in statics if isinstance(s, str)}
    for s in statics:
        if isinstance(s, int) and 0 <= s < len(params):
            names.add(params[s].arg)
    return names


def _looks_static(param: ast.arg, default: Optional[ast.AST]) -> bool:
    if isinstance(param.annotation, ast.Name) and param.annotation.id in _STATIC_ANNOTATIONS:
        return True
    if isinstance(default, ast.Constant) and isinstance(default.value, (bool, int, str)) \
            and default.value is not None:
        return True
    return False


def _defaults_by_param(fn: ast.FunctionDef) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    positional = list(fn.args.posonlyargs) + list(fn.args.args)
    for param, default in zip(reversed(positional), reversed(fn.args.defaults)):
        out[param.arg] = default
    for param, default in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if default is not None:
            out[param.arg] = default
    return out


def _iter_jitted_functions(tree: ast.Module):
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for deco in node.decorator_list:
            hit = _jit_decoration(deco)
            if hit is not None:
                yield node, _static_param_names(node, hit[0])
                break


def rule_jit_static(ctx: FileContext) -> Iterable[Violation]:
    """A Python-scalar knob (int/bool/str annotation or default) passed
    through `jax.jit` without `static_argnames` becomes a traced 0-d
    array: `if knob:` then either fails or, worse, retraces per value.
    Every obviously-static parameter must be listed."""
    for fn, static_names in _iter_jitted_functions(ctx.tree):
        defaults = _defaults_by_param(fn)
        for param in _params_of(fn):
            if param.arg in static_names or param.arg in ("self", "cls"):
                continue
            if _looks_static(param, defaults.get(param.arg)):
                yield Violation(
                    ctx.path, "jit-static", param.lineno, param.col_offset,
                    f"jitted `{fn.name}` parameter `{param.arg}` looks static "
                    "(scalar annotation/default) but is missing from "
                    "static_argnames — it will be traced, and branching on it "
                    "will fail or silently retrace",
                    ctx.line_text(param.lineno),
                )


class _TracedBranchVisitor(ast.NodeVisitor):
    """Flag `if`/`while` whose test mentions a traced (non-static)
    parameter of the enclosing jitted function. Nested functions that
    rebind a name shadow it (their params are their own scope)."""

    def __init__(self, ctx: FileContext, fn: ast.FunctionDef, traced: Set[str]):
        self.ctx = ctx
        self.fn_name = fn.name
        self.traced = traced
        self.out: List[Violation] = []

    def _visit_scope(self, node, removed: Set[str]):
        saved = self.traced
        self.traced = self.traced - removed
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self.traced = saved

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self._visit_scope(node, {a.arg for a in _params_of(node)})

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda):
        self._visit_scope(node, {a.arg for a in node.args.args})

    @staticmethod
    def _is_none_check(node) -> bool:
        """`x is None` / `x is not None`: a trace-time static fact (did
        the caller pass None), the standard optional-argument idiom."""
        return (
            isinstance(node, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
            and all(
                isinstance(c, ast.Constant) and c.value is None
                for c in node.comparators
            )
        )

    def _names_outside_none_checks(self, node, acc: Set[str]):
        if self._is_none_check(node):
            return
        if isinstance(node, ast.Name):
            acc.add(node.id)
        for child in ast.iter_child_nodes(node):
            self._names_outside_none_checks(child, acc)

    def _check_test(self, node):
        referenced: Set[str] = set()
        self._names_outside_none_checks(node.test, referenced)
        names = referenced & self.traced
        if names:
            kind = "if" if isinstance(node, ast.If) else "while"
            self.out.append(Violation(
                self.ctx.path, "traced-branch", node.lineno, node.col_offset,
                f"Python `{kind}` on traced value(s) {sorted(names)} inside "
                f"jitted `{self.fn_name}` — use lax.cond/lax.while_loop or "
                "mark the argument static",
                self.ctx.line_text(node.lineno),
            ))

    def visit_If(self, node: ast.If):
        self._check_test(node)
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._check_test(node)
        self.generic_visit(node)


def rule_traced_branch(ctx: FileContext) -> Iterable[Violation]:
    """Heuristic for the classic jit trap: `if x > 0:` on a traced
    value raises TracerBoolConversionError at best, and at worst (when
    x is a numpy scalar on the first call) silently bakes one branch
    into the compiled program."""
    for fn, static_names in _iter_jitted_functions(ctx.tree):
        traced = {p.arg for p in _params_of(fn)} - static_names - {"self", "cls"}
        visitor = _TracedBranchVisitor(ctx, fn, traced)
        for stmt in fn.body:
            visitor.visit(stmt)
        yield from visitor.out


# ---------------------------------------------------------------------------
# generic Python hygiene
# ---------------------------------------------------------------------------


def rule_mutable_default(ctx: FileContext) -> Iterable[Violation]:
    """A list/dict/set default is evaluated once and shared by every
    call — state leaks across invocations."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]
        for default in defaults:
            bad = isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set")
            )
            if bad:
                name = getattr(node, "name", "<lambda>")
                yield Violation(
                    ctx.path, "mutable-default", default.lineno, default.col_offset,
                    f"mutable default argument in `{name}` is shared across calls; "
                    "default to None and materialize inside",
                    ctx.line_text(default.lineno),
                )


def rule_bare_except(ctx: FileContext) -> Iterable[Violation]:
    """`except:` catches KeyboardInterrupt/SystemExit too; name the
    exception types (or `except Exception` at the very least)."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield Violation(
                ctx.path, "bare-except", node.lineno, node.col_offset,
                "bare `except:` swallows KeyboardInterrupt/SystemExit; name the "
                "exception types",
                ctx.line_text(node.lineno),
            )


def rule_raw_print(ctx: FileContext) -> Iterable[Violation]:
    """Library code reports through `warnings`/logging/return values so
    callers and tests can capture it; `print` is for CLI entry points
    (cli.py, tools/)."""
    if not ctx.in_library or ctx.is_cli:
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            yield Violation(
                ctx.path, "raw-print", node.lineno, node.col_offset,
                "raw `print` in library code; use warnings.warn/logging so "
                "callers can capture it",
                ctx.line_text(node.lineno),
            )


# ---------------------------------------------------------------------------
# Level 3: directive parsing, the compiled-program sweep, waiver audits
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Directive:
    """One parsed `# kschedlint: ...` comment."""

    line: int
    kind: str  # "host-only" | "disable" | "program" | "unknown"
    rules: Tuple[str, ...] = ()
    program: str = ""
    has_rationale: bool = False
    text: str = ""


def parse_directive(line: int, comment: str) -> Optional[Directive]:
    marker = comment.find("kschedlint:")
    if marker < 0:
        return None
    text = comment[marker + len("kschedlint:"):].strip()
    if text.startswith("host-only"):
        rest = text[len("host-only"):].strip()
        return Directive(line, "host-only", has_rationale=bool(rest), text=text)
    if text.startswith("disable="):
        body = text[len("disable="):]
        names_part = body.split("--")[0].split("(")[0]
        names = tuple(n.strip() for n in names_part.split(",") if n.strip())
        has_rat = "--" in body and bool(body.split("--", 1)[1].strip())
        return Directive(line, "disable", rules=names, has_rationale=has_rat, text=text)
    if text.startswith("program="):
        body = text[len("program="):]
        name = body.split("--")[0].split("(")[0].strip()
        has_rat = ("--" in body and bool(body.split("--", 1)[1].strip())) or "(" in body
        return Directive(line, "program", program=name, has_rationale=has_rat, text=text)
    return Directive(line, "unknown", text=text)


def iter_directives(ctx: FileContext) -> Iterable[Directive]:
    for line in sorted(ctx.comments):
        d = parse_directive(line, ctx.comments[line])
        if d is not None:
            yield d


@dataclass(frozen=True)
class ProgramSite:
    """One jax.jit / pl.pallas_call / shard_map call site."""

    line: int  # anchor: the line of the jit/pallas_call/shard_map token
    end_line: int  # last line of the call/decorator span
    kind: str  # "jit" | "pallas_call" | "shard_map"
    callee: str
    program: Optional[str] = None  # program= annotation found in the span
    program_line: Optional[int] = None
    waiver_line: Optional[int] = None  # disable=unregistered-program line


def _site_of_call(node: ast.Call) -> Optional[Tuple[str, str, int]]:
    """(kind, callee, anchor_line) when the Call compiles a program."""
    callee = _dotted(node.func)
    last = callee.rsplit(".", 1)[-1]
    if callee in ("functools.partial", "partial"):
        if node.args:
            inner = _dotted(node.args[0])
            if inner.rsplit(".", 1)[-1] == "jit":
                return "jit", inner or "jit", node.args[0].lineno
        return None
    if last == "jit":
        return "jit", callee, node.func.lineno
    if last == "pallas_call":
        return "pallas_call", callee, node.func.lineno
    if "shard_map" in last:  # shard_map / _shard_map / _shard_map_native
        return "shard_map", callee or last, node.func.lineno
    return None


def collect_program_sites(ctx: FileContext) -> List[ProgramSite]:
    """Every compiled-program call site, with any `program=` annotation
    or `disable=unregistered-program` waiver found on the lines the
    call spans (multi-line `functools.partial(jax.jit, ...)` decorators
    carry theirs next to the `jax.jit` argument)."""
    hits: List[Tuple[ast.AST, str, str, int]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            found = _site_of_call(node)
            if found is not None:
                hits.append((node, *found))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:  # bare @jax.jit / @jit
                if not isinstance(deco, ast.Call) and _dotted(deco) in ("jax.jit", "jit"):
                    hits.append((deco, "jit", _dotted(deco), deco.lineno))
    sites: List[ProgramSite] = []
    for node, kind, callee, anchor in hits:
        end = getattr(node, "end_lineno", None) or node.lineno
        program = program_line = waiver_line = None
        for ln in range(node.lineno, end + 1):
            comment = ctx.comments.get(ln)
            if not comment:
                continue
            d = parse_directive(ln, comment)
            if d is None:
                continue
            if d.kind == "program" and program is None:
                program, program_line = d.program, ln
            elif d.kind == "disable" and "unregistered-program" in d.rules \
                    and waiver_line is None:
                waiver_line = ln
        sites.append(ProgramSite(anchor, end, kind, callee, program,
                                 program_line, waiver_line))
    sites.sort(key=lambda s: (s.line, s.kind, s.callee))
    return sites


def rule_unregistered_program(ctx: FileContext) -> Iterable[Violation]:
    """The Level-3 coverage ratchet: a compiled program nobody
    registered is a program nobody audits — its donation config,
    scatter policy, and hash stability are all unchecked. Register it
    in analysis/program_registry.py and annotate the site, or waive
    with a rationale."""
    if not ctx.in_library:
        return
    for site in collect_program_sites(ctx):
        if site.program is not None:
            if site.program in SITE_NAMES:
                continue
            yield Violation(
                ctx.path, "unregistered-program", site.program_line, 0,
                f"`program={site.program}` names no registered program — "
                "register it in ksched_tpu/analysis/program_registry.py",
                ctx.line_text(site.program_line),
            )
            continue
        vline = site.waiver_line or site.line
        yield Violation(
            ctx.path, "unregistered-program", vline, 0,
            f"`{site.callee}` compiles an UNREGISTERED program (no contract "
            "audit covers it); register it in analysis/program_registry.py "
            "and annotate `# kschedlint: program=<name>`, or waive with "
            "`# kschedlint: disable=unregistered-program -- rationale`",
            ctx.line_text(vline),
        )


#: the directive-audit rules exclude themselves when re-running the
#: rule set to decide what a directive suppresses
_WAIVER_AUDIT_RULES = ("stale-waiver", "bad-waiver")


def _raw_violations(ctx: FileContext) -> List[Violation]:
    out: List[Violation] = []
    for name, fn in RULES.items():
        if name in _WAIVER_AUDIT_RULES:
            continue
        out.extend(fn(ctx))
    return out


def rule_stale_waiver(ctx: FileContext) -> Iterable[Violation]:
    """A suppression that suppresses nothing is a latent hole: the code
    it excused is gone (or was fixed), and the directive would silently
    excuse the NEXT violation someone introduces on that line. Same for
    a `program=` annotation attached to no call site. Waivers only
    shrink."""
    directives = list(iter_directives(ctx))
    if not directives:
        return
    by_line: Dict[int, Set[str]] = {}
    for v in _raw_violations(ctx):
        by_line.setdefault(v.line, set()).add(v.rule)
    program_lines = {
        s.program_line for s in collect_program_sites(ctx)
        if s.program_line is not None
    }
    for d in directives:
        if d.kind == "host-only":
            if "dtype64" not in by_line.get(d.line, ()):
                yield Violation(
                    ctx.path, "stale-waiver", d.line, 0,
                    "`host-only` waiver suppresses nothing (no dtype64 "
                    "violation on this line) — remove it",
                    ctx.line_text(d.line),
                )
        elif d.kind == "disable":
            known = [r for r in d.rules if r in RULES]
            dead = [r for r in known if r not in by_line.get(d.line, ())]
            if dead:
                yield Violation(
                    ctx.path, "stale-waiver", d.line, 0,
                    f"disable={','.join(dead)} suppresses nothing on this "
                    "line — remove the dead waiver",
                    ctx.line_text(d.line),
                )
        elif d.kind == "program":
            if d.line not in program_lines:
                yield Violation(
                    ctx.path, "stale-waiver", d.line, 0,
                    f"`program={d.program}` annotation is attached to no "
                    "jit/pallas_call/shard_map call site — remove it",
                    ctx.line_text(d.line),
                )


def rule_bad_waiver(ctx: FileContext) -> Iterable[Violation]:
    """A malformed directive checks nothing — the typo'd rule name is
    the classic case (satellite of ISSUE 18: it used to silently
    disable nothing and nobody noticed)."""
    for d in iter_directives(ctx):
        if d.kind == "unknown":
            yield Violation(
                ctx.path, "bad-waiver", d.line, 0,
                f"unrecognized kschedlint directive `{d.text}` (expected "
                "host-only, disable=<rules> -- rationale, or program=<name>)",
                ctx.line_text(d.line),
            )
        elif d.kind == "disable":
            unknown = [r for r in d.rules if r not in RULES]
            if not d.rules:
                yield Violation(
                    ctx.path, "bad-waiver", d.line, 0,
                    "disable= names no rules", ctx.line_text(d.line),
                )
            if unknown:
                yield Violation(
                    ctx.path, "bad-waiver", d.line, 0,
                    f"disable= names unknown rule(s) {unknown} — a typo here "
                    "would silently check nothing",
                    ctx.line_text(d.line),
                )
            if "unregistered-program" in d.rules and not d.has_rationale:
                yield Violation(
                    ctx.path, "bad-waiver", d.line, 0,
                    "an unregistered-program waiver must carry a "
                    "`-- rationale` (why is this program exempt from the "
                    "registry audit?)",
                    ctx.line_text(d.line),
                )
        elif d.kind == "program" and not d.program:
            yield Violation(
                ctx.path, "bad-waiver", d.line, 0,
                "program= names nothing", ctx.line_text(d.line),
            )


RULES: Dict[str, Callable[[FileContext], Iterable[Violation]]] = {
    "dtype64": rule_dtype64,
    "implicit-dtype": rule_implicit_dtype,
    "jit-static": rule_jit_static,
    "traced-branch": rule_traced_branch,
    "mutable-default": rule_mutable_default,
    "bare-except": rule_bare_except,
    "raw-print": rule_raw_print,
    "unregistered-program": rule_unregistered_program,
    "stale-waiver": rule_stale_waiver,
    "bad-waiver": rule_bad_waiver,
}

#: package whose modules count as "library" for dtype64/raw-print
LIBRARY_ROOT = "ksched_tpu"

#: library files that are CLI entry points (print allowed)
_CLI_BASENAMES = ("cli.py", "__main__.py")


def build_context(path: str, source: str) -> FileContext:
    tree = ast.parse(source, filename=path)
    norm = path.replace("\\", "/")
    in_library = norm.startswith(LIBRARY_ROOT + "/") or norm == LIBRARY_ROOT
    return FileContext(
        path=norm,
        source=source,
        tree=tree,
        lines=source.splitlines(),
        comments=_collect_comments(source),
        device_bound=_imports_jax(tree),
        in_library=in_library,
        is_cli=norm.rsplit("/", 1)[-1] in _CLI_BASENAMES,
    )


def lint_source(path: str, source: str, rules: Optional[Sequence[str]] = None) -> List[Violation]:
    """Lint one file's source; returns unsuppressed violations, sorted.

    An unparsable file is reported as a single `syntax-error` violation
    (a clean diagnostic that fails the gate) rather than a traceback."""
    try:
        ctx = build_context(path, source)
    except SyntaxError as e:
        return [Violation(
            path.replace("\\", "/"), "syntax-error",
            e.lineno or 1, (e.offset or 1) - 1,
            f"file does not parse: {e.msg}",
            (e.text or "").rstrip("\n"),
        )]
    selected = RULES if rules is None else {r: RULES[r] for r in rules}
    out: List[Violation] = []
    for rule_fn in selected.values():
        for v in rule_fn(ctx):
            if not ctx.suppressed(v.line, v.rule):
                out.append(v)
    out.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return out


def lint_file(
    path: str, repo_root: str = ".", rules: Optional[Sequence[str]] = None
) -> List[Violation]:
    import os

    abs_path = path if os.path.isabs(path) else os.path.join(repo_root, path)
    with open(abs_path, "r", encoding="utf-8") as fh:
        source = fh.read()
    rel = os.path.relpath(abs_path, repo_root)
    return lint_source(rel, source, rules=rules)


def iter_py_files(paths: Sequence[str], repo_root: str = "."):
    """Expand files/directories into .py paths (repo-relative)."""
    import os

    for p in paths:
        abs_p = p if os.path.isabs(p) else os.path.join(repo_root, p)
        if os.path.isfile(abs_p):
            yield os.path.relpath(abs_p, repo_root)
            continue
        for dirpath, dirnames, filenames in os.walk(abs_p):
            dirnames[:] = [
                d for d in dirnames if d not in ("__pycache__", ".git", ".pytest_cache")
            ]
            for fname in sorted(filenames):
                if fname.endswith(".py"):
                    yield os.path.relpath(os.path.join(dirpath, fname), repo_root)


def program_coverage(paths: Sequence[str], repo_root: str = ".") -> Dict[str, object]:
    """The Level-3 coverage report over library files in `paths`:
    every jit/pallas_call/shard_map call site bucketed into annotated
    (carries a `program=` naming a registered program), waived
    (`disable=unregistered-program`), or unaudited — plus the reverse
    cross-check: registered site names annotated at NO call site
    (a registry entry auditing a program that is never compiled from
    the swept tree is itself a coverage hole)."""
    annotated: List[Dict[str, object]] = []
    waived: List[Dict[str, object]] = []
    unaudited: List[Dict[str, object]] = []
    seen_programs: Set[str] = set()
    for rel in iter_py_files(paths, repo_root):
        import os

        with open(os.path.join(repo_root, rel), "r", encoding="utf-8") as fh:
            source = fh.read()
        try:
            ctx = build_context(rel, source)
        except SyntaxError:
            continue
        if not ctx.in_library:
            continue
        for site in collect_program_sites(ctx):
            entry = {
                "path": ctx.path, "line": site.line, "kind": site.kind,
                "callee": site.callee,
            }
            if site.program is not None and site.program in SITE_NAMES:
                entry["program"] = site.program
                annotated.append(entry)
                seen_programs.add(site.program)
            elif site.waiver_line is not None:
                waived.append(entry)
            else:
                if site.program is not None:
                    entry["program"] = site.program  # names no registered spec
                unaudited.append(entry)
    unannotated = sorted(SITE_NAMES - seen_programs)
    return {
        "annotated": annotated,
        "waived": waived,
        "unaudited": unaudited,
        "unannotated_registered": unannotated,
        "sites": len(annotated) + len(waived) + len(unaudited),
    }


def lint_paths(
    paths: Sequence[str], repo_root: str = ".", rules: Optional[Sequence[str]] = None
) -> List[Violation]:
    out: List[Violation] = []
    for rel in iter_py_files(paths, repo_root):
        out.extend(lint_file(rel, repo_root, rules=rules))
    out.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return out
