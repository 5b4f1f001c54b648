"""kschedlint: the repo's AST lint CLI (Levels 1+3 of ksched_tpu.analysis).

Usage:
    python -m tools.kschedlint ksched_tpu tools
    python -m tools.kschedlint --coverage ksched_tpu tools
    python -m tools.kschedlint --rules dtype64,unregistered-program ksched_tpu
    python -m tools.kschedlint --json ksched_tpu tools
    python -m tools.kschedlint --prune-baseline ksched_tpu tools

Exit status: 0 when every violation is suppressed inline or recorded in
the baseline AND the baseline carries no stale entries; 1 when NEW
violations exist (printed one per line as `path:line:col: rule:
message`) or when baseline entries match no current violation (the
ratchet only shrinks — run --prune-baseline to shed fixed debt);
2 on usage errors, including unknown rule names in --rules.

--coverage adds the Level-3 program-coverage report: every
jax.jit / pl.pallas_call / shard_map call site in library code must be
annotated with a registered `# kschedlint: program=<name>` or waived
with `# kschedlint: disable=unregistered-program -- rationale`, and
every registered site name must be annotated somewhere. The summary
line is printed either way.

The jaxpr contracts and the registry engine (Level 2/3 dynamic checks)
need jax and are run by tests/test_static_analysis.py, not this CLI,
so the lint stays usable in environments without the jax_graft
toolchain. The registry's declarative side (program names, site
annotations) is stdlib-only and IS checked here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # `python tools/kschedlint.py` direct invocation
    sys.path.insert(0, _REPO_ROOT)

from ksched_tpu.analysis import (  # noqa: E402
    RULES,
    fingerprint,
    lint_paths,
    load_baseline,
    program_coverage,
    split_by_baseline,
    write_baseline,
)
from ksched_tpu.analysis.program_registry import PROGRAMS  # noqa: E402

DEFAULT_BASELINE = os.path.join("tools", "kschedlint_baseline.json")


def _coverage_summary(cov) -> str:
    return (
        f"kschedlint L3: {len(PROGRAMS)} programs registered / "
        f"{cov['sites']} call sites swept / "
        f"{len(cov['waived'])} waived / "
        f"{len(cov['unaudited'])} unaudited"
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="kschedlint", description=__doc__)
    parser.add_argument("paths", nargs="*", default=["ksched_tpu", "tools"],
                        help="files/directories to lint (default: the library "
                        "and tools)")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="baseline JSON (repo-relative)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline: every violation fails")
    parser.add_argument("--write-baseline", action="store_true",
                        help="accept current violations into the baseline and exit 0")
    parser.add_argument("--prune-baseline", action="store_true",
                        help="shed stale baseline entries (shrink-only: never "
                        "adds debt) and exit 0 if nothing new")
    parser.add_argument("--rules", default=None, metavar="R1,R2",
                        help="run only these rules (unknown names exit 2)")
    parser.add_argument("--coverage", action="store_true",
                        help="also run the Level-3 program-coverage report; "
                        "unaudited sites or unannotated registered programs fail")
    parser.add_argument("--json", dest="as_json", action="store_true",
                        help="emit one machine-readable JSON object on stdout")
    parser.add_argument("--list-rules", action="store_true")
    parser.add_argument("--root", default=_REPO_ROOT,
                        help="repo root paths are resolved against")
    args = parser.parse_args(argv)

    if args.list_rules:
        for name, fn in RULES.items():
            doc = (fn.__doc__ or "").strip().split("\n")[0]
            print(f"{name:20s} {doc}")
        return 0

    rules = None
    if args.rules is not None:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rules if r not in RULES]
        if unknown or not rules:
            print(f"kschedlint: unknown rule(s) in --rules: {unknown or '(none given)'} "
                  f"(known: {', '.join(RULES)})", file=sys.stderr)
            return 2

    for p in args.paths:
        # os.path.join passes absolute p through untouched, so this
        # also rejects a typo'd absolute path instead of "cleanly"
        # linting zero files
        if not os.path.exists(os.path.join(args.root, p)):
            print(f"kschedlint: no such path: {p}", file=sys.stderr)
            return 2

    violations = lint_paths(args.paths, repo_root=args.root, rules=rules)
    baseline_path = os.path.join(args.root, args.baseline)

    if args.write_baseline:
        count = write_baseline(baseline_path, violations)
        print(f"kschedlint: baseline written with {count} entr{'y' if count == 1 else 'ies'}")
        return 0

    baseline = Counter() if args.no_baseline else load_baseline(baseline_path)
    new, old, stale = split_by_baseline(violations, baseline)

    if args.prune_baseline:
        # shrink-only: keep exactly the entries current violations
        # still consume; NEVER admits new debt (that is --write-baseline,
        # which demands an explicit decision)
        count = write_baseline(baseline_path, old)
        stale = Counter()

    cov = None
    coverage_problems = []
    if args.coverage:
        cov = program_coverage(args.paths, repo_root=args.root)
        for entry in cov["unaudited"]:
            coverage_problems.append(
                f"{entry['path']}:{entry['line']}: unaudited program site "
                f"`{entry['callee']}` ({entry['kind']})"
            )
        for name in cov["unannotated_registered"]:
            coverage_problems.append(
                f"registry: program site `{name}` is registered but annotated "
                "at no call site — annotate it or drop the spec"
            )

    if args.as_json:
        payload = {
            "new": [
                {"path": v.path, "line": v.line, "col": v.col,
                 "rule": v.rule, "message": v.message}
                for v in new
            ],
            "baselined": len(old),
            "stale_baseline": [
                {"path": p, "rule": r, "hash": h, "count": c}
                for (p, r, h), c in sorted(stale.items())
            ],
            "rules": list(RULES if rules is None else rules),
        }
        if cov is not None:
            payload["coverage"] = {
                "programs_registered": len(PROGRAMS),
                "sites": cov["sites"],
                "annotated": cov["annotated"],
                "waived": cov["waived"],
                "unaudited": cov["unaudited"],
                "unannotated_registered": cov["unannotated_registered"],
                "summary": _coverage_summary(cov),
            }
        payload["ok"] = not (new or stale or coverage_problems)
        print(json.dumps(payload, indent=2, sort_keys=True))
        return 0 if payload["ok"] else 1

    for v in new:
        print(v.render())
    for line in coverage_problems:
        print(line)
    if old:
        print(f"kschedlint: {len(old)} baselined violation(s) not shown "
              f"(ratchet debt in {args.baseline})", file=sys.stderr)
    if stale:
        print(f"kschedlint: {sum(stale.values())} stale baseline entr(y/ies) — "
              "the violations were fixed; run --prune-baseline to shed them",
              file=sys.stderr)
    if cov is not None:
        print(_coverage_summary(cov), file=sys.stderr)
    if new or stale or coverage_problems:
        problems = len(new) + sum(stale.values()) + len(coverage_problems)
        print(f"kschedlint: {problems} problem(s)", file=sys.stderr)
        return 1
    print(f"kschedlint: clean ({len(old)} baselined, "
          f"{len(list(RULES if rules is None else rules))} rules)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
