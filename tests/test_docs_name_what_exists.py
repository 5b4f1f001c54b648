"""The documents a reader is sent to name files, directories and make
targets that exist. PERF.md and ROADMAP.md (they name files that are
planned) and CHANGES.md (it tells history) are left out."""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP = ("ksched_tpu", "tools", "tests", "docs", "benchmarks")
DOCUMENTS = ["README.md", "BASELINE.md", "Makefile", ".claude/skills/verify/SKILL.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(ROOT, "docs")) if name.endswith(".md")
)


@functools.lru_cache(maxsize=None)
def _tree():
    """(file names outside the directories .gitignore lists, make targets)."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {".git"} | {ln.strip().strip("/").split("/")[-1] for ln in f if ln.strip().endswith("/")}
    basenames = set()
    for _, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in ignored]
        basenames.update(names)
    with open(os.path.join(ROOT, "Makefile")) as f:
        targets = set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))
    return basenames, targets


def _missing(document):
    """Repo paths and make targets `document` names that do not exist.

    A path is a token ending in .py/.json/.jsonl/.md anywhere, or a
    back-ticked token with a `/` that starts at a top-level directory.
    It may be written from the root, from `ksched_tpu/`, from the
    document's own directory or, without a `/`, as a bare file name.
    Skipped: absolute paths (outside the repo; where an example's
    outputs belong) and placeholders (`<`, `*`, `...`)."""
    basenames, targets = _tree()
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    tokens = set(re.findall(r"[\w.<>*/-]+\.(?:py|jsonl?|md)\b", text))
    tokens.update(
        tok for tok in re.findall(r"`([\w.<>*/-]+/[\w.<>*/-]*)`", text) if tok.split("/")[0] in TOP
    )
    missing = []
    for tok in sorted(tokens):
        if tok.startswith("/") or "..." in tok or any(c in tok for c in "<>*"):
            continue
        if "/" not in tok:
            found = tok in basenames
        else:
            found = any(
                os.path.exists(os.path.join(ROOT, base, tok))
                for base in ("", "ksched_tpu", os.path.dirname(document))
            )
        if not found:
            missing.append(tok)
    named = set(re.findall(r"(?:`|^[ \t#]*)make ([a-z][\w-]*)", text, re.M))
    return missing + sorted("make " + t for t in named - targets)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_what_exists(document):
    assert _missing(document) == []
