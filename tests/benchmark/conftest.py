"""Nothing is hooked here any more.

Until PR 37 this file marked `test_benchmark_checks.py`'s pin of the stated
guarantees to "these five" as a strict expected failure; PR 37 rewrote the
pin (stated == the modules under `checks/`) and the mark went with it. The
file stays, empty, for one reason: `.claude/skills/verify/SKILL.md` names
it, `tests/test_docs_name_what_exists.py` holds every document to the files
it names, and a `benchmark` PR may change nothing outside `BENCHMARK.json`'s
`paths`. The next PR that may edit the skill file takes out its sentence
("a clean run reports `1 xfailed`") and deletes this file with it (PERF.md,
section 7)."""
