"""Bring-up contracts (ISSUE 21): no CPU fallback under a device
metric's name, one process per chip, a compile cache that can be
placed, a native library named by its source, and chip_smoke.py's
rehearsal."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, env=None, timeout=300):
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    base["JAX_PLATFORMS"] = "cpu"
    base.pop("JAX_COMPILATION_CACHE_DIR", None)
    base.update(env or {})
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=base,
        capture_output=True, text=True, timeout=timeout,
    )


#: what each phase's line must state besides `pass`: the comparison the
#: phase exists for, in the words chip_smoke.py prints
PHASE_FACTS = {
    "served": (
        # every podgen pod bound in the cold round, objective == native
        r" pods=(?P<pods>\d+) backend=jax/no-degrade "
        r"round1\[bound=(?P=pods) supersteps=\d+ objective=\d+==native ",
        r"round2\[bound=\d+ supersteps=\d+ objective=\d+==native ",
        r"noop_rounds=0 ",
        r"backend=auto round1\[last_path=\w+ ",
    ),
    "array": (
        r"all converged, invariants hold",
        r"pallas\(interpret\)==xla: placements, supersteps, pu_running identical",
    ),
    "kernels": (
        r"transport\(interpret\)==xla supersteps=\d+",
        r"tiered\(interpret\)==xla supersteps=\d+",
    ),
    "general": (
        r"scan-CSR converged supersteps=\d+$",
    ),
    "sharded": (r"sharded: not_run \(1 device\)",),
    "resident": (
        r"objectives==native in both",
        r"mirror and plan mirror equal the host's",
        r"compiles after a stretch's first round: resident=\{'trickle': 0, 'waves': 0\} "
        r"synchronous=\{'trickle': 0, 'waves': 0\}",
    ),
    "antiaffinity": (
        r"machines=125 nodes=2048 arcs=8192 ",
        r"objectives==native in every round",
        r"\d+ Bindings and completions replayed: no node held two pods of a workload",
        r"compiles after the first trickle round: 0",
    ),
    "zonespread": (
        r"machines=125 nodes=2048 arcs=4096 ",
        r"objectives==native in every round",
        r"\d+ Bindings and completions replayed over 6 rounds in 3 zones: largest skew [01] \(maxSkew 5\)",
        r"compiles after the first trickle round: 0",
    ),
    "preemption": (
        r"machines=125 nodes=1024 arcs=4096 ",
        r"objectives==native and bound / evicted by tier == the greedy's in every round",
        r"\d+ entries replayed: bound \[\d+, 42\], evicted \[\d+, 0\], [1-9]\d* evicted pods bound again",
        r"compiles after the first trickle round: 0",
    ),
    "quincy": (
        r"machines=312 nodes=8192 arcs=16384 ",
        r"objectives==native in every round",
        r"\d+ Bindings and completions replayed over 6 rounds in 250 racks: "
        r"served cost (?P<cost>\d+) == optimum (?P=cost), bound through a machine / rack / X arc ",
        r"compiles after the first trickle round: 0",
    ),
}


@pytest.fixture(scope="module")
def rehearsal():
    """chip_smoke.py --rehearse-cpu, run once for every test below."""
    r = _run("chip_smoke.py", "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout.strip().splitlines()


def test_chip_smoke_rehearsal_runs_green(rehearsal):
    """Every phase at tiny sizes, in order; every line says cpu; the
    last stdout line is the result."""
    result = json.loads(rehearsal[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert all("cpu" in line for line in rehearsal)
    phases = [ln.split("phase ")[1].split(":")[0] for ln in rehearsal if " phase " in ln]
    assert phases == list(PHASE_FACTS)  # chip_smoke.PHASES, in its order


@pytest.mark.parametrize("phase", list(PHASE_FACTS))
def test_rehearsed_phase_passes_and_states_its_facts(rehearsal, phase):
    (line,) = [ln for ln in rehearsal if f" phase {phase}: " in ln]
    assert f" phase {phase}: pass " in line
    for fact in PHASE_FACTS[phase]:
        assert re.search(fact, line), (fact, line)


def test_no_chip_is_an_error_and_prints_no_metric_line():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no accelerator" in r.stderr or "no chip" in r.stderr


def test_compile_cache_is_placed_by_env_or_at_the_checkout(monkeypatch):
    import jax

    from ksched_tpu.utils import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        # the operator's variable is JAX's to read: nothing was set
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(ROOT, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_native_library_is_named_by_its_source_bytes(tmp_path):
    from ksched_tpu.native.build import _SRC, _lib_name, library_path

    a = tmp_path / "a.cpp"
    b = tmp_path / "b.cpp"
    a.write_bytes(b"int f() { return 1; }\n")
    b.write_bytes(b"int f() { return 2; }\n")
    assert _lib_name(str(a)) != _lib_name(str(b))
    b.write_bytes(a.read_bytes())
    assert _lib_name(str(a)) == _lib_name(str(b))
    # the loaded library is the one named for mcmf.cpp as it is on disk
    assert os.path.basename(library_path()) == _lib_name(_SRC)
