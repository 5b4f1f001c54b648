"""Bring-up contracts (ISSUE 21): no CPU fallback under a device
metric's name, one process per chip, a compile cache that can be
placed, a native library named by its source, and chip_smoke.py's
rehearsal."""

import json
import os
import subprocess
import sys
import types

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


def test_chip_smoke_rehearsal_runs_green():
    """Every phase at tiny sizes, Pallas under the interpreter by
    name; every line says cpu; the last stdout line is the result."""
    r = _run("chip_smoke.py", "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert all("cpu" in line for line in lines)
    phases = [ln.split("phase ")[1].split(":")[0] for ln in lines if " phase " in ln]
    assert phases == ["served", "array", "kernels", "general", "sharded", "resident"]
    assert all(": pass " in ln for ln in lines if " phase " in ln)
    assert "mega(interpret): flows bit-equal" in r.stdout
    assert "sharded: not_run (1 device)" in r.stdout


@pytest.mark.parametrize("argv", [
    ("chip_smoke.py",),
    ("bench.py",),
    ("bench.py", "--config", "coco50k"),
    ("bench.py", "--suite", "--suite-out", os.devnull),
])
def test_no_chip_is_an_error_and_prints_no_metric_line(argv):
    r = _run(*argv)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no accelerator" in r.stderr or "no chip" in r.stderr


def test_suite_parent_never_touches_a_backend(monkeypatch, tmp_path, capsys):
    """run_suite's parent stamps the artifact from the FIRST CHILD's
    record; jax.devices() in the parent would take the chip from the
    children."""
    import jax

    sys.path.insert(0, ROOT)
    import bench

    def no_backend(*a, **kw):
        raise AssertionError("the suite parent initialised a JAX backend")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.setattr(jax, "default_backend", no_backend)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    calls = []

    def fake_run(cmd, **kw):
        if cmd[0] == "git":
            return types.SimpleNamespace(returncode=0, stdout="abc123\n", stderr="")
        calls.append(cmd)
        name = cmd[cmd.index("--config") + 1]
        rec = {"metric": f"m backend=device/tpu", "value": 1.0, "unit": "ms",
               "config": name, "device": device}
        return types.SimpleNamespace(returncode=0, stdout=json.dumps(rec) + "\n", stderr="")

    monkeypatch.setattr(subprocess, "run", fake_run)
    out = tmp_path / "suite.jsonl"
    args = types.SimpleNamespace(
        suite_out=str(out), rounds=8, chunk=4, cpu=False, verbose=False
    )
    assert bench.run_suite(args) == 0
    assert len(calls) == len(bench.SUITE_CONFIGS)
    assert all("--fell-back" not in c for c in calls)
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert lines[0]["suite_stamp"] and lines[0]["device"] == device
    assert lines[0]["platform"] == "tpu"
    assert [ln["config"] for ln in lines[1:]] == list(bench.SUITE_CONFIGS)


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
