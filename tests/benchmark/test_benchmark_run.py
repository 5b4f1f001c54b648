"""benchmarks/run.py end to end at rehearsal size: the last line has the
contract's shape, no chip is an error, and a lost Binding trips `correct`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _run(argv, timeout=300):
    # as deployed: the benchmark sets no KSCHED_* variable, and neither
    # does its test (tests/conftest.py turns soltel off for the suite)
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR") and not k.startswith("KSCHED_")
    }
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, *argv], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )


def _cell_argv(cell, trace, seconds="3"):
    return [*BENCH["command"][1:], "--workload", cell, "--seed", "5",
            "--seconds", seconds, "--trace", str(trace)]


def _metric_names(kind, cell):
    return {
        m["name"] for m in BENCH[kind] if "workloads" not in m or cell in m["workloads"]
    }


@pytest.mark.parametrize("cell, trace", [
    ("trivial-10kx1k.trickle", 0),
    ("coco-50kx1k.waves", 1),
])
def test_rehearsal_prints_a_last_line_of_the_contracts_shape(cell, trace):
    r = _run(_cell_argv(cell, trace) + ["--rehearse-cpu"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True, out["facts"]["faults"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert "memory_peak_bytes" in out["device"] and out["facts"]["rehearsal"] is True
    kind = "per_layer" if trace else "end_to_end"
    assert set(out["metrics"]) <= _metric_names(kind, cell)
    units = {m["name"]: m["unit"] for m in BENCH[kind]}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name] and isinstance(m["value"], float)
    if trace:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert {"device_ops", "idle_gaps"} == set(out["breakdown"])
        assert all(len(v) <= 10 for v in out["breakdown"].values())
        assert out["metrics"]["device_round_share"]["value"] == 100.0
        assert out["metrics"]["compiles_in_window"]["value"] == 0.0
        assert "solve_roofline" not in out["metrics"]  # the host has no peaks on file
        assert not os.path.exists(os.path.join(ROOT, ".bench_out", f"trace-{cell}"))
    else:
        assert set(out["metrics"]) == _metric_names(kind, cell)
        assert all(m["value"] > 0 for m in out["metrics"].values())


def test_no_chip_is_an_error_and_prints_no_result():
    r = _run(_cell_argv("trivial-10kx1k.trickle", 0))
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no chip" in r.stderr
    r = _run(_cell_argv("no-such.cell", 0) + ["--rehearse-cpu"])
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_a_lost_binding_trips_correct():
    """The service binds every pod; the client never hears of some."""
    patch = (
        "import sys; sys.argv = ['run.py'] + sys.argv[1:]\n"
        "import benchmarks.run as run, benchmarks.client as client\n"
        "keep = client.BenchClusterAPI.assign_bindings\n"
        "def lossy(self, bindings):\n"
        "    keep(self, [b for b in bindings if not b.pod_id.endswith('7_0')])\n"
        "client.BenchClusterAPI.assign_bindings = lossy\n"
        "sys.exit(run.main())\n"
    )
    argv = _cell_argv("trivial-10kx1k.waves", 0, seconds="2")[1:] + ["--rehearse-cpu"]
    r = _run(["-c", patch, *argv])
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is False and out["failed"] >= 1
    assert any("got no Binding" in f for f in out["facts"]["faults"])
