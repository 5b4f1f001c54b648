"""The bench-trajectory ratchet (tools/bench_compare.py + `make
bench-gate`): append normalizes bench records into trajectory entries,
gate fails on >tolerance p50 regression within a (config, platform)
series and never compares across platforms."""

import json
import subprocess
import sys

import pytest

from tools.bench_compare import (
    DEFAULT_TOLERANCE,
    entry_from_record,
    load_trajectory,
)


def _write(path, entries):
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")


def _gate(path, tolerance=DEFAULT_TOLERANCE):
    return subprocess.run(
        [sys.executable, "-m", "tools.bench_compare", "gate", str(path),
         "--tolerance", str(tolerance)],
        capture_output=True, text=True,
    )


def _entry(config, p50, platform="cpu", **kw):
    return {"config": config, "platform": platform, "p50_ms": p50,
            "commit": "t", **kw}


def test_gate_passes_within_tolerance(tmp_path):
    p = tmp_path / "traj.jsonl"
    _write(p, [_entry("a", 10.0), _entry("a", 11.0)])
    r = _gate(p)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_gate_fails_on_regression(tmp_path):
    p = tmp_path / "traj.jsonl"
    _write(p, [_entry("a", 10.0), _entry("a", 12.0)])
    r = _gate(p)
    assert r.returncode == 1
    assert "REGRESSED" in r.stdout and "BENCH GATE FAILED" in r.stderr


def test_gate_compares_last_two_only(tmp_path):
    """A recovered regression does not keep failing the gate."""
    p = tmp_path / "traj.jsonl"
    _write(p, [_entry("a", 10.0), _entry("a", 20.0), _entry("a", 20.5)])
    assert _gate(p).returncode == 0


def test_gate_ignores_cross_platform_series(tmp_path):
    p = tmp_path / "traj.jsonl"
    _write(p, [_entry("a", 2.0, platform="tpu"), _entry("a", 50.0)])
    r = _gate(p)
    assert r.returncode == 0  # different platforms: no comparison


def test_gate_ratchets_supersteps_p50(tmp_path):
    """Series carrying supersteps_p50 ratchet it alongside latency: a
    warm-start price war creeping back (10 → 600 supersteps) fails the
    gate even when the idle-CPU wall clock stayed flat."""
    p = tmp_path / "traj.jsonl"
    _write(p, [
        _entry("churn", 10.0, supersteps_p50=10),
        _entry("churn", 10.0, supersteps_p50=600),
    ])
    r = _gate(p)
    assert r.returncode == 1
    assert "supersteps_p50" in r.stderr and "price war" in r.stderr


def test_gate_supersteps_slack_absorbs_quantization(tmp_path):
    """Small integer jitter near the healthy ~10 band is quantization,
    not regression: +25% relative alone (10 → 13) must pass — the
    absolute slack gates it out."""
    p = tmp_path / "traj.jsonl"
    _write(p, [
        _entry("churn", 10.0, supersteps_p50=10),
        _entry("churn", 10.0, supersteps_p50=13),
    ])
    assert _gate(p).returncode == 0


def test_gate_supersteps_absent_is_not_gated(tmp_path):
    """A series without the field (non-churn configs) never trips the
    supersteps ratchet, and a series that only just gained it has no
    baseline to compare against."""
    p = tmp_path / "traj.jsonl"
    _write(p, [
        _entry("a", 10.0),
        _entry("a", 10.5, supersteps_p50=9),
    ])
    assert _gate(p).returncode == 0


def test_gate_single_entry_series_passes(tmp_path):
    p = tmp_path / "traj.jsonl"
    _write(p, [_entry("a", 10.0), _entry("b", 5.0)])
    assert _gate(p).returncode == 0


def test_gate_rejects_bad_json_line(tmp_path):
    p = tmp_path / "traj.jsonl"
    p.write_text('{"config": "a"}\nnot json\n')
    r = _gate(p)
    assert r.returncode != 0


def test_entry_from_record_normalizes():
    rec = {
        "metric": "p50 ... backend=jax/cpu",
        "value": 12.5,
        "vs_baseline": 0.8,
        "config": "10kx1k",
        "detail": {"supersteps_p50": 7, "supersteps_max": 40},
    }
    e = entry_from_record(rec)
    assert e["config"] == "10kx1k" and e["platform"] == "cpu"
    assert e["p50_ms"] == 12.5 and e["supersteps_p50"] == 7
    assert "utc" in e and "commit" in e


def test_entry_platform_comes_from_the_device_stamp():
    rec = {"metric": "p50 ... backend=device/cpu", "value": 1.0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert entry_from_record(rec, config="x")["platform"] == "tpu"


def test_mesh_shape_is_part_of_the_series_key():
    """A 2-dev CPU reading must never baseline (or gate) an 8-dev
    series: entries with different mesh_devices are different series,
    so a fast small-mesh run followed by a slower big-mesh run is NOT
    a regression (and vice versa can't mask one)."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = d + "/traj.jsonl"
        _write(p, [
            _entry("sharded", 10.0, mesh_devices=2),
            _entry("sharded", 50.0, mesh_devices=8),  # not a regression
        ])
        assert _gate(p).returncode == 0
        _write(p, [
            _entry("sharded", 10.0, mesh_devices=8),
            _entry("sharded", 50.0, mesh_devices=8),  # IS a regression
        ])
        r = _gate(p)
        assert r.returncode == 1 and "8dev" in r.stdout


def test_entry_from_record_lifts_mesh_devices():
    rec = {
        "metric": "p50 ... backend=sharded/cpu",
        "value": 5.0,
        "detail": {"mesh_devices": 8},
    }
    e = entry_from_record(rec, config="gtrace100k")
    assert e["mesh_devices"] == 8


def test_checked_in_trajectory_is_wellformed_and_gates_clean():
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_TRAJECTORY.jsonl")
    entries = load_trajectory(path)
    assert entries, "BENCH_TRAJECTORY.jsonl must not be empty"
    for e in entries:
        assert e.get("config") and e.get("p50_ms") is not None
    assert _gate(path).returncode == 0, "checked-in trajectory must gate clean"
