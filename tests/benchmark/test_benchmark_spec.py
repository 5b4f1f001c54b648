"""BENCHMARK.json and the files it names hold together, and the traffic
generator is a pure function of its seed."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import spec, traffic

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def argv_value(argv, flag):
    return argv[argv.index(flag) + 1]


def test_benchmark_json_has_exactly_the_contracts_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    assert all(os.path.isdir(os.path.join(spec.ROOT, p)) for p in BENCH["paths"])
    assert spec.check_names(BENCH) == []
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in BENCH["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}" and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_what_the_contract_asks(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] == cell.split(".")[0] and c.traffic["name"] == cell.split(".")[1]
    assert c.traffic["kind"] in traffic.KINDS
    assert c.config["reduced"] == [] and c.config["assumed"] and c.config["guarantees"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer and all(m["moves"] in e2e for m in c.per_layer)
    for m in c.per_layer:
        reader = importlib.import_module(f"benchmarks.readers.{m['reader']}")
        assert callable(reader.read)
    # the fill plus one wave fits the pod channel (submit_pod blocks on a full one)
    chan = int(argv_value(c.config["argv"], "--pod-chan-size"))
    assert chan >= c.config["resident_pods"] + c.config["wave_pods"]


def test_a_layer_metric_file_that_disagrees_with_benchmark_json_is_refused(tmp_path, monkeypatch):
    import shutil

    shutil.copytree(spec.HERE, tmp_path / "benchmarks")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    path = tmp_path / "benchmarks" / "layer_metrics" / "apply_ms.json"
    own = json.loads(path.read_text())
    own["unit"] = "us"
    path.write_text(json.dumps(own))
    monkeypatch.setattr(spec, "HERE", str(tmp_path / "benchmarks"))
    with pytest.raises(spec.SpecError, match="apply_ms.json says unit"):
        spec.load_cell(CELLS[0], root=str(tmp_path))
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no-such.cell")


def test_rehearsal_keeps_the_shapes_and_shrinks_the_scale():
    c = spec.load_cell("coco-50kx1k.waves").config
    r = spec.rehearsal_config(c)
    assert argv_value(r["argv"], "--num-machines") == "25"
    assert (r["resident_pods"], r["wave_pods"]) == (1250, 62)
    for flag in ("--pus-per-core", "--max-tasks-per-pu", "--cost-model", "--backend"):
        assert argv_value(r["argv"], flag) == argv_value(c["argv"], flag)
    assert c["resident_pods"] == 50000  # the configuration itself is not touched


# -- the generator ------------------------------------------------------------------


def _plan(cell, seed, seconds=5.0):
    c = spec.load_cell(cell)
    return traffic.build_plan(c.traffic, spec.rehearsal_config(c.config), seed, seconds)


@pytest.mark.parametrize("cell", ["coco-50kx1k.trickle", "coco-50kx1k.waves"])
def test_the_generator_is_a_pure_function_of_the_seed(cell):
    a, b, other = _plan(cell, 7), _plan(cell, 7), _plan(cell, 8)
    assert a.resident == b.resident and a.victims == b.victims and a.closing == b.closing
    assert a.wave(3) == b.wave(3) if a.kind == "closed_waves" else True
    assert a.victims != other.victims
    if a.kind == "open_poisson":
        assert np.array_equal(a.arrival_offsets_s, b.arrival_offsets_s)
        assert np.array_equal(a.arrival_classes, b.arrival_classes)
        assert not np.array_equal(a.arrival_offsets_s, other.arrival_offsets_s)
    else:
        assert a.wave(3) != a.wave(4) and a.wave(3) != other.wave(3)


def test_open_poisson_arrives_at_its_rate_and_covers_warmup_window_and_drain():
    p = _plan("trivial-10kx1k.trickle", 3, seconds=30.0)
    gaps = np.diff(p.arrival_offsets_s)
    assert (gaps > 0).all() and np.mean(gaps) == pytest.approx(1 / p.rate_per_s, rel=0.05)
    assert p.arrival_offsets_s[-1] > p.warmup_s * 4 + 30.0 + 5.0
    assert sorted(p.victims) == sorted(pod for pod, _c in p.resident)
    assert len(p.closing) == traffic.CLOSING_PODS


def test_the_class_sweep_holds_one_to_k_minus_one_distinct_classes():
    p = _plan("coco-50kx1k.trickle", 1)
    assert [len({c for _p, c in burst}) for burst in p.class_sweep] == [1, 2, 3]
    assert _plan("trivial-10kx1k.trickle", 1).class_sweep == []


def test_wave_size_is_a_number_the_configs_own_or_a_share():
    config = {"wave_pods": 1000, "resident_pods": 10000}
    assert traffic.wave_size({"wave_pods": "config"}, config) == 1000
    assert traffic.wave_size({"wave_pods": 64}, config) == 64
    assert traffic.wave_size({"wave_share": 0.05}, config) == 500
    with pytest.raises(ValueError, match="not one of"):
        traffic.build_plan({"kind": "sawtooth"}, config, 0, 1.0)
