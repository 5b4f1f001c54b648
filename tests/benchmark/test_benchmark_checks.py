"""`correct` is one check for every guarantee the configuration states:
every configuration's every guarantee resolves to `checks/<guarantee>.py`,
a configuration that states one without a module (or none) is refused
before anything is built, and each check holds on a clean hand-built run
and finds the fault planted in one."""

import importlib
import json
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import correct, reference_antiaffinity, spec, traffic

BENCH = spec.load_benchmark()
CONFIGS = {c["name"]: c["file"] for c in BENCH["configs"]}
GUARANTEES = sorted(
    (name, key)
    for name, file in CONFIGS.items()
    for key in json.load(open(os.path.join(spec.ROOT, file)))["guarantees"]
)

# -- a hand-built run -------------------------------------------------------------
#
# Three pods of two workloads on two machines of two slots, nothing held:
# all three are placed through their EC (cost 2 each), so the closing
# round's optimum is 6 (reference_antiaffinity's equations).
CLOSING = reference_antiaffinity.build_problem([("a", 0), ("b", 0), ("c", 1)], [2, 2], [])
CLOSING_OBJECTIVE = 6
CONFIG = {
    "resident_pods": 8, "task_classes": 2, "wave_pods": 4,
    "guarantees": {k: "" for k in ("binding", "capacity", "answer", "resident", "anti_affinity")},
}
TRICKLE = {"kind": "open_poisson", "rate_per_s": 100.0, "completions_per_arrival": 1,
           "warmup_s": 0.1}
WAVES = {"kind": "closed_waves", "wave_pods": "config", "warmup_waves": 1}


def _mirror(problem=CLOSING):
    return SimpleNamespace(
        d_excess=problem.excess.astype(np.int32), d_src=problem.src.copy(),
        d_dst=problem.dst.copy(), d_cap=problem.cap.copy(),
        d_cost=problem.cost.astype(np.int32), version=40,
        last_upload_kind="delta", last_plan_kind="delta",
    )


def _svc(objective=CLOSING_OBJECTIVE, noop=0, degradations=0, mirror=None):
    solver = SimpleNamespace(
        last_result=SimpleNamespace(objective=objective),
        state=SimpleNamespace(problem=lambda: CLOSING), resident=mirror,
    )
    return SimpleNamespace(
        noop_rounds=noop, ladder=SimpleNamespace(degradations_total=degradations),
        scheduler=SimpleNamespace(solver=solver),
    )


def _clean_log(plan, pods):
    """Each pod on the node numbered by its rank within its workload: a
    node holds one pod a workload, two in all."""
    rank = {}
    log = []
    for t, (pod, g) in enumerate(pods):
        rank[g] = rank.get(g, -1) + 1
        log.append(("bind", pod, f"n{rank[g]}", float(t)))
    return log


def _ctx(mix=TRICKLE, **over):
    plan = traffic.build_plan(mix, CONFIG, 11, 1.0)
    pods = plan.resident + [plan.arrival(i) for i in range(4)] if mix is TRICKLE else (
        plan.resident + plan.wave(0) + plan.wave(2)
    )
    log = _clean_log(plan, pods)
    first = plan.resident[0][0]
    log.append(("done", first, "", 100.0))
    fields = dict(
        config=CONFIG, plan=plan, make_pod=spec.pod_maker(spec.DEFAULT_PODS, CONFIG, 11),
        svc=_svc(mirror=_mirror()),
        svc_args=SimpleNamespace(cores_per_machine=1, pus_per_core=1, max_tasks_per_pu=2),
        due={pod: (0.0, 0.0) for pod, _g in pods[8:]},
        bind_stamps={pod: [1.0] for pod, _g in pods}, log=log,
        completions_refused=0, compiles_in_window=0,
    )
    fields.update(over)
    return correct.Context(**fields)


def _check(name, ctx):
    return importlib.import_module(f"benchmarks.checks.{name}").check(ctx)


# -- the configurations and their modules -----------------------------------------


@pytest.mark.parametrize("config, guarantee", GUARANTEES)
def test_every_guarantee_a_configuration_states_resolves_to_a_check(config, guarantee):
    assert os.path.isfile(os.path.join(spec.HERE, "checks", guarantee + ".py"))
    module = importlib.import_module(f"benchmarks.checks.{guarantee}")
    assert callable(module.check) and guarantee in module.__doc__.split(":")[0]


def test_the_guarantees_stated_today_are_the_check_modules_and_each_cell_loads():
    """What the configurations state is what `checks/` holds a module for,
    no more and no less: an equality a new guarantee, which comes with its
    module, keeps true."""
    modules = {
        name[:-3] for name in os.listdir(os.path.join(spec.HERE, "checks"))
        if name.endswith(".py") and not name.startswith("_")
    }
    assert {g for _c, g in GUARANTEES} == modules
    assert modules >= {"binding", "capacity", "answer", "resident", "anti_affinity"}
    for w in BENCH["workloads"]:
        assert spec.load_cell(w["name"]).config["guarantees"]


def _root_with(tmp_path, monkeypatch, edit):
    """A copy of the benchmark whose k8s-5000-antiaffinity file `edit` changed."""
    shutil.copytree(spec.HERE, tmp_path / "benchmarks")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    path = tmp_path / CONFIGS["k8s-5000-antiaffinity"]
    config = json.loads(path.read_text())
    edit(config)
    path.write_text(json.dumps(config))
    monkeypatch.setattr(spec, "HERE", str(tmp_path / "benchmarks"))
    return str(tmp_path)


def test_a_guarantee_without_a_module_is_refused_before_anything_is_built(tmp_path, monkeypatch):
    root = _root_with(
        tmp_path, monkeypatch, lambda c: c["guarantees"].update(no_such_guarantee="never checked")
    )
    with pytest.raises(spec.SpecError, match="no_such_guarantee.*checks/no_such_guarantee.py"):
        spec.load_cell("k8s-5000-antiaffinity.trickle", root=root)
    # the other configurations of that root are as they were
    assert spec.load_cell("coco-50kx1k.trickle", root=root).config["guarantees"]


@pytest.mark.parametrize("edit", [
    lambda c: c.pop("guarantees"), lambda c: c.update(guarantees={}),
    lambda c: c.update(guarantees=["binding"]),
], ids=["absent", "empty", "a-list"])
def test_a_configuration_that_states_no_guarantee_is_refused(tmp_path, monkeypatch, edit):
    root = _root_with(tmp_path, monkeypatch, edit)
    with pytest.raises(spec.SpecError, match="states no `guarantees`"):
        spec.load_cell("k8s-5000-antiaffinity.trickle", root=root)


def test_a_new_guarantee_is_one_new_file(tmp_path, monkeypatch):
    root = _root_with(
        tmp_path, monkeypatch, lambda c: c["guarantees"].update(binding_after_eviction="...")
    )
    (tmp_path / "benchmarks" / "checks" / "binding_after_eviction.py").write_text(
        "def check(ctx):\n    return []\n"
    )
    cell = spec.load_cell("k8s-5000-antiaffinity.trickle", root=root)
    assert list(cell.config["guarantees"])[-1] == "binding_after_eviction"


def test_no_name_of_a_check_appears_in_run_py():
    """run.py imports no check and calls none by name: it hands the
    context to `correct.run_checks`, which goes by the configuration."""
    source = open(os.path.join(spec.HERE, "run.py")).read()
    for name in ("check_bindings", "check_capacity", "check_service", "check_closing_objective",
                 "anti_affinity", "benchmarks.checks", "checks/", "checks import"):
        assert name not in source, name
    assert source.count("correct.run_checks(ctx)") == 1
    runner = open(os.path.join(spec.HERE, "correct.py")).read()
    assert 'import_module(f"benchmarks.checks.{name}")' in runner


# -- the conjunction -----------------------------------------------------------------


def test_run_checks_runs_the_stated_guarantees_in_the_files_order_and_no_other():
    ctx = _ctx()
    faults, names = correct.run_checks(ctx)
    assert faults == [] and names == list(CONFIG["guarantees"])
    assert list(ctx.facts["check_seconds"]) == names
    assert ctx.facts["closing"] == {
        "objective": CLOSING_OBJECTIVE, "native_objective": CLOSING_OBJECTIVE,
    }
    fewer = {**CONFIG, "guarantees": {"capacity": "", "binding": ""}}
    # a second Binding: held against `binding`, and against nothing where it is not stated
    pod = ctx.plan.resident[0][0]
    twice = _ctx(config=fewer, bind_stamps={**ctx.bind_stamps, pod: [1.0, 2.0]})
    faults, names = correct.run_checks(twice)
    assert names == ["capacity", "binding"] and len(faults) == 1
    none = _ctx(config={**CONFIG, "guarantees": {"capacity": ""}}, bind_stamps=twice.bind_stamps)
    assert correct.run_checks(none) == ([], ["capacity"])
    with pytest.raises(ModuleNotFoundError):
        correct.run_checks(_ctx(config={**CONFIG, "guarantees": {"no_such_guarantee": ""}}))


@pytest.mark.parametrize("mix", [TRICKLE, WAVES], ids=["trickle", "waves"])
def test_pod_classes_knows_every_pod_of_the_run(mix):
    ctx = _ctx(mix)
    classes = correct.pod_classes(ctx.plan, ctx.log)
    assert {pod for _k, pod, _n, _t in ctx.log} <= set(classes)
    assert set(dict(ctx.plan.closing)) <= set(classes)
    for burst in ctx.plan.class_sweep:
        assert set(dict(burst)) <= set(classes)
    if mix is WAVES:  # the waves the log names, and no wave it does not
        assert classes["w2_3"] == dict(ctx.plan.wave(2))["w2_3"] and "w1_0" not in classes


# -- each check: held on the clean run, tripped by the planted fault ------------------


def test_a_pod_without_a_binding_and_a_pod_bound_twice_are_faults():
    ctx = _ctx()
    assert _check("binding", ctx) == []
    assert ctx.facts["binding"] == {"due": 4, "unbound": 0, "bound": 12, "bound_twice": 0}
    lost = next(iter(ctx.due))
    (missing,) = _check("binding", _ctx(
        bind_stamps={p: s for p, s in ctx.bind_stamps.items() if p != lost}
    ))
    assert "got no Binding" in missing and lost in missing
    (twice,) = _check("binding", _ctx(bind_stamps={**ctx.bind_stamps, "r3": [1.0, 2.0]}))
    assert "more than one Binding" in twice and "r3" in twice


def test_replay_trips_on_a_node_over_capacity_and_not_under_it():
    ctx = _ctx()
    assert _check("capacity", ctx) == []
    assert ctx.facts["capacity"]["peak_node_load"] <= ctx.facts["capacity"]["node_capacity"] == 2
    log = [("bind", "a", "n0", 1.0), ("bind", "b", "n0", 1.0), ("done", "a", "", 2.0),
           ("bind", "c", "n0", 3.0)]
    assert _check("capacity", _ctx(log=log)) == []
    (fault,) = _check("capacity", _ctx(log=log + [("bind", "d", "n0", 4.0)]))
    assert "held 3 pods, capacity 2" in fault
    (fault,) = _check("capacity", _ctx(log=[("done", "z", "", 1.0)]))
    assert "without a Binding" in fault


@pytest.mark.parametrize("over, word", [
    (dict(svc=_svc(noop=1)), "NOOP"),
    (dict(svc=_svc(degradations=2)), "ladder"),
    (dict(compiles_in_window=3), "compiled inside the window"),
    (dict(completions_refused=2), "2 completions of pods that were not bound"),
    (dict(svc=_svc(objective=CLOSING_OBJECTIVE + 3)), "closing round objective 9 != native C++ 6"),
])
def test_a_noop_round_a_degradation_and_a_compile_in_the_window_are_faults(over, word):
    assert _check("answer", _ctx()) == []
    ctx = _ctx(**over)
    (fault,) = _check("answer", ctx)
    assert word in fault
    assert ctx.facts["closing"]["native_objective"] == CLOSING_OBJECTIVE


def test_answer_faults_where_no_round_was_solved():
    svc = _svc()
    svc.scheduler.solver.last_result = None
    ctx = _ctx(svc=svc)
    assert _check("answer", ctx) == ["no round was solved"] and ctx.facts["closing"] == {}


def test_two_pods_of_one_workload_on_a_node_is_a_fault_and_one_after_the_other_is_not():
    ctx = _ctx()
    assert _check("anti_affinity", ctx) == []
    assert ctx.facts["anti_affinity"]["replayed"] == len(ctx.log)
    assert ctx.facts["anti_affinity"]["workloads"] == 2
    (pod, g), node = ctx.plan.resident[1], ctx.log[1][2]
    twin = next(p for p, c in ctx.plan.closing if c == g)
    (fault,) = _check("anti_affinity", _ctx(log=ctx.log + [("bind", twin, node, 200.0)]))
    assert f"node {node} holds 2 pods of workload {g}" in fault and twin in fault
    after = ctx.log + [("done", pod, "", 150.0), ("bind", twin, node, 200.0)]
    assert _check("anti_affinity", _ctx(log=after)) == []
    # a pod of another workload may share the node
    other = next(p for p, c in ctx.plan.closing if c != g)
    shared = [e for e in ctx.log if e[2] != node or e[1] == pod]
    assert _check("anti_affinity", _ctx(log=shared + [("bind", other, node, 200.0)])) == []


def test_the_waves_of_a_closed_loop_are_held_to_the_rule_too():
    ctx = _ctx(WAVES)
    assert _check("anti_affinity", ctx) == []
    (pod, g), node = ctx.plan.wave(2)[0], next(e[2] for e in ctx.log if e[1] == "w2_0")
    twin = next(p for p, c in ctx.plan.wave(0) + ctx.plan.closing if c == g and p != pod)
    (fault,) = _check("anti_affinity", _ctx(WAVES, log=ctx.log + [("bind", twin, node, 200.0)]))
    assert f"node {node} holds 2 pods of workload {g}" in fault


def test_a_mirror_that_differs_in_one_entry_and_a_service_without_one_are_faults():
    ctx = _ctx()
    assert _check("resident", ctx) == []
    n, m = CLOSING.num_nodes, CLOSING.num_arcs
    assert ctx.facts["resident"] == {
        "mirror_entries": n + 4 * m, "differ": 0, "limit": 0, "refreshes": 40,
        "closing_upload": "delta", "closing_plan": "delta",
    }
    drifted = _mirror()
    drifted.d_cap[5] += 1
    ctx = _ctx(svc=_svc(mirror=drifted))
    (fault,) = _check("resident", ctx)
    assert "`cap` differs from the host's in 1 of" in fault and "first: [5]" in fault
    assert ctx.facts["resident"]["differ"] == 1
    shorter = _mirror()
    shorter.d_excess = shorter.d_excess[:-1]
    (fault,) = _check("resident", _ctx(svc=_svc(mirror=shorter)))
    assert "`excess` has shape" in fault
    (fault,) = _check("resident", _ctx(svc=_svc(mirror=None)))
    assert "keeps no arrays on the device" in fault
    # a closing round that went up whole is said, and is no fault (checks/resident.py)
    whole = _mirror()
    whole.last_plan_kind = "rebuild"
    ctx = _ctx(svc=_svc(mirror=whole))
    assert _check("resident", ctx) == [] and ctx.facts["resident"]["closing_plan"] == "rebuild"
