"""Chaos harness + hardened control loop: seeded fault-schedule
determinism, the solver degradation ladder (configured → jax → cpu_ref
→ NOOP), NaN'd-cost rejection, the closed-vs-outage loop fix, dropped
binding POSTs re-surfacing, and a short in-process chaos soak with
fault accounting and cross-run determinism."""

import threading
import time

import numpy as np
import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
from ksched_tpu.runtime import (
    ChaosBackendError,
    ChaosClusterAPI,
    ChaosPolicy,
    DegradingSolver,
    FaultInjector,
    LadderExhausted,
    RoundTracer,
    build_degradation_ladder,
)
from ksched_tpu.solver.base import FlowResult, FlowSolver
from ksched_tpu.solver.cpu_ref import ReferenceSolver

# -- injector determinism --------------------------------------------------


def _drive(injector, rounds=64):
    log = []
    for r in range(rounds):
        injector.begin_round(r)
        log.append((
            injector.outage_active(),
            injector.drop_binding(),
            injector.solver_fault(0),
            injector.machine_silent(7),
            injector.http_fault("bind"),
        ))
    return log


def test_same_seed_same_fault_schedule():
    policy = ChaosPolicy(
        seed=11, api_outage_prob=0.2, binding_drop_prob=0.3,
        solver_fault_prob=0.25, machine_flap_prob=0.15,
        http_error_prob=0.1, http_hang_prob=0.05, http_latency_prob=0.1,
    )
    a, b = FaultInjector(policy), FaultInjector(policy)
    assert _drive(a) == _drive(b)
    assert dict(a.counters) == dict(b.counters)
    assert sum(a.counters.values()) > 0  # the schedule actually fired


def test_different_seeds_differ():
    pol = dict(api_outage_prob=0.2, binding_drop_prob=0.3, solver_fault_prob=0.25)
    a = FaultInjector(ChaosPolicy(seed=1, **pol))
    b = FaultInjector(ChaosPolicy(seed=2, **pol))
    assert _drive(a) != _drive(b)


def test_domain_streams_independent():
    """Consuming one fault domain at a different rate must not perturb
    another domain's schedule (per-domain RNG streams)."""
    policy = ChaosPolicy(seed=5, binding_drop_prob=0.3, solver_fault_prob=0.25)
    a, b = FaultInjector(policy), FaultInjector(policy)
    sched_a, sched_b = [], []
    for r in range(64):
        a.begin_round(r)
        b.begin_round(r)
        a.drop_binding()  # a consumes the binding stream faster
        a.drop_binding()
        b.drop_binding()
        sched_a.append(a.solver_fault(0))
        sched_b.append(b.solver_fault(0))
    assert sched_a == sched_b


def test_quiesce_stops_faults():
    inj = FaultInjector(ChaosPolicy(
        seed=0, api_outage_prob=1.0, binding_drop_prob=1.0, solver_fault_prob=1.0,
    ))
    inj.begin_round(0)
    assert inj.outage_active() and inj.drop_binding()
    inj.quiesce()
    inj.begin_round(1)
    assert not inj.outage_active()
    assert not inj.drop_binding()
    assert inj.solver_fault(0) is None


def test_policy_rejects_unknown_fault_kind():
    with pytest.raises(ValueError, match="unknown solver fault kinds"):
        ChaosPolicy(solver_fault_kinds=("segfault",))


# -- degradation ladder ----------------------------------------------------


class _AlwaysFails(FlowSolver):
    def __init__(self, exc):
        self.exc = exc
        self.calls = 0

    def solve(self, problem):
        self.calls += 1
        raise self.exc


def _tiny_cluster(backend, **kw):
    from ksched_tpu.drivers import add_job, build_cluster

    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, pus_per_core=2, max_tasks_per_pu=1, backend=backend, **kw
    )
    add_job(sched, jmap, tmap, num_tasks=3)
    return sched


def test_ladder_steps_down_on_failure():
    failing = _AlwaysFails(RuntimeError("did not converge"))
    ladder = DegradingSolver([("broken", failing), ("cpu_ref", ReferenceSolver())])
    sched = _tiny_cluster(ladder)
    with pytest.warns(RuntimeWarning, match="degrading to 'cpu_ref'"):
        n, _ = sched.schedule_all_jobs()
    assert n == 3  # the fallback rung produced the round
    assert failing.calls == 1
    assert ladder.last_rung == 1 and ladder.last_rung_name == "cpu_ref"
    assert ladder.degradations_total == 1


def test_ladder_exhausted_raises_with_all_failures():
    ladder = DegradingSolver([
        ("a", _AlwaysFails(RuntimeError("x"))),
        ("b", _AlwaysFails(OverflowError("y"))),
    ])
    sched = _tiny_cluster(ladder)
    with pytest.warns(RuntimeWarning):
        with pytest.raises(LadderExhausted) as ei:
            sched.schedule_all_jobs()
    assert [name for name, _ in ei.value.failures] == ["a", "b"]


def test_ladder_does_not_absorb_nondegradable_errors():
    ladder = DegradingSolver([
        ("buggy", _AlwaysFails(TypeError("bug"))),
        ("cpu_ref", ReferenceSolver()),
    ])
    sched = _tiny_cluster(ladder)
    with pytest.raises(TypeError):
        sched.schedule_all_jobs()


def test_build_ladder_dedups_configured_rung():
    names = build_degradation_ladder(ReferenceSolver(), "ref").rung_names()
    assert names == ["ref", "jax"]  # no second cpu_ref rung
    lazy = build_degradation_ladder(_AlwaysFails(RuntimeError("x")), "custom")
    assert lazy.rung_names() == ["custom", "jax", "cpu_ref"]


def test_injected_solver_faults_fire_through_ladder():
    inj = FaultInjector(ChaosPolicy(seed=0, solver_fault_prob=1.0,
                                    solver_fault_kinds=("exception",)))
    oracle = ReferenceSolver()
    ladder = DegradingSolver([("primary", oracle), ("cpu_ref", ReferenceSolver())],
                             injector=inj)
    sched = _tiny_cluster(ladder)
    inj.begin_round(0)
    with pytest.warns(RuntimeWarning, match="injected backend exception"):
        n, _ = sched.schedule_all_jobs()
    assert n == 3  # rung 1 (unfaulted) carried the round
    assert inj.counters["solver_exception"] == 1


def test_nan_cost_rejected_by_backends():
    """Satellite hardening: NaN'd cost inputs must be *rejected* by
    EVERY selectable backend (shared solver/base.check_finite_costs),
    not cast into garbage int costs — a rung that 'succeeds' on a
    poisoned cost model would commit nonsense placements instead of
    triggering the degradation ladder."""
    from ksched_tpu.runtime.chaos import poison_costs
    from ksched_tpu.solver.jax_solver import JaxSolver
    from ksched_tpu.solver.native import NativeSolver
    from ksched_tpu.solver.placement import PlacementSolver

    sched = _tiny_cluster(ReferenceSolver())
    sched.gm.compute_topology_statistics(sched.gm.sink_node)
    jds = [jd for jd in sched.jobs_to_schedule.values()
           if sched._compute_runnable_tasks_for_job(jd)]
    sched.gm.add_or_update_job_nodes(jds)
    ps = PlacementSolver(sched.gm, ReferenceSolver())
    ps.state.full_build(sched.gm.cm.graph)
    ps.state.set_excess(sched.gm.sink_node.id, sched.gm.sink_node.excess)
    problem = ps.state.problem()
    bad = poison_costs(problem)
    assert bad.cost.dtype.kind == "f" and np.isnan(bad.cost).any()
    for backend in (ReferenceSolver(), JaxSolver(), NativeSolver()):
        with pytest.raises(ValueError, match="non-finite arc costs"):
            backend.solve(bad)
    # the clean problem still solves (the check has no false positives)
    assert ReferenceSolver().solve(problem).flow.sum() >= 0


# -- service NOOP round + loop hardening -----------------------------------


def _service(api=None, **kw):
    api = api or SyntheticClusterAPI()
    svc = SchedulerService(api, max_tasks_per_pu=1, **kw)
    svc.init_topology(fake_machines=2, pus_per_core=2)
    return api, svc


def test_noop_round_keeps_previous_assignments():
    """When every rung fails, the round is a NOOP: previous placements
    survive untouched, nothing crashes, and the next (healthy) round
    schedules the backlog."""
    inj = FaultInjector(ChaosPolicy(seed=0, solver_fault_kinds=("nonconverge",)))
    api, svc = _service(injector=inj, tracer=RoundTracer())
    svc.run_round([PodEvent(pod_id="p0"), PodEvent(pod_id="p1")])
    before = dict(svc.scheduler.task_bindings)
    assert len(before) == 2 and len(api.bindings()) == 2

    # force an all-rungs outage for one round
    inj._solver_plan = {0: "nonconverge"}
    inj._solver_plan_all = True
    with pytest.warns(RuntimeWarning, match="NOOP round"):
        bound = svc.run_round([PodEvent(pod_id="p2"), PodEvent(pod_id="p3")])
    assert bound == 0
    assert svc.noop_rounds == 1
    assert svc.backlog_dirty  # the kept backlog flags the next idle poll
    assert dict(svc.scheduler.task_bindings) == before  # assignments kept
    rec = svc.tracer.records[-1]
    assert rec.noop_round and rec.solver_rung == -1
    assert rec.faults_injected.get("solver_nonconverge", 0) >= 1

    # ladder heals next round: backlog (p2, p3) schedules
    inj._solver_plan = {}
    inj._solver_plan_all = False
    bound = svc.run_round([])
    assert bound == 2
    assert len(svc.scheduler.task_bindings) == 4
    assert not svc.backlog_dirty  # a clean full solve clears the flag


def test_run_survives_transient_outage_and_exits_on_close():
    """Satellite regression: an empty batch with the channel OPEN (a
    transient API-server outage longer than the batch timeout) must not
    exit the scheduler; close() must."""
    api, svc = _service()
    done = threading.Event()

    def drive():
        svc.run(pod_batch_timeout_s=0.05, max_rounds=1)
        done.set()

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    # several batch-timeout windows of silence: the loop must idle, not exit
    time.sleep(0.3)
    assert not done.is_set(), "scheduler exited on a transient empty batch"
    api.submit_pod(PodEvent(pod_id="late_pod"))
    t.join(timeout=5)
    assert done.is_set()
    assert len(api.bindings()) == 1  # the late pod was scheduled

    # and with the channel CLOSED, run() exits promptly without a pod
    api2, svc2 = _service()
    api2.close()
    t0 = time.monotonic()
    svc2.run(pod_batch_timeout_s=0.05, max_rounds=5)
    assert time.monotonic() - t0 < 2.0


def test_idle_polls_are_sweep_only_until_backlog_dirty():
    """Regression: a quiet-but-open channel must not cost a full graph
    rebuild + MCMF solve per batch timeout — idle polls run only the
    heartbeat sweep while the backlog is clean; the solver runs again
    when a real batch (or dirty backlog) arrives."""
    api, svc = _service()
    solves = []
    orig = svc.run_once

    def counting(pods):
        solves.append(len(pods))
        return orig(pods)

    svc.run_once = counting
    t = threading.Thread(
        target=svc.run,
        kwargs=dict(pod_batch_timeout_s=0.02, max_rounds=1),
        daemon=True,
    )
    t.start()
    time.sleep(0.3)  # many idle polls' worth of silence
    assert solves == []  # sweep-only: no solver work while quiet
    api.submit_pod(PodEvent(pod_id="p0"))
    t.join(timeout=5)
    assert solves == [1]  # the real batch solved exactly once
    assert len(api.bindings()) == 1


def test_run_advances_injector_rounds_on_idle_polls():
    """Regression: idle (empty-batch) iterations must advance the fault
    injector's round clock — a stale index would re-roll the same
    round's draws on every poll and freeze outage countdowns for the
    whole outage they are meant to time out."""
    inj = FaultInjector(ChaosPolicy(seed=0))
    api, svc = _service(injector=inj)
    t = threading.Thread(
        target=svc.run,
        kwargs=dict(pod_batch_timeout_s=0.02, max_rounds=1),
        daemon=True,
    )
    t.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and inj.round_index < 3:
        time.sleep(0.02)
    assert inj.round_index >= 3  # each idle poll consumed one round
    api.submit_pod(PodEvent(pod_id="p0"))
    t.join(timeout=5)
    assert not t.is_alive()


def test_cluster_api_default_poll_pair_agrees_on_close():
    """Regression: a minimal ClusterAPI subclass overriding neither
    poll_pod_batch nor is_closed keeps the blocking contract's
    empty==closed — otherwise run() would busy-spin forever on instant
    empty batches after close."""
    from ksched_tpu.cluster.api import ClusterAPI

    class Minimal(ClusterAPI):
        def get_pod_batch(self, timeout_s):
            return []  # blocking contract: [] only on close

        def get_node_batch(self, timeout_s):
            return []

        def assign_bindings(self, bindings):
            pass

    api = Minimal()
    assert not api.is_closed()  # open until a poll says otherwise
    assert api.poll_pod_batch(0.01) == []
    assert api.is_closed()  # the default pair agrees: the loop exits


def test_dropped_binding_resurfaces_and_reposts():
    """A dropped binding POST re-surfaces the pod; the service re-posts
    on a later round and the binding eventually lands."""
    inj = FaultInjector(ChaosPolicy(seed=0, binding_drop_prob=1.0))
    chaos = ChaosClusterAPI(SyntheticClusterAPI(), inj)
    _, svc = _service(api=chaos, injector=inj, tracer=RoundTracer())
    svc.run_round([PodEvent(pod_id="p0")])
    assert chaos.bindings() == {}  # POST dropped
    assert inj.counters["binding_drop"] == 1
    # scheduler-side the task IS placed; the re-post must not re-place
    assert len(svc.scheduler.task_bindings) == 1

    inj.quiesce()  # next POST goes through
    pods = chaos.poll_pod_batch(0.01)
    assert [p.pod_id for p in pods] == ["p0"]  # re-surfaced
    svc.run_round(pods)
    assert len(chaos.bindings()) == 1
    assert len(svc.scheduler.task_bindings) == 1  # still exactly one task


def test_outage_holds_events_for_later_delivery():
    inj = FaultInjector(ChaosPolicy(seed=0))
    chaos = ChaosClusterAPI(SyntheticClusterAPI(), inj)
    chaos.submit_pod(PodEvent(pod_id="p0"))
    inj._outage_rounds_left = 2
    assert chaos.poll_pod_batch(0.01) == []  # suppressed, not dropped
    assert inj.counters["api_outage_round"] == 1
    inj._outage_rounds_left = 0
    assert [p.pod_id for p in chaos.poll_pod_batch(0.05)] == ["p0"]


# -- the short chaos soak (the CI smoke, in-process) -----------------------


@pytest.mark.parametrize("seed", [7])
def test_chaos_soak_deterministic_with_fault_accounting(seed):
    """A short fixed-seed chaos soak: zero crashes, invariants clean,
    every injected fault accounted for in RoundRecord counters (the
    accounting assert lives inside run_chaos_soak), and final
    placements identical across two runs with the same seed."""
    import argparse

    from tools.soak import run_chaos_soak

    args = argparse.Namespace(
        rounds=48, machines=4, slots=4, seed=seed, chunk=24,
        chaos_backend="ref", chaos_restore_every=20,
    )
    a = run_chaos_soak(args, log=lambda *a, **k: None)
    b = run_chaos_soak(args, log=lambda *a, **k: None)
    assert a["placements"] == b["placements"]
    assert a["all_bindings"] == b["all_bindings"]
    assert a["fault_totals"] == b["fault_totals"]
    assert a["restores"] >= 1  # mid-soak kill-and-restore actually ran
    assert sum(a["fault_totals"].values()) > 0  # chaos actually happened
