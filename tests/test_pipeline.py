"""Pipelined graph-path rounds: solve dispatch overlapping host build.

The reference's daemon-mode solver (placement/solver.go:60-90) crunches
DIMACS in a subprocess while the Go process is free; the TPU rebuild
gets the same overlap from asynchronous dispatch:
schedule_all_jobs_async() exports the journal snapshot and dispatches
the device solve, the host keeps ingesting ARRIVALS (their mutations
journal for the next round — the reference's pod-batching pattern), and
finish_scheduling() synchronizes, decodes, and applies deltas.
"""

import numpy as np
import pytest

from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.utils import seed_rng


def _cluster(backend=None):
    seed_rng(7)
    return build_cluster(
        num_machines=3, num_cores=1, pus_per_core=2, max_tasks_per_pu=1,
        backend=backend,
    )


@pytest.mark.parametrize("backend_factory", [None, JaxSolver])
def test_pipelined_round_matches_sync(backend_factory):
    """Round-for-round parity: async dispatch + finish produces the
    same bindings as the synchronous path on the same scenario."""
    outs = []
    for mode in ("sync", "async"):
        backend = backend_factory() if backend_factory else None
        sched, rmap, jmap, tmap, root = _cluster(backend)
        add_job(sched, jmap, tmap, num_tasks=4)
        if mode == "sync":
            n1, _ = sched.schedule_all_jobs()
        else:
            token = sched.schedule_all_jobs_async()
            assert token is not None
            n1, _ = sched.finish_scheduling()
        add_job(sched, jmap, tmap, num_tasks=3)
        if mode == "sync":
            n2, _ = sched.schedule_all_jobs()
        else:
            token = sched.schedule_all_jobs_async()
            n2, _ = sched.finish_scheduling()
        outs.append((n1, n2, len(sched.get_task_bindings())))
    assert outs[0] == outs[1], outs


def test_arrivals_overlap_in_flight_round():
    """Jobs added while a round is in flight are NOT placed by it (the
    solve works on the dispatched snapshot) but are picked up by the
    next round — the batching semantics of the reference's pod loop."""
    sched, rmap, jmap, tmap, root = _cluster()
    add_job(sched, jmap, tmap, num_tasks=2)
    token = sched.schedule_all_jobs_async()
    # overlap: a new job arrives while the solve is in flight
    add_job(sched, jmap, tmap, num_tasks=2)
    n1, _ = sched.finish_scheduling()
    assert n1 == 2  # only the snapshot's tasks
    n2, _ = sched.schedule_all_jobs()
    assert n2 == 2  # the overlapped arrivals place next round
    assert len(sched.get_task_bindings()) == 4


def test_mutating_events_fenced_while_in_flight():
    sched, rmap, jmap, tmap, root = _cluster()
    job = add_job(sched, jmap, tmap, num_tasks=2)
    n, _ = sched.schedule_all_jobs()
    assert n == 2
    add_job(sched, jmap, tmap, num_tasks=1)
    token = sched.schedule_all_jobs_async()
    (tid, td) = next(iter(
        (t, d) for t, d in tmap.items() if d.job_id == str(job)
    ))
    with pytest.raises(RuntimeError, match="in flight"):
        sched.handle_task_completion(td)
    with pytest.raises(RuntimeError, match="in flight"):
        sched.schedule_jobs([])
    sched.finish_scheduling()
    # after the round closes, the event proceeds normally
    sched.handle_task_completion(td)


def test_async_empty_round_returns_none():
    sched, rmap, jmap, tmap, root = _cluster()
    assert sched.schedule_all_jobs_async() is None
    with pytest.raises(RuntimeError, match="no scheduling round"):
        sched.finish_scheduling()


def test_placement_and_migration_fenced_while_in_flight():
    """The extended in-flight guard: external placement/migration
    events raise while a pipelined round is in flight (the dispatched
    snapshot still maps those tasks); delta application still works
    because it runs after the latch clears."""
    sched, rmap, jmap, tmap, root = _cluster()
    add_job(sched, jmap, tmap, num_tasks=2)
    n, _ = sched.schedule_all_jobs()
    assert n == 2
    add_job(sched, jmap, tmap, num_tasks=1)
    sched.schedule_all_jobs_async()
    tid, rid = next(iter(sched.task_bindings.items()))
    td = tmap.find(tid)
    rs = rmap.find(rid)
    with pytest.raises(RuntimeError, match="in flight"):
        sched.handle_task_migration(td, rs.descriptor)
    with pytest.raises(RuntimeError, match="in flight"):
        sched.handle_task_placement(td, rs.descriptor)
    sched.finish_scheduling()


# ---------------------------------------------------------------------------
# Device-resident rounds (graph/device_export.DeviceResidentState)
# ---------------------------------------------------------------------------


def _churn_rounds(sched, jmap, tmap, job_id, rounds, k=2, seed=11):
    """Deterministic churn driver: complete k bound tasks + add k new
    ones per round; yields after each schedule."""
    from ksched_tpu.drivers.synthetic import add_task_to_job

    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        bound = sorted(sched.task_bindings.items())
        if len(bound) >= k:
            for i in sorted(
                (int(x) for x in rng.choice(len(bound), k, replace=False)),
                reverse=True,
            ):
                sched.handle_task_completion(tmap.find(bound[i][0]))
        for _ in range(k):
            add_task_to_job(job_id, jmap, tmap, scheduler=sched)
        sched.add_job(jmap.find(job_id))
        sched.schedule_all_jobs()
        yield


def test_device_resident_rounds_match_host_rounds():
    """The tentpole parity claim at unit scale: a device-resident
    scheduler (persistent buffers + delta-record scatter + device-
    carried warm flow) decodes bit-identical bindings to the host
    export path, round for round, under churn."""
    from ksched_tpu.scheduler.flow_scheduler import FlowScheduler  # noqa: F401

    snaps = {}
    for resident in (False, True):
        seed_rng(7)
        sched, rmap, jmap, tmap, root = build_cluster(
            num_machines=4, num_cores=1, pus_per_core=2, max_tasks_per_pu=2,
            backend=JaxSolver(),
        )
        sched.solver.device_resident = resident
        if resident:
            from ksched_tpu.graph.device_export import DeviceResidentState

            sched.solver.resident = DeviceResidentState(sched.solver.state)
        job_id = add_job(sched, jmap, tmap, num_tasks=10)
        sched.schedule_all_jobs()
        hist = [dict(sched.task_bindings)]
        for _ in _churn_rounds(sched, jmap, tmap, job_id, rounds=6):
            hist.append({tmap.find(t).name: r for t, r in sched.task_bindings.items()})
        snaps[resident] = hist[1:]
        if resident:
            # the mirror itself must equal the host folded arrays
            sched.solver.resident.parity_check()
            assert sched.solver.resident.last_upload_kind == "delta"
    assert snaps[False] == snaps[True]


def test_resident_delta_bytes_track_churn_not_graph():
    """After the initial full upload, refreshes ship packed records
    sized by the round's dirty slots/nodes — not the padded arrays."""
    from ksched_tpu.graph.device_export import DeviceResidentState
    from ksched_tpu.obs.devprof import problem_nbytes
    from ksched_tpu.solver.cpu_ref import ReferenceSolver

    seed_rng(3)
    # eight machines: a round's records (two completions, two binds, and
    # since PR 52 the PU -> sink slot of each, whose folded bound moves
    # with the pin) stay one bucket of sixteen whatever the cluster holds
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=8, num_cores=1, pus_per_core=2, max_tasks_per_pu=2,
        backend=ReferenceSolver(),
    )
    sched.solver.device_resident = True
    sched.solver.resident = DeviceResidentState(sched.solver.state)
    job_id = add_job(sched, jmap, tmap, num_tasks=10)
    sched.schedule_all_jobs()
    res = sched.solver.resident
    assert res.last_upload_kind == "full_build"
    full_bytes = problem_nbytes(sched.solver.state.problem())
    deltas = []
    for _ in _churn_rounds(sched, jmap, tmap, job_id, rounds=5):
        if res.last_upload_kind == "delta":
            deltas.append(res.last_upload_bytes)
        res.parity_check()
    assert len(deltas) >= 2, "no steady delta refreshes in 5 churn rounds"
    # steady-state records are churn-sized; the FIRST churn round also
    # carries the fill round's post-solve mutations (and at this toy
    # scale the pow2 record padding), so judge the steady tail
    assert max(deltas[1:]) < full_bytes / 2, (deltas, full_bytes)


def test_problem_cache_reuses_and_isolates():
    """Satellite: problem() returns the cached object when nothing was
    journaled since the last materialize; a later mutation builds NEW
    arrays instead of touching the snapshot a solver may still hold."""
    from ksched_tpu.solver.cpu_ref import ReferenceSolver

    seed_rng(5)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, num_cores=1, pus_per_core=2, max_tasks_per_pu=1,
        backend=ReferenceSolver(),
    )
    add_job(sched, jmap, tmap, num_tasks=2)
    sched.schedule_all_jobs()
    state = sched.solver.state
    p1 = state.problem()
    assert state.problem() is p1  # clean: cached object comes back
    snap_excess = p1.excess.copy()
    snap_cap = p1.cap.copy()
    # mutate the sink excess through the tracked path
    state.set_excess(1, int(state.excess[1]) + 5)
    p2 = state.problem()
    assert p2 is not p1
    # the old snapshot is untouched (solvers may still hold it)...
    assert np.array_equal(p1.excess, snap_excess)
    assert np.array_equal(p1.cap, snap_cap)
    # ...and clean groups are shared, dirty groups rebuilt
    assert p2.cap is p1.cap
    assert p2.excess is not p1.excess
    state.set_excess(1, int(snap_excess[1]))  # restore


def test_device_warm_flow_matches_host_mask():
    """The device warm-flow program is bit-identical to the host
    mask: keep flow where endpoints are unchanged, clipped to the new
    cap; zero where they changed."""
    from ksched_tpu.graph.device_export import device_warm_flow_fn

    rng = np.random.default_rng(0)
    m = 64
    src0 = rng.integers(1, 9, m).astype(np.int32)
    dst0 = rng.integers(1, 9, m).astype(np.int32)
    src1 = src0.copy()
    dst1 = dst0.copy()
    moved = rng.random(m) < 0.3
    src1[moved] = rng.integers(1, 9, int(moved.sum())).astype(np.int32)
    prev = rng.integers(0, 10, m).astype(np.int32)
    cap = rng.integers(0, 6, m).astype(np.int32)
    got = np.asarray(device_warm_flow_fn()(prev, src0, dst0, src1, dst1, cap))
    same = (src0 == src1) & (dst0 == dst1)
    want = np.where(same, np.minimum(prev, cap), 0).astype(np.int32)
    assert np.array_equal(got, want)


def test_restart_budget_same_objectives_fewer_wasted_steps():
    """The budgeted warm attempt escapes a price-war round to a fresh
    restart — every solve still lands on an EXACT optimum (objectives
    match the unbudgeted solver's round for round)."""
    objs = {}
    for budget in (None, 8):
        seed_rng(7)
        solver = JaxSolver(restart_budget=budget)
        sched, rmap, jmap, tmap, root = build_cluster(
            num_machines=4, num_cores=1, pus_per_core=2, max_tasks_per_pu=2,
            backend=solver,
        )
        job_id = add_job(sched, jmap, tmap, num_tasks=10)
        sched.schedule_all_jobs()
        seq = []
        for _ in _churn_rounds(sched, jmap, tmap, job_id, rounds=5):
            seq.append(sched.solver.last_result.objective)
        objs[budget] = seq
    assert objs[None] == objs[8], objs


# ---------------------------------------------------------------------------
# The double-buffered service loop (cli.SchedulerService pipeline mode)
# ---------------------------------------------------------------------------


def _service(pipeline, device_resident=False, backend_name="jax"):
    from ksched_tpu.cli import SchedulerService
    from ksched_tpu.cluster import SyntheticClusterAPI
    from ksched_tpu.solver.select import make_backend

    seed_rng(9)
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api,
        max_tasks_per_pu=2,
        backend=make_backend(backend_name),
        backend_name=backend_name,
        pipeline=pipeline,
        device_resident=device_resident,
    )
    svc.init_topology(fake_machines=3, pus_per_core=2)
    return svc, api


def test_pipelined_service_defers_posts_to_next_dispatch_window():
    from ksched_tpu.cluster import PodEvent

    svc, api = _service(pipeline=True)
    bound = svc.run_round([PodEvent(pod_id=f"p{i}") for i in range(4)])
    assert bound == 4
    # scheduler state is complete, but the POSTs ride the NEXT window
    assert len(svc.scheduler.task_bindings) == 4
    assert len(api.bindings()) == 0
    assert len(svc._pending_bindings) == 4
    # next round's dispatch window flushes them
    svc.run_round([PodEvent(pod_id="p4")])
    assert len(api.bindings()) == 4
    # an explicit flush drains the rest (loop exit / checkpoint path)
    svc.flush_pending_bindings()
    assert len(api.bindings()) == 5


def test_idle_sweep_flushes_stranded_posts():
    """A quiet pod channel must not strand the last active round's
    deferred POSTs: the idle sweep (run_round with solve=False) is a
    flush point, so pods bind on the control plane even when no new
    pod ever arrives."""
    from ksched_tpu.cluster import PodEvent
    from ksched_tpu.runtime.trace import RoundTracer

    svc, api = _service(pipeline=True)
    svc.tracer = RoundTracer()
    svc.run_round([PodEvent(pod_id=f"p{i}") for i in range(3)])
    assert len(api.bindings()) == 0 and len(svc._pending_bindings) == 3
    svc.run_round([], solve=False)  # the quiet-channel idle sweep
    assert len(api.bindings()) == 3
    assert not svc._pending_bindings


def test_service_loop_modes_bit_identical():
    """sync / pipelined / pipelined+device-resident services fed the
    same pod + completion schedule end with identical scheduler
    bindings AND identical API-side bindings after the final flush."""
    from ksched_tpu.cluster import PodEvent

    finals = {}
    for label, pipeline, resident in (
        ("sync", False, False),
        ("pipelined", True, False),
        ("resident", True, True),
    ):
        svc, api = _service(pipeline=pipeline, device_resident=resident)
        seq = 0
        rng = np.random.default_rng(2)
        for r in range(6):
            pods = [PodEvent(pod_id=f"p{seq + i}") for i in range(2)]
            seq += 2
            svc.flush_pending_bindings()  # logical-round driver (see soak)
            svc.run_round(pods)
            bound_pods = sorted(
                p for p, t in svc.pod_to_task.items()
                if t in svc.scheduler.task_bindings
            )
            if len(bound_pods) > 2:
                k = int(rng.integers(1, 3))
                for j in sorted(int(x) for x in rng.choice(len(bound_pods), k, replace=False)):
                    svc.complete_pod(bound_pods[j])
        svc.flush_pending_bindings()
        finals[label] = (
            {svc.task_to_pod[t]: r for t, r in svc.scheduler.task_bindings.items()},
            dict(api.bindings()),
        )
    assert finals["sync"] == finals["pipelined"] == finals["resident"]


def test_ladder_async_rung_failure_degrades_synchronously():
    """A pipelined round whose configured rung fails mid-flight falls
    back to the synchronous ladder path inside complete(): the round
    still produces placements (from a lower rung) and the degradation
    is counted."""
    from ksched_tpu.cluster import PodEvent
    from ksched_tpu.runtime.chaos import ChaosPolicy, FaultInjector

    policy = ChaosPolicy(seed=1, solver_fault_prob=1.0, solver_fault_kinds=("nonconverge",))
    injector = FaultInjector(policy)
    from ksched_tpu.cli import SchedulerService
    from ksched_tpu.cluster import SyntheticClusterAPI
    from ksched_tpu.solver.select import make_backend

    seed_rng(9)
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api,
        max_tasks_per_pu=2,
        backend=make_backend("jax"),
        backend_name="jax",
        injector=injector,
        pipeline=True,
    )
    svc.init_topology(fake_machines=2, pus_per_core=2)
    injector.begin_round(0)
    bound = svc.run_round([PodEvent(pod_id="p0"), PodEvent(pod_id="p1")])
    assert bound == 2  # the cpu_ref rung still placed the round
    assert svc.ladder is not None and svc.ladder.last_degradations >= 1
    assert svc.ladder.last_rung_name == "cpu_ref"
