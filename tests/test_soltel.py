"""Solver-interior telemetry (obs/soltel.py + in-kernel counters).

The contract under test, per backend:

1. **Bit-identical flows on/off** — the telemetry counters read state
   each superstep already computes; they must never feed back. Checked
   for every compiled backend (jax, layered, sharded) at 3
   shape buckets, plus step-count equality.
2. **Explicit truncation** — a solve longer than the ring keeps the
   FINAL supersteps, reports `truncated` + `start_step`, and the kept
   rows match a full-capacity recording row for row.
3. **Stall detection** — the structured rules (excess plateau, eps
   plateau, budget exhaustion, cap proximity) fire on telemetry shaped
   like each pathology, and a genuine non-convergence raises
   SolverStallError carrying reason + telemetry.
4. **Flight integration** — a ladder failure deposits a structured
   stall event (with telemetry tail) that FlightRecorder.dump embeds.
5. **Publication** — solve_traced feeds the registry histograms and
   synthesizes per-superstep child spans under backend_solve.
"""

import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from ksched_tpu.obs import soltel
from ksched_tpu.obs.metrics import Registry, scoped_registry
from ksched_tpu.obs.soltel import (
    SOLTEL_COLS,
    SOLTEL_WIDTH,
    SolverStallError,
    SolveTelemetry,
    decode,
    detect_stall,
)
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.solver.layered import (
    LayeredProblem,
    LayeredTransportSolver,
)
from ksched_tpu.parallel.sharded_solver import ShardedJaxSolver

from test_jax_solver import random_scheduling_problem

#: 3 shape buckets (tasks, machines) for the bit-identity sweep —
#: distinct pow2 node/arc buckets, kept SMALL: every (backend, bucket,
#: cap) triple is a fresh compile and tier-1 has a hard wall
SHAPE_BUCKETS = [(8, 3), (14, 4), (22, 5)]

#: the one telemetry capacity the suite compiles (beyond 0/off) —
#: reused across tests so executables are shared via the jit cache
CAP = 64


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8
    return Mesh(np.array(devs[:8]), ("x",))


def _problem(tasks, machines, seed):
    rng = np.random.default_rng(seed)
    return random_scheduling_problem(
        rng, num_tasks=tasks, num_machines=machines, slots_per_machine=2
    )


def _general_backends(mesh):
    return {
        "jax": lambda tel: JaxSolver(telemetry=tel),
        "sharded": lambda tel: ShardedJaxSolver(mesh, telemetry=tel),
    }


# ---------------------------------------------------------------------------
# 1. bit-identical flows, telemetry on vs off
# ---------------------------------------------------------------------------


#: sharded bit-identity beyond the first bucket is slow-marked: each
#: (bucket, on/off) pair is a fresh shard_map compile (~8 s), and the
#: budgeted tier-1 wall is compile-bound (same reasoning that
#: slow-marks test_sharded_transport); `pytest tests/` runs all three
_SWEEP = [("jax", b) for b in SHAPE_BUCKETS] + \
    [("sharded", SHAPE_BUCKETS[0])] + [
        pytest.param("sharded", b, marks=pytest.mark.slow)
        for b in SHAPE_BUCKETS[1:]
    ]


@pytest.mark.parametrize("backend,bucket", _SWEEP, ids=str)
def test_flows_bit_identical_on_off(backend, bucket, mesh):
    make = _general_backends(mesh)[backend]
    p = _problem(*bucket, seed=7)
    r_off = make(0).solve(p)
    s_on = make(CAP)
    r_on = s_on.solve(p)
    assert np.array_equal(r_on.flow, r_off.flow), backend
    assert r_on.objective == r_off.objective
    assert r_on.iterations == r_off.iterations
    tel = s_on.last_telemetry
    assert isinstance(tel, SolveTelemetry)
    assert tel.backend == backend
    assert tel.steps == s_on.last_supersteps
    assert tel.rows.shape[1] == SOLTEL_WIDTH
    if tel.steps:
        # a discharge ends with the last superstep doing something
        assert (tel.rows[:, 3] + tel.rows[:, 4]).max() > 0


@pytest.mark.parametrize("bucket", [(4, 40), (4, 130), (6, 300)], ids=str)
def test_layered_flows_bit_identical_on_off(bucket):
    C, M = bucket
    rng = np.random.default_rng(11)
    lp = LayeredProblem(
        supply=rng.integers(1, 30, C).astype(np.int32),
        col_cap=rng.integers(0, 3, M).astype(np.int32),
        cost_cm=rng.integers(0, 50, (C, M)).astype(np.int32),
        unsched_cost=40,
        ec_cost=2,
    )
    off = LayeredTransportSolver(telemetry=0)
    on = LayeredTransportSolver(telemetry=CAP)
    r_off = off.solve_layered(lp)
    r_on = on.solve_layered(lp)
    assert np.array_equal(r_on.y, r_off.y)
    assert r_on.objective == r_off.objective
    assert r_on.supersteps == r_off.supersteps
    if r_on.supersteps:
        tel = on.last_telemetry
        assert tel is not None and tel.backend == "layered"
        assert tel.steps == r_on.supersteps
    else:
        assert on.last_telemetry is None  # closed-form path: no loop ran


def test_disabled_module_resolves_cap_zero():
    prior = soltel.enabled()
    try:
        soltel.set_enabled(False)
        assert soltel.resolve_cap(None) == 0
        s = JaxSolver(telemetry=soltel.resolve_cap(None))
        s.solve(_problem(8, 3, seed=1))
        assert s.last_telemetry is None
        soltel.set_enabled(True)
        assert soltel.resolve_cap(None) == soltel.SOLTEL_DEFAULT_CAP
        assert soltel.resolve_cap(7) == 7
        assert soltel.resolve_cap(0) == 0  # explicit off overrides on
    finally:
        soltel.set_enabled(prior)


# ---------------------------------------------------------------------------
# 2. decode / explicit truncation
# ---------------------------------------------------------------------------


def test_decode_no_truncation():
    cap = 16
    buf = np.zeros((cap, SOLTEL_WIDTH), np.int32)
    for i in range(5):
        buf[i] = i + 1
    tel = decode(buf, steps=5, cap=cap, backend="t", budget=100)
    assert not tel.truncated and tel.start_step == 0
    assert tel.rows.shape == (5, SOLTEL_WIDTH)
    assert tel.rows[-1, 0] == 5


def test_decode_ring_truncation_is_explicit():
    cap = 8
    buf = np.zeros((cap, SOLTEL_WIDTH), np.int32)
    steps = 21  # rows 13..20 survive, at ring slots 13%8.. etc.
    for s in range(steps - cap, steps):
        buf[s % cap] = s
    tel = decode(buf, steps=steps, cap=cap, backend="t", budget=100)
    assert tel.truncated and tel.start_step == steps - cap
    assert list(tel.rows[:, 0]) == list(range(steps - cap, steps))


def test_solver_ring_keeps_final_supersteps():
    """A tiny ring on a real solve keeps exactly the last rows of the
    CAP-capacity recording — truncation loses the head, never the
    tail, and says so. (The CAP recording itself may be truncated; the
    tiny ring's rows must still be its exact suffix.)"""
    p = _problem(14, 4, seed=7)
    full = JaxSolver(telemetry=CAP)
    tiny = JaxSolver(telemetry=4)
    full.solve(p)
    tiny.solve(p)
    t_full, t_tiny = full.last_telemetry, tiny.last_telemetry
    assert t_full.steps == t_tiny.steps
    assert t_tiny.truncated == (t_tiny.steps > 4)
    assert np.array_equal(t_tiny.rows, t_full.rows[-len(t_tiny.rows):])
    assert t_tiny.start_step == t_full.steps - len(t_tiny.rows)


def test_decode_rejects_shape_mismatch():
    with pytest.raises(ValueError):
        decode(np.zeros((4, 3)), steps=2, cap=4, backend="t", budget=10)


# ---------------------------------------------------------------------------
# 3. stall detection
# ---------------------------------------------------------------------------


def _tel(rows, steps=None, budget=10_000, converged=True):
    rows = np.asarray(rows, np.int32)
    return SolveTelemetry(
        backend="t", steps=steps if steps is not None else len(rows),
        budget=budget, cap=len(rows), truncated=False, start_step=0,
        rows=rows, converged=converged,
    )


def _rows(n, eps=1, excess=5, active=2):
    r = np.zeros((n, SOLTEL_WIDTH), np.int32)
    r[:, 0] = eps
    r[:, 1] = active
    r[:, 2] = excess
    return r


def test_detect_excess_plateau():
    reason = detect_stall(_tel(_rows(64), converged=False), window=64)
    assert reason["kind"] == "excess_plateau"
    assert reason["window"] == 64 and reason["excess"] == 5


def test_detect_eps_plateau():
    rows = _rows(128, eps=64)
    rows[:, 2] = np.arange(128, 0, -1)  # excess IS decreasing (slowly)
    reason = detect_stall(_tel(rows, converged=False), window=64)
    assert reason["kind"] == "eps_plateau"


def test_detect_budget_exhausted():
    rows = _rows(8)
    rows[:, 2] = np.arange(8, 0, -1)
    reason = detect_stall(_tel(rows, steps=8, budget=8, converged=False))
    assert reason["kind"] == "superstep_budget_exhausted"


def test_detect_cap_proximity_on_converged_solve():
    rows = _rows(95)
    rows[:, 2] = np.arange(95, 0, -1)
    reason = detect_stall(_tel(rows, steps=95, budget=100, converged=True),
                          window=200)
    assert reason["kind"] == "superstep_cap_proximity"


def test_detect_nothing_on_healthy_solve():
    rows = _rows(10)
    rows[:, 2] = np.arange(10, 0, -1)
    assert detect_stall(_tel(rows, budget=10_000)) is None


def test_real_nonconvergence_raises_stall_error_with_telemetry():
    p = _problem(22, 5, seed=5)
    s = JaxSolver(max_supersteps=3, telemetry=CAP)
    with pytest.raises(SolverStallError) as ei:
        s.solve(p)
    err = ei.value
    assert isinstance(err, RuntimeError)  # ladder-absorbable
    assert err.telemetry is not None and err.telemetry.steps > 0
    assert not err.telemetry.converged
    assert err.reason is not None and err.reason["kind"] in (
        "superstep_budget_exhausted", "excess_plateau", "eps_plateau",
    )


# ---------------------------------------------------------------------------
# 4. ladder + flight integration
# ---------------------------------------------------------------------------


def test_ladder_failure_feeds_flight_dump(tmp_path):
    from ksched_tpu.obs.flight import FlightRecorder
    from ksched_tpu.runtime.degrade import DegradingSolver

    soltel.reset_stalls()
    with scoped_registry():
        p = _problem(22, 5, seed=5)
        # rung 0 cannot converge in 3 supersteps; rung 1 succeeds
        ladder = DegradingSolver([
            ("tiny", JaxSolver(max_supersteps=3, telemetry=CAP)),
            ("jax", JaxSolver(telemetry=CAP)),
        ])
        res = ladder.solve(p)
        assert res is not None and ladder.last_rung == 1
        assert ladder.last_failure_reasons, "no structured reason recorded"
        reason = ladder.last_failure_reasons[0]
        assert reason["rung"] == "tiny"
        assert reason["kind"] in (
            "superstep_budget_exhausted", "excess_plateau", "eps_plateau",
        )
        assert reason["telemetry_tail"], "no telemetry tail on the event"
        assert reason["telemetry_cols"] == list(SOLTEL_COLS)

        # the flight dump embeds the stall ring; the failed rung's
        # structured event is in it (the SUCCEEDING rung may also have
        # noted a converged-solve plateau warning — that's the tail
        # early-warning, not the failure)
        fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
        path = fr.dump("manual")
        import json

        dump = json.load(open(path))
        stalls = dump["solver_stalls"]
        rung_evs = [s for s in stalls if s.get("rung") == "tiny"]
        assert rung_evs and rung_evs[-1]["kind"] == reason["kind"]
        assert rung_evs[-1]["telemetry_tail"] == reason["telemetry_tail"]
        assert rung_evs[-1]["converged"] is False
    soltel.reset_stalls()


def test_failure_reason_classifies_injected_fault():
    reason = soltel.failure_reason("jax", RuntimeError("chaos: forced non-convergence"))
    assert reason["kind"] == "injected_fault"
    reason = soltel.failure_reason("jax", ValueError("non-finite arc costs"))
    assert reason["kind"] == "rejected_input"
    reason = soltel.failure_reason("jax", OverflowError("potentials"))
    assert reason["kind"] == "overflow"


# ---------------------------------------------------------------------------
# 5. publication: registry + synthesized child spans
# ---------------------------------------------------------------------------


def test_solve_traced_publishes_histograms_and_spans():
    from ksched_tpu.obs.spans import SpanTracer

    p = _problem(14, 4, seed=7)
    s = JaxSolver(telemetry=CAP)
    tracer = SpanTracer()
    with scoped_registry() as reg:
        with tracer:
            s.solve_traced(p)
        steps = s.last_supersteps
        assert reg.value("ksched_solve_supersteps", backend="jax") == 1
        assert reg.value("ksched_solve_pushes_total", backend="jax") > 0
        events = tracer.events()
        solve_ev = [e for e in events if e["name"] == "backend_solve"]
        steps_ev = [e for e in events if e["name"] == "superstep"]
        assert len(solve_ev) == 1
        assert len(steps_ev) == min(steps, CAP)
        # child spans sit INSIDE the backend_solve span and carry the
        # convergence args Perfetto shows
        parent = solve_ev[0]
        for ev in steps_ev:
            assert ev["args"]["parent_sid"] == parent["args"]["sid"]
            assert ev["ts"] >= parent["ts"] - 1e-6
            assert "eps" in ev["args"] and "active" in ev["args"]
        # steps are consecutive and end at the last superstep
        idx = [ev["args"]["step"] for ev in steps_ev]
        assert idx == list(range(steps - len(steps_ev), steps))
        # a backend with no span of its own: laid over the (then open)
        # backend_solve, from its start to the moment of publication
        assert getattr(s, "last_solve_span", None) is None
        assert steps_ev[0]["ts"] == pytest.approx(parent["ts"], abs=1e-3)
        last = steps_ev[-1]
        assert last["ts"] + last["dur"] <= parent["ts"] + parent["dur"] + 1e-3


def test_auto_solver_lays_its_supersteps_over_the_transport_span(monkeypatch):
    """Under AutoSolver `backend_solve` is the audit, the kernel call and
    the reconstruction; the supersteps ran in the second of them only."""
    from ksched_tpu.cli import SchedulerService
    from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
    from ksched_tpu.costmodels import CostModelType
    from ksched_tpu.obs.spans import SpanTracer
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    monkeypatch.setattr(soltel, "_enabled", True)  # conftest turns it off for the suite
    auto = AutoSolver(ReferenceSolver())
    svc = SchedulerService(
        SyntheticClusterAPI(), max_tasks_per_pu=4, cost_model=CostModelType.COCO,
        backend=auto, backend_name="auto", degrade=False,
    )
    svc.init_topology(fake_machines=6, pus_per_core=2)
    tracer = SpanTracer()
    with scoped_registry() as reg:
        # the second round sees the first's interference: several cost rows
        svc.run_round([PodEvent(pod_id=f"a{i}", task_class=i % 3) for i in range(5)])
        with tracer:
            svc.run_round([PodEvent(pod_id=f"b{i}", task_class=i % 3) for i in range(5)])
        assert auto.last_path == "dense" and auto.last_supersteps > 0
        assert reg.value("ksched_solve_supersteps", backend="layered") == 1
    events = tracer.events()
    (transport,) = [e for e in events if e["name"] == "transport"]
    (audit,) = [e for e in events if e["name"] == "collapse_audit"]
    (rebuild,) = [e for e in events if e["name"] == "flow_reconstruct"]
    steps_ev = [e for e in events if e["name"] == "superstep"]
    assert len(steps_ev) == auto.last_supersteps == transport["args"]["supersteps"]
    t0, t1 = transport["ts"], transport["ts"] + transport["dur"]
    for ev in steps_ev:
        assert ev["args"]["parent"] == "transport"
        assert ev["args"]["parent_sid"] == transport["args"]["sid"]
        assert t0 - 1e-3 <= ev["ts"] and ev["ts"] + ev["dur"] <= t1 + 1e-3
    # they fill the transport span exactly, and none runs during the
    # audit before it or the reconstruction after it
    assert steps_ev[0]["ts"] == pytest.approx(t0, abs=1e-3)
    assert steps_ev[-1]["ts"] + steps_ev[-1]["dur"] == pytest.approx(t1, abs=1e-3)
    assert audit["ts"] + audit["dur"] <= steps_ev[0]["ts"] + 1e-3
    assert rebuild["ts"] >= t1 - 1e-3


def test_publish_counts_truncation():
    with scoped_registry() as reg:
        rows = _rows(4)
        tel = SolveTelemetry(
            backend="t", steps=9, budget=100, cap=4, truncated=True,
            start_step=5, rows=rows,
        )
        soltel.publish(tel)
        assert reg.value("ksched_solve_telemetry_truncated_total", backend="t") == 1


def test_phases_split_on_eps_transitions():
    rows = np.zeros((7, SOLTEL_WIDTH), np.int32)
    rows[:, 0] = [64, 64, 8, 8, 8, 1, 1]
    tel = _tel(rows)
    assert tel.phases() == [
        {"eps": 64, "supersteps": 2},
        {"eps": 8, "supersteps": 3},
        {"eps": 1, "supersteps": 2},
    ]
