"""The active-set superstep (PR 50) against the dense one.

`_solve_mcmf(active_set=(K, R))` traces a second form of `superstep`
beside the one it had: over the compacted rows of the nodes that hold
excess, taken by a superstep whose active nodes number at most K and
whose regions span at most R plan rows. It is the same algorithm on
the same integers, so it is held here, bit for bit, to the program
with the argument off (which tests/test_csr_entry_state.py holds to PR
28's): flow, potentials, superstep count, `converged`, `p_overflow` and
every soltel row, over that file's problems in both plan layouts (dead
rows inside an active region, a relocated region), fresh, down a cold
ladder and refitted, with and without the global price update, under
caps that some supersteps fit and others do not. Then the rule itself
(a cap exactly met, and one over), the served path of four cost models
(every solve a service dispatches answered by both programs), and who
does not get the sparse form (`stacked_solve_fn`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ksched_tpu.analysis import jaxpr_contracts as jc
from ksched_tpu.solver import jax_solver
from ksched_tpu.solver.jax_solver import (
    JaxSolver, _solve_mcmf, active_set_caps, stacked_solve_fn,
)

from test_csr_entry_state import MAX_SUPERSTEPS, PROBLEMS, TEL_CAP, _inputs, _oracle_problem, _packed_inputs

OUTPUTS = ("flow", "p", "steps", "converged", "p_overflow", "telemetry")
#: caps that a problem's bulk supersteps exceed and its tail fits, and
#: caps that every superstep of every problem here fits
TIGHT, AMPLE = (4, 64), (64, 512)


def _run(inp, flow0, eps, warm_p, telemetry_cap, active_set, price_update_every=0,
         max_supersteps=MAX_SUPERSTEPS):
    out = _solve_mcmf(
        jnp.asarray(inp["cap"]), jnp.asarray(inp["cost"]), jnp.asarray(inp["supply"]),
        jnp.asarray(flow0), jnp.asarray(np.int32(eps)), *inp["plan"],
        warm_p=warm_p, use_warm_p=warm_p is not None,
        max_supersteps=max_supersteps, telemetry_cap=telemetry_cap,
        slot_stable=inp["slot_stable"], price_update_every=price_update_every,
        active_set=active_set,
    )
    return [np.asarray(x) for x in out]


def _both(inp, flow0, eps, warm_p, telemetry_cap, active_set, **kw):
    """(the dense program's outputs, the active-set program's without
    its counter, the counter), after comparing them."""
    dense = _run(inp, flow0, eps, warm_p, telemetry_cap, None, **kw)
    sparse = _run(inp, flow0, eps, warm_p, telemetry_cap, active_set, **kw)
    took = int(sparse.pop(5))
    assert len(sparse) == len(dense) == (6 if telemetry_cap else 5)
    for what, a, b in zip(OUTPUTS, sparse, dense):
        assert a.dtype == b.dtype and a.shape == b.shape, what
        assert np.array_equal(a, b), f"{what} differs from the dense program"
    assert 0 <= took <= int(dense[2])
    return dense, sparse, took


VARIANTS = {
    "tel_off": (0, 0, TIGHT),
    "tel_on": (TEL_CAP, 0, TIGHT),
    "price_update": (TEL_CAP, 8, TIGHT),
    "ample": (TEL_CAP, 0, AMPLE),
}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("mode", ["fresh", "ladder", "refit"])
@pytest.mark.parametrize("name", PROBLEMS)
def test_the_active_set_program_is_bit_identical_to_the_dense_one(name, mode, variant):
    telemetry_cap, price_update_every, caps = VARIANTS[variant]
    _problem, inp = _inputs(name)
    m = len(inp["cap"])
    flow0, warm_p = np.zeros(m, np.int32), None
    if mode == "refit":
        # the carried state of a round whose costs then moved (as
        # tests/test_csr_entry_state.py builds it)
        before = dict(inp, cost=inp["cost"] + (np.arange(m, dtype=np.int32) % 3) * np.int32(
            len(inp["supply"])))
        prev = _run(before, flow0, 1, None, 0, None)
        assert prev[3], "the round before did not converge"
        flow0, warm_p = np.minimum(prev[0], inp["cap"]), jnp.asarray(prev[1])
    eps = max(1, int(np.abs(inp["cost"]).max())) if mode == "ladder" else 1
    dense, _sparse, took = _both(
        inp, flow0, eps, warm_p, telemetry_cap, caps, price_update_every=price_update_every
    )
    assert dense[3] and not dense[4]
    if caps == AMPLE:
        assert took == int(dense[2]), "a superstep within ample caps ran dense"


@pytest.mark.parametrize("name", ["packed-random1", "packed-random2", "slot-churned"])
def test_one_solve_crosses_the_caps_in_both_directions(name):
    """Supersteps wider than the caps run dense and narrower ones sparse,
    in one solve and in turn: the counter lies strictly between none and
    all, and by the telemetry's `active` column the sparse ones are not
    one stretch (a wide superstep follows a narrow one and is followed by
    one)."""
    _problem, inp = _inputs(name)
    m = len(inp["cap"])
    caps = (2, 512)  # rows ample: the nodes decide
    dense, _sparse, took = _both(inp, np.zeros(m, np.int32), 1, None, 4096, caps)
    steps = int(dense[2])
    active = dense[5][: min(steps, 4096), 1]
    fits = active <= caps[0]
    if steps <= 4096:
        assert took == int(fits.sum())
    assert 0 < took < steps
    flips = int(np.abs(np.diff(fits.astype(np.int8))).sum())
    assert flips >= 2, "the solve went one way across the caps only"


@pytest.mark.parametrize("nodes", [1, 2, 3])
def test_the_node_cap_exactly_met_runs_sparse_and_one_over_runs_dense(nodes):
    """With rows to spare, a superstep takes the sparse form exactly when
    at most K nodes hold excess: counted from the dense program's own
    telemetry (`active`), for K below, at and above what `assign`'s first
    superstep holds (its two tasks)."""
    inp = _packed_inputs(_oracle_problem("assign"))
    m = len(inp["cap"])
    dense, _sparse, took = _both(inp, np.zeros(m, np.int32), 1, None, 4096, (nodes, 512))
    active = dense[5][: int(dense[2]), 1]
    assert active.max() == 2 and int(dense[2]) <= 4096
    assert took == int((active <= nodes).sum())
    assert (took == int(dense[2])) == (nodes >= 2)


@pytest.mark.parametrize("rows,every", [(1, False), (2, True), (3, True)])
def test_the_row_cap_exactly_met_runs_sparse_and_one_over_runs_dense(rows, every):
    """`single` is a chain 1 -> 2 -> 3 with one unit at node 1: every
    superstep has one active node, node 1 (one row: its arc) or node 2
    (two rows: that arc's backward entry and its own). R = 2 holds
    both; R = 1 only node 1's supersteps."""
    inp = _packed_inputs(_oracle_problem("single"))
    plan = [np.asarray(x) for x in inp["plan"]]
    extent = plan[8] - plan[7] + 1  # node_last - node_first + 1
    assert (int(extent[1]), int(extent[2])) == (1, 2)
    m = len(inp["cap"])
    dense, _sparse, took = _both(inp, np.zeros(m, np.int32), 1, None, 0, (1, rows))
    steps = int(dense[2])
    assert (took == steps) == every
    assert 0 < took


def test_far_ends_that_repeat_within_one_superstep_add_up():
    """`assign`'s two tasks push to one class node in one superstep: two
    compacted rows with one far end, whose excess takes both units (the
    telemetry row of that superstep says two pushed, the next has the
    one node active with both)."""
    inp = _packed_inputs(_oracle_problem("assign"))
    m = len(inp["cap"])
    dense, _sparse, took = _both(inp, np.zeros(m, np.int32), 1, None, 4096, AMPLE)
    steps = int(dense[2])
    assert took == steps
    rows = dense[5][:steps]
    together = [t for t in range(steps - 1) if rows[t, 1] == 2 and rows[t, 3] == 2]
    assert together, "no superstep pushed from both tasks at once"
    t = together[0]
    assert rows[t + 1, 1] == 1 and rows[t + 1, 2] == 2  # one node, both units


def test_an_active_node_without_rows_rides_along_inert():
    """A node with supply and no arc has an empty segment: it stays
    active, the solve cannot converge, and both programs leave the same
    state behind after the same supersteps, all of them sparse."""
    problem = _oracle_problem("single")
    inp = _packed_inputs(problem)
    supply = np.array(inp["supply"])
    supply[40] = 1  # a padded node: no arc touches it
    assert not np.asarray(inp["plan"][9])[40]  # node_nonempty
    inp = dict(inp, supply=supply)
    m = len(inp["cap"])
    dense, _sparse, took = _both(
        inp, np.zeros(m, np.int32), 1, None, TEL_CAP, AMPLE, max_supersteps=40
    )
    assert not dense[3] and int(dense[2]) == 40 == took


# ---------------------------------------------------------------------------
# the served path: every solve a service dispatches, answered by both
# ---------------------------------------------------------------------------


@pytest.fixture
def both_programs(monkeypatch):
    """Every `_solve_mcmf` call `JaxSolver` makes also runs with the
    active set off and must agree bit for bit; plans of any size get the
    sparse form. Yields the (supersteps, sparse supersteps) of each call."""
    seen = []
    real = jax_solver._solve_mcmf

    def both(*args, **kw):
        out = real(*args, **kw)
        caps = kw.get("active_set")
        assert caps is not None, "JaxSolver dispatched the dense-only program"
        dense = real(*args, **dict(kw, active_set=None))
        mine = list(out)
        took = int(mine.pop(5))
        assert len(mine) == len(dense)
        for what, a, b in zip(OUTPUTS, mine, dense):
            assert np.array_equal(np.asarray(a), np.asarray(b)), f"{what} differs from the dense program"
        seen.append((int(dense[2]), took))
        return out

    monkeypatch.setattr(jax_solver, "_ACTIVE_MIN_PLAN_ROWS", 0)
    monkeypatch.setattr(jax_solver, "_solve_mcmf", both)
    return seen


def _requests_rounds():
    from test_k8s_requests_model import Stream

    s = Stream(40, 9, backend="jax")
    out = [s.round(np.zeros(300, int))]
    for sizes, gone in [([0] * 5, 4), ([0, 0, 0], 6), ([0] * 5, 2)]:
        out.append(s.round(sizes, gone))
    return [(o[0], o[1], o[2], o[3]) for o in out]


def _quincy_rounds():
    from test_quincy_served import Stream

    s = Stream(24, 3, 4, 6, backend="jax")
    out = [s.round(40, 0)]
    for arrivals, gone in [(6, 4), (4, 5), (5, 3)]:
        out.append(s.round(arrivals, gone))
    s.holds_the_guarantee()
    return out


def _zonespread_rounds():
    from test_k8s_zonespread import Stream

    s = Stream(12, 20, 3, 4, 7, backend="jax")
    out = []
    for arrivals, gone in [(60, 0), (6, 5), (5, 4), (6, 3)]:
        ours, reference = s.round(arrivals, gone)
        out.append((ours, reference))
    return out


def _preemption_rounds():
    from test_k8s_priority import Stream

    s = Stream(30, 4, 2, 12, "jax")
    out = [s.round([0] * 120)]
    for arrivals in ([1], [1] * 10, [1] * 40, []):
        out.append(s.round(arrivals))
    assert s.svc.ladder.degradations_total == 0 and s.svc.ladder.last_rung == 0
    return out


@pytest.mark.parametrize(
    "rounds", [_requests_rounds, _quincy_rounds, _zonespread_rounds, _preemption_rounds],
    ids=["k8s_requests", "quincy", "k8s_zonespread", "preemption"],
)
def test_served_rounds_bind_what_the_dense_program_binds_and_native_prices(rounds, both_programs):
    """Each stream holds its rounds to its plain reference and, where it
    solves the round's problem again, to native C++ (its own asserts and
    the tuples compared here); the fixture holds every dispatched solve
    to the dense program. And the sparse form did run."""
    for got in rounds():
        if len(got) == 4:  # (objective, served, reference, native)
            assert got[0] == got[1] == got[2] == got[3]
        else:  # (ours, reference)
            assert got[0] == got[1]
    assert both_programs, "no solve was dispatched"
    assert sum(took for _steps, took in both_programs) >= 1


def test_a_trickle_round_of_the_served_path_counts_its_sparse_supersteps(monkeypatch):
    """A trickle round of a 100-machine cluster (8,192 plan rows after
    the re-fit; the floor lowered to let a plan of a test's size in)
    takes the sparse form for the supersteps that move the arrivals
    down the tree, and the round's record and span say so."""
    monkeypatch.setattr(jax_solver, "_ACTIVE_MIN_PLAN_ROWS", 4_096)
    from ksched_tpu.obs.spans import SpanTracer
    from ksched_tpu.runtime.trace import RoundTracer
    from test_k8s_requests_model import Stream

    span_tracer = SpanTracer(capacity=1 << 16).install()
    try:
        s = Stream(100, 9, backend="jax", tracer=RoundTracer(), span_tracer=span_tracer)
        s.round(np.zeros(2000, int))
        for _ in range(2):
            objective, served, want, native = s.round([0] * 5, 3)
            assert objective == served == want == native
        events = span_tracer.events()
    finally:
        span_tracer.uninstall()
    rung = s.svc.ladder.primary
    assert isinstance(rung, JaxSolver)
    assert s.svc.scheduler.last_timing.plan_rows >= jax_solver._ACTIVE_MIN_PLAN_ROWS
    assert 1 <= rung.last_sparse_supersteps <= rung.last_supersteps
    rec = s.svc.tracer.records[-1]
    assert rec.supersteps_sparse == rung.last_sparse_supersteps
    assert rec.solver_work == rung.last_supersteps
    solves = [e for e in events if e["name"] == "backend_solve"]
    assert solves[-1]["args"]["supersteps_sparse"] == rec.supersteps_sparse


def test_every_superstep_of_a_trickle_round_is_sparse_since_no_pu_holds_excess(monkeypatch):
    """The same service with the telemetry ring on (PR 52). The export
    routes the 2,000 pins' units PU -> sink itself, so the round's first
    two supersteps hold its five arrivals and not 50 PUs beside them:
    `active` starts at the arrivals' count, `pushed` never holds the
    pins' 2,000 (the parent read [55, 55, 1, ...] and [0, 2005, 0, 5,
    ...]), and every superstep takes the sparse form."""
    monkeypatch.setattr(jax_solver, "_ACTIVE_MIN_PLAN_ROWS", 4_096)
    from ksched_tpu.runtime.trace import RoundTracer
    from test_k8s_requests_model import Stream

    s = Stream(100, 9, backend="jax", tracer=RoundTracer())
    rung = s.svc.ladder.primary
    assert isinstance(rung, JaxSolver)
    rung.telemetry = 64
    s.round(np.zeros(2000, int))
    for k in range(3):
        objective, served, want, native = s.round([0] * 5, 3)
        assert objective == served == want == native
        # five hops, a push each (PR 54: the prologue leaves the tree
        # admissible; ten until then, a relabel before every push)
        assert rung.last_sparse_supersteps == rung.last_supersteps == 5
        tel = rung.last_telemetry
        assert tel.col("active").tolist() == [5, 1, 1, 1, 1]
        assert tel.col("pushed").tolist() == [5, 5, 5, 5, 5]
        rec = s.svc.tracer.records[-1]
        assert (rec.supersteps_sparse, rec.solver_work) == (5, 5)
        # the pods bound before the round, less the three that left in it
        assert rec.supply_prerouted == 2000 + 2 * k - 3
    state = s.svc.scheduler.solver.state
    assert not state.problem().excess[sorted(s.svc.scheduler.gm.leaf_node_ids)].any()


# ---------------------------------------------------------------------------
# who gets the sparse form, and what it may hold
# ---------------------------------------------------------------------------


def test_the_caps_follow_the_plan_and_small_plans_keep_the_one_form():
    assert active_set_caps(jax_solver._ACTIVE_MIN_PLAN_ROWS - 1) is None
    for rows in (32_768, 65_536, 262_144, 524_288):
        nodes, span = active_set_caps(rows)
        assert nodes == jax_solver._ACTIVE_NODES and span == rows // jax_solver._ACTIVE_ROWS_SHARE
    # gtrace-12500-quincy's cluster aggregator (12,500 arcs, a quarter of slack) fits at its plan
    assert active_set_caps(524_288)[1] >= 12_500 * 5 // 4 + 64


def _primitives(jaxpr):
    return [e.primitive.name for e, _p, _l in jc.walk_eqns(jaxpr)]


@pytest.mark.parametrize("use_warm_p", [False, True], ids=["cold", "refit"])
def test_the_stacked_lanes_trace_no_sparse_op_and_return_no_counter(use_warm_p):
    """Under `vmap` a `cond` is a select and both forms would run: the
    lanes keep the dense superstep. Five outputs, no scatter, no top_k."""
    closed = jc.trace_stacked(4, 20, 100, use_warm_p=use_warm_p)
    names = _primitives(closed.jaxpr)
    assert "top_k" not in names and not any(n.startswith("scatter") for n in names)
    assert len(closed.out_avals) == 5
    assert jc.active_set_branches(closed) is None
    fn = stacked_solve_fn(use_warm_p=use_warm_p)
    assert fn is stacked_solve_fn(use_warm_p=use_warm_p)


def test_the_sparse_branch_holds_what_the_chip_reading_admitted_and_no_more():
    """Four scatter-adds, all in the sparse branch; no gather, prefix sum
    or top_k there as long as the plan; the dense branch is the
    superstep of the program without the argument, gather for gather;
    and the checker is not vacuous."""
    closed = jc.trace_jax_active(20, 100)
    assert jc.active_set_faults(closed) == []
    dense, sparse = jc.active_set_branches(closed)
    assert sum(n.startswith("scatter") for n in _primitives(sparse)) == 4
    assert not any(n.startswith("scatter") for n in _primitives(dense))
    plain = jc.trace_jax_slot_stable(20, 100)
    assert jc.active_set_branches(plain) is None
    assert jc.active_set_faults(plain) == ["no superstep chooses between two forms"]
    gathers = lambda jaxpr: sorted(  # noqa: E731
        e.outvars[0].aval.shape for e, _p, _l in jc.walk_eqns(jaxpr) if e.primitive.name == "gather"
    )
    from test_csr_entry_state import _phase_branches

    assert gathers(dense) == gathers(_phase_branches(plain.jaxpr)[1])
    # a sparse branch that gathered over the plan's rows is a fault
    n, m = jc.bucketed_sizes(20, 100)
    e = jc.slot_stable_entry_cap(m)
    wide = jax.make_jaxpr(
        lambda *a: _solve_mcmf(*a, slot_stable=True, active_set=(4, e)),
    )(
        *(jax.ShapeDtypeStruct(s, d) for s, d in (
            ((m,), jnp.int32), ((m,), jnp.int32), ((n,), jnp.int32), ((m,), jnp.int32), ((), jnp.int32),
            ((e,), jnp.int32), ((e,), jnp.int32), ((e,), jnp.int32), ((e,), jnp.int32), ((e,), jnp.int32),
            ((e,), jnp.bool_), ((2 * m,), jnp.int32), ((n,), jnp.int32), ((n,), jnp.int32), ((n,), jnp.bool_),
        ))
    )
    assert any("in the sparse branch" in f for f in jc.active_set_faults(wide))
