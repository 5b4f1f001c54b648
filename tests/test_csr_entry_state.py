"""Entry-space scan-CSR (PR 29) against the program it replaced.

`_solve_mcmf` carries each sorted entry's residual and each node's
excess through the phase loop and gathers what the loop never changes
(capacity, signed cost, partner row) once a solve. It is the same
algorithm on the same integers, so it is held here, bit for bit, to a
frozen copy of PR 28's function (per-arc flow in the loop state, every
row value gathered again each iteration): flow, potentials, superstep
count, `converged`, `p_overflow` and every soltel row, over the graph
families of tests/test_solver_oracle.py and tests/test_jax_solver.py
in the packed layout and churned DeviceGraphState problems in the
slot-stable one (dead rows, a relocated region), cold and refit, at
eps 1 and down a cold ladder (where `next_phase` saturates), telemetry
off and on. The objective is ReferenceSolver's in every case.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ksched_tpu.graph.changes import ArcType, NewArcChange
from ksched_tpu.graph.device_export import DeviceGraphState
from ksched_tpu.solver import ReferenceSolver
from ksched_tpu.solver.base import lower_bound_cost
from ksched_tpu.solver.jax_solver import _solve_mcmf, build_csr_plan

from test_jax_solver import random_scheduling_problem
from test_slot_plan import SCRIPT, _build_graph, _churn_round
from test_solver_oracle import make_problem

# ---------------------------------------------------------------------------
# the reference: PR 28's program, frozen
# ---------------------------------------------------------------------------

_BIG = jnp.int32(1 << 30)
_P_GUARD = 1 << 30
_BIG_D = 1 << 28


def _seg_sum(vals, node_first, node_last, node_nonempty):
    """Per-node sum over a sorted-entry array: cumsum + boundary gathers."""
    c = jnp.cumsum(vals)
    excl_first = c[node_first] - vals[node_first]
    seg = c[node_last] - excl_first
    return jnp.where(node_nonempty, seg, 0)


def _seg_max(vals, isstart, node_last, node_nonempty, identity):
    """Per-node max via a segmented-max associative scan."""

    def combine(a, b):
        f1, v1 = a
        f2, v2 = b
        return f1 | f2, jnp.where(f2, v2, jnp.maximum(v1, v2))

    _, scanned = lax.associative_scan(combine, (isstart, vals))
    return jnp.where(node_nonempty, scanned[node_last], identity)


def _seg_min(vals, isstart, node_last, node_nonempty, identity):
    """Per-node min via a segmented-min associative scan."""

    def combine(a, b):
        f1, v1 = a
        f2, v2 = b
        return f1 | f2, jnp.where(f2, v2, jnp.minimum(v1, v2))

    _, scanned = lax.associative_scan(combine, (isstart, vals))
    return jnp.where(node_nonempty, scanned[node_last], identity)


@functools.partial(jax.jit, static_argnames=("alpha", "max_supersteps", "tighten_sweeps", "telemetry_cap", "use_warm_p", "slot_stable"))
def _solve_mcmf_frozen(
    cap, cost, supply, flow0, eps_init,
    s_arc, s_sign, s_src, s_dst, s_segstart, s_isstart, inv_order,
    node_first, node_last, node_nonempty,
    warm_p=None,
    alpha: int = 8,
    max_supersteps: int = 50_000,
    tighten_sweeps: int = 32,
    telemetry_cap: int = 0,
    use_warm_p: bool = False,
    slot_stable: bool = False,
):
    """PR 28's `_solve_mcmf`, verbatim but for this docstring and PR
    54's prologue (`tighten` counts every row one dearer, `saturate`
    leaves an arc within [-eps, +eps] alone: the algorithm's change,
    taken here too so that what this file compares stays the two STATE
    layouts): per-arc flow carried through the loop, every row value
    gathered again each iteration."""
    from ksched_tpu.obs.soltel import SOLTEL_WIDTH

    m = cap.shape[0]
    i32 = jnp.int32

    def residual(a_flow):
        """Residual per sorted entry; in slot-stable mode a dead row
        (sign 0) gets residual 0 and thus cannot push, relabel, carry
        excess, or consume prefix allocation."""
        if slot_stable:
            return jnp.where(
                s_sign > 0, cap[s_arc] - a_flow,
                jnp.where(s_sign < 0, a_flow, i32(0)),
            )
        return jnp.where(s_sign > 0, cap[s_arc] - a_flow, a_flow)

    def excess_of(flow):
        flow_signed = s_sign * flow[s_arc]
        return supply - _seg_sum(flow_signed, node_first, node_last, node_nonempty)

    def saturate(flow, p, eps):
        """Refine step: saturate every residual entry whose reduced cost
        lies below -eps, making the pseudoflow eps-optimal for the phase
        (PR 54: an arc within [-eps, +eps] keeps its flow)."""
        rc_fwd = cost + p[cap_src] - p[cap_dst]
        return jnp.where(rc_fwd < -eps, cap, jnp.where(rc_fwd > eps, i32(0), flow))

    # Per-arc endpoints for the saturate step, recovered from the sorted
    # entries to avoid shipping src/dst twice: arc j's forward entry sits
    # at inv_order[j].
    fwd_pos = inv_order[:m]
    cap_src = s_src[fwd_pos]
    cap_dst = s_dst[fwd_pos]

    def tighten(flow, d0=None):
        """Price tightening: p = -(shortest residual-cost distance to a
        demand node), via synchronous Bellman-Ford sweeps over the sorted
        entries. Afterwards every residual arc between reachable nodes
        has nonnegative reduced cost, so the discharge can run at eps=1
        regardless of how flows/capacities changed since the last round —
        this is what makes warm restarts cheap and drift-free.

        With an explicit ``d0`` this is the warm-prologue REFIT instead:
        seeded from the carried prices, the relaxation only moves nodes
        whose residual out-arcs are violated (the dirty frontier), and
        the `changed` early-exit stops as soon as the frontier drains —
        a bounded Bellman sweep over the journal-touched subgraph,
        expressed data-parallel."""
        excess0 = excess_of(flow) if d0 is None else None
        a_flow = flow[s_arc]
        r = residual(a_flow)
        s_cost = s_sign * cost[s_arc]
        if d0 is None:
            d0 = jnp.where(excess0 < 0, i32(0), i32(_BIG_D))

        def t_cond(state):
            _d, changed, it = state
            return changed & (it < tighten_sweeps)

        def t_body(state):
            d, _, it = state
            cand = jnp.where(r > 0, s_cost + 1 + d[s_dst], i32(_BIG_D))
            best = _seg_min(cand, s_isstart, node_last, node_nonempty, i32(_BIG_D))
            # Clamp from below: a negative-cost residual cycle (possible
            # transiently with warm flows + changed costs) must not run d
            # toward int32 wraparound; the discharge handles the rest.
            d2 = jnp.maximum(jnp.minimum(d, best), -i32(_BIG_D))
            return d2, jnp.any(d2 != d), it + 1

        d, _, _ = lax.while_loop(t_cond, t_body, (d0, jnp.bool_(True), i32(0)))
        return -jnp.minimum(d, i32(_BIG_D))

    def superstep(flow, p, eps, excess):
        a_flow = flow[s_arc]
        r = residual(a_flow)
        s_cost = s_sign * cost[s_arc]
        rc = s_cost + p[s_src] - p[s_dst]
        e_at = excess[s_src]
        admissible = (r > 0) & (rc < 0) & (e_at > 0)

        # Maximal push: allocate each node's excess across its admissible
        # entries front-to-back via an in-segment exclusive prefix sum.
        r_adm = jnp.where(admissible, r, i32(0))
        cum = jnp.cumsum(r_adm)
        excl = cum - r_adm
        prefix_before = excl - excl[s_segstart]
        delta = jnp.clip(e_at - prefix_before, 0, r_adm)

        delta_orig = delta[inv_order]
        new_flow = flow + delta_orig[:m] - delta_orig[m:]

        # Relabel nodes that were active but pushed nothing (maximal push
        # guarantees active nodes with an admissible entry push >= 1).
        pushed = _seg_sum(delta, node_first, node_last, node_nonempty)
        sum_r = _seg_sum(r, node_first, node_last, node_nonempty)
        cand = jnp.where(r > 0, p[s_dst] - s_cost, -_BIG)
        best = _seg_max(cand, s_isstart, node_last, node_nonempty, -_BIG)
        relabel = (excess > 0) & (pushed == 0) & (sum_r > 0)
        new_p = jnp.where(relabel, best - eps, p)
        if not telemetry_cap:
            return new_flow, new_p, ()
        # counters over state this superstep already computed (soltel
        # row cols 3..6); purely observational, never fed back — and
        # appended AFTER the original dataflow so the telemetry-off
        # trace keeps the exact pre-telemetry op order (pinned hash).
        # Cost discipline: `pushed` is the already-reduced [N] per-node
        # push total (sum == sum(delta) since segments partition the
        # entries), and the saturated mask reuses r/s_sign — the only
        # NEW entry-space passes are two compare+sum sweeps, no
        # gathers (a zero-capacity arc counts as saturated: its
        # residual is zero, which is what the counter means).
        aux = (
            jnp.sum(pushed),
            jnp.sum(relabel.astype(i32)),
            jnp.sum(((s_sign > 0) & (r == 0)).astype(i32)),
            # r_adm > 0 <=> admissible (admissibility requires r > 0),
            # and r_adm is already materialized for the prefix cumsum
            jnp.sum((r_adm > 0).astype(i32)),
        )
        return new_flow, new_p, aux

    if telemetry_cap:
        from ksched_tpu.obs import soltel as _soltel

        _tel_rows_iota = _soltel.device_rows_iota(telemetry_cap)

    def tel_row(eps, excess, aux):
        active = jnp.sum((excess > 0).astype(i32))
        exc_pos = jnp.sum(jnp.maximum(excess, 0))
        return _soltel.device_row(eps, active, exc_pos, *aux)

    def tel_write(tel, steps, row):
        return _soltel.device_ring_write(
            tel, steps, row, telemetry_cap, _tel_rows_iota
        )

    def phase_cond(state):
        done = state[4]
        steps = state[3]
        return ~done & (steps < max_supersteps)

    def phase_body(state):
        if telemetry_cap:
            flow, p, eps, steps, done, tel = state
        else:
            flow, p, eps, steps, done = state
        excess = excess_of(flow)
        any_active = jnp.any(excess > 0)

        def do_superstep(_):
            f2, p2, aux = superstep(flow, p, eps, excess)
            if not telemetry_cap:
                return f2, p2, eps, steps + 1, jnp.bool_(False)
            tel2 = tel_write(tel, steps, tel_row(eps, excess, aux))
            return f2, p2, eps, steps + 1, jnp.bool_(False), tel2

        def next_phase(_):
            finished = eps <= 1
            new_eps = jnp.maximum(i32(1), eps // alpha)
            f2 = jnp.where(finished, flow, saturate(flow, p, new_eps))
            out = (f2, p, jnp.where(finished, eps, new_eps), steps, finished)
            return out + ((tel,) if telemetry_cap else ())

        return lax.cond(any_active, do_superstep, next_phase, operand=None)

    if use_warm_p:
        # dirty-frontier refit: Bellman sweeps seeded from the carried
        # prices (clipped into tighten's distance range so the relax
        # arithmetic cannot overflow int32)
        p0 = tighten(
            flow0, d0=jnp.clip(-warm_p, -i32(_BIG_D), i32(_BIG_D))
        )
    else:
        p0 = tighten(flow0)
    flow1 = saturate(flow0, p0, eps_init)  # mop up any residual violations
    state = (flow1, p0, eps_init, i32(0), jnp.bool_(False))
    if telemetry_cap:
        state = state + (jnp.zeros((telemetry_cap, SOLTEL_WIDTH), i32),)
        flow, p, eps, steps, done, tel = lax.while_loop(
            phase_cond, phase_body, state
        )
    else:
        flow, p, eps, steps, done = lax.while_loop(phase_cond, phase_body, state)
    converged = done & (jnp.max(jnp.abs(excess_of(flow))) == 0)
    p_overflow = jnp.max(jnp.abs(p)) >= _P_GUARD
    if telemetry_cap:
        return flow, p, steps, converged, p_overflow, tel
    return flow, p, steps, converged, p_overflow


# ---------------------------------------------------------------------------
# problems
# ---------------------------------------------------------------------------

#: every packed problem is padded to one shape, so the file compiles
#: each (warm, telemetry) program once and not once a problem
PACKED_N, PACKED_M = 64, 256
MAX_SUPERSTEPS = 20_000
TEL_CAP = 64

_ASSIGN = [
    (1, 3, 0, 1, 2), (2, 3, 0, 1, 2), (3, 4, 0, 1, 0), (3, 5, 0, 1, 4),
    (4, 6, 0, 1, 0), (5, 6, 0, 1, 0), (1, 7, 0, 1, 50), (2, 7, 0, 1, 50),
    (7, 6, 0, 2, 0),
]
_ESCAPE = [
    (1, 3, 0, 1, 2), (2, 3, 0, 1, 2), (3, 4, 0, 1, 0), (4, 6, 0, 1, 0),
    (1, 7, 0, 1, 5), (2, 7, 0, 1, 5), (7, 6, 0, 2, 0),
]


def _oracle_problem(name):
    """The hand-built instances of tests/test_solver_oracle.py."""
    if name == "single":
        return make_problem(4, {1: 1, 3: -1}, [(1, 2, 0, 1, 2), (2, 3, 0, 1, 3)])
    if name == "cheap":
        return make_problem(
            4, {1: 1, 3: -1}, [(1, 3, 0, 1, 10), (1, 2, 0, 1, 2), (2, 3, 0, 1, 3)]
        )
    if name == "split":
        return make_problem(
            4, {1: 2, 3: -2}, [(1, 3, 0, 9, 10), (1, 2, 0, 1, 2), (2, 3, 0, 9, 3)]
        )
    if name == "assign":
        return make_problem(8, {1: 1, 2: 1, 6: -2}, _ASSIGN)
    if name == "escape":
        return make_problem(8, {1: 1, 2: 1, 6: -2}, _ESCAPE)
    if name == "negative":
        return make_problem(
            4, {1: 1, 3: -1}, [(1, 2, 0, 1, -2), (2, 3, 0, 1, 3), (1, 3, 0, 1, 5)]
        )
    if name == "lower_bound":
        return make_problem(
            4, {1: 1, 3: -1}, [(1, 2, 1, 1, 7), (2, 3, 0, 1, 0), (1, 3, 0, 1, 1)]
        )
    raise AssertionError(name)


def _random_problem(seed):
    rng = np.random.default_rng(seed)
    return random_scheduling_problem(
        rng,
        num_tasks=int(rng.integers(8, 25)),
        num_machines=int(rng.integers(2, 6)),
        slots_per_machine=int(rng.integers(1, 4)),
    )


def _packed_inputs(problem):
    """Solver inputs in the packed `build_csr_plan` layout, padded to
    PACKED_N x PACKED_M with capacity-0 self-loops at node 0."""
    n, m = problem.num_nodes, len(problem.src)
    assert n <= PACKED_N and m <= PACKED_M
    pad = lambda a, size: np.concatenate(  # noqa: E731
        [np.asarray(a, np.int32), np.zeros(size - len(a), np.int32)]
    )
    src, dst = pad(problem.src, PACKED_M), pad(problem.dst, PACKED_M)
    plan = build_csr_plan(src, dst, PACKED_N)
    plan_args = (
        plan.s_arc, plan.s_sign, plan.s_src, plan.s_dst, plan.s_segstart,
        plan.s_isstart, plan.inv_order, plan.node_first, plan.node_last,
        plan.node_nonempty,
    )
    return dict(
        cap=pad(problem.cap, PACKED_M),
        cost=pad(problem.cost, PACKED_M) * np.int32(PACKED_N),
        supply=pad(problem.excess, PACKED_N),
        plan=tuple(jnp.asarray(x) for x in plan_args),
        slot_stable=False,
    )


def _slot_state(name):
    """A churned DeviceGraphState whose slot-stable plan holds dead
    rows (and, for `relocated`, a region moved into the tail pool)."""
    nt, nm = {"small": (8, 3), "churned": (24, 5), "relocated": (10, 4)}[name]
    g, _sink, machines, tasks = _build_graph(nt, nm)
    st = DeviceGraphState()
    st.full_build(g)
    st.plan.ensure_built()
    rng = np.random.default_rng(7)
    if name == "relocated":
        t = tasks[0]
        for d in machines + tasks[1:8]:
            if (t, d) not in st._arc_slot:
                st.apply_changes([NewArcChange(t, d, 0, 1, 3, ArcType.OTHER)])
        assert st.plan.region_relocations >= 1, "region never relocated"
    for kind in SCRIPT[:4]:  # cost, rewire, recycle, rewire
        _churn_round(st, kind, tasks, machines, rng)
    return st


def _slot_inputs(st):
    problem = st.problem()
    plan = st.plan.device_args()
    sign = np.asarray(plan[1])
    assert (sign == 0).any() and (sign != 0).any()
    n = problem.num_nodes
    return problem, dict(
        cap=problem.cap.astype(np.int32),
        cost=problem.cost.astype(np.int32) * np.int32(n),
        supply=problem.excess.astype(np.int32),
        plan=plan,
        slot_stable=True,
    )


PROBLEMS = (
    [f"packed-{k}" for k in
     ("single", "cheap", "split", "assign", "escape", "negative", "lower_bound")]
    + [f"packed-random{seed}" for seed in (0, 1, 2)]
    + [f"slot-{k}" for k in ("small", "churned", "relocated")]
)


@functools.lru_cache(maxsize=None)
def _inputs(name):
    layout, kind = name.split("-", 1)
    if layout == "slot":
        return _slot_inputs(_slot_state(kind))
    problem = (
        _random_problem(int(kind[len("random"):])) if kind.startswith("random")
        else _oracle_problem(kind)
    )
    return problem, _packed_inputs(problem)


def _run(fn, inp, flow0, eps, warm_p, telemetry_cap):
    out = fn(
        jnp.asarray(inp["cap"]), jnp.asarray(inp["cost"]), jnp.asarray(inp["supply"]),
        jnp.asarray(flow0), jnp.asarray(np.int32(eps)), *inp["plan"],
        warm_p=warm_p, use_warm_p=warm_p is not None,
        max_supersteps=MAX_SUPERSTEPS, telemetry_cap=telemetry_cap,
        slot_stable=inp["slot_stable"],
    )
    return [np.asarray(x) for x in out]


OUTPUTS = ("flow", "p", "steps", "converged", "p_overflow", "telemetry")


@pytest.mark.parametrize("telemetry_cap", [0, TEL_CAP], ids=["tel_off", "tel_on"])
@pytest.mark.parametrize("ladder", [False, True], ids=["eps1", "ladder"])
@pytest.mark.parametrize("warm", [False, True], ids=["cold", "refit"])
@pytest.mark.parametrize("name", PROBLEMS)
def test_entry_state_is_bit_identical_to_the_arc_state_program(
    name, warm, ladder, telemetry_cap
):
    problem, inp = _inputs(name)
    m = len(inp["cap"])
    flow0, warm_p = np.zeros(m, np.int32), None
    if warm:
        # the carried state of a round whose costs then moved: its flow
        # (clipped to capacity) and its prices, refitted by the prologue
        before = dict(inp, cost=inp["cost"] + (np.arange(m, dtype=np.int32) % 3) * np.int32(
            len(inp["supply"])))
        prev = _run(_solve_mcmf, before, flow0, 1, None, 0)
        assert prev[3], "the round before did not converge"
        flow0, warm_p = np.minimum(prev[0], inp["cap"]), jnp.asarray(prev[1])
    eps = max(1, int(np.abs(inp["cost"]).max())) if ladder else 1
    new = _run(_solve_mcmf, inp, flow0, eps, warm_p, telemetry_cap)
    old = _run(_solve_mcmf_frozen, inp, flow0, eps, warm_p, telemetry_cap)
    assert len(new) == len(old) == (6 if telemetry_cap else 5)
    for what, a, b in zip(OUTPUTS, new, old):
        assert a.dtype == b.dtype and a.shape == b.shape, what
        assert np.array_equal(a, b), f"{what} differs from the frozen program"
    flow, _p, steps, converged, p_overflow = new[:5]
    assert converged and not p_overflow
    assert warm or steps > 0, "a cold solve that ran no superstep tests nothing"
    if ladder and not warm:
        assert eps > 1, "a cold ladder needs a cost above 1"
    flow = flow[: len(problem.src)]
    objective = int(
        (flow.astype(np.int64) * problem.cost.astype(np.int64)).sum()
    ) + lower_bound_cost(problem)
    assert objective == ReferenceSolver().solve(problem).objective


# ---------------------------------------------------------------------------
# what a superstep gathers over the plan rows
# ---------------------------------------------------------------------------


def _gathers(jaxpr):
    """Every gather equation of a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


def _gathers_with_output(jaxpr, extent):
    """How many gathers of a jaxpr give a result of `extent` rows."""
    return sum(g.outvars[0].aval.shape[0] == extent for g in _gathers(jaxpr))


def _phase_branches(jaxpr):
    """The two branches of the phase loop's `cond` (next_phase,
    superstep), found as the only `cond` directly in a `while` body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            conds = [e for e in body.eqns if e.primitive.name == "cond"]
            if conds:
                (cond,) = conds
                return [b.jaxpr for b in cond.params["branches"]]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _phase_branches(sub)
            if found:
                return found
    return None


@pytest.mark.parametrize("slot_stable", [False, True], ids=["packed", "slot_stable"])
@pytest.mark.parametrize("telemetry_cap", [0, TEL_CAP], ids=["tel_off", "tel_on"])
def test_a_superstep_gathers_four_times_over_the_plan_rows_and_only_rows(
    slot_stable, telemetry_cap
):
    """The loop keeps its state where the superstep reads it: per
    superstep the plan rows are gathered into four times (the values
    at a row's head node, `p` and `excess` in one; `p` at its tail
    node; the prefix base; the partner's push) and the nodes twice
    (their first and their last row); per phase change the rows twice
    (`saturate`'s potentials). PR 28's program gathered into the plan
    rows eight times a superstep, into the arcs once and into the nodes
    thirteen times. And every gather of the program moves rows two or
    more wide: a gather of scalars is the element-by-element path
    (`_rows`). The sizes are chosen so that the extents differ."""
    n, m, e = 32, 64, 512 if slot_stable else 128
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    closed = jax.make_jaxpr(functools.partial(
        _solve_mcmf, telemetry_cap=telemetry_cap, slot_stable=slot_stable,
    ))(
        sds((m,)), sds((m,)), sds((n,)), sds((m,)), sds(()),
        sds((e,)), sds((e,)), sds((e,)), sds((e,)), sds((e,)),
        sds((e,), jnp.bool_), sds((2 * m,)),
        sds((n,)), sds((n,)), sds((n,), jnp.bool_),
    )
    next_phase, superstep = _phase_branches(closed.jaxpr)
    assert _gathers_with_output(superstep, e) == 4
    assert _gathers_with_output(superstep, n) == 2
    assert len(list(_gathers(superstep))) == 6
    assert _gathers_with_output(next_phase, e) == 2
    assert len(list(_gathers(next_phase))) == 4
    for g in _gathers(closed.jaxpr):
        assert g.params["slice_sizes"][-1] >= 2, "a gather of scalars"
