"""The rung's host half by name, the bytes both ways, what `stats`
gathers from and the collector's pauses (docs/observability.md).

A small scan-CSR service under a SpanTracer, synchronous, `--pipeline`
and `--device-resident`: every served round opens each leaf of the solve
once (`decode_set`, `solve_prepare`, `problem_upload` with the plan's
re-ship inside it as `plan_upload`, `solve_launch` before
`backend_solve`; `solve_wait`, `result_readback`, `result_unpack`,
`soltel_publish` inside it), the children of `backend_solve` fill it,
a retry is a second `solve_launch` / `solve_wait` pair,
`solve_h2d_bytes` is the `nbytes` a spy on `jnp.asarray` saw and what
the shapes say, `stats_children_gathered` is a count made another way,
a collection inside a round is a `gc_pause` and in `gc_pause_ms`, and
the hook leaves with the tracer. Placements and objectives under the
tracer are those of a service without one and of the reference solver.
"""

import gc

import jax.numpy as jnp
import numpy as np
import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
from ksched_tpu.costmodels import CostModelType
from ksched_tpu.drivers import build_cluster
from ksched_tpu.graph.device_export import FlowProblem
from ksched_tpu.obs import spans as spans_mod
from ksched_tpu.obs.spans import SpanTracer, span
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver import jax_solver
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.jax_solver import JaxSolver
from ksched_tpu.utils import seed_rng
from test_graph_worklist import _admit

MACHINES, PUS = 6, 2
#: rows of the telemetry ring the tests' solvers keep (tests/conftest.py
#: turns the default ring off), 8 int32 a row
RING = 64
#: leaf -> the span it is a child of, synchronous round (`solve` reads
#: `solve_dispatch` / `solve_sync` under --pipeline)
BEFORE = ("decode_set", "solve_prepare", "problem_upload", "solve_launch")
INSIDE = ("solve_wait", "result_readback", "result_unpack", "soltel_publish")
MODES = {
    "sync": {},
    "pipeline": {"pipeline": True},
    "resident": {"pipeline": True, "device_resident": True},
}


class AsarraySpy:
    """Sums the `nbytes` of the host arrays handed to `jnp.asarray`
    while it is on; a tracer's or a device array's conversion is not an
    upload and is not counted."""

    def __init__(self, monkeypatch):
        self.total = 0
        self.on = False
        real = jnp.asarray

        def asarray(x, *a, **kw):
            if self.on and isinstance(x, (np.ndarray, np.generic)):
                self.total += x.nbytes
            return real(x, *a, **kw)

        monkeypatch.setattr(jnp, "asarray", asarray)

    def take(self):
        total, self.total = self.total, 0
        return total


def _pods(tag, n):
    return [PodEvent(pod_id=f"{tag}_{i}", task_class=0) for i in range(n)]


def _service(backend, tracer=None, **kw):
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api, max_tasks_per_pu=4, cost_model=CostModelType.TRIVIAL, backend=backend,
        backend_name="jax" if isinstance(backend, JaxSolver) else "ref", tracer=tracer, **kw,
    )
    svc.init_topology(fake_machines=MACHINES, pus_per_core=PUS)
    return svc, api


def _stream(svc, api, each=None):
    """A fill, two batches with a completion between, a batch after an
    idle sweep; `each(round_index)` runs after every served round."""
    bound = []
    for i, (tag, n) in enumerate((("a", 9), ("b", 4), ("c", 3), ("d", 2))):
        if tag == "c":
            svc.complete_pod("a_0")
        if tag == "d":
            svc.run_round([], solve=False)
        bound.append(svc.run_round(_pods(tag, n)))
        if each is not None:
            each(i)
    svc.run_round([], solve=False)  # the pipeline's last Bindings go out
    api.close()
    return bound


def _bindings(svc):
    task_to_pod = {t: p for p, t in svc.pod_to_task.items()}
    return {task_to_pod[t]: rid for t, rid in svc.scheduler.task_bindings.items()}


@pytest.fixture(scope="module", params=sorted(MODES))
def traced(request):
    """Four served rounds under a tracer with a spy on `jnp.asarray`
    around each solve; then the events, the records, what the spy saw a
    round and the shapes at each solve."""
    mp = pytest.MonkeyPatch()
    seed_rng(0)
    spy = AsarraySpy(mp)
    tracer = SpanTracer().install()
    seen, shapes = [], []
    try:
        svc, api = _service(JaxSolver(telemetry=RING), RoundTracer(), **MODES[request.param])
        solver = svc.scheduler.solver
        jaxs = solver.backend.primary
        real_async, real_complete = jaxs.solve_async, jaxs.complete

        def solve_async(problem):
            spy.on = True
            try:
                return real_async(problem)
            finally:
                spy.on = False

        def complete(pending):
            spy.on = True
            try:
                return real_complete(pending)
            finally:
                spy.on = False

        jaxs.solve_async, jaxs.complete = solve_async, complete

        def each(_i):
            plan = solver.state.plan
            seen.append(spy.take())
            shapes.append((solver.state.n_cap, solver.state.m_cap, plan.entry_cap))

        bound = _stream(svc, api, each)
    finally:
        tracer.uninstall()
        mp.undo()
    assert bound == [9, 4, 3, 2]
    records = [r for r in svc.tracer.records if r.solver_rung >= 0]
    return request.param, svc, tracer.events(), records, seen, shapes


def _rounds(events):
    """The events of each served round, by name."""
    out = []
    for svc_round in sorted(
        (e for e in events if e["name"] == "service_round" and e["args"]["solve"]),
        key=lambda e: e["ts"],
    ):
        t0, t1 = svc_round["ts"], svc_round["ts"] + svc_round["dur"]
        by_name = {}
        for e in events:
            if t0 <= e["ts"] <= t1 and "sid" in e["args"]:
                by_name.setdefault(e["name"], []).append(e)
        out.append(by_name)
    return out


def test_every_served_round_opens_each_leaf_of_the_solve_once(traced):
    mode, svc, events, records, _seen, _shapes = traced
    by_sid = {e["args"]["sid"]: e for e in events if "sid" in e["args"]}
    rounds = _rounds(events)
    assert len(rounds) == len(records) == 4
    outer = "solve" if mode == "sync" else "solve_dispatch"
    for by_name in rounds:
        for name in BEFORE + INSIDE + ("backend_solve", "export_accounting"):
            assert len(by_name.get(name, ())) == 1, (mode, name)
        (backend,) = by_name["backend_solve"]
        for name in BEFORE:
            (ev,) = by_name[name]
            assert ev["args"]["parent"] == outer
            assert ev["ts"] + ev["dur"] <= backend["ts"]  # the rung starts before its wait
        for name in INSIDE:
            (ev,) = by_name[name]
            assert by_sid[ev["args"]["parent_sid"]] is backend
        assert by_name["export_accounting"][0]["args"]["parent"] == "graph_export"
        order = [by_name[n][0]["ts"] for n in BEFORE + INSIDE]
        assert order == sorted(order)
        plan_ships = by_name.get("plan_upload", [])
        if mode == "resident":
            # the mirror's own scatter, inside the export; the solve sends no plan
            assert all(e["args"]["parent"] == "graph_export" for e in plan_ships)
        else:
            (ship,) = plan_ships  # every served round dirties the plan
            assert ship["args"]["parent"] == "problem_upload"
            assert 0 < ship["args"]["bytes"] < by_name["problem_upload"][0]["args"]["bytes"]


def test_each_leaf_carries_the_size_it_worked_on(traced):
    mode, svc, events, records, _seen, shapes = traced
    for by_name, rec, (n_cap, m_cap, rows) in zip(_rounds(events), records, shapes):
        prep = by_name["solve_prepare"][0]["args"]
        assert (prep["arcs"], prep["nodes"]) == (m_cap, n_cap)
        assert prep["warm"] in ("cold", "fresh")  # a served round re-wires arcs: no carried flow
        assert by_name["decode_set"][0]["args"] == {
            **by_name["decode_set"][0]["args"], "tasks": rec.decode_tasks, "nodes": n_cap,
        }
        launch = by_name["solve_launch"][0]["args"]
        assert (launch["attempt"], launch["rows"]) == ("1", rows)
        wait = by_name["solve_wait"][0]["args"]
        assert (wait["attempt"], wait["supersteps"]) == ("1", rec.solver_work)
        assert by_name["result_unpack"][0]["args"]["arcs"] == m_cap
        assert by_name["soltel_publish"][0]["args"]["rows"] == min(rec.solver_work, RING)
        down = by_name["result_readback"][0]["args"]["bytes"]
        assert down == 4 * m_cap + 4 * 8 * RING  # the flow and the telemetry ring
        assert rec.solve_d2h_bytes == down + 4 + 1 + 1  # steps, converged, overflow


def test_the_children_of_backend_solve_fill_it(traced):
    _mode, _svc, events, _records, _seen, _shapes = traced
    gaps = []
    for by_name in _rounds(events):
        (backend,) = by_name["backend_solve"]
        inside = sum(by_name[n][0]["dur"] for n in INSIDE)
        gaps.append((backend["dur"] - inside) / 1e3)
        for n in INSIDE:
            (ev,) = by_name[n]
            assert backend["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= backend["ts"] + backend["dur"]
    # what is left is the cost of opening and recording four spans
    assert min(gaps) >= 0.0 and min(gaps) < 0.2, gaps


def test_solve_h2d_bytes_is_what_jnp_asarray_was_handed_and_what_the_shapes_say(traced):
    mode, svc, events, records, seen, shapes = traced
    for by_name, rec, spied, (n_cap, m_cap, rows) in zip(_rounds(events), records, seen, shapes):
        up = by_name["problem_upload"][0]["args"]["bytes"]
        if mode == "resident":
            # the warm flow and the prices stay on the device: eps alone
            assert up == 4 and rec.upload_bytes > 0
            assert rec.solve_h2d_bytes == rec.upload_bytes + 4
            continue
        assert rec.upload_bytes == 0
        assert rec.solve_h2d_bytes == up == spied
        problem = 4 * (3 * m_cap + n_cap) + 4  # cap, cost, warm flow; supply; eps
        values = 4 * (4 * rows + 2 * m_cap)  # p_arc, p_sign, p_src, p_dst; inv_order
        # seg_start and is_start (a bool a row); node_first, node_last, node_nonempty (a bool)
        static = (4 + 1) * rows + (4 + 4 + 1) * n_cap
        ship = by_name["plan_upload"][0]["args"]
        assert ship["kind"] in ("values", "static_and_values")
        assert ship["bytes"] == values + (static if ship["kind"] == "static_and_values" else 0)
        assert up == problem + ship["bytes"]


def test_placements_and_objectives_are_those_of_an_untraced_service_and_of_the_reference(traced):
    mode, svc, _events, records, _seen, _shapes = traced
    assert spans_mod.active_tracer() is None
    seed_rng(0)
    plain, api = _service(JaxSolver(telemetry=RING), RoundTracer(), **MODES[mode])
    assert _stream(plain, api) == [9, 4, 3, 2]
    seed_rng(0)
    ref, api = _service(ReferenceSolver(), RoundTracer())
    assert _stream(ref, api) == [9, 4, 3, 2]
    assert _bindings(svc) == _bindings(plain)
    plain_records = [r for r in plain.tracer.records if r.solver_rung >= 0]
    ref_records = [r for r in ref.tracer.records if r.solver_rung >= 0]
    for mine, theirs, want in zip(records, plain_records, ref_records):
        assert mine.solver_work == theirs.solver_work  # the same supersteps
        assert (mine.solve_h2d_bytes, mine.solve_d2h_bytes) == (
            theirs.solve_h2d_bytes, theirs.solve_d2h_bytes,
        )  # counted with or without a tracer
        assert mine.num_scheduled == want.num_scheduled
        assert mine.gc_pause_ms >= 0.0 and theirs.gc_pause_ms == 0.0  # no tracer, no hook
    assert svc.scheduler.last_timing.objective == plain.scheduler.last_timing.objective
    assert svc.scheduler.last_timing.objective == ref.scheduler.last_timing.objective


# -- retries -------------------------------------------------------------------


def _chain_problem():
    """Three units from node 1 to node 4 over two routes (row 0 pads)."""
    src = np.array([1, 1, 2, 3], np.int32)
    dst = np.array([2, 3, 4, 4], np.int32)
    return FlowProblem(
        num_nodes=5, num_arcs=4, src=src, dst=dst, node_type=np.zeros(5, np.int8),
        cap=np.array([2, 2, 2, 2], np.int32), cost=np.array([1, 3, 1, 1], np.int32),
        excess=np.array([0, 3, 0, 0, -3], np.int64), flow_offset=np.zeros(4, np.int32),
    )


def _fail_first_attempt(monkeypatch):
    """`_solve_mcmf` reports its first call of a solve as not converged."""
    real = jax_solver._solve_mcmf
    calls = []

    def flaky(*a, **kw):
        out = real(*a, **kw)
        calls.append(kw)
        if len(calls) == 1:
            out = (*out[:3], jnp.asarray(False), *out[4:])
        return out

    monkeypatch.setattr(jax_solver, "_solve_mcmf", flaky)
    return calls


@pytest.mark.parametrize("budget, attempt", [(None, "cold"), (64, "1b")])
def test_a_retry_is_a_second_launch_and_wait_with_its_attempt(monkeypatch, budget, attempt):
    problem = _chain_problem()
    solver = JaxSolver(restart_budget=budget, telemetry=RING)
    want = solver.solve(problem)  # and the state a warm attempt carries
    calls = _fail_first_attempt(monkeypatch)
    with SpanTracer() as tracer:
        with span("backend_solve"):
            got = solver.solve(problem)
    assert got.objective == want.objective == ReferenceSolver().solve(problem).objective
    assert len(calls) == 2
    events = sorted(tracer.events(), key=lambda e: e["ts"])
    names = [e["name"] for e in events if e["name"] in ("solve_launch", "solve_wait")]
    assert names == ["solve_launch", "solve_wait", "solve_launch", "solve_wait"]
    attempts = [e["args"]["attempt"] for e in events if e["name"] == "solve_launch"]
    assert attempts == ["1", attempt]
    assert [e["args"]["attempt"] for e in events if e["name"] == "solve_wait"] == attempts
    assert [e["args"]["warm"] for e in events if e["name"] == "solve_prepare"] == ["warm"]
    # the retry's zero flow and eps went up too, and its scalars came down
    m, n = len(problem.src), problem.num_nodes
    assert solver.last_h2d_bytes == (4 * (3 * m + n) + 4) + (4 * m + 4)
    one_attempt = 4 + 1 + 1
    ring = 4 * 8 * RING
    assert solver.last_d2h_bytes == 2 * one_attempt + ring + 4 * m
    # the cached plan stood: nothing of it went up again
    assert not [e for e in events if e["name"] == "plan_upload"]


def test_a_plain_array_problem_ships_its_plan_once_under_plan_upload():
    problem = _chain_problem()
    solver = JaxSolver()
    with SpanTracer() as tracer:
        solver.solve(problem)
        first = solver.last_h2d_bytes
        solver.solve(problem)
    ships = [e for e in tracer.events() if e["name"] == "plan_upload"]
    assert [e["args"]["kind"] for e in ships] == ["csr_build"]
    assert ships[0]["args"]["parent"] == "problem_upload"
    m, n = len(problem.src), problem.num_nodes
    assert first == 4 * (3 * m + n) + 4 + ships[0]["args"]["bytes"]
    assert solver.last_h2d_bytes == 4 * (3 * m + n) + 4  # the cached plan stands


def test_with_no_tracer_nothing_is_recorded_and_the_counters_are_filled():
    assert spans_mod.active_tracer() is None
    solver = JaxSolver()
    solver.solve(_chain_problem())
    assert solver.last_h2d_bytes > 0 and solver.last_d2h_bytes > 0
    assert solver.last_warm_scope == "cold"


# -- what `stats` gathers from -------------------------------------------------


def _tree(machines=5, cores=2, pus=3):
    seed_rng(3)
    sched, rmap, jmap, tmap, _root = build_cluster(
        num_machines=machines, num_cores=cores, pus_per_core=pus, max_tasks_per_pu=4,
    )
    return sched, rmap, jmap, tmap


def _count_gathers(sched):
    """Every call of the model's gather hook from here on."""
    calls = []
    real = sched.cost_model.gather_stats

    def gather(accumulator, other):
        calls.append((accumulator.id, other.id))
        return real(accumulator, other)

    sched.cost_model.gather_stats = gather
    return calls


def _arcs_a_full_walk_goes_over(gm):
    """The incoming arcs of every node reachable backwards from the sink
    without passing a task."""
    seen, stack, arcs = {gm.sink_node.id}, [gm.sink_node], 0
    while stack:
        node = stack.pop()
        arcs += len(node.incoming)
        for arc in node.incoming.values():
            src = arc.src_node
            if src.task is None and src.id not in seen:
                seen.add(src.id)
                stack.append(src)
    return arcs


def test_stats_children_gathered_is_a_brute_count_on_a_hand_built_tree():
    machines, cores, pus = 5, 2, 3
    sched, rmap, jmap, tmap = _tree(machines, cores, pus)
    calls = _count_gathers(sched)
    _admit(sched, jmap, tmap, 7, range(1, 5))
    assert sched.schedule_all_jobs()[0] == 4
    t = sched.last_timing
    # the first pass walks every node, and counts the arcs it went over
    # (the graph at that pass held no task yet: each arc was gathered)
    assert t.stats_full_walk == 1
    assert t.stats_children_gathered == len(calls) > machines * cores * pus
    del calls[:]
    _admit(sched, jmap, tmap, 7, range(11, 13))
    assert sched.schedule_all_jobs()[0] == 2
    t = sched.last_timing
    assert t.stats_full_walk == 0 and 0 < t.stats_pus_dirty <= 4
    # a patched pass: each dirty PU from the sink, each ancestor from ALL its children
    assert t.stats_children_gathered == len(calls)
    gm = sched.gm
    dirty_pus = {acc for acc, other in calls if other == gm.sink_node.id}
    ancestors = set()
    for pu in dirty_pus:
        node = gm.cm.graph.node(pu)
        while (node := gm.node_to_parent_node.get(node.id)) is not None:
            ancestors.add(node.id)
    assert t.stats_pus_dirty == len(dirty_pus)
    assert t.stats_nodes_visited == len(dirty_pus) + len(ancestors)
    assert t.stats_children_gathered == len(dirty_pus) + sum(
        len(gm.cm.graph.node(a).outgoing) for a in ancestors
    )
    # the coordinator re-read every machine for at most four dirty paths
    assert t.stats_children_gathered >= machines + len(dirty_pus)
    # and a pass that walks every node again counts what the walk goes over
    gm._stats_topology_changed = True
    want = _arcs_a_full_walk_goes_over(gm)
    _admit(sched, jmap, tmap, 7, range(21, 22))
    assert sched.schedule_all_jobs()[0] == 1
    t = sched.last_timing
    assert (t.stats_full_walk, t.stats_children_gathered) == (1, want)


def test_the_stats_span_and_the_round_record_carry_the_count():
    seed_rng(0)
    tracer = SpanTracer().install()
    try:
        svc, api = _service(JaxSolver(telemetry=RING), RoundTracer())
        svc.run_round(_pods("a", 6))
        svc.run_round(_pods("b", 3))
    finally:
        tracer.uninstall()
    stats = sorted((e for e in tracer.events() if e["name"] == "stats"), key=lambda e: e["ts"])
    records = [r for r in svc.tracer.records if r.solver_rung >= 0]
    assert [e["args"]["stats_children_gathered"] for e in stats] == [
        r.stats_children_gathered for r in records
    ]
    full, patched = records
    assert (full.stats_full_walk, patched.stats_full_walk) == (1, 0)
    # MACHINES children of the coordinator, one core a machine, PUS a core
    assert patched.stats_children_gathered == patched.stats_pus_dirty + sum(
        {1: PUS, 2: 1, 3: MACHINES}[depth]
        for depth in _ancestor_depths(svc, patched.stats_nodes_visited - patched.stats_pus_dirty)
    )


def _ancestor_depths(svc, want):
    """Depths (1: core, 2: machine, 3: coordinator) of the ancestors of
    the PUs the first batch was bound to: the lists that changed between
    the first pass and the second."""
    gm = svc.scheduler.gm
    depths = {}
    for i in range(6):
        rid = svc.scheduler.task_bindings[svc.pod_to_task[f"a_{i}"]]
        node, depth = gm.resource_to_node[rid], 0
        while (node := gm.node_to_parent_node.get(node.id)) is not None:
            depth += 1
            depths[node.id] = depth
    assert len(depths) == want
    return depths.values()


# -- the collector's pauses ----------------------------------------------------


def test_a_collection_inside_a_round_is_a_gc_pause_and_in_gc_pause_ms():
    seed_rng(0)
    assert spans_mod._gc_hook not in gc.callbacks
    tracer = SpanTracer().install()
    try:
        assert spans_mod._gc_hook in gc.callbacks
        svc, api = _service(JaxSolver(telemetry=RING), RoundTracer())
        svc.run_round(_pods("a", 6))
        real = svc.scheduler.gm.add_or_update_job_nodes

        def update_and_collect(jds):
            real(jds)
            gc.collect()

        svc.scheduler.gm.add_or_update_job_nodes = update_and_collect
        svc.run_round(_pods("b", 3))
        svc.scheduler.gm.add_or_update_job_nodes = real
        svc.run_round(_pods("c", 2))
    finally:
        tracer.uninstall()
    assert spans_mod._gc_hook not in gc.callbacks  # the hook left with the tracer
    events = tracer.events()
    rounds = sorted(
        (e for e in events if e["name"] == "service_round"), key=lambda e: e["ts"]
    )
    forced = [
        e for e in events
        if e["name"] == "gc_pause" and e["args"]["generation"] == 2
        and rounds[1]["ts"] <= e["ts"] <= rounds[1]["ts"] + rounds[1]["dur"]
    ]
    assert len(forced) == 1
    (pause,) = forced
    assert pause["args"]["parent"] == "graph_update" and "sid" in pause["args"]
    assert pause["dur"] / 1e6 >= spans_mod.GC_PAUSE_FLOOR_S
    records = [r for r in svc.tracer.records if r.solver_rung >= 0]
    assert records[1].gc_pause_ms >= pause["dur"] / 1e3 > 0.0
    # a round's record holds its own pauses, not the one before it
    assert records[2].gc_pause_ms < records[1].gc_pause_ms
    # and a collection with no tracer installed is nobody's
    before = spans_mod.gc_pause_total_s()
    gc.collect()
    assert spans_mod.gc_pause_total_s() == before


def test_the_hook_stays_while_any_tracer_is_installed_and_takes_no_lock():
    outer = SpanTracer().install()
    inner = SpanTracer().install()
    inner.uninstall()
    assert spans_mod._gc_hook in gc.callbacks and spans_mod.active_tracer() is outer
    # a collection while the ring's lock is held: the pause waits on the
    # pending list and the next record takes it into the ring
    with outer._lock:
        gc.collect()
    assert not [e for e in outer._events if e["name"] == "gc_pause"]
    with span("after"):
        pass
    outer.uninstall()
    assert spans_mod._gc_hook not in gc.callbacks
    names = [e["name"] for e in outer.events()]
    assert "gc_pause" in names and names.index("gc_pause") < names.index("after")
