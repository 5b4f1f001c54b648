"""The spans one level below `graph_update`, `collapse_audit` and
`graph_export`, the span on the EC purge, and the four counters that go
with them (docs/observability.md).

A small served cluster under a SpanTracer: every new span lies inside
its parent in time and by `parent_sid`, the children never exceed the
parent, a refused audit closes what it opened, each counter equals a
count made another way, a round opens a `res_refresh` span for each run
of resource-node turns and not for each node (where it takes such turns:
a model that prices its resource arcs at constants gets none, and the
served models all do, so the walk is switched back on where its span is
what is tested), and with no tracer installed nothing is recorded while
`RoundTiming` is filled as before.
Placements, problems and objectives under a tracer are those of the
root-down walk, which opens none of these spans.
"""

import random

import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
from ksched_tpu.costmodels import CostModelType, TrivialCostModel
from ksched_tpu.data import TaskType
from ksched_tpu.drivers import build_cluster
from ksched_tpu.obs import spans as spans_mod
from ksched_tpu.obs.spans import SpanTracer, span
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.graph_collapse import AutoSolver, try_collapse
from ksched_tpu.utils import seed_rng
from test_graph_worklist import MODELS, _admit, _filled_cluster, _same_problem, _World

AUDIT = (
    "audit_index", "audit_pins", "audit_subtrees", "audit_task_arcs", "audit_ec_routes",
    "audit_escapes", "audit_rows",
)
#: new span -> the span it is a child of
PARENT = {
    "task_refresh": "graph_update", "res_refresh": "graph_update",
    **{name: "collapse_audit" for name in AUDIT},
    "journal_collect": "graph_export", "journal_apply": "graph_export",
    "problem_snapshot": "graph_export", "ec_purge": "round",
}
#: the sizes each pass of the audit carries
AUDIT_ARG = {
    "audit_index": ("arcs",), "audit_pins": ("pins", "walked"), "audit_subtrees": ("nodes",),
    "audit_task_arcs": ("tasks",), "audit_ec_routes": ("ecs",), "audit_escapes": ("tasks",),
    "audit_rows": ("rows",),
}
MACHINES, PUS = 6, 2
#: what the cluster EC's sweep queues, and each node its children:
#: machine, core, PUs; nothing points at the coordinator
RES_NODES = MACHINES * (1 + 1 + PUS)


def _by_name(events):
    out = {}
    for ev in events:
        out.setdefault(ev["name"], []).append(ev)
    return out


def _end(ev):
    return ev["ts"] + ev["dur"]


def _auto_service(model=CostModelType.TRIVIAL, **kw):
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api, max_tasks_per_pu=4, cost_model=model, backend=AutoSolver(ReferenceSolver()),
        backend_name="auto", tracer=RoundTracer(), **kw,
    )
    svc.init_topology(fake_machines=MACHINES, pus_per_core=PUS)
    return svc, api


def _pods(tag, n, classes=1):
    return [PodEvent(pod_id=f"{tag}_{i}", task_class=i % classes) for i in range(n)]


@pytest.fixture(scope="module", params=["sync", "pipeline", "as_served"])
def traced(request):
    """Four solved rounds of a six-machine service whose every round
    collapses: a fill, two batches with a completion between, and a
    batch after two idle rounds; then the events and the records.
    `as_served` is `sync` as the trivial model is served, with no
    resource-node turn; the other two put every turn back, so that the
    `res_refresh` span has something to time."""
    seed_rng(0)
    tracer = SpanTracer().install()
    try:
        svc, api = _auto_service(pipeline=request.param == "pipeline")
        svc.scheduler.gm._res_turns = request.param != "as_served"
        bound = [svc.run_round(_pods("a", 9))]
        bound.append(svc.run_round(_pods("b", 4)))
        svc.complete_pod("a_0")
        bound.append(svc.run_round(_pods("c", 3)))
        svc.run_round([], solve=False)
        bound.append(svc.run_round(_pods("d", 2)))
        svc.run_round([], solve=False)  # the pipeline's last Bindings go out
        api.close()
    finally:
        tracer.uninstall()
    assert bound == [9, 4, 3, 2]
    records = [r for r in svc.tracer.records if r.solver_rung >= 0]
    return svc, tracer.events(), records


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_new_span_lies_inside_its_parent_in_time_and_by_parent_sid(traced, name):
    svc, events, records = traced
    by_sid = {e["args"]["sid"]: e for e in events}
    got = _by_name(events).get(name, [])
    if name == "res_refresh" and not svc.scheduler.gm._res_turns:
        assert not got  # no turn, and no empty span kept in its place
        return
    assert got, name
    for ev in got:
        parent = by_sid[ev["args"]["parent_sid"]]
        assert (parent["name"], ev["args"]["parent"]) == (PARENT[name], PARENT[name])
        assert parent["ts"] <= ev["ts"] and _end(ev) <= _end(parent) + 1e-3
    if name in ("task_refresh", "res_refresh"):
        return  # one a run: counted below
    # the others open once a solved round; the first builds the arrays
    # whole and has no journal to collect
    assert len(records) == 4 and len(got) == (3 if name == "journal_collect" else 4)


@pytest.mark.parametrize("parent", ["graph_update", "collapse_audit", "graph_export"])
def test_the_children_of_a_span_follow_one_another_and_do_not_exceed_it(traced, parent):
    _svc, events, _records = traced
    family = [n for n, p in PARENT.items() if p == parent]
    if parent == "graph_update":
        family += ["ec_refresh", "ec_chain_refresh"]
    parents = _by_name(events)[parent]
    assert len(parents) == 4
    for outer in parents:
        kids = sorted(
            (e for e in events
             if e["name"] in family and e["args"]["parent_sid"] == outer["args"]["sid"]),
            key=lambda e: e["ts"],
        )
        assert kids
        for a, b in zip(kids, kids[1:]):
            assert _end(a) <= b["ts"] + 1e-3  # no two overlap
        assert sum(e["dur"] for e in kids) <= outer["dur"] + 1e-3
        if parent == "collapse_audit":
            assert [e["name"] for e in kids] == list(AUDIT)
            assert all(arg in e["args"] for e in kids for arg in AUDIT_ARG[e["name"]])


def test_the_audits_sizes_are_the_problems(traced):
    svc, events, _records = traced
    last = {n: sorted(_by_name(events)[n], key=lambda e: e["ts"])[-1]["args"] for n in AUDIT}
    problem = svc.scheduler.solver.state.problem()
    assert last["audit_index"]["arcs"] == len(problem.src)
    assert last["audit_subtrees"]["nodes"] == problem.num_nodes
    # the last round's two pods, one row (one class, one escape cost), the
    # trivial model's one EC; every resident pod is a folded pin, which the
    # export has routed to the sink before the audit looks (PR 52)
    assert (last["audit_task_arcs"]["tasks"], last["audit_escapes"]["tasks"]) == (2, 2)
    assert (last["audit_ec_routes"]["ecs"], last["audit_rows"]["rows"]) == (1, 1)
    assert last["audit_pins"]["pins"] == 0
    held = sorted(_by_name(events)["graph_export"], key=lambda e: e["ts"])[-1]["args"]["supply_prerouted"]
    assert held == svc.scheduler.solver.state.supply_prerouted >= 1


def test_no_served_round_walks_a_pin(traced):
    """A preemption-off service pins its pods to PUs, whose one arc ends
    at the sink: since PR 52 the export routes every one (`graph_export`'s
    `supply_prerouted`, the pods bound so far), so the audit finds no
    resource node with excess in any round and walks none."""
    _svc, events, records = traced
    pins = sorted(_by_name(events)["audit_pins"], key=lambda e: e["ts"])
    assert [e["args"]["walked"] for e in pins] == [0, 0, 0, 0]
    assert [e["args"]["pins"] for e in pins] == [0, 0, 0, 0]
    exports = sorted(_by_name(events)["graph_export"], key=lambda e: e["ts"])
    held = [e["args"]["supply_prerouted"] for e in exports]
    assert held[0] == 0  # the fill round finds an empty cluster
    assert all(1 <= a <= b for a, b in zip(held[1:], held[2:])) and len(held) == 4
    assert held == [r.supply_prerouted for r in records]


def test_a_refused_audit_closes_every_span_it_opened():
    """Running tasks that keep arcs to their leaves (preemption) are
    refused in `audit_task_arcs`: the passes before it and that one are
    recorded, closed, and the next span has no stale parent."""
    from tests.test_scheduler_backends import add_job

    sched, _rmap, jmap, tmap, _root = build_cluster(
        num_machines=3, num_cores=2, backend=ReferenceSolver(), preemption=True,
    )
    add_job(sched, jmap, tmap, num_tasks=4)
    sched.schedule_all_jobs()
    add_job(sched, jmap, tmap, num_tasks=4)
    sched.schedule_all_jobs()
    problem = sched.solver.state.problem()
    with SpanTracer() as tracer:
        with span("collapse_audit"):
            collapse, reason = try_collapse(problem)
        with span("after"):
            pass
    assert collapse is None and "leaf/keep-mode" in reason
    events = tracer.events()
    names = [e["name"] for e in events]
    assert names == list(AUDIT[:4]) + ["collapse_audit", "after"]
    audit = events[-2]
    for ev in events[:4]:
        assert ev["args"]["parent_sid"] == audit["args"]["sid"] and "error" not in ev["args"]
        assert audit["ts"] <= ev["ts"] and _end(ev) <= _end(audit) + 1e-3
    assert "parent_sid" not in events[-1]["args"]
    assert spans_mod._current.get() is None


def test_a_round_opens_a_span_for_each_run_of_turns_not_for_each_node(traced):
    svc, events, records = traced
    turns = svc.scheduler.gm._res_turns
    by_name = _by_name(events)
    updates = sorted(by_name["graph_update"], key=lambda e: e["ts"])
    for outer, rec in zip(updates, records):
        inside = [e for e in events if e["args"].get("parent_sid") == outer["args"]["sid"]]
        res = [e for e in inside if e["name"] == "res_refresh"]
        tasks = [e for e in inside if e["name"] == "task_refresh"]
        # the batch's tasks (in the fill round the job's root, whose turn
        # queues the one EC ahead of its children's), the EC, then every
        # resource node the sweep queued in one run: none as served
        assert len(tasks) in (1, 2) and len(res) == int(turns)
        assert sum(e["args"]["nodes"] for e in res) == rec.res_nodes_visited
        assert rec.res_nodes_visited == (RES_NODES if turns else 0)
        assert sum(e["args"]["tasks"] for e in tasks) >= rec.graph_tasks_visited >= 2
        assert sum(e["args"]["arcs_changed"] for e in res) == rec.res_arcs_changed
        assert (outer["args"]["res_nodes_visited"], outer["args"]["res_arcs_changed"]) == (
            rec.res_nodes_visited, rec.res_arcs_changed,
        )
    # a solved round records far fewer new events than it visits nodes
    new = [e for e in events if e["name"] in PARENT]
    assert len(new) <= 4 * (len(PARENT) + 2) < 4 * 40


class _Interleaved(TrivialCostModel):
    """No cluster-wide EC: each task prefers two PUs directly, so a
    deeper job's task turns and the resource turns they queue alternate.
    It disclaims the trivial model's constant resource prices: a model
    that claims them has no resource turn to alternate with."""

    resource_arc_costs_are_fixed = False

    def get_task_equiv_classes(self, task_id):
        return []

    def get_task_preference_arcs(self, task_id):
        leaves = sorted(self.leaf_resource_ids)
        return [leaves[task_id % len(leaves)], leaves[(task_id + 1) % len(leaves)]]


def test_runs_alternate_where_the_fifo_alternates_and_their_sizes_add_up():
    seed_rng(4)
    sched, _rmap, jmap, tmap, _root = build_cluster(
        num_machines=4, num_cores=1, pus_per_core=2, max_tasks_per_pu=8,
        backend=ReferenceSolver(), cost_model_factory=_Interleaved,
    )
    _admit(sched, jmap, tmap, 7, [1, 2, 3])
    _admit(sched, jmap, tmap, 7, [11, 12], parent_uid=2)  # a level below the root's children
    with SpanTracer() as tracer:
        placed, _ = sched.schedule_all_jobs()
    assert placed == 5
    (update,) = _by_name(tracer.events())["graph_update"]
    runs = sorted(
        (e for e in tracer.events() if e["args"].get("parent_sid") == update["args"]["sid"]),
        key=lambda e: e["ts"],
    )
    kinds = [e["name"] for e in runs]
    assert set(kinds) == {"task_refresh", "res_refresh"}
    assert all(a != b for a, b in zip(kinds, kinds[1:]))  # a run ends where the kind changes
    assert kinds.count("res_refresh") >= 2  # the PUs each level queued
    gm = sched.gm
    assert sum(e["args"]["nodes"] for e in runs if e["name"] == "res_refresh") == gm.res_nodes_visited
    # phase 0 and phase 1 turns: every listed task is updated once, the
    # four below the root are added in their parent's turn
    assert sum(e["args"]["tasks"] for e in runs if e["name"] == "task_refresh") == 5 + 4
    assert 0 < gm.res_nodes_visited < len(gm.resource_to_node)


class _RisingSinkCost(TrivialCostModel):
    """Re-prices the sink arc of every third PU each round; overriding
    the method drops the trivial model's claim of constant prices."""

    rounds = 0

    def leaf_resource_node_to_sink_cost(self, resource_id):
        return self.rounds if resource_id % 3 == 0 else 0

    def note_round(self, unscheduled_task_ids):
        self.rounds += 1


def _resource_arcs(gm):
    return {
        (arc.src, arc.dst): (arc.cap_lower, arc.cap_upper, arc.cost)
        for node in gm.resource_to_node.values() for arc in node.outgoing.values()
    }


@pytest.mark.parametrize("model", [TrivialCostModel, _RisingSinkCost], ids=["flat", "rising"])
def test_the_resource_counters_are_a_sweeps_nodes_and_the_arcs_that_really_changed(model):
    sched, _rmap, jmap, tmap = _filled_cluster(80, model=model, backend=ReferenceSolver())
    gm = sched.gm
    update = gm.add_or_update_job_nodes
    seen = []

    def counting(jobs):
        before = _resource_arcs(gm)
        update(jobs)
        after = _resource_arcs(gm)
        seen.append(sum(after[k] != before.get(k) for k in after))

    gm.add_or_update_job_nodes = counting
    for r in range(1, 4):
        _admit(sched, jmap, tmap, 7, range(1000 * r, 1000 * r + 5))
        placed, _ = sched.schedule_all_jobs()
        assert placed == 5
        t = sched.last_timing
        # a model that may re-price: the EC's sweep queues every machine, and
        # each node its children, every resource node but the coordinator;
        # one whose prices are constants: no resource node takes a turn
        rising = model is _RisingSinkCost
        assert model.resource_arc_costs_are_fixed != rising
        assert t.res_nodes_visited == gm.res_nodes_visited == (
            len(gm.resource_to_node) - 1 if rising else 0
        )
        assert t.res_arcs_changed == gm.res_arcs_changed == seen[-1]
    thirds = sum(rid % 3 == 0 for rid in gm.leaf_resource_ids)
    assert seen[-1] == (thirds if rising else 0)
    assert thirds > 0


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipeline"])
def test_journal_changes_is_the_length_of_the_journal_the_export_applied(pipeline):
    sched, _rmap, jmap, tmap = _filled_cluster(40, backend=ReferenceSolver())
    cm = sched.gm.cm
    optimized, lengths = cm.get_optimized_graph_changes, []

    def recording():
        changes = optimized()
        lengths.append(len(changes))
        return changes

    cm.get_optimized_graph_changes = recording
    with SpanTracer() as tracer:
        for r in range(1, 4):
            _admit(sched, jmap, tmap, 7, range(1000 * r, 1000 * r + 3))
            if pipeline:
                assert sched.schedule_all_jobs_async() is not None
                sched.finish_scheduling()
            else:
                sched.schedule_all_jobs()
            want = lengths[-1] if r > 1 else 0  # the first export builds the arrays whole
            assert sched.last_timing.journal_changes == sched.solver.journal_changes == want
    assert len(lengths) == 2 and min(lengths) > 3
    by_name = _by_name(tracer.events())
    applied = sorted(by_name["journal_apply"], key=lambda e: e["ts"])
    assert [(e["args"]["kind"], e["args"]["changes"]) for e in applied] == [
        ("full_build", 0), ("delta", lengths[0]), ("delta", lengths[1]),
    ]
    assert [e["args"]["changes"] for e in by_name["journal_collect"]] == lengths
    assert len(by_name["problem_snapshot"]) == 3
    parent = "solve_dispatch" if pipeline else "solve"
    assert {e["args"]["parent"] for e in by_name["graph_export"]} == {parent}


def test_the_resident_export_takes_the_same_snapshot_beside_its_own_children():
    from ksched_tpu.solver.jax_solver import JaxSolver

    seed_rng(2)
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api, max_tasks_per_pu=4, backend=JaxSolver(), backend_name="jax", tracer=RoundTracer(),
        pipeline=True, device_resident=True,
    )
    svc.init_topology(fake_machines=3, pus_per_core=2)
    with SpanTracer() as tracer:
        assert svc.run_round(_pods("a", 4)) == 4
        assert svc.run_round(_pods("b", 2)) == 2
        svc.run_round([], solve=False)
    by_name = _by_name(tracer.events())
    assert len(by_name["journal_apply"]) == 2 and len(by_name["journal_collect"]) == 1
    assert len(by_name["problem_snapshot"]) == 2  # the host consumers' copy, before the deltas ship
    exports = {e["args"]["sid"] for e in by_name["graph_export"]}
    for name in ("journal_collect", "journal_apply", "problem_snapshot", "delta_upload"):
        assert {e["args"]["parent_sid"] for e in by_name[name]} <= exports
    rec = [r for r in svc.tracer.records if r.solver_rung >= 0][-1]
    assert rec.journal_changes == by_name["journal_collect"][0]["args"]["changes"] > 0


def test_ec_purged_is_the_ec_nodes_before_less_those_after():
    """Two workloads under `k8s_antiaffinity`: the one with no arrival
    two rounds running loses its EC and its arcs; the span and the
    record say so, and the next arrival lists it anew."""
    seed_rng(15)
    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api, max_tasks_per_pu=5, cost_model=CostModelType.K8S_ANTIAFFINITY,
        backend=ReferenceSolver(), backend_name="configured", tracer=RoundTracer(),
    )
    svc.init_topology(fake_machines=MACHINES)
    gm = svc.scheduler.gm
    purge = gm.purge_unconnected_equiv_class_nodes
    counts = []

    def counting():
        before = len(gm.task_ec_to_node)
        arcs = sum(len(n.outgoing) + len(n.incoming) for n in gm.task_ec_to_node.values())
        purge()
        after = sum(len(n.outgoing) + len(n.incoming) for n in gm.task_ec_to_node.values())
        counts.append((before - len(gm.task_ec_to_node), arcs - after))

    gm.purge_unconnected_equiv_class_nodes = counting

    def serve(tag, group):
        return svc.run_round([PodEvent(pod_id=tag, task_class=group)])

    with SpanTracer() as tracer:
        assert serve("a", 0) + serve("b", 1) == 2
        for i in range(3):
            assert serve(f"c{i}", 1) == 1  # workload 0 idle: marked, then purged
        assert serve("d", 0) == 1  # listed anew
    purges = sorted(_by_name(tracer.events())["ec_purge"], key=lambda e: e["ts"])
    records = [r for r in svc.tracer.records if r.solver_rung >= 0]
    assert len(purges) == len(records) == len(counts) == 6
    assert [r.ec_purged for r in records] == [c[0] for c in counts]
    assert [(e["args"]["ec_purged"], e["args"]["ec_arcs_dropped"]) for e in purges] == counts
    assert sum(c[0] for c in counts) == 1 and max(c[1] for c in counts) >= MACHINES - 1
    assert svc.scheduler.last_timing.ec_purged == gm.ec_purged == counts[-1][0]
    # the round after the purge re-listed the workload's arcs
    assert records[-1].ec_arcs_changed >= MACHINES - 1


def test_with_no_tracer_installed_nothing_is_recorded_and_the_timing_is_filled():
    assert spans_mod.active_tracer() is None
    tracer = SpanTracer()  # never installed
    svc, api = _auto_service()
    assert svc.run_round(_pods("a", 5)) == 5
    assert svc.run_round(_pods("b", 3)) == 3
    api.close()
    assert (tracer.total, tracer.events()) == (0, [])
    assert spans_mod._current.get() is None
    t = svc.scheduler.last_timing
    assert t.graph_update_s > 0 and t.solve_s > 0 and t.apply_s > 0
    assert t.total_s >= t.stats_s + t.graph_update_s + t.solve_s + t.deltas_s + t.apply_s
    assert (t.graph_tasks_visited, t.res_nodes_visited, t.res_arcs_changed) == (3, 0, 0)
    assert t.journal_changes > 0 and t.ec_purged == 0
    rec = svc.tracer.records[-1]
    assert (rec.res_nodes_visited, rec.res_arcs_changed, rec.journal_changes, rec.ec_purged) == (
        t.res_nodes_visited, t.res_arcs_changed, t.journal_changes, t.ec_purged,
    )
    idle = svc.tracer.record_flow_round(svc.scheduler, 0, solved=False)
    assert idle.journal_changes == t.journal_changes  # a sweep re-reports the timing it is given


@pytest.mark.parametrize("preemption", [False, True], ids=["pinned", "preemption"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_under_a_tracer_placements_problems_and_objectives_are_the_root_down_walks(
    model, preemption
):
    """The seeded stream of the work-list tests, the new world under an
    installed tracer, the reference on the walk that opens no run span."""
    new = _World(MODELS[model], preemption, root_down=False)
    ref = _World(MODELS[model], preemption, root_down=True)
    rnd = random.Random(5)
    jobs, uid = [101, 202, 303], 1000
    members = {j: [] for j in jobs}
    for step in range(10):
        for _ in range(rnd.randrange(3, 9)):
            job = rnd.choice(jobs)
            uid += 1
            parent = None
            if len(members[job]) > 2 and rnd.random() < 0.3:
                parent = rnd.choice(members[job][1:])
            ttype = TaskType(rnd.randrange(4))
            for w in (new, ref):
                w.admit(job, uid, parent, ttype)
            members[job].append(uid)
        running = sorted(new.sched.task_bindings)
        rnd.shuffle(running)
        for t in running[: rnd.randrange(0, 4) if step else 0]:
            for w in (new, ref):
                w.complete(t)
        with SpanTracer() as tracer:
            got = new.round()
        want = ref.round()
        assert got[0] == want[0]
        assert [(d.type, d.task_id, d.resource_id) for d in got[1]] == [
            (d.type, d.task_id, d.resource_id) for d in want[1]
        ]
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert (
            new.sched.solver.last_result.objective == ref.sched.solver.last_result.objective
        )
        assert new.journals == ref.journals
        assert new.sched.task_bindings == ref.sched.task_bindings
        names = {e["name"] for e in tracer.events()}
        assert {"task_refresh", "ec_purge", "journal_apply", "problem_snapshot"} <= names
        # the walk's every resource turn, and none (and no span for none) here
        assert ref.sched.gm.root_down_res_turns == len(new.sched.gm.resource_to_node) - 1
        assert new.sched.gm.res_nodes_visited == 0 and "res_refresh" not in names
