"""The graph manager's per-round update visits a work list of the tasks
that can change, not every task under each job's root.

Parity: a twin scheduler whose graph manager runs the root-down walk
(kept here as the reference, a plain copy of what the work list
replaced) must see the same FlowProblem and the same change journal,
bit for bit, after every round of a seeded stream of events. Counts:
what a round visits is the batch, whatever is resident.
"""

import os
import random
import types
from collections import deque

import numpy as np
import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, TrivialCostModel
from ksched_tpu.data import JobDescriptor, JobState, TaskDescriptor, TaskState, TaskType
from ksched_tpu.drivers import add_machine, build_cluster
from ksched_tpu.graph.graph_manager import task_needs_node
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import job_id_from_string, seed_rng

# ---------------------------------------------------------------------------
# The reference: the root-down walk, as graph_manager.py had it
# ---------------------------------------------------------------------------


def _root_down_add_or_update_job_nodes(gm, jobs):
    node_queue = deque()
    marked = set()
    gm.root_down_res_turns = 0
    for job in jobs:
        jid = job_id_from_string(job.uuid)
        if jid not in gm.job_unsched_to_node:
            gm._add_unscheduled_agg_node(jid)
        root_td = job.root_task
        root_node = gm.task_to_node.get(root_td.uid)
        if root_node is not None:
            node_queue.append((root_node, root_td))
            marked.add(root_node.id)
            continue
        if task_needs_node(root_td):
            root_node = gm._add_task_node(jid, root_td)
            gm._update_unscheduled_agg_node(gm.job_unsched_to_node[jid], 1)
            node_queue.append((root_node, root_td))
            marked.add(root_node.id)
        else:
            node_queue.append((None, root_td))
    while node_queue:
        node, task = node_queue.popleft()
        if node is None:
            _root_down_children(gm, task, node_queue, marked)
        elif node.is_task_node:
            gm._update_task_node(node, node_queue, marked)
            _root_down_children(gm, task, node_queue, marked)
        elif node.is_equiv_class_node:
            gm._update_equiv_class_node(node, node_queue, marked)
        else:
            assert node.is_resource_node
            gm.root_down_res_turns += 1
            gm._update_res_outgoing_arcs(node, node_queue, marked)


def _root_down_children(gm, td, node_queue, marked):
    for child in td.spawned:
        child_node = gm.task_to_node.get(child.uid)
        if child_node is not None:
            if child_node.id not in marked:
                node_queue.append((child_node, child))
                marked.add(child_node.id)
            continue
        if not task_needs_node(child):
            node_queue.append((None, child))
            continue
        jid = job_id_from_string(child.job_id)
        child_node = gm._add_task_node(jid, child)
        gm._update_unscheduled_agg_node(gm.job_unsched_to_node[jid], 1)
        node_queue.append((child_node, child))
        marked.add(child_node.id)


def _every_node_statistics(gm, start):
    gm._cur_traversal_counter += 1
    counter = gm._cur_traversal_counter
    to_visit = deque([start])
    start.visited = counter
    while to_visit:
        cur = to_visit.popleft()
        for arc in cur.incoming.values():
            src = arc.src_node
            if src.visited != counter:
                gm.cost_model.prepare_stats(src)
                to_visit.append(src)
                src.visited = counter
            gm.cost_model.gather_stats(src, cur)
            gm.cost_model.update_stats(src, cur)


def _use_root_down_walk(sched):
    gm = sched.gm
    # the walk it was: every resource node an EC or a task prefers takes a
    # turn, and every node below it, whatever the model says of its prices
    gm._res_turns = True
    gm.add_or_update_job_nodes = types.MethodType(_root_down_add_or_update_job_nodes, gm)
    gm.compute_topology_statistics = types.MethodType(_every_node_statistics, gm)


# ---------------------------------------------------------------------------
# Two worlds fed the same events
# ---------------------------------------------------------------------------


def _admit(sched, jmap, tmap, job_id, uids, parent_uid=None, task_type=TaskType.SHEEP, workload=0):
    """New CREATED tasks under the job's root (the first becomes the
    root), or under the task `parent_uid`; the job is (re-)offered."""
    jd = jmap.find(job_id)
    for uid in uids:
        td = TaskDescriptor(
            uid=uid, name=f"t{uid}", state=TaskState.CREATED, job_id=str(job_id),
            task_type=task_type, workload=workload,
        )
        tmap.insert(uid, td)
        if jd is None:
            jd = JobDescriptor(uuid=str(job_id), name=f"j{job_id}", state=JobState.CREATED, root_task=td)
            jmap.insert(job_id, jd)
        elif parent_uid is None:
            sched.add_task(jd.root_task, td)
        else:
            sched.add_task(tmap.find(parent_uid), td)
    sched.add_job(jd)
    return jd


class _Recording(ReferenceSolver):
    """Keeps a copy of every problem it is given."""

    def __init__(self):
        super().__init__()
        self.problems = []

    def solve(self, problem):
        self.problems.append(
            {
                "num_nodes": problem.num_nodes,
                "num_arcs": problem.num_arcs,
                **{
                    k: np.array(getattr(problem, k))
                    for k in ("src", "dst", "cap", "cost", "excess", "node_type", "flow_offset")
                },
            }
        )
        return super().solve(problem)


class _World:
    def __init__(self, model, preemption, root_down):
        seed_rng(11)  # the same resource ids in both worlds
        self.backend = _Recording()
        self.sched, self.rmap, self.jmap, self.tmap, self.root = build_cluster(
            num_machines=6, num_cores=2, pus_per_core=2, max_tasks_per_pu=6,
            backend=self.backend, cost_model_factory=model, preemption=preemption,
        )
        if root_down:
            _use_root_down_walk(self.sched)
        self.journals = []
        cm = self.sched.gm.cm
        optimized = cm.get_optimized_graph_changes

        def recording_changes():
            changes = optimized()
            self.journals.append(list(changes))
            return changes

        cm.get_optimized_graph_changes = recording_changes

    def admit(self, job_id, uid, parent_uid, task_type, workload=0):
        _admit(self.sched, self.jmap, self.tmap, job_id, [uid], parent_uid, task_type, workload)

    def complete(self, uid):
        self.sched.handle_task_completion(self.tmap.find(uid))

    def evict(self, uid):
        rid = self.sched.task_bindings[uid]
        self.sched.handle_task_eviction(self.tmap.find(uid), self.rmap.find(rid).descriptor)

    def remove_machine(self, index):
        self.sched.deregister_resource(self.root.children[index])

    def add_machine(self, index):
        seed_rng(1000 + index)  # the same resource ids in both worlds
        return add_machine(self.sched, self.rmap, self.root, 2, 2, 6, machine_index=index)

    def round(self):
        return self.sched.schedule_all_jobs()


def _same_problem(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(a[k], b[k]), k


MODELS = {
    "trivial": MODEL_REGISTRY[CostModelType.TRIVIAL],
    "coco": MODEL_REGISTRY[CostModelType.COCO],
    "whare": MODEL_REGISTRY[CostModelType.WHARE],
}


@pytest.mark.parametrize("preemption", [False, True], ids=["pinned", "preemption"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_work_list_gives_the_root_down_walks_problem_and_journal(model, preemption):
    new = _World(MODELS[model], preemption, root_down=False)
    ref = _World(MODELS[model], preemption, root_down=True)
    assert new.sched.gm._tasks_inert
    worlds = (new, ref)
    rnd = random.Random(5)
    jobs = [101, 202, 303]
    uid = 1000
    members = {j: [] for j in jobs}  # job -> uids admitted, root first
    for step in range(14):
        # admissions: a root's child, or two levels down under an older task
        for _ in range(rnd.randrange(3, 9)):
            job = rnd.choice(jobs)
            uid += 1
            parent = None
            if len(members[job]) > 2 and rnd.random() < 0.3:
                parent = rnd.choice(members[job][1:])
            ttype = TaskType(rnd.randrange(4))
            for w in worlds:
                w.admit(job, uid, parent, ttype)
            members[job].append(uid)
        running = sorted(new.sched.task_bindings)
        assert running == sorted(ref.sched.task_bindings)
        rnd.shuffle(running)
        n_done = rnd.randrange(0, 4) if step else 0
        for t in running[:n_done]:
            for w in worlds:
                w.complete(t)
        for t in running[n_done:n_done + (rnd.randrange(0, 3) if step > 1 else 0)]:
            for w in worlds:
                w.evict(t)
        if step == 7:
            for w in worlds:
                w.remove_machine(2)
        results = [w.round() for w in worlds]
        assert results[0][0] == results[1][0]
        assert [(d.type, d.task_id, d.resource_id) for d in results[0][1]] == [
            (d.type, d.task_id, d.resource_id) for d in results[1][1]
        ]
        assert len(new.backend.problems) == len(ref.backend.problems) == step + 1
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert new.journals == ref.journals and len(new.journals) == step  # round 0 is a full build
        assert new.sched.task_bindings == ref.sched.task_bindings
        gm = new.sched.gm
        # the walk gave every resource node but the coordinator a turn, the
        # work list none (these models price their resource arcs at constants)
        assert (gm.res_nodes_visited, gm.res_arcs_changed) == (0, 0)
        assert ref.sched.gm.root_down_res_turns == len(gm.resource_to_node) - 1
        assert [n.id for n in gm.task_to_node.values()] == [
            n.id for n in ref.sched.gm.task_to_node.values()
        ]
        # every task node is on the list or counted as pinned and left alone
        listed_nodes = sum(uid in gm.task_to_node for v in gm._worklist.values() for uid in v)
        unlisted = sum(gm._pinned_unlisted.values())
        assert listed_nodes + unlisted == len(gm.task_to_node)
        if preemption:
            assert unlisted == 0
        else:
            assert unlisted == len(gm.task_to_running_arc)
    assert sum(len(j) for j in new.journals) > 100  # the stream did exercise the journal


# ---------------------------------------------------------------------------
# Counts: a round visits its batch
# ---------------------------------------------------------------------------


def _filled_cluster(resident, model=None, preemption=False, backend=None):
    """A cluster with `resident` pods bound through the placement event
    (as checkpoint restore binds them), no solve."""
    seed_rng(3)
    machines = resident // 40 + 2
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=machines, num_cores=2, pus_per_core=2, max_tasks_per_pu=12,
        backend=backend, cost_model_factory=model, preemption=preemption,
    )
    jd = _admit(sched, jmap, tmap, 7, range(1, resident + 1))
    assert sched._compute_runnable_tasks_for_job(jd)
    sched.gm.compute_topology_statistics(sched.gm.sink_node)
    sched.gm.add_or_update_job_nodes([jd])
    assert sched.gm.tasks_visited == resident
    pus = [rs.descriptor for _rid, rs in rmap.items() if rs.descriptor.type == 0]
    for i, td in enumerate(list(jd.root_task.spawned) + [jd.root_task]):
        if td.state == TaskState.RUNNABLE:
            sched.handle_task_placement(td, pus[i % len(pus)])
    assert len(sched.task_bindings) == resident
    return sched, rmap, jmap, tmap


@pytest.mark.parametrize("resident", [200, 2000])
def test_a_round_visits_its_batch_whatever_is_resident(resident):
    sched, rmap, jmap, tmap = _filled_cluster(resident, backend=make_backend("native"))
    batch = 10
    _admit(sched, jmap, tmap, 7, range(10_001, 10_001 + batch))
    placed, _ = sched.schedule_all_jobs()
    assert placed == batch
    assert (sched.gm.tasks_visited, sched.gm.tasks_skipped) == (batch, resident)
    assert (sched.last_timing.graph_tasks_visited, sched.last_timing.graph_tasks_skipped) == (
        batch, resident,
    )
    done = min(500, resident // 2)
    for uid in range(2, 2 + done):
        sched.handle_task_completion(tmap.find(uid))
    _admit(sched, jmap, tmap, 7, range(20_001, 20_001 + batch))
    placed, _ = sched.schedule_all_jobs()
    assert placed == batch
    assert (sched.gm.tasks_visited, sched.gm.tasks_skipped) == (batch, resident + batch - done)
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node)


class _RisingContinuation(TrivialCostModel):
    """Says nothing about pinned tasks, and re-prices them every round."""

    rounds = 0

    def task_continuation_cost(self, task_id):
        return self.rounds

    def note_round(self, unscheduled_task_ids):
        self.rounds += 1


def test_a_model_that_declares_nothing_has_every_running_arc_repriced_every_round():
    assert TrivialCostModel.pinned_tasks_are_inert
    assert not _RisingContinuation.pinned_tasks_are_inert  # overriding the method drops the claim
    sched, rmap, jmap, tmap = _filled_cluster(60, model=_RisingContinuation)
    assert sched.gm._pinned_unlisted == {}
    for r in range(1, 4):
        sched.cost_model.rounds = r
        _admit(sched, jmap, tmap, 7, range(1000 * r, 1000 * r + 4))
        placed, _ = sched.schedule_all_jobs()
        assert placed == 4
        assert (sched.gm.tasks_visited, sched.gm.tasks_skipped) == (60 + 4 * r, 0)
        old = [a for t, a in sched.gm.task_to_running_arc.items() if t < 1000 * r]
        assert len(old) == 60 + 4 * (r - 1) and {a.cost for a in old} == {r}


def test_every_registered_model_says_what_its_methods_do():
    """The claim is checked against behaviour: on a task node the stats
    hooks change nothing, and the continuation cost does not move."""
    for kind, model in MODEL_REGISTRY.items():
        # a model that prices preemption is built with it (k8s_priority)
        sched, rmap, jmap, tmap = _filled_cluster(8, model=model, preemption=model.needs_preemption)
        assert model.pinned_tasks_are_inert, kind
        cm, gm = sched.cost_model, sched.gm
        task_node = next(iter(gm.task_to_node.values()))
        pu = gm.task_to_running_arc[task_node.task.uid].dst_node
        before = (vars(pu.resource_descriptor).copy(), cm.task_continuation_cost(task_node.task.uid))
        cm.prepare_stats(task_node)
        assert cm.gather_stats(task_node, pu) is task_node
        assert cm.update_stats(task_node, pu) is task_node
        sched.schedule_all_jobs()
        assert (vars(pu.resource_descriptor), cm.task_continuation_cost(task_node.task.uid)) == before


def test_pods_admitted_while_a_pipelined_round_is_in_flight_are_visited_by_the_next():
    sched, rmap, jmap, tmap = _filled_cluster(50)
    _admit(sched, jmap, tmap, 7, range(1001, 1006))
    assert sched.schedule_all_jobs_async() is not None
    assert sched.gm.tasks_visited == 5
    _admit(sched, jmap, tmap, 7, range(2001, 2004))  # journal for the next round
    placed, _ = sched.finish_scheduling()
    assert placed == 5 and not any(uid in sched.task_bindings for uid in range(2001, 2004))
    assert sched.schedule_all_jobs_async() is not None
    assert (sched.gm.tasks_visited, sched.gm.tasks_skipped) == (3, 55)
    placed, _ = sched.finish_scheduling()
    assert placed == 3 and all(uid in sched.task_bindings for uid in range(2001, 2004))


# ---------------------------------------------------------------------------
# The service: restore (warm, cold) and a forced full_build
# ---------------------------------------------------------------------------


def _service(api, tracer):
    svc = SchedulerService(
        api, max_tasks_per_pu=4, backend=make_backend("native"), backend_name="native",
        tracer=tracer,
    )
    svc.init_topology(fake_machines=6, pus_per_core=2)
    return svc


def _serve(svc, api, tag, pods):
    for i in range(pods):
        api.submit_pod(PodEvent(pod_id=f"{tag}_{i}"))
    bound = svc.run_round(api.poll_pod_batch(0.01))
    rec = svc.tracer.records[-1]
    assert all(
        svc.pod_to_task[f"{tag}_{i}"] in svc.scheduler.task_bindings for i in range(pods)
    )
    return bound, rec


@pytest.mark.parametrize("kind", ["warm", "cold"])
def test_the_round_after_a_restore_visits_the_new_pods_and_binds_them(tmp_path, kind):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    bound, rec = _serve(svc, api, "b", 4)
    assert (bound, rec.graph_tasks_visited, rec.graph_tasks_skipped) == (4, 4, 9)
    svc.complete_pod("a_0")
    ck = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(ck)
    if kind == "cold":
        os.remove(ck + ".wal")
    svc2 = SchedulerService.restore(
        api, ck, backend=make_backend("native"), backend_name="native", tracer=RoundTracer(),
    )
    assert svc2.restored_warm == (kind == "warm")
    gm = svc2.scheduler.gm
    assert sum(len(v) for v in gm._worklist.values()) == 0
    assert sum(gm._pinned_unlisted.values()) == 12
    bound, rec = _serve(svc2, api, "c", 5)
    assert (bound, rec.graph_tasks_visited, rec.graph_tasks_skipped) == (5, 5, 12)
    bound, rec = _serve(svc2, api, "d", 2)
    assert (bound, rec.graph_tasks_visited, rec.graph_tasks_skipped) == (2, 2, 17)


def test_the_round_after_a_forced_full_build_visits_the_new_pods_and_binds_them():
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    solver = svc.scheduler.solver
    solver._started = False  # the next export rebuilds the solver's state from the host graph
    rebuilds = solver.state.rebuild_count
    bound, rec = _serve(svc, api, "b", 4)
    assert (bound, rec.graph_tasks_visited, rec.graph_tasks_skipped) == (4, 4, 9)
    assert solver.state.rebuild_count == rebuilds + 1
    bound, rec = _serve(svc, api, "c", 3)
    assert (bound, rec.graph_tasks_visited, rec.graph_tasks_skipped) == (3, 3, 13)


def test_an_idle_sweep_and_a_round_without_runnable_work_report_no_visit():
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 3)
    svc.run_round([], solve=False)
    svc.run_round([])
    for rec in svc.tracer.records[-2:]:
        assert (rec.graph_tasks_visited, rec.graph_tasks_skipped) == (0, 0)


def test_the_graph_update_span_carries_both_counts():
    sched, rmap, jmap, tmap = _filled_cluster(30)
    _admit(sched, jmap, tmap, 7, range(1001, 1004))
    with SpanTracer() as tracer:
        sched.schedule_all_jobs()
    (ev,) = [e for e in tracer.events() if e["name"] == "graph_update"]
    assert (ev["args"]["graph_tasks_visited"], ev["args"]["graph_tasks_skipped"]) == (3, 30)
