"""The scan for runnable tasks is fed by events: a child added to a tree
the scheduler has walked is recorded (`FlowScheduler.add_task`), and the
next scan looks at what was recorded, not at every descriptor ever
admitted.

The oracle: a twin scheduler that walks every job's whole tree in every
scan (`_walk_job_tree`, the reference's walk, which the scheduler still
runs the first time it meets a job) must see, after every round of a
seeded stream of events, the same runnable sets in the same order, the
same jobs in the same order, the same `task_runnable(td, path)` calls,
the same problem and the same change journal, bit for bit. Counts: what
a served round scans is its batch, whatever is resident; the round that
first meets a job, and the one after a restore, scan the tree.
"""

import os
import random

import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType
from ksched_tpu.data import TaskDescriptor, TaskState, TaskType
from ksched_tpu.drivers import add_task_to_job
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.checkpoint import restore_scheduler, save_scheduler
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import job_id_from_string, seed_rng
from test_graph_worklist import _admit, _filled_cluster, _same_problem, _serve, _World

MODELS = {
    "trivial": MODEL_REGISTRY[CostModelType.TRIVIAL],
    "coco": MODEL_REGISTRY[CostModelType.COCO],
    "quincy": MODEL_REGISTRY[CostModelType.QUINCY],
}


# ---------------------------------------------------------------------------
# Two worlds fed the same events: one scans by events, one walks every tree
# ---------------------------------------------------------------------------


def _walk_every_scan(sched):
    """The scan as it was: every job's tree, root down, every time."""

    def compute(jd):
        sched._tasks_scanned += sched._walk_job_tree(jd)
        return sched.runnable_tasks.setdefault(job_id_from_string(jd.uuid), set())

    sched._compute_runnable_tasks_for_job = compute


class _ScanWorld(_World):
    def __init__(self, model, preemption, walk):
        super().__init__(model, preemption, root_down=False)
        self.model, self.walk = model, walk
        self.scans = []  # per scan: the jobs it returned, in order
        self.promotions = []  # every task_runnable(td, path), in order
        self.scanned = []  # per round: runnable_tasks_scanned
        self._hook()

    def _hook(self):
        sched = self.sched
        if self.walk:
            _walk_every_scan(sched)
        runnable_jobs, task_runnable = sched._runnable_jobs, sched.gm.task_runnable

        def recording_scan():
            jds = runnable_jobs()
            self.scans.append([jd.uuid for jd in jds])
            return jds

        def recording_promotion(td, path):
            self.promotions.append((td.uid, path))
            task_runnable(td, path)

        sched._runnable_jobs = recording_scan
        sched.gm.task_runnable = recording_promotion

    def round(self):
        result = super().round()
        self.scanned.append(self.sched.last_timing.runnable_tasks_scanned)
        return result

    def offer(self, job_id):
        self.sched.add_job(self.jmap.find(job_id))

    def fail(self, uid):
        self.sched.handle_task_failure(self.tmap.find(uid))

    def kill(self, uid):
        self.sched.kill_running_task(uid)

    def complete_job(self, job_id):
        self.sched.handle_job_completion(job_id)

    def place_ahead_of_the_round(self, limit):
        """A scan and a graph update without a solve, then an external
        placement of up to ``limit`` runnable tasks (what a restore does
        with its bindings). Returns the tasks placed."""
        sched = self.sched
        jds = sched._runnable_jobs()
        if not jds:
            return []
        sched.gm.compute_topology_statistics(sched.gm.sink_node)
        sched.gm.add_or_update_job_nodes(jds)
        waiting = sorted(
            uid for uids in sched.runnable_tasks.values() for uid in uids
            if uid not in sched.task_bindings
        )[:limit]
        pus = sorted(
            (rs.descriptor for _rid, rs in self.rmap.items() if rs.descriptor.type == 0),
            key=lambda rd: (len(rd.current_running_tasks), rd.uuid),
        )
        for uid, rd in zip(waiting, pus):
            sched.handle_task_placement(self.tmap.find(uid), rd)
        return waiting

    def restore(self, path):
        """Through runtime/checkpoint: a new scheduler that has met no
        job, rebuilt from the pickled trees."""
        save_scheduler(self.sched, path)
        journals = self.journals
        self.sched, self.rmap, self.jmap, self.tmap = restore_scheduler(
            path, cost_model_factory=self.model, backend=self.backend
        )
        self.root = self.sched.resource_topology
        cm = self.sched.gm.cm
        optimized = cm.get_optimized_graph_changes

        def recording_changes():
            changes = optimized()
            journals.append(list(changes))
            return changes

        cm.get_optimized_graph_changes = recording_changes
        self._hook()


def _runnable_in_order(sched):
    return {job: list(uids) for job, uids in sched.runnable_tasks.items()}


def _same_scans(new, ref):
    assert new.scans == ref.scans
    assert new.promotions == ref.promotions
    assert _runnable_in_order(new.sched) == _runnable_in_order(ref.sched)
    assert list(new.sched.jobs_to_schedule) == list(ref.sched.jobs_to_schedule)


@pytest.mark.parametrize("preemption", [False, True], ids=["pinned", "preemption"])
@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("seed", [5, 23])
def test_the_events_give_the_walks_runnable_sets_calls_and_journal(tmp_path, seed, model, preemption):
    new = _ScanWorld(MODELS[model], preemption, walk=False)
    ref = _ScanWorld(MODELS[model], preemption, walk=True)
    worlds = (new, ref)
    rnd = random.Random(seed)
    jobs = [101, 202, 303, 404]
    uid = 1000
    members = {j: [] for j in jobs}  # job -> uids admitted, root first
    parents = {j: set() for j in jobs}  # job -> uids that have a child
    gone = set()  # finished, failed or killed: no event may name them again
    retiring = None  # the job that takes no more tasks until it completes
    retired = False
    restored_at = 9 if not preemption else None  # a restored scheduler has preemption off
    for step in range(24):
        # admissions: a root's child, a child of a task that has children,
        # the first child of a leaf
        for _ in range(rnd.randrange(2, 9)):
            job = rnd.choice([j for j in jobs if j != retiring])
            uid += 1
            parent, dice = None, rnd.random()
            if len(members[job]) > 2 and dice < 0.45:
                leaves = [u for u in members[job][1:] if u not in parents[job]]
                inner = sorted(parents[job] - {members[job][0]})
                parent = rnd.choice(leaves if dice < 0.25 or not inner else inner)
                parents[job].add(parent)
            elif members[job]:
                parents[job].add(members[job][0])
            ttype = TaskType(rnd.randrange(4))
            for w in worlds:
                w.admit(job, uid, parent, ttype)
            members[job].append(uid)
        if step % 5 == 3:
            # a job the scheduler knows, offered again with nothing new
            again = rnd.choice([j for j in jobs if members[j]])
            for w in worlds:
                w.offer(again)
        if step % 4 == 2:
            placed = [w.place_ahead_of_the_round(3) for w in worlds]
            assert placed[0] == placed[1]
            _same_scans(new, ref)
        running = sorted(set(new.sched.task_bindings) - gone)
        assert running == sorted(set(ref.sched.task_bindings) - gone)
        rnd.shuffle(running)
        doomed = iter(running)
        for event, n in (
            ("complete", rnd.randrange(0, 4) if step else 0),
            ("evict", rnd.randrange(0, 3) if step > 1 else 0),
            ("fail", int(step > 3 and rnd.random() < 0.4)),
            # a killed task keeps its binding, which a restore would replay
            ("kill", int(step > (restored_at or 3) and rnd.random() < 0.4)),
        ):
            for _, t in zip(range(n), doomed):
                for w in worlds:
                    getattr(w, event)(t)
                if event != "evict":
                    gone.add(t)
        if step == 7:
            for w in worlds:
                w.remove_machine(2)
        if step == restored_at:
            for i, w in enumerate(worlds):
                w.restore(str(tmp_path / f"world{i}.ckpt"))
            _same_scans(new, ref)
        if step == 12:
            retiring = 202
        if retiring and step > 12:
            left = [u for u in members[retiring] if u not in gone]
            if all(
                u in new.sched.task_bindings or new.tmap.find(u).state == TaskState.CREATED
                for u in left
            ):
                # no task of the job waits for a place (a child admitted
                # under a failed root is never promoted, and stays CREATED):
                # what runs finishes, the job completes, and later steps
                # offer it again with new children
                for u in left:
                    if u in new.sched.task_bindings:
                        for w in worlds:
                            w.complete(u)
                    gone.add(u)
                for w in worlds:
                    w.complete_job(retiring)
                retiring, retired = None, True
        results = [w.round() for w in worlds]
        assert results[0][0] == results[1][0]
        assert [(d.type, d.task_id, d.resource_id) for d in results[0][1]] == [
            (d.type, d.task_id, d.resource_id) for d in results[1][1]
        ]
        _same_scans(new, ref)
        assert len(new.backend.problems) == len(ref.backend.problems)
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert new.journals == ref.journals
        assert new.sched.task_bindings == ref.sched.task_bindings
        assert new.sched.last_timing.objective == ref.sched.last_timing.objective
    assert sum(len(j) for j in new.journals) > 100  # the stream did exercise the journal
    assert any(len(path) > 1 for _uid, path in new.promotions) and retired
    # the events looked at what arrived, the walk at every tree every time
    assert sum(new.scanned) * 3 < sum(ref.scanned)
    assert new.scanned[-1] < 9 < ref.scanned[-1]


# ---------------------------------------------------------------------------
# When the walk still runs
# ---------------------------------------------------------------------------


def _filled(resident):
    """`_filled_cluster`, whose walk of the tree (outside any round, as a
    restore's) no round's count has to carry."""
    sched, rmap, jmap, tmap = _filled_cluster(resident)
    assert sched._take_tasks_scanned() == resident
    return sched, rmap, jmap, tmap


def test_a_root_grown_without_a_word_costs_a_walk_and_loses_no_task():
    sched, rmap, jmap, tmap = _filled(30)
    jd = jmap.find(7)
    _admit(sched, jmap, tmap, 7, range(1001, 1004))
    assert sched.schedule_all_jobs()[0] == 3
    assert sched.last_timing.runnable_tasks_scanned == 3
    # the driver's helper, without the scheduler: a plain append
    td = add_task_to_job(7, jmap, tmap)
    sched.add_job(jd)
    assert sched.schedule_all_jobs()[0] == 1 and td.uid in sched.task_bindings
    assert sched.last_timing.runnable_tasks_scanned == 1 + 30 + 3  # the whole tree
    td = add_task_to_job(7, jmap, tmap, scheduler=sched)
    assert sched.schedule_all_jobs()[0] == 1 and td.uid in sched.task_bindings
    assert sched.last_timing.runnable_tasks_scanned == 1  # told: an event again


def test_a_child_under_a_parent_the_walk_never_saw_costs_a_walk():
    sched, rmap, jmap, tmap = _filled(12)
    jd = jmap.find(7)
    sched.schedule_all_jobs()
    hidden = TaskDescriptor(uid=5001, name="hidden", state=TaskState.COMPLETED, job_id="7")
    tmap.insert(5001, hidden)
    tmap.find(3).spawned.append(hidden)  # under a task that is not the root, without a word
    _admit(sched, jmap, tmap, 7, [5002], parent_uid=5001)
    assert job_id_from_string(jd.uuid) not in sched._met_jobs
    assert sched.schedule_all_jobs()[0] == 1 and 5002 in sched.task_bindings
    assert sched.last_timing.runnable_tasks_scanned == 12 + 2
    place = tmap.find(3).spawned.index(hidden)
    assert sched._met_jobs[7].paths[5002] == (jd.root_task.spawned.index(tmap.find(3)), place, 0)


def test_a_job_offered_again_is_not_walked_again_and_a_completed_one_is_forgotten():
    sched, rmap, jmap, tmap = _filled(20)
    jd = jmap.find(7)
    walks = []
    walk = sched._walk_job_tree
    sched._walk_job_tree = lambda jd: walks.append(jd.uuid) or walk(jd)
    for _ in range(3):
        sched.add_job(jd)
        sched.schedule_all_jobs()
    assert walks == [] and sched.last_timing.runnable_tasks_scanned == 0
    for uid in range(1, 21):
        sched.handle_task_completion(tmap.find(uid))
    _admit(sched, jmap, tmap, 7, [9001])
    sched.schedule_all_jobs()  # the job's node: a completion has one to delete
    sched.handle_task_completion(tmap.find(9001))
    sched.handle_job_completion(7)
    assert 7 not in sched._met_jobs and 7 not in sched.jobs_to_schedule
    _admit(sched, jmap, tmap, 7, [9002])
    assert sched.schedule_all_jobs()[0] == 1
    assert walks == ["7"] and sched.last_timing.runnable_tasks_scanned == 22


def test_a_child_added_while_a_pipelined_round_is_in_flight_is_scanned_by_the_next():
    sched, rmap, jmap, tmap = _filled(50)
    _admit(sched, jmap, tmap, 7, range(1001, 1006))
    assert sched.schedule_all_jobs_async() is not None
    _admit(sched, jmap, tmap, 7, range(2001, 2004))
    _admit(sched, jmap, tmap, 7, [2004], parent_uid=2002)  # under a leaf not yet promoted
    assert sched.finish_scheduling()[0] == 5
    assert sched.last_timing.runnable_tasks_scanned == 5
    assert sched.schedule_all_jobs_async() is not None
    assert sched.finish_scheduling()[0] == 4
    assert sched.last_timing.runnable_tasks_scanned == 4
    assert sched.gm.task_to_node[2004].tree_path == (49 + 5 + 1, 0)  # 49 children at the fill, 5, and 2001


def test_the_scan_span_carries_the_count_and_a_round_without_work_reports_it():
    sched, rmap, jmap, tmap = _filled(30)
    _admit(sched, jmap, tmap, 7, range(1001, 1005))
    with SpanTracer() as tracer:
        sched.schedule_all_jobs()
        sched.schedule_all_jobs()
    first, second = sorted(
        (e for e in tracer.events() if e["name"] == "runnable_scan"), key=lambda e: e["ts"]
    )
    assert (first["args"]["runnable_tasks_scanned"], first["args"]["jobs"]) == (4, 1)
    assert (second["args"]["runnable_tasks_scanned"], second["args"]["jobs"]) == (0, 0)
    assert sched.last_timing.runnable_tasks_scanned == 0


# ---------------------------------------------------------------------------
# Counts: a served round scans its batch
# ---------------------------------------------------------------------------


def _service(api, machines, **kw):
    svc = SchedulerService(
        api, max_tasks_per_pu=4, backend=make_backend("native"), backend_name="native",
        tracer=RoundTracer(), **kw,
    )
    svc.init_topology(fake_machines=machines, pus_per_core=2)
    return svc


@pytest.mark.parametrize("resident", [200, 2000])
def test_a_served_round_scans_its_batch_whatever_is_resident(resident):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, machines=resident // 8 + 4)
    # the round that first meets the job walks its tree: the fill
    bound, rec = _serve(svc, api, "fill", resident)
    assert (bound, rec.runnable_tasks_scanned) == (resident, resident)
    for step, batch in enumerate((10, 3, 7)):
        bound, rec = _serve(svc, api, f"b{step}", batch)
        assert (bound, rec.runnable_tasks_scanned, rec.graph_tasks_visited) == (batch, batch, batch)
    for i in range(0, resident, 2):
        svc.complete_pod(f"fill_{i}")
    bound, rec = _serve(svc, api, "after", 5)
    assert (bound, rec.runnable_tasks_scanned) == (5, 5)
    svc.run_round([], solve=False)
    svc.run_round([])
    assert [r.runnable_tasks_scanned for r in svc.tracer.records[-2:]] == [0, 0]
    assert len(svc.job_map.find(svc.job_id).root_task.spawned) == resident + 24  # they all stay


@pytest.mark.parametrize("kind", ["warm", "cold"])
def test_the_round_after_a_restore_scans_the_tree_and_the_next_its_batch(tmp_path, kind):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, machines=8)
    _serve(svc, api, "a", 9)
    bound, rec = _serve(svc, api, "b", 4)
    assert (bound, rec.runnable_tasks_scanned) == (4, 4)
    svc.complete_pod("a_0")
    ck = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(ck)
    if kind == "cold":
        os.remove(ck + ".wal")
    svc2 = SchedulerService.restore(
        api, ck, backend=make_backend("native"), backend_name="native", tracer=RoundTracer(),
    )
    assert svc2.restored_warm == (kind == "warm")
    bound, rec = _serve(svc2, api, "c", 5)
    # 13 descriptors came back (a finished one among them), 5 arrived: the
    # cold replay walked the 13 and the scan took 5 events, the warm one
    # carries no record of the job and its scan walked all 18
    assert (bound, rec.runnable_tasks_scanned, rec.graph_tasks_visited) == (5, 18, 5)
    bound, rec = _serve(svc2, api, "d", 2)
    assert (bound, rec.runnable_tasks_scanned, rec.graph_tasks_visited) == (2, 2, 2)
