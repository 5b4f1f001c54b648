"""The statistics pass gathers the PUs whose running-task lists changed
and their ancestors, not every resource node.

Parity: a twin scheduler whose graph manager walks every node every
round (`_every_node_statistics` of test_graph_worklist.py, the world as
it was) must hold the same aggregates on every resource descriptor
right after the pass and after the round, export the same FlowProblem
and reach the same objective, round by round, over a seeded stream of
every event that moves a task or a machine. Counts: what the pass
prepares is the dirty PUs' paths to the root, and it says when it
walked every node and why it had to.
"""

import dataclasses
import os
import random
import types

import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType
from ksched_tpu.data import TaskType
from ksched_tpu.drivers import build_cluster
from ksched_tpu.drivers.synthetic import add_machine
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.checkpoint import restore_scheduler, save_scheduler
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng
from test_graph_worklist import (
    _admit,
    _every_node_statistics,
    _filled_cluster,
    _Recording,
    _RisingContinuation,
    _same_problem,
    _serve,
    _service,
)

MODELS = {
    name: MODEL_REGISTRY[getattr(CostModelType, name.upper())]
    for name in ("trivial", "coco", "whare", "net", "k8s_antiaffinity", "k8s_zonespread")
}
CORES, PUS_PER_CORE, SLOTS = 2, 2, 3
DEPTH = 4  # PU, core, machine, coordinator
WORKLOADS = 5


def _aggregates(rmap):
    """Resource id -> the four aggregates the pass leaves on its descriptor."""
    return {
        rid: (
            rs.descriptor.num_slots_below,
            rs.descriptor.num_running_tasks_below,
            dataclasses.astuple(rs.descriptor.whare_map_stats),
            rs.descriptor.reserved_resources.net_bw,
        )
        for rid, rs in rmap.items()
    }


class _Objectives(_Recording):
    """Keeps every problem it is given, and the objective it reached."""

    def __init__(self):
        super().__init__()
        self.objectives = []

    def solve(self, problem):
        result = super().solve(problem)
        self.objectives.append(result.objective)
        return result


class _World:
    def __init__(self, model, every_node, machines=12):
        seed_rng(11)  # the same resource ids in both worlds
        self.model, self.every_node = model, every_node
        self.backend = _Objectives()
        self.sched, self.rmap, self.jmap, self.tmap, self.root = build_cluster(
            num_machines=machines, num_cores=CORES, pus_per_core=PUS_PER_CORE,
            max_tasks_per_pu=SLOTS, backend=self.backend, cost_model_factory=MODELS[model],
        )
        for machine in self.root.children:
            machine.resource_desc.capacity.net_bw = 1000
        self.machines_added = machines
        self._install()

    def _install(self):
        """The pass of this world, and a record of what it left."""
        gm = self.sched.gm
        if self.every_node:
            gm.compute_topology_statistics = types.MethodType(_every_node_statistics, gm)
        run = gm.compute_topology_statistics

        def recording(start):
            run(start)
            self.after_pass = _aggregates(self.rmap)

        gm.compute_topology_statistics = recording

    def td(self, uid):
        return self.tmap.find(uid)

    def rd(self, rid):
        return self.rmap.find(rid).descriptor

    def admit(self, job_id, uid, task_type, workload, net_bw):
        _admit(self.sched, self.jmap, self.tmap, job_id, [uid], None, task_type)
        self.td(uid).workload = workload
        self.td(uid).resource_request.net_bw = net_bw

    def complete(self, uid):
        self.sched.handle_task_completion(self.td(uid))

    def fail(self, uid):
        self.sched.handle_task_failure(self.td(uid))

    def kill(self, uid):
        self.sched.kill_running_task(uid)

    def evict(self, uid):
        self.sched.handle_task_eviction(self.td(uid), self.rd(self.sched.task_bindings[uid]))

    def place(self, uid, rid):
        self.sched.handle_task_placement(self.td(uid), self.rd(rid))

    def migrate(self, uid, rid):
        self.sched.handle_task_migration(self.td(uid), self.rd(rid))

    def add_machine(self):
        seed_rng(500 + self.machines_added)
        machine = add_machine(
            self.sched, self.rmap, self.root, CORES, PUS_PER_CORE, SLOTS,
            machine_index=self.machines_added,
        )
        machine.resource_desc.capacity.net_bw = 1000
        self.machines_added += 1

    def remove_machine(self, index):
        self.sched.deregister_resource(self.root.children[index])

    def restore(self, path):
        save_scheduler(self.sched, path)
        self.sched, self.rmap, self.jmap, self.tmap = restore_scheduler(
            path, cost_model_factory=MODELS[self.model], backend=self.backend
        )
        self.root = self.sched.resource_topology
        self._install()

    def free_pu(self, rnd, uid):
        """A PU with a free slot, on a machine that holds no task of
        `uid`'s workload and is not the one `uid` runs on."""
        workload = self.td(uid).workload
        here = self.sched.task_bindings.get(uid)
        choices = []
        for machine in self.root.children:
            pus = [pu.resource_desc for core in machine.children for pu in core.children]
            held = [t for pu in pus for t in pu.current_running_tasks]
            if any(self.td(t).workload == workload for t in held):
                continue
            choices += [pu for pu in pus if len(pu.current_running_tasks) < SLOTS]
        rids = sorted(int(pu.uuid) for pu in choices)
        rids = [rid for rid in rids if rid != here]
        return rnd.choice(rids) if rids else None


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_dirty_pass_leaves_what_the_walk_of_every_node_leaves(model, tmp_path):
    new = _World(model, every_node=False)
    ref = _World(model, every_node=True)
    assert new.sched.gm._tasks_inert
    worlds = (new, ref)
    rnd = random.Random(7)
    jobs = [101, 202, 303]
    uid = 1000
    gone = set()  # killed: bound for ever, never to be touched again
    walked_all = True  # the first pass
    partial_rounds = 0
    for step in range(16):
        for _ in range(rnd.randrange(3, 8)):
            uid += 1
            args = (rnd.choice(jobs), uid, TaskType(rnd.randrange(4)),
                    rnd.randrange(WORKLOADS), rnd.choice((0, 0, 40, 90)))
            for w in worlds:
                w.admit(*args)
        running = sorted(t for t in new.sched.task_bindings if t not in gone)
        assert sorted(new.sched.task_bindings) == sorted(ref.sched.task_bindings)
        rnd.shuffle(running)
        if step:
            for t in [running.pop() for _ in range(min(len(running), rnd.randrange(0, 4)))]:
                for w in worlds:
                    w.complete(t)
        if step % 5 == 2 and running:
            t = running.pop()
            for w in worlds:
                w.fail(t)
        if step > 11 and running:  # a restore cannot replay a killed task's binding
            t = running.pop()
            gone.add(t)
            for w in worlds:
                w.kill(t)
        if step > 1:
            for t in [running.pop() for _ in range(min(len(running), rnd.randrange(0, 3)))]:
                for w in worlds:
                    w.evict(t)
                rid = new.free_pu(rnd, t) if rnd.random() < 0.5 else None
                if rid is not None:  # placed again by hand, not by the round
                    for w in worlds:
                        w.place(t, rid)
        if step > 2 and running:
            t = running.pop()
            rid = new.free_pu(rnd, t)
            if rid is not None:
                for w in worlds:
                    w.migrate(t, rid)
        if step == 6:
            for w in worlds:
                w.add_machine()
            walked_all = True
        if step == 9:
            for w in worlds:
                w.remove_machine(2)
            walked_all = True
        if step == 11:
            for i, w in enumerate(worlds):
                w.restore(str(tmp_path / f"world{i}.ckpt"))
            assert new.sched.gm.stats_full_walk == 1  # the pass the restore made
            walked_all = True  # and no `deltas` phase has run since

        results = [w.sched.schedule_all_jobs() for w in worlds]
        assert results[0][0] == results[1][0]
        assert [(d.type, d.task_id, d.resource_id) for d in results[0][1]] == [
            (d.type, d.task_id, d.resource_id) for d in results[1][1]
        ]
        assert new.after_pass == ref.after_pass
        assert _aggregates(new.rmap) == _aggregates(ref.rmap)
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert new.backend.objectives == ref.backend.objectives
        assert len(new.backend.objectives) == step + 1
        assert new.sched.task_bindings == ref.sched.task_bindings

        t = new.sched.last_timing
        nodes = len(new.sched.gm.resource_to_node)
        assert t.stats_full_walk == int(walked_all or t.stats_pus_dirty * DEPTH >= nodes)
        if t.stats_full_walk:
            assert t.stats_nodes_visited == nodes
        else:
            partial_rounds += 1
            assert t.stats_nodes_visited <= t.stats_pus_dirty * DEPTH
            assert t.stats_nodes_visited < nodes // 2
        walked_all = False
    assert partial_rounds >= 10  # the stream did exercise the dirty pass
    # the stream moved every aggregate the models keep
    census = [a[2] for a in new.after_pass.values()]
    if model in ("coco", "whare"):
        assert len({c[1:] for c in census}) > 4
    if model == "net":
        assert len({a[3] for a in new.after_pass.values()}) > 2


# ---------------------------------------------------------------------------
# Counts, and when every node is walked
# ---------------------------------------------------------------------------


def _batch(sched, jmap, tmap, first_uid, n):
    """A round over `n` new pods; the PUs it bound them to, and its timing."""
    uids = range(first_uid, first_uid + n)
    _admit(sched, jmap, tmap, 7, uids)
    sched.schedule_all_jobs()
    assert all(uid in sched.task_bindings for uid in uids)
    return {sched.task_bindings[uid] for uid in uids}, sched.last_timing


def _stats(t):
    return (t.stats_pus_dirty, t.stats_nodes_visited, t.stats_full_walk)


def _paths(pus):
    """Bounds on the resource nodes on the paths from `pus` PUs up to the root."""
    return range(len(pus) + DEPTH - 1, len(pus) * (DEPTH - 1) + 2)


def test_a_round_gathers_its_batchs_pus_and_their_ancestors_whatever_the_cluster():
    sched, rmap, jmap, tmap = _filled_cluster(2000, backend=make_backend("native"))
    gm = sched.gm
    nodes = len(gm.resource_to_node)  # 52 machines x (1 + 2 + 4) + the coordinator
    assert nodes == 365
    # the fill bound its pods by the placement event: no `deltas` phase
    # has vouched for the lists yet
    first, t = _batch(sched, jmap, tmap, 10_001, 5)
    assert _stats(t) == (208, nodes, 1)
    # that round's placements, and nothing else
    second, t = _batch(sched, jmap, tmap, 10_101, 5)
    assert (t.stats_pus_dirty, t.stats_full_walk) == (len(first), 0)
    assert t.stats_nodes_visited in _paths(first)
    # two completions: their PUs are dirty from the NEXT round's `deltas` phase on
    done = {sched.task_bindings[uid] for uid in (10_001, 10_002)}
    for uid in (10_001, 10_002):
        sched.handle_task_completion(tmap.find(uid))
    third, t = _batch(sched, jmap, tmap, 10_201, 3)
    assert (t.stats_pus_dirty, t.stats_full_walk) == (len(second), 0)
    assert gm._stats_dirty_pus == done | third  # dropped by that round's `deltas` phase
    _, t = _batch(sched, jmap, tmap, 10_301, 3)
    assert (t.stats_pus_dirty, t.stats_full_walk) == (len(done | third), 0)
    assert t.stats_nodes_visited in _paths(done | third)
    root = sched.resource_topology.resource_desc
    assert root.num_running_tasks_below == len(sched.task_bindings) == 2000 + 5 + 5 + 3 + 3 - 2


def test_a_round_that_changes_most_pus_walks_every_node():
    sched, rmap, jmap, tmap = _filled_cluster(200, backend=make_backend("native"))
    nodes = len(sched.gm.resource_to_node)  # 7 machines: 50 nodes, 28 PUs
    _batch(sched, jmap, tmap, 10_001, 2)
    assert _batch(sched, jmap, tmap, 10_101, 2)[1].stats_full_walk == 0
    for uid in range(1, 101):
        sched.handle_task_completion(tmap.find(uid))
    _batch(sched, jmap, tmap, 10_201, 2)  # its `deltas` phase drops the hundred
    _, t = _batch(sched, jmap, tmap, 10_301, 2)
    assert t.stats_pus_dirty * DEPTH >= nodes
    assert _stats(t) == (t.stats_pus_dirty, nodes, 1)
    assert _batch(sched, jmap, tmap, 10_401, 2)[1].stats_full_walk == 0
    root = sched.resource_topology.resource_desc
    assert root.num_running_tasks_below == len(sched.task_bindings) == 200 + 10 - 100


@pytest.mark.parametrize("why", ["topology_added", "topology_removed", "preemption", "not_inert", "no_word"])
def test_every_node_is_walked_when_the_dirty_set_cannot_be_trusted(why):
    kwargs = {}
    if why == "preemption":
        kwargs["preemption"] = True
    if why == "not_inert":
        kwargs["model"] = _RisingContinuation
        assert not _RisingContinuation.pinned_tasks_are_inert
    sched, rmap, jmap, tmap = _filled_cluster(120, backend=make_backend("native"), **kwargs)
    gm = sched.gm
    always = why in ("preemption", "not_inert")
    _batch(sched, jmap, tmap, 10_001, 3)
    assert _batch(sched, jmap, tmap, 10_101, 3)[1].stats_full_walk == int(always)
    if why == "topology_added":
        seed_rng(77)
        add_machine(sched, rmap, sched.resource_topology, 2, 2, 12, machine_index=99)
    elif why == "topology_removed":
        sched.deregister_resource(sched.resource_topology.children[0])
    elif why == "no_word":
        # a pass that no `deltas` phase of FlowScheduler's preceded
        gm.compute_topology_statistics(gm.sink_node)
        assert gm.stats_full_walk == 0  # the round before did vouch
        gm.compute_topology_statistics(gm.sink_node)
        assert (gm.stats_pus_dirty, gm.stats_full_walk) == (0, 1)
    if why != "no_word":
        _, t = _batch(sched, jmap, tmap, 10_201, 3)
        assert _stats(t)[1:] == (len(gm.resource_to_node), 1)
    _, t = _batch(sched, jmap, tmap, 10_301, 3)
    assert t.stats_full_walk == int(always or why == "no_word")
    assert (t.stats_nodes_visited == len(gm.resource_to_node)) == bool(t.stats_full_walk)
    _, t = _batch(sched, jmap, tmap, 10_401, 3)
    assert t.stats_full_walk == int(always)
    root = sched.resource_topology.resource_desc
    assert root.num_running_tasks_below == len(sched.task_bindings)


@pytest.mark.parametrize("kind", ["warm", "cold"])
def test_a_restored_service_walks_every_node_once_and_then_its_batches(tmp_path, kind):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    bound, rec = _serve(svc, api, "b", 4)
    assert rec.stats_pus_dirty > 0 and rec.stats_full_walk == 0
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
    nodes = len(gm.resource_to_node)
    if kind == "warm":
        # the graph manager came back with its set and the last round's word
        assert gm._stats_dirty_pus == svc.scheduler.gm._stats_dirty_pus != set()
    else:
        assert gm.stats_full_walk == 1  # the pass the restore made
    bound, rec = _serve(svc2, api, "c", 5)
    if kind == "warm":
        assert rec.stats_full_walk == 0 and rec.stats_nodes_visited < nodes
    else:  # placements replayed, and no `deltas` phase since the restore's pass
        assert (rec.stats_nodes_visited, rec.stats_full_walk) == (nodes, 1)
    bound, rec = _serve(svc2, api, "d", 2)
    assert rec.stats_full_walk == 0 and rec.stats_nodes_visited < nodes
    root = svc2.scheduler.resource_topology.resource_desc
    assert root.num_running_tasks_below == len(svc2.scheduler.task_bindings) == 9 + 4 - 1 + 5 + 2


def test_the_stats_span_and_the_round_record_carry_the_three_counts():
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 6)
    pus = {svc.scheduler.task_bindings[svc.pod_to_task[f"a_{i}"]] for i in range(6)}
    with SpanTracer() as tracer:
        bound, rec = _serve(svc, api, "b", 3)
    (ev,) = [e for e in tracer.events() if e["name"] == "stats"]
    counts = {k: ev["args"][k] for k in ("stats_pus_dirty", "stats_nodes_visited", "stats_full_walk")}
    assert counts == {
        "stats_pus_dirty": len(pus),
        "stats_nodes_visited": rec.stats_nodes_visited,
        "stats_full_walk": 0,
    }
    assert (rec.stats_pus_dirty, rec.stats_full_walk) == (len(pus), 0)
    assert rec.stats_nodes_visited in _paths(pus)
    svc.run_round([], solve=False)
    svc.run_round([])
    for idle in svc.tracer.records[-2:]:
        assert (idle.stats_pus_dirty, idle.stats_nodes_visited, idle.stats_full_walk) == (0, 0, 0)
