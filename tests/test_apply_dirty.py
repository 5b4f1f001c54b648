"""`apply` refreshes the resource tree above the PUs whose running-task
lists changed, not every resource node.

Parity: a twin scheduler whose post-solve half calls
`update_resource_topology` on every root (the world as it was) must hold
the same two counts on every resource descriptor and the same capacity
on every parent -> child arc, journal the same changes in the same
order, export the same FlowProblem and reach the same objective, round
by round, over a seeded stream of every event that moves a task or a
machine, on both round paths. Counts: what the refresh visits is the
dirty PUs' paths to the root, and it says when it walked every node and
why it had to.
"""

import json
import os
import pickle
import random

import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.data import TaskType
from ksched_tpu.drivers.synthetic import add_machine
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime import checkpoint
from ksched_tpu.runtime.integrity import read_records, write_records
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng
from test_graph_worklist import (
    _admit,
    _filled_cluster,
    _RisingContinuation,
    _same_problem,
    _serve,
    _service,
)
from test_stats_dirty import DEPTH, MODELS, WORKLOADS, _batch, _paths
from test_stats_dirty import _World as _StatsWorld


def _every_root_refresh(gm):
    """The refresh as it was: the reference's walk from every root."""

    def refresh(roots):
        for rtnd in roots:
            gm.update_resource_topology(rtnd)

    return refresh


def _tree(sched):
    """Resource id -> (slots below, running tasks below, capacity of the
    arc from its parent; None for a root)."""
    gm = sched.gm
    out = {}
    for rid, node in gm.resource_to_node.items():
        rd = node.resource_descriptor
        parent = gm.node_to_parent_node.get(node.id)
        cap = None if parent is None else gm.cm.graph.get_arc(parent, node).cap_upper
        out[rid] = (rd.num_slots_below, rd.num_running_tasks_below, cap)
    return out


class _World(_StatsWorld):
    """`every_node`: the world whose refresh walks every node."""

    def __init__(self, model, every_node, pipeline):
        self.pipeline = pipeline
        self.journals = []
        super().__init__(model, every_node)

    def _install(self):
        gm = self.sched.gm
        if self.every_node:
            gm.refresh_resource_topology = _every_root_refresh(gm)
        optimized = gm.cm.get_optimized_graph_changes

        def recording_changes():
            changes = optimized()
            self.journals.append(list(changes))
            return changes

        gm.cm.get_optimized_graph_changes = recording_changes

    def round(self):
        if not self.pipeline:
            return self.sched.schedule_all_jobs()
        assert self.sched.schedule_all_jobs_async() is not None
        return self.sched.finish_scheduling()


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipeline"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_dirty_refresh_leaves_what_the_walk_of_every_node_leaves(model, pipeline, tmp_path):
    new = _World(model, every_node=False, pipeline=pipeline)
    ref = _World(model, every_node=True, pipeline=pipeline)
    worlds = (new, ref)
    rnd = random.Random(7)
    jobs = [101, 202, 303]
    uid = 1000
    gone = set()  # killed: bound for ever, never to be touched again
    walked_all = True  # the first refresh
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
        if step > 1:  # between two rounds: only `apply` writes the arcs on these paths
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
            walked_all = True  # a new graph manager's first refresh

        results = [w.round() for w in worlds]
        assert results[0][0] == results[1][0]
        assert [(d.type, d.task_id, d.resource_id) for d in results[0][1]] == [
            (d.type, d.task_id, d.resource_id) for d in results[1][1]
        ]
        assert _tree(new.sched) == _tree(ref.sched)
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert new.journals == ref.journals
        assert new.backend.objectives == ref.backend.objectives
        assert len(new.backend.objectives) == step + 1
        assert new.sched.task_bindings == ref.sched.task_bindings

        t = new.sched.last_timing
        nodes = len(new.sched.gm.resource_to_node)
        assert t.apply_full_walk == int(walked_all or t.apply_pus_dirty * DEPTH >= nodes)
        if t.apply_full_walk:
            assert t.apply_nodes_visited == nodes
        else:
            partial_rounds += 1
            assert t.apply_nodes_visited <= t.apply_pus_dirty * DEPTH
            assert t.apply_nodes_visited < nodes // 2
        walked_all = False
    assert partial_rounds >= 10  # the stream did exercise the dirty refresh
    assert sum(len(j) for j in new.journals) > 100
    # one more round, so that the last one's capacities reach a problem
    for w in worlds:
        w.admit(101, uid + 1, TaskType(0), 0, 0)
        w.round()
    _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
    assert new.backend.objectives == ref.backend.objectives


# ---------------------------------------------------------------------------
# Counts, and when every node is walked
# ---------------------------------------------------------------------------


def _apply(t):
    return (t.apply_pus_dirty, t.apply_nodes_visited, t.apply_full_walk)


def _whole_tree_agrees(sched):
    """The tree as the refresh left it is what the walk of every node
    would leave: walking it now changes nothing."""
    before = _tree(sched)
    for rid in sched.resource_roots:
        sched.gm.update_resource_topology(sched._root_rtnds[rid])
    assert _tree(sched) == before
    root = sched.resource_topology.resource_desc
    assert root.num_running_tasks_below == len(sched.task_bindings) + sum(
        len(gone) for gone in sched._departed.values()
    )


@pytest.mark.parametrize("resident", [200, 2000])
def test_a_round_refreshes_its_batchs_pus_and_their_ancestors_whatever_the_cluster(resident):
    sched, rmap, jmap, tmap = _filled_cluster(resident, backend=make_backend("native"))
    gm = sched.gm
    nodes = len(gm.resource_to_node)  # machines x (1 + 2 + 4) + the coordinator
    assert nodes == {200: 50, 2000: 365}[resident]
    # the first refresh of a graph manager walks every node
    first, t = _batch(sched, jmap, tmap, 10_001, 2)
    assert _apply(t) == (len(gm.leaf_node_ids), nodes, 1)
    # from then on the round's own placements, and nothing else
    second, t = _batch(sched, jmap, tmap, 10_101, 2)
    assert _apply(t)[::2] == (len(second), 0)
    assert t.apply_nodes_visited in _paths(second)
    # two completions: their PUs are dirty from the NEXT round's `deltas` phase on
    done = {sched.task_bindings[uid] for uid in (10_001, 10_002)}
    for uid in (10_001, 10_002):
        sched.handle_task_completion(tmap.find(uid))
    assert not gm._apply_dirty_pus
    third, t = _batch(sched, jmap, tmap, 10_201, 3)
    assert _apply(t)[::2] == (len(done | third), 0)
    assert t.apply_nodes_visited in _paths(done | third)
    # an eviction between two rounds: `stats` drains its own set at the
    # start of the next one, and the refresh still finds the PU in its
    evicted = sched.task_bindings[10_201]
    sched.handle_task_eviction(tmap.find(10_201), rmap.find(evicted).descriptor)
    assert (gm._apply_dirty_pus, gm._stats_dirty_pus) == ({evicted}, done | third)
    fourth, t = _batch(sched, jmap, tmap, 10_301, 1)
    assert t.stats_pus_dirty == len(done | third)
    assert _apply(t)[::2] == (len({evicted} | fourth | {sched.task_bindings[10_201]}), 0)
    assert not gm._apply_dirty_pus
    _whole_tree_agrees(sched)


def test_a_round_that_changes_most_pus_walks_every_node():
    sched, rmap, jmap, tmap = _filled_cluster(200, backend=make_backend("native"))
    nodes = len(sched.gm.resource_to_node)  # 7 machines: 50 nodes, 28 PUs
    _batch(sched, jmap, tmap, 10_001, 2)
    assert _batch(sched, jmap, tmap, 10_101, 2)[1].apply_full_walk == 0
    for uid in range(1, 101):
        sched.handle_task_completion(tmap.find(uid))
    _, t = _batch(sched, jmap, tmap, 10_201, 2)  # its `deltas` phase drops the hundred
    assert t.apply_pus_dirty * DEPTH >= nodes
    assert _apply(t) == (t.apply_pus_dirty, nodes, 1)
    assert _batch(sched, jmap, tmap, 10_301, 2)[1].apply_full_walk == 0
    _whole_tree_agrees(sched)


@pytest.mark.parametrize(
    "why", ["topology_added", "topology_removed", "preemption", "no_word", "raised", "not_inert"]
)
def test_every_node_is_walked_when_the_dirty_set_cannot_be_trusted(why):
    kwargs = {}
    if why == "preemption":
        kwargs["preemption"] = True
    if why == "not_inert":  # no hook of the model is called: it takes the short path too
        kwargs["model"] = _RisingContinuation
    sched, rmap, jmap, tmap = _filled_cluster(120, backend=make_backend("native"), **kwargs)
    gm = sched.gm
    roots = sched._root_rtnds.values()
    always = why == "preemption"
    _batch(sched, jmap, tmap, 10_001, 3)
    assert _batch(sched, jmap, tmap, 10_101, 3)[1].apply_full_walk == int(always)
    expect = 1
    if why == "topology_added":
        seed_rng(77)
        add_machine(sched, rmap, sched.resource_topology, 2, 2, 12, machine_index=99)
    elif why == "topology_removed":
        sched.deregister_resource(sched.resource_topology.children[0])
    elif why == "no_word":
        # a refresh that no `deltas` phase of FlowScheduler's preceded
        gm.refresh_resource_topology(roots)
        assert _apply(gm) == (0, len(gm.resource_to_node), 1)
        expect = int(always)
    elif why == "raised":
        # a refresh that did not reach its end owes a walk of every node
        change, boom = gm.cm.change_arc_capacity, RuntimeError("half-way")

        def raising(*args):
            raise boom

        gm.cm.change_arc_capacity = raising
        _admit(sched, jmap, tmap, 7, [10_151])
        with pytest.raises(RuntimeError, match="half-way"):
            sched.schedule_all_jobs()
        gm.cm.change_arc_capacity = change
        assert gm._apply_dirty_pus
    elif why == "not_inert":
        assert not gm._tasks_inert
        expect = 0
    _, t = _batch(sched, jmap, tmap, 10_201, 3)
    assert t.apply_full_walk == expect
    assert (t.apply_nodes_visited == len(gm.resource_to_node)) == bool(expect)
    _, t = _batch(sched, jmap, tmap, 10_301, 3)
    assert t.apply_full_walk == int(always)
    _whole_tree_agrees(sched)


def _old_manifest(wal_path):
    """The manifest as the build before this set wrote it: version 5,
    its graph manager without the refresh's set and flags."""
    records = dict(read_records(wal_path))
    meta = json.loads(records["meta"])
    assert meta["version"] == checkpoint.WARM_MANIFEST_VERSION >= 6
    meta["version"] = 5
    payload = pickle.loads(records["core"])
    gm = payload["scheduler"]["gm"]
    for name in ("_apply_dirty_pus", "_apply_walk_owed", "_apply_lists_kept"):
        delattr(gm, name)
    write_records(
        wal_path,
        [("meta", json.dumps(meta).encode()), ("core", pickle.dumps(payload)), ("warm", records["warm"])],
    )


@pytest.mark.parametrize("kind", ["warm_restore", "cold_restore", "old_manifest"])
def test_a_restored_service_walks_every_node_at_most_once_and_then_its_batches(tmp_path, kind):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    bound, rec = _serve(svc, api, "b", 4)
    assert rec.apply_pus_dirty > 0 and rec.apply_full_walk == 0
    svc.complete_pod("a_0")
    # a migration after the last refresh: both PUs are on the set the checkpoint carries
    sched = svc.scheduler
    uid = svc.pod_to_task["b_0"]
    here = sched.task_bindings[uid]
    there = next(rid for rid in sorted(sched.gm.leaf_resource_ids) if not sched.resource_bindings.get(rid))
    sched.handle_task_migration(sched.task_map.find(uid), sched.resource_map.find(there).descriptor)
    ck = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(ck)
    if kind == "cold_restore":
        os.remove(ck + ".wal")
    elif kind == "old_manifest":
        _old_manifest(ck + ".wal")

    def restore():
        return SchedulerService.restore(
            api, ck, backend=make_backend("native"), backend_name="native", tracer=RoundTracer(),
        )

    if kind == "old_manifest":
        with pytest.warns(RuntimeWarning, match="unsupported warm manifest version 5"):
            svc2 = restore()
    else:
        svc2 = restore()
    warm = kind == "warm_restore"
    assert svc2.restored_warm == warm
    gm = svc2.scheduler.gm
    nodes = len(gm.resource_to_node)
    if warm:  # the graph manager came back with its set and no walk owed
        assert gm._apply_dirty_pus == sched.gm._apply_dirty_pus == {here, there}
        assert not gm._apply_walk_owed
    else:
        assert gm._apply_walk_owed
    bound, rec = _serve(svc2, api, "c", 5)
    if warm:
        assert rec.apply_full_walk == 0 and rec.apply_nodes_visited < nodes
        assert rec.apply_pus_dirty >= 3  # the migration's two PUs, the departure's, the batch's
    else:
        assert (rec.apply_nodes_visited, rec.apply_full_walk) == (nodes, 1)
    bound, rec = _serve(svc2, api, "d", 2)
    assert rec.apply_full_walk == 0 and rec.apply_nodes_visited < nodes
    _whole_tree_agrees(svc2.scheduler)
    assert len(svc2.scheduler.task_bindings) == 9 + 4 - 1 + 5 + 2


def test_the_apply_span_and_the_round_record_carry_the_three_counts():
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 6)
    with SpanTracer() as tracer:
        bound, rec = _serve(svc, api, "b", 3)
    pus = {svc.scheduler.task_bindings[svc.pod_to_task[f"b_{i}"]] for i in range(3)}
    (ev,) = [e for e in tracer.events() if e["name"] == "apply"]
    counts = {k: ev["args"][k] for k in ("apply_pus_dirty", "apply_nodes_visited", "apply_full_walk")}
    assert counts == {
        "apply_pus_dirty": len(pus),
        "apply_nodes_visited": rec.apply_nodes_visited,
        "apply_full_walk": 0,
    }
    assert (rec.apply_pus_dirty, rec.apply_full_walk) == (len(pus), 0)
    assert rec.apply_nodes_visited in _paths(pus)
    t = svc.scheduler.last_timing
    assert _apply(t) == (rec.apply_pus_dirty, rec.apply_nodes_visited, rec.apply_full_walk)
    svc.run_round([], solve=False)
    svc.run_round([])
    for idle in svc.tracer.records[-2:]:
        assert (idle.apply_pus_dirty, idle.apply_nodes_visited, idle.apply_full_walk) == (0, 0, 0)
