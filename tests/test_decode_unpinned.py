"""The post-solve half of a round works on the tasks whose binding can
change: `decode` drops the arcs of pinned tasks with a mask, `deltas`
runs over that mapping only, and every PU's `current_running_tasks` is
kept by events instead of being emptied and refilled each round.

Parity: a twin scheduler that runs what this replaced (kept here as the
reference: the full decode, the PU walk, the per-mapped-task call and
the unbind that left the lists alone, plain copies of the code as it
was) is fed the same seeded stream. After every round the new decode
must equal the full one restricted to unpinned tasks, the deltas must
be the same multiset, and the lists must hold the same members at both
points where they are read. Counts only; no times.

One thing differs from the copy: the full decode took its nodes in the
order `set` iteration over its frontiers gave, which depends on the
pinned nodes being there (a set's order follows its size) and decides
which of several equal-cost PUs a task reads. No decode without them
can reproduce it, so both take nodes by (stratum, node id); the copy
with the old order is held to what no order can change (the same tasks
on the same multiset of PUs).
"""

import functools
import os
import random
import types

import numpy as np
import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType
from ksched_tpu.data import (
    DeltaType,
    ResourceState,
    ResourceType,
    SchedulingDelta,
    TaskState,
    TaskType,
)
from ksched_tpu.drivers import build_cluster
from ksched_tpu.graph.flowgraph import NodeType
from ksched_tpu.obs.spans import SpanTracer, span
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.decode import flow_to_mapping
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import resource_id_from_string, seed_rng
from test_graph_worklist import (
    _admit,
    _filled_cluster,
    _Recording,
    _same_problem,
    _serve,
    _service,
)

# ---------------------------------------------------------------------------
# The reference: the post-solve half as it was
# ---------------------------------------------------------------------------


def _flow_to_mapping_as_it_was(
    problem, total_flow, leaf_node_ids, sink_node_id, task_node_ids, by_node_id
):
    """solver/decode.py before pinned tasks left it. ``by_node_id``
    replaces its one accident (see the module docstring)."""
    src = problem.src
    dst = problem.dst
    live = np.nonzero(total_flow > 0)[0]
    task_nodes = set(int(t) for t in task_node_ids)
    leaf_set = set(int(x) for x in leaf_node_ids)
    incoming = {}
    for i in live:
        incoming.setdefault(int(dst[i]), []).append((int(src[i]), int(total_flow[i])))
    level = {sink_node_id: 0}
    frontier = {sink_node_id}
    rounds = 0
    while frontier:
        rounds += 1
        if rounds > problem.num_nodes:
            raise RuntimeError("positive-flow cycle detected during decode")
        nxt = set()
        for w in frontier:
            lw = level[w]
            for s, _f in incoming.get(w, []):
                if level.get(s, -1) < lw + 1:
                    level[s] = lw + 1
                    nxt.add(s)
        frontier = nxt
    pu_units = {}
    for s, f in incoming.get(sink_node_id, []):
        if s in leaf_set and f > 0:
            pu_units[s] = [s] * f
    mapping = {}
    key = (lambda v: (level[v], v)) if by_node_id else (lambda v: level[v])
    order = sorted((v for v in level if v != sink_node_id), key=key)
    for v in order:
        units = pu_units.get(v)
        if units is None:
            continue
        if v in task_nodes:
            if len(units) != 1:
                raise AssertionError(f"task node {v} decoded {len(units)} units")
            mapping[v] = units[0]
            continue
        it = 0
        for s, f in incoming.get(v, []):
            take = min(f, len(units) - it)
            if take > 0:
                pu_units.setdefault(s, []).extend(units[it : it + take])
                it += take
            if it >= len(units):
                break
    return mapping


def _binding_delta_as_it_was(gm, task_node_id, res_node_id, task_bindings):
    task_node = gm.cm.graph.node(task_node_id)
    assert task_node is not None and task_node.is_task_node
    res_node = gm.cm.graph.node(res_node_id)
    assert res_node is not None and res_node.type == NodeType.PU
    task = task_node.task
    rd = res_node.resource_descriptor
    bound = task_bindings.get(task.uid)
    if bound is None:
        return SchedulingDelta(DeltaType.PLACE, task.uid, rd.uuid)
    if bound != resource_id_from_string(rd.uuid):
        return SchedulingDelta(DeltaType.MIGRATE, task.uid, rd.uuid)
    rd.current_running_tasks.append(task.uid)
    return None


def _preempt_deltas_as_they_were(gm, task_mapping, resource_map):
    deltas = []
    for rs in resource_map.unsafe_get().values():
        rd = rs.descriptor
        for task_id in rd.current_running_tasks:
            task_node = gm.task_to_node.get(task_id)
            if task_node is None:
                continue
            if task_node.id not in task_mapping:
                deltas.append(SchedulingDelta(DeltaType.PREEMPT, task_id, rd.uuid))
        rd.current_running_tasks = []
    return deltas


def _finish_round_as_it_was(self, task_mappings, timing, round_span):
    with span("deltas"):
        deltas = _preempt_deltas_as_they_were(self.gm, task_mappings, self.resource_map)
        self.delta_calls = len(task_mappings)
        for task_node_id, res_node_id in task_mappings.items():
            delta = _binding_delta_as_it_was(
                self.gm, task_node_id, res_node_id, self.task_bindings
            )
            if delta is not None:
                deltas.append(delta)
    with span("apply"):
        num_scheduled = self._apply_scheduling_deltas(deltas)
        for rid in self.resource_roots:
            self.gm.update_resource_topology(self._root_rtnds[rid])
    self.gm.purge_unconnected_equiv_class_nodes()
    unscheduled = [
        t for tasks in self.runnable_tasks.values() for t in tasks if t not in self.task_bindings
    ]
    self.cost_model.note_round(unscheduled)
    round_span.set("num_scheduled", num_scheduled)
    timing.total_s = round_span.finish()
    self.last_timing = timing
    return num_scheduled, deltas


def _unbind_as_it_was(self, td, rid, departed=False):
    task_id = td.uid
    rd = self.resource_map.find(rid).descriptor
    if len(rd.current_running_tasks) == 0:
        rd.state = ResourceState.IDLE
    if task_id not in self.task_bindings:
        return False
    task_set = self.resource_bindings.get(rid, set())
    if task_id not in task_set:
        return False
    del self.task_bindings[task_id]
    task_set.discard(task_id)
    return True


def _complete_as_it_was(self, token):
    """PlacementSolver.complete: every task node, every positive arc."""
    problem, _decode_set, pending, is_async = token
    assert not is_async
    self.last_result = pending
    gm = self.gm
    self.full_mapping = _flow_to_mapping_as_it_was(
        problem,
        pending.total_flow(problem),
        gm.leaf_node_ids,
        gm.sink_node.id,
        [node.id for node in gm.task_to_node.values()],
        by_node_id=True,
    )
    return self.full_mapping


# ---------------------------------------------------------------------------
# Two worlds fed the same events
# ---------------------------------------------------------------------------


def _lists(rmap):
    """PU resource id -> its current_running_tasks, sorted."""
    return {
        rid: sorted(rs.descriptor.current_running_tasks)
        for rid, rs in rmap.items()
        if rs.descriptor.type == ResourceType.PU
    }


class _World:
    def __init__(self, model, preemption, as_it_was):
        seed_rng(11)  # the same resource ids in both worlds
        self.backend = _Recording()
        self.sched, self.rmap, self.jmap, self.tmap, self.root = build_cluster(
            num_machines=5, num_cores=2, pus_per_core=2, max_tasks_per_pu=4,
            backend=self.backend, cost_model_factory=model, preemption=preemption,
        )
        sched = self.sched
        self.as_it_was = as_it_was
        if as_it_was:
            sched._finish_round = types.MethodType(_finish_round_as_it_was, sched)
            sched._unbind_task_from_resource = types.MethodType(_unbind_as_it_was, sched)
            sched.solver.complete = types.MethodType(_complete_as_it_was, sched.solver)
        else:
            complete = sched.solver.complete

            def recording_complete(token):
                # what the dispatch handed the decode, and what came back
                self.unpinned_at_dispatch = set(token[1][0])
                self.mapping = complete(token)
                return self.mapping

            sched.solver.complete = recording_complete
        stats = sched.gm.compute_topology_statistics

        def recording_stats(start):
            self.lists_at_stats = _lists(self.rmap)
            return stats(start)

        sched.gm.compute_topology_statistics = recording_stats

    def evict(self, uid):
        rid = self.sched.task_bindings[uid]
        rd = self.rmap.find(rid).descriptor
        self.sched.handle_task_eviction(self.tmap.find(uid), rd)
        if self.as_it_was:
            # the one intended difference (ROADMAP D10): an evicted task
            # leaves its PU's list when it is evicted; as it was it
            # stayed until the next round's walk
            assert uid in rd.current_running_tasks
            rd.current_running_tasks.remove(uid)
        assert uid not in rd.current_running_tasks


def _delta_key(d):
    return (int(d.type), d.task_id, d.resource_id)


@functools.lru_cache(maxsize=None)
def _stream(model, preemption):
    """Both worlds through 12 rounds of arrivals, completions, a failure
    and an eviction; one record a round."""
    factory = MODEL_REGISTRY[getattr(CostModelType, model.upper())]
    new = _World(factory, preemption, as_it_was=False)
    ref = _World(factory, preemption, as_it_was=True)
    worlds = (new, ref)
    rnd = random.Random(7)
    uid = 1000
    rounds = []
    for step in range(12):
        for _ in range(rnd.randrange(3, 8)):
            uid += 1
            ttype = TaskType(rnd.randrange(4))
            for w in worlds:
                _admit(w.sched, w.jmap, w.tmap, 100 + uid % 3, [uid], task_type=ttype)
        running = sorted(new.sched.task_bindings)
        assert running == sorted(ref.sched.task_bindings)
        rnd.shuffle(running)
        done = running[: rnd.randrange(1, 4)] if step else []
        for t in done:
            for w in worlds:
                w.sched.handle_task_completion(w.tmap.find(t))
        failed = running[3:4] if step == 4 else []
        for t in failed:
            for w in worlds:
                w.sched.handle_task_failure(w.tmap.find(t))
        evicted = running[4:5] if step == 6 else []
        for t in evicted:
            for w in worlds:
                w.evict(t)
        before = {t: new.sched.gm.task_to_node[t].id for t in new.sched.task_bindings}
        results = [w.sched.schedule_all_jobs() for w in worlds]
        gm = new.sched.gm
        rounds.append(
            {
                "step": step,
                "gone": done + failed,
                "evicted": evicted,
                "running_nodes_before": before,
                "placed": [r[0] for r in results],
                "deltas": [[_delta_key(d) for d in r[1]] for r in results],
                "mapping": list(new.mapping.items()),
                "unpinned_at_dispatch": new.unpinned_at_dispatch,
                "full_mapping": list(ref.sched.solver.full_mapping.items()),
                "problems": (new.backend.problems[-1], ref.backend.problems[-1]),
                "lists_at_stats": (new.lists_at_stats, ref.lists_at_stats),
                "lists_after_apply": (_lists(new.rmap), _lists(ref.rmap)),
                "bindings": (dict(new.sched.task_bindings), dict(ref.sched.task_bindings)),
                "timing": new.sched.last_timing,
                "ref_delta_calls": ref.sched.delta_calls,
                "pinned_now": gm.num_pinned,
                "unpinned_now": set(gm.unpinned_task_nodes),
                "task_nodes_now": {n.id for n in gm.task_to_node.values()},
                "mask_now": set(np.flatnonzero(gm.pinned_mask(4096)).tolist()),
                "running_arcs_now": {gm.task_to_node[t].id for t in gm.task_to_running_arc},
            }
        )
    return rounds


MODELS = ["coco", "trivial"]
PINNED = pytest.mark.parametrize("model", MODELS)


@PINNED
def test_the_stream_exercises_what_it_claims(model):
    rounds = _stream(model, False)
    assert sum(len(r["gone"]) for r in rounds) >= 10
    assert sum(len(r["evicted"]) for r in rounds) == 1
    assert all(r["placed"][0] > 0 for r in rounds)
    # a resident population the decode leaves alone, from the second round on
    assert all(r["timing"].decode_pinned_skipped >= 2 for r in rounds[1:])


@PINNED
def test_both_worlds_solve_the_same_problem_every_round(model):
    for r in _stream(model, False):
        new, ref = r["problems"]
        _same_problem(new, ref)
        assert r["placed"][0] == r["placed"][1]
        assert r["bindings"][0] == r["bindings"][1]


@PINNED
def test_decode_equals_the_full_decode_restricted_to_unpinned_tasks(model):
    for r in _stream(model, False):
        unpinned = r["unpinned_at_dispatch"]
        want = [(t, pu) for t, pu in r["full_mapping"] if t in unpinned]
        assert r["mapping"] == want, r["step"]  # pair for pair, and in the same order
        # what was left out is every running task, on the PU it was bound to
        left_out = {t for t, _pu in r["full_mapping"]} - unpinned
        assert left_out == set(r["running_nodes_before"].values())


@PINNED
def test_deltas_are_the_multiset_the_full_walk_produced(model):
    for r in _stream(model, False):
        assert sorted(r["deltas"][0]) == sorted(r["deltas"][1]), r["step"]
        assert all(d[0] == int(DeltaType.PLACE) for d in r["deltas"][0])
        # and they come from the batch, not from every resident task
        t = r["timing"]
        assert len(r["mapping"]) == r["placed"][0] <= t.decode_tasks
        assert r["ref_delta_calls"] == len(r["mapping"]) + t.decode_pinned_skipped


@PINNED
def test_the_lists_hold_the_same_members_where_they_are_read(model):
    rounds = _stream(model, False)
    for r in rounds:
        assert r["lists_at_stats"][0] == r["lists_at_stats"][1], r["step"]
        assert r["lists_after_apply"][0] == r["lists_after_apply"][1], r["step"]
        at_stats = [t for members in r["lists_at_stats"][0].values() for t in members]
        after = [t for members in r["lists_after_apply"][0].values() for t in members]
        for t in r["gone"]:
            # a finished or failed task keeps its slot through `stats`
            # and the solve of the round after; `deltas` lets go of it
            assert t in at_stats and t not in after
        for t in r["evicted"]:
            assert t not in at_stats
        # after a round each PU lists exactly the tasks bound to it
        assert sorted(after) == sorted(r["bindings"][0])
        for rid, members in r["lists_after_apply"][0].items():
            assert all(r["bindings"][0][t] == rid for t in members)


@PINNED
def test_the_pinned_mask_follows_the_events(model):
    for r in _stream(model, False):
        assert r["mask_now"] == r["running_arcs_now"]
        assert r["pinned_now"] == len(r["mask_now"])
        assert r["unpinned_now"] == r["task_nodes_now"] - r["mask_now"]


@PINNED
def test_under_preemption_mapping_and_deltas_are_as_they_were_order_included(model):
    rounds = _stream(model, True)
    for r in rounds:
        assert r["mapping"] == r["full_mapping"], r["step"]
        assert r["deltas"][0] == r["deltas"][1], r["step"]
        assert r["lists_at_stats"][0] == r["lists_at_stats"][1]
        assert r["lists_after_apply"][0] == r["lists_after_apply"][1]
        t = r["timing"]
        assert t.decode_pinned_skipped == 0 and r["pinned_now"] == 0
        assert t.decode_tasks == len(r["unpinned_at_dispatch"]) >= len(r["running_nodes_before"])
        assert len(r["mapping"]) == r["ref_delta_calls"] <= t.decode_tasks
        _same_problem(*r["problems"])
    assert any(r["timing"].decode_tasks > 20 for r in rounds)  # the full walk, by itself


@pytest.mark.parametrize("preemption", [False, True], ids=["pinned", "preemption"])
@PINNED
def test_the_old_node_order_moves_ties_only(model, preemption):
    """The decode as it was, `set` order and all: the same tasks, and
    the same number of them on every PU."""
    sched, rmap, jmap, tmap = _filled_cluster(
        40, MODEL_REGISTRY[getattr(CostModelType, model.upper())], preemption
    )
    _admit(sched, jmap, tmap, 7, range(1001, 1013))
    captured = {}
    complete = sched.solver.complete

    def capture(token):
        captured["token"] = token
        return complete(token)

    sched.solver.complete = capture
    sched.schedule_all_jobs()
    problem, (unpinned, pinned, num_pinned), result, _ = captured["token"]
    gm = sched.gm
    every_task = [n.id for n in gm.task_to_node.values()]
    args = (problem, result.total_flow(problem), gm.leaf_node_ids, gm.sink_node.id)
    old = _flow_to_mapping_as_it_was(*args, every_task, by_node_id=False)
    new = flow_to_mapping(*args, unpinned, pinned)
    old_unpinned = {t: pu for t, pu in old.items() if t in unpinned}
    assert new.keys() == old_unpinned.keys() and len(new) == (52 if preemption else 12)
    assert sorted(new.values()) == sorted(old_unpinned.values())
    assert num_pinned == (0 if preemption else 40)
    # without a mask the function is the full decode
    assert flow_to_mapping(*args, every_task) == _flow_to_mapping_as_it_was(
        *args, every_task, by_node_id=True
    )


# ---------------------------------------------------------------------------
# Counts: the post-solve half follows the batch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("resident", [200, 2000])
def test_decode_and_deltas_follow_the_batch_whatever_is_resident(resident):
    sched, rmap, jmap, tmap = _filled_cluster(resident, backend=make_backend("native"))
    assert sched.gm.num_pinned == resident and not sched.gm.unpinned_task_nodes
    batch = 10
    _admit(sched, jmap, tmap, 7, range(10_001, 10_001 + batch))
    with SpanTracer() as tracer:
        placed, deltas = sched.schedule_all_jobs()
    assert placed == batch == len(deltas)
    t = sched.last_timing
    assert (t.decode_tasks, t.decode_pinned_skipped) == (batch, resident)
    (dec,) = [e for e in tracer.events() if e["name"] == "decode"]
    (dlt,) = [e for e in tracer.events() if e["name"] == "deltas"]
    assert (dec["args"]["decode_tasks"], dec["args"]["decode_pinned_skipped"]) == (batch, resident)
    assert dlt["args"]["parent"] == "round"  # the span stays; the mapping's length is no arg of it
    done = min(500, resident // 2)
    for uid in range(2, 2 + done):
        sched.handle_task_completion(tmap.find(uid))
    assert sum(len(v) for v in _lists(rmap).values()) == resident + batch  # still listed
    _admit(sched, jmap, tmap, 7, range(20_001, 20_001 + batch))
    placed, _ = sched.schedule_all_jobs()
    t = sched.last_timing
    assert placed == batch
    assert (t.decode_tasks, t.decode_pinned_skipped) == (batch, resident + batch - done)
    assert sum(len(v) for v in _lists(rmap).values()) == resident + 2 * batch - done


def test_under_preemption_nothing_is_skipped_and_every_running_task_is_decoded():
    sched, rmap, jmap, tmap = _filled_cluster(60, preemption=True)
    assert sched.gm.num_pinned == 0 and len(sched.gm.unpinned_task_nodes) == 60
    _admit(sched, jmap, tmap, 7, range(1001, 1006))
    with SpanTracer() as tracer:
        placed, _ = sched.schedule_all_jobs()
    t = sched.last_timing
    assert placed == 5
    assert (t.decode_tasks, t.decode_pinned_skipped) == (65, 0)
    (dec,) = [e for e in tracer.events() if e["name"] == "decode"]
    assert (dec["args"]["decode_tasks"], dec["args"]["decode_pinned_skipped"]) == (65, 0)
    assert sum(len(v) for v in _lists(rmap).values()) == 65


def test_an_unscheduled_backlog_is_decoded_every_round_and_yields_no_delta():
    """Unpinned means the solve can change it, placed or not."""
    seed_rng(3)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=1, num_cores=1, pus_per_core=2, max_tasks_per_pu=2,
    )
    _admit(sched, jmap, tmap, 7, range(1, 8))  # 7 tasks, 4 slots
    placed, _ = sched.schedule_all_jobs()
    t = sched.last_timing
    assert (placed, t.decode_tasks, t.decode_pinned_skipped) == (4, 7, 0)
    placed, deltas = sched.schedule_all_jobs()
    t = sched.last_timing
    assert (placed, deltas) == (0, [])
    assert (t.decode_tasks, t.decode_pinned_skipped) == (3, 4)


def test_a_task_admitted_while_a_pipelined_round_is_in_flight_is_not_decoded_by_it():
    sched, rmap, jmap, tmap = _filled_cluster(50)
    _admit(sched, jmap, tmap, 7, range(1001, 1006))
    assert sched.schedule_all_jobs_async() is not None
    _admit(sched, jmap, tmap, 7, range(2001, 2004))
    placed, _ = sched.finish_scheduling()
    t = sched.last_timing
    assert (placed, t.decode_tasks, t.decode_pinned_skipped) == (5, 5, 50)
    assert sched.schedule_all_jobs_async() is not None
    placed, _ = sched.finish_scheduling()
    t = sched.last_timing
    assert (placed, t.decode_tasks, t.decode_pinned_skipped) == (3, 3, 55)


def test_a_reused_node_id_starts_unpinned():
    sched, rmap, jmap, tmap = _filled_cluster(20)
    gm = sched.gm
    freed = {gm.task_to_node[uid].id for uid in range(2, 8)}
    for uid in range(2, 8):
        sched.handle_task_completion(tmap.find(uid))
    assert gm.num_pinned == 14 and not gm.pinned_mask(64)[sorted(freed)].any()
    _admit(sched, jmap, tmap, 7, range(1001, 1007))
    sched._runnable_jobs()
    gm.add_or_update_job_nodes([jmap.find(7)])
    reused = {gm.task_to_node[uid].id for uid in range(1001, 1007)}
    assert reused & freed  # the graph hands ids out again
    assert reused == gm.unpinned_task_nodes and not gm.pinned_mask(64)[sorted(reused)].any()
    placed, _ = sched.schedule_all_jobs()
    assert placed == 6 and gm.num_pinned == 20 and not gm.unpinned_task_nodes


def test_a_killed_task_keeps_its_binding_and_leaves_its_list_in_the_next_deltas():
    sched, rmap, jmap, tmap = _filled_cluster(8)
    rid = sched.task_bindings[3]
    rd = rmap.find(rid).descriptor
    sched.kill_running_task(3)
    assert 3 in rd.current_running_tasks and sched.task_bindings[3] == rid
    _admit(sched, jmap, tmap, 7, [1001])
    sched.schedule_all_jobs()
    assert 3 not in rd.current_running_tasks and sched.gm.num_pinned == 8


def test_a_migrated_task_changes_lists_when_it_migrates():
    sched, rmap, jmap, tmap = _filled_cluster(8)
    old = sched.task_bindings[3]
    new = next(rid for rid in _lists(rmap) if rid != old)
    sched.handle_task_migration(tmap.find(3), rmap.find(new).descriptor)
    lists = _lists(rmap)
    assert 3 in lists[new] and 3 not in lists[old]
    assert sched.gm.num_pinned == 8 and not sched.gm.unpinned_task_nodes


# ---------------------------------------------------------------------------
# ROADMAP D10: an evicted task the next round cannot place again
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", MODELS)
def test_an_evicted_task_with_nowhere_to_go_is_neither_preempted_nor_a_keyerror(model):
    seed_rng(3)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=1, num_cores=1, pus_per_core=1, max_tasks_per_pu=1,
        cost_model_factory=MODEL_REGISTRY[getattr(CostModelType, model.upper())],
    )
    _admit(sched, jmap, tmap, 7, [1, 2])  # two tasks, one slot
    placed, _ = sched.schedule_all_jobs()
    assert placed == 1
    (winner,) = sched.task_bindings
    loser = 3 - winner
    (rid,) = _lists(rmap)
    rd = rmap.find(rid).descriptor
    sched.handle_task_eviction(tmap.find(winner), rd)
    assert rd.current_running_tasks == []
    # the slot goes to the other task before the evicted one can return
    sched.handle_task_placement(tmap.find(loser), rd)
    placed, deltas = sched.schedule_all_jobs()  # as it was: KeyError in task_evicted
    assert (placed, deltas) == (0, [])
    assert rd.current_running_tasks == [loser] and sched.task_bindings == {loser: rid}
    td = tmap.find(winner)
    assert td.state == TaskState.RUNNABLE and winner in sched.gm.task_to_node
    t = sched.last_timing
    assert (t.decode_tasks, t.decode_pinned_skipped) == (1, 1)
    # and it is placed when the slot frees (a completion frees it one round late)
    sched.handle_task_completion(tmap.find(loser))
    assert sched.schedule_all_jobs()[0] == 0
    placed, deltas = sched.schedule_all_jobs()
    assert placed == 1 and sched.task_bindings == {winner: rid}
    assert [d.type for d in deltas] == [DeltaType.PLACE]


# ---------------------------------------------------------------------------
# The service: RoundRecords, and a restore (warm, cold)
# ---------------------------------------------------------------------------


def _counts(rec):
    return (rec.decode_tasks, rec.decode_pinned_skipped)


def test_the_round_record_carries_the_two_counts():
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    bound, rec = _serve(svc, api, "a", 9)
    assert (bound, _counts(rec)) == (9, (9, 0))
    bound, rec = _serve(svc, api, "b", 4)
    assert (bound, _counts(rec)) == (4, (4, 9))
    svc.run_round([], solve=False)
    svc.run_round([])
    for rec in svc.tracer.records[-2:]:
        assert _counts(rec) == (0, 0)


@pytest.mark.parametrize("kind", ["warm", "cold"])
def test_a_restore_yields_the_lists_and_counts_of_the_uninterrupted_run(tmp_path, kind):
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    svc.complete_pod("a_0")
    bound, rec = _serve(svc, api, "b", 4)
    assert (bound, _counts(rec)) == (4, (4, 8))
    ck = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(ck)
    if kind == "cold":
        os.remove(ck + ".wal")
    svc2 = SchedulerService.restore(
        api, ck, backend=make_backend("native"), backend_name="native", tracer=RoundTracer(),
    )
    assert svc2.restored_warm == (kind == "warm")
    s1, s2 = svc.scheduler, svc2.scheduler
    assert _lists(s2.resource_map) == _lists(s1.resource_map)
    assert sum(len(v) for v in _lists(s2.resource_map).values()) == 12
    assert (s2.gm.num_pinned, s2.gm.unpinned_task_nodes) == (12, set()) == (
        s1.gm.num_pinned, s1.gm.unpinned_task_nodes,
    )
    assert s2._departed == {} == s1._departed
    # the next round, in both: a completion pending, five pods
    for s in (svc, svc2):
        s.complete_pod("a_1")
    api2 = SyntheticClusterAPI()
    svc2.api = api2
    recs = []
    for s, a in ((svc, api), (svc2, api2)):
        bound, rec = _serve(s, a, "c", 5)
        assert bound == 5
        recs.append(rec)
    assert _counts(recs[0]) == _counts(recs[1]) == (5, 11)
    for s in (s1, s2):
        lists = _lists(s.resource_map)
        assert sorted(t for v in lists.values() for t in v) == sorted(s.task_bindings)
        assert len(s.task_bindings) == 16 == s.gm.num_pinned
