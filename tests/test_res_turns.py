"""A model that prices its resource arcs at constants gets no
resource-node turn in the graph manager's per-round update.

The claim (`CostModeler.resource_arc_costs_are_fixed`) is checked
against behaviour for every registered model, and the guard that takes
it from a subclass which overrides one of the two methods is the one
that serves `pinned_tasks_are_inert`. Parity: a twin world whose graph
manager has the decision switched back (`_res_turns`, read by the one
method the three queueing sites call) takes every turn the FIFO took
before, and must see the same change journal, problem, deltas and
objective, bit for bit, after every round of a seeded stream that
binds, completes, evicts, purges an EC and lists it anew, and removes
and adds a machine.
"""

import json
import os
import pickle
import random

import pytest

from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, TrivialCostModel
from ksched_tpu.costmodels.base import _CLAIM_METHODS, CostModeler
from ksched_tpu.data import TaskType
from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.runtime import checkpoint
from ksched_tpu.runtime.integrity import read_records, write_records
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.cpu_ref import ReferenceSolver
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import resource_id_from_string, seed_rng
from test_graph_worklist import (
    MODELS, _admit, _filled_cluster, _same_problem, _serve, _service, _World,
)

# ---------------------------------------------------------------------------
# The claim, and the guard it shares
# ---------------------------------------------------------------------------


def _asked_prices(gm, cm):
    """What the model asks for each arc out of a resource node, and
    what the arc carries."""
    asked, carried = {}, {}
    for node in gm.resource_to_node.values():
        for arc in node.outgoing.values():
            if arc.dst_node is gm.sink_node:
                cost = cm.leaf_resource_node_to_sink_cost(node.resource_id)
            else:
                cost = cm.resource_node_to_resource_node_cost(
                    node.resource_descriptor, arc.dst_node.resource_descriptor
                )
            asked[(arc.src, arc.dst)] = cost
            carried[(arc.src, arc.dst)] = arc.cost
    return asked, carried


@pytest.mark.parametrize("kind", sorted(MODEL_REGISTRY), ids=lambda k: CostModelType(k).name.lower())
def test_a_registered_model_asks_the_same_two_prices_before_and_after_rounds(kind):
    """Checked against behaviour: over rounds that bind, complete, evict
    and change the census, both methods return what they returned, and
    every arc out of a resource node carries it."""
    model = MODEL_REGISTRY[kind]
    assert model.resource_arc_costs_are_fixed, kind
    sched, rmap, jmap, tmap = _filled_cluster(
        8, model=model, backend=ReferenceSolver(), preemption=model.needs_preemption
    )
    gm, cm = sched.gm, sched.cost_model
    assert not gm._res_turns
    before, carried = _asked_prices(gm, cm)
    assert before == carried and len(before) >= len(gm.resource_to_node) - 1
    bound = 0
    for r in range(1, 4):
        for i, ttype in enumerate(TaskType):
            _admit(sched, jmap, tmap, 7, [1000 * r + i], task_type=ttype, workload=i)
        sched.schedule_all_jobs()
        assert gm.res_nodes_visited == 0
        assert _asked_prices(gm, cm) == (before, before)
        # (the void model binds nothing, anti-affinity not all)
        batch = [uid for uid in range(1000 * r, 1000 * r + len(TaskType)) if uid in sched.task_bindings]
        bound += len(batch)
        for uid, leave in zip(batch, ("complete", "evict")):
            if leave == "complete":
                sched.handle_task_completion(tmap.find(uid))
            else:
                rid = sched.task_bindings[uid]
                sched.handle_task_eviction(tmap.find(uid), rmap.find(rid).descriptor)
        assert _asked_prices(gm, cm) == (before, before)
    assert bound >= 6 or kind == CostModelType.VOID


class _OwnSinkPrice(TrivialCostModel):
    def leaf_resource_node_to_sink_cost(self, resource_id):
        return 1


class _OwnTreePrice(TrivialCostModel):
    def resource_node_to_resource_node_cost(self, source, destination):
        return 1


class _OwnSinkPriceRestated(TrivialCostModel):
    resource_arc_costs_are_fixed = True

    def leaf_resource_node_to_sink_cost(self, resource_id):
        return 1


class _BelowTheRestated(_OwnSinkPriceRestated):
    pass


class _OwnContinuation(TrivialCostModel):
    def task_continuation_cost(self, task_id):
        return 1


#: class -> (pinned_tasks_are_inert, resource_arc_costs_are_fixed)
CLAIMS = {
    TrivialCostModel: (True, True),
    _OwnSinkPrice: (True, False),  # overriding either method drops the claim about them
    _OwnTreePrice: (True, False),
    _OwnSinkPriceRestated: (True, True),  # overriding and saying it again keeps it
    _BelowTheRestated: (True, True),  # and a subclass that overrides nothing inherits
    _OwnContinuation: (False, True),  # each claim answers for its own methods alone
}


@pytest.mark.parametrize("cls", CLAIMS, ids=lambda c: c.__name__.strip("_"))
def test_a_subclass_that_overrides_a_claims_method_loses_that_claim_alone(cls):
    assert (cls.pinned_tasks_are_inert, cls.resource_arc_costs_are_fixed) == CLAIMS[cls]


def test_one_table_of_claim_and_methods_serves_every_claim_about_methods():
    assert set(_CLAIM_METHODS) == {
        "pinned_tasks_are_inert", "resource_arc_costs_are_fixed", "full_resources_stay_listed"}
    for claim, methods in _CLAIM_METHODS.items():
        assert getattr(CostModeler, claim) is False  # nothing is claimed by default
        assert all(callable(getattr(CostModeler, m)) for m in methods)
    # no method answers to two claims
    every = [m for methods in _CLAIM_METHODS.values() for m in methods]
    assert len(set(every)) == len(every)


# ---------------------------------------------------------------------------
# Bit-identity against the every-node walk
# ---------------------------------------------------------------------------

PARITY_MODELS = {
    "trivial": MODELS["trivial"],
    "coco": MODELS["coco"],
    "k8s_antiaffinity": MODEL_REGISTRY[CostModelType.K8S_ANTIAFFINITY],
}
#: workload 2 has arrivals in the first steps and again from RELIST on:
#: under `k8s_antiaffinity` its EC is purged between and listed anew
QUIET, RELIST = 3, 9
REMOVE, ADD = 5, 10


def _machine_arcs(gm, machine):
    """(src, dst) -> (capacity, cost) of every arc into or out of the
    resource nodes of `machine`'s subtree."""
    out = {}
    stack = [machine]
    while stack:
        rtnd = stack.pop()
        node = gm.resource_to_node[resource_id_from_string(rtnd.resource_desc.uuid)]
        for arc in list(node.incoming.values()) + list(node.outgoing.values()):
            if arc.src_node.is_resource_node:
                out[(arc.src, arc.dst)] = (arc.cap_lower, arc.cap_upper, arc.cost)
        stack.extend(rtnd.children)
    return out


@pytest.mark.parametrize("preemption", [False, True], ids=["pinned", "preemption"])
@pytest.mark.parametrize("model", sorted(PARITY_MODELS))
def test_no_resource_turn_gives_the_every_node_walks_journal_problem_deltas_and_objective(
    model, preemption
):
    new = _World(PARITY_MODELS[model], preemption, root_down=False)
    ref = _World(PARITY_MODELS[model], preemption, root_down=False)
    assert not new.sched.gm._res_turns
    ref.sched.gm._res_turns = True  # the one switch: the FIFO as it was, turn for turn
    worlds = (new, ref)
    rnd = random.Random(7)
    jobs = [101, 202, 303]
    uid = 1000
    members = {j: [] for j in jobs}
    ref_turns, purged = [], []
    added = None
    for step in range(14):
        for _ in range(rnd.randrange(3, 9)):
            job = rnd.choice(jobs)
            uid += 1
            parent = None
            if len(members[job]) > 2 and rnd.random() < 0.3:
                parent = rnd.choice(members[job][1:])
            ttype = TaskType(rnd.randrange(4))
            workload = rnd.randrange(3 if step < QUIET or step >= RELIST else 2)
            for w in worlds:
                w.admit(job, uid, parent, ttype, workload)
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
        if step == REMOVE:
            for w in worlds:
                w.remove_machine(2)
        if step == ADD:
            added = [w.add_machine(6) for w in worlds]
            # the arcs of a machine that joins are priced where they are made
            arcs = [_machine_arcs(w.sched.gm, m) for w, m in zip(worlds, added)]
            assert arcs[0] == arcs[1] and len(arcs[0]) == 1 + 2 + 4 + 4
            asked, carried = _asked_prices(new.sched.gm, new.sched.cost_model)
            assert asked == carried
        results = [w.round() for w in worlds]
        assert results[0][0] == results[1][0]
        assert [(d.type, d.task_id, d.resource_id) for d in results[0][1]] == [
            (d.type, d.task_id, d.resource_id) for d in results[1][1]
        ]
        _same_problem(new.backend.problems[-1], ref.backend.problems[-1])
        assert (
            new.sched.solver.last_result.objective == ref.sched.solver.last_result.objective
        )
        assert new.journals == ref.journals and len(new.journals) == step
        assert new.sched.task_bindings == ref.sched.task_bindings
        gm, ref_gm = new.sched.gm, ref.sched.gm
        assert (gm.res_nodes_visited, gm.res_arcs_changed, ref_gm.res_arcs_changed) == (0, 0, 0)
        assert gm.ec_arcs_changed == ref_gm.ec_arcs_changed
        assert (gm.tasks_visited, gm.tasks_skipped) == (ref_gm.tasks_visited, ref_gm.tasks_skipped)
        assert gm.ec_purged == ref_gm.ec_purged
        ref_turns.append(ref_gm.res_nodes_visited)
        purged.append(gm.ec_purged)
        every_node_but_the_root = len(gm.resource_to_node) - 1
        if model == "trivial" or (model == "coco" and preemption):
            # a cluster-wide EC's sweep queues every machine, each node its children
            # (`coco`'s class ECs sweep after a pass that walked every node: under
            # preemption, every pass)
            assert ref_turns[-1] == every_node_but_the_root
    assert sum(len(j) for j in new.journals) > 100
    if model == "coco" and not preemption:
        # a class EC with arcs is patched from the census keeper's record and queues
        # nothing; it sweeps, and the sweep queues every machine, where it lists: in the
        # first round, and after a machine came or went
        assert ref_turns[0] > 0 and ref_turns[REMOVE] > 0 and ref_turns[ADD] > 0 and 0 in ref_turns
    if model == "k8s_antiaffinity":
        # an EC with arcs is patched from the model's record and queues
        # nothing; one listed for the first time, or anew after its purge,
        # is swept, and the sweep queued the machines it lists
        assert ref_turns[0] == 6 * (1 + 2 + 4) and 0 in ref_turns
        if not preemption:  # a running pod keeps its EC connected under preemption
            assert sum(purged[QUIET:RELIST]) >= 1 and ref_turns[RELIST] > 0
    # the machine that joined took pods, with no turn of its own in `new`
    machine = added[0]
    pus = {
        resource_id_from_string(pu.resource_desc.uuid)
        for core in machine.children for pu in core.children
    }
    # enough pods to fill every slot of the six machines; under
    # anti-affinity one workload's pods go one to a machine, so six
    flood = 6 if model == "k8s_antiaffinity" else 6 * 4 * 6 - len(new.sched.task_bindings)
    for w in worlds:
        _admit(w.sched, w.jmap, w.tmap, 404, range(5001, 5001 + flood), workload=9)
    placed = [w.round()[0] for w in worlds]
    assert placed[0] == placed[1] >= 6
    assert new.sched.task_bindings == ref.sched.task_bindings
    assert new.journals[-1] == ref.journals[-1]
    assert pus & set(new.sched.task_bindings.values())
    assert new.sched.gm.res_nodes_visited == 0
    # `coco`'s class ECs patch the machines the last round touched, and a patch queues none
    patches = model == "coco" and not preemption
    assert (ref.sched.gm.res_nodes_visited == 0) == patches


def test_a_model_that_re_prices_a_resource_arc_keeps_every_turn_and_its_prices_land():
    """The other side of the switch: nothing is claimed, so the update
    queues what it meets and the re-pricing reaches the journal."""

    class Rising(TrivialCostModel):
        rounds = 0

        def resource_node_to_resource_node_cost(self, source, destination):
            return self.rounds % 2

        def note_round(self, unscheduled_task_ids):
            self.rounds += 1

    assert not Rising.resource_arc_costs_are_fixed
    sched, _rmap, jmap, tmap = _filled_cluster(40, model=Rising, backend=ReferenceSolver())
    gm = sched.gm
    assert gm._res_turns
    for r in range(1, 4):
        _admit(sched, jmap, tmap, 7, range(1000 * r, 1000 * r + 3))
        placed, _ = sched.schedule_all_jobs()
        assert placed == 3
        between = [
            arc for node in gm.resource_to_node.values() for arc in node.outgoing.values()
            if arc.dst_node is not gm.sink_node
        ]
        # the coordinator takes no turn: its arcs keep the price they were made at
        below_a_machine = [a for a in between if a.src_node.id in gm.node_to_parent_node]
        assert {a.cost for a in below_a_machine} == {(r - 1) % 2}
        assert gm.res_nodes_visited == len(gm.resource_to_node) - 1
        assert gm.res_arcs_changed == (len(below_a_machine) if r > 1 else 0)


# ---------------------------------------------------------------------------
# A restored service
# ---------------------------------------------------------------------------


def _manifest_without_the_switch(wal_path):
    """The manifest as the build before this one wrote it: version 6,
    its graph manager without `_res_turns`."""
    records = dict(read_records(wal_path))
    meta = json.loads(records["meta"])
    assert meta["version"] == checkpoint.WARM_MANIFEST_VERSION >= 7
    meta["version"] = 6
    payload = pickle.loads(records["core"])
    del payload["scheduler"]["gm"]._res_turns
    write_records(
        wal_path,
        [("meta", json.dumps(meta).encode()), ("core", pickle.dumps(payload)), ("warm", records["warm"])],
    )


@pytest.mark.parametrize("kind", ["warm", "cold", "old_manifest"])
def test_a_restored_service_takes_no_resource_turn_either(tmp_path, kind):
    """A warm restore brings the decision back with the pickled graph
    manager; a manifest from before it is refused and the cold replay
    builds a graph manager that reads the model again."""
    seed_rng(0)
    api = SyntheticClusterAPI()
    svc = _service(api, RoundTracer())
    _serve(svc, api, "a", 9)
    _bound, rec = _serve(svc, api, "b", 4)
    assert (rec.res_nodes_visited, rec.res_arcs_changed) == (0, 0)
    ck = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(ck)
    if kind == "cold":
        os.remove(ck + ".wal")
    elif kind == "old_manifest":
        _manifest_without_the_switch(ck + ".wal")

    def restore():
        return SchedulerService.restore(
            api, ck, backend=make_backend("native"), backend_name="native", tracer=RoundTracer(),
        )

    if kind == "old_manifest":
        with pytest.warns(RuntimeWarning, match="unsupported warm manifest version 6"):
            svc2 = restore()
    else:
        svc2 = restore()
    assert svc2.restored_warm == (kind == "warm")
    gm = svc2.scheduler.gm
    assert not gm._res_turns
    prices = _asked_prices(gm, svc2.scheduler.cost_model)
    assert prices[0] == prices[1]  # the restored arcs carry the model's prices
    for tag, pods in (("c", 5), ("d", 2)):
        bound, rec = _serve(svc2, api, tag, pods)
        assert (bound, rec.res_nodes_visited, rec.res_arcs_changed) == (pods, 0, 0)
