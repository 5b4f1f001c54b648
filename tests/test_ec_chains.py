"""Hierarchical equivalence-class chains through the graph manager
(_update_equiv_to_equiv_arcs, reference graph_manager.go:939-970): a
cost model routing task -> job-EC -> rack-EC -> machines must schedule
through the two-level aggregation, and stale EC->EC preferences must be
pruned (removeInvalidECPrefArcs, :732-760)."""

from typing import List, Tuple

from ksched_tpu.costmodels import TrivialCostModel
from ksched_tpu.costmodels.base import Cost
from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.graph.flowgraph import NodeType

JOB_EC = 777_001
RACK_EC = 777_002


class TwoLevelECModel(TrivialCostModel):
    """task -> JOB_EC -> RACK_EC -> every machine (the quincy-style
    rack-aggregator shape). Inherits the trivial model's stats
    machinery; only the preference topology differs."""

    def get_task_equiv_classes(self, task_id: int) -> List[int]:
        return [JOB_EC]

    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
        return [RACK_EC] if ec == JOB_EC else []

    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]:
        # ample capacity through the chain; cost 1 per hop
        return 1, 64

    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
        # only the RACK EC talks to machines
        return list(self._machines) if ec == RACK_EC else []

    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost:
        return 2


def test_two_level_ec_chain_schedules_tasks():
    # preemption on: running tasks keep their EC arcs, so the chain
    # stays connected across the round (with preemption off, pinning
    # drops the arcs and the per-round purge removes idle ECs — see
    # test_pinned_round_purges_idle_ecs)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, pus_per_core=2, preemption=True,
        cost_model_factory=TwoLevelECModel,
    )
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    assert n == 3
    # both EC nodes exist and the chain arc is present
    assert JOB_EC in sched.gm.task_ec_to_node
    assert RACK_EC in sched.gm.task_ec_to_node
    job_node = sched.gm.task_ec_to_node[JOB_EC]
    rack_node = sched.gm.task_ec_to_node[RACK_EC]
    chain = sched.gm.cm.graph.get_arc(job_node, rack_node)
    assert chain is not None and chain.cost == 1 and chain.cap_upper == 64
    # machines hang off the RACK EC only
    rack_out = {a.dst_node.type for a in rack_node.outgoing.values()}
    assert NodeType.MACHINE in rack_out
    assert all(
        a.dst_node.type != NodeType.MACHINE for a in job_node.outgoing.values()
    )
    # supply invariant after routing through the chain
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node)


def test_stale_ec_chain_is_pruned():
    """Dropping the EC->EC preference must delete the chain arc on the
    next round (removeInvalidECPrefArcs parity)."""

    class RetractableModel(TwoLevelECModel):
        chain_on = True

        def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]:
            return [RACK_EC] if (ec == JOB_EC and self.chain_on) else []

        def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]:
            if ec == RACK_EC:
                return list(self._machines)
            if ec == JOB_EC and not self.chain_on:
                return list(self._machines)  # fall back to direct fan-out
            return []

    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, pus_per_core=2, preemption=True,
        cost_model_factory=RetractableModel,
    )
    add_job(sched, jmap, tmap, num_tasks=1)
    sched.schedule_all_jobs()
    job_node = sched.gm.task_ec_to_node[JOB_EC]
    rack_node = sched.gm.task_ec_to_node[RACK_EC]
    assert sched.gm.cm.graph.get_arc(job_node, rack_node) is not None

    sched.cost_model.chain_on = False
    add_job(sched, jmap, tmap, num_tasks=1)  # forces a graph update pass
    sched.schedule_all_jobs()
    assert sched.gm.cm.graph.get_arc(job_node, rack_node) is None


def test_pinned_round_purges_idle_ecs_with_debounce():
    """With preemption OFF, placed tasks are pinned (their EC arcs
    deleted), leaving the chain ECs unconnected. The purge is
    debounced: the round's own purge does not count (tasks pointed at
    the ECs in it), one idle purge marks them, a second removes them:
    transiently idle aggregators don't churn, and persistently idle
    ones don't accumulate. The cascade (RACK_EC orphaned by JOB_EC's
    removal) resolves in the same call."""
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, pus_per_core=2,
        cost_model_factory=TwoLevelECModel,
    )
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    assert n == 3
    # everyone pinned, but the ECs were in use this round: not marked
    sched.gm.purge_unconnected_equiv_class_nodes()  # idle: marked
    assert JOB_EC in sched.gm.task_ec_to_node
    sched.gm.purge_unconnected_equiv_class_nodes()  # second observation
    assert not sched.gm.task_ec_to_node  # JOB_EC purged, RACK_EC cascaded
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node)
