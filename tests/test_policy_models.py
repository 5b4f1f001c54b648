"""Policy-model tests: Void, Random, Octopus, SJF, Quincy, Net.

Each model runs end-to-end through the real scheduler (graph build →
MCMF solve → delta apply) on a small synthetic cluster, and each test
asserts the policy's signature behavior — not just that it runs.
"""

import numpy as np
import pytest

from ksched_tpu.costmodels import (
    MODEL_REGISTRY,
    CostModelType,
    NetCostModel,
    OctopusCostModel,
    QuincyCostModel,
    RandomCostModel,
    SjfCostModel,
    VoidCostModel,
)
from ksched_tpu.data import ReferenceDescriptor, ReferenceType
from ksched_tpu.drivers import add_job, add_machine, build_cluster
from ksched_tpu.utils import resource_id_from_string, seed_rng


def _cluster(model_cls, machines=3, cores=1, pus=2, slots=1):
    return build_cluster(
        num_machines=machines,
        num_cores=cores,
        pus_per_core=pus,
        max_tasks_per_pu=slots,
        cost_model_factory=model_cls,
        # a model that prices preemption is served with it (k8s_priority)
        preemption=model_cls.needs_preemption,
    )


def test_registry_covers_every_enumerated_model():
    assert set(MODEL_REGISTRY) == set(CostModelType)


@pytest.mark.parametrize("model_type", list(CostModelType))
def test_every_model_schedules_end_to_end(model_type):
    sched, rmap, jmap, tmap, root = _cluster(MODEL_REGISTRY[model_type])
    add_job(sched, jmap, tmap, num_tasks=4)
    n, deltas = sched.schedule_all_jobs()
    # Void legitimately may place nothing (all-zero costs), and required
    # anti-affinity places one task of a workload a machine (the four
    # are of workload 0, the machines three); everyone else must fill
    # the demand.
    if model_type == CostModelType.K8S_ANTIAFFINITY:
        assert n == 3, f"{model_type.name} placed {n}/4 on 3 machines"
    elif model_type != CostModelType.VOID:
        assert n == 4, f"{model_type.name} placed {n}/4"
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node)


def test_random_is_reproducible_under_seed():
    def run():
        seed_rng(123)
        sched, rmap, jmap, tmap, root = _cluster(RandomCostModel)
        add_job(sched, jmap, tmap, num_tasks=4)
        sched.schedule_all_jobs()
        return sorted(sched.get_task_bindings().values())

    assert run() == run()


def test_octopus_balances_load():
    # 4 machines x 2 PUs; tasks arrive one per round. Octopus prices a
    # machine by its observed load (stats refresh between rounds — the
    # model is load-reactive, like Firmament's octopus), so each arrival
    # must land on a still-idle machine: 1 task per machine, not packed.
    sched, rmap, jmap, tmap, root = _cluster(OctopusCostModel, machines=4, pus=2)
    n = 0
    for _ in range(4):
        add_job(sched, jmap, tmap, num_tasks=1)
        placed, _ = sched.schedule_all_jobs()
        n += placed
    assert n == 4
    # map bound PUs -> machine: count tasks per machine
    per_machine = {}
    for t, pu_rid in sched.get_task_bindings().items():
        rs = rmap.find(pu_rid)
        # walk up to the machine via parent ids
        node = rs.topology_node
        while node.resource_desc.type.name != "MACHINE":
            parent_rid = resource_id_from_string(node.parent_id)
            node = rmap.find(parent_rid).topology_node
        per_machine[node.resource_desc.uuid] = per_machine.get(node.resource_desc.uuid, 0) + 1
    assert max(per_machine.values()) == 1, f"octopus packed: {per_machine}"


def test_sjf_prioritizes_short_jobs_under_contention():
    # 1 machine x 2 slots; short job (2 tasks) + long job (2 tasks).
    sched, rmap, jmap, tmap, root = _cluster(SjfCostModel, machines=1, pus=2)
    short_job = add_job(sched, jmap, tmap, num_tasks=2)
    long_job = add_job(sched, jmap, tmap, num_tasks=2)
    model: SjfCostModel = sched.cost_model
    model.record_completion(str(short_job), 10.0)
    model.record_completion(str(long_job), 9000.0)
    n, _ = sched.schedule_all_jobs()
    assert n == 2  # only two slots
    placed = set(sched.get_task_bindings().keys())
    short_tasks = {t for t, td in tmap.items() if td.job_id == str(short_job)}
    assert placed == short_tasks, "SJF must give contended slots to the short job"


def test_quincy_prefers_data_local_machine():
    sched, rmap, jmap, tmap, root = _cluster(QuincyCostModel, machines=3, pus=2)
    model: QuincyCostModel = sched.cost_model
    machines = list(model._machines.keys())
    target = machines[1]
    job = add_job(sched, jmap, tmap, num_tasks=1)
    (task_id,) = [t for t, td in tmap.items() if td.job_id == str(job)]
    td = tmap.find(task_id)
    # task reads one 512 MB block that lives on machine[1]
    td.dependencies.append(
        ReferenceDescriptor(id=77, type=ReferenceType.CONCRETE, size=512 << 20)
    )
    model.blocks.register(77, 512 << 20, [target])
    assert model.get_task_preference_arcs(task_id) == [target]
    n, _ = sched.schedule_all_jobs()
    assert n == 1
    (pu_rid,) = sched.get_task_bindings().values()
    node = rmap.find(pu_rid).topology_node
    while node.resource_desc.type.name != "MACHINE":
        node = rmap.find(resource_id_from_string(node.parent_id)).topology_node
    assert resource_id_from_string(node.resource_desc.uuid) == target


def test_quincy_wait_cost_grows():
    sched, rmap, jmap, tmap, root = _cluster(QuincyCostModel, machines=1, pus=1)
    model: QuincyCostModel = sched.cost_model
    model.add_task(42)
    c0 = model.task_to_unscheduled_agg_cost(42)
    model.note_round([42])
    model.note_round([42])
    assert model.task_to_unscheduled_agg_cost(42) > c0


def test_net_gates_machines_without_bandwidth():
    sched, rmap, jmap, tmap, root = _cluster(NetCostModel, machines=2, pus=2)
    model: NetCostModel = sched.cost_model
    machines = list(model._machines.keys())
    # The GATED machine comes first in arc order so a tie-break cannot
    # mask a broken gate; the roomy machine is second.
    rmap.find(machines[0]).descriptor.capacity.net_bw = 1
    rmap.find(machines[1]).descriptor.capacity.net_bw = 100
    machines = [machines[1]]  # expected landing spot
    job = add_job(sched, jmap, tmap, num_tasks=2)
    for t, td in tmap.items():
        if td.job_id == str(job):
            td.resource_request.net_bw = 40
    n, _ = sched.schedule_all_jobs()
    assert n == 2
    # both tasks must land on the machine that can fit 40 bw each
    for t, pu_rid in sched.get_task_bindings().items():
        node = rmap.find(pu_rid).topology_node
        while node.resource_desc.type.name != "MACHINE":
            node = rmap.find(resource_id_from_string(node.parent_id)).topology_node
        assert resource_id_from_string(node.resource_desc.uuid) == machines[0]


def test_net_leaves_unfittable_task_unscheduled():
    # Request 50 exceeds every machine's bandwidth: the unsched escape
    # (cheaper than the gate) must win — no overcommitted placement.
    sched, rmap, jmap, tmap, root = _cluster(NetCostModel, machines=2, pus=2)
    model: NetCostModel = sched.cost_model
    for m in model._machines:
        rmap.find(m).descriptor.capacity.net_bw = 10
    job = add_job(sched, jmap, tmap, num_tasks=1)
    for t, td in tmap.items():
        if td.job_id == str(job):
            td.resource_request.net_bw = 50
    n, _ = sched.schedule_all_jobs()
    assert n == 0
    assert sched.get_task_bindings() == {}


def test_void_keeps_supply_conserved():
    sched, rmap, jmap, tmap, root = _cluster(VoidCostModel)
    add_job(sched, jmap, tmap, num_tasks=3)
    sched.schedule_all_jobs()
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node)
