"""CoCo / Whare-Map cost models: vectorized matrices, object-layer
parity, and end-to-end class-aware bulk scheduling."""

import numpy as np


from ksched_tpu.costmodels import (
    CLASS_ECS,
    CocoCostModel,
    WhareMapCostModel,
    class_ec,
    coco_cost_matrix,
    ec_class,
    whare_cost_matrix,
)
from ksched_tpu.costmodels.coco import INTERFERENCE, MAX_COST
from ksched_tpu.costmodels.whare import PSI_PRIOR
from ksched_tpu.data import TaskType
from ksched_tpu.scheduler.bulk import BulkCluster
from ksched_tpu.solver.cpu_ref import ReferenceSolver


def test_class_ec_roundtrip():
    for t in TaskType:
        ec = class_ec(t)
        assert ec_class(ec) == int(t)
    assert ec_class(12345) is None
    assert len(set(CLASS_ECS)) == 4


def test_coco_cost_matrix_shape_and_policy():
    census = np.zeros((3, 4), np.int64)
    census[0] = [0, 0, 0, 0]  # empty machine
    census[1] = [0, 0, 5, 0]  # devil-heavy machine
    census[2] = [5, 0, 0, 0]  # sheep-only machine
    cost = coco_cost_matrix(census)
    assert cost.shape == (4, 3)
    # empty machine is free
    assert (cost[:, 0] == 0).all()
    # a rabbit avoids the devil machine more than the sheep machine
    rabbit = int(TaskType.RABBIT)
    assert cost[rabbit, 1] > cost[rabbit, 2]
    # a turtle barely cares
    turtle = int(TaskType.TURTLE)
    assert cost[turtle, 1] <= cost[rabbit, 1]
    # clamped
    big = np.full((1, 4), 10_000, np.int64)
    assert coco_cost_matrix(big).max() <= MAX_COST


def test_whare_cost_matrix_idle_bonus():
    census = np.zeros((2, 4), np.int64)
    census[0] = [2, 0, 0, 0]
    census[1] = [2, 0, 0, 0]
    idle = np.array([8, 0])
    slots = np.array([16, 16])
    cost = whare_cost_matrix(census, idle, slots)
    assert cost.shape == (4, 2)
    # same census, more idle slots -> cheaper
    assert (cost[:, 0] <= cost[:, 1]).all()


def test_whare_online_map_update():
    from ksched_tpu.utils import ResourceMap, TaskMap

    m = WhareMapCostModel(ResourceMap(), TaskMap(), set(), 4)
    prior = m.psi_int().copy()
    for _ in range(10):
        m.record_runtime(1, 0, 2, 400.0)
    after = m.psi_int()
    # learned that rabbits suffer next to devils on platform A: that cell of
    # the [class, platform, co-runner] map, and no other
    assert after[1, 0, 2] > prior[1, 0, 2]
    changed = after != prior
    assert changed.sum() == 1 and changed[1, 0, 2]


def _bulk(class_cost_fn, C=4, M=4, P=2, S=2, J=2, cap=256):
    return BulkCluster(
        num_machines=M,
        pus_per_machine=P,
        slots_per_pu=S,
        num_jobs=J,
        backend=ReferenceSolver(),
        num_task_classes=C,
        class_cost_fn=class_cost_fn,
        task_capacity=cap,
        unsched_cost=3_000,
    )


def test_bulk_classes_coco_end_to_end():
    def fn(cluster):
        return coco_cost_matrix(cluster.machine_census)

    cluster = _bulk(fn)
    rng = np.random.default_rng(0)
    classes = rng.integers(0, 4, 12).astype(np.int32)
    jobs = rng.integers(0, 2, 12).astype(np.int32)
    cluster.add_tasks(12, jobs, classes)
    r = cluster.round()
    assert len(r.placed_tasks) == 12
    assert r.num_unscheduled == 0
    # census bookkeeping matches placements
    assert cluster.machine_census.sum() == 12
    rows = r.placed_tasks - cluster.task0
    for m in range(cluster.M):
        on_m = (r.placed_pus - cluster.pu0) // cluster.P == m
        for c in range(4):
            expect = int((cluster.task_class[rows[on_m]] == c).sum())
            assert cluster.machine_census[m, c] == expect
    # completion decrements census
    cluster.complete_tasks(r.placed_tasks[:5])
    assert cluster.machine_census.sum() == 7


def test_bulk_coco_devils_spread_from_rabbits():
    """With strong interference costs and ample capacity, the solver
    should not co-locate rabbits onto devil-saturated machines."""

    def fn(cluster):
        return coco_cost_matrix(cluster.machine_census)

    cluster = _bulk(fn, M=2, P=2, S=4, J=1)
    # Fill machine 0 with devils (place 4 devils first).
    devils = cluster.add_tasks(4, np.zeros(4, np.int32), np.full(4, int(TaskType.DEVIL), np.int32))
    r1 = cluster.round()
    assert len(r1.placed_tasks) == 4
    devil_machines = set((r1.placed_pus - cluster.pu0) // cluster.P)
    # Now add rabbits; they should land on the other machine(s) first.
    cluster.add_tasks(4, np.zeros(4, np.int32), np.full(4, int(TaskType.RABBIT), np.int32))
    r2 = cluster.round()
    rabbit_machines = (r2.placed_pus - cluster.pu0) // cluster.P
    census = cluster.machine_census
    # The devil machine should not have received the bulk of the rabbits
    # while an emptier machine existed.
    if len(devil_machines) == 1:
        dm = devil_machines.pop()
        other = 1 - dm
        assert census[other, int(TaskType.RABBIT)] >= census[dm, int(TaskType.RABBIT)]


def test_bulk_whare_prefers_idle_machines():
    def fn(cluster):
        pu_free = cluster.S - cluster.pu_running
        machine_free = pu_free.reshape(cluster.M, cluster.P).sum(axis=1)
        slots = np.full(cluster.M, cluster.P * cluster.S)
        return whare_cost_matrix(cluster.machine_census, machine_free, slots)

    cluster = _bulk(fn, M=3, P=1, S=4, J=1)
    cluster.add_tasks(6, np.zeros(6, np.int32), np.zeros(6, np.int32))
    r = cluster.round()
    assert len(r.placed_tasks) == 6
    # load should spread (no machine takes everything)
    per_machine = np.bincount((r.placed_pus - cluster.pu0) // cluster.P, minlength=3)
    assert per_machine.max() < 6


def test_object_layer_coco_model_costs():
    """CocoCostModel against hand-built resource state."""
    from ksched_tpu.data import (
        ResourceDescriptor,
        ResourceTopologyNodeDescriptor,
        ResourceType,
        TaskDescriptor,
    )
    from ksched_tpu.utils import ResourceMap, ResourceStatus, TaskMap, resource_id_from_string

    rmap, tmap = ResourceMap(), TaskMap()
    model = CocoCostModel(rmap, tmap, set(), 4)

    rd = ResourceDescriptor(uuid="41", type=ResourceType.MACHINE)
    rd.num_slots_below = 8
    rd.num_running_tasks_below = 2
    rd.whare_map_stats.num_devils = 2
    rtnd = ResourceTopologyNodeDescriptor(resource_desc=rd)
    rid = resource_id_from_string("41")
    rmap.insert(rid, ResourceStatus(rd, rtnd, "", 0))
    model.add_machine(rtnd)

    rabbit_ec = CLASS_ECS[int(TaskType.RABBIT)]
    cost, cap = model.equiv_class_to_resource_node(rabbit_ec, rid)
    assert cap == 6
    assert cost == int(INTERFERENCE[int(TaskType.RABBIT), int(TaskType.DEVIL)]) * 2

    td = TaskDescriptor(uid=7, task_type=TaskType.RABBIT)
    tmap.insert(7, td)
    assert model.get_task_equiv_classes(7) == [rabbit_ec]
    # unscheduled escape must dominate any machine cost
    assert model.task_to_unscheduled_agg_cost(7) > MAX_COST
