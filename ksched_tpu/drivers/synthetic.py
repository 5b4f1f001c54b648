"""Synthetic cluster driver: fabricate machines, jobs, and tasks.

The fakeMachines analogue (reference: cmd/k8sscheduler/scheduler.go:
37-39,191-202,297-350) plus the in-memory fixture builders the
integration test uses (reference: flowscheduler/schedule_iteration_test.go:
152-331). Machines are built as machine → core* → PU* topologies,
registered into the resource map, and handed to the scheduler; jobs are a
root task plus spawned children under one JobDescriptor.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..data import (
    JobDescriptor,
    JobState,
    ResourceDescriptor,
    ResourceState,
    ResourceTopologyNodeDescriptor,
    ResourceType,
    TaskDescriptor,
    TaskState,
)
from ..scheduler import FlowScheduler
from ..utils import (
    JobMap,
    ResourceMap,
    ResourceStatus,
    TaskMap,
    rand_uint64,
    resource_id_from_string,
)


def make_resource_desc(
    rtype: ResourceType, friendly_name: str = "", uuid: Optional[int] = None
) -> ResourceDescriptor:
    if uuid is None:
        uuid = rand_uint64()
    return ResourceDescriptor(
        uuid=str(uuid),
        friendly_name=friendly_name or f"{rtype.name.lower()}_{uuid % 10_000}",
        type=rtype,
        state=ResourceState.UNKNOWN,
        schedulable=rtype == ResourceType.PU,
    )


def make_coordinator_root() -> ResourceTopologyNodeDescriptor:
    return ResourceTopologyNodeDescriptor(
        resource_desc=make_resource_desc(ResourceType.COORDINATOR, "coordinator")
    )


def _register_subtree(rtnd: ResourceTopologyNodeDescriptor, resource_map: ResourceMap) -> None:
    rid = resource_id_from_string(rtnd.resource_desc.uuid)
    resource_map.insert(rid, ResourceStatus(descriptor=rtnd.resource_desc, topology_node=rtnd))
    for child in rtnd.children:
        _register_subtree(child, resource_map)


def build_machine_topology(
    num_cores: int,
    pus_per_core: int,
    task_capacity_per_pu: int,
    parent: ResourceTopologyNodeDescriptor,
    machine_index: int = 0,
    labels: Optional[Dict[str, str]] = None,
    allocatable: Tuple[int, int] = (0, 0),
) -> ResourceTopologyNodeDescriptor:
    """machine → core* → PU* subtree attached under parent (reference:
    schedule_iteration_test.go:257-331 createMachineNode). ``labels``
    are the machine's (the node's, on the cluster API), ``allocatable``
    its (CPU millicores, memory MiB), on its `capacity` before any cost
    model's `add_machine` sees it."""
    machine_rd = make_resource_desc(ResourceType.MACHINE, f"machine_{machine_index}")
    machine_rd.labels = dict(labels or {})
    machine_rd.capacity.cpu_cores = allocatable[0] / 1000.0
    machine_rd.capacity.ram_cap = allocatable[1]
    machine = ResourceTopologyNodeDescriptor(
        resource_desc=machine_rd, parent_id=parent.resource_desc.uuid
    )
    parent.children.append(machine)
    for c in range(num_cores):
        core_rd = make_resource_desc(ResourceType.CORE, f"machine_{machine_index}_core_{c}")
        core = ResourceTopologyNodeDescriptor(
            resource_desc=core_rd, parent_id=machine_rd.uuid
        )
        machine.children.append(core)
        for p in range(pus_per_core):
            pu_rd = make_resource_desc(
                ResourceType.PU, f"machine_{machine_index}_core_{c}_pu_{p}"
            )
            pu_rd.task_capacity = task_capacity_per_pu
            pu = ResourceTopologyNodeDescriptor(resource_desc=pu_rd, parent_id=core_rd.uuid)
            core.children.append(pu)
    return machine


def add_machine(
    scheduler: FlowScheduler,
    resource_map: ResourceMap,
    root: ResourceTopologyNodeDescriptor,
    num_cores: int = 1,
    pus_per_core: int = 1,
    task_capacity_per_pu: int = 1,
    machine_index: int = 0,
    labels: Optional[Dict[str, str]] = None,
    allocatable: Tuple[int, int] = (0, 0),
) -> ResourceTopologyNodeDescriptor:
    machine = build_machine_topology(
        num_cores, pus_per_core, task_capacity_per_pu, root, machine_index, labels, allocatable
    )
    _register_subtree(machine, resource_map)
    scheduler.register_resource(machine)
    return machine


def add_task_to_job(
    job_id: int,
    job_map: JobMap,
    task_map: TaskMap,
    name: str = "",
    scheduler: Optional[FlowScheduler] = None,
) -> TaskDescriptor:
    """Create a task under the job's root task (first task becomes the
    root; reference: schedule_iteration_test.go:212-253). ``scheduler``
    is the one the job was offered to, if it was: it records the new
    child (FlowScheduler.add_task), where a root grown without a word
    costs it a walk of the whole tree."""
    jd = job_map.find(job_id)
    task_id = rand_uint64()
    td = TaskDescriptor(
        uid=task_id,
        name=name or f"task_{task_id % 100_000}",
        state=TaskState.CREATED,
        job_id=str(job_id),
    )
    if jd is None:
        jd = JobDescriptor(
            uuid=str(job_id),
            name=f"job_{job_id % 100_000}",
            state=JobState.CREATED,
            root_task=td,
        )
        job_map.insert(job_id, jd)
    elif scheduler is not None:
        scheduler.add_task(jd.root_task, td)
    else:
        jd.root_task.spawned.append(td)
    task_map.insert(task_id, td)
    return td


def add_job(
    scheduler: FlowScheduler,
    job_map: JobMap,
    task_map: TaskMap,
    num_tasks: int,
) -> int:
    """Create a job with num_tasks tasks and register it (reference:
    schedule_iteration_test.go:152-162)."""
    job_id = rand_uint64()
    for _ in range(num_tasks):
        add_task_to_job(job_id, job_map, task_map)
    jd = job_map.find(job_id)
    if jd is not None:
        scheduler.add_job(jd)
    return job_id


def build_cluster(
    num_machines: int,
    num_cores: int = 1,
    pus_per_core: int = 1,
    max_tasks_per_pu: int = 1,
    backend=None,
    cost_model_factory=None,
    preemption: bool = False,
):
    """Assemble maps + root + scheduler + machines in one call. Returns
    (scheduler, resource_map, job_map, task_map, root)."""
    resource_map = ResourceMap()
    job_map = JobMap()
    task_map = TaskMap()
    root = make_coordinator_root()
    resource_map.insert(
        resource_id_from_string(root.resource_desc.uuid),
        ResourceStatus(descriptor=root.resource_desc, topology_node=root),
    )
    scheduler = FlowScheduler(
        resource_map,
        job_map,
        task_map,
        root,
        max_tasks_per_pu=max_tasks_per_pu,
        cost_model_factory=cost_model_factory,
        backend=backend,
        preemption=preemption,
    )
    for i in range(num_machines):
        add_machine(
            scheduler, resource_map, root, num_cores, pus_per_core, max_tasks_per_pu, machine_index=i
        )
    return scheduler, resource_map, job_map, task_map, root
