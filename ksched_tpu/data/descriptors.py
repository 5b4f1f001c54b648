"""L0 data model: task/job/resource descriptors.

TPU-native rebuild of the reference protobuf schema (reference:
proto/task_desc.proto, proto/resource_desc.proto, proto/job_desc.proto,
proto/resource_topology_node_desc.proto, proto/resource_vector.proto,
proto/whare_map_stats.proto, proto/coco_interference_scores.proto,
proto/task_final_report.proto, proto/reference_desc.proto).

We keep field-level parity for every field the scheduling logic reads
(states, spawned children, num_slots_below, current_running_tasks,
CoCo/Whare stats) and represent them as plain dataclasses: the device
solver consumes flat arrays, so the descriptor layer exists for the
host-side event API, not for wire serialization.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional


#: the well-known node label that names a node's zone (Kubernetes,
#: "Well-Known Labels, Annotations and Taints")
ZONE_LABEL = "topology.kubernetes.io/zone"
#: the node label that names a node's rack, for a policy that prices the
#: switches between a task and its input (costmodels/quincy.py);
#: Kubernetes has no well-known key for it, clusters that label racks
#: use one of this shape
RACK_LABEL = "topology.kubernetes.io/rack"
#: the node label that names a node's platform (its microarchitecture
#: generation), for a policy that prices a task by the machine it runs
#: on (costmodels/whare.py); Kubernetes has no well-known key for it
#: (node.kubernetes.io/instance-type names a cloud's machine shape)
PLATFORM_LABEL = "ksched.io/platform"


class TaskState(enum.IntEnum):
    """Task lifecycle (reference: proto/task_desc.proto:12-22)."""

    CREATED = 0
    BLOCKING = 1
    RUNNABLE = 2
    ASSIGNED = 3
    RUNNING = 4
    COMPLETED = 5
    FAILED = 6
    ABORTED = 7
    DELEGATED = 8
    UNKNOWN = 9


class TaskType(enum.IntEnum):
    """CoCo workload classes (reference: proto/task_desc.proto:25-30)."""

    SHEEP = 0
    RABBIT = 1
    DEVIL = 2
    TURTLE = 3


class ResourceState(enum.IntEnum):
    """Resource lifecycle (reference: proto/resource_desc.proto:18-23)."""

    UNKNOWN = 0
    IDLE = 1
    BUSY = 2
    LOST = 3


class ResourceType(enum.IntEnum):
    """Resource topology node kinds (reference: proto/resource_desc.proto:25-37)."""

    PU = 0
    CORE = 1
    CACHE = 2
    NIC = 3
    DISK = 4
    SSD = 5
    MACHINE = 6
    LOGICAL = 7
    NUMA_NODE = 8
    SOCKET = 9
    COORDINATOR = 10


class JobState(enum.IntEnum):
    """Job lifecycle (reference: proto/job_desc.proto:17-24)."""

    NEW = 0
    CREATED = 1
    RUNNING = 2
    COMPLETED = 3
    FAILED = 4
    ABORTED = 5
    UNKNOWN = 6


class ReferenceType(enum.IntEnum):
    """Dataflow reference kinds (reference: proto/reference_desc.proto:16-24)."""

    TOMBSTONE = 0
    FUTURE = 1
    CONCRETE = 2
    STREAM = 3
    VALUE = 4
    ERROR = 5


class ReferenceScope(enum.IntEnum):
    """Dataflow reference visibility (reference: proto/reference_desc.proto:26-30)."""

    PUBLIC = 0
    PRIVATE = 1


@dataclass
class ResourceVector:
    """Multi-dimensional resource quantity (reference: proto/resource_vector.proto:12-19)."""

    cpu_cores: float = 0.0
    ram_bw: int = 0
    ram_cap: int = 0
    disk_bw: int = 0
    disk_cap: int = 0
    net_bw: int = 0


@dataclass
class WhareMapStats:
    """Per-machine co-location census for the Whare-Map cost model
    (reference: proto/whare_map_stats.proto:12-18)."""

    num_idle: int = 0
    num_devils: int = 0
    num_rabbits: int = 0
    num_sheep: int = 0
    num_turtles: int = 0


@dataclass
class CoCoInterferenceScores:
    """Per-class co-location penalties for the CoCo cost model
    (reference: proto/coco_interference_scores.proto:11-16)."""

    turtle_penalty: int = 0
    sheep_penalty: int = 0
    rabbit_penalty: int = 0
    devil_penalty: int = 0


@dataclass
class TaskFinalReport:
    """Post-mortem perf counters (reference: proto/task_final_report.proto:10-19)."""

    instructions: int = 0
    cycles: int = 0
    llc_refs: int = 0
    llc_misses: int = 0
    runtime: float = 0.0


@dataclass
class ReferenceDescriptor:
    """Dataflow input/output reference (reference: proto/reference_desc.proto:15-45)."""

    id: int = 0
    type: ReferenceType = ReferenceType.TOMBSTONE
    scope: ReferenceScope = ReferenceScope.PUBLIC
    non_deterministic: bool = False
    size: int = 0
    location: str = ""
    producing_task: int = 0


@dataclass
class TaskDescriptor:
    """A schedulable task (reference: proto/task_desc.proto:11-79).

    ``spawned`` forms the per-job task tree rooted at the job's root task;
    ``uid`` is a cluster-unique integer id.
    """

    uid: int = 0
    name: str = ""
    state: TaskState = TaskState.CREATED
    job_id: str = ""
    index: int = 0
    dependencies: List[ReferenceDescriptor] = field(default_factory=list)
    outputs: List[ReferenceDescriptor] = field(default_factory=list)
    binary: bytes = b""
    args: List[str] = field(default_factory=list)
    spawned: List["TaskDescriptor"] = field(default_factory=list)
    scheduled_to_resource: str = ""
    last_heartbeat_location: str = ""
    last_heartbeat_time: int = 0
    delegated_to: str = ""
    delegated_from: str = ""
    submit_time: int = 0
    start_time: int = 0
    finish_time: int = 0
    total_unscheduled_time: int = 0
    total_run_time: int = 0
    relative_deadline: int = 0
    absolute_deadline: int = 0
    port: int = 0
    input_size: int = 0
    inject_task_lib: bool = False
    resource_request: ResourceVector = field(default_factory=ResourceVector)
    priority: int = 0
    task_type: TaskType = TaskType.SHEEP
    #: index of the workload (Deployment) the task is a replica of, for
    #: models that place by workload (costmodels/k8s_antiaffinity.py)
    workload: int = 0
    final_report: Optional[TaskFinalReport] = None
    trace_job_id: int = 0
    trace_task_id: int = 0


@dataclass
class ResourceDescriptor:
    """A node in the resource topology (reference: proto/resource_desc.proto:18-64)."""

    uuid: str = ""
    friendly_name: str = ""
    descriptive_name: str = ""
    state: ResourceState = ResourceState.UNKNOWN
    task_capacity: int = 0
    last_heartbeat: int = 0
    type: ResourceType = ResourceType.PU
    schedulable: bool = False
    current_running_tasks: List[int] = field(default_factory=list)
    # Aggregates maintained by the graph manager / stats traversal
    # (reference: proto/resource_desc.proto:48-51).
    num_running_tasks_below: int = 0
    num_slots_below: int = 0
    available_resources: ResourceVector = field(default_factory=ResourceVector)
    reserved_resources: ResourceVector = field(default_factory=ResourceVector)
    min_available_resources_below: ResourceVector = field(default_factory=ResourceVector)
    max_available_resources_below: ResourceVector = field(default_factory=ResourceVector)
    capacity: ResourceVector = field(default_factory=ResourceVector)
    max_unavailable_resources_below: ResourceVector = field(default_factory=ResourceVector)
    whare_map_stats: WhareMapStats = field(default_factory=WhareMapStats)
    coco_interference_scores: CoCoInterferenceScores = field(default_factory=CoCoInterferenceScores)
    trace_machine_id: int = 0
    #: the node's labels as the control plane has them (a machine's:
    #: `NodeEvent.labels`; no reference counterpart). A cost model reads
    #: them in `add_machine`, e.g. the zone under ZONE_LABEL
    labels: Dict[str, str] = field(default_factory=dict)


@dataclass
class ResourceTopologyNodeDescriptor:
    """Recursive resource-topology tree (reference:
    proto/resource_topology_node_desc.proto:16-20)."""

    resource_desc: ResourceDescriptor = field(default_factory=ResourceDescriptor)
    parent_id: str = ""
    children: List["ResourceTopologyNodeDescriptor"] = field(default_factory=list)


@dataclass
class JobDescriptor:
    """A job: a tree of tasks under a root task (reference: proto/job_desc.proto:16-31)."""

    uuid: str = ""
    name: str = ""
    state: JobState = JobState.NEW
    root_task: Optional[TaskDescriptor] = None
    output_ids: List[int] = field(default_factory=list)
