"""L4: the pluggable cost-model (policy) interface.

Reference: scheduling/flow/costmodel/interface.go:27-136. The 16-method
surface is kept intact — arc costs, preference/EC enumeration, lifecycle
hooks, and the stats traversal — because the graph manager drives policy
exclusively through it. TPU-specific extension: cost models may override
the vectorized batch hooks (``ec_to_resource_batch`` etc.) to emit whole
cost/capacity arrays at once for the array fast path; the default
implementations fan out to the scalar methods.
"""

from __future__ import annotations

import abc
import enum
from typing import Dict, List, Optional, Sequence, Tuple, TYPE_CHECKING

from ..data import ResourceDescriptor, ResourceTopologyNodeDescriptor, TaskType
from ..utils import equiv_class_from_bytes

if TYPE_CHECKING:  # pragma: no cover
    from ..graph.flowgraph import Node


class CostModelType(enum.IntEnum):
    """Reference: costmodel/interface.go:33-43."""

    TRIVIAL = 0
    RANDOM = 1
    SJF = 2
    QUINCY = 3
    WHARE = 4
    COCO = 5
    OCTOPUS = 6
    VOID = 7
    NET = 8
    #: not of the reference's enumeration: required hostname
    #: anti-affinity (costmodels/k8s_antiaffinity.py)
    K8S_ANTIAFFINITY = 9
    #: nor this: a hard zone topology-spread constraint
    #: (costmodels/k8s_zonespread.py)
    K8S_ZONESPREAD = 10
    #: nor this: pod priority and preemption over slots
    #: (costmodels/k8s_priority.py)
    K8S_PRIORITY = 11
    #: nor this: CPU and memory requests against a node's allocatable
    #: (costmodels/k8s_requests.py)
    K8S_REQUESTS = 12


# The wildcard equivalence class every task points at in aggregate-style
# cost models (reference: costmodel/interface.go:46).
CLUSTER_AGGREGATOR_EC = equiv_class_from_bytes(b"CLUSTER_AGG")

Cost = int


#: what a model may say of itself -> the methods the claim is about: a
#: subclass that overrides one of them and does not say it again loses
#: the claim (CostModeler.__init_subclass__)
_CLAIM_METHODS = {
    # the methods through which a model could reach a pinned task's node
    "pinned_tasks_are_inert": (
        "task_continuation_cost", "prepare_stats", "gather_stats", "update_stats",
    ),
    # the two prices a resource node's turn asks for
    "resource_arc_costs_are_fixed": (
        "resource_node_to_resource_node_cost", "leaf_resource_node_to_sink_cost",
    ),
    # the listing that decides which arcs of an EC a sweep keeps
    "full_resources_stay_listed": ("get_outgoing_equiv_class_pref_arcs",),
}


class CostModeler(abc.ABC):
    """Reference: costmodel/interface.go:54-136."""

    #: What a model says about itself so the graph manager can leave
    #: pinned tasks (preemption off: one arc, cap_lower == cap_upper ==
    #: 1) off its per-round work list: ``task_continuation_cost`` is a
    #: constant, and ``prepare_stats`` / ``gather_stats`` /
    #: ``update_stats`` do nothing for an accumulator that is not a
    #: resource node. False keeps the visit of every task every round.
    #: The statistics pass relies on it for more (GraphManager.
    #: compute_topology_statistics gathers only the PUs whose lists
    #: changed and their ancestors): for a resource accumulator the
    #: three hooks read only the PU's ``current_running_tasks``,
    #: per-task facts that never change after admission, and the
    #: children's aggregates. A subclass that overrides one of those
    #: four methods has to say it again for itself; it does not inherit
    #: the claim.
    pinned_tasks_are_inert: bool = False

    #: What a model says about itself so the graph manager can take no
    #: resource-node turn in its per-round update (GraphManager.
    #: _queue_res_turn): ``resource_node_to_resource_node_cost(src,
    #: dst)`` and ``leaf_resource_node_to_sink_cost(rid)`` return, for
    #: the same arguments, the same value in every round. A resource
    #: node's turn does nothing but ask those two and re-price the arcs
    #: that are there; it changes no capacity and adds no arc. So with
    #: the claim the turn is a no-op in the journal and is not taken,
    #: and this is the precondition: a machine that joins gets its arcs
    #: priced by the same two hooks where _add_resource_topology_dfs
    #: makes them, capacities move through update_resource_topology /
    #: refresh_resource_topology, and a restored checkpoint carries the
    #: arcs with their costs. False keeps the turn of every resource
    #: node an EC or a task prefers, and of every node below it. A
    #: subclass that overrides one of the two methods has to say it
    #: again for itself; it does not inherit the claim.
    resource_arc_costs_are_fixed: bool = False

    #: What a model says about itself where
    #: ``get_outgoing_equiv_class_pref_arcs`` lists a resource whatever
    #: room it has, so that a sweep leaves an arc of capacity 0 to a full
    #: one (the census-priced models list every machine). The patch of
    #: the arcs ``equiv_class_pref_arc_changes`` names then writes
    #: capacity 0 on the arc as the sweep would, and the graph is the
    #: sweep's, arc for arc. False: the listing leaves a full resource
    #: out, the sweep deletes its arc, and so does the patch.
    full_resources_stay_listed: bool = False

    #: What a model says about itself where its prices only make sense
    #: while running tasks keep their arcs (a preemption cost, an EC ->
    #: machine capacity that counts every slot): a FlowScheduler built
    #: without ``preemption`` refuses it.
    needs_preemption: bool = False

    #: What a model says about itself where a task's turn lists arcs of
    #: the task's own (to machines, to equivalence classes other than
    #: the cluster aggregator): the graph manager then opens a
    #: `pref_refresh` span around that half of every task's turn. False:
    #: no such span (a span a task would cost the other models' waves).
    lists_task_preferences: bool = False

    #: What a model says about itself where the routes it gives one
    #: task differ in cost (a preferred machine, its rack, the cluster):
    #: two tasks that contend for one slot then settle, in the scan-CSR
    #: rung's eps = 1 discharge, by unit relabels over the cost gap
    #: times the node count (22,291 supersteps for 4 arrivals on 312
    #: machines, one gap of 4; CHANGES.md, PR 42), which ends in no
    #: round's time. `cli.build_service` then asks that rung for its
    #: global price update (JaxSolver.price_update_every), as it does
    #: under `--preemption`. False: the routes of a task cost alike.
    routes_differ_in_cost: bool = False

    #: What a model says about itself where a machine takes, in one
    #: round, fewer new tasks than it has free slots: pods that differ in
    #: size (costmodels/k8s_requests.py), where the number that fit
    #: together follows from the machine's books. ``machine_intake`` is
    #: that bound, and the graph manager writes it on the arcs from the
    #: machine node to its children (each child's free slots, over all
    #: of them no more than the bound; GraphManager._write_machine_intake),
    #: so the ECs together cannot send more; it reads the bound where it
    #: refreshes the tree, and asks ``take_machine_intake_changes`` at
    #: the end of every graph update for the machines whose bound moved
    #: since the last one, so that no solve sees a stale bound. The
    #: service reads the same fact in `_round_accounting`: a round that
    #: bound pods and left others waiting was cut by the offer, and the
    #: Bindings moved it, so it owes another round on a quiet poll. False:
    #: every capacity below a machine is its free slots.
    bounds_machine_intake: bool = False

    #: The model fits what a task asks for into what its machine says it
    #: can give (`ResourceDescriptor.capacity.cpu_cores`, `ram_cap`):
    #: the service refuses, by name, a node whose control plane gave
    #: neither (cli.SchedulerService.add_node), where it would else join
    #: with no arc and its pods wait with no word. False: no arc reads them.
    reads_machine_allocatable: bool = False

    #: The largest cost the model puts on any arc, where it states one
    #: (None: it states none). The scan-CSR rung scales costs by the
    #: node count and refuses `max|cost| * nodes >= 2^30` inside a round
    #: (solver/jax_solver.py); `cli.build_service` holds a model that
    #: states its largest cost, and a cluster whose size the flags give,
    #: to that bound before the service exists.
    largest_cost: Optional[int] = None

    #: 1 while the graph update of the round in progress had to leave
    #: the model's own allotment for a per-pod predicate (the zone
    #: spread model, where a zone is short of room); the scheduler
    #: stamps it on the round (RoundTiming.spread_fallback)
    spread_fallback: int = 0

    def __init_subclass__(cls, **kwargs) -> None:
        super().__init_subclass__(**kwargs)
        for claim, methods in _CLAIM_METHODS.items():
            if claim not in cls.__dict__ and any(m in cls.__dict__ for m in methods):
                setattr(cls, claim, False)

    # -- arc costs --------------------------------------------------------

    @abc.abstractmethod
    def task_to_unscheduled_agg_cost(self, task_id: int) -> Cost:
        """Cost of leaving the task unscheduled this round; should rise
        monotonically across rounds so starvation is bounded."""

    @abc.abstractmethod
    def unscheduled_agg_to_sink_cost(self, job_id: int) -> Cost: ...

    @abc.abstractmethod
    def task_to_resource_node_cost(self, task_id: int, resource_id: int) -> Cost: ...

    @abc.abstractmethod
    def resource_node_to_resource_node_cost(
        self, source: Optional[ResourceDescriptor], destination: ResourceDescriptor
    ) -> Cost: ...

    @abc.abstractmethod
    def leaf_resource_node_to_sink_cost(self, resource_id: int) -> Cost: ...

    @abc.abstractmethod
    def task_continuation_cost(self, task_id: int) -> Cost: ...

    @abc.abstractmethod
    def task_preemption_cost(self, task_id: int) -> Cost: ...

    @abc.abstractmethod
    def task_to_equiv_class_aggregator(self, task_id: int, ec: int) -> Cost: ...

    @abc.abstractmethod
    def equiv_class_to_resource_node(self, ec: int, resource_id: int) -> Tuple[Cost, int]:
        """Returns (cost, capacity); capacity is typically free slots below."""

    @abc.abstractmethod
    def equiv_class_to_equiv_class(self, ec1: int, ec2: int) -> Tuple[Cost, int]: ...

    # -- preference enumeration -------------------------------------------

    @abc.abstractmethod
    def get_task_equiv_classes(self, task_id: int) -> List[int]: ...

    @abc.abstractmethod
    def get_outgoing_equiv_class_pref_arcs(self, ec: int) -> List[int]: ...

    @abc.abstractmethod
    def get_task_preference_arcs(self, task_id: int) -> List[int]: ...

    @abc.abstractmethod
    def get_equiv_class_to_equiv_classes_arcs(self, ec: int) -> List[int]: ...

    # -- lifecycle --------------------------------------------------------

    @abc.abstractmethod
    def add_machine(self, rtnd: ResourceTopologyNodeDescriptor) -> None: ...

    @abc.abstractmethod
    def add_task(self, task_id: int) -> None: ...

    @abc.abstractmethod
    def remove_machine(self, resource_id: int) -> None: ...

    @abc.abstractmethod
    def remove_task(self, task_id: int) -> None: ...

    # -- stats traversal (reverse BFS from the sink) ----------------------

    @abc.abstractmethod
    def gather_stats(self, accumulator: "Node", other: "Node") -> "Node": ...

    @abc.abstractmethod
    def prepare_stats(self, accumulator: "Node") -> None: ...

    @abc.abstractmethod
    def update_stats(self, accumulator: "Node", other: "Node") -> "Node": ...

    # -- policy feedback (no-op defaults; models override as needed) ------

    def note_round(self, unscheduled_task_ids: Sequence[int]) -> None:
        """Called by the scheduler after every round with the runnable
        tasks that stayed unscheduled (e.g. Quincy's wait-cost bound)."""

    def record_task_completion(self, td) -> None:
        """Called by the scheduler when a task completes; models that
        learn from observed runtimes (SJF, Whare-Map) override this."""

    def task_bound(self, td, pu_resource_id: int) -> None:
        """Called by the scheduler where a task joins a PU's
        ``current_running_tasks`` (placement, migration)."""

    def task_unbound(self, task_id: int, pu_resource_id: int) -> None:
        """Called by the scheduler where a task leaves a PU's
        ``current_running_tasks``: at once when it is evicted or
        migrated away, in the next round's `deltas` phase when it
        completed, failed or was killed."""

    def task_class_fields(self, task_class: int) -> Dict[str, object]:
        """What ``PodEvent.task_class`` is on a ``TaskDescriptor`` for
        this model, as the fields to set; ``ValueError`` where the model
        has no such class. The default: one of the four CoCo classes,
        ``task_type``."""
        if not 0 <= task_class < len(TaskType):
            raise ValueError(
                f"task_class {task_class} is not one of the {len(TaskType)} CoCo "
                f"classes (0..{len(TaskType) - 1}) that {type(self).__name__} reads; "
                "a workload index needs a model that takes one: --cost-model "
                "k8s_antiaffinity or k8s_zonespread"
            )
        return {"task_type": TaskType(task_class)}

    def task_priority_fields(self, priority: int) -> Dict[str, object]:
        """What ``PodEvent.priority`` is on a ``TaskDescriptor`` for this
        model, as the fields to set; ``ValueError`` where the model
        prices priority and has no such tier. The default: carried as it
        is, and read by nothing."""
        return {"priority": priority}

    def task_input_fields(self, blocks: Sequence[Tuple[int, int, Sequence[int]]]) -> Dict[str, object]:
        """What ``PodEvent.inputs`` is on a ``TaskDescriptor`` for this
        model, as the fields to set: one (block id, bytes, machines that
        hold a replica, as resource ids) a block, the nodes already
        resolved by the service. The default: nothing (a model that
        prices no locality reads no input)."""
        return {}

    def round_locality(self) -> Optional[Tuple[int, int, int, int, int]]:
        """What the round in progress has bound so far, for a model that
        places by data locality: tasks bound by the cheapest route they
        had to their machine (a machine arc, a rack arc, the cluster
        aggregator), and the bytes those tasks read and read from
        another machine. None (the default): the model keeps no such
        count. Read by the scheduler before ``note_round``, which starts
        the counts again."""
        return None

    def take_census_machines_dirty(self) -> int:
        """Machines whose class census the round's statistics pass
        gathered again, for a model that keeps one (costmodels/census.py):
        every machine in a pass that walked every node. 0 (the default):
        the model keeps no census. Read by the scheduler after `stats`."""
        return 0

    def machine_intake(self, resource_id: int) -> int:
        """Under ``bounds_machine_intake``: the most new tasks the machine
        takes in the round, whatever their classes."""
        raise NotImplementedError

    def take_machine_intake_changes(self) -> List[int]:
        """Under ``bounds_machine_intake``: the machines whose bound moved
        since the last call, whatever moved it."""
        return []

    def round_books(self) -> Optional[Tuple[int, int, int]]:
        """For a model that keeps books of what its machines have
        reserved (costmodels/k8s_requests.py), as they stand at the
        round's solve: machines whose books moved since the last call,
        machines that some size class cannot use (a hole in their
        column, or no room at all), and the sum of the machines' bounds.
        None (the default): the model keeps no books. Read by the
        scheduler after `graph_update`."""
        return None

    def equiv_class_pref_arc_changes(self, ec: int) -> Optional[List[int]]:
        """The resources whose arc from ``ec`` may have changed (come,
        gone, another cost or capacity) since the arcs of ``ec`` were
        last listed, by this method or by
        ``get_outgoing_equiv_class_pref_arcs``; the graph manager then
        asks ``ec_to_resource_batch`` about those alone, in one call,
        where capacity 0 means no arc (or, under
        ``full_resources_stay_listed``, an arc of capacity 0). None (the
        default): the model keeps no such record, or cannot trust it
        this time, and every preferred resource is visited.

        Precondition of answering: the graph manager does not queue the
        listed resources, so ``_update_res_outgoing_arcs`` does not run
        for them on this EC's account. A model may answer only if the
        costs and capacities of its resource -> resource and PU -> sink
        arcs do not depend on the round (the trivial model's are
        constants: ``resource_arc_costs_are_fixed``, under which no
        resource is queued by anyone); one whose resource -> resource
        arcs follow a census must return None. That is about the arcs
        between resources. A model whose EC -> resource arcs follow a
        census may answer once the census says which machines it
        gathered again since the listing (costmodels/census.py:
        every change of a machine's counts reaches the statistics pass
        through ``GraphManager.running_tasks_changed`` before the next
        graph update reads them)."""
        return None

    # -- debug ------------------------------------------------------------

    def debug_info(self) -> str:
        return ""

    def debug_info_csv(self) -> str:
        return ""

    # -- vectorized batch hooks (TPU fast path; optional overrides) -------

    def ec_to_resource_batch(
        self, ec: int, resource_ids: Sequence[int]
    ) -> Tuple[List[Cost], List[int]]:
        """Batch form of equiv_class_to_resource_node: returns parallel
        (costs, capacities) lists for all given resources."""
        costs: List[Cost] = []
        caps: List[int] = []
        for rid in resource_ids:
            c, cap = self.equiv_class_to_resource_node(ec, rid)
            costs.append(c)
            caps.append(cap)
        return costs, caps

    def task_to_unscheduled_agg_cost_batch(self, task_ids: Sequence[int]) -> List[Cost]:
        return [self.task_to_unscheduled_agg_cost(t) for t in task_ids]

    def task_to_equiv_class_aggregator_batch(
        self, task_ids: Sequence[int], ec: int
    ) -> List[Cost]:
        return [self.task_to_equiv_class_aggregator(t, ec) for t in task_ids]
