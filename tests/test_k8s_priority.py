"""`--cost-model k8s_priority --preemption` on the served path: pod
priority and preemption over slots (costmodels/k8s_priority.py).

Seeded multi-round streams through `cli.build_service`, the test's own
books of who holds which slot: every served round's pods bound and evicted
by tier equal the plain reference's greedy
(benchmarks/reference_preemption.py: no graph manager, no cost model
class, no solver), its objective equals the closed form and native C++'s,
the replay of the run's log holds every round to the guarantee, evictions
are posted before Bindings, an evicted pod is the same task and gets a
second Binding when a slot frees, and the flag and the model refuse the
pairs that are not served. Nothing here depends on the wall clock."""

import importlib

import numpy as np
import pytest

from benchmarks.client import BenchClusterAPI
from benchmarks.reference_preemption import (
    EC_COST,
    TIER_FACTOR,
    UNSCHEDULED_COST,
    check_priority_preemption,
    reference_round,
    round_objective,
)
from ksched_tpu import cli
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.cluster.api import Binding, ClusterAPI, PodEvent
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, K8sPriorityCostModel
from ksched_tpu.data import TaskState
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.scheduler import FlowScheduler
from ksched_tpu.solver.jax_solver import PREEMPTION_PRICE_UPDATE_EVERY
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng


def _args(machines, slots, backend="native", extra=""):
    return cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --max-tasks-per-pu {slots} "
        f"--cost-model k8s_priority --preemption --backend {backend} {extra}".split()
    )


def _service(machines, slots, backend="native", **kw):
    api = BenchClusterAPI(pod_chan_size=10_000)
    svc = cli.build_service(_args(machines, slots, backend), api, **kw)
    api.svc = svc
    svc.init_topology(fake_machines=machines)
    return svc, api


def drain(api, n):
    """The `n` pods submitted so far, in as many polls as the debounce
    takes: its quiet timer runs on the machine's clock, and under load a
    poll can return before it has emptied the channel. The first poll also
    delivers the completions queued for this round."""
    batch = api.poll_pod_batch(0.002)
    while len(batch) < n:
        batch += api.poll_pod_batch(0.002)
    return batch


class Stream:
    """A seeded stream of arrivals and completions, with the test's own
    books: what the reference is given, and what the log is read against."""

    def __init__(self, machines, slots, tiers, seed, backend="native", **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, slots, backend, **kw)
        self.capacity = slots
        self.total = machines * slots
        self.tiers = tiers
        self.rng = np.random.default_rng(seed)
        self.tier_of = {}
        self.bound = {}  # pod -> node, pods alive and bound
        self.pending = []  # submitted, alive, without a slot
        self.k = 0
        self.mark = 0  # entries of the log read so far

    def by_tier(self, pods):
        counts = [0] * self.tiers
        for p in pods:
            counts[self.tier_of[p]] += 1
        return counts

    def round(self, arrivals, completions=0):
        """One served round: `arrivals` is a list of tiers (or a number of
        pods of random tiers), `completions` a number of random bound pods
        (or a list of them). Returns ((bound by tier, evicted by tier),
        objective) of the service, and the same of the reference."""
        if isinstance(completions, int):
            completions = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        if isinstance(arrivals, int):
            arrivals = [int(t) for t in self.rng.integers(0, self.tiers, arrivals)]
        # under preemption a completed pod's slot is free at once: its
        # node went with the completion, PU -> sink carries every slot
        for p in completions:
            del self.bound[p]
        self.api.complete_later(completions)
        for tier in arrivals:
            pod = f"p{self.k}"
            self.k += 1
            self.tier_of[pod] = tier
            self.pending.append(pod)
            self.api.submit_pod(PodEvent(pod_id=pod, priority=tier))
        pending_before = self.by_tier(self.pending)
        want = reference_round(
            self.total - len(self.bound), self.by_tier(self.bound), pending_before
        )
        self.svc.run_round(drain(self.api, len(arrivals)))
        evicted, placed = [], []
        for kind, pod, node, _t in self.api.log[self.mark:]:
            if kind == "evict":
                assert self.bound.pop(pod) == node
                self.pending.append(pod)
                evicted.append(pod)
            elif kind == "bind":
                assert pod not in self.bound
                self.bound[pod] = node
                self.pending.remove(pod)
                placed.append(pod)
        self.mark = len(self.api.log)
        got = (self.by_tier(placed), self.by_tier(evicted))
        objective = int(self.svc.scheduler.last_timing.objective)
        return (got, objective), (want, round_objective(*want, pending_before))

    def native_objective(self):
        """Native C++ on the arrays as the last solve left them, beside
        that solve's own objective: the round's, or, in a round that
        re-fitted the slot plan, the one that followed the re-fit."""
        solver = self.svc.scheduler.solver
        native = make_backend("native", warm_start=False, fallback=False)
        return (
            int(native.solve(solver.state.problem()).objective),
            int(solver.last_result.objective),
        )

    def holds_the_guarantee(self):
        faults, facts = check_priority_preemption(
            self.api.log, self.tier_of, self.capacity, num_nodes=self.total // self.capacity
        )
        assert faults == [], faults
        return facts

    def books_agree(self):
        """The service's maps against the test's books."""
        svc = self.svc
        bound = {svc.task_to_pod[t] for t in svc.scheduler.task_bindings}
        assert bound == set(self.bound)
        assert {svc.task_to_pod[t] for t in svc._evicted_pending} <= set(self.pending)
        for pod in self.pending:
            assert svc.task_map.find(svc.pod_to_task[pod]).state == TaskState.RUNNABLE
        loads = {}
        for node in self.bound.values():
            loads[node] = loads.get(node, 0) + 1
        assert max(loads.values(), default=0) <= self.capacity


STREAMS = [
    # machines, slots, tiers, seed, backend, fill (pods of tier 0), rounds, arrivals, completions
    (5, 4, 2, 1, "native", 20, 6, 4, 2),  # exactly full
    (5, 4, 2, 1, "jax", 20, 5, 4, 2),
    (8, 4, 2, 2, "native", 20, 7, 6, 1),  # part full: free slots first
    (8, 4, 3, 3, "native", 32, 8, 7, 3),  # three tiers, mixed arrivals
    (8, 4, 3, 3, "jax", 32, 5, 7, 3),
    (25, 4, 3, 4, "native", 90, 8, 20, 8),
    (125, 4, 2, 5, "native", 500, 5, 40, 10),  # the cell's rehearsal size
    (125, 4, 2, 6, "jax", 500, 3, 40, 10),
]


@pytest.mark.parametrize(
    "machines,slots,tiers,seed,backend,fill,rounds,arrivals,completions", STREAMS
)
def test_every_round_equals_the_reference_tier_by_tier_and_holds_the_guarantee(
    machines, slots, tiers, seed, backend, fill, rounds, arrivals, completions
):
    s = Stream(machines, slots, tiers, seed, backend)
    ours, reference = s.round([0] * fill)
    assert ours == reference and ours[0] == ([fill] + [0] * (tiers - 1), [0] * tiers)
    evictions = 0
    for r in range(rounds):
        if r < rounds - 2:
            ours, reference = s.round(arrivals, completions if r % 2 else 0)
        else:
            # the stream ends on completions alone, enough of them for
            # every pod that waits: the evicted are bound again
            ours, reference = s.round(0, min(len(s.bound), len(s.pending) // (rounds - r) + 1))
        assert ours == reference, f"round {r}"
        theirs, last = s.native_objective()
        assert theirs == last, f"round {r}"
        assert last == ours[1] or s.svc.scheduler.last_timing.plan_refits, f"round {r}"
        s.books_agree()
        t = s.svc.scheduler.last_timing
        assert t.unscheduled_by_rule == 0 and t.stats_full_walk == t.apply_full_walk == 1
        evictions += sum(ours[0][1])
    facts = s.holds_the_guarantee()
    # a round that bound nothing (tier-0 arrivals on a full cluster) leaves no entry
    assert 2 <= facts["rounds"] <= rounds + 1 and sum(facts["evicted_by_tier"]) == evictions > 0
    assert facts["evicted_by_tier"][-1] == 0  # the top tier is never evicted
    assert facts["evicted_then_bound_again"] > 0 and not s.pending
    assert s.svc.noop_rounds == 0 and s.svc.ladder.degradations_total == 0


@pytest.mark.parametrize("backend", ["native", "jax"])
def test_an_equal_tier_arrival_on_a_full_cluster_stays_pending_and_evicts_nothing(backend):
    s = Stream(3, 2, 2, 7, backend)
    s.round([1] * 4 + [0] * 2)
    ours, reference = s.round([1, 1, 0])
    assert ours == reference
    # one tier-1 arrival for each tier-0 incumbent; the third stays pending
    assert ours[0] == ([0, 2], [2, 0]) and sorted(s.by_tier(s.pending)) == [0, 3]
    ours, reference = s.round([1, 0])  # full of tier 1: nobody can evict
    assert ours == reference and ours[0] == ([0, 0], [0, 0])
    assert all(kind != "evict" for kind, *_ in s.api.log[-1:])
    assert ours[1] == 4 * UNSCHEDULED_COST + 1 * UNSCHEDULED_COST * TIER_FACTOR  # 4 of tier 0, 1 of tier 1 wait
    s.holds_the_guarantee()


def test_an_evicted_pod_is_the_same_task_and_gets_a_second_binding_when_a_slot_frees():
    s = Stream(2, 2, 2, 8)
    s.round([0] * 4)
    task_of = dict(s.svc.pod_to_task)
    ours, reference = s.round([1])
    assert ours == reference and ours[0] == ([0, 1], [1, 0])
    (victim,) = [p for p in s.pending]
    assert s.svc.pod_to_task[victim] == task_of[victim]  # pending, the same task
    assert s.svc.complete_pod(victim) is False  # a pending pod cannot complete
    assert s.svc._evicted_pending == {task_of[victim]}
    other = next(p for p in s.bound if s.tier_of[p] == 0)
    ours, reference = s.round([], [other])  # a slot frees: the victim returns
    assert ours == reference and ours[0] == ([1, 0], [0, 0])
    assert not s.svc._evicted_pending and s.svc.pod_to_task[victim] == task_of[victim]
    history = [(kind, node) for kind, pod, node, _t in s.api.log if pod == victim]
    assert [k for k, _n in history] == ["bind", "evict", "bind"]
    assert history[0][1] == history[1][1]  # evicted from the node it was bound to
    assert len(s.api.bind_stamps[victim]) == 2
    s.holds_the_guarantee()


def test_evictions_are_posted_in_one_call_before_the_rounds_bindings():
    calls = []

    class Recorder(SyntheticClusterAPI):
        def evict_pods(self, evictions):
            calls.append(("evict", [(e.pod_id, e.node_id) for e in evictions]))

        def assign_bindings(self, bindings):
            super().assign_bindings(bindings)
            calls.append(("bind", [(b.pod_id, b.node_id) for b in bindings]))

    seed_rng(9)
    api = Recorder(pod_chan_size=100)
    svc = cli.build_service(_args(2, 2), api)
    svc.init_topology(fake_machines=2)
    svc.run_round([PodEvent(pod_id=f"r{i}") for i in range(4)])
    svc.run_round([PodEvent(pod_id="h0", priority=1), PodEvent(pod_id="h1", priority=1)])
    assert [kind for kind, _ in calls] == ["bind", "evict", "bind"]
    evicted, bound = calls[1][1], calls[2][1]
    assert len(evicted) == len(bound) == 2 and {p for p, _n in bound} == {"h0", "h1"}
    before = dict(calls[0][1])
    assert all(before[pod] == node for pod, node in evicted)
    # each freed slot is taken at once: the nodes that lost a pod are the nodes that got one
    assert sorted(n for _p, n in evicted) == sorted(n for _p, n in bound)
    # the default hook is a no-op that every adapter inherits
    assert ClusterAPI.evict_pods(api, [Binding("x", "y")]) is None
    assert "evict_pods" not in SyntheticClusterAPI.__dict__


def test_a_migration_is_an_eviction_from_the_old_node_and_a_binding_to_the_new():
    s = Stream(2, 1, 2, 10)
    s.round([0])
    (pod, old), = s.bound.items()
    svc = s.svc
    task = svc.pod_to_task[pod]
    new = next(n for n in svc.node_to_machine if n != old)
    pu = next(
        r for r in svc.scheduler.cost_model.leaf_resource_ids
        if svc._node_of(r) == new
    )
    svc.scheduler.handle_task_migration(svc.task_map.find(task), svc.resource_map.find(pu).descriptor)
    evictions, out = svc._collect_bindings()
    assert evictions == [Binding(pod, old)] and out == [Binding(pod, new)]
    assert (svc._pods_evicted, svc._pods_migrated) == (1, 1) and not svc._evicted_pending


def test_the_pairs_that_are_not_served_are_refused_with_a_message():
    api = SyntheticClusterAPI()
    parser = cli.build_arg_parser()
    no_flag = parser.parse_args("--fake-machines --cost-model k8s_priority".split())
    with pytest.raises(ValueError, match="only with preemption on"):
        cli.build_service(no_flag, api)
    with pytest.raises(ValueError, match="only with preemption on"):
        FlowScheduler(None, None, None, None, cost_model_factory=K8sPriorityCostModel)
    for extra, word in (
        ("--pipeline", "drop --pipeline"), ("--device-resident", "drop --device-resident"),
        ("--pipeline --device-resident", "drop --pipeline and --device-resident"),
    ):
        with pytest.raises(ValueError, match=word):
            cli.build_service(_args(2, 2, "jax", extra), api)
    with pytest.raises(ValueError, match="global price update"):
        cli.build_service(_args(2, 2, "auto"), api)
    with pytest.raises(SystemExit) as e:  # the CLI says it as a usage error
        cli.main("--fake-machines --cost-model k8s_priority --podgen 1 --one-shot".split())
    assert e.value.code == 2
    assert parser.parse_args([]).preemption is False
    # the flag alone is served under any model: nothing is pinned
    svc = cli.build_service(parser.parse_args("--fake-machines --preemption".split()), api)
    assert svc.preemption and svc.scheduler.gm.preemption


def test_a_tier_the_model_does_not_price_is_refused_where_the_pod_is_admitted():
    svc, _api = _service(2, 2)
    for bad in (-1, K8sPriorityCostModel.MAX_TIERS):
        with pytest.raises(ValueError, match=f"pod x: priority {bad} is not one of the 4 tiers"):
            svc.run_round([PodEvent(pod_id="x", priority=bad)])
    assert svc.run_round([PodEvent(pod_id="y", priority=K8sPriorityCostModel.MAX_TIERS - 1)]) == 1
    # every other model carries a priority and reads nothing from it
    plain = cli.build_service(cli.build_arg_parser().parse_args(["--fake-machines"]), SyntheticClusterAPI())
    plain.init_topology(fake_machines=1)
    assert plain.run_round([PodEvent(pod_id="z", priority=77)]) == 1
    assert plain.task_map.find(plain.pod_to_task["z"]).priority == 77


def test_the_constants_meet_the_two_conditions_and_fit_the_scaled_int32():
    u = K8sPriorityCostModel.unscheduled_cost
    e = K8sPriorityCostModel.CLUSTER_AGG_COST
    assert (e, u(0), K8sPriorityCostModel.TIER_FACTOR) == (EC_COST, UNSCHEDULED_COST, TIER_FACTOR)
    for k in range(1, K8sPriorityCostModel.MAX_TIERS):
        assert e + u(k - 1) < u(k)  # (1) a higher tier displaces a lower one
    for k in range(K8sPriorityCostModel.MAX_TIERS):
        assert u(k) < e + u(k)  # (2) an equal tier never displaces an incumbent
    padded_nodes = 65_536  # the 5,000-node cluster's bucket
    assert u(K8sPriorityCostModel.MAX_TIERS - 1) * padded_nodes < 1 << 30
    assert u(K8sPriorityCostModel.MAX_TIERS) * padded_nodes >= 1 << 30


def test_the_model_is_registered_and_its_docstring_holds_the_semantics():
    assert MODEL_REGISTRY[CostModelType.K8S_PRIORITY] is K8sPriorityCostModel
    assert int(CostModelType.K8S_PRIORITY) == 11 and len(MODEL_REGISTRY) == 13
    assert "k8s_priority" in cli.build_arg_parser().format_help()
    assert K8sPriorityCostModel.pinned_tasks_are_inert
    assert K8sPriorityCostModel.resource_arc_costs_are_fixed
    assert K8sPriorityCostModel.needs_preemption
    text = importlib.import_module(K8sPriorityCostModel.__module__).__doc__
    for phrase in (
        "strictly lower priority", "(1) e + u(k-1) < u(k)", "(2) u(k) < e + c(k)",
        "u(k) = 5 * 8^k", "capacity slots(m)", "incumbents\nfirst within a tier", "K = 4",
        "Departures from Kubernetes", "ONE eviction for each preempting pod",
    ):
        assert phrase in text, phrase
    reference = importlib.import_module("benchmarks.reference_preemption").__doc__
    assert "ONE eviction for each preempting pod" in reference and "ksched_tpu" in reference


def test_the_cluster_ecs_arcs_are_listed_once_and_follow_the_machines_that_join():
    s = Stream(4, 2, 2, 11)
    s.round([0] * 8)
    gm = s.svc.scheduler.gm
    assert gm.ec_arcs_changed == 4  # the first listing: one arc a machine
    (ec_node,) = gm.task_ec_to_node.values()
    assert sorted(a.cap_upper for a in ec_node.outgoing.values()) == [2] * 4  # every slot, not the free ones
    for _ in range(3):
        s.round([1])
        assert gm.ec_arcs_changed == 0  # nothing about a machine changed: no arc is touched
    from ksched_tpu.cluster.api import NodeEvent

    s.svc.add_node(NodeEvent(node_id="late", pus_per_core=2))
    s.svc.run_round([PodEvent(pod_id="late_pod", priority=0)])
    assert gm.ec_arcs_changed == 1
    assert sorted(a.cap_upper for a in ec_node.outgoing.values()) == [2] * 4 + [4]
    assert s.api.bindings()["late_pod"] == "late"  # a free slot before an eviction


def test_a_full_cluster_round_ends_on_the_jax_rung_within_a_few_price_updates():
    """The repair this deployment forced: without the global price update
    one arrival on a full 125-machine cluster takes the scan-CSR discharge
    45,617 supersteps and a step down the ladder."""
    s = Stream(30, 4, 2, 12, "jax")
    rung = s.svc.ladder.primary
    assert rung.price_update_every == PREEMPTION_PRICE_UPDATE_EVERY == 8
    s.round([0] * 120)
    for arrivals in ([1], [1] * 10, [1] * 40, []):
        ours, reference = s.round(arrivals)
        assert ours == reference
        assert rung.last_supersteps <= 16 * rung.price_update_every
    assert s.svc.ladder.degradations_total == 0 and s.svc.ladder.last_rung == 0
    # a service without the flag gets the program it always had
    plain = cli.build_service(
        cli.build_arg_parser().parse_args("--fake-machines --backend jax".split()),
        SyntheticClusterAPI(),
    )
    assert plain.ladder.primary.price_update_every == 0


def test_the_spans_and_the_counters_ride_the_round_record():
    tracer = SpanTracer(capacity=1 << 14).install()
    try:
        s = Stream(3, 2, 2, 13, tracer=RoundTracer(), span_tracer=tracer)
        s.round([0] * 6)
        s.round([1, 1, 0])
        events = tracer.events()
    finally:
        tracer.uninstall()
    fill, rec = s.svc.tracer.records[-2:]
    assert (fill.tasks_unpinned, fill.pods_evicted, fill.pods_pending_evicted) == (0, 0, 0)
    assert (rec.tasks_unpinned, rec.pods_evicted, rec.pods_migrated) == (6, 2, 0)
    assert (rec.pods_pending_evicted, rec.num_scheduled, rec.decode_pinned_skipped) == (2, 2, 0)
    assert rec.decode_tasks == 9 and rec.stats_full_walk == rec.apply_full_walk == 1
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (post,) = by_name["evictions_post"]
    assert post["args"]["n"] == 2
    bind = by_name["bindings_post"][-1]
    assert post["ts"] + post["dur"] <= bind["ts"]  # beside it, and first
    walk = by_name["preempt_deltas"][-1]
    deltas = by_name["deltas"][-1]
    assert walk["args"]["preempted"] == 2
    assert deltas["ts"] <= walk["ts"] and walk["ts"] + walk["dur"] <= deltas["ts"] + deltas["dur"]
    assert by_name["bindings_collect"][-1]["args"]["evicted"] == 2
    # an idle sweep reports no eviction of the round before it
    s.svc.run_round([], solve=False)
    idle = s.svc.tracer.records[-1]
    assert (idle.pods_evicted, idle.pods_pending_evicted) == (0, 2)
