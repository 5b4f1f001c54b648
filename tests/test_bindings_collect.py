"""`SchedulerService._collect_bindings` looks at the tasks whose binding
changed, and emits what a diff of every resident Binding would.

The whole-dict diff the service ran until PR 56 lives on here as the
oracle (`whole_dict_diff`: it walks every entry of the scheduler's
`task_bindings`, under `--preemption` every entry of `old_bindings` too,
and ends on a copy of the whole dict). `Checked` hangs it on a service:
every collection of every round shape (synchronous, `--pipeline`, the
split `dispatch_round` / `complete_round`) is run by both on books of
their own and must give the same Bindings (in the same order for the
tasks the scheduler changed; the re-posts of re-delivered pods as a
multiset: they come first now, where the diff had them at the task's
first place), the same evictions as a multiset, and leave the same
`old_bindings`, `_evicted_pending`, `_pods_evicted`, `_pods_migrated`.

The histories are seeded and random on a small cluster: arrivals,
completions, a re-delivered pod (same spec; changed spec, which evicts),
`handle_task_migration`, evictions and migrations under `--preemption`
(`k8s_priority`, where a higher tier takes a lower one's slot), a node
lost with pods on it (the heartbeat sweep's own path), a failed task, a
NOOP round between solved ones, a task unbound and bound back to the same
PU between two collections, `save_checkpoint` / restore and more rounds
(`restore` takes no `--preemption`, so that leg runs without the flag).
Counts only: nothing here reads a clock."""

import inspect
import json
import os
import re

import numpy as np
import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI
from ksched_tpu.cluster.api import Binding
from ksched_tpu.costmodels import CostModelType
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.chaos import ChaosPolicy, FaultInjector
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.scheduler import FlowScheduler
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng

MACHINES, PUS, SLOTS = 6, 2, 2
MODES = ("sync", "pipeline", "preemption")


def whole_dict_diff(svc, old_bindings, evicted_pending):
    """The collection as it stood before PR 56, on the caller's books:
    (evictions, Bindings, the next `old_bindings`, migrated). Reads the
    service's maps and the scheduler's live dict, writes neither."""
    new_bindings = svc.scheduler.get_task_bindings()
    evictions = []
    migrated = 0
    if svc.preemption:
        for task_id, pu_rid in old_bindings.items():
            now = new_bindings.get(task_id)
            if now == pu_rid:
                continue
            pod_id = svc.task_to_pod.get(task_id)
            if pod_id is None:
                continue
            node_id = svc._node_of(pu_rid)
            if node_id is None:
                continue
            evictions.append(Binding(pod_id=pod_id, node_id=node_id))
            if now is None:
                evicted_pending.add(task_id)
            else:
                migrated += 1
    out = []
    for task_id, pu_rid in new_bindings.items():
        if old_bindings.get(task_id) == pu_rid:
            continue
        node_id = svc._node_of(pu_rid)
        if node_id is None:
            continue
        pod_id = svc.task_to_pod.get(task_id)
        if pod_id is None:
            continue
        out.append(Binding(pod_id=pod_id, node_id=node_id))
        evicted_pending.discard(task_id)
    return evictions, out, dict(new_bindings), migrated


class Checked:
    """A service whose every collection is held against the oracle's.
    The oracle keeps `old` and `pending` as the service kept
    `old_bindings` and `_evicted_pending` before PR 56: `complete_pod`
    and a re-delivered pod forget an entry between two collections."""

    def __init__(self, svc, old=None):
        self.svc = svc
        self.old = dict(old or {})
        self.pending = set()
        self.collections = 0
        self.examined = []
        self.bindings = []
        self.evictions = []
        collect, complete, add = svc._collect_bindings, svc.complete_pod, svc._add_pod

        def _collect_bindings():
            sched = svc.scheduler
            reposts = {
                svc.task_to_pod.get(t)
                for t in svc._bindings_forgotten if t not in sched._bindings_changed
            }
            want_ev, want_out, self.old, want_migrated = whole_dict_diff(svc, self.old, self.pending)
            evictions, out = collect()
            self.collections += 1
            self.examined.append(svc._bindings_examined)
            key = lambda b: (b.pod_id, b.node_id)  # noqa: E731
            assert sorted(out, key=key) == sorted(want_out, key=key)
            assert (
                [b for b in out if b.pod_id not in reposts]
                == [b for b in want_out if b.pod_id not in reposts]
            )
            assert sorted(evictions, key=key) == sorted(want_ev, key=key)
            assert svc.old_bindings == self.old
            assert svc._evicted_pending == self.pending
            assert (svc._pods_evicted, svc._pods_migrated) == (len(want_ev), want_migrated)
            assert not sched._bindings_changed and not svc._bindings_forgotten
            self.bindings += out
            self.evictions += evictions
            return evictions, out

        def complete_pod(pod_id):
            task = svc.pod_to_task.get(pod_id)
            done = complete(pod_id)
            if done:
                self.old.pop(task, None)
                self.pending.discard(task)
            return done

        def _add_pod(pod):
            existing = svc.pod_to_task.get(pod.pod_id)
            add(pod)
            if existing is not None:
                self.old.pop(existing, None)

        svc._collect_bindings = _collect_bindings
        svc.complete_pod = complete_pod
        svc._add_pod = _add_pod


def _service(mode, slots=SLOTS, machines=MACHINES, **kw):
    api = SyntheticClusterAPI()
    name = "jax" if mode == "pipeline" else "native"
    if mode == "preemption":  # a higher tier takes a lower one's slot
        kw.update(preemption=True, cost_model=CostModelType.K8S_PRIORITY)
    svc = SchedulerService(
        api, max_tasks_per_pu=slots, backend=make_backend(name), backend_name=name,
        pipeline=mode == "pipeline", **kw,
    )
    svc.init_topology(fake_machines=machines, pus_per_core=PUS)
    return svc, api


def _pus_with_room(svc, slots=SLOTS):
    """PUs of machines the service still knows that hold fewer tasks than slots."""
    out = []
    for rid in svc.scheduler.cost_model.leaf_resource_ids:
        rs = svc.resource_map.find(rid)
        if rs is None or svc._node_of(rid) is None:
            continue
        if len(svc.scheduler.resource_bindings.get(rid, ())) < slots and len(
            rs.descriptor.current_running_tasks
        ) < slots:
            out.append(rid)
    return sorted(out)


class History:
    """A seeded stream of what can happen to a Binding, one service round
    after each step's events."""

    def __init__(self, mode, seed):
        seed_rng(seed)
        self.mode = mode
        self.rng = np.random.default_rng(seed)
        self.injector = FaultInjector(ChaosPolicy(seed=0, solver_fault_kinds=("nonconverge",)))
        self.svc, self.api = _service(mode, injector=self.injector, tracer=RoundTracer())
        self.checked = Checked(self.svc)
        self.clock = [0.0]
        self.svc.enable_heartbeats(machine_timeout_s=10.0, task_timeout_s=10.0, clock=lambda: self.clock[0])
        self.n = 0
        self.seen = {name: 0 for name in (
            "arrival", "completion", "redelivered", "respecced", "migration", "eviction",
            "bound_back", "node_lost", "task_failed", "noop",
        )}

    # -- the events ----------------------------------------------------------

    def _bound_pods(self):
        bound = self.svc.scheduler.task_bindings
        return sorted(p for p, t in self.svc.pod_to_task.items() if t in bound)

    def _pick(self, seq):
        return seq[int(self.rng.integers(len(seq)))] if seq else None

    def _new_pod(self):
        self.n += 1
        tier = int(self.rng.integers(3)) if self.mode == "preemption" else 0
        return PodEvent(pod_id=f"pod{self.n}", priority=tier)

    def _again(self, pod_id, **changes):
        td = self.svc.task_map.find(self.svc.pod_to_task[pod_id])
        spec = dict(
            cpu_request=td.resource_request.cpu_cores, memory_request=td.resource_request.ram_cap,
            net_bw_request=td.resource_request.net_bw, priority=td.priority,
        )
        spec.update(changes)
        return PodEvent(pod_id=pod_id, **spec)

    # each event returns whether it found something to happen to

    def _complete(self, batch):
        pod = self._pick(self._bound_pods())
        return pod is not None and self.svc.complete_pod(pod)

    def _redeliver(self, batch):
        pod = self._pick([p for p in sorted(self.svc.pod_to_task) if p not in {b.pod_id for b in batch}])
        if pod is not None:
            batch.append(self._again(pod))
        return pod is not None

    def _respec(self, batch):
        """Deleted and created again under its name with another request: evicted where bound."""
        pod = self._pick([p for p in sorted(self.svc.pod_to_task) if p not in {b.pod_id for b in batch}])
        if pod is not None:
            td = self.svc.task_map.find(self.svc.pod_to_task[pod])
            batch.append(self._again(pod, cpu_request=td.resource_request.cpu_cores + 1.0))
        return pod is not None

    def _bound_task(self):
        pod = self._pick(self._bound_pods())
        if pod is None:
            return None, None
        task = self.svc.pod_to_task[pod]
        rd = self.svc.resource_map.find(self.svc.scheduler.task_bindings[task]).descriptor
        return self.svc.task_map.find(task), rd

    def _migrate(self, batch):
        td, _rd = self._bound_task()
        if td is None:
            return False
        room = [r for r in _pus_with_room(self.svc) if r != self.svc.scheduler.task_bindings[td.uid]]
        if room:
            self.svc.scheduler.handle_task_migration(td, self.svc.resource_map.find(self._pick(room)).descriptor)
        return bool(room)

    def _evict(self, batch):
        td, rd = self._bound_task()
        if td is not None:
            self.svc.scheduler.handle_task_eviction(td, rd)
        return td is not None

    def _bind_back(self, batch):
        """Unbound and bound back to the same PU between two collections."""
        td, rd = self._bound_task()
        if td is not None:
            self.svc.scheduler.handle_task_eviction(td, rd)
            self.svc.scheduler.handle_task_placement(td, rd)
        return td is not None

    EVENTS = {
        "completion": _complete, "redelivered": _redeliver, "respecced": _respec,
        "migration": _migrate, "eviction": _evict, "bound_back": _bind_back,
    }

    def _sweep_with_a_silent(self, machine=False, task=False):
        """The heartbeat sweep's own path: everything beats at t, one loaded
        machine (or one bound task) beat long before, the round sweeps at t."""
        svc = self.svc
        self.clock[0] += 100.0
        t = self.clock[0]
        machines = sorted(svc.machine_to_node)
        for rid in machines:
            svc.monitor.record_machine_heartbeat(rid, now=t)
        if machine:
            bound = svc.scheduler.task_bindings.values()
            loaded = [m for m in machines if any(svc._find_parent_machine(pu) == m for pu in bound)]
            svc.monitor.record_machine_heartbeat(self._pick(loaded or machines), now=t - 50.0)
            self.seen["node_lost"] += 1
        if task and self._bound_pods():
            svc.monitor.record_task_heartbeat(svc.pod_to_task[self._pick(self._bound_pods())], now=t - 50.0)
            self.seen["task_failed"] += 1
        return t

    def step(self, force=None, noop=False, lose_node=False, fail_task=False):
        svc, rng = self.svc, self.rng
        batch = []
        for name, event in self.EVENTS.items():
            for _ in range(int(rng.integers(0, 3)) if name == "completion" else 1):
                if (name == force or rng.random() < 0.25) and event(self, batch):
                    self.seen[name] += 1
        now = self._sweep_with_a_silent(lose_node, fail_task) if lose_node or fail_task else None
        for _ in range(int(rng.integers(1 if noop else 0, 4))):  # a NOOP round needs a solve to fail
            batch.append(self._new_pod())
            self.seen["arrival"] += 1
        if noop:
            self.injector._solver_plan = {0: "nonconverge"}
            self.injector._solver_plan_all = True
            before, rounds = self.checked.collections, svc.noop_rounds
            with pytest.warns(RuntimeWarning, match="NOOP round"):
                svc.run_round(batch, now=now)
            self.injector._solver_plan = {}
            self.injector._solver_plan_all = False
            # the round collected nothing, and the record waits for the next
            assert svc.noop_rounds == rounds + 1 and self.checked.collections == before
            self.seen["noop"] += 1
        else:
            svc.run_round(batch, now=now)

    def run(self, steps=24):
        special = {5: "lose_node", 9: "noop", 13: "fail_task", 17: "noop", 19: "lose_node"}
        names = sorted(self.EVENTS)
        for i in range(steps):
            self.step(force=names[i % len(names)], **({special[i]: True} if i in special else {}))
        self.svc.run_round([])  # the last step's events and losses, collected
        return self


@pytest.mark.parametrize("seed", (1, 2, 3, 4))
@pytest.mark.parametrize("mode", MODES)
def test_a_seeded_history_collects_what_the_whole_dict_diff_would(mode, seed):
    h = History(mode, seed).run()
    assert h.checked.collections >= 20 and h.checked.bindings
    assert all(count > 0 for count in h.seen.values()), h.seen
    # the stream did what it is there for: re-posts, and under the flag evictions and migrations
    pods = [b.pod_id for b in h.checked.bindings]
    assert len(pods) > len(set(pods))
    if mode == "preemption":
        assert h.checked.evictions
        assert sum(r.pods_migrated for r in h.svc.tracer.records) > 0
        assert sum(r.pods_evicted for r in h.svc.tracer.records) == len(h.checked.evictions)
    # every solved round's record says what its collection looked at
    # every round's record says what its collection looked at; a NOOP round made none
    records = h.svc.tracer.records
    assert [r.bindings_examined for r in records if not r.noop_round] == h.checked.examined
    assert [r.bindings_examined for r in records if r.noop_round] == [0] * h.seen["noop"]


@pytest.mark.parametrize("manifest", ("warm", "cold"))
@pytest.mark.parametrize("mode,seed", (("sync", 5), ("pipeline", 6)))
def test_a_restored_service_collects_what_the_whole_dict_diff_would(mode, seed, manifest, tmp_path):
    h = History(mode, seed).run(steps=10)
    svc, sched = h.svc, h.svc.scheduler
    # an emitted Binding whose task is unbound at the save (the restored
    # `old_bindings` holds it and no re-pin names it), and a pod re-delivered
    # since the last collection (bound, and `old_bindings` has forgotten it)
    td, rd = h._bound_task()
    sched.handle_task_eviction(td, rd)
    evicted, again = svc.task_to_pod[td.uid], h._pick(h._bound_pods())
    svc._add_pod(h._again(again))
    path = str(tmp_path / "svc.ckpt")
    svc.save_checkpoint(path)
    saved = dict(svc.old_bindings)
    assert td.uid in saved and td.uid not in sched.task_bindings and saved == h.checked.old
    assert svc.pod_to_task[again] not in saved
    if manifest == "cold":
        os.remove(path + ".wal")  # the cold replay re-pins every Binding
    name = "jax" if mode == "pipeline" else "native"
    back = SchedulerService.restore(
        h.api, path, backend=make_backend(name), backend_name=name, pipeline=mode == "pipeline",
        tracer=RoundTracer(),
    )
    assert back.restored_warm == (manifest == "warm") and back.old_bindings == saved
    bound = back.scheduler.task_bindings
    assert set(back.scheduler._bindings_changed) == (set(bound) if manifest == "cold" else set())
    # the first collection looks at every task once, and emits what differs
    # from what was emitted before the kill: the new pods' Bindings, the
    # evicted pod's second one and the re-delivered pod's re-post
    checked = Checked(back, old=saved)
    back.run_round([PodEvent(pod_id=f"after{i}") for i in range(3)])
    assert checked.examined == [len(set(saved) | set(bound))]
    # (the cold replay does not offer a task that was evicted and waiting at the save again)
    want = ["after0", "after1", "after2", again] + [evicted] * (td.uid in bound)
    assert (td.uid in bound) or manifest == "cold"
    assert sorted(b.pod_id for b in checked.bindings) == sorted(want)
    assert back.old_bindings == dict(bound)
    h.svc, h.checked, h.n = back, checked, 1000
    back.enable_heartbeats(machine_timeout_s=10.0, task_timeout_s=10.0, clock=lambda: h.clock[0])
    for i in range(6):
        h.step(force=sorted(h.EVENTS)[i])
    back.run_round([])
    assert checked.collections >= 7 and max(checked.examined[1:]) < len(bound)


def test_a_round_of_three_arrivals_and_three_completions_examines_six_of_5000():
    seed_rng(7)
    svc, _api = _service("sync", slots=40, machines=64, tracer=RoundTracer())
    checked = Checked(svc)
    with SpanTracer() as tracer:
        assert svc.run_round([PodEvent(pod_id=f"fill{i}") for i in range(5000)]) == 5000
        for i in range(3):
            assert svc.complete_pod(f"fill{i}")
        assert svc.run_round([PodEvent(pod_id=f"new{i}") for i in range(3)]) == 3
    fill, served = [e["args"] for e in tracer.events() if e["name"] == "bindings_collect"]
    # the fill's one collection looks at every pod it bound, as before
    assert (fill["resident"], fill["examined"], fill["new"]) == (5000, 5000, 5000)
    assert (served["resident"], served["new"], served["evicted"]) == (5000, 3, 0)
    assert served["examined"] <= 6
    assert checked.examined == [5000, served["examined"]]
    assert [r.bindings_examined for r in svc.tracer.records] == checked.examined
    assert len(svc.old_bindings) == 5000


def test_a_split_round_without_its_second_half_keeps_the_record():
    """`dispatch_round` without `complete_round` yet, a quiet poll, a
    round with nothing runnable: none collects, none loses a task."""
    seed_rng(8)
    svc, _api = _service("pipeline", tracer=RoundTracer())
    checked = Checked(svc)
    assert svc.dispatch_round([PodEvent(pod_id="a"), PodEvent(pod_id="b")])
    assert checked.collections == 0
    assert svc.complete_round() == 2 and checked.examined == [2]
    task_a = svc.pod_to_task["a"]
    assert svc.complete_pod("a")
    svc.run_round([], solve=False)  # a quiet poll: no collection
    assert checked.collections == 1 and list(svc.scheduler._bindings_changed) == [task_a]
    svc.dispatch_round([PodEvent(pod_id="c")])
    svc.complete_round()
    # the completion waited out the quiet poll and was looked at with the arrival
    assert checked.examined == [2, 2] and [b.pod_id for b in checked.bindings] == ["a", "b", "c"]


def _small_scheduler_with_one_bound_task():
    from ksched_tpu.drivers import add_job, build_cluster

    sched, rmap, _jmap, tmap, _root = build_cluster(
        num_machines=2, num_cores=1, pus_per_core=2, max_tasks_per_pu=1
    )
    add_job(sched, _jmap, tmap, num_tasks=1)
    sched.schedule_all_jobs()
    ((task, pu),) = sched.task_bindings.items()
    assert list(sched.take_changed_bindings()) == [task] and not sched._bindings_changed
    return sched, rmap, tmap.find(task), pu


def _other_pu(sched, pu):
    return next(r for r in sorted(sched.cost_model.leaf_resource_ids) if r != pu)


WRITERS = {
    "handle_task_completion": lambda s, rmap, td, pu: s.handle_task_completion(td),
    "handle_task_failure": lambda s, rmap, td, pu: s.handle_task_failure(td),
    "handle_task_eviction": lambda s, rmap, td, pu: s.handle_task_eviction(td, rmap.find(pu).descriptor),
    "handle_task_migration": lambda s, rmap, td, pu: s.handle_task_migration(
        td, rmap.find(_other_pu(s, pu)).descriptor
    ),
    "evict_then_handle_task_placement": lambda s, rmap, td, pu: (
        s.handle_task_eviction(td, rmap.find(pu).descriptor),
        s.take_changed_bindings(),
        s.handle_task_placement(td, rmap.find(pu).descriptor),
    ),
    "deregister_resource": lambda s, rmap, td, pu: s.deregister_resource(
        rmap.find(_machine_of(rmap, pu)).topology_node
    ),
}


def _machine_of(rmap, pu):
    from ksched_tpu.utils import resource_id_from_string

    rs = rmap.find(pu)
    while rs.descriptor.type.name != "MACHINE":
        rs = rmap.find(resource_id_from_string(rs.topology_node.parent_id))
    return resource_id_from_string(rs.descriptor.uuid)


@pytest.mark.parametrize("writer", sorted(WRITERS))
def test_every_writer_of_task_bindings_leaves_its_task_in_the_record(writer):
    sched, rmap, td, pu = _small_scheduler_with_one_bound_task()
    before = dict(sched.task_bindings)
    WRITERS[writer](sched, rmap, td, pu)
    assert sched.task_bindings != before or writer == "evict_then_handle_task_placement"
    changed = sched.take_changed_bindings()
    assert list(changed) == [td.uid]
    assert not sched._bindings_changed and not sched.take_changed_bindings()


def test_task_bindings_is_written_where_the_record_is_and_nowhere_else():
    """A third writer added later fails here: the scheduler's source sets or
    deletes an entry of `task_bindings` in two places, each followed by the note."""
    src = inspect.getsource(FlowScheduler)
    writes = re.findall(r"del self\.task_bindings\[|self\.task_bindings\[[^\]]+\] = ", src)
    assert len(writes) == 2, writes
    assert src.count("self._note_binding_changed(task_id)") == 2
    for method in (FlowScheduler._bind_task_to_resource, FlowScheduler._unbind_task_from_resource):
        body = inspect.getsource(method)
        assert "self.task_bindings[task_id]" in body and "self._note_binding_changed(task_id)" in body


def test_a_task_touched_again_moves_to_the_end_as_in_task_bindings():
    from ksched_tpu.drivers import add_job, build_cluster

    sched, rmap, jmap, tmap, _root = build_cluster(
        num_machines=3, num_cores=1, pus_per_core=2, max_tasks_per_pu=1
    )
    add_job(sched, jmap, tmap, num_tasks=4)
    sched.schedule_all_jobs()
    assert list(sched.take_changed_bindings()) == list(sched.task_bindings)
    first = next(iter(sched.task_bindings))
    free = next(r for r in sorted(sched.cost_model.leaf_resource_ids) if r not in sched.resource_bindings or not sched.resource_bindings[r])
    sched.handle_task_migration(tmap.find(first), rmap.find(free).descriptor)
    last = list(sched.task_bindings)[-2]
    sched.handle_task_completion(tmap.find(last))
    assert list(sched.task_bindings)[-1] == first
    # the bound ones among the changed stand in task_bindings in the record's order
    changed = list(sched.take_changed_bindings())
    assert changed == [first, last]
    assert [t for t in changed if t in sched.task_bindings] == [t for t in sched.task_bindings if t in changed]


# -- the per-layer entry that reads the count (benchmarks/layer_metrics/bindings_examined_p50.json) --

NAME = "bindings_examined_p50"


def _bench():
    from benchmarks import spec

    return spec, spec.load_benchmark()


def test_the_entry_is_the_last_of_per_layer_equals_its_file_and_lists_the_graph_path_cells():
    spec, bench = _bench()
    entry = bench["per_layer"][-1]
    with open(os.path.join(spec.ROOT, "benchmarks", "layer_metrics", NAME + ".json")) as f:
        own = json.load(f)
    assert entry["name"] == NAME and {k: own[k] for k in entry if k != "workloads"} == {
        k: v for k, v in entry.items() if k != "workloads"
    }
    assert (entry["layer"], entry["moves"], entry["better"], entry["source"], entry["unit"]) == (
        "decode / apply / post", "bind_p50_ms", "lower", "program_counter", "tasks")
    assert (own["reader"], own["params"]) == ("round_field", {"field": "bindings_examined", "reduce": "p50"})
    cells = [w["name"] for w in bench["workloads"]]
    assert entry["workloads"] == [c for c in cells if "-array" not in c] and len(entry["workloads"]) == 12
    # the span's own metric stands where it stood, in every cell, with the layer's name letter for letter
    collect = next(m for m in bench["per_layer"] if m["name"] == "bindings_collect_ms")
    assert "workloads" not in collect and collect["layer"] == entry["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()[1]["workloads"]])
def test_a_cell_loads_the_entry_if_its_service_runs_the_collection(cell):
    spec, _bench_json = _bench()
    loaded = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert (NAME in loaded) == ("-array" not in cell) and "bindings_collect_ms" in loaded


def test_the_reader_gives_the_median_over_rounds_that_bound_a_pod_and_nothing_on_the_parent():
    from benchmarks import observe
    from benchmarks.readers import round_field

    def observation(records):
        return observe.Observation(
            device_kind="cpu", rounds=[], records=records, client={}, counters={}, shapes={},
            trace=None, rehearsal=True,
        )

    params = {"field": "bindings_examined", "reduce": "p50"}
    records = [
        {"num_scheduled": 150000, "bindings_examined": 150000},  # the fill
        {"num_scheduled": 14, "bindings_examined": 27},
        {"num_scheduled": 12, "bindings_examined": 25},
        {"num_scheduled": 0, "bindings_examined": 9},  # bound nothing: no sample
    ]
    assert round_field.read(params, observation(records)) == 27.0
    # a program that does not stamp the field (the parent): nothing, and no raise
    parent = [{k: v for k, v in r.items() if k != "bindings_examined"} for r in records]
    assert round_field.read(params, observation(parent)) is None
