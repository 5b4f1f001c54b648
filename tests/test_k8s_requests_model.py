"""`--cost-model k8s_requests`: CPU and memory requests fitted into a node's
allocatable vector, priced by `LeastAllocated` and `BalancedAllocation`.

The equations by hand on a dozen nodes; k(m) and the cap = 1 case; the
bound on the arc below the machine; a round of mixed sizes never
overcommits; books kept by events equal books by a full walk after binds,
completions and a restore; every served round of a seeded 125-node cluster
costs what the plain reference says (benchmarks/reference_requests.py: the
equations again and a textbook successive shortest path, nothing of
`ksched_tpu`), on the dense rung; the flags, and what is refused.
"""

import argparse

import numpy as np
import pytest

from benchmarks import reference_requests as ref
from benchmarks.client import BenchClusterAPI
from ksched_tpu import cli
from ksched_tpu.cluster.api import NodeEvent, PodEvent
from ksched_tpu.costmodels import MODEL_REGISTRY, CostModelType, K8sRequestsCostModel
from ksched_tpu.costmodels import k8s_requests as model
from ksched_tpu.obs.spans import SpanTracer
from ksched_tpu.runtime.checkpoint import restore_scheduler, save_scheduler
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.solver.select import make_backend
from ksched_tpu.utils import seed_rng
from test_k8s_priority import drain

A = (4000, 32768)
P = 110
SIZES = [(100, 256), (250, 1024), (500, 1024), (250, 4096)]


def _service(machines, backend="auto", allocatable="4000:32768", slots=P, **kw):
    args = cli.build_arg_parser().parse_args(
        f"--fake-machines --num-machines {machines} --max-tasks-per-pu {slots} "
        f"--fake-node-allocatable {allocatable} --cost-model k8s_requests --backend {backend}".split()
    )
    api = BenchClusterAPI(pod_chan_size=100_000)
    svc = cli.build_service(args, api, **kw)
    api.svc = svc
    svc.init_topology(fake_machines=machines)
    return svc, api


def books_by_walk(m):
    """machine -> (reserved CPU, reserved memory, pods) from the PUs'
    `current_running_tasks` and the task descriptors: what the model's
    events keep, recomputed."""
    out = {rid: [0, 0, 0] for rid in m._row}
    for pu, machine in m._pu_machine.items():
        rs = m.resource_map.find(pu)
        if rs is None or machine not in out:
            continue
        for task_id in rs.descriptor.current_running_tasks:
            cpu, mem = model.request_of(m.task_map.find(task_id))
            acc = out[machine]
            acc[0] += cpu
            acc[1] += mem
            acc[2] += 1
    return {rid: tuple(v) for rid, v in out.items()}


def _pod(pod_id, size):
    return PodEvent(pod_id=pod_id, cpu_request=size[0] / 1000.0, memory_request=size[1])


def _node_index(node):
    return int(node.rsplit("_", 1)[1])


# -- the equations, by hand -------------------------------------------------------------------


@pytest.mark.parametrize("reserved, asks, want", [
    # (reserved cpu, mem), the pod's request -> (u_cpu + u_mem) // 2 + |u_cpu - u_mem| // 2
    ((0, 0), (100, 256), 2),            # u = (2, 0): 1 + 1
    ((0, 0), (250, 1024), 5),           # u = (6, 3): 4 + 1
    ((0, 0), (500, 1024), 11),          # u = (12, 3): 7 + 4
    ((0, 0), (250, 4096), 12),          # u = (6, 12): 9 + 3
    ((2000, 16384), (500, 1024), 61),   # u = (62, 53): 57 + 4
    ((3500, 8192), (500, 1024), 100),   # u = (100, 28): 64 + 36
    ((3900, 32000), (100, 256), 100),   # u = (100, 98): 99 + 1
    ((4000, 0), (0, 0), 100),           # u = (100, 0): 50 + 50
])
def test_the_price_is_the_two_default_scores_in_integers(reserved, asks, want):
    got = int(model.requests_cost(
        np.array([reserved[0]]), np.array([reserved[1]]), np.array([A[0]]), np.array([A[1]]), asks
    )[0])
    theirs, fits = ref.cost_rows(np.array([reserved]), A, [asks])
    assert got == want == int(theirs[0, 0])
    assert bool(fits[0, 0]) == (reserved[0] + asks[0] <= A[0] and reserved[1] + asks[1] <= A[1])


@pytest.mark.parametrize("reserved, running, r_max, k, cap", [
    ((0, 0), 0, (500, 4096), 8, 8),            # min(8, 8, 110)
    ((0, 0), 0, (100, 256), 40, 40),           # min(40, 128, 110)
    ((0, 0), 0, (0, 0), 110, 110),             # no pod seen yet: the slots
    ((2200, 12800), 8, (500, 4096), 3, 3),     # min(1800 // 500, 19968 // 4096 = 4, 102)
    ((3600, 4096), 9, (500, 4096), 0, 1),      # 400m left: the largest does not fit, a 250m pod may
    ((4000, 4096), 8, (500, 4096), 0, 1),      # nothing fits by CPU: cap 1, and no arc at all
    ((1000, 1000), 110, (500, 4096), 0, 0),    # no pod slot left: nothing
    ((1000, 1000), 109, (500, 4096), 1, 1),    # one slot left bounds it
    ((0, 30000), 3, (500, 4096), 0, 1),        # memory decides
])
def test_k_and_cap_by_the_largest_request_seen(reserved, running, r_max, k, cap):
    free = (np.array([A[0] - reserved[0]]), np.array([A[1] - reserved[1]]))
    slots = np.array([P - running])
    got_k = int(model.fit_count(free[0], free[1], slots, r_max)[0])
    assert max(got_k, 0) == k
    assert int(model.intake(np.array([got_k]), slots)[0]) == cap
    theirs = ref.node_intake(np.array([reserved]), np.array([running]), A, P, r_max)
    assert int(theirs[0]) == cap


def test_a_dozen_nodes_by_hand():
    """Twelve nodes with books set by hand: arcs only where the request fits,
    capacity cap(m), the price of the round's start."""
    svc, api = _service(12)
    m = svc.scheduler.cost_model
    assert isinstance(m, K8sRequestsCostModel)
    rids = [svc.node_to_machine[f"fake_node_{i}"] for i in range(12)]
    rows = [m._row[r] for r in rids]
    books = [  # reserved cpu, reserved mem, pods
        (0, 0, 0), (500, 1024, 1), (3500, 8192, 9), (3600, 4096, 9), (4000, 4096, 8),
        (1000, 30000, 4), (3900, 32000, 20), (2000, 16384, 110), (2000, 16384, 109),
        (250, 28672, 7), (3750, 1024, 12), (1000, 1000, 2),
    ]
    for row, (cpu, mem, pods) in zip(rows, books):
        m._res_cpu[row], m._res_mem[row], m._running[row] = cpu, mem, pods
    for size in SIZES:
        m._note_request(size)
    assert m.r_max == (500, 4096)
    m._cap[: len(rows)] = m._intake(slice(0, len(rows)))
    want_cap = [8, 7, 1, 1, 1, 1, 1, 0, 1, 1, 1, 6]
    assert [m.machine_intake(r) for r in rids] == want_cap
    for size in SIZES:
        ec = model.request_ec(size)
        m._ec_request[ec] = size
        listed = m.get_outgoing_equiv_class_pref_arcs(ec)
        costs, caps = m.ec_to_resource_batch(ec, rids)
        for i, (cpu, mem, pods) in enumerate(books):
            fits = cpu + size[0] <= A[0] and mem + size[1] <= A[1] and pods < P
            assert (rids[i] in listed) == fits, (size, i)
            assert caps[i] == (want_cap[i] if fits else 0), (size, i)
            u = ((cpu + size[0]) * 100 // A[0], (mem + size[1]) * 100 // A[1])
            assert costs[i] == (u[0] + u[1]) // 2 + abs(u[0] - u[1]) // 2
            assert (costs[i], caps[i]) == m.equiv_class_to_resource_node(ec, rids[i])
    # node 4 is full of CPU: no size has an arc there though its bound reads 1
    assert all(rids[4] not in m.get_outgoing_equiv_class_pref_arcs(model.request_ec(s)) for s in SIZES)
    # node 2 has 500m and 24 GiB left: every size fits alone, no two of the largest
    assert all(rids[2] in m.get_outgoing_equiv_class_pref_arcs(model.request_ec(s)) for s in SIZES)
    # k(m) = 0 on nodes 3, 4, 5, 6, 10 (the largest request does not fit) and 7 (no slot)
    assert m.round_books() == (0, 6, sum(want_cap))


# -- the bound sits on the machine's own path to the sink ---------------------------------------


def _bound_below(gm, machine_rid):
    """The capacity of the arcs below the machine's node, summed (an int:
    a failing assert on an Arc would print the graph)."""
    return sum(int(a.cap_upper) for a in gm.resource_to_node[machine_rid].outgoing.values())


def _arc_below(svc, node):
    return _bound_below(svc.scheduler.gm, svc.node_to_machine[node])


def test_the_arc_below_a_machine_carries_the_bound_and_follows_the_books():
    seed_rng(7)
    svc, api = _service(6)
    assert all(_arc_below(svc, f"fake_node_{i}") == P for i in range(6))  # no pod seen yet
    for i in range(20):
        api.submit_pod(_pod(f"a{i}", SIZES[2]))
    svc.run_round(drain(api, 20))
    m = svc.scheduler.cost_model
    # r_max = (500, 1024): 8 a node in the round; after it each node's arc reads its new bound
    assert len(api.bindings()) == 20
    for i in range(6):
        rid = svc.node_to_machine[f"fake_node_{i}"]
        cpu, mem, pods = m.books()[rid]
        k = min((A[0] - cpu) // 500, (A[1] - mem) // 1024, P - pods)
        assert _arc_below(svc, f"fake_node_{i}") == m.machine_intake(rid) == max(k, min(1, P - pods))
        assert pods <= 8
    # a pod larger than any before it moves every bound before the solve that places it
    api.submit_pod(_pod("big", (250, 16384)))
    before = {i: _arc_below(svc, f"fake_node_{i}") for i in range(6)}
    svc.run_round(drain(api, 1))
    assert m.r_max == (500, 16384)
    assert "big" in api.bindings()
    for i in range(6):
        rid = svc.node_to_machine[f"fake_node_{i}"]
        assert _arc_below(svc, f"fake_node_{i}") == m.machine_intake(rid) <= min(before[i], 2)
    assert svc.scheduler.last_timing.res_nodes_visited == 0  # no resource-node turn came back


def test_a_round_of_mixed_sizes_never_overcommits_and_the_backlog_owes_rounds():
    """200 pods of four sizes onto 12 nodes: three rounds, each within
    cap(m) a node; no node ever over its vector; the loop re-solves on its
    own after a round that bound pods and left others waiting."""
    seed_rng(11)
    svc, api = _service(12)
    rng = np.random.default_rng(11)
    size_of = {f"r{i}": SIZES[int(c)] for i, c in enumerate(rng.integers(0, 4, 200))}
    for pod, size in size_of.items():
        api.submit_pod(_pod(pod, size))
    batch = drain(api, 200)
    rounds = 0
    m = svc.scheduler.cost_model
    while True:
        before = dict(api.bindings())
        offered = {svc.node_to_machine[f"fake_node_{i}"]: m.machine_intake(
            svc.node_to_machine[f"fake_node_{i}"]) for i in range(12)}
        svc.run_round(batch, solve=True)
        batch, rounds = [], rounds + 1
        took = {}
        for pod, node in api.bindings().items():
            if pod not in before:
                rid = svc.node_to_machine[node]
                took[rid] = took.get(rid, 0) + 1
        if rounds > 1:  # the first round's bound moved with r_max inside its own graph update
            assert all(n <= offered[rid] for rid, n in took.items())
        for cpu, mem, pods in m.books().values():
            assert cpu <= A[0] and mem <= A[1] and pods <= P
        assert m.books() == books_by_walk(m)
        if not svc.backlog_dirty:
            break
        assert took, "a round that bound nothing must not owe another"
    held = sum(pods for _c, _m, pods in m.books().values())
    assert rounds >= 2 and held == len(api.bindings())
    # whoever waits fits nowhere: every node is out of CPU or memory for its size
    for pod, size in size_of.items():
        if pod not in api.bindings():
            assert all(cpu + size[0] > A[0] or mem + size[1] > A[1] for cpu, mem, _p in m.books().values())
    assert sum(size_of[p][0] for p in api.bindings()) == sum(c for c, _m, _p in m.books().values())


def test_books_by_events_equal_books_by_a_walk_after_binds_completions_and_a_restore(tmp_path):
    seed_rng(13)
    svc, api = _service(10)
    rng = np.random.default_rng(13)
    m = svc.scheduler.cost_model
    k = 0
    for _ in range(8):
        bound = sorted(api.bindings())
        gone = [str(p) for p in rng.permutation(bound)[: int(rng.integers(0, 6))]]
        api.complete_later(gone)
        n = int(rng.integers(3, 15))
        for _i in range(n):
            api.submit_pod(_pod(f"p{k}", SIZES[int(rng.integers(0, 4))]))
            k += 1
        svc.run_round(drain(api, n))
        while svc.backlog_dirty:
            svc.run_round([], solve=True)
        assert m.books() == books_by_walk(m)
    by_node = {svc.machine_to_node[rid]: v for rid, v in m.books().items()}
    assert sum(v[2] for v in by_node.values()) == len(svc.scheduler.task_bindings)
    path = str(tmp_path / "ckpt")
    save_scheduler(svc.scheduler, path)
    restored, _rmap, _jmap, _tmap = restore_scheduler(
        path, cost_model_factory=MODEL_REGISTRY[CostModelType.K8S_REQUESTS],
        backend=make_backend("auto"),
    )
    again = restored.cost_model
    assert again.books() == books_by_walk(again)
    assert sorted(again.books().values()) == sorted(m.books().values())
    assert again.r_max == m.r_max
    # a restore's placements come after its one graph update and before any refresh of
    # the tree: the bounds they moved reach the arcs at the end of the next graph update,
    # so before the next solve
    stale = [rid for rid in again._row if _bound_below(restored.gm, rid) != again.machine_intake(rid)]
    assert stale
    restored.gm.add_or_update_job_nodes([])
    assert all(_bound_below(restored.gm, rid) == again.machine_intake(rid) for rid in again._row)


# -- the service against the plain reference, round by round -----------------------------------


class Stream:
    """A seeded stream of arrivals of the four sizes and of completions on
    125 nodes, with the test's own books, as the reference is given them."""

    def __init__(self, machines, seed, **kw):
        seed_rng(seed)
        self.svc, self.api = _service(machines, **kw)
        self.nodes = [f"fake_node_{i}" for i in range(machines)]
        self.reserved = np.zeros((machines, 2), np.int64)
        self.running = np.zeros(machines, np.int64)
        self.rng = np.random.default_rng(seed)
        self.size_of, self.bound, self.submitted = {}, {}, []
        self.r_max = (0, 0)
        self.k = 0

    def round(self, sizes, completions=0):
        gone = [str(p) for p in self.rng.permutation(sorted(self.bound))[:completions]]
        self.api.complete_later(gone)
        new = []
        for s in sizes:
            pod = f"p{self.k}"
            self.k += 1
            self.size_of[pod] = SIZES[int(s)]
            self.r_max = tuple(max(a, b) for a, b in zip(self.r_max, SIZES[int(s)]))
            new.append(pod)
            self.api.submit_pod(_pod(pod, SIZES[int(s)]))
        self.submitted += new
        waiting = [p for p in self.submitted if p not in self.bound]
        kinds = sorted({self.size_of[p] for p in waiting})
        cap = ref.node_intake(self.reserved, self.running, A, P, self.r_max)
        cost, fits = ref.cost_rows(self.reserved, A, kinds)
        open_cell = fits & (cap > 0)[None, :]
        by_kind = np.bincount([kinds.index(self.size_of[p]) for p in waiting], minlength=len(kinds))
        want = ref.reference_round(cost, open_cell, cap, by_kind)
        self.svc.run_round(drain(self.api, len(new)), solve=True)
        now = self.api.bindings()
        served, took = 0, np.zeros(len(self.nodes), np.int64)
        for pod in waiting:
            if pod in now:
                at = _node_index(now[pod])
                served += int(cost[kinds.index(self.size_of[pod]), at])
                took[at] += 1
                self.bound[pod] = at
                self.reserved[at] += self.size_of[pod]
                self.running[at] += 1
            else:
                served += ref.UNSCHEDULED_COST
        assert (took <= cap).all()
        for pod in gone:
            at = self.bound.pop(pod)
            self.submitted.remove(pod)
            self.reserved[at] -= self.size_of[pod]
            self.running[at] -= 1
        solver, timing = self.svc.scheduler.solver, self.svc.scheduler.last_timing
        if timing.plan_refits:
            # the round re-fitted its slot plan and exported the graph it LEFT: nothing to compare
            return int(timing.objective), served, want, int(timing.objective)
        native = make_backend("native", warm_start=False, fallback=False)
        theirs = int(native.solve(solver.state.problem()).objective)
        return int(timing.objective), served, want, theirs


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_every_rounds_bindings_cost_what_the_reference_says_on_the_dense_rung(seed):
    s = Stream(125, seed)
    rung = s.svc.ladder.primary
    out = [s.round(s.rng.integers(0, 4, 1275))]  # the fill: two rounds of it
    assert s.svc.backlog_dirty
    out.append(s.round([]))
    assert not s.svc.backlog_dirty and len(s.bound) == 1275
    for _ in range(14):
        out.append(s.round(s.rng.integers(0, 4, int(s.rng.integers(1, 12))), int(s.rng.integers(0, 12))))
        assert rung.last_path == "dense" and rung.last_refusal == ""
    for objective, served, want, native in out:
        assert objective == served == want == native
    assert all(o[0] > 0 for o in out)
    assert s.svc.ladder.degradations_total == 0 and s.svc.noop_rounds == 0
    m = s.svc.scheduler.cost_model
    assert m.books() == books_by_walk(m)
    for i, node in enumerate(s.nodes):
        # a pod that completed in the last round's poll is still on the model's books
        cpu, mem, pods = m.books()[s.svc.node_to_machine[node]]
        assert (cpu, mem, pods) == (*s.reserved[i].tolist(), int(s.running[i]))
    requests = dict(s.size_of)
    faults, facts = ref.check_requests_fit(
        s.api.log, requests, s.nodes, A, P, [f"p{i}" for i in range(s.k)],
        [(t1, n) for _t0, t1, n in s.api.polls if n],
    )
    assert faults == []
    assert facts["rounds"] == facts["rounds_compared"] == 16 and facts["rounds_that_left_pods"] == 1
    assert facts["served_cost"] == facts["optimum_cost"] == sum(o[0] for o in out)
    assert facts["peak_cpu"] <= A[0] and facts["peak_mem"] <= A[1] and facts["peak_pods"] <= P


def test_the_scan_csr_rung_answers_rounds_at_the_same_optimum():
    s = Stream(40, 9, backend="jax")
    out = [s.round(s.rng.integers(0, 4, 400))]  # 40 x 8 places for 400 pods: a second round
    assert s.svc.backlog_dirty
    out.append(s.round([]))
    for sizes, gone in [([0, 1, 2, 3, 3], 4), ([2, 2, 3], 6), ([3, 3, 3, 3, 1], 2)]:
        out.append(s.round(sizes, gone))
    for objective, served, want, native in out:
        assert objective == served == want == native and objective > 0
    rung = s.svc.ladder.primary
    assert getattr(rung, "last_path", "csr") == "csr"
    assert rung.price_update_every  # the model says its routes differ in cost


def test_the_round_stamps_its_books_and_the_pricing_has_a_span():
    seed_rng(21)
    span_tracer = SpanTracer(capacity=1 << 16).install()
    try:
        tracer = RoundTracer()
        svc, api = _service(30, tracer=tracer, span_tracer=span_tracer)
        for i in range(300):
            api.submit_pod(_pod(f"r{i}", SIZES[i % 4]))
        svc.run_round(drain(api, 300))
        while svc.backlog_dirty:
            svc.run_round([], solve=True)
        for i in range(3):  # the fill's extra rounds let the purge take the ECs: list them again
            api.submit_pod(_pod(f"o{i}", SIZES[i]))
        svc.run_round(drain(api, 3))
        api.complete_later(["r0", "r5"])
        for i in range(3):
            api.submit_pod(_pod(f"p{i}", SIZES[i]))
        mark = span_tracer.mark()
        svc.run_round(drain(api, 3))
        events = span_tracer.events_since(mark)
    finally:
        span_tracer.uninstall()
    rec = tracer.records[-1]
    fill = tracer.records[0]
    assert fill.books_machines_dirty == 0 and fill.columns_offered == 30 * 8 and fill.machines_gated == 0
    assert 0 < rec.books_machines_dirty <= 30
    assert rec.ec_arcs_repriced <= 3 * rec.books_machines_dirty  # three ECs patch what moved
    assert 0 < rec.columns_offered < 30 * 8 and 0 <= rec.machines_gated <= 30
    assert rec.res_nodes_visited == 0
    priced = [e for e in events if e["name"] == "requests_costs"]
    assert priced and all(e["args"]["machines"] >= 1 for e in priced)
    refresh = [e for e in events if e["name"] == "ec_refresh"]
    assert len(refresh) == 3 and all(e["args"]["swept"] == 0 for e in refresh)


# -- the flags, the API, and what is refused -----------------------------------------------------


def test_the_flag_and_the_fields_ride_the_descriptors():
    assert cli.parse_allocatable("4000:32768") == (4000, 32768)
    for bad in ("4000", "0:1", "a:b", "1:2:3", "-5:10"):
        with pytest.raises(argparse.ArgumentTypeError):
            cli.parse_allocatable(bad)
    svc, api = _service(3, allocatable="2000:8192", slots=20)
    for machine in svc.machine_to_node:
        capacity = svc.resource_map.find(machine).descriptor.capacity
        assert (capacity.cpu_cores, capacity.ram_cap) == (2.0, 8192)
    api.submit_pod(PodEvent(pod_id="x", cpu_request=0.25, memory_request=1024))
    svc.run_round(drain(api, 1))
    td = svc.task_map.find(svc.pod_to_task["x"])
    assert (td.resource_request.cpu_cores, td.resource_request.ram_cap) == (0.25, 1024)
    assert model.request_of(td) == (250, 1024)
    # a node surfaced by a control plane says its own allocatable
    svc.add_node(NodeEvent(node_id="n9", cpu_allocatable_millis=8000, memory_allocatable_mib=65536))
    m = svc.scheduler.cost_model
    row = m._row[svc.node_to_machine["n9"]]
    assert (int(m._alloc_cpu[row]), int(m._alloc_mem[row]), int(m._limit[row])) == (8000, 65536, 20)


def test_what_the_service_refuses():
    parse = cli.build_arg_parser().parse_args
    api = BenchClusterAPI(pod_chan_size=10)
    base = "--fake-machines --num-machines 4 --cost-model k8s_requests --backend auto"
    # fake machines that say nothing of their allocatable: the model refuses the first
    # node by name, where its pods would else wait for ever with no arc and no word
    svc = cli.build_service(parse(base.split()), api)
    with pytest.raises(ValueError, match=r"node fake_node_0: .*--fake-node-allocatable"):
        svc.init_topology(fake_machines=4)
    assert not svc.node_to_machine
    # and so does the command line, with argparse's exit
    with pytest.raises(SystemExit) as refused:
        cli.main(base.split() + ["--one-shot", "--podgen", "1"])
    assert refused.value.code == 2
    # a node a control plane surfaced with CPU and no memory is refused alike
    svc, _api = _service(2)
    with pytest.raises(ValueError, match=r"node n7: .*allocatable \(4000, 0\)"):
        svc.add_node(NodeEvent(node_id="n7", cpu_allocatable_millis=4000))
    assert len(svc.scheduler.cost_model.books()) == 2
    # the one model that reads a machine's allocatable says so; a library cluster in
    # which nothing states a size is priced by slots alone (test_policy_models.py)
    for kind, cls in MODEL_REGISTRY.items():
        assert cls.reads_machine_allocatable == (kind == CostModelType.K8S_REQUESTS)
    # the other models keep every capacity below a machine at its free slots
    for kind, cls in MODEL_REGISTRY.items():
        assert cls.bounds_machine_intake == (kind == CostModelType.K8S_REQUESTS)


def test_a_machine_of_several_cores_shares_one_bound_over_the_arcs_below_it():
    """3 cores x 2 PUs x 2 pods a node: the arcs below the machine carry
    each core's free slots and, together, no more than cap(m)."""
    seed_rng(17)
    args = cli.build_arg_parser().parse_args(
        "--fake-machines --num-machines 3 --cores-per-machine 3 --pus-per-core 2 --max-tasks-per-pu 2 "
        "--fake-node-allocatable 4000:32768 --cost-model k8s_requests --backend auto".split()
    )
    api = BenchClusterAPI(pod_chan_size=1000)
    svc = cli.build_service(args, api)
    api.svc = svc
    svc.init_topology(fake_machines=3, cores_per_machine=3, pus_per_core=2)
    gm, m = svc.scheduler.gm, svc.scheduler.cost_model
    below = lambda rid: [int(a.cap_upper) for a in gm.resource_to_node[rid].outgoing.values()]  # noqa: E731
    assert all(below(rid) == [4, 4, 4] for rid in svc.machine_to_node)  # no pod seen: 12 slots
    for i in range(30):
        api.submit_pod(_pod(f"a{i}", SIZES[2]))
    svc.run_round(drain(api, 30))
    rung = svc.ladder.primary
    assert rung.last_path == "dense" and len(api.bindings()) == 24  # 8 a node by CPU, of 12 slots
    for rid in svc.machine_to_node:
        assert m.books()[rid] == (4000, 8192, 8) and m.machine_intake(rid) == 1
        assert sum(below(rid)) == 1 and max(below(rid)) == 1
    assert not svc.backlog_dirty or svc.run_round([], solve=True) == 0
    api.complete_later(["a0", "a1", "a2"])
    api.submit_pod(_pod("b", SIZES[0]))
    svc.run_round(drain(api, 1))
    svc.run_round([], solve=True)  # the freed requests reach the books a round later
    assert len(api.bindings()) >= 25
    for rid in svc.machine_to_node:
        cpu, mem, pods = m.books()[rid]
        assert cpu <= A[0] and sum(below(rid)) == m.machine_intake(rid) <= 12 - pods
    assert m.books() == books_by_walk(m)
