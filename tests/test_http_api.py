"""End-to-end over real sockets: FakeAPIServer <- HTTPClusterAPI <-
SchedulerService. The informer-shaped watch loops must surface pods and
nodes, the scheduler must place them, and the Binding subresource POSTs
must land server-side (reference: k8s/k8sclient/client.go informers +
AssignBinding, run against a bare kube-apiserver per README.md:55-70)."""

import time

import pytest

from ksched_tpu.cli import SchedulerService
from ksched_tpu.cluster import Binding, FakeAPIServer, HTTPClusterAPI


@pytest.fixture
def server():
    s = FakeAPIServer().start()
    yield s
    s.stop()


def test_watch_surfaces_pods_and_nodes(server):
    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        server.add_node("node_a", cores=2, pus_per_core=2)
        server.add_node("node_skip", unschedulable=True)
        server.create_pods(3)
        nodes = api.get_node_batch(timeout_s=0.3)
        assert [n.node_id for n in nodes] == ["node_a"]  # unschedulable skipped
        assert nodes[0].num_cores == 2 and nodes[0].pus_per_core == 2
        pods = api.get_pod_batch(timeout_s=0.3)
        assert sorted(p.pod_id for p in pods) == ["pod_0", "pod_1", "pod_2"]
    finally:
        api.close()


def test_binding_post_lands_and_pod_leaves_pending(server):
    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        server.create_pods(2)
        api.get_pod_batch(timeout_s=0.3)
        api.assign_bindings([Binding("pod_0", "node_x")])
        assert server.bindings() == {"pod_0": "node_x"}
        assert server.pending_pods() == 1
    finally:
        api.close()


def test_redelivered_pod_does_not_duplicate_task(server):
    """A pod re-surfaced by the watch (e.g. after a failed binding POST)
    must not create a second task — and its binding must be re-emitted
    on the next round."""
    from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI

    api = SyntheticClusterAPI()
    svc = SchedulerService(api, max_tasks_per_pu=1)
    svc.init_topology(fake_machines=2)
    svc.run_once([PodEvent(pod_id="pod_x")])
    assert len(svc.pod_to_task) == 1
    tid = svc.pod_to_task["pod_x"]
    assert tid in svc.old_bindings
    # re-delivery: same pod again
    emitted = svc.run_once([PodEvent(pod_id="pod_x")])
    assert len(svc.pod_to_task) == 1  # no duplicate task
    assert svc.pod_to_task["pod_x"] == tid
    assert emitted == 1  # the binding was re-posted


def test_cli_one_shot_against_http_server(server):
    """The full binary surface over HTTP: ksched-tpu --api-server URL
    --podgen N --one-shot — pods created via the API server (podgen
    parity), scheduled, bindings POSTed back."""
    from ksched_tpu.cli import main

    for i in range(2):
        server.add_node(f"node_{i}", cores=1, pus_per_core=2)
    rc = main([
        "--api-server", server.base_url,
        "--podgen", "4", "--one-shot",
        "--node-batch-timeout", "0.4",
        "--pod-batch-timeout", "0.3",
        "--max-tasks-per-pu", "1",
    ])
    assert rc == 0
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline and len(server.bindings()) < 4:
        time.sleep(0.05)
    assert len(server.bindings()) == 4
    assert server.pending_pods() == 0


def test_scheduler_service_end_to_end_over_http(server):
    for i in range(3):
        server.add_node(f"node_{i}", cores=1, pus_per_core=2)
    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        svc = SchedulerService(api, max_tasks_per_pu=1)
        svc.init_topology(node_batch_timeout_s=0.4)
        server.create_pods(5)  # podgen side-door
        svc.run(pod_batch_timeout_s=0.3, max_rounds=1)
        # placements arrived at the control plane as Binding POSTs
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and len(server.bindings()) < 5:
            time.sleep(0.05)
        got = server.bindings()
        assert len(got) == 5
        assert all(v.startswith("node_") for v in got.values())
        assert server.pending_pods() == 0
    finally:
        api.close()


def test_seen_pods_reconciled_and_recreated_pod_resurfaces(server):
    """_seen_pods must track the pending listing (bounded, lock-guarded):
    a bound pod is forgotten, and a pod later re-created with the same
    name re-enters a batch instead of being filtered forever."""
    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        server.create_pods(1)  # pod_0
        batch = api.get_pod_batch(timeout_s=0.5)
        assert [p.pod_id for p in batch] == ["pod_0"]
        api.assign_bindings([Binding("pod_0", "node_x")])
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and api._seen_pods:
            time.sleep(0.02)
        assert not api._seen_pods  # reconciled away once off the listing
        # simulate delete + re-create with the same name: the binding
        # disappears server-side and the pod is pending again
        with server._state.lock:
            server._state.bindings.pop("pod_0")
        batch = api.get_pod_batch(timeout_s=0.5)
        assert [p.pod_id for p in batch] == ["pod_0"]  # re-surfaced
    finally:
        api.close()


def test_requests_and_allocatable_ride_the_http_adapter_into_the_requests_model(server):
    """`cpu_request` and `memory_request` of a pod's spec and `cpu_millis` /
    `memory_mib` of a node's capacity reach the descriptors, and
    `--cost-model k8s_requests` fits the one into the other: three 4 GiB
    pods on two nodes of 8 GiB bind two a node at most."""
    from ksched_tpu.costmodels import CostModelType

    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        for name in ("node_a", "node_b"):
            server.add_node(name, cpu_millis=2000, memory_mib=8192)
        server.create_pods(3, cpu_request=0.25, memory_request=4096)
        nodes = api.get_node_batch(timeout_s=0.3)
        assert [(n.cpu_allocatable_millis, n.memory_allocatable_mib) for n in nodes] == [(2000, 8192)] * 2
        pods = api.get_pod_batch(timeout_s=0.3)
        assert [(p.cpu_request, p.memory_request) for p in pods] == [(0.25, 4096)] * 3
        svc = SchedulerService(api, max_tasks_per_pu=10, cost_model=CostModelType.K8S_REQUESTS)
        for node in nodes:
            svc.add_node(node)
        svc.run_once(pods)
        while svc.backlog_dirty:
            svc.run_round([], solve=True)
        books = sorted(svc.scheduler.cost_model.books().values())
        assert books == [(250, 4096, 1), (500, 8192, 2)]
        td = svc.task_map.find(svc.pod_to_task["pod_0"])
        assert (td.resource_request.cpu_cores, td.resource_request.ram_cap) == (0.25, 4096)
        # a node whose control plane says nothing of its allocatable surfaces with zeros,
        # and the service refuses it by name where the model fits requests into them: it
        # is not taken in with no arc, its pods waiting at 500 for ever
        server.add_node("node_c")
        (bare,) = api.get_node_batch(timeout_s=0.3)
        assert (bare.cpu_allocatable_millis, bare.memory_allocatable_mib) == (0, 0)
        with pytest.raises(ValueError, match=r"node node_c: .*allocatable \(0, 0\)"):
            svc.add_node(bare)
        assert "node_c" not in svc.node_to_machine
        assert len(svc.scheduler.cost_model.books()) == 2
    finally:
        api.close()


def test_a_model_that_reads_no_allocatable_takes_a_node_that_says_none(server):
    api = HTTPClusterAPI(server.base_url, poll_interval_s=0.05)
    try:
        server.add_node("node_c")
        (bare,) = api.get_node_batch(timeout_s=0.3)
        svc = SchedulerService(api, max_tasks_per_pu=10)
        svc.add_node(bare)
        assert "node_c" in svc.node_to_machine
    finally:
        api.close()
