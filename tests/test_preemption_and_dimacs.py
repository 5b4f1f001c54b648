"""Coverage for reference-behavior axes the suite didn't yet pin down:

- the preemption flag's two effects (pin-vs-keep task arcs,
  graph_manager.go:675-720 vs :855-888; the capacity-to-parent rule,
  :662-667) and preemption deltas (:297-339);
- task migration deltas (MIGRATE when bound elsewhere, :253-295);
- the DIMACS wire format (doc.go:3-22; solver-side node taxonomy
  export.go:53-70; incremental lines + "c EOI" framing export.go:28-37);
- EC purge and job completion (graph_manager.go:341-357).
"""

import io

from ksched_tpu.data import DeltaType
from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.graph.changes import ChangeManager, ChangeType
from ksched_tpu.graph.dimacs import export, export_incremental, parse_graph
from ksched_tpu.graph.flowgraph import ArcType, NodeType


# ---------------------------------------------------------------------------
# preemption semantics
# ---------------------------------------------------------------------------


def _bound_task_nodes(sched):
    return [
        sched.gm.task_to_node[tid]
        for tid in sched.task_bindings
    ]


def test_preemption_off_pins_scheduled_tasks():
    """Without preemption a placed task keeps exactly one outgoing arc:
    the running arc, lower bound 1 (graph_manager.go:675-720)."""
    sched, rmap, jmap, tmap, root = build_cluster(num_machines=2, pus_per_core=2)
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    assert n == 3
    for node in _bound_task_nodes(sched):
        arcs = list(node.outgoing.values())
        assert len(arcs) == 1
        assert arcs[0].type == ArcType.RUNNING
        assert arcs[0].cap_lower == 1


def test_preemption_on_keeps_unscheduled_escape_arc():
    """With preemption every placed task keeps its unsched escape arc
    (priced as preemption cost) next to the running arc
    (graph_manager.go:855-888, :1164-1181)."""
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=2, pus_per_core=2, preemption=True
    )
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    assert n == 3
    for node in _bound_task_nodes(sched):
        arcs = list(node.outgoing.values())
        kinds = sorted(a.type for a in arcs)
        assert ArcType.RUNNING in kinds
        unsched_arcs = [
            a for a in arcs if a.dst_node.type == NodeType.JOB_AGGREGATOR
        ]
        assert len(unsched_arcs) == 1
        assert unsched_arcs[0].cap_lower == 0  # escape stays optional


def test_capacity_rule_flips_with_preemption():
    """capacityFromResNodeToParent: slots-below minus running-below when
    preemption is off, slots-below when on (graph_manager.go:662-667)."""
    results = {}
    for flag in (False, True):
        sched, rmap, jmap, tmap, root = build_cluster(
            num_machines=1, pus_per_core=2, preemption=flag
        )
        add_job(sched, jmap, tmap, num_tasks=2)
        sched.schedule_all_jobs()
        # running-task stats reconcile on the NEXT round's topology
        # refresh (reference-parity lag; flowscheduler/scheduler.go:375)
        sched.schedule_all_jobs()
        machine_node = next(
            node
            for node in sched.gm.resource_to_node.values()
            if node.type == NodeType.MACHINE
        )
        parent = sched.gm.node_to_parent_node[machine_node.id]
        arc = sched.gm.cm.graph.get_arc(parent, machine_node)
        results[flag] = arc.cap_upper
    assert results[False] == 0  # both slots occupied, not reclaimable
    assert results[True] == 2  # preemption can reclaim them


def test_preempt_delta_emitted_for_vanished_mapping():
    """A running task absent from the new solver mapping becomes a
    PREEMPT delta and its slot frees (graph_manager.go:297-339)."""
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=1, pus_per_core=1, preemption=True
    )
    add_job(sched, jmap, tmap, num_tasks=1)
    n, _ = sched.schedule_all_jobs()
    assert n == 1
    (tid,) = list(sched.task_bindings)
    deltas = sched.gm.scheduling_deltas_for_preempted_tasks({}, rmap)
    assert [d.type for d in deltas] == [DeltaType.PREEMPT]
    assert deltas[0].task_id == tid


def test_migration_rebinds_task():
    """MIGRATE: binding moves, old slot frees, new slot fills
    (flowscheduler/scheduler.go:248-270)."""
    sched, rmap, jmap, tmap, root = build_cluster(num_machines=2, pus_per_core=1)
    add_job(sched, jmap, tmap, num_tasks=1)
    n, _ = sched.schedule_all_jobs()
    assert n == 1
    (tid,) = list(sched.task_bindings)
    old_rid = sched.task_bindings[tid]
    # the other machine's PU
    other = next(
        rid
        for rid, node in sched.gm.resource_to_node.items()
        if node.type == NodeType.PU and rid != old_rid
    )
    td = tmap.find(tid)
    rs = rmap.find(other)
    sched.handle_task_migration(td, rs.descriptor)
    assert sched.task_bindings[tid] == other
    assert tid in rs.descriptor.current_running_tasks


# ---------------------------------------------------------------------------
# DIMACS wire format (golden)
# ---------------------------------------------------------------------------


def _tiny_graph():
    cm = ChangeManager()
    sink = cm.add_node(NodeType.SINK, 0, ChangeType.ADD_SINK_NODE, "SINK")
    task = cm.add_node(NodeType.UNSCHEDULED_TASK, 1, ChangeType.ADD_TASK_NODE, "t")
    sink.excess = -1  # the graph manager's supply bookkeeping
    pu = cm.add_node(NodeType.PU, 0, ChangeType.ADD_RESOURCE_NODE, "pu")
    cm.add_arc(task, pu, 0, 1, 42, ArcType.OTHER, ChangeType.ADD_ARC_TASK_TO_RES, "t->pu")
    cm.add_arc(pu, sink, 0, 1, 0, ArcType.OTHER, ChangeType.ADD_ARC_RES_TO_SINK, "pu->sink")
    return cm, sink, task, pu


def test_dimacs_full_export_golden():
    cm, sink, task, pu = _tiny_graph()
    buf = io.StringIO()
    export(cm.graph, buf)
    text = buf.getvalue()
    lines = text.strip().splitlines()
    assert lines[-1] == "c EOI"
    header, nodes, arcs = parse_graph(lines)
    assert header == (3, 2)
    # solver-side taxonomy: task=1, PU=2, sink=3 (export.go:53-70)
    by_id = {n[0]: n for n in nodes}
    assert by_id[task.id][1:] == (1, 1)   # excess 1, type task
    assert by_id[pu.id][1:] == (0, 2)     # type PU
    assert by_id[sink.id][1:] == (-1, 3)  # absorbed supply, type sink
    assert (task.id, pu.id, 0, 1, 42) in arcs
    assert (pu.id, sink.id, 0, 1, 0) in arcs


def test_dimacs_incremental_export_golden():
    cm, sink, task, pu = _tiny_graph()
    cm.reset_changes()
    arc = cm.graph.get_arc(task, pu)
    cm.change_arc_cost(arc, 7, ChangeType.CHG_ARC_TASK_TO_RES, "reprice")
    cm.delete_arc(
        cm.graph.get_arc(pu, sink), ChangeType.DEL_ARC_BETWEEN_RES, "drop"
    )
    buf = io.StringIO()
    export_incremental(cm.get_graph_changes(), buf)
    lines = buf.getvalue().strip().splitlines()
    assert lines[-1] == "c EOI"
    body = [l for l in lines if not l.startswith("c")]
    # reprice first: update-arc line carries old cost last
    # (update_arc_change.go:46-54); delete = capacity-to-zero update
    # (graph_change_manager.go:184-193).
    assert body[0].startswith(f"x {task.id} {pu.id} 0 1 7")
    assert body[0].endswith("42")
    assert any(
        l.startswith(f"x {pu.id} {sink.id} 0 0 0") for l in body[1:]
    )


# ---------------------------------------------------------------------------
# EC purge + job completion
# ---------------------------------------------------------------------------


def test_purge_unconnected_equiv_class_nodes():
    """The per-round purge (beyond-parity: the reference declares the
    API but never calls it, graph_manager.go:347-357; upstream
    Firmament purges in its loop) removes the cluster-agg EC once every
    task is pinned; a waiting task keeps it alive."""
    sched, rmap, jmap, tmap, root = build_cluster(num_machines=1, pus_per_core=1)
    add_job(sched, jmap, tmap, num_tasks=2)  # 1 slot: one pins, one waits
    sched.schedule_all_jobs()
    # the waiting task's EC arc keeps the aggregator connected
    assert sched.gm.task_ec_to_node
    (tid,) = list(sched.task_bindings)
    sched.handle_task_completion(tmap.find(tid))
    sched.schedule_all_jobs()  # sees pre-completion stats (1-round lag)
    sched.schedule_all_jobs()  # places + pins the waiter
    assert len(sched.task_bindings) == 1
    # everyone pinned, but the waiter pointed at the EC this round; an
    # idle purge marks it (debounce) and a second observation removes it
    sched.gm.purge_unconnected_equiv_class_nodes()
    assert sched.gm.task_ec_to_node
    sched.gm.purge_unconnected_equiv_class_nodes()
    assert not sched.gm.task_ec_to_node


def test_job_completion_removes_unsched_aggregator():
    sched, rmap, jmap, tmap, root = build_cluster(num_machines=1, pus_per_core=2)
    jid = add_job(sched, jmap, tmap, num_tasks=2)
    n, _ = sched.schedule_all_jobs()
    assert n == 2
    for tid in list(sched.task_bindings):
        sched.handle_task_completion(tmap.find(tid))
    sched.handle_job_completion(jid)
    assert not sched.gm.job_unsched_to_node
    # supply conservation after full teardown
    assert sched.gm.sink_node.excess == -len(sched.gm.task_to_node) == 0


# ---------------------------------------------------------------------------
# solver flow-response codec (the loop back from an external solver)
# ---------------------------------------------------------------------------


def test_flow_response_round_trip_matches_in_process_decode():
    """export_flow -> parse_flow -> flow_on_arcs -> flow_to_mapping must
    reproduce the in-process decode exactly, closing the DIMACS loop so
    an external solver (e.g. real Flowlessly) can serve as a parity
    oracle (response format: placement/solver.go:134-179)."""
    from ksched_tpu.graph.dimacs import export_flow, flow_on_arcs, parse_flow
    from ksched_tpu.solver.decode import flow_to_mapping

    sched, rmap, jmap, tmap, root = build_cluster(num_machines=2, pus_per_core=2)
    add_job(sched, jmap, tmap, num_tasks=3)
    n, _ = sched.schedule_all_jobs()
    assert n == 3

    ps = sched.solver
    problem = ps.state.problem()
    result = ps.backend.solve(problem)
    tf = result.total_flow(problem)
    assert tf.sum() > 0
    task_ids = [node.id for node in sched.gm.task_to_node.values()]
    direct = flow_to_mapping(
        problem, tf, sched.gm.leaf_node_ids, sched.gm.sink_node.id, task_ids
    )
    assert direct  # placements exist

    buf = io.StringIO()
    export_flow(problem.src, problem.dst, tf, buf)
    text = buf.getvalue()
    assert text.endswith("c EOI\n")
    # prepend the solver's timing chatter the reference skips
    # (solver.go:169-170) and trailing garbage the EOI framing must hide
    wire = "c ALGORITHM successive_shortest_path 12ms\n" + text + "f 9 9 9\n"
    flows = parse_flow(io.StringIO(wire))
    assert (9, 9) not in flows  # post-EOI lines belong to the next round
    tf2 = flow_on_arcs(flows, problem.src, problem.dst)
    assert (tf2 == tf).all()
    external = flow_to_mapping(
        problem, tf2, sched.gm.leaf_node_ids, sched.gm.sink_node.id, task_ids
    )
    assert external == direct


def test_parse_flow_last_pair_wins_and_rejects_junk():
    from ksched_tpu.graph.dimacs import parse_flow

    flows = parse_flow(io.StringIO("f 1 2 3\nf 1 2 5\nc EOI\n"))
    assert flows == {(1, 2): 5}
    try:
        parse_flow(io.StringIO("q nonsense\n"))
    except ValueError:
        pass
    else:
        raise AssertionError("junk line must raise")
    try:
        parse_flow(io.StringIO("f 1 2 3\n"))  # dead solver / cut pipe
    except ValueError:
        pass
    else:
        raise AssertionError("truncated response (no c EOI) must raise")
