"""`array_round`: every round is answered by the device round, and the table
the service keeps on the chip is the cluster the record describes.

Stands where `answer` and `resident` stand on the graph path. As far as a run
can show it: 0 rounds fetched with `converged` false, 0 admissions the device
made short of their count or put off for want of a row, 0 cost overflows, 0
programs compiled inside the window (every shape is fixed when the table is
built), 0 completions refused; and after the closing round the device's table
(`DeviceBulkCluster.fetch_state`) equals books rebuilt from the record alone:
every pod the record says is bound (a Binding and no completion since) holds a
live row whose PU is on the node of its Binding, no other row is placed,
`pu_running` equals a recount of the `pu` column, no PU holds more than its
slots. Which row is a pod's is the service's mirror to say (`row_of`), and the
comparison holds the mirror too: a row it names must be live on the device.
The numbers compared go to `facts["array_round"]`.
"""

from typing import Dict, List

import numpy as np

COUNTERS = (
    ("unconverged_rounds", "rounds fetched with `converged` false"),
    ("admissions_short", "admissions the device made short of their count"),
    ("admissions_deferred", "batches the table had no row for"),
    ("cost_overflows", "rounds whose scaled costs overflowed"),
)


def table_faults(state: dict, bound: Dict[str, str], row_of: Dict[str, int], nodes: List[str],
                 pus_per_machine: int, slots_per_pu: int, facts: dict) -> List[str]:
    """The fetched table against the books: `bound` is pod -> node by the
    record, `row_of` the service's pod -> row."""
    live, pu = np.asarray(state["live"]), np.asarray(state["pu"])
    running = np.asarray(state["pu_running"])
    placed = live & (pu >= 0)
    recount = np.bincount(pu[placed], minlength=len(running))
    facts.update(
        rows=int(live.size), rows_live=int(live.sum()), rows_placed=int(placed.sum()),
        pods_bound_by_the_record=len(bound), pu_running_differs=int((recount != running).sum()),
        pu_peak=int(running.max(initial=0)), pu_slots=slots_per_pu,
    )
    faults = []
    index = {node: i for i, node in enumerate(nodes)}
    astray = 0
    first = None
    for pod, node in bound.items():
        row = row_of.get(pod)
        if row is None or not placed[row] or pu[row] // pus_per_machine != index.get(node):
            astray += 1
            first = first or (pod, node, row)
    facts["pods_not_where_the_record_has_them"] = astray
    if astray:
        pod, node, row = first
        faults.append(
            f"{astray} pods the record has bound are not on a PU of their node in the device's "
            f"table (first: {pod} on {node}, row {row})"
        )
    if int(placed.sum()) != len(bound):
        faults.append(
            f"the device's table has {int(placed.sum())} rows placed, the record {len(bound)} pods bound"
        )
    if (recount != running).any():
        i = int(np.flatnonzero(recount != running)[0])
        faults.append(
            f"pu_running differs from a recount of the pu column on {int((recount != running).sum())} "
            f"PUs (first: PU {i} says {int(running[i])}, holds {int(recount[i])})"
        )
    if running.max(initial=0) > slots_per_pu:
        faults.append(f"a PU holds {int(running.max())} pods, it has {slots_per_pu} slots")
    return faults


def check(ctx) -> List[str]:
    svc = ctx.svc
    cluster = getattr(svc, "cluster", None)
    if cluster is None:
        return ["the service keeps no table on the device (--array-round did not take)"]
    facts = ctx.facts["array_round"] = {"rounds": svc.rounds, "limit": 0}
    faults = []
    for name, what in COUNTERS:
        facts[name] = int(getattr(svc, name))
        if facts[name]:
            faults.append(f"{facts[name]} {what}")
    facts["compiles_in_window"] = ctx.compiles_in_window
    if ctx.compiles_in_window:
        faults.append(f"{ctx.compiles_in_window} programs compiled inside the window")
    facts["completions_refused"] = ctx.completions_refused
    if ctx.completions_refused:
        faults.append(f"{ctx.completions_refused} completions of pods that were not bound")
    bound: Dict[str, str] = {}
    for kind, pod, node, _t in ctx.log:
        if kind == "bind":
            bound[pod] = node
        elif kind == "done":
            bound.pop(pod, None)
    return faults + table_faults(
        cluster.fetch_state(), bound, svc.row_of, svc.nodes, cluster.P, cluster.S, facts
    )
