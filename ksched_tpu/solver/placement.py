"""The placement solver driver: graph manager → backend → task mapping.

Reference: scheduling/flow/placement/solver.go:60-123. Round 1 exports
the full graph; round N first refreshes task→unsched costs
(UpdateAllCostsToUnscheduledAggs) and then ships only the journaled
changes. In the reference the export is DIMACS text to a daemon
subprocess; here it is a scatter into the flat device arrays
(DeviceGraphState), and the backend is called in-process.
"""

from __future__ import annotations

import warnings
from typing import Dict, Optional

from ..graph.device_export import DeviceGraphState, DeviceResidentState
from ..graph.graph_manager import GraphManager, TaskMapping
from ..obs.devprof import get_profiler
from ..obs.spans import span
from .base import FlowSolver
from .decode import flow_to_mapping


class PlacementSolver:
    """``device_resident=True`` keeps the folded problem arrays live on
    device between rounds (graph/device_export.DeviceResidentState):
    after the first full upload, each round ships only the packed delta
    records — one jit'd scatter applies them — and device-aware
    backends consume the handle without re-uploading anything. Host
    consumers (decode, cpu_ref/native ladder rungs) are unaffected: the
    handle still carries the host arrays."""

    def __init__(
        self,
        gm: GraphManager,
        backend: FlowSolver,
        incremental: bool = True,
        device_resident: bool = False,
    ) -> None:
        self.gm = gm
        self.backend = backend
        self.incremental = incremental
        self.device_resident = device_resident
        self.state = DeviceGraphState()
        self.resident = DeviceResidentState(self.state) if device_resident else None
        self._started = False
        self.last_result = None
        #: the last decode: unpinned task nodes it was handed, and
        #: pinned tasks it left alone (the dispatch's snapshot)
        self.decode_tasks = 0
        self.decode_pinned_skipped = 0
        #: records of the change journal the last export applied to the
        #: flat arrays (0 when it built them whole)
        self.journal_changes = 0
        # ---- device-state integrity (runtime/integrity.py) -----------
        #: audit cadence in exports (0 = off); the service sets it from
        #: --audit-every. On due rounds the post-refresh mirror is
        #: fingerprinted against the host journal truth and divergence
        #: is repaired through the escalating ladder before the solve.
        self.audit_every = 0
        self.auditor = None
        self._export_count = 0
        #: array names that diverged on the LAST audited export (the
        #: service's flight-dump trigger), None when clean
        self.last_divergence = None
        #: cumulative integrity accounting for this solver's lifetime
        #: (divergences, repair_<rung>) — soaks sum it across restores
        from collections import Counter as _Counter

        self.integrity_counts = _Counter()

    @property
    def collapse_shape(self):
        """(tasks grouped, rows, padded columns) of the dense problem the
        configured rung's last solve made of the graph; zeros where that
        rung has no dense collapse or the collapse refused the graph
        (AutoSolver.last_collapse_shape)."""
        rung = getattr(self.backend, "primary", self.backend)
        return getattr(rung, "last_collapse_shape", (0, 0, 0))

    def solve_async(self):
        """Phase 1 of a pipelined round: export the journal, snapshot
        the problem, and DISPATCH the backend solve, returning before
        it completes. The problem arrays are a snapshot, so the caller
        may keep journaling next-round graph mutations while the solve
        is in flight — the overlap the reference's daemon-mode
        subprocess provides across its pipe boundary
        (placement/solver.go:60-90). Backends without solve_async run
        synchronously here (the token then carries the result)."""
        gm = self.gm
        full = not self._started or not self.incremental
        changes = None
        with span("graph_export", kind="full_build" if full else "delta") as export_span:
            if full:
                self._started = True
                with span("journal_apply", kind="full_build", changes=0):
                    self.state.full_build(gm.cm.graph)
                    gm.cm.reset_changes()
                self.backend.reset()
            else:
                with span("journal_collect") as sp:
                    gm.update_all_costs_to_unscheduled_aggs()
                    changes = gm.cm.get_optimized_graph_changes()
                    sp.set("changes", len(changes))
                with span("journal_apply", kind="delta", changes=len(changes)):
                    self.state.apply_changes(changes)
                    gm.cm.reset_changes()
            self.journal_changes = len(changes) if changes is not None else 0
            # Sink excess is maintained outside the journal (reference:
            # graph_manager.go:636-640); sync it before each solve.
            self.state.set_excess(gm.sink_node.id, gm.sink_node.excess)
            export_span.set("supply_prerouted", self.state.supply_prerouted)
            if self.resident is not None:
                if full:
                    # a slot-stable rung enables the plan at its first
                    # solve, a round too late for the mirror: enable it
                    # now, so that the plan goes up, and its scatter
                    # shapes compile, in the round that allocates the
                    # rest
                    from ..runtime.checkpoint import find_jax_solver

                    jaxs = find_jax_solver(self.backend)
                    if jaxs is not None and jaxs.slot_stable:
                        self.state.plan.ensure_built()
                # pack + scatter this round's delta into the persistent
                # device buffers (delta_pack / delta_upload child spans)
                problem = self.resident.refresh()
                problem = self._integrity_gate(problem)
            else:
                with span("problem_snapshot"):
                    problem = self.state.problem()
            self._account_export(problem, full, changes)
        # What the decode works on, captured NOW: it must map the
        # snapshot's tasks, not tasks added while the solve is in
        # flight. Pinned tasks are known without it (their mask drops
        # their arcs), so the set holds the unpinned task nodes only.
        with span(
            "decode_set", tasks=len(gm.unpinned_task_nodes), nodes=int(problem.num_nodes)
        ):
            decode_set = (
                set(gm.unpinned_task_nodes),
                gm.pinned_mask(problem.num_nodes),
                gm.num_pinned,
            )
        get_profiler().solve_starting()
        try:
            if hasattr(self.backend, "solve_async"):
                pending = self.backend.solve_async(problem)
                return (problem, decode_set, pending, True)
            return (problem, decode_set, self.backend.solve_traced(problem), False)
        except BaseException:
            get_profiler().solve_failed()  # stop an Nth-solve capture
            raise

    def _account_export(self, problem, full: bool, changes) -> None:
        """Byte accounting of the export, inside `graph_export`: in
        device-resident mode the EXACT nbytes that crossed the boundary
        (packed records, or the rebuild upload); otherwise from the
        journal just applied, record by record — NOT from the per-round
        ChangeStats, which miss the previous round's post-solve
        mutations (journaled after the round-start stats reset but
        shipped in this scatter)."""
        with span(
            "export_accounting", changes=len(changes) if changes is not None else 0
        ):
            if self.resident is not None:
                get_profiler().note_export(
                    problem,
                    full=self.resident.last_upload_kind == "full_build",
                    exact_bytes=self.resident.last_upload_bytes,
                )
            else:
                get_profiler().note_export(problem, full=full, changes=changes)

    @property
    def solve_bytes(self):
        """(host-to-device, device-to-host) bytes of the last solve's
        own transfers where the configured rung is the scan-CSR solver
        (JaxSolver.last_h2d_bytes / last_d2h_bytes); zeros on any other
        rung. The device-resident export's bytes are not in it
        (`resident.last_upload_bytes`)."""
        from ..runtime.checkpoint import find_jax_solver

        jaxs = find_jax_solver(self.backend)
        if jaxs is None:
            return 0, 0
        return jaxs.last_h2d_bytes, jaxs.last_d2h_bytes

    def _integrity_gate(self, problem):
        """The post-refresh integrity seam: apply any injected device
        corruption (the chaos seam — the injector rides the ladder
        backend, so corruption is drawn per cell and contained exactly
        like solver faults), then on audit-due rounds fingerprint the
        mirror against the host truth and run the divergence response
        ladder: re-scatter dirty span -> full re-upload -> plan
        _rebuild -> full_build (here) -> the degradation ladder's NOOP
        backstop. Repairs restore the exact host values, so a repaired
        round's placements are bit-identical to a clean-state solve."""
        inj = getattr(self.backend, "injector", None)
        if inj is not None and hasattr(inj, "device_corruption"):
            available = set(("excess", "src", "dst", "cap", "cost"))
            if self.resident.d_p_sign is not None:
                available |= {"p_arc", "p_sign", "p_src", "p_dst"}
            spec = inj.device_corruption(
                self.state.n_cap, self.state.m_cap, available=available
            )
            if spec is not None:
                from ..runtime.integrity import apply_device_corruption

                apply_device_corruption(self.resident, spec)
                self.resident.rebind(problem)
        self.last_divergence = None
        self._export_count += 1
        if not self.audit_every or (self._export_count - 1) % self.audit_every:
            return problem
        from ..runtime.integrity import IntegrityError, StateAuditor

        if self.auditor is None or self.auditor.resident is not self.resident:
            self.auditor = StateAuditor(self.resident)
        # the solver's carried warm flow is solver-owned device state:
        # fingerprint it against the solver's host copy alongside the
        # mirror (a diverged warm carry escalates straight to
        # full_build below, whose backend.reset() drops it)
        from ..runtime.checkpoint import find_jax_solver

        jaxs = find_jax_solver(self.backend)
        warm_flow = warm_expected = None
        if jaxs is not None and jaxs._prev_dev is not None and jaxs._prev is not None:
            warm_flow, warm_expected = jaxs._prev_dev, jaxs._prev
        with span("state_audit"):
            diverged = self.auditor.audit(warm_flow, warm_expected)
        if not diverged:
            return problem
        self.last_divergence = list(diverged)
        self.integrity_counts["divergences"] += 1
        try:
            with span("state_repair", arrays=len(diverged)):
                rung = self.auditor.repair(diverged)
            self.integrity_counts[f"repair_{rung}"] += 1
            self.resident.rebind(problem)
            return problem
        except IntegrityError:
            pass
        # ladder exhausted on the mirror: rebuild the device state from
        # the host graph wholesale — the last repair rung before the
        # degradation ladder's NOOP round. full_build reassigns the
        # slot table, so warm solver state is dropped with it.
        self.integrity_counts["repair_full_build"] += 1
        with span("state_repair", kind="full_build"):
            gm = self.gm
            self.state.full_build(gm.cm.graph)
            gm.cm.reset_changes()
            self.backend.reset()
            self.state.set_excess(gm.sink_node.id, gm.sink_node.excess)
            problem = self.resident.refresh()
        if self.auditor is not None:
            self.auditor._m_repairs.labels(rung="full_build").inc()
        return problem

    def complete(self, token) -> TaskMapping:
        """Phase 2: synchronize the solve and decode the task mapping.
        `backend_solve` is the WAIT (and the read-back, the unpacking,
        the telemetry's publication: its children): the rung starts at
        `solve_prepare`, in solve_async, and the device has been
        running since `solve_launch`."""
        problem, (task_node_ids, pinned, num_pinned), pending, is_async = token
        if is_async:
            try:
                with span("backend_solve", backend=type(self.backend).__name__) as sp:
                    result = self.backend.complete(pending)
                    sparse = getattr(self.backend, "last_sparse_supersteps", None)
                    if sparse is not None:
                        sp.set("supersteps_sparse", int(sparse))
                    # async dispatches bypass solve_traced; publish the
                    # solver-interior telemetry here instead (registry
                    # histograms + per-superstep child spans + stall
                    # detection — obs/soltel.py)
                    tel = getattr(self.backend, "last_telemetry", None)
                    if tel is not None:
                        from ..obs import soltel

                        soltel.publish(tel, sp)
            except BaseException:
                get_profiler().solve_failed()  # stop an Nth-solve capture
                raise
        else:
            result = pending
        self.last_result = result
        get_profiler().note_solve(self.backend, problem, result)
        gm = self.gm
        self.decode_tasks = len(task_node_ids)
        self.decode_pinned_skipped = num_pinned
        with span(
            "decode", decode_tasks=len(task_node_ids), decode_pinned_skipped=num_pinned
        ):
            return flow_to_mapping(
                problem,
                result.total_flow(problem),
                gm.leaf_node_ids,
                gm.sink_node.id,
                task_node_ids,
                pinned,
            )

    def solve(self) -> TaskMapping:
        return self.complete(self.solve_async())

    def rehearse(self) -> Optional[TaskMapping]:
        """The round's own path once more, on the graph as the round
        left it: the export of the journal `apply` just wrote, the
        uploads, the rung, the decode. After a re-fit of the slot plan
        (FlowScheduler._refit_plan) every program whose shape follows
        `entry_cap` has then RUN in this process before the round
        returns, and the next round's export finds an empty journal.
        The mapping is the caller's to look at and drop, never to
        apply; None if the solve failed (the round itself is done: the
        next one then meets the new shapes first). `last_result` and
        `state.problem()` stay a pair: both are this solve's now, and
        the round's own objective is on its RoundTiming."""
        try:
            return self.solve()
        except Exception as e:  # noqa: BLE001 — warned about, not raised
            warnings.warn(
                f"the solve that follows a plan re-fit failed ({e!r}); "
                "the next round runs the re-fitted shapes first",
                RuntimeWarning,
                stacklevel=2,
            )
            return None
