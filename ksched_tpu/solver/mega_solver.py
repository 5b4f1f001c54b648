"""MegaSolver: the general-graph MCMF backend on the Pallas megakernel.

Same FlowSolver seam, same algorithm, same host-cached `build_csr_plan`
ordering as solver/jax_solver.py — but the whole superstep loop runs
inside one `pl.pallas_call` with every table VMEM-resident
(ops/mcmf_pallas.py), instead of ~6 HBM gather passes + 3 global scans
per superstep. Flows are bit-identical to the CSR solver's.

The megakernel's reach is bounded by VMEM (~16 MB/core): graphs whose
padded entry tables exceed `mega_fits_vmem` are refused by `fits()`.
A standalone MegaSolver (--backend mega) delegates refused solves to
its `fallback` CSR solver; under AutoSolver (solver/graph_collapse.py)
the refusal routes the solve to the scan-based CSR backend instead —
the dense -> mega -> scan-CSR escalation ladder.

The plan adds three derived structures to the CSR ordering, all
structure-only (cached and rebuilt with the same key as CsrPlan):

- the partner permutation (each entry's reverse twin), which replaces
  every cross-node gather inside the kernel;
- segment START and END flags for the flag-carrying segmented scans;
- padding to the [R, MEGA_LANES] tile grid, with pad entries forming
  one inert trailing segment (sign 0, supply 0, partner self).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax.numpy as jnp

from ..graph.device_export import FlowProblem
from .base import FlowResult, FlowSolver, check_finite_costs, lower_bound_cost
from .jax_solver import CsrPlan, build_csr_plan


def _pad_pow2(x: np.ndarray, floor: int = 256) -> np.ndarray:
    """Zero-pad a 1D array to a power-of-two length (>= floor), so the
    kernel wrapper's traced shapes bucket instead of recompiling for
    every arc/node count (DeviceGraphState already grows its padded
    generations the same way). Padded arc slots are never referenced
    by a live entry; padded node slots carry zero supply."""
    from ..utils import next_pow2

    p = max(floor, next_pow2(len(x)))
    if p == len(x):
        return x
    return np.concatenate([x, np.zeros(p - len(x), x.dtype)])


@dataclass
class MegaPlan:
    """Padded, partner-linked entry tables for the megakernel."""

    R: int  # block rows of the [R, L] entry tiling
    L: int  # lanes
    e_arc: np.ndarray  # int32[R*L] arc slot (0 on pad)
    e_sign: np.ndarray  # int32[R*L] +1/-1, 0 on pad
    e_src: np.ndarray  # int32[R*L] source node (0 on pad)
    e_hs: np.ndarray  # int32[R*L] segment-start flags
    e_he: np.ndarray  # int32[R*L] segment-end flags
    e_prow: np.ndarray  # int32[R*L] partner block row
    e_pcol: np.ndarray  # int32[R*L] partner lane
    fwd_pos: np.ndarray  # int32[M] flat entry position of each arc's fwd entry
    src: np.ndarray  # int32[M] endpoints the plan was built for
    dst: np.ndarray  # int32[M]


def build_mega_plan(plan: CsrPlan, lanes: Optional[int] = None) -> MegaPlan:
    """Derive the megakernel tables from a (cached) CsrPlan."""
    from ..ops.mcmf_pallas import MEGA_LANES, mega_entry_rows

    L = MEGA_LANES if lanes is None else lanes
    m2 = len(plan.s_arc)
    m = m2 // 2
    R = mega_entry_rows(m2, L)
    E = R * L
    pad = E - m2

    e_arc = np.zeros(E, np.int32)
    e_arc[:m2] = plan.s_arc
    e_sign = np.zeros(E, np.int32)
    e_sign[:m2] = plan.s_sign
    e_src = np.zeros(E, np.int32)
    e_src[:m2] = plan.s_src
    e_hs = np.zeros(E, np.int32)
    e_hs[:m2] = plan.s_isstart
    e_he = np.zeros(E, np.int32)
    if m2:
        e_he[: m2 - 1] = plan.s_isstart[1:]
        e_he[m2 - 1] = 1
    if pad:
        e_hs[m2] = 1  # the pad region is one inert segment
        e_he[E - 1] = 1

    # partner permutation: entry (u, v) of arc a pairs with (v, u) —
    # the fwd entry's twin is original entry a + m, and vice versa
    ppos = np.arange(E, dtype=np.int64)  # kschedlint: host-only (numpy plan build)
    ppos[:m2] = plan.inv_order[
        np.where(plan.s_sign > 0, plan.s_arc + m, plan.s_arc)
    ]
    e_prow = (ppos // L).astype(np.int32)
    e_pcol = (ppos % L).astype(np.int32)

    return MegaPlan(
        R=R, L=L,
        e_arc=e_arc, e_sign=e_sign, e_src=e_src,
        e_hs=e_hs, e_he=e_he, e_prow=e_prow, e_pcol=e_pcol,
        fwd_pos=plan.inv_order[:m].astype(np.int32),
        src=plan.src.copy(), dst=plan.dst.copy(),
    )


class MegaSolver(FlowSolver):
    """VMEM-resident megakernel push-relabel, warm-started across
    rounds — drop-in for JaxSolver on graphs that fit VMEM.

    interpret: None = compiled unless set_pallas_mode("interpret")
    asks for the Pallas interpreter by name (never inferred from the
    backend); True/False force. fallback: optional CSR FlowSolver for graphs `fits()`
    refuses (oversized / degenerate); without one, refused solves
    raise."""

    def __init__(
        self,
        alpha: int = 8,
        max_supersteps: int = 50_000,
        warm_start: bool = True,
        lanes: Optional[int] = None,
        vmem_budget_bytes: Optional[int] = None,
        interpret: Optional[bool] = None,
        fallback: Optional[FlowSolver] = None,
        telemetry: Optional[int] = None,
    ):
        from .layered import validate_alpha
        from ..ops.mcmf_pallas import MEGA_LANES, _MEGA_VMEM_BUDGET_BYTES

        self.alpha = validate_alpha(alpha)
        self.max_supersteps = max_supersteps
        self.warm_start = warm_start
        self.lanes = MEGA_LANES if lanes is None else int(lanes)
        self.vmem_budget_bytes = (
            _MEGA_VMEM_BUDGET_BYTES
            if vmem_budget_bytes is None
            else int(vmem_budget_bytes)
        )
        self.interpret = interpret
        self.fallback = fallback
        self.telemetry = telemetry
        self._prev: Optional[np.ndarray] = None
        self._plan: Optional[MegaPlan] = None
        self._plan_dev: Optional[tuple] = None
        #: endpoint-generation key of the cached plan (FlowProblem.
        #: plan_key): equal keys skip the O(M) endpoint scans entirely
        self._plan_key = None
        self._fits_ok_for: Optional[FlowProblem] = None
        self._prev_dev = None  # warm flow as a device array (no re-upload)
        # endpoints at the LAST SUCCESSFUL SOLVE (see jax_solver)
        self._prev_src_dev = None
        self._prev_dst_dev = None
        self.last_supersteps = 0
        self.last_telemetry = None
        self.last_refusal = ""

    def reset(self) -> None:
        self._prev = None
        self._prev_dev = None
        self._prev_src_dev = None
        self._prev_dst_dev = None
        if self.fallback is not None:
            self.fallback.reset()

    def _resolve_interpret(self) -> bool:
        if self.interpret is not None:
            return bool(self.interpret)
        from ..ops import get_pallas_mode

        return get_pallas_mode() == "interpret"

    def fits(self, problem: FlowProblem) -> bool:
        """Whether the megakernel can take this solve; on refusal
        `last_refusal` names why (the AutoSolver escalation reads it)."""
        from ..obs import soltel
        from ..ops.mcmf_pallas import mega_fits_vmem

        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            self.last_refusal = "empty graph"
            return False
        if not mega_fits_vmem(
            2 * m, self.lanes, self.vmem_budget_bytes,
            telemetry=soltel.resolve_cap(self.telemetry) > 0,
        ):
            self.last_refusal = (
                f"{2 * m} entries exceed the VMEM tiling budget "
                f"({self.vmem_budget_bytes} bytes)"
            )
            return False
        # the kernel shares the CSR solver's exactness contract (costs
        # pre-scaled by the node count must fit int32); refusing here
        # keeps the dispatch ladder total — the fallback rung (native
        # CSR under AutoSolver) solves such graphs on raw costs
        max_cost = int(np.abs(problem.cost).max()) if m else 0
        if max_cost * problem.num_nodes >= (1 << 30):
            self.last_refusal = (
                f"scaled costs overflow int32 (max|cost|={max_cost} at "
                f"{problem.num_nodes} nodes)"
            )
            return False
        # nodes with excess but no entries never appear in the kernel's
        # segment space: their (infeasible) excess would go unnoticed,
        # so route such graphs to the CSR solver's canonical handling
        deg = np.bincount(
            np.concatenate([problem.src, problem.dst]),
            minlength=problem.num_nodes,
        )
        if (np.asarray(problem.excess)[deg == 0] != 0).any():
            self.last_refusal = "isolated node with nonzero excess"
            return False
        self.last_refusal = ""
        # remember the vetted problem (by identity) so the dispatch
        # seam's fits() + solve() sequence audits the arrays once
        self._fits_ok_for = problem
        return True

    def _plan_for(self, src: np.ndarray, dst: np.ndarray, n: int, plan_key=None) -> tuple:
        plan = self._plan
        if plan_key is not None and self._plan_key == plan_key and plan is not None:
            return self._plan_dev  # generation key match: no scans at all
        if plan is None or len(plan.src) != len(src) or plan_key is not None or not (
            np.array_equal(plan.src, src) and np.array_equal(plan.dst, dst)
        ):
            plan = build_mega_plan(build_csr_plan(src, dst, n), self.lanes)
            self._plan = plan
            # fwd_pos rides the cache PADDED (zero fill: the garbage
            # tail rows of the gathered flow are sliced off in
            # complete()) so its traced shape buckets with cap/cost
            self._plan_dev = tuple(
                jnp.asarray(x)
                for x in (
                    plan.e_arc, plan.e_sign, plan.e_src,
                    plan.e_hs, plan.e_he, plan.e_prow, plan.e_pcol,
                    _pad_pow2(plan.fwd_pos),
                )
            )
        self._plan_key = plan_key
        return self._plan_dev

    def solve_async(self, problem: FlowProblem):
        from ..ops.mcmf_pallas import mcmf_loop_pallas

        n = problem.num_nodes
        m = len(problem.src)
        if m == 0 or problem.num_arcs == 0:
            if (problem.excess > 0).any():
                raise RuntimeError("infeasible flow problem: supply but no arcs")
            return (problem, None, None, None)
        check_finite_costs(problem)
        vetted = self._fits_ok_for is problem
        self._fits_ok_for = None
        if not vetted and not self.fits(problem):
            if self.fallback is None:
                raise RuntimeError(
                    f"megakernel refused the graph ({self.last_refusal}) "
                    "and no fallback solver is attached"
                )
            return (problem, None, None, self.fallback.solve_async(problem))
        # the internal fits() call above re-primed the cache; vetting
        # is single-use — a re-solve of a MUTATED problem object must
        # re-audit (costs may have drifted past the overflow bound)
        self._fits_ok_for = None
        src = problem.src.astype(np.int32)
        dst = problem.dst.astype(np.int32)
        max_cost = int(np.abs(problem.cost).max()) if m else 0

        prev_plan = self._plan
        plan_dev = self._plan_for(
            src, dst, n, plan_key=getattr(problem, "plan_key", None)
        )

        from ..obs import soltel
        from ..ops.mcmf_pallas import mega_telemetry_cap

        interpret = self._resolve_interpret()
        # A device-resident handle is consumable directly only when the
        # resident pow2 extents already satisfy the kernel's _pad_pow2
        # floor (256) — then the padded shapes ARE the resident shapes
        # and no per-round re-upload (or device-side re-pad) is needed.
        resident = (
            getattr(problem, "d_cap", None) is not None
            and m >= 256
            and n >= 256
        )
        if resident:
            from ..graph.device_export import resident_solver_inputs

            dev_args, flow0_dev, _warm = resident_solver_inputs(
                problem, self._prev_dev, self._prev_src_dev,
                self._prev_dst_dev, self.warm_start,
            )
        else:
            cap = problem.cap.astype(np.int32)
            supply = problem.excess.astype(np.int32)
            cost = problem.cost.astype(np.int32) * np.int32(n)
            dev_args = (
                jnp.asarray(_pad_pow2(cap)),
                jnp.asarray(_pad_pow2(cost)),
                jnp.asarray(_pad_pow2(supply)),
            )
            flow0 = np.zeros(m, dtype=np.int32)
            if self.warm_start and self._prev is not None:
                f_prev = self._prev
                if len(f_prev) == m and prev_plan is not None and len(prev_plan.src) == m:
                    same = (prev_plan.src == src) & (prev_plan.dst == dst)
                    flow0 = np.where(same, np.minimum(f_prev, cap), 0).astype(np.int32)
            flow0_dev = jnp.asarray(_pad_pow2(flow0))
        # geometry rides the pending token: a later solve_async for a
        # different graph may rebuild self._plan before this dispatch
        # is complete()d (the async-pipelining seam)
        RL = (self._plan.R, self._plan.L)
        tel_cap = soltel.resolve_cap(self.telemetry)
        if tel_cap:
            # ring clamped to one [R, L] entry tile (the +1-tile VMEM
            # budget fits() charged); decode needs the effective cap
            tel_cap = mega_telemetry_cap(RL[0], RL[1], tel_cap)
        fut = mcmf_loop_pallas(
            *dev_args,
            flow0_dev,
            jnp.asarray(np.int32(1)),
            *plan_dev,
            R=RL[0], L=RL[1],
            alpha=self.alpha,
            max_supersteps=min(4096, self.max_supersteps),
            interpret=interpret,
            telemetry_cap=tel_cap,
        )
        cold = (
            _pad_pow2(np.zeros(m, dtype=np.int32)),
            max(1, max_cost * n),
            interpret,
            resident,
        )
        return (problem, fut, (dev_args, plan_dev, RL, cold, tel_cap), None)

    def complete(self, pending) -> FlowResult:
        from ..obs import soltel
        from ..ops.mcmf_pallas import mcmf_loop_pallas

        problem, fut, rest, delegated = pending
        if delegated is not None:
            res = self.fallback.complete(delegated)
            self.last_supersteps = getattr(
                self.fallback, "last_supersteps", res.iterations
            )
            self.last_telemetry = getattr(self.fallback, "last_telemetry", None)
            return res
        if fut is None:
            self.last_telemetry = None
            return FlowResult(
                flow=np.zeros(len(problem.src), dtype=np.int64),  # kschedlint: host-only (FlowResult contract is int64)
                objective=0, iterations=0,
            )
        dev_args, plan_args, (R, L), (f0_cold, eps_cold, interpret, resident), tel_cap = rest
        tel_buf = None
        if tel_cap:
            flow, steps, converged, p_overflow, tel_buf = fut
        else:
            flow, steps, converged, p_overflow = fut
        if not (bool(converged) and not bool(p_overflow)):
            out = mcmf_loop_pallas(
                *dev_args,
                jnp.asarray(f0_cold),
                jnp.asarray(np.int32(eps_cold)),
                *plan_args,
                R=R, L=L,
                alpha=self.alpha,
                max_supersteps=self.max_supersteps,
                interpret=interpret,
                telemetry_cap=tel_cap,
            )
            if tel_cap:
                flow, steps, converged, p_overflow, tel_buf = out
            else:
                flow, steps, converged, p_overflow = out
        self.last_supersteps = int(steps)
        # budget = the SOLVER's budget, not the warm attempt's 4096 cap
        # (see jax_solver.complete)
        self.last_telemetry = (
            soltel.decode(
                tel_buf, int(steps), tel_cap, "mega", self.max_supersteps,
                converged=bool(converged) and not bool(p_overflow),
                nodes=problem.num_nodes, arcs=len(problem.src),
            )
            if tel_buf is not None
            else None
        )
        if bool(p_overflow) or not bool(converged):
            self._prev = None
            self._prev_dev = None
        if bool(p_overflow):
            raise OverflowError("push-relabel potentials approached int32 range")
        if not bool(converged):
            tel = self.last_telemetry
            raise soltel.SolverStallError(
                f"push-relabel did not converge within {self.max_supersteps} "
                "supersteps; the flow problem may be infeasible",
                reason=soltel.detect_stall(tel) if tel is not None else None,
                telemetry=tel,
            )
        flow_np = np.asarray(flow)[: len(problem.src)]
        if self.warm_start:
            self._prev = flow_np.astype(np.int32)
            # the padded kernel flow aligns with the resident extent
            # only when no extra _pad_pow2 padding was applied
            keep = resident and flow.shape[0] == len(problem.src)
            self._prev_dev = flow if keep else None
            self._prev_src_dev = problem.d_src if keep else None
            self._prev_dst_dev = problem.d_dst if keep else None
        objective = int(
            (flow_np.astype(np.int64) * problem.cost.astype(np.int64)).sum()  # kschedlint: host-only (int64 objective math on host)
        ) + lower_bound_cost(problem)
        return FlowResult(
            flow=flow_np.astype(np.int64), objective=objective,  # kschedlint: host-only (FlowResult contract is int64)
            iterations=int(steps),
        )

    def solve(self, problem: FlowProblem) -> FlowResult:
        return self.complete(self.solve_async(problem))
