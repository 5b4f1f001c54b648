"""L5': the solver-dispatch boundary.

Reference: scheduling/flow/placement/solver.go:36-38 — a single-method
``Solve() -> TaskMapping`` seam behind which the MCMF backend lives. The
TPU build keeps the seam but the wire format is flat arrays
(graph/device_export.FlowProblem) instead of DIMACS text, and three
backends plug in:

- ReferenceSolver (solver/cpu_ref.py): exact successive-shortest-path
  oracle, pure Python — the mock-solver/test oracle the reference lacks;
- NativeSolver (solver/native.py): in-process C++ library, the
  Flowlessly-equivalent CPU production backend;
- JaxSolver (solver/jax_solver.py): jit cost-scaling push-relabel on TPU,
  warm-started across rounds — the centerpiece of the rebuild.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass

import numpy as np

from ..graph.device_export import FlowProblem


@dataclass
class FlowResult:
    """A feasible min-cost flow over a FlowProblem's arc slots.

    ``flow`` excludes lower-bound offsets; add ``problem.flow_offset`` to
    recover total arc flow. ``objective`` is the total cost including the
    lower-bound flow's cost.
    """

    flow: np.ndarray  # int64[M]
    objective: int
    iterations: int = 0

    def total_flow(self, problem: FlowProblem) -> np.ndarray:
        return self.flow + problem.flow_offset


def check_finite_costs(problem: FlowProblem) -> None:
    """Reject a poisoned cost model (NaN/inf costs) up front. Every
    backend calls this before its int cast — a non-finite float would
    otherwise wrap into garbage potentials and be "solved" silently
    (the chaos harness's nan_cost fault exists to catch exactly that;
    see runtime/chaos.poison_costs)."""
    if problem.cost.dtype.kind == "f" and not np.isfinite(problem.cost).all():
        raise ValueError(
            "non-finite arc costs in flow problem (NaN/inf from the "
            "cost model); refusing to solve"
        )


def lower_bound_cost(problem: FlowProblem) -> int:
    """Cost carried by the folded lower-bound flow; every backend adds
    this to its solved objective so objectives are comparable."""
    return int(
        (problem.flow_offset.astype(np.int64) * problem.cost.astype(np.int64)).sum()
    )


class FlowSolver(abc.ABC):
    """A min-cost max-flow backend over flat arrays."""

    @abc.abstractmethod
    def solve(self, problem: FlowProblem) -> FlowResult: ...

    def solve_traced(self, problem: FlowProblem) -> FlowResult:
        """``solve()`` inside a ``backend_solve`` obs span carrying the
        backend name, problem shape, and solver effort. This is the one
        instrumentation seam shared by every backend — the placement
        driver and the degradation ladder call it, so each rung attempt
        (including a failing one, whose span records the error) is a
        nested span in a captured trace. Costs two ``perf_counter``
        reads when no tracer is installed.

        Backends that emit solver-interior telemetry (``last_telemetry``
        after a solve — the compiled jax/layered/sharded
        loops) additionally get their buffer decoded here: superstep
        histograms onto the registry, per-superstep child spans under
        this span, or under the backend's own ``last_solve_span`` when
        it keeps one (Perfetto shows the convergence shape), and the
        stall detector (obs/soltel.py). ``native``/``cpu_ref`` expose
        no interior telemetry and skip all of it."""
        from ..obs import soltel
        from ..obs.spans import span

        with span(
            "backend_solve",
            backend=type(self).__name__,
            nodes=int(problem.num_nodes),
            arcs=int(problem.num_arcs),
        ) as sp:
            result = self.solve(problem)
            work = int(result.iterations or 0) or int(
                getattr(self, "last_iterations", 0)
                or getattr(self, "last_supersteps", 0)
                or 0
            )
            if work:
                sp.set("supersteps", work)
            sparse = getattr(self, "last_sparse_supersteps", None)
            if sparse is not None:
                # of those, the ones over the active nodes' rows alone
                # (scan-CSR's active-set superstep)
                sp.set("supersteps_sparse", int(sparse))
            tel = getattr(self, "last_telemetry", None)
            if tel is not None:
                # a backend that timed its own kernel call (AutoSolver's
                # `transport`) gets its supersteps laid over that span,
                # not over the host work around it
                soltel.publish(tel, getattr(self, "last_solve_span", None) or sp)
        return result

    def reset(self) -> None:
        """Drop warm-start state (e.g. after a full graph rebuild)."""
