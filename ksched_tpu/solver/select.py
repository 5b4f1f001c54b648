"""One place to pick an MCMF backend by name.

Mirrors the reference's solver selection flags (placement/solver.go:
30-34) with graceful degradation: "native" needs a C++ toolchain at
first use (compile-on-demand), so callers that cannot guarantee one get
the JAX backend instead of a traceback.
"""

from __future__ import annotations

import warnings

from .base import FlowSolver


def make_backend(
    name: str, warm_start: bool = True, fallback: bool = True, preemption: bool = False,
    price_updates: bool = False,
) -> FlowSolver:
    """name: "native" | "jax" | "sharded" | "ref" | "layered" | "auto".
    With fallback=True a failed native build degrades to the JAX solver
    with a RuntimeWarning (capturable by callers/tests via
    warnings.catch_warnings, unlike the stderr print it replaced).
    ``preemption`` says the graphs to come keep their running tasks'
    arcs: the scan-CSR rung ("jax") then runs its global price update
    (JaxSolver.price_update_every), which no other rung has; every
    other name ignores it. ``price_updates`` asks for the same update
    for another reason: a cost model whose routes differ in cost
    (CostModeler.routes_differ_in_cost)."""
    if name == "native":
        try:
            from .native import NativeSolver

            return NativeSolver(algorithm="cost_scaling", warm_start=warm_start)
        except (RuntimeError, OSError, FileNotFoundError) as e:
            if not fallback:
                raise
            warnings.warn(
                f"native backend unavailable ({e}); using jax",
                RuntimeWarning,
                stacklevel=2,
            )
            name = "jax"
    if name == "jax":
        from .jax_solver import PREEMPTION_PRICE_UPDATE_EVERY, JaxSolver

        return JaxSolver(
            warm_start=warm_start,
            price_update_every=(
                PREEMPTION_PRICE_UPDATE_EVERY if preemption or price_updates else 0
            ),
        )
    if name == "sharded":
        # the multi-chip slot-stable backend over the full device mesh
        # (parallel/sharded_solver.py); under AutoSolver ("auto") it is
        # the fourth rung behind the HBM fitting gate — selecting it
        # directly forces every general-graph solve onto the mesh
        import numpy as _np
        import jax
        from jax.sharding import Mesh

        from ..parallel.sharded_solver import ShardedJaxSolver

        return ShardedJaxSolver(
            Mesh(_np.array(jax.devices()), ("x",)), warm_start=warm_start
        )
    if name == "ref":
        from .cpu_ref import ReferenceSolver

        return ReferenceSolver()
    if name == "layered":
        from .layered import LayeredTransportSolver

        return LayeredTransportSolver()
    if name == "auto":
        # the policy-dispatch seam (docs/solver_coverage.md): dense
        # transport whenever the graph audits as collapsible, the
        # scan-based CSR backend while its HBM working set fits one
        # chip, the sharded multi-chip backend beyond that — per
        # solve, automatically. The sharded rung is attached (lazily —
        # no mesh or shard_map compile until the fitting gate
        # escalates) whenever the process sees more than one device.
        from .graph_collapse import AutoSolver

        sharded = None
        import jax

        if len(jax.devices()) > 1:
            def _make_sharded():
                import numpy as _np
                from jax.sharding import Mesh

                from ..parallel.sharded_solver import ShardedJaxSolver

                devs = _np.array(jax.devices())
                return ShardedJaxSolver(
                    Mesh(devs, ("x",)), warm_start=warm_start
                )

            sharded = _make_sharded
        return AutoSolver(
            make_backend("native", warm_start=warm_start, fallback=fallback),
            sharded=sharded,
        )
    raise ValueError(
        f"unknown backend {name!r}; want native | jax | sharded | ref | "
        "layered | auto"
    )
