"""Pallas TPU kernels for the solver hot ops, plus the dispatch switch.

`set_pallas_mode` controls whether the layered-transport solve runs as
the fused Pallas kernel (ops/transport_pallas.py) or the multi-op XLA
path (solver/layered.py):

- "auto" (default): Pallas on TPU backends, XLA elsewhere;
- "on": always Pallas (compiled);
- "interpret": always Pallas under the interpreter (CPU test envs);
- "off": always the XLA path.

The mode is read at TRACE time: it must be set before the consuming
program is built (before constructing a DeviceBulkCluster, and before a
solver's first solve). Already-compiled programs keep the dispatch they
were traced with — rebuild the cluster/solver after switching modes.

`jax.experimental.pallas.tpu` is imported lazily, only when a Pallas
branch is actually taken, so XLA-only deployments never depend on the
Pallas TPU lowerings being importable.
"""

from __future__ import annotations

from typing import Tuple

_PALLAS_MODE = "auto"
_VALID_MODES = ("auto", "on", "interpret", "off")


def set_pallas_mode(mode: str) -> None:
    if mode not in _VALID_MODES:
        raise ValueError(f"pallas mode must be one of {_VALID_MODES}, got {mode!r}")
    global _PALLAS_MODE
    _PALLAS_MODE = mode


def get_pallas_mode() -> str:
    return _PALLAS_MODE


def resolve_pallas() -> Tuple[bool, bool]:
    """(use_pallas, interpret) for the ambient backend, at trace time."""
    mode = _PALLAS_MODE
    if mode == "on":
        return True, False
    if mode == "interpret":
        return True, True
    if mode == "off":
        return False, False
    import jax

    return jax.default_backend() == "tpu", False


#: the fused kernel holds the whole solve state in VMEM; XLA's scoped
#: vmem limit for custom calls is 16 MiB (measured: a [512, 1024] i32
#: instance wants 21.33M against a 16.00M limit), and the kernel's live
#: set is ~10 [C, Mp] i32 tiles across a superstep (wS/U/y +
#: push/relabel temps). Beyond the budget the XLA phase loop
#: (HBM-resident state, fused per superstep) is the correct dispatch —
#: for many-row instances (hundreds of groups) its per-superstep HBM
#: traffic amortizes fine, and the kernel's VMEM-residency win matters
#: most exactly where instances are small.
_PALLAS_VMEM_BUDGET_BYTES = 15 << 20
_PALLAS_LIVE_TILES = 10


def transport_solve(
    wS, supply, col_cap, eps_init, pm0=None, *,
    alpha: int = 8, max_supersteps: int = 20_000, refine_waves: int = 0,
):
    """The layered-transport solve behind the mode switch: the fused
    Pallas kernel or the XLA phase loop, one call site for both.
    pm0 optionally warm-starts machine prices (carried across rounds).
    refine_waves > 0 enables price refinement between eps phases (see
    solver/layered.py _price_refine) in both implementations.
    Returns (y, pm, steps, converged); traceable inside jit/scan."""
    use_pallas, interpret = resolve_pallas()
    if use_pallas and not interpret:
        C, Mp = wS.shape
        if _PALLAS_LIVE_TILES * C * Mp * 4 > _PALLAS_VMEM_BUDGET_BYTES:
            use_pallas = False  # state would not fit VMEM-resident
    if use_pallas:
        from .transport_pallas import transport_loop_pallas

        return transport_loop_pallas(
            wS, supply, col_cap, eps_init, pm0,
            alpha=alpha, max_supersteps=max_supersteps, interpret=interpret,
            refine_waves=refine_waves,
        )
    from ..solver.layered import _solve_transport

    return _solve_transport(
        wS, supply, col_cap, eps_init, pm0,
        alpha=alpha, max_supersteps=max_supersteps,
        refine_waves=refine_waves,
    )


#: the tiered kernel's live set is larger (two cost tiers + resident
#: caps + per-tier splits) — budget conservatively
_PALLAS_TIERED_LIVE_TILES = 16


def transport_solve_tiered(
    wLo, wHi, R, supply, col_cap, eps_init, *,
    alpha: int = 8, max_supersteps: int = 20_000, refine_waves: int = 0,
):
    """The tiered (continuation-priced) solve behind the mode switch:
    the fused tiered Pallas kernel or the XLA phase loop — the
    preemption-on twin of transport_solve. Bit-identical results both
    ways. Returns (y, pm, steps, converged); traceable inside
    jit/scan."""
    use_pallas, interpret = resolve_pallas()
    if use_pallas and not interpret:
        C, Mp = wLo.shape
        if _PALLAS_TIERED_LIVE_TILES * C * Mp * 4 > _PALLAS_VMEM_BUDGET_BYTES:
            use_pallas = False
    if use_pallas:
        from .transport_pallas import transport_loop_pallas_tiered

        return transport_loop_pallas_tiered(
            wLo, wHi, R, supply, col_cap, eps_init,
            alpha=alpha, max_supersteps=max_supersteps, interpret=interpret,
            refine_waves=refine_waves,
        )
    from ..solver.layered import _solve_transport_tiered

    return _solve_transport_tiered(
        wLo, wHi, R, supply, col_cap, eps_init,
        alpha=alpha, max_supersteps=max_supersteps,
        refine_waves=refine_waves,
    )


def __getattr__(name):
    if name == "transport_loop_pallas":
        from .transport_pallas import transport_loop_pallas

        return transport_loop_pallas
    if name == "transport_loop_pallas_tiered":
        from .transport_pallas import transport_loop_pallas_tiered

        return transport_loop_pallas_tiered
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# transport_loop_pallas is intentionally NOT in __all__: a star import
# would trigger the lazy Pallas TPU import that XLA-only deployments
# must never take. Access it explicitly (module __getattr__).
__all__ = [
    "transport_solve",
    "transport_solve_tiered",
    "set_pallas_mode",
    "get_pallas_mode",
    "resolve_pallas",
]
