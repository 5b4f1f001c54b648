"""solver/select.py edge paths: unknown names, fallback semantics, and
the class each registered name resolves to (ISSUE 3 satellite)."""

import warnings

import pytest

import ksched_tpu.solver.native as native_mod
from ksched_tpu.solver.select import make_backend


class _ExplodingNativeSolver:
    def __init__(self, *a, **kw):
        raise RuntimeError("no C++ toolchain in this test")


@pytest.fixture()
def broken_native(monkeypatch):
    monkeypatch.setattr(native_mod, "NativeSolver", _ExplodingNativeSolver)


def test_unknown_backend_raises_value_error():
    with pytest.raises(ValueError, match="unknown backend 'bogus'"):
        make_backend("bogus")


def test_native_fallback_false_reraises(broken_native):
    with pytest.raises(RuntimeError, match="no C\\+\\+ toolchain"):
        make_backend("native", fallback=False)


def test_native_fallback_warns_and_degrades_to_jax(broken_native):
    from ksched_tpu.solver.jax_solver import JaxSolver

    with pytest.warns(RuntimeWarning, match="native backend unavailable"):
        solver = make_backend("native", fallback=True)
    assert isinstance(solver, JaxSolver)


def test_ref_returns_reference_solver():
    from ksched_tpu.solver.cpu_ref import ReferenceSolver

    assert isinstance(make_backend("ref"), ReferenceSolver)


def test_layered_returns_layered_solver():
    from ksched_tpu.solver.layered import LayeredTransportSolver

    assert isinstance(make_backend("layered"), LayeredTransportSolver)


def test_jax_and_ell_and_mega_resolve():
    from ksched_tpu.solver.ell_solver import EllSolver
    from ksched_tpu.solver.jax_solver import JaxSolver
    from ksched_tpu.solver.mega_solver import MegaSolver

    from ksched_tpu.ops import get_pallas_mode, set_pallas_mode

    assert isinstance(make_backend("jax"), JaxSolver)
    assert isinstance(make_backend("ell"), EllSolver)
    prev = get_pallas_mode()
    try:
        # the interpreter is taken only when asked for by name
        set_pallas_mode("interpret")
        mega = make_backend("mega")
    finally:
        set_pallas_mode(prev)
    assert isinstance(mega, MegaSolver)
    # --backend mega stays total: oversized graphs delegate to a CSR fallback
    assert isinstance(mega.fallback, JaxSolver)


def test_compiled_mega_is_refused_with_the_compilers_words():
    """Mosaic (jax 0.9.0) refuses the kernel's 2-D partner gather.
    The compiled backend must say so by name — never hand over to the
    interpreter or to scan-CSR — and 'auto' must detach the rung with
    a warning carrying the same message. (Flip this test when S2
    lands a kernel the compiler takes.)"""
    from ksched_tpu.ops import get_pallas_mode, set_pallas_mode

    with pytest.raises(RuntimeError, match="refused by the Pallas TPU compiler.*_gather_lowering_rule"):
        make_backend("mega")
    prev = get_pallas_mode()
    try:
        set_pallas_mode("on")  # what "auto" resolves to on a TPU
        with pytest.warns(RuntimeWarning, match="megakernel rung not attached"):
            auto = make_backend("auto")
    finally:
        set_pallas_mode(prev)
    assert auto.mega is None


class _WorkingNativeSolver:
    def __init__(self, *a, **kw):
        pass


def test_working_native_emits_no_warning(monkeypatch):
    """When the native build succeeds, the native path must hand back
    the solver without the fallback warning; direct backends likewise."""
    monkeypatch.setattr(native_mod, "NativeSolver", _WorkingNativeSolver)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        solver = make_backend("native")
        make_backend("jax")
        make_backend("ref")
    assert isinstance(solver, _WorkingNativeSolver)
