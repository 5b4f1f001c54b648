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


def test_jax_resolves():
    from ksched_tpu.solver.jax_solver import JaxSolver

    assert isinstance(make_backend("jax"), JaxSolver)


@pytest.mark.parametrize("name", ["ell", "mega"])
def test_names_that_left_are_refused(name):
    with pytest.raises(ValueError) as e:
        make_backend(name)
    assert f"unknown backend {name!r}" in str(e.value)
    assert "native | jax | sharded | ref | layered | auto" in str(e.value)


def test_auto_under_the_interpreter_attaches_no_kernel_rung():
    """Pallas dispatch live (what a TPU resolves to, here by name): the
    ladder is built without a probe of the compiler, warns of nothing
    and carries no `mega` attribute."""
    from ksched_tpu.ops import get_pallas_mode, set_pallas_mode
    from ksched_tpu.solver.graph_collapse import AutoSolver

    prev = get_pallas_mode()
    try:
        set_pallas_mode("interpret")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            auto = make_backend("auto")
    finally:
        set_pallas_mode(prev)
    assert isinstance(auto, AutoSolver)
    assert not hasattr(auto, "mega")


def test_cli_refuses_backend_ell(capsys):
    from ksched_tpu.cli import build_arg_parser

    with pytest.raises(SystemExit) as e:
        build_arg_parser().parse_args(["--backend", "ell"])
    assert e.value.code == 2
    assert "invalid choice: 'ell'" in capsys.readouterr().err


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
