"""Compile-on-first-use build of the native MCMF library.

Equivalent in role to the reference's build/Dockerfile:5-12 step that
builds Flowlessly via cmake — except the artifact is a shared library
loaded in-process. The library's file name carries a hash of mcmf.cpp's
bytes, so only a build of the source that is on disk can be loaded: a
`_build/` left behind by another source (copied along with the
checkout, mtimes and all) is never trusted. Thread-safe via an atomic
rename.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

_SRC = os.path.join(os.path.dirname(__file__), "mcmf.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _lib_name(src_path: str) -> str:
    """`libksched_mcmf-<sha256 of the source bytes, 16 hex>.so`."""
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"libksched_mcmf-{digest}.so"


def library_path() -> str:
    """Path to the library built from mcmf.cpp as it is on disk,
    building it if no build of exactly these bytes exists."""
    lib = os.path.join(_BUILD_DIR, _lib_name(_SRC))
    if os.path.exists(lib):
        return lib
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, lib)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native solver build failed:\n{e.stderr}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return lib


def load_library() -> ctypes.CDLL:
    """Load (building if needed) and type the library. Cached per process."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(library_path())
        lib.ksched_mcmf_ctx_new.restype = ctypes.c_void_p
        lib.ksched_mcmf_ctx_new.argtypes = []
        lib.ksched_mcmf_ctx_free.restype = None
        lib.ksched_mcmf_ctx_free.argtypes = [ctypes.c_void_p]
        lib.ksched_mcmf_solve.restype = ctypes.c_int32
        lib.ksched_mcmf_solve.argtypes = [
            ctypes.c_void_p,  # ctx (nullable)
            ctypes.c_int32,  # algorithm
            ctypes.c_int32,  # n
            ctypes.c_int64,  # m
            ctypes.POINTER(ctypes.c_int32),  # src
            ctypes.POINTER(ctypes.c_int32),  # dst
            ctypes.POINTER(ctypes.c_int32),  # cap
            ctypes.POINTER(ctypes.c_int32),  # cost
            ctypes.POINTER(ctypes.c_int64),  # excess
            ctypes.POINTER(ctypes.c_int64),  # flow_out
            ctypes.POINTER(ctypes.c_int64),  # objective_out
            ctypes.POINTER(ctypes.c_int64),  # iters_out
        ]
        _lib = lib
        return _lib
