"""Native CPU backend: ctypes bindings to the C++ oracle solver.

The reference's serial and OpenMP stages are native C++
(``stage0/Withoutopenmp1.cpp``, ``stage1-openmp/Withopenmp1.cpp``); this
package keeps that capability native in the new framework —
``poisson_oracle.cpp`` is compiled to a shared library on first use (g++,
``-O2 -fopenmp``) and driven through ctypes. It is the fp64 correctness
oracle for the TPU paths and the framework's shared-memory CPU backend
(thread count = the reference's ``omp_set_num_threads`` loop,
``stage1-openmp/Withopenmp1.cpp:205-229``).

Build is hermetic and cached: the ``.so`` lives next to the source under a
name keyed on the hash of the source and the compile command, so a copied
tree carrying a library built elsewhere from other source never loads it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import NamedTuple, Optional

import numpy as np

from poisson_tpu.config import Problem

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "poisson_oracle.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class NativeResult(NamedTuple):
    """Mirrors ``solvers.pcg.PCGResult`` (numpy instead of jax arrays)."""

    w: np.ndarray
    iterations: int
    diff: float
    residual_dot: float


def _build_command() -> list[str]:
    # CXX/CXXFLAGS are overridable; the flags the shared library cannot
    # link or load without are not.
    cxx = os.environ.get("CXX", "g++")
    cxxflags = os.environ.get("CXXFLAGS", "-O2").split()
    return [cxx, *cxxflags, "-std=c++17", "-fPIC", "-fopenmp", "-shared",
            _SRC]


def library_path() -> str:
    """Where the library built from this source and command lives."""
    key = hashlib.sha256(open(_SRC, "rb").read())
    key.update("\0".join(_build_command()).encode())
    return os.path.join(_DIR, f"_poisson_oracle-{key.hexdigest()[:16]}.so")


def build(force: bool = False) -> str:
    """Compile the oracle library unless this source's build exists;
    returns its path."""
    lib = library_path()
    with _lock:
        if force or not os.path.exists(lib):
            # Unique temp name: concurrent processes (pytest-xdist, parallel
            # CI) may compile simultaneously; each writes its own file and
            # the os.replace is atomic.
            tmp = f"{lib}.{os.getpid()}.tmp"
            cmd = [*_build_command(), "-o", tmp]
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True)
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"native oracle build failed "
                        f"({' '.join(cmd)}):\n{proc.stderr}"
                    )
                os.replace(tmp, lib)
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
    return lib


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(build())
        lib.poisson_native_solve.restype = ctypes.c_int
        lib.poisson_native_solve.argtypes = [
            ctypes.c_int, ctypes.c_int,                      # M, N
            ctypes.c_double, ctypes.c_double,                # x_min, x_max
            ctypes.c_double, ctypes.c_double,                # y_min, y_max
            ctypes.c_double, ctypes.c_double,                # f_val, delta
            ctypes.c_int64,                                  # max_iter
            ctypes.c_int, ctypes.c_int,                      # weighted, threads
            ctypes.POINTER(ctypes.c_double),                 # w_out
            ctypes.POINTER(ctypes.c_int64),                  # iters_out
            ctypes.POINTER(ctypes.c_double),                 # diff_out
            ctypes.POINTER(ctypes.c_double),                 # zr_out
        ]
        lib.poisson_native_has_openmp.restype = ctypes.c_int
        lib.poisson_native_has_openmp.argtypes = []
        _lib = lib
    return _lib


def has_openmp() -> bool:
    return bool(_load().poisson_native_has_openmp())


def native_solve(problem: Problem, num_threads: int = 0) -> NativeResult:
    """fp64 PCG solve in native code. ``num_threads=0`` keeps the library's
    current OpenMP team (serial arithmetic semantics are identical; only
    reduction summation order differs across team sizes)."""
    lib = _load()
    w = np.zeros(problem.grid_shape, dtype=np.float64)
    iters = ctypes.c_int64(0)
    diff = ctypes.c_double(0.0)
    zr = ctypes.c_double(0.0)
    rc = lib.poisson_native_solve(
        problem.M, problem.N,
        problem.x_min, problem.x_max, problem.y_min, problem.y_max,
        problem.f_val, problem.delta, problem.iteration_cap,
        int(problem.weighted_norm), num_threads,
        w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.byref(iters), ctypes.byref(diff), ctypes.byref(zr),
    )
    if rc != 0:
        raise RuntimeError(f"poisson_native_solve failed with code {rc}")
    return NativeResult(
        w=w, iterations=int(iters.value), diff=diff.value,
        residual_dot=zr.value,
    )
