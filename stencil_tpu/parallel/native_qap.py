"""ctypes bindings for the native QAP solvers (native/qap.cpp).

Loads ``libstencil_native.so`` through ``make -C native`` — the library is
git-ignored, so a checkout has none (or a stale one) until the first load;
make rebuilds only when ``qap.cpp`` is newer.  Importing this module raises
ImportError when the library cannot be built — ``qap.solve_auto`` catches
that, falls back to the pure-Python solvers and logs which solver is in
use.  Set ``STENCIL_NATIVE=0`` to force the fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Tuple

import numpy as np

from stencil_tpu.utils.config import env_bool

try:
    _native_enabled = env_bool("STENCIL_NATIVE", True)
except ValueError as e:
    # module-import-time read, lazily triggered from qap.solve_auto whose
    # fallback guard catches ImportError/OSError only: a malformed value
    # must warn-and-default (the STENCIL_OUTPUT_LEVEL convention), not
    # abort placement planning with an escaping ValueError
    from stencil_tpu.utils.logging import log_warn

    log_warn(f"{e}; treating STENCIL_NATIVE as enabled")
    _native_enabled = True
if not _native_enabled:
    raise ImportError("native disabled via STENCIL_NATIVE=0")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libstencil_native.so")


def _load() -> ctypes.CDLL:
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.SubprocessError) as e:
        raise ImportError(f"cannot build native library: {e}") from e
    return ctypes.CDLL(_LIB_PATH)


_lib = _load()

_DP = ctypes.POINTER(ctypes.c_double)
_IP = ctypes.POINTER(ctypes.c_int)
for name in ("stencil_qap_solve", "stencil_qap_solve_catch"):
    fn = getattr(_lib, name)
    fn.argtypes = [_DP, _DP, ctypes.c_int, _IP]
    fn.restype = ctypes.c_double
_lib.stencil_qap_cost.argtypes = [_DP, _DP, _IP, ctypes.c_int]
_lib.stencil_qap_cost.restype = ctypes.c_double


def _as_c(m: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(m, dtype=np.float64))


def qap_cost(w: np.ndarray, d: np.ndarray, f) -> float:
    w, d = _as_c(w), _as_c(d)
    fa = np.ascontiguousarray(np.asarray(f, dtype=np.int32))
    return float(
        _lib.stencil_qap_cost(
            w.ctypes.data_as(_DP), d.ctypes.data_as(_DP), fa.ctypes.data_as(_IP), w.shape[0]
        )
    )


def _solve(fn, w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
    w, d = _as_c(w), _as_c(d)
    n = w.shape[0]
    assert w.shape == (n, n) and d.shape == (n, n), (w.shape, d.shape)
    out = np.zeros(n, dtype=np.int32)
    c = fn(w.ctypes.data_as(_DP), d.ctypes.data_as(_DP), n, out.ctypes.data_as(_IP))
    return out.tolist(), float(c)


def qap_solve(w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
    return _solve(_lib.stencil_qap_solve, w, d)


def qap_solve_catch(w: np.ndarray, d: np.ndarray) -> Tuple[List[int], float]:
    return _solve(_lib.stencil_qap_solve_catch, w, d)


def solve_auto(w: np.ndarray, d: np.ndarray, exact_limit: int = 8) -> Tuple[List[int], float]:
    n = np.asarray(w).shape[0]
    if n <= exact_limit:
        return qap_solve(w, d)
    return qap_solve_catch(w, d)
