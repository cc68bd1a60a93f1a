"""Native kernel loader — the NativeLoader analogue (core/env/NativeLoader.java:28-62).

The reference extracts platform .so files from jars and ``System.load``s
them; here the C++ sources live in ``ops/native`` and are compiled on first
use with g++ into the package build dir, then bound via ctypes. Absence of
a toolchain degrades gracefully to the numpy paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_lock = threading.Lock()
_lib: Optional["_NativeLib"] = None
_failed = False

_SRC_DIR = os.path.join(os.path.dirname(__file__), "native")
_BUILD_DIR = os.path.join(_SRC_DIR, "build")


class _NativeLib:
    def __init__(self, lib: ctypes.CDLL):
        self._lib = lib
        lib.mml_murmur3_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
            ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.mml_murmur3_batch.restype = None
        lib.mml_bin_features_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.mml_bin_features_f32.restype = None
        lib.mml_parse_csv.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64,
        ]
        lib.mml_parse_csv.restype = ctypes.c_int64
        lib.mml_csv_dims.argtypes = [
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.mml_csv_dims.restype = None

    def murmur3_batch(self, toks: list, seed: int) -> np.ndarray:
        n = len(toks)
        arr = (ctypes.c_char_p * n)(*toks)
        lens = np.array([len(t) for t in toks], dtype=np.int32)
        out = np.empty(n, dtype=np.uint32)
        self._lib.mml_murmur3_batch(
            ctypes.cast(arr, ctypes.POINTER(ctypes.c_char_p)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n,
            seed,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        )
        return out

    def bin_features(self, x: np.ndarray, uppers: list) -> np.ndarray:
        """(n, d) float32 -> uint8 bins via per-feature edge search (threaded):
        1 + the number of edges below the value, as the float64 comparison
        ``edge < value`` counts them."""
        x = np.ascontiguousarray(x, np.float32)
        n, d = x.shape
        offsets = np.zeros(d + 1, np.int64)
        for f, u in enumerate(uppers):
            offsets[f + 1] = offsets[f] + len(u)
        edges = (
            np.concatenate([np.asarray(u, np.float64) for u in uppers])
            if offsets[-1]
            else np.zeros(0, np.float64)
        )
        thresholds = _float32_thresholds(edges)
        out = np.empty((n, d), np.uint8)
        self._lib.mml_bin_features_f32(
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n,
            d,
            thresholds.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return out

    def parse_csv(self, data: bytes) -> np.ndarray:
        """Numeric CSV bytes -> (rows, cols) float64 (bad fields = NaN)."""
        n_rows = ctypes.c_int64()
        n_cols = ctypes.c_int64()
        self._lib.mml_csv_dims(data, len(data), ctypes.byref(n_rows), ctypes.byref(n_cols))
        out = np.empty((n_rows.value, n_cols.value), np.float64)
        got = self._lib.mml_parse_csv(
            data,
            len(data),
            n_cols.value,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n_rows.value,
        )
        return out[:got]


def _float32_thresholds(edges: np.ndarray) -> np.ndarray:
    """For each float64 edge ``e`` the float32 ``t`` with ``e < v  <=>  t <= v``
    for every float32 ``v``: the smallest float32 above ``e``. The kernel
    then compares float32 with float32 and finds the bins the float64
    comparison gives. An edge no value exceeds (+inf, NaN) becomes NaN,
    which no value reaches either."""
    with np.errstate(over="ignore"):  # an edge beyond float32's range: t = inf
        near = edges.astype(np.float32)  # nearest: the answer, or the float32 just below it
        above = np.nextafter(near, np.float32(np.inf))
    t = np.where(near.astype(np.float64) > edges, near, above)
    t[np.isposinf(edges)] = np.nan
    return np.ascontiguousarray(t, np.float32)


def _build() -> Optional[str]:
    so_path = os.path.join(_BUILD_DIR, "libmmltpu.so")
    src = os.path.join(_SRC_DIR, "mmltpu.cc")
    if os.path.exists(so_path) and os.path.getmtime(so_path) >= os.path.getmtime(src):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread", src, "-o", so_path]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except Exception:
        return None
    return so_path


def try_load() -> Optional[_NativeLib]:
    """Build+load the native kernel library, or None if unavailable."""
    global _lib, _failed
    if _lib is not None:
        return _lib
    if _failed or os.environ.get("MMLSPARK_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None or _failed:
            return _lib
        so = _build()
        if so is None:
            _failed = True
            return None
        try:
            _lib = _NativeLib(ctypes.CDLL(so))
        except Exception:
            _failed = True
            return None
    return _lib
