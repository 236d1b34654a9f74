"""Native runtime loader — builds and binds the C++ components.

The reference's performance-critical host paths are JVM code (radix
sort/merge `water/rapids/RadixOrder.java` + `BinaryMerge.java`, CSV tokenizer
`water/parser/CsvParser.java`); ours are C++ (native/*.cpp), compiled on first
use with the in-image toolchain (g++ -O3) into a cached shared library and
bound via ctypes (no pybind11 in the image). Every native entry point has a
numpy fallback, so the package works even where a compiler is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_CACHE = os.path.join(_SRC_DIR, "build")


def _build() -> str | None:
    src = os.path.join(_SRC_DIR, "radix.cpp")
    try:
        if not os.path.exists(src):
            return None
        os.makedirs(_CACHE, exist_ok=True)
        # no -march=native: the build directory is git-ignored but travels
        # with a copied tree, so the artefact must run on ANY x86-64 host
        # it lands on. The name hashes source AND flags — a stale .so
        # never gets picked up; rebuilds happen exactly when either changes
        import hashlib

        flags = ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]
        with open(src, "rb") as f:
            tag = hashlib.sha256(
                f.read() + " ".join(flags).encode()).hexdigest()[:12]
        out = os.path.join(_CACHE, f"libh2otpu-{tag}.so")
        if os.path.exists(out):
            return out
        cmd = ["g++", *flags, src, "-o", out]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return out
    except (subprocess.SubprocessError, OSError) as e:
        # covers missing compiler, read-only installs, and build errors —
        # the numpy fallback below keeps every caller working
        from ..utils.log import warn

        warn(f"native build failed ({e}); using numpy fallbacks")
        return None


def lib() -> ctypes.CDLL | None:
    """The loaded native library, or None when unavailable."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        path = _build()
        if path is None:
            return None
        try:
            L = ctypes.CDLL(path)
        except OSError as e:  # incompatible binary → numpy fallback
            from ..utils.log import warn

            warn(f"native library load failed ({e}); using numpy fallbacks")
            return None
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        L.h2otpu_radix_argsort_u64.argtypes = [u64p, ctypes.c_int64, i64p,
                                               ctypes.c_int]
        L.h2otpu_radix_refine_u64.argtypes = [u64p, ctypes.c_int64, i64p,
                                              ctypes.c_int]
        L.h2otpu_gather_u64.argtypes = [u64p, i64p, ctypes.c_int64, u64p,
                                        ctypes.c_int]
        _LIB = L
        return _LIB


def _as_sortable_u64(col: np.ndarray, ascending: bool = True,
                     na_first: bool = True) -> np.ndarray:
    """Map a float64 column to order-preserving uint64 keys.

    IEEE-754 trick: flip the sign bit for non-negatives, all bits for
    negatives. NaN maps to an extreme so NA ordering is explicit (H2O sorts
    NAs first ascending)."""
    col = np.ascontiguousarray(col, dtype=np.float64)
    bits = col.view(np.uint64).copy()
    neg = bits >> 63 != 0
    bits[neg] = ~bits[neg]
    bits[~neg] |= np.uint64(1) << np.uint64(63)
    nan = np.isnan(col)
    bits[nan] = 0 if na_first == ascending else np.uint64(0xFFFFFFFFFFFFFFFF)
    if not ascending:
        bits = np.uint64(0xFFFFFFFFFFFFFFFF) - bits
    return bits


def radix_lexsort(columns: list[np.ndarray], ascending: list[bool] | None = None,
                  na_first: bool = True, nthreads: int = 0) -> np.ndarray:
    """Stable multi-column argsort; columns[0] is the PRIMARY key (unlike
    np.lexsort). Native parallel radix when available, np.lexsort fallback."""
    n = len(columns[0])
    ascending = ascending or [True] * len(columns)
    L = lib()
    if L is None or n < (1 << 15):  # small inputs: numpy is fine
        # same u64 key transform as the native path, so the two paths produce
        # IDENTICAL permutations (incl. NaN-vs-±inf ordering and na_first)
        keys = [_as_sortable_u64(np.asarray(c, dtype=np.float64), asc, na_first)
                for c, asc in zip(columns, ascending)]
        return np.lexsort(list(reversed(keys)))

    order = np.empty(n, dtype=np.int64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    # least-significant column first; each refine is stable on prior order
    first = True
    for col, asc in zip(reversed(columns), reversed(ascending)):
        keys = _as_sortable_u64(np.asarray(col, dtype=np.float64), asc, na_first)
        kp = keys.ctypes.data_as(u64p)
        op = order.ctypes.data_as(i64p)
        if first:
            L.h2otpu_radix_argsort_u64(kp, n, op, nthreads)
            first = False
        else:
            L.h2otpu_radix_refine_u64(kp, n, op, nthreads)
    return order
