"""Native (C++) runtime kernels, loaded via ctypes.

SURVEY §2.11: the reference's native inventory is the XGBoost C++ core
(serving + training behind JNI wrappers) and the in-tree Java
``StreamingHistogram``.  Here the native library covers the host-side hot
paths — batched tree-ensemble/linear scoring for the Spark-free ``local``
scorer, quantile-bin application, and the streaming histogram — while tree
*training* stays on device (JAX/XLA).

The shared library is built on demand with ``g++ -O3`` (no pybind11 in this
environment; plain C ABI + ctypes) and cached next to the source.  Every
entry point has a numpy fallback, so the package works identically when no
compiler is present: check ``native.AVAILABLE``.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

__all__ = [
    "AVAILABLE", "load", "build",
    "predict_ensemble", "apply_bins", "linear_margin", "sigmoid", "softmax",
    "NativeStreamingHistogram",
]

_SRC = os.path.join(os.path.dirname(__file__), "src", "tmog_native.cpp")
_LIB_PATH = os.path.join(os.path.dirname(__file__), "libtmognative.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def build(force: bool = False) -> bool:
    """Compile the shared library with g++; returns success.

    Compiles to a temp file then ``os.rename``s it into place so concurrent
    processes can never dlopen a partially written .so.  Portable codegen
    (no -march=native): the cached artifact may be shared across machines.
    """
    if os.path.exists(_LIB_PATH) and not force \
            and os.path.getmtime(_LIB_PATH) >= os.path.getmtime(_SRC):
        return True
    tmp = f"{_LIB_PATH}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-pthread", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        os.rename(tmp, _LIB_PATH)
        return True
    except (OSError, subprocess.SubprocessError):
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
        return False


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    i32, i64, f32p = ctypes.c_int32, ctypes.c_int64, \
        ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64 = ctypes.c_double
    f64p = ctypes.POINTER(ctypes.c_double)
    vp = ctypes.c_void_p
    lib.tmog_predict_ensemble.argtypes = [
        i32p, i64, i64, i32p, i32p, f32p, i64, i32, i64, f32p, i32]
    lib.tmog_apply_bins.argtypes = [f32p, i64, i64, f32p, i32, i32p]
    lib.tmog_linear_margin.argtypes = [f32p, i64, i64, f32p, f32p]
    lib.tmog_sigmoid.argtypes = [f32p, i64, f32p]
    lib.tmog_softmax.argtypes = [f32p, i64, i64, f32p]
    lib.tmog_hist_new.argtypes = [i32]
    lib.tmog_hist_new.restype = vp
    lib.tmog_hist_free.argtypes = [vp]
    lib.tmog_hist_load.argtypes = [vp, f64p, f64p, i64]
    lib.tmog_hist_update.argtypes = [vp, f64p, i64]
    lib.tmog_hist_merge.argtypes = [vp, vp]
    lib.tmog_hist_size.argtypes = [vp]
    lib.tmog_hist_size.restype = i32
    lib.tmog_hist_get.argtypes = [vp, f64p, f64p]
    lib.tmog_hist_sum.argtypes = [vp, f64]
    lib.tmog_hist_sum.restype = f64
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("TMOG_DISABLE_NATIVE"):
            _load_failed = True
            return None
        if not build():
            _load_failed = True
            return None
        try:
            _lib = _declare(ctypes.CDLL(_LIB_PATH))
        except OSError:
            _load_failed = True
    return _lib


class _Available:
    """Lazy truthiness: first check triggers the build."""

    def __bool__(self) -> bool:
        return load() is not None


AVAILABLE = _Available()


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _f64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


# ---------------------------------------------------------------------------
# Kernels (numpy fallback in every branch)
# ---------------------------------------------------------------------------

def predict_ensemble(binned: np.ndarray, feat: np.ndarray, thresh: np.ndarray,
                     leaf: np.ndarray, depth: int,
                     n_threads: int = 0) -> np.ndarray:
    """Sum of all trees' leaf values; layouts match gbdt_kernels.predict_ensemble
    (binned (N,D) int32; feat/thresh (T, 2^depth-1); leaf (T, 2^depth, K))."""
    binned = np.ascontiguousarray(binned, np.int32)
    feat = np.ascontiguousarray(feat, np.int32)
    thresh = np.ascontiguousarray(thresh, np.int32)
    leaf = np.ascontiguousarray(leaf, np.float32)
    n, d = binned.shape
    n_trees, k = leaf.shape[0], leaf.shape[2]
    lib = load()
    if lib is not None:
        out = np.zeros((n, k), np.float32)
        if n_threads <= 0:
            n_threads = min(os.cpu_count() or 1, 16)
        lib.tmog_predict_ensemble(
            _i32p(binned), n, d, _i32p(feat), _i32p(thresh), _f32p(leaf),
            n_trees, depth, k, _f32p(out), n_threads)
        return out
    # numpy fallback: vectorized heap walk per tree; routing mirrors
    # gbdt_kernels._route_right (a negative threshold is a default-
    # direction split: effective threshold -t-1, bin 0 routes right)
    out = np.zeros((n, k), np.float32)
    rows = np.arange(n)
    for t in range(n_trees):
        node = np.zeros(n, np.int64)
        for l in range(depth):
            heap = (1 << l) - 1 + node
            x = binned[rows, feat[t][heap]]
            th = thresh[t][heap]
            dr = th < 0
            go_right = (x > np.where(dr, -th - 1, th)) | (dr & (x == 0))
            node = 2 * node + go_right
        out += leaf[t][node]
    return out


def apply_bins(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Quantized (N, D) int32; parity with gbdt_kernels.apply_bins."""
    X = np.ascontiguousarray(X, np.float32)
    edges = np.ascontiguousarray(edges, np.float32)
    n, d = X.shape
    lib = load()
    if lib is not None:
        out = np.empty((n, d), np.int32)
        lib.tmog_apply_bins(_f32p(X), n, d, _f32p(edges), edges.shape[1],
                            _i32p(out))
        return out
    return np.sum(X[:, :, None] > edges[None, :, :], axis=2).astype(np.int32)


def linear_margin(X: np.ndarray, beta: np.ndarray) -> np.ndarray:
    """X @ beta[:-1] + beta[-1] in float32."""
    X = np.ascontiguousarray(X, np.float32)
    beta = np.ascontiguousarray(beta, np.float32)
    lib = load()
    if lib is not None:
        out = np.empty(X.shape[0], np.float32)
        lib.tmog_linear_margin(_f32p(X), X.shape[0], X.shape[1], _f32p(beta),
                               _f32p(out))
        return out
    return (X @ beta[:-1] + beta[-1]).astype(np.float32)


def sigmoid(x: np.ndarray) -> np.ndarray:
    x = np.ascontiguousarray(x, np.float32)
    lib = load()
    if lib is not None:
        out = np.empty(x.shape, np.float32)
        lib.tmog_sigmoid(_f32p(x), x.size, _f32p(out))
        return out
    return (1.0 / (1.0 + np.exp(-x))).astype(np.float32)


def softmax(x: np.ndarray) -> np.ndarray:
    x = np.ascontiguousarray(x, np.float32)
    n, k = x.shape
    lib = load()
    if lib is not None:
        out = np.empty((n, k), np.float32)
        lib.tmog_softmax(_f32p(x), n, k, _f32p(out))
        return out
    m = x - x.max(axis=1, keepdims=True)
    e = np.exp(m)
    return (e / e.sum(axis=1, keepdims=True)).astype(np.float32)


class NativeStreamingHistogram:
    """ctypes wrapper over the C++ Ben-Haim/Tom-Tov histogram.

    Same surface as utils.streaming_histogram.StreamingHistogram (update /
    merge / bins / sum); raises RuntimeError when the library is absent —
    callers pick the implementation via ``native.AVAILABLE``.
    """

    def __init__(self, max_bins: int = 100):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.max_bins = max_bins
        self._h = lib.tmog_hist_new(max_bins)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.tmog_hist_free(h)
            self._h = None

    def update(self, values) -> "NativeStreamingHistogram":
        v = np.ascontiguousarray(np.asarray(values, np.float64).ravel())
        self._lib.tmog_hist_update(self._h, _f64p(v), v.size)
        return self

    def load(self, centers: np.ndarray, counts: np.ndarray
             ) -> "NativeStreamingHistogram":
        """Seed with weighted bins (resuming from a serialized state)."""
        c = np.ascontiguousarray(centers, np.float64)
        m = np.ascontiguousarray(counts, np.float64)
        self._lib.tmog_hist_load(self._h, _f64p(c), _f64p(m), c.size)
        return self

    def merge(self, other: "NativeStreamingHistogram"
              ) -> "NativeStreamingHistogram":
        self._lib.tmog_hist_merge(self._h, other._h)
        return self

    @property
    def bins(self):
        nb = self._lib.tmog_hist_size(self._h)
        centers = np.empty(nb, np.float64)
        counts = np.empty(nb, np.float64)
        if nb:
            self._lib.tmog_hist_get(self._h, _f64p(centers), _f64p(counts))
        return centers, counts

    def sum(self, x: float) -> float:
        return float(self._lib.tmog_hist_sum(self._h, float(x)))
