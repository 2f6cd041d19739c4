"""Tree-ensemble model stages: Random Forest, GBT, Decision Tree, XGBoost-parity.

Reference wrappers being re-implemented natively (no JNI, no Spark):
 * OpRandomForestClassifier (impl/classification/OpRandomForestClassifier.scala:58)
 * OpGBTClassifier (:46), OpDecisionTreeClassifier (:46)
 * OpRandomForestRegressor / OpGBTRegressor / OpDecisionTreeRegressor
   (impl/regression/:47)
 * OpXGBoostClassifier / OpXGBoostRegressor (OpXGBoostClassifier.scala:47,
   OpXGBoostRegressor.scala:48) — the reference's only C++ component
   (xgboost4j, SURVEY §2.11); here the histogram GBDT runs as jitted XLA
   kernels (models.gbdt_kernels) with XGBoost's parameterisation (eta,
   num_round, gamma as RAW loss-reduction threshold, min_child_weight,
   early stopping on a
   validation slice, aucpr eval — DefaultSelectorParams.scala XGB block).

All training happens on the quantized (N, D) int matrix resident on device;
bootstrap resampling is expressed as Poisson sample-weights (no copies).
"""
from __future__ import annotations

import functools
import hashlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types.columns import ColumnarDataset
from .gbdt_kernels import (
    TreeEnsemble, apply_bins, grow_forest_rf, grow_tree, predict_ensemble,
    quantile_bins,
)
from .prediction import PredictionBatch, PredictorEstimator, PredictorModel

__all__ = [
    "OpRandomForestClassifier", "OpRandomForestRegressor",
    "OpGBTClassifier", "OpGBTRegressor",
    "OpDecisionTreeClassifier", "OpDecisionTreeRegressor",
    "OpXGBoostClassifier", "OpXGBoostRegressor",
    "TreeEnsembleModel",
]


class TreeEnsembleModel(PredictorModel):
    """Fitted forest/boosted ensemble.

    mode: 'rf_cls' (leaf = class probs, average), 'rf_reg' (average),
    'gbdt_binary' (sum -> sigmoid), 'gbdt_multi' (sum -> softmax),
    'gbdt_reg' (sum + base).
    """

    def __init__(self, mode: str, edges, feat, thresh, leaf,
                 base_score: float = 0.0, n_classes: int = 2,
                 uid: Optional[str] = None):
        super().__init__(operation_name="treeEnsemble", uid=uid)
        self.mode = mode
        self.edges = edges
        self.feat = feat
        self.thresh = thresh
        self.leaf = leaf
        self.base_score = base_score
        self.n_classes = n_classes

    def _raw(self, X: np.ndarray) -> np.ndarray:
        depth = int(np.log2(self.feat.shape[1] + 1))
        from .. import native
        # small-batch serving (the local scorer's case): the C++ kernels skip
        # JAX dispatch + device transfer (the 4096-row cutoff is a choice a
        # chip measurement must re-decide — ROADMAP Queue 3).
        # Only when the ensemble is already host-resident, though: a freshly
        # fitted model keeps its trees on device so CV never downloads the
        # ~3 MB ensemble per candidate just to score it; XLA predicts and only
        # the (N, K) scores come back.  Large batches stay on XLA either way.
        host_trees = isinstance(self.feat, np.ndarray)
        if native.AVAILABLE and host_trees and len(X) <= 4096:
            binned = native.apply_bins(np.asarray(X, np.float32),
                                       np.asarray(self.edges, np.float32))
            return native.predict_ensemble(
                binned, np.asarray(self.feat), np.asarray(self.thresh),
                np.asarray(self.leaf), depth)
        # memoized binning, on the device (row blocks past ROW_BLOCK rows)
        binned = _binned_for_edges(X, self.edges)
        feat = jnp.asarray(self.feat, jnp.int32)
        thresh = jnp.asarray(self.thresh, jnp.int32)
        leaf = jnp.asarray(self.leaf, jnp.float32)
        out = predict_ensemble(binned, feat, thresh, leaf, depth)
        return np.asarray(out)

    def score_device(self, X: np.ndarray, problem_type: str):
        """Device validation scores: ONE fused program (predict + mode
        transform) instead of a dispatch per un-jitted op."""
        depth = int(np.log2(self.feat.shape[1] + 1))
        binned = _binned_for_edges(X, self.edges)
        return _score_ensemble_jit(
            binned, jnp.asarray(self.feat, jnp.int32),
            jnp.asarray(self.thresh, jnp.int32),
            jnp.asarray(self.leaf, jnp.float32),
            jnp.float32(self.base_score), depth, self.mode, problem_type)

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        raw = self._raw(X)
        t = self.feat.shape[0]
        if self.mode == "rf_cls":
            proba = raw / t
            proba = np.clip(proba, 1e-9, 1.0)
            proba = proba / proba.sum(axis=1, keepdims=True)
            return PredictionBatch(
                prediction=proba.argmax(axis=1).astype(np.float64),
                raw_prediction=raw, probability=proba)
        if self.mode == "rf_reg":
            return PredictionBatch(prediction=(raw[:, 0] / t
                                               + self.base_score).astype(np.float64))
        if self.mode == "gbdt_binary":
            z = raw[:, 0] + self.base_score
            p1 = 1.0 / (1.0 + np.exp(-z))
            proba = np.stack([1 - p1, p1], axis=1)
            return PredictionBatch(
                prediction=(p1 >= 0.5).astype(np.float64),
                raw_prediction=np.stack([-z, z], axis=1), probability=proba)
        if self.mode == "gbdt_multi":
            z = raw + self.base_score
            e = np.exp(z - z.max(axis=1, keepdims=True))
            proba = e / e.sum(axis=1, keepdims=True)
            return PredictionBatch(
                prediction=proba.argmax(axis=1).astype(np.float64),
                raw_prediction=z, probability=proba)
        # gbdt_reg
        return PredictionBatch(
            prediction=(raw[:, 0] + self.base_score).astype(np.float64))


@functools.partial(jax.jit,
                   static_argnames=("depth", "mode", "problem_type"))
def _score_ensemble_jit(binned, feat, thresh, leaf, base_score, depth: int,
                        mode: str, problem_type: str):
    raw = predict_ensemble(binned, feat, thresh, leaf, depth)
    t = feat.shape[0]
    if mode == "rf_cls":
        proba = jnp.clip(raw / t, 1e-9, 1.0)
        proba = proba / proba.sum(axis=1, keepdims=True)
        return (proba[:, 1] if problem_type == "binary"
                else jnp.argmax(proba, axis=1).astype(jnp.float32))
    if mode == "rf_reg":
        return raw[:, 0] / t + base_score
    if mode == "gbdt_binary":
        p1 = jax.nn.sigmoid(raw[:, 0] + base_score)
        return (p1 if problem_type == "binary"
                else (p1 >= 0.5).astype(jnp.float32))
    if mode == "gbdt_multi":
        return jnp.argmax(raw, axis=1).astype(jnp.float32)
    return raw[:, 0] + base_score  # gbdt_reg


import contextlib
import threading
from collections import OrderedDict

from ..obs.trace import phases as _phases
from ..obs.trace import span as _span
from ..utils.profiling import count_fresh, count_hash, count_memo, launch

_BIN_CACHE: "OrderedDict" = OrderedDict()
_BIN_CACHE_CAPACITY = 32
_HASH_BY_ID: dict = {}
_MEMO_LOCK = threading.Lock()
#: key -> Event for builds in flight (the sketch-prefetch thread and the
#: sweep's tree group may race to the same prep; second caller waits)
_MEMO_INFLIGHT: dict = {}
#: bumped by clear_sweep_caches: an in-flight build that started before a
#: clear must not repopulate the cache after it (device buffers would
#: outlive the end-of-train housekeeping)
_MEMO_GEN = 0


def clear_sweep_caches() -> None:
    """Release the sweep memos' device buffers (end-of-train housekeeping).

    Takes the memo lock (a prefetch thread may be mutating the cache) and
    bumps the generation so in-flight builds that started before the clear
    do not repopulate it afterwards."""
    global _MEMO_GEN
    with _MEMO_LOCK:
        _MEMO_GEN += 1
        _BIN_CACHE.clear()
        _HASH_BY_ID.clear()
        _CONTIG_BY_ID.clear()


def _memo_peek(key):
    """Memo probe without building (None on miss; a hit is counted)."""
    with _MEMO_LOCK:
        hit = _BIN_CACHE.get(key)
        if hit is not None:
            _BIN_CACHE.move_to_end(key)
    if hit is not None:
        count_memo(key[0], "hits")
    return hit


def _memo(key, build, span: Optional[str] = None):
    """Content-keyed sweep memo with LRU eviction.

    A CV×grid sweep re-touches the same fold matrices for every candidate,
    so device uploads and binning launches deduplicate by content hash.
    Eviction is oldest-first — a wholesale clear would re-upload the
    sweep's hot fold matrices mid-run.

    Thread-aware: concurrent builders of the SAME key deduplicate (the
    selector's sketch-prefetch thread overlaps host prep with the sweep's
    queued device work; when the tree group arrives it waits for the
    in-flight build instead of re-sketching a GB-scale matrix).

    Every probe is counted by memo kind (``key[0]``) in
    ``COUNTERS.memo_tags`` as a hit, a build or a wait; ``span`` names the
    span a traced run records round the build (``tree.prep.*``), and a
    wait for another thread's build is the span ``tree.prep.wait``.
    """
    with _MEMO_LOCK:
        hit = _BIN_CACHE.get(key)
        if hit is not None:
            _BIN_CACHE.move_to_end(key)
        else:
            ev = _MEMO_INFLIGHT.get(key)
            owner = ev is None
            if owner:
                ev = threading.Event()
                _MEMO_INFLIGHT[key] = ev
            gen = _MEMO_GEN
    if hit is not None:
        count_memo(key[0], "hits")
        return hit
    if not owner:
        count_memo(key[0], "waits")
        with _span("tree.prep.wait", cat="prep", memo=key[0]):
            ev.wait()
        with _MEMO_LOCK:
            hit = _BIN_CACHE.get(key)
        if hit is not None:
            return hit
        # the owning build failed (or a clear raced it): build here too —
        # concurrent rebuilds on this rare path are benign (same content)
    count_memo(key[0], "builds")
    try:
        with (_span(span, cat="prep", memo=key[0]) if span
              else contextlib.nullcontext()):
            val = build()
        with _MEMO_LOCK:
            # insert BEFORE waking waiters (they re-probe the cache on
            # wake); skip if clear_sweep_caches ran since the build began
            if _MEMO_GEN == gen:
                while len(_BIN_CACHE) >= _BIN_CACHE_CAPACITY:
                    _BIN_CACHE.popitem(last=False)
                _BIN_CACHE[key] = val
    finally:
        if owner:
            with _MEMO_LOCK:
                _MEMO_INFLIGHT.pop(key, None)
            ev.set()
    return val


_BIG_ARRAY_BYTES = 64 << 20


def _sample_digest(a: np.ndarray) -> str:
    """Cheap per-call digest over a strided sample + both array ends.

    Guards the per-object hash cache of NON-frozen (view) arrays against
    IN-PLACE mutation: any realistic batch overwrite perturbs the sampled
    bytes, changing the memo key even though the cached base hash is stale.
    ~40 KB of work regardless of array size.
    """
    flat = a.reshape(-1)
    step = max(1, flat.size // 8192)
    parts = (np.ascontiguousarray(flat[::step]).tobytes()
             + flat[:1024].tobytes() + flat[-1024:].tobytes())
    return hashlib.md5(parts).hexdigest()[:16]


def _full_hash(a: np.ndarray) -> str:
    """Full-bytes content hash: md5 up to 64 MB, crc32+adler32 (each ~GB/s
    in C) beyond, where md5's ~1 s/GB would show up in sweep latency.  The
    weaker big-array checksum pair is never used alone — ``_content_hash``
    always appends the per-call md5 sample digest to the memo key."""
    if a.nbytes > _BIG_ARRAY_BYTES:
        import zlib
        mv = memoryview(np.ascontiguousarray(a)).cast("B")
        return f"crc{zlib.crc32(mv):08x}a{zlib.adler32(mv):08x}n{len(mv)}"
    count_fresh("tree.hash.copy", a.nbytes)    # tobytes: a copy to hash
    return hashlib.md5(a.tobytes()).hexdigest()


_SMALL_REHASH_BYTES = 1 << 20


def _content_hash(a: np.ndarray) -> str:
    """Memo key component for an array: full-bytes content hash + per-call
    mutation guard.

    The sweep usually probes the memo with the SAME matrix object for every
    candidate; a per-object cache makes those probes free.  Arrays up to
    1 MB are fully re-hashed on every probe (sub-ms — exact, no staleness).
    Bigger arrays hash their full bytes ONCE per object (ADVICE r1: big
    arrays previously keyed by identity only) and append a per-call sampled
    digest (strided sample + both ends) so realistic in-place overwrites
    change the key even though the cached base hash is stale.  In-place
    batch reuse of a fitted matrix therefore stays supported.
    """
    if a.nbytes <= _SMALL_REHASH_BYTES:
        return hashlib.md5(a.tobytes()).hexdigest()
    import weakref
    k = id(a)
    h = _HASH_BY_ID.get(k)
    if h is None:
        count_hash(a.nbytes)
        with _span("tree.prep.hash", cat="prep", bytes=a.nbytes):
            h = _full_hash(a)
        _HASH_BY_ID[k] = h
        try:
            weakref.finalize(a, _HASH_BY_ID.pop, k, None)
        except TypeError:  # pragma: no cover - non-weakrefable view
            _HASH_BY_ID.pop(k, None)
    return f"{h}-{_sample_digest(a)}"


_CONTIG_BY_ID: dict = {}


def _view_digest(Xf: np.ndarray) -> str:
    """Cheap mutation guard for a possibly-strided array: strided row sample
    + both ends, no full reshape (reshape(-1) of a non-contiguous matrix
    would copy the whole thing)."""
    if Xf.ndim == 0 or Xf.size == 0:
        return hashlib.md5(Xf.tobytes()).hexdigest()[:16]
    step = max(1, Xf.shape[0] // 256)
    parts = (np.ascontiguousarray(Xf[::step]).tobytes()
             + np.ascontiguousarray(Xf[:1]).tobytes()
             + np.ascontiguousarray(Xf[-1:]).tobytes())
    return hashlib.md5(parts).hexdigest()[:16]


def _as_f32(X) -> np.ndarray:
    """float32 C-contiguous view; returns X itself when already so (keeps
    object identity stable for the per-object hash cache).

    A non-contiguous input (e.g. a caller's column slice ``X[:, ::2]`` or a
    Fortran-ordered matrix; the fitted SanityChecker writes its filtered
    matrix row-major itself and never takes this path)
    is copied ONCE per object and memoized — the selector sweep probes with
    the same matrix for every candidate, and re-copying a GB-scale matrix
    per probe measured ~17 s of a 200k-row sweep.  A sampled digest guards
    the cache against in-place mutation of the source."""
    Xf = np.asarray(X, np.float32)
    if Xf is not X:
        count_fresh("tree.f32", Xf.nbytes)     # another dtype: converted
    if Xf.flags.c_contiguous:
        return Xf
    k = id(X)
    digest = _view_digest(Xf)
    hit = _CONTIG_BY_ID.get(k)
    if hit is not None and hit[0] == digest:
        return hit[1]
    with _span("tree.prep.contiguous", cat="prep", bytes=Xf.nbytes):
        Xc = np.ascontiguousarray(Xf)
    count_fresh("tree.contiguous", Xc.nbytes)
    _CONTIG_BY_ID[k] = (digest, Xc)
    try:
        import weakref
        weakref.finalize(X, _CONTIG_BY_ID.pop, k, None)
    except TypeError:  # pragma: no cover - non-weakrefable input
        _CONTIG_BY_ID.pop(k, None)
    return Xc


def _host_copy(x, site: str) -> np.ndarray:
    """``np.asarray(x)``, booked under ``site`` as a fresh host array
    (``count_fresh``) where it makes one: a ``jax.Array`` keeps the host
    copy of its first fetch, so a later ``np.asarray`` of it hands that one
    out again.  (A CPU backend's fetch is a view and keeps none: it is
    booked every time.)"""
    fresh = (not isinstance(x, np.ndarray)
             and getattr(x, "_npy_value", None) is None)
    host = np.asarray(x)
    if fresh:
        count_fresh(site, host.nbytes)
    return host


def _upload_timed(a):
    """jnp.asarray with transfer accounting (bytes + enqueue-blocking time)."""
    import time as _time

    from ..utils.profiling import count_upload
    with _span("tree.prep.upload", cat="prep", bytes=a.nbytes):
        t0 = _time.perf_counter()
        out = jnp.asarray(a)
        count_upload(a.nbytes, _time.perf_counter() - t0)
    return out


def _dev_memo(arr, tag: str = "up"):
    """Upload a host array once per distinct content."""
    a = np.asarray(arr)
    if not a.flags.c_contiguous:
        if a.dtype == np.float32:
            a = _as_f32(arr)
        else:
            a = np.ascontiguousarray(a)
            count_fresh("tree.contiguous", a.nbytes)
    key = (tag, _content_hash(a), a.shape, str(a.dtype))
    return _memo(key, lambda: _upload_timed(a))


#: past this element count the shared matrix uploads as bf16 (half the
#: upload bytes and half the HBM).  bf16 keeps f32's exponent range (no
#: overflow on large-magnitude features); matmul consumers accumulate in
#: f32 either way.  The threshold is a choice a chip measurement must
#: re-decide (ROADMAP Queue 3).
_BF16_UPLOAD_ELEMS = 1 << 25


def _dev_f32(X, tag: str = "X_f32"):
    """THE shared device upload of a host matrix.

    Every consumer of the full matrix (linear-model fits, device
    standardization stats, SanityChecker-scale stats) goes through this one
    memo, so a selector sweep uploads the GB-scale matrix exactly once per
    train.  Large matrices (``_BF16_UPLOAD_ELEMS``) upload as bf16 and
    consumers upcast on device; small ones stay exact f32.  Tree binning
    (``_binned_cached``) is a reader of the exact f32 entry only: it bins
    that copy where it lies, and never the bf16 one.

    This applies to the sweep AND to big-matrix refits/scoring of the
    winning linear model — a deliberate trade (bf16 keeps f32's exponent
    range; coefficient noise is ~1e-3 relative and measured AuPR-neutral)
    against a second, full-precision upload.  Set
    ``TMOG_MATRIX_PRECISION=f32`` to force exact uploads.
    """
    import os

    from .gbdt_kernels import _accel_bf16

    Xf = _as_f32(X)
    force_f32 = (os.environ.get("TMOG_MATRIX_PRECISION", "auto") == "f32"
                 or not _accel_bf16())   # nothing to save on CPU, and
    #                                      XLA-CPU bf16 matmuls are emulated
    if tag == "X_f32" and Xf.size > _BF16_UPLOAD_ELEMS and not force_f32:
        hx = _content_hash(Xf)
        key = ("X_bf16", hx, Xf.shape)

        def build():
            import ml_dtypes
            Xb = Xf.astype(ml_dtypes.bfloat16)
            count_fresh("tree.upload.bf16", Xb.nbytes)
            return _upload_timed(Xb)
        return _memo(key, build)
    return _dev_memo(Xf, tag)


def _dev_memo_sharded(arr, sharding, tag: str = "up"):
    """Upload a host array ONCE per (content, sharding) — the mesh sweep
    probes with the same fold matrices for every grid candidate."""
    import jax

    given = np.asarray(arr)
    a = np.ascontiguousarray(given)
    if a is not given:
        count_fresh("tree.contiguous", a.nbytes)   # a strided one: copied
    key = (tag, _content_hash(a), a.shape, str(a.dtype), str(sharding))

    def build():
        with _span("tree.prep.upload", cat="prep", bytes=a.nbytes):
            return jax.device_put(a, sharding)
    return _memo(key, build)


def _binned_host_padded(binned, ndata: int) -> np.ndarray:
    """The device's binned matrix fetched back to the host, row-padded where
    its rows do not tile a data axis of ``ndata`` shards; fetch and padded
    copy are booked under ``tree.pad`` where they are new arrays."""
    from ..parallel.mesh import pad_to_multiple

    host, n_pad = pad_to_multiple(_host_copy(binned, "tree.pad"), ndata,
                                  axis=0)
    if n_pad:
        count_fresh("tree.pad", host.nbytes)
    return host


def _binned_sharded(binned, mesh):
    """The binned matrix row-padded to tile the mesh's data axis and
    committed ``P(data, None)``, with its padded row count: ONE placement
    per (content, mesh) for every grid group of the sweep and the winner's
    mesh refit alike (they hold the same binned matrix since the refit
    joins the sweep's preparation, ``_prep_tree_inputs_mesh``)."""
    from ..parallel.mesh import sweep_matrix_sharding

    host = _binned_host_padded(binned, mesh.shape[mesh.axis_names[0]])
    return (_dev_memo_sharded(host, sweep_matrix_sharding(mesh),
                              "binned_sharded"), host.shape[0])


@jax.jit
def _apply_bins_i8(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """On-device quantization to int8 (B <= 127) of a matrix that is
    already device-resident in f32: one launch, no upload."""
    return jnp.sum(X[:, :, None] > edges[None, :, :], axis=2).astype(jnp.int8)


@functools.partial(jax.jit, donate_argnums=(0,))
def _bin_block_into(out, x_blk, edges, start):
    """Quantize one f32 row block and write it into ``out`` at row
    ``start``: the compare-and-count fuses into the update of the donated
    result, so a block costs one read of its f32 rows and one write of its
    bins.  ``start`` is traced: ONE program serves every block of a matrix
    shape.  Also returns one element of the block's bins, the walk's token
    that this block's f32 rows have been consumed."""
    b = jnp.sum(x_blk[:, :, None] > edges[None, :, :],
                axis=2).astype(out.dtype)
    return jax.lax.dynamic_update_slice(out, b, (start, 0)), b[0, 0]


def _device_bins(Xf: np.ndarray, ef: np.ndarray):
    """Quantize a HOST f32 matrix on the device, block by block.

    Count of ``edges < x`` on f32 against f32: exactly
    ``np.searchsorted(sorted edges, x, "left")``; NaN compares false and
    lands in bin 0, ``+inf`` sentinel edges never trigger.  The matrix is
    walked in ``gbdt_kernels.ROW_BLOCK`` rows (65 MB of f32 at 500
    columns): each block is placed, binned by ``_bin_block_into`` and
    dropped, with at most two blocks in flight (the next block's upload
    overlaps this one's launch), so the f32 matrix is never resident; the
    int8 result (int32 from 127 edges up) is.  A tail shorter than a block
    is covered by a last block that ENDS at the last row and overlaps its
    predecessor (the same bins twice), so no block is padded or copied on
    the host and every block has the one shape.  The block rows depend on
    the shape alone: a warm-up train builds every program of the walk (the
    result's ``zeros`` and the block program)."""
    from . import gbdt_kernels

    n, d = Xf.shape
    out = jnp.zeros((n, d), jnp.int8 if ef.shape[1] < 127 else jnp.int32)
    if n == 0:
        return out
    edges = jnp.asarray(ef)
    rb = min(int(gbdt_kernels.ROW_BLOCK), n)
    starts = list(range(0, n - rb, rb)) + [n - rb]
    tokens = []
    for i, s in enumerate(starts):
        if i >= 2:
            tokens[i - 2].block_until_ready()
        x_blk = _upload_timed(Xf[s:s + rb])   # its own span, and the bytes
        with launch("device_bin"):
            out, tok = _bin_block_into(out, x_blk, edges, s)
        tokens.append(tok)
    return out


def _binned_for_edges(X, edges):
    """Device-binned matrix for given edges (scoring path).

    Shares one memo entry with the fit path (``_prep_tree_inputs``), keyed by
    (matrix, edges) content — scoring the training matrix re-binned it from
    scratch before (measured 2x the whole binning cost per sweep)."""
    Xf = _as_f32(X)
    return _binned_cached(Xf, _content_hash(Xf), edges)


def _binned_cached(Xf: np.ndarray, hx: str, edges):
    """THE binned matrix of (matrix content, edges content): int8 on the
    device (int32 from 127 edges up), binned ON the device whatever the
    size, by every tree family's fit, the grid groups and the scoring path.

    A matrix that the sweep's shared upload already holds in exact f32
    (``_dev_f32``'s ``X_f32`` memo) is binned where it lies in one launch;
    any other is walked in row blocks (``_device_bins``).  Both count f32
    values against f32 edges, so the bins do not depend on which models
    share the selector: the bf16 copy that ``_dev_f32`` keeps of a large
    matrix is never binned (a value within bf16 rounding of an edge would
    change bins)."""
    ef = np.ascontiguousarray(np.asarray(edges, np.float32))
    key = ("bins", hx, _content_hash(ef), Xf.shape)

    def build():
        xdev = _memo_peek(("X_f32", hx, Xf.shape, "float32"))
        if xdev is None:
            return _device_bins(Xf, ef)
        with launch("device_bin"):
            fn = _apply_bins_i8 if ef.shape[1] < 127 else apply_bins
            return fn(xdev, jnp.asarray(ef))
    return _memo(key, build, span="tree.prep.bin")


def _prep_tree_inputs(X, max_bins):
    """Quantile-sketch + binning (fit path); shares the binned-matrix memo
    with the scoring path (same (matrix, edges) key)."""
    Xf = _as_f32(X)
    hx = _content_hash(Xf)
    edges = _memo(("edges", hx, Xf.shape, max_bins),
                  lambda: quantile_bins(Xf, max_bins),
                  span="tree.prep.sketch")
    return edges, _binned_cached(Xf, hx, edges)


#: sampled zero fraction at/above which the tree fit sketches its bin
#: edges over the NONZERO values
_SPARSE_ZERO_FRAC = 0.75
#: below this element count the all-values sketch is kept
_SPARSE_MIN_ELEMS = 1 << 24


def _takes_sparse_sketch(Xf: np.ndarray) -> bool:
    """THE rule that picks the sparse-aware sketch: a matrix of at least
    ``_SPARSE_MIN_ELEMS`` elements whose sampled rows (every n/4096-th)
    are at least ``_SPARSE_ZERO_FRAC`` zeros."""
    if Xf.size < _SPARSE_MIN_ELEMS:
        return False
    step = max(1, Xf.shape[0] // 4096)
    return float((Xf[::step] == 0).mean()) >= _SPARSE_ZERO_FRAC


def _prep_tree_inputs_mesh(X, max_bins, mesh):
    """Quantile sketch + binning of a fit on a mesh.

    The fit JOINS the preparation the train already made: where the memo
    holds the host sketch of the same content (``edges``, built by the
    sweep's tree groups through ``_prep_tree_inputs_weighted``), those edges
    are returned, and with them the sweep's binned matrix (``bins``).  So
    the selector's winner is refitted on the edges its candidates were
    validated on, as on one chip, and a train prepares its matrix once.

    Only on a miss (a stand-alone ``fit_raw`` with no sweep before it, or a
    sweep that sketched a matrix truncated of trailing zero-weight rows
    under another hash) is the sketch MESH-SHARDED: each shard samples its
    rows, the samples all_gather over ICI, quantiles compute replicated
    (parallel.sharded.quantile_bins_sharded — the analogue of the
    reference's executor-distributed sketch, RawFeatureFilter.scala:
    489-545 / XGBoost's Rabit sketch), memoised per (matrix, mesh topology).

    Mostly-zero matrices keep the HOST sparse-aware sketch (pinned 0.0
    edge, full resolution on the nonzeros): the sharded sketch has no
    nonzero-aware variant yet, and an all-values sketch of a 95%-zero
    feature collapses to ~2 usable bins (code-review r5)."""
    from ..parallel.sharded import quantile_bins_sharded

    Xf = _as_f32(X)
    if _takes_sparse_sketch(Xf):
        return _prep_tree_inputs_sparse(Xf, max_bins)
    hx = _content_hash(Xf)
    edges = _memo_peek(("edges", hx, Xf.shape, max_bins))
    if edges is None:
        mesh_key = tuple(sorted(mesh.shape.items()))
        edges = _memo(("edges_mesh", hx, Xf.shape, max_bins, mesh_key),
                      lambda: quantile_bins_sharded(Xf, mesh, max_bins),
                      span="tree.prep.sketch")
    return edges, _binned_cached(Xf, hx, edges)


def _prep_tree_inputs_sparse(X, max_bins):
    """Like ``_prep_tree_inputs`` but detects wide mostly-zero matrices:
    their bin edges sketch over the NONZERO values
    (quantile_bins_sparse_aware) — an all-values sketch of a 95%-zero
    feature collapses to ~2 usable bins, while XGBoost's sketch is
    sparsity-aware (SURVEY §2.11); matching it measured +0.016 train AuPR
    on the config-5 shape at the same round budget.  Only the SKETCH is
    sparse-aware: the histograms of such a matrix are built like any
    other's.
    """
    from .gbdt_kernels import quantile_bins_sparse_aware

    Xf = _as_f32(X)
    if not _takes_sparse_sketch(Xf):
        return _prep_tree_inputs(Xf, max_bins)
    hx = _content_hash(Xf)
    edges = _memo(("edges_sp", hx, Xf.shape, max_bins),
                  lambda: quantile_bins_sparse_aware(Xf, max_bins),
                  span="tree.prep.sketch")
    return edges, _binned_cached(Xf, hx, edges)


def _efb_enabled() -> bool:
    """``TMOG_EFB``: '0' disables exclusive feature bundling, '1' forces
    it past the width-ratio gate, 'auto' (default) engages when the
    greedy packer shrinks the histogram width enough to pay for the
    re-encode pass (gbdt_kernels.EFB_MIN_WIDTH_RATIO)."""
    import os

    return os.environ.get("TMOG_EFB", "auto") != "0"


def _maybe_bundle(hx: str, edges, binned, max_bins: int):
    """Memoized EFB plan + bundled device matrices for a fit matrix.

    Returns ``(FeatureBundles, bundled binned device array, end-bin device
    array)`` or None when bundling declines.  Keyed on the SAME content
    hash as the edges/binned memos, so one host pack serves every
    candidate of a sweep; the host binned matrix downloads once (the
    device copy is the memoized upload — on-host backends this is free).
    """
    import os

    from .gbdt_kernels import (EFB_MIN_WIDTH_RATIO, bundle_features,
                               bundle_matrix)

    force = os.environ.get("TMOG_EFB", "auto") == "1"
    # edges participate in the key: the weight-aware sketch can produce
    # different edges for the same matrix content (TM024 pad rows)
    ec = np.ascontiguousarray(np.asarray(edges, np.float32))
    key = ("efb", hx, _content_hash(ec), tuple(binned.shape), max_bins,
           force)

    def build():
        with _span("tree.prep.bundle", cat="prep"):
            host = _host_copy(binned, "tree.bundle.host")
            b = bundle_features(host, np.asarray(edges), max_bins,
                                min_width_ratio=(1.0 if force
                                                 else EFB_MIN_WIDTH_RATIO))
            if b is None:
                return ()
            bundled = bundle_matrix(b, host)
            count_fresh("tree.bundle.packed", bundled.nbytes)
        return (b, _upload_timed(bundled), _upload_timed(b.end_bin))

    val = _memo(key, build)
    return val if val else None


def _prep_tree_inputs_weighted(X, max_bins: int, row_weight=None):
    """``_prep_tree_inputs_sparse`` with a PADDING-aware sketch: a
    TRAILING block of zero-total-weight rows (mesh row padding — the
    TM024 contract's shape) is excluded from the quantile sketch, since
    pad rows participate in no fit and must not move the bin edges;
    binning still covers every row.  INTERIOR zero-weight rows (holdout
    reservations, balancer drops) stay in the sketch — the sequential
    per-candidate fits sketch over all rows, and the batched groups must
    bin with the same edges those fits would win selection with.
    """
    Xf = _as_f32(X)
    if row_weight is None:
        return _prep_tree_inputs_sparse(Xf, max_bins)
    w = np.asarray(row_weight)
    nz = np.nonzero(w > 0)[0]
    if len(nz) == 0 or nz[-1] == len(w) - 1:
        return _prep_tree_inputs_sparse(Xf, max_bins)
    Xm = np.ascontiguousarray(Xf[: nz[-1] + 1])
    hxm = _content_hash(Xm)
    if _takes_sparse_sketch(Xm):
        from .gbdt_kernels import quantile_bins_sparse_aware

        edges = _memo(("edges_sp", hxm, Xm.shape, max_bins),
                      lambda: quantile_bins_sparse_aware(Xm, max_bins),
                      span="tree.prep.sketch")
    else:
        edges = _memo(("edges", hxm, Xm.shape, max_bins),
                      lambda: quantile_bins(Xm, max_bins),
                      span="tree.prep.sketch")
    return edges, _binned_cached(Xf, _content_hash(Xf), edges)


def _feature_subset_size(strategy: str, d: int, is_classification: bool) -> int:
    if strategy == "all":
        return d
    if strategy == "sqrt" or (strategy == "auto" and is_classification):
        return max(1, int(np.sqrt(d)))
    if strategy == "onethird" or (strategy == "auto" and not is_classification):
        return max(1, d // 3)
    return d


class _RandomForestBase(PredictorEstimator):
    def __init__(self, num_trees: int = 20, max_depth: int = 5,
                 max_bins: int = 32, min_instances_per_node: int = 1,
                 min_info_gain: float = 0.0, subsample_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(operation_name=self._op_name, uid=uid)
        self.num_trees = num_trees
        self.max_depth = max_depth
        self.max_bins = max_bins
        self.min_instances_per_node = min_instances_per_node
        self.min_info_gain = min_info_gain
        self.subsample_rate = subsample_rate
        self.feature_subset_strategy = feature_subset_strategy
        self.seed = seed
        #: optional jax.sharding.Mesh: rows shard over the mesh's data axis
        #: and per-level histograms psum over ICI (grow_forest_sharded);
        #: runtime-only (not a persisted ctor param)
        self.mesh = None

    def with_mesh(self, mesh) -> "_RandomForestBase":
        self.mesh = mesh
        return self

    _op_name = "randomForest"
    _classification = True

    def fit_columns(self, data: ColumnarDataset, label_col, features_col):
        X = np.asarray(features_col.values, dtype=np.float32)
        y = np.nan_to_num(np.asarray(label_col.values, dtype=np.float32))
        return self.fit_raw(X, y)

    def fit_raw(self, X: np.ndarray, y: np.ndarray, w=None):
        # a traced run splits every tree fit in three: up to the first
        # growth launch, the launches, the grown arrays' way to the model
        with _phases("tree.fit.prepare", cat="fit") as ph:
            return self._fit_phased(X, y, w, ph)

    def _fit_phased(self, X, y, w, ph):
        """``fit_raw``'s body; ``ph`` holds the ``tree.fit.*`` span that is
        open (``prepare`` on entry) and moves on to ``grow`` at the first
        growth launch and to ``fetch`` where the grown arrays become the
        model."""
        n, d = X.shape
        if self.mesh is not None:
            # the sweep's edges and binned matrix where the memo holds
            # them; else the mesh-sharded sketch (all_gather'd per-shard
            # samples, the reference's executor-distributed sketch)
            edges, binned = _prep_tree_inputs_mesh(X, self.max_bins,
                                                   self.mesh)
        else:
            # sparse-aware sketch: the SAME edges/memo keys as
            # RFGridGroup's sweep, so a winner refit on a qualifying sparse
            # matrix trains with the bin edges the candidate won selection
            # on (ADVICE r4 medium) and reuses the sweep's host sketch +
            # binned-matrix upload
            edges, binned = _prep_tree_inputs_sparse(X, self.max_bins)
        base_w = (np.ones(n, np.float32) if w is None
                  else np.asarray(w, np.float32))
        if self._classification:
            k = max(int(y.max()) + 1, 2)
            Y = np.eye(k, dtype=np.float32)[y.astype(int)]
        else:
            k = 1
            Y = y[:, None].astype(np.float32)
        msub = _feature_subset_size(self.feature_subset_strategy, d,
                                    self._classification)
        if self.mesh is not None:
            f, th, lf = self._fit_sharded(binned, Y, base_w, msub, ph)
        else:
            # bootstrap bags (Poisson weights) + feature subsets generate ON
            # DEVICE from the seed (grow_forest_rf); the fold data uploads
            # once (memoized), so each candidate fit is a couple of
            # scalar-arg launches — no per-tree weights are uploaded
            Yj, wj = _dev_memo(Y, "rf_Y"), _dev_memo(base_w, "rf_w")
            ph.to("tree.fit.grow")
            f, th, lf = grow_forest_rf(
                binned, Yj, wj,
                seed=self.seed, n_trees=self.num_trees, msub=msub,
                subsample_rate=self.subsample_rate,
                max_depth=self.max_depth, n_bins=self.max_bins, lam=1e-3,
                min_info_gain=self.min_info_gain,
                min_instances=float(self.min_instances_per_node),
                onehot_targets=self._classification)
        # ensemble stays device-resident: during model selection only the
        # scores come back to host; the winning ensemble downloads lazily at
        # persistence/native-serving time (TreeEnsembleModel._raw)
        ph.to("tree.fit.fetch")
        mode = "rf_cls" if self._classification else "rf_reg"
        return TreeEnsembleModel(
            mode=mode, edges=edges, feat=f, thresh=th, leaf=lf,
            n_classes=k if self._classification else 2)


    def _fit_sharded(self, binned, Y, base_w, msub: int, ph):
        """Multi-chip fit: pad rows to tile the mesh's data axis (padded
        rows carry zero bag weight) and grow with psum'd histograms.
        Bags/feature subsets come from the SAME generator as the
        single-device path (gbdt_kernels._rf_bag_and_features) so both grow
        from identical randomness; split decisions can still differ at
        rounding margins (bf16 subset histograms vs f32 full-width)."""
        from ..parallel.mesh import pad_to_multiple
        from ..parallel.sharded import grow_forest_sharded
        from .gbdt_kernels import rf_bags_and_features

        n, d = binned.shape
        T = self.num_trees
        BWr, feat_idx = rf_bags_and_features(
            self.seed, T, n, d, msub, self.subsample_rate)
        BW = np.asarray(base_w, np.float32)[None, :] * BWr
        masks = np.zeros((T, d), bool)
        np.put_along_axis(masks, feat_idx, True, axis=1)
        ndata = self.mesh.shape[self.mesh.axis_names[0]]
        binned_h = _binned_host_padded(binned, ndata)
        count_fresh("tree.bags", BW.nbytes)          # the weighted bags
        BW, _ = pad_to_multiple(BW, ndata, axis=1)   # zero weight on pad
        Y_h, _ = pad_to_multiple(np.asarray(Y, np.float32), ndata, axis=0)
        ph.to("tree.fit.grow")
        return grow_forest_sharded(
            binned_h, Y_h, BW, masks, self.mesh,
            max_depth=self.max_depth, n_bins=self.max_bins, lam=1e-3,
            min_info_gain=self.min_info_gain,
            min_instances=float(self.min_instances_per_node),
            onehot_targets=self._classification)


class OpRandomForestClassifier(_RandomForestBase):
    _op_name = "randomForestCls"
    _classification = True


class OpRandomForestRegressor(_RandomForestBase):
    _op_name = "randomForestReg"
    _classification = False


class OpDecisionTreeClassifier(OpRandomForestClassifier):
    """Single unbagged tree (OpDecisionTreeClassifier parity)."""

    _op_name = "decisionTreeCls"

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(num_trees=1, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain, subsample_rate=1.0,
                         feature_subset_strategy="all", seed=seed, uid=uid)
        # single tree: no bootstrap
        self.subsample_rate = 0.0

    def _fit_phased(self, X, y, w, ph):
        # bypass Poisson bagging: weight 1 everywhere
        self_copy = self
        n, d = X.shape
        edges, binned = _prep_tree_inputs(X, self.max_bins)
        base_w = (np.ones(n, np.float32) if w is None
                  else np.asarray(w, np.float32))
        if self._classification:
            k = max(int(y.max()) + 1, 2)
            Y = np.eye(k, dtype=np.float32)[y.astype(int)]
        else:
            k = 1
            Y = y[:, None].astype(np.float32)
        G = jnp.asarray(Y * base_w[:, None])
        H = jnp.asarray(np.repeat(base_w[:, None], k, axis=1))
        wj = jnp.asarray(base_w)
        ph.to("tree.fit.grow")
        f, th, lf = grow_tree(
            binned, G, H, wj, max_depth=self.max_depth,
            n_bins=self.max_bins, lam=1e-3, min_info_gain=self.min_info_gain,
            min_instances=float(self.min_instances_per_node),
            newton_leaf=False)
        ph.to("tree.fit.fetch")
        mode = "rf_cls" if self._classification else "rf_reg"
        return TreeEnsembleModel(
            mode=mode, edges=edges, feat=np.asarray(f)[None],
            thresh=np.asarray(th)[None], leaf=np.asarray(lf)[None],
            n_classes=k if self._classification else 2)


class OpDecisionTreeRegressor(OpDecisionTreeClassifier):
    _op_name = "decisionTreeReg"
    _classification = False


class _GBTBase(PredictorEstimator):
    """Gradient-boosted trees (binary logistic / multiclass softmax / squared).

    Spark-GBT parameterisation (maxIter, stepSize, maxDepth) with XGBoost
    extras (reg_lambda, min_child_weight, gamma->min_split_gain, subsample,
    colsample, early stopping).
    """

    _op_name = "gbt"
    _objective = "binary"  # or "regression", "multiclass"

    def __init__(self, max_iter: int = 20, max_depth: int = 5,
                 step_size: float = 0.1, max_bins: int = 32,
                 reg_lambda: float = 1.0, min_child_weight: float = 1.0,
                 min_info_gain: float = 0.0, subsample_rate: float = 1.0,
                 colsample: float = 1.0,
                 early_stopping_rounds: int = 0,
                 validation_fraction: float = 0.2,
                 min_instances_per_node: int = 1,
                 min_split_gain_raw: float = 0.0,
                 seed: int = 42, hist_precision: str = "bf16",
                 sparse_default_direction: bool = False,
                 uid: Optional[str] = None):
        super().__init__(operation_name=self._op_name, uid=uid)
        self.max_iter = max_iter
        self.max_depth = max_depth
        self.step_size = step_size
        self.max_bins = max_bins
        self.reg_lambda = reg_lambda
        self.min_child_weight = min_child_weight
        self.min_info_gain = min_info_gain
        self.subsample_rate = subsample_rate
        self.colsample = colsample
        self.early_stopping_rounds = early_stopping_rounds
        self.validation_fraction = validation_fraction
        self.min_instances_per_node = min_instances_per_node
        #: XGBoost's gamma: RAW loss-reduction threshold (not Spark's
        #: per-node-weight minInfoGain)
        self.min_split_gain_raw = min_split_gain_raw
        self.seed = seed
        #: XGBoost missing-value semantics: each split also learns a
        #: default direction for the bin-0 (missing/absent) bucket by
        #: trying both routings in the gain search — the actual sparsity
        #: feature of the C++ core (OpXGBoostClassifier.scala:47 wraps it).
        #: Default ON for the XGB-parameterised estimators, OFF for the
        #: Spark-GBT-parity ones (MLlib has no default direction).
        self.sparse_default_direction = sparse_default_direction
        #: 'bf16' (default) or 'f32': histogram one-hot/dot precision.
        #: bf16 halves the (rows, bins·features) one-hot stream — the
        #: kernel's bandwidth floor — and runs the dots at ~2x MXU
        #: throughput.  RF always ran it (integer channels, exact); for
        #: GBT's continuous compounding gradients the default is backed by
        #: the measured quality gate in tests/test_bf16_gate.py (holdout
        #: AuPR/RMSE deltas inside seed noise).  Set 'f32' to opt out.
        self.hist_precision = hist_precision
        self.mesh = None

    def _hist_bf16(self) -> bool:
        """The STATIC hist-precision flag handed to the jitted growth
        programs: requested precision AND the backend gate, resolved here
        so it participates in the jit cache key (resolving inside the
        traced body let a CPU-traced f32 executable be reused under a bf16
        key — ADVICE r4)."""
        from .gbdt_kernels import _accel_bf16

        return self.hist_precision == "bf16" and _accel_bf16()

    def streaming_bin_edges(self, chunks, hist_bins: int = 0) -> np.ndarray:
        """Quantile bin edges from CHUNKED feature matrices — the sketch
        half of an external-memory tree fit (arXiv:1806.11248): per-feature
        ``StreamingHistogram`` sketches absorb (n, D) chunks, then edges
        come from the sketch quantiles (``gbdt_kernels.
        quantile_bins_streaming``; documented rank tolerance ~0.05 at the
        default ``8 * max_bins`` sketch budget).  The tree growth itself
        consumes the materialized packed matrix (the two-pass driver's
        output), exactly like the paper's split."""
        from .gbdt_kernels import (quantile_bins_streaming,
                                   streaming_histograms_for)

        hists = streaming_histograms_for(
            chunks, hist_bins=hist_bins or 8 * self.max_bins)
        return quantile_bins_streaming(hists, self.max_bins)

    def with_mesh(self, mesh) -> "_GBTBase":
        """Multi-chip boosting: the binned matrix, labels and per-row state
        (margins, gradients) live row-sharded on the mesh's data axis and
        every boosting iteration's histogram/gradient programs run under
        GSPMD, which inserts the ICI reductions (the XLA analogue of
        XGBoost's Rabit allreduce, SURVEY §2.11-2.12).  Padded rows carry
        zero training weight, so results match the single-device fit."""
        self.mesh = mesh
        return self

    def fit_columns(self, data: ColumnarDataset, label_col, features_col):
        X = np.asarray(features_col.values, dtype=np.float32)
        y = np.nan_to_num(np.asarray(label_col.values, dtype=np.float32))
        return self.fit_raw(X, y)

    def fit_raw(self, X: np.ndarray, y: np.ndarray, w=None):
        with _phases("tree.fit.prepare", cat="fit") as ph:
            return self._fit_phased(X, y, w, ph)

    def _fit_phased(self, X, y, w, ph):
        """``fit_raw``'s body, under the ``tree.fit.*`` spans as
        ``_RandomForestBase._fit_phased`` is."""
        n, d = X.shape
        if self.mesh is None:
            # wide mostly-zero matrices sketch their edges over the
            # nonzero values (XGBoost-core parity, SURVEY §2.11)
            edges, binned = _prep_tree_inputs_sparse(X, self.max_bins)
        else:
            # the sweep's preparation where the memo holds it, else the
            # mesh-sharded sketch over ICI
            edges, binned = _prep_tree_inputs_mesh(X, self.max_bins,
                                                   self.mesh)
        rng = np.random.default_rng(self.seed)
        base_w = (np.ones(n, np.float32) if w is None
                  else np.asarray(w, np.float32))

        use_es = self.early_stopping_rounds > 0
        if use_es:
            val = rng.random(n) < self.validation_fraction
            train_w = base_w * (~val)
        else:
            val = np.zeros(n, bool)
            train_w = base_w

        obj = self._objective
        Y = None
        if obj == "multiclass":
            k = max(int(y.max()) + 1, 2)
            Y = np.eye(k, dtype=np.float32)[y.astype(int)]
            base = np.zeros(k, np.float32)
        elif obj == "binary":
            k = 1
            pos = float((base_w * y).sum())
            tot = float(base_w.sum())
            p0 = min(max(pos / max(tot, 1e-9), 1e-6), 1 - 1e-6)
            base = np.float32(np.log(p0 / (1 - p0)))
        else:
            k = 1
            base = np.float32((base_w @ y) / max(base_w.sum(), 1e-9))

        if self.mesh is not None:
            # row-shard the boosting state over the mesh's data axis; zero
            # weight on padded rows keeps histograms identical
            from ..parallel.mesh import data_sharding, pad_to_multiple

            ndata = self.mesh.shape[self.mesh.axis_names[0]]
            y_h, _ = pad_to_multiple(np.asarray(y, np.float32), ndata)
            tw_h, _ = pad_to_multiple(np.asarray(train_w, np.float32), ndata)
            ds = data_sharding(self.mesh)
            # content-memoized sharded uploads: a sweep probes with the same
            # fold matrices for every grid candidate, and the winner's refit
            # finds the binned matrix its group placed
            binned, n_pad = _binned_sharded(binned, self.mesh)
            yj = _dev_memo_sharded(y_h, ds, "gbt_y")
            twj = _dev_memo_sharded(tw_h, ds, "gbt_w")
            if obj == "multiclass":
                Y_h, _ = pad_to_multiple(Y, ndata, axis=0)
                Yj = _dev_memo_sharded(Y_h, ds, "gbt_Y")
            else:
                Yj = None
            # no explicit mesh context needed: the committed shardings on
            # these inputs propagate through every jitted program below and
            # GSPMD inserts the cross-device reductions
            F = jax.device_put(np.full((n_pad, k), base, np.float32), ds)
        else:
            yj = jnp.asarray(y, jnp.float32)
            Yj = jnp.asarray(Y) if obj == "multiclass" else None
            twj = jnp.asarray(train_w)
            F = jnp.full((n, k), base, jnp.float32)

        if (self.mesh is None and self.subsample_rate >= 1.0
                and self.colsample >= 1.0
                and obj in ("binary", "regression")):
            # no per-round host RNG: the whole fit runs as scan-chunked
            # launches (the 1-chain case of the grid group's kernel): one
            # dispatch per chunk of rounds, not one per round
            return self._fit_scan_chunks(binned, edges, yj, twj, obj,
                                         float(base), use_es,
                                         np.where(val)[0],
                                         integer_weights=bool(
                                             (train_w == np.floor(train_w))
                                             .all()),
                                         hx=_content_hash(_as_f32(X)),
                                         ph=ph)

        feats, threshs, leaves = [], [], []
        best_metric, best_len, stall = -np.inf, 0, 0
        val_idx = np.where(val)[0]
        from .gbdt_kernels import default_dir_mask
        # default-direction eligibility from the bin edges (pinned-zero
        # features only)
        dd = (jnp.asarray(default_dir_mask(edges))
              if self.sparse_default_direction else None)
        # early-stopping metrics fetch in CHUNKS: a per-round host sync
        # would stall the boosting pipeline 200 times per fit; the stall
        # decision replays per-round on host from the fetched
        # chunk, so best_len (and the truncated model) is unchanged — at
        # most chunk-1 extra rounds of compute are grown then discarded
        es_chunk = max(1, min(8, self.early_stopping_rounds))
        # hoisted: re-uploading the index vector every round is a per-round
        # transfer the chunked sync is meant to remove
        vi_dev = (jnp.asarray(val_idx, jnp.int32)
                  if use_es and len(val_idx) else None)
        pending: list = []
        lagged: list = []
        stop = False
        ph.to("tree.fit.grow")
        for it in range(self.max_iter):
            G, H = _grad_hess(obj, F, yj, Yj, twj)
            bw = twj
            if self.subsample_rate < 1.0:
                # draw over the REAL rows (same rng stream as the
                # single-device fit), then pad for the sharded state
                sub = (rng.random(n) < self.subsample_rate).astype(np.float32)
                if len(sub) < int(twj.shape[0]):
                    sub = np.pad(sub, (0, int(twj.shape[0]) - len(sub)))
                bw = twj * jnp.asarray(sub)
                G, H = _grad_hess(obj, F, yj, Yj, bw)
            mask = np.ones(d, bool)
            if self.colsample < 1.0:
                mask = np.zeros(d, bool)
                msub = max(1, int(d * self.colsample))
                mask[rng.choice(d, msub, replace=False)] = True
            f, th, lf = grow_tree(
                binned, G, H, bw, max_depth=self.max_depth,
                n_bins=self.max_bins, lam=self.reg_lambda,
                min_child_weight=self.min_child_weight,
                min_info_gain=self.min_info_gain,
                min_instances=float(self.min_instances_per_node),
                feat_mask=jnp.asarray(mask), newton_leaf=True,
                learning_rate=self.step_size,
                min_gain_raw=self.min_split_gain_raw,
                hist_bf16=self._hist_bf16(),
                default_dir=self.sparse_default_direction, dd_mask=dd)
            from .gbdt_kernels import predict_tree

            heap_depth = int(np.log2(f.shape[0] + 1))
            F = F + predict_tree(binned, f, th, lf, heap_depth)
            # trees stay device-resident: a per-iteration np.asarray is a
            # host sync — 3 fetches × max_iter per fit
            feats.append(f)
            threshs.append(th)
            leaves.append(lf)
            if use_es and len(val_idx):
                pending.append((len(feats),
                                self._eval_metric_dev(F, yj, vi_dev)))
                if len(pending) >= es_chunk:
                    # LAGGED fetch: materialize the chunk enqueued one chunk
                    # ago (finished ~es_chunk rounds back — near-free sync)
                    # instead of blocking on the fresh one, which would
                    # serialize the boosting pipeline on the fetch round trip
                    best_metric, best_len, stall, stop = _es_patience(
                        _materialize_es(lagged, overlapped=True),
                        best_metric, best_len,
                        stall, self.early_stopping_rounds)
                    lagged, pending = pending, []
                    if stop:
                        break
        if use_es and len(val_idx) and not stop:
            # drain the in-flight chunks so best_len is exact
            best_metric, best_len, stall, _ = _es_patience(
                _materialize_es(lagged + pending), best_metric, best_len,
                stall, self.early_stopping_rounds)
        if use_es and best_len:
            feats, threshs, leaves = (feats[:best_len], threshs[:best_len],
                                      leaves[:best_len])
        ph.to("tree.fit.fetch")
        mode = {"binary": "gbdt_binary", "multiclass": "gbdt_multi",
                "regression": "gbdt_reg"}[obj]
        return TreeEnsembleModel(
            mode=mode, edges=edges, feat=jnp.stack(feats),
            thresh=jnp.stack(threshs), leaf=jnp.stack(leaves),
            base_score=float(base) if k == 1 else 0.0,
            n_classes=(k if obj == "multiclass" else 2))

    def _fit_scan_chunks(self, binned, edges, yj, twj, obj: str,
                         base: float, use_es: bool, val_idx,
                         integer_weights: bool = True,
                         hx: Optional[str] = None, *, ph):
        """Whole-fit scan-chunked boosting: es_chunk rounds per launch via
        ``_gbt_chain_rounds_jit`` with S=1 — the same kernel, patience rule
        and masked trimming as the batched GBT grid group, so the two paths
        cannot diverge.  Requires subsample/colsample == 1 (no per-round
        host RNG) and a single device.

        The tree fast path composes here: EFB (``_maybe_bundle``) shrinks
        the histogram width before any launch and the grown splits
        unbundle back to original columns at the end; GOSS
        (``goss_plan``) engages for deep fits (max_depth >= 8), growing
        each round's tree on a gradient-selected row gather; bf16
        histogram accumulation rides ``TMOG_MATRIX_PRECISION=bf16``."""
        from ..utils.profiling import launch
        from .gbdt_kernels import (_gbt_chain_rounds_jit,
                                   _resolve_compile_depth, default_dir_mask,
                                   goss_plan, hist_accum_bf16,
                                   unbundle_ensemble)

        n = int(binned.shape[0])
        dd_host = (default_dir_mask(edges)
                   if self.sparse_default_direction else None)
        bundles = None
        bend = None
        if _efb_enabled() and hx is not None:
            eb = _maybe_bundle(hx, edges, binned, self.max_bins)
            if eb is not None:
                bundles, binned, bend = eb
                if dd_host is not None:
                    dd_host = bundles.bundled_dd_mask(dd_host)
        dd = jnp.asarray(dd_host) if dd_host is not None else None
        goss = goss_plan(n, self.max_depth)
        acc = hist_accum_bf16()
        # family compile-depth hint: sequential-fallback candidates of
        # differing max_depth share ONE compiled scan program (their own
        # depth rides the traced depth limit) instead of recompiling the
        # whole n-rounds scan per distinct depth (ADVICE r3)
        heap_depth = _resolve_compile_depth(self.max_depth)
        # XGB-style gating (min_child_weight + gamma) with no count-based
        # gates: the count histogram channel is inert — drop it (1/3 off
        # the per-chain histogram cost; gbdt_kernels bag_mode='newton').
        # Integer weights only: the count channel is WEIGHTED, so with
        # fractional sample weights 'CL >= 1' can gate a split that
        # dropping the channel would allow (code-review r4)
        skip_counts = (float(self.min_instances_per_node) <= 1
                       and float(self.min_info_gain) == 0.0
                       and integer_weights)
        es_chunk = max(1, min(8, self.early_stopping_rounds or 8))
        run_es = use_es and len(val_idx) > 0
        vi_arr = (jnp.asarray(val_idx, jnp.int32) if run_es
                  else jnp.zeros(1, jnp.int32))
        Fm = jnp.full((1, n), base, jnp.float32)
        W1 = twj[None, :]

        def one(v):
            return jnp.full((1,), v, jnp.float32)

        depth1 = jnp.full((1,), self.max_depth, jnp.int32)
        lagged: list = []
        best_metric = np.full(1, -np.inf)
        best_len_a = np.zeros(1, np.int32)
        stall_a = np.zeros(1, np.int32)
        stopped = np.zeros(1, bool)
        fb, tb, lb = [], [], []
        n_rounds = 0
        ph.to("tree.fit.grow")
        for ci in range(-(-self.max_iter // es_chunk)):
            with launch("gbt_rounds"):
                Fm, fs, ts, lfs, ms = _gbt_chain_rounds_jit(
                    binned, yj, W1, Fm, vi_arr, depth1,
                    one(self.reg_lambda), one(self.min_child_weight),
                    one(self.min_info_gain),
                    one(self.min_instances_per_node),
                    one(self.step_size), one(self.min_split_gain_raw),
                    es_chunk, heap_depth, self.max_bins, obj,
                    self._hist_bf16(), run_es, skip_counts=skip_counts,
                    default_dir=self.sparse_default_direction, dd_mask=dd,
                    bundle_end=bend, acc_bf16=acc, goss=goss,
                    goss_seed=jnp.int32(self.seed),
                    chain_ids=jnp.zeros(1, jnp.int32),
                    round_offset=jnp.int32(n_rounds))
            fb.append(fs)
            tb.append(ts)
            lb.append(lfs)
            start = n_rounds
            n_rounds += es_chunk
            if run_es:
                pending = [(start + j + 1, ms[j]) for j in range(es_chunk)
                           if start + j + 1 <= self.max_iter]
                if es_patience_vec(_materialize_es(lagged, overlapped=True),
                                   stopped,
                                   best_metric, best_len_a, stall_a,
                                   self.early_stopping_rounds):
                    break
                lagged = pending
        if run_es and not stopped.all():
            es_patience_vec(_materialize_es(lagged), stopped, best_metric,
                            best_len_a, stall_a, self.early_stopping_rounds)
        if run_es and best_len_a[0]:
            best_len = int(best_len_a[0])
        else:
            best_len = n_rounds
        best_len = min(best_len, self.max_iter)
        ph.to("tree.fit.fetch")
        feat = jnp.concatenate(fb)[:best_len, 0]
        thresh = jnp.concatenate(tb)[:best_len, 0]
        leaf = jnp.concatenate(lb)[:best_len, 0]
        if bundles is not None:
            # splits grown in bundled column space map back to original
            # (feature, threshold) pairs — the persisted model routes on
            # the ORIGINAL edges/binned matrix
            feat, thresh = unbundle_ensemble(
                bundles, np.asarray(feat), np.asarray(thresh))
            leaf = np.asarray(leaf)
        mode = "gbdt_binary" if obj == "binary" else "gbdt_reg"
        return TreeEnsembleModel(
            mode=mode, edges=edges, feat=feat, thresh=thresh, leaf=leaf,
            base_score=base, n_classes=2)

    def _eval_metric_dev(self, F, yj, val_idx):
        """Early-stopping metric as a device scalar (sync is the caller's)."""
        from ..evaluators.metrics import _aupr_dev

        vi = (val_idx if isinstance(val_idx, jax.Array)
              else jnp.asarray(val_idx, jnp.int32))
        if self._objective == "binary":
            return _aupr_dev(yj[vi], jax.nn.sigmoid(F[vi, 0]))
        if self._objective == "multiclass":
            return jnp.mean((jnp.argmax(F[vi], axis=1)
                             == yj[vi].astype(jnp.int32)).astype(jnp.float32))
        return -jnp.mean((F[vi, 0] - yj[vi]) ** 2)


def _materialize_es(chunk_rows, overlapped: bool = False):
    """Fetch a chunk of (round, device-metric) pairs in ONE sync — THE
    chunk-fetch idiom for both ES paths: metrics may be scalars (single
    chain) or (S,) chain vectors (the batched GBT grid group).  The sync
    books queue-drain separately from the byte transfer (fetch_timed);
    ``overlapped=True`` is the LAGGED call sites' booking (the next
    chunk's rounds are already enqueued behind these values, so the wait
    runs under live compute — ``overlapSecs``, not ``drainSecs``), while
    the end-of-fit drain of the in-flight chunk stays a genuine drain."""
    if not chunk_rows:
        return []
    from ..utils.profiling import fetch_timed
    vals = fetch_timed(jnp.stack([m for _, m in chunk_rows]),
                       tag="gbt.es", overlapped=overlapped)
    return [(n_at, m) for (n_at, _), m in zip(chunk_rows, vals)]


def es_patience_vec(rows, stopped, best_metric, best_len, stall,
                    patience: int) -> bool:
    """THE early-stopping patience rule (improve/stall/stop), vectorized
    over chains: single-estimator fits are the 1-chain case
    (``_es_patience``) and the batched GBT grid group replays whole chain
    chunks through it, so the two paths cannot desynchronize.  ``rows`` is
    a list of (round, metric-vector) pairs; the state arrays mutate in
    place.  Returns True when every chain has stopped."""
    for n_at, mrow in rows:
        live = ~stopped
        better = live & (mrow > best_metric + 1e-9)
        best_metric[better] = mrow[better]
        best_len[better] = n_at
        stall[better] = 0
        stall[live & ~better] += 1
        stopped |= stall >= patience
    return bool(stopped.all())


def _es_patience(rows, best_metric, best_len, stall, patience):
    """Single-chain view of ``es_patience_vec`` (same rule, scalar state)."""
    bm = np.asarray([best_metric], np.float64)
    bl = np.asarray([best_len], np.int64)
    st = np.asarray([stall], np.int64)
    stopped = np.zeros(1, bool)
    es_patience_vec([(n, np.asarray([m])) for n, m in rows],
                    stopped, bm, bl, st, patience)
    return float(bm[0]), int(bl[0]), int(st[0]), bool(stopped[0])


def _grad_hess(obj, F, y, Y, w):
    if obj == "binary":
        p = jax.nn.sigmoid(F[:, 0])
        g = (w * (p - y))[:, None]
        h = (w * jnp.maximum(p * (1 - p), 1e-6))[:, None]
        return g, h
    if obj == "multiclass":
        P = jax.nn.softmax(F, axis=1)
        g = w[:, None] * (P - Y)
        h = w[:, None] * jnp.maximum(P * (1 - P), 1e-6)
        return g, h
    g = (w * (F[:, 0] - y))[:, None]
    h = w[:, None]
    return g, h


class OpGBTClassifier(_GBTBase):
    """Binary GBT (OpGBTClassifier parity; Spark GBT supports binary only)."""
    _op_name = "gbtCls"
    _objective = "binary"


class OpGBTRegressor(_GBTBase):
    _op_name = "gbtReg"
    _objective = "regression"


class OpXGBoostClassifier(_GBTBase):
    """XGBoost-parameterised boosted classifier (binary or multiclass).

    Defaults follow the reference's XGB defaults for binary selection
    (DefaultSelectorParams: NumRound=200, Eta=0.02, MaxDepth=10,
    MinChildWeight in {1,10}, Gamma=0.8, aucpr early stopping after 20).
    """

    _op_name = "xgbCls"
    _objective = "binary"

    def __init__(self, num_round: int = 200, eta: float = 0.02,
                 max_depth: int = 10, min_child_weight: float = 1.0,
                 gamma: float = 0.8, reg_lambda: float = 1.0,
                 subsample: float = 1.0, colsample_bytree: float = 1.0,
                 max_bins: int = 32, early_stopping_rounds: int = 20,
                 num_class: int = 0, seed: int = 42,
                 hist_precision: str = "bf16",
                 sparse_default_direction: bool = True,
                 uid: Optional[str] = None):
        super().__init__(
            max_iter=num_round, max_depth=max_depth, step_size=eta,
            max_bins=max_bins, reg_lambda=reg_lambda,
            min_child_weight=min_child_weight,
            min_split_gain_raw=gamma, subsample_rate=subsample,
            colsample=colsample_bytree,
            early_stopping_rounds=early_stopping_rounds, seed=seed,
            hist_precision=hist_precision,
            sparse_default_direction=sparse_default_direction, uid=uid)
        self.num_round = num_round
        self.eta = eta
        self.gamma = gamma
        self.subsample = subsample
        self.colsample_bytree = colsample_bytree
        self.num_class = num_class

    def fit_raw(self, X, y, w=None):
        if self.num_class > 2 or (self.num_class == 0 and y.max() > 1):
            self._objective = "multiclass"
        return super().fit_raw(X, y, w)


class OpXGBoostRegressor(OpXGBoostClassifier):
    _op_name = "xgbReg"
    _objective = "regression"

    def fit_raw(self, X, y, w=None):
        self._objective = "regression"
        return _GBTBase.fit_raw(self, X, y, w)
