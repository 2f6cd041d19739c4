"""Metric kernels — numpy for host-resident inputs, JAX for device-resident.

Reference: OpBinaryClassificationEvaluator (AuROC, AuPR, precision/recall/F1,
Brier, threshold metrics — core/.../evaluators/OpBinaryClassificationEvaluator.scala:56,192-223),
OpMultiClassificationEvaluator, OpRegressionEvaluator, OpForecastEvaluator
(SMAPE/MASE).

All binary metrics are computed from one descending sort of the scores —
the TPU-friendly replacement for Spark's `BinaryClassificationMetrics`
thresholded RDD sweeps.  Weighted variants support the CV fold-mask design.

Dispatch: metrics are O(N log N) scalar reductions, so HOST-RESIDENT inputs
always take the numpy path — an XLA metric program costs an upload + a
per-shape compile + a fetch for milliseconds of math.  Device-resident inputs (the sweep's score vectors)
use the jitted sort-based kernels so nothing is fetched per candidate.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: Metrics where SMALLER is better — the single source of truth for
#: selection direction (ModelSelector.larger_better, SelectedModelCombiner).
MINIMIZE_METRICS = (
    "RootMeanSquaredError", "MeanSquaredError", "MeanAbsoluteError",
    "Error", "LogLoss", "BrierScore", "SMAPE", "MASE", "SeasonalError",
)

__all__ = [
    "MINIMIZE_METRICS",
    "auroc", "aupr", "binary_metrics_at_threshold", "brier_score", "log_loss",
    "binary_classification_metrics", "multiclass_metrics",
    "multiclass_threshold_metrics",
    "regression_metrics", "forecast_metrics", "threshold_curves",
]


def _on_host(*arrays) -> bool:
    """Host numpy metrics for HOST-RESIDENT inputs of any size: a 1M-row
    numpy sort is ~0.2 s, while routing host data through the device costs
    an upload + a per-shape XLA compile + a fetch.  The jitted kernels are
    for inputs that ALREADY live on device (sweep score vectors), where the
    fetch is the expensive side."""
    return all(a is None or isinstance(a, np.ndarray) or np.isscalar(a)
               or isinstance(a, (list, tuple)) for a in arrays)


def _weights(y, w):
    y = jnp.asarray(y, jnp.float32)
    if w is None:
        w = jnp.ones_like(y)
    else:
        w = jnp.asarray(w, jnp.float32)
    return y, w


def _np_weights(y, w):
    y = np.asarray(y, np.float64)
    w = np.ones_like(y) if w is None else np.asarray(w, np.float64)
    return y, w


def auroc(y_true, y_score, sample_weight=None):
    """Weighted AUC = P(s+ > s-) + 0.5 P(s+ = s-) over score tie groups."""
    if _on_host(y_true, y_score, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        s = np.asarray(y_score, np.float64)
        order = np.argsort(s, kind="stable")
        s_sorted = s[order]
        wy = (w * y)[order]
        wn = (w * (1 - y))[order]
        is_new = np.concatenate([[True], s_sorted[1:] != s_sorted[:-1]])
        starts = np.flatnonzero(is_new)
        pos_g = np.add.reduceat(wy, starts)
        neg_g = np.add.reduceat(wn, starts)
        neg_below = np.cumsum(neg_g) - neg_g
        num = float(np.sum(pos_g * (neg_below + 0.5 * neg_g)))
        denom = max(float(wy.sum()) * float(wn.sum()), 1e-12)
        return float(np.clip(num / denom, 0.0, 1.0))
    return _auroc_dev(y_true, y_score, sample_weight)


@jax.jit
def _auroc_dev(y_true, y_score, sample_weight=None) -> jnp.ndarray:
    y, w = _weights(y_true, sample_weight)
    s = jnp.asarray(y_score, jnp.float32)
    n = s.shape[0]
    order = jnp.argsort(s)
    s_sorted = s[order]
    wy = (w * y)[order]
    wn = (w * (1 - y))[order]
    is_new = jnp.concatenate([jnp.ones(1, bool), s_sorted[1:] != s_sorted[:-1]])
    gid = jnp.cumsum(is_new) - 1  # tie-group id per element
    pos_g = jax.ops.segment_sum(wy, gid, num_segments=n)
    neg_g = jax.ops.segment_sum(wn, gid, num_segments=n)
    neg_below = jnp.cumsum(neg_g) - neg_g
    w_pos = jnp.sum(wy)
    w_neg = jnp.sum(wn)
    num = jnp.sum(pos_g * (neg_below + 0.5 * neg_g))
    return jnp.clip(num / jnp.maximum(w_pos * w_neg, 1e-12), 0.0, 1.0)


def aupr(y_true, y_score, sample_weight=None):
    """Area under precision-recall via descending-score sweep (average-
    precision style, matches sklearn/Spark)."""
    if _on_host(y_true, y_score, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        s = np.asarray(y_score, np.float64)
        order = np.argsort(-s, kind="stable")
        s_sorted = s[order]
        wy = (w * y)[order]
        ww = w[order]
        is_new = np.concatenate([[True], s_sorted[1:] != s_sorted[:-1]])
        starts = np.flatnonzero(is_new)
        pos_g = np.add.reduceat(wy, starts)
        tot_g = np.add.reduceat(ww, starts)
        tp = np.cumsum(pos_g)
        all_pred = np.cumsum(tot_g)
        pos = max(float(wy.sum()), 1e-12)
        precision = tp / np.maximum(all_pred, 1e-12)
        return float(np.clip(np.sum((pos_g / pos) * precision), 0.0, 1.0))
    return _aupr_dev(y_true, y_score, sample_weight)


@jax.jit
def _aupr_dev(y_true, y_score, sample_weight=None) -> jnp.ndarray:
    # (the scope is inside the jitted body: a jit traces with a fresh name
    # stack, so a scope round ``binary_metric_grid`` would not reach the ops)
    with jax.named_scope("metric.grid"):
        y, w = _weights(y_true, sample_weight)
        s = jnp.asarray(y_score, jnp.float32)
        n = s.shape[0]
        order = jnp.argsort(-s)
        s_sorted = s[order]
        wy = (w * y)[order]
        ww = w[order]
        # evaluate precision/recall only at distinct-threshold boundaries
        is_new = jnp.concatenate(
            [jnp.ones(1, bool), s_sorted[1:] != s_sorted[:-1]])
        gid = jnp.cumsum(is_new) - 1
        pos_g = jax.ops.segment_sum(wy, gid, num_segments=n)
        tot_g = jax.ops.segment_sum(ww, gid, num_segments=n)
        tp = jnp.cumsum(pos_g)
        all_pred = jnp.cumsum(tot_g)
        pos = jnp.maximum(jnp.sum(wy), 1e-12)
        precision = tp / jnp.maximum(all_pred, 1e-12)
        dr = pos_g / pos
        return jnp.clip(jnp.sum(dr * precision), 0.0, 1.0)


def _grid_by_fold(fn, y, scores, weights):
    """``fn(labels, scores, weights)`` over a grid whose folds each bring
    their own rows: (F, L) labels mapped over the fold axis together with
    the (F, C, L) scores and the (F, L) weights -> (F, C)."""
    return jax.vmap(lambda y_f, s_f, w_f:
                    jax.vmap(lambda s: fn(y_f, s, w_f))(s_f))(
                        y, scores, weights)


def binary_metric_grid(y_true, scores, weights, metric: str):
    """Batched device metric for a validation sweep: ``scores`` (F, C, N)
    per-(fold, candidate) score rows and ``weights`` (F, N) per-fold eval
    weights (broadcast over candidates — never replicated) against one
    shared label vector, or against (F, N) labels, a row a fold (each
    fold's own rows: ``grid_groups._fold_eval_rows``) -> (F, C) device
    metric values, or None when ``metric`` has no device kernel (callers
    fall back to per-candidate host metrics)."""
    fn = {"AuPR": _aupr_dev, "AuROC": _auroc_dev}.get(metric)
    if fn is None:
        return None
    y = jnp.asarray(y_true, jnp.float32)
    if y.ndim == 2:
        return _grid_by_fold(fn, y, scores, weights)
    return jax.vmap(lambda s_f, w_f:
                    jax.vmap(lambda s: fn(y, s, w_f))(s_f))(scores, weights)


def _regression_metric_dev(y, p, w, metric: str):
    """THE weighted regression metric kernel — shared by the sequential
    sweep path (ModelSelector._metric_device) and the batched grid."""
    ws = jnp.maximum(w.sum(), 1e-12)
    err = p - y
    if metric == "MeanAbsoluteError":
        return (w * jnp.abs(err)).sum() / ws
    mse = (w * err ** 2).sum() / ws
    if metric == "MeanSquaredError":
        return mse
    if metric == "RootMeanSquaredError":
        return jnp.sqrt(mse)
    mean = (w * y).sum() / ws
    var = (w * (y - mean) ** 2).sum() / ws
    return 1.0 - mse / jnp.maximum(var, 1e-12)


def regression_metric_grid(y_true, preds, weights, metric: str):
    """Batched device regression metric: (F, C, N) predictions + (F, N)
    weights against (N,) labels, or (F, N) a row a fold -> (F, C) device
    values; None when unsupported."""
    if metric not in ("RootMeanSquaredError", "MeanSquaredError",
                     "MeanAbsoluteError", "R2"):
        return None
    y = jnp.asarray(y_true, jnp.float32)
    if y.ndim == 2:
        return _grid_by_fold(
            lambda y_f, p, w_f: _regression_metric_dev(y_f, p, w_f, metric),
            y, preds, weights)
    return jax.vmap(lambda p_f, w_f: jax.vmap(
        lambda p: _regression_metric_dev(y, p, w_f, metric))(p_f))(
            preds, weights)


_MULTI_GRID_METRICS = ("F1", "Error", "Accuracy", "Precision", "Recall")


def _multiclass_metric_dev(y, p, w, n_classes: int, metric: str):
    """Weighted multiclass metric from int-valued label/prediction vectors —
    confusion matrix as one one-hot matmul (no scatter), shared by the
    batched grid below."""
    ok = ((y >= 0) & (y < n_classes) & (p >= 0) & (p < n_classes)
          ).astype(jnp.float32)
    wk = w * ok
    wsum = jnp.maximum(wk.sum(), 1e-12)
    if metric in ("Accuracy", "Error"):
        acc = jnp.sum(wk * (y == p)) / wsum
        return acc if metric == "Accuracy" else 1.0 - acc
    yo = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)
    po = jax.nn.one_hot(p, n_classes, dtype=jnp.float32)
    conf = jax.lax.dot((yo * wk[:, None]).T, po,
                       precision=jax.lax.Precision.HIGHEST)  # (K, K)
    tp = jnp.diagonal(conf)
    support = conf.sum(axis=1)
    pred_count = conf.sum(axis=0)
    prec_k = tp / jnp.maximum(pred_count, 1e-12)
    rec_k = tp / jnp.maximum(support, 1e-12)
    wts = support / wsum
    if metric == "Precision":
        return jnp.sum(wts * prec_k)
    if metric == "Recall":
        return jnp.sum(wts * rec_k)
    f1_k = 2 * prec_k * rec_k / jnp.maximum(prec_k + rec_k, 1e-12)
    return jnp.sum(wts * f1_k)


def multiclass_metric_grid(y_true, preds, weights, n_classes: int,
                           metric: str):
    """Batched device multiclass metric: (F, C, N) predicted labels (float
    or int) + (F, N) eval weights against one shared label vector, or
    (F, N) labels a row a fold -> (F, C) device values; None when
    ``metric`` has no device kernel."""
    if metric not in _MULTI_GRID_METRICS:
        return None
    y = jnp.asarray(y_true, jnp.int32)
    if y.ndim == 2:
        return _grid_by_fold(
            lambda y_f, p, w_f: _multiclass_metric_dev(
                y_f, jnp.asarray(p, jnp.int32), w_f, n_classes, metric),
            y, preds, weights)
    return jax.vmap(lambda p_f, w_f: jax.vmap(
        lambda p: _multiclass_metric_dev(
            y, jnp.asarray(p, jnp.int32), w_f, n_classes, metric))(p_f))(
            preds, weights)


def binary_metrics_at_threshold(y_true, y_score, threshold=0.5,
                                sample_weight=None):
    if _on_host(y_true, y_score, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        s = np.asarray(y_score, np.float64)
        pred = (s >= threshold).astype(np.float64)
        tp = float(np.sum(w * pred * y))
        fp = float(np.sum(w * pred * (1 - y)))
        fn = float(np.sum(w * (1 - pred) * y))
        tn = float(np.sum(w * (1 - pred) * (1 - y)))
        precision = tp / max(tp + fp, 1e-12)
        recall = tp / max(tp + fn, 1e-12)
        f1 = 2 * precision * recall / max(precision + recall, 1e-12)
        error = (fp + fn) / max(tp + fp + fn + tn, 1e-12)
        return {"Precision": precision, "Recall": recall, "F1": f1,
                "Error": error, "TP": tp, "TN": tn, "FP": fp, "FN": fn}
    return _binary_at_threshold_dev(y_true, y_score, threshold, sample_weight)


@jax.jit
def _binary_at_threshold_dev(y_true, y_score, threshold=0.5,
                             sample_weight=None):
    y, w = _weights(y_true, sample_weight)
    s = jnp.asarray(y_score, jnp.float32)
    pred = (s >= threshold).astype(jnp.float32)
    tp = jnp.sum(w * pred * y)
    fp = jnp.sum(w * pred * (1 - y))
    fn = jnp.sum(w * (1 - pred) * y)
    tn = jnp.sum(w * (1 - pred) * (1 - y))
    precision = tp / jnp.maximum(tp + fp, 1e-12)
    recall = tp / jnp.maximum(tp + fn, 1e-12)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, 1e-12)
    error = (fp + fn) / jnp.maximum(tp + fp + fn + tn, 1e-12)
    return {"Precision": precision, "Recall": recall, "F1": f1,
            "Error": error, "TP": tp, "TN": tn, "FP": fp, "FN": fn}


def brier_score(y_true, y_prob, sample_weight=None):
    if _on_host(y_true, y_prob, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        p = np.asarray(y_prob, np.float64)
        return float(np.sum(w * (p - y) ** 2) / max(np.sum(w), 1e-12))
    return _brier_dev(y_true, y_prob, sample_weight)


@jax.jit
def _brier_dev(y_true, y_prob, sample_weight=None):
    y, w = _weights(y_true, sample_weight)
    p = jnp.asarray(y_prob, jnp.float32)
    return jnp.sum(w * (p - y) ** 2) / jnp.maximum(jnp.sum(w), 1e-12)


def log_loss(y_true, y_prob, sample_weight=None, eps: float = 1e-15):
    if _on_host(y_true, y_prob, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        p = np.clip(np.asarray(y_prob, np.float64), eps, 1 - eps)
        ll = -(y * np.log(p) + (1 - y) * np.log1p(-p))
        return float(np.sum(w * ll) / max(np.sum(w), 1e-12))
    return _log_loss_dev(y_true, y_prob, sample_weight, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _log_loss_dev(y_true, y_prob, sample_weight=None, eps: float = 1e-15):
    y, w = _weights(y_true, sample_weight)
    p = jnp.clip(jnp.asarray(y_prob, jnp.float32), eps, 1 - eps)
    ll = -(y * jnp.log(p) + (1 - y) * jnp.log1p(-p))
    return jnp.sum(w * ll) / jnp.maximum(jnp.sum(w), 1e-12)


def binary_classification_metrics(y_true, y_prob, sample_weight=None,
                                  threshold: float = 0.5) -> Dict[str, float]:
    """Full binary metric set (OpBinaryClassificationEvaluator parity)."""
    at_t = binary_metrics_at_threshold(y_true, y_prob, threshold, sample_weight)
    out = {
        "AuROC": float(auroc(y_true, y_prob, sample_weight)),
        "AuPR": float(aupr(y_true, y_prob, sample_weight)),
        "BrierScore": float(brier_score(y_true, y_prob, sample_weight)),
        "LogLoss": float(log_loss(y_true, y_prob, sample_weight)),
    }
    out.update({k: float(v) for k, v in at_t.items()})
    return out


def threshold_curves(y_true, y_prob, n_thresholds: int = 100,
                     sample_weight=None) -> Dict[str, np.ndarray]:
    """Precision/recall/F1 across a threshold sweep (thresholdMetrics parity)."""
    ts = np.linspace(0.0, 1.0, n_thresholds)
    if _on_host(y_true, y_prob, sample_weight):
        rows = [binary_metrics_at_threshold(y_true, y_prob, t, sample_weight)
                for t in ts]
        return {"thresholds": ts,
                "precisionByThreshold": np.asarray([r["Precision"] for r in rows]),
                "recallByThreshold": np.asarray([r["Recall"] for r in rows]),
                "f1ByThreshold": np.asarray([r["F1"] for r in rows])}
    f = jax.jit(jax.vmap(
        lambda t: _binary_at_threshold_dev(y_true, y_prob, t, sample_weight)
    ))
    res = f(jnp.asarray(ts, jnp.float32))
    return {"thresholds": ts,
            "precisionByThreshold": np.asarray(res["Precision"]),
            "recallByThreshold": np.asarray(res["Recall"]),
            "f1ByThreshold": np.asarray(res["F1"])}


def multiclass_threshold_metrics(y_true, proba, top_ns=(1, 3),
                                 thresholds=None) -> Dict:
    """Top-N / confidence-threshold histograms for multiclass predictions.

    Parity with ``OpMultiClassificationEvaluator.calculateThresholdMetrics``
    (core/.../evaluators/OpMultiClassificationEvaluator.scala:153-240): for
    every topN value and every threshold, counts of rows whose TRUE class
    score is in the row's top-N and above threshold (``correct``), rows
    whose top score clears the threshold but the true class misses the top-N
    or falls below threshold (``incorrect``), and the remainder
    (``noPrediction``); the three sum to N at every threshold.

    TPU redesign of the reference's per-row sort + treeAggregate: the true
    class RANK is two masked reductions (no sort), and each count array is
    one (N,)x(N,T) masked-comparison matmul — the whole computation is a
    handful of fused reductions on device for at-scale inputs.
    """
    thr = (np.arange(0, 101) / 100.0 if thresholds is None
           else np.asarray(thresholds, np.float64))
    if thr.size == 0 or not np.all((thr >= 0) & (thr <= 1)):
        raise ValueError("thresholds must be a non-empty sequence in [0, 1]")
    tns = list(dict.fromkeys(int(t) for t in top_ns))  # order-keeping dedupe
    if not tns or any(t <= 0 for t in tns):
        raise ValueError("top_ns must be a non-empty sequence of positive "
                         "integers")
    on_host = _on_host(y_true, None) and not isinstance(proba, jax.Array)
    xp = np if on_host else jnp
    P = xp.asarray(proba, xp.float32 if xp is jnp else np.float64)
    y = xp.asarray(y_true, xp.int32 if xp is jnp else np.int64)
    n, k = P.shape
    lbl = xp.clip(y, 0, k - 1)
    seen = (y >= 0) & (y < k)  # unseen classes score 0 (reference :192)
    rows = xp.arange(n)
    true_score = xp.where(seen, P[rows, lbl], 0.0)
    top_score = P.max(axis=1)
    # stable-descending rank of the true class: scores strictly greater,
    # plus equal scores at earlier indices (matches the reference's stable
    # sortBy(-score) take(t) membership)
    gt = (P > true_score[:, None]).sum(axis=1)
    eq_before = ((P == true_score[:, None])
                 & (xp.arange(k)[None, :] < lbl[:, None])).sum(axis=1)
    rank = xp.where(seen, gt + eq_before, k)
    thr_x = xp.asarray(thr, P.dtype)
    # (N, T): does the true/top score clear each threshold
    true_ge = true_score[:, None] >= thr_x[None, :]
    top_ge = top_score[:, None] >= thr_x[None, :]
    out = {"topNs": tns, "thresholds": [float(t) for t in thr],
           "correctCounts": {}, "incorrectCounts": {},
           "noPredictionCounts": {}}
    for t in tns:
        in_top = (rank < t)
        correct = (in_top[:, None] & true_ge).sum(axis=0)
        incorrect = ((in_top[:, None] & top_ge & ~true_ge)
                     | (~in_top[:, None] & top_ge)).sum(axis=0)
        if xp is jnp:
            correct = np.asarray(correct)
            incorrect = np.asarray(incorrect)
        out["correctCounts"][t] = [int(c) for c in correct]
        out["incorrectCounts"][t] = [int(c) for c in incorrect]
        out["noPredictionCounts"][t] = [int(n - c - i) for c, i
                                        in zip(correct, incorrect)]
    return out


@functools.partial(jax.jit, static_argnames=("n_classes",))
def _multiclass_core(y_true, y_pred, n_classes, sample_weight=None):
    y = jnp.asarray(y_true, jnp.int32)
    p = jnp.asarray(y_pred, jnp.int32)
    w = (jnp.ones(y.shape[0], jnp.float32) if sample_weight is None
         else jnp.asarray(sample_weight, jnp.float32))
    wsum = jnp.maximum(w.sum(), 1e-12)
    correct = (y == p).astype(jnp.float32)
    acc = jnp.sum(w * correct) / wsum
    conf = jnp.zeros((n_classes, n_classes), jnp.float32).at[y, p].add(w)
    tp = jnp.diag(conf)
    support = conf.sum(axis=1)
    pred_count = conf.sum(axis=0)
    prec_k = tp / jnp.maximum(pred_count, 1e-12)
    rec_k = tp / jnp.maximum(support, 1e-12)
    f1_k = 2 * prec_k * rec_k / jnp.maximum(prec_k + rec_k, 1e-12)
    wts = support / wsum
    return {
        "Accuracy": acc,
        "Error": 1.0 - acc,
        "Precision": jnp.sum(wts * prec_k),
        "Recall": jnp.sum(wts * rec_k),
        "F1": jnp.sum(wts * f1_k),
        "confusion": conf,
    }


def multiclass_metrics(y_true, y_pred, n_classes: int,
                       sample_weight=None) -> Dict[str, float]:
    if _on_host(y_true, y_pred, sample_weight):
        y = np.asarray(y_true, np.int64)
        p = np.asarray(y_pred, np.int64)
        w = (np.ones(len(y)) if sample_weight is None
             else np.asarray(sample_weight, np.float64))
        # drop out-of-range labels (e.g. factorize's -1 for NaN) the same way
        # the device kernel's mode="drop" scatter does
        ok = (y >= 0) & (y < n_classes) & (p >= 0) & (p < n_classes)
        y, p, w = y[ok], p[ok], w[ok]
        wsum = max(w.sum(), 1e-12)
        acc = float(np.sum(w * (y == p)) / wsum)
        conf = np.zeros((n_classes, n_classes))
        np.add.at(conf, (y, p), w)
        tp = np.diag(conf)
        support = conf.sum(axis=1)
        pred_count = conf.sum(axis=0)
        prec_k = tp / np.maximum(pred_count, 1e-12)
        rec_k = tp / np.maximum(support, 1e-12)
        f1_k = 2 * prec_k * rec_k / np.maximum(prec_k + rec_k, 1e-12)
        wts = support / wsum
        return {"Accuracy": acc, "Error": 1.0 - acc,
                "Precision": float(np.sum(wts * prec_k)),
                "Recall": float(np.sum(wts * rec_k)),
                "F1": float(np.sum(wts * f1_k)), "confusion": conf}
    res = _multiclass_core(y_true, y_pred, n_classes, sample_weight)
    return {k: (float(v) if k != "confusion" else np.asarray(v))
            for k, v in res.items()}


@jax.jit
def _regression_core(y_true, y_pred, sample_weight=None):
    y, w = _weights(y_true, sample_weight)
    p = jnp.asarray(y_pred, jnp.float32)
    wsum = jnp.maximum(w.sum(), 1e-12)
    err = p - y
    mse = jnp.sum(w * err ** 2) / wsum
    mae = jnp.sum(w * jnp.abs(err)) / wsum
    ym = jnp.sum(w * y) / wsum
    ss_tot = jnp.sum(w * (y - ym) ** 2)
    ss_res = jnp.sum(w * err ** 2)
    r2 = 1.0 - ss_res / jnp.maximum(ss_tot, 1e-12)
    return {"RootMeanSquaredError": jnp.sqrt(mse), "MeanSquaredError": mse,
            "MeanAbsoluteError": mae, "R2": r2}


def regression_metrics(y_true, y_pred, sample_weight=None) -> Dict[str, float]:
    if _on_host(y_true, y_pred, sample_weight):
        y, w = _np_weights(y_true, sample_weight)
        p = np.asarray(y_pred, np.float64)
        wsum = max(w.sum(), 1e-12)
        err = p - y
        mse = float(np.sum(w * err ** 2) / wsum)
        mae = float(np.sum(w * np.abs(err)) / wsum)
        ym = np.sum(w * y) / wsum
        ss_tot = float(np.sum(w * (y - ym) ** 2))
        r2 = 1.0 - float(np.sum(w * err ** 2)) / max(ss_tot, 1e-12)
        return {"RootMeanSquaredError": float(np.sqrt(mse)),
                "MeanSquaredError": mse, "MeanAbsoluteError": mae, "R2": r2}
    return {k: float(v) for k, v in _regression_core(y_true, y_pred, sample_weight).items()}


def forecast_metrics(y_true, y_pred, seasonal_period: int = 1) -> Dict[str, float]:
    """SMAPE + MASE (OpForecastEvaluator parity)."""
    y = np.asarray(y_true, np.float64)
    p = np.asarray(y_pred, np.float64)
    smape = float(np.mean(
        2.0 * np.abs(p - y) / np.maximum(np.abs(p) + np.abs(y), 1e-12)))
    m = seasonal_period
    if len(y) > m:
        scale = np.mean(np.abs(y[m:] - y[:-m]))
        mase = float(np.mean(np.abs(p - y)) / max(scale, 1e-12))
    else:
        mase = float("nan")
    return {"SMAPE": smape, "MASE": mase}
