"""Statistics kernels — matmul/segment-sum formulations for TPU.

Reference: ``OpStatistics`` (utils/stats/OpStatistics.scala:39-202 —
correlations, chi-square, Cramér's V, pointwise mutual information) and the
column statistics used by ``SanityChecker.fitFn``
(core/.../impl/preparators/SanityChecker.scala:380-470).

Everything is one or two MXU matmuls over the (N, D) feature matrix:
 * colStats: count/mean/var/min/max via reductions
 * Pearson: gram matrix of standardized columns
 * Spearman: same on rank-transformed columns (sort-based ranks, SURVEY §7d)
 * chi²/Cramér's V: contingency tables via one-hot matmuls
In multi-chip mode these reduce over a batch-sharded mesh with psum
(see transmogrifai_tpu.parallel).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ColStats", "col_stats", "pearson_with_label", "pearson_matrix",
           "spearman_with_label", "ranks", "cramers_v", "chi_square",
           "contingency_stats"]


class ColStats(NamedTuple):
    count: jnp.ndarray
    mean: jnp.ndarray
    variance: jnp.ndarray
    min: jnp.ndarray
    max: jnp.ndarray
    num_nonzero: jnp.ndarray


@jax.jit
def col_stats(X: jnp.ndarray, sample_weight: Optional[jnp.ndarray] = None) -> ColStats:
    """Per-column stats (Statistics.colStats parity), weighted for CV masks."""
    X = jnp.asarray(X, jnp.float32)
    n, d = X.shape
    w = (jnp.ones(n, jnp.float32) if sample_weight is None
         else jnp.asarray(sample_weight, jnp.float32))
    wsum = jnp.maximum(w.sum(), 1e-12)
    mean = (w @ X) / wsum
    var = (w @ (X - mean) ** 2) / jnp.maximum(wsum - 1.0, 1.0)
    big = jnp.float32(3.4e38)
    wpos = w > 0
    mn = jnp.min(jnp.where(wpos[:, None], X, big), axis=0)
    mx = jnp.max(jnp.where(wpos[:, None], X, -big), axis=0)
    nnz = (w @ (X != 0).astype(jnp.float32))
    return ColStats(wsum, mean, var, mn, mx, nnz)


@jax.jit
def pearson_with_label(X: jnp.ndarray, y: jnp.ndarray,
                       sample_weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """corr(x_j, y) for every column — one matvec (SanityChecker's
    correlationsWithLabel via OpStatistics.computeCorrelationsWithLabel)."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    n = X.shape[0]
    w = (jnp.ones(n, jnp.float32) if sample_weight is None
         else jnp.asarray(sample_weight, jnp.float32))
    wsum = jnp.maximum(w.sum(), 1e-12)
    mx = (w @ X) / wsum
    my = jnp.dot(w, y) / wsum
    Xc = X - mx
    yc = y - my
    cov = (w * yc) @ Xc / wsum
    vx = (w @ Xc ** 2) / wsum
    vy = jnp.dot(w, yc ** 2) / wsum
    return cov / jnp.sqrt(jnp.maximum(vx * vy, 1e-24))


@jax.jit
def pearson_matrix(X: jnp.ndarray,
                   sample_weight: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full (D, D) correlation matrix — one gram matmul on the MXU."""
    X = jnp.asarray(X, jnp.float32)
    n = X.shape[0]
    w = (jnp.ones(n, jnp.float32) if sample_weight is None
         else jnp.asarray(sample_weight, jnp.float32))
    wsum = jnp.maximum(w.sum(), 1e-12)
    mx = (w @ X) / wsum
    Xc = (X - mx) * jnp.sqrt(w)[:, None]
    cov = Xc.T @ Xc / wsum
    sd = jnp.sqrt(jnp.maximum(jnp.diag(cov), 1e-24))
    return cov / jnp.outer(sd, sd)


@jax.jit
def ranks(x: jnp.ndarray) -> jnp.ndarray:
    """Average ranks (ties get midranks) via double argsort + segment means."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    order = jnp.argsort(x)
    xs = x[order]
    is_new = jnp.concatenate([jnp.ones(1, bool), xs[1:] != xs[:-1]])
    gid = jnp.cumsum(is_new) - 1
    pos = jnp.arange(1, n + 1, dtype=jnp.float32)
    gsum = jax.ops.segment_sum(pos, gid, num_segments=n)
    gcnt = jax.ops.segment_sum(jnp.ones(n, jnp.float32), gid, num_segments=n)
    midrank = gsum / jnp.maximum(gcnt, 1.0)
    r_sorted = midrank[gid]
    return jnp.zeros(n, jnp.float32).at[order].set(r_sorted)


@jax.jit
def spearman_with_label(X: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Spearman corr per column: Pearson on rank transforms (vmapped sort)."""
    Xr = jax.vmap(ranks, in_axes=1, out_axes=1)(jnp.asarray(X, jnp.float32))
    yr = ranks(jnp.asarray(y, jnp.float32))
    return pearson_with_label(Xr, yr)


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cols"))
def _contingency(row_idx, col_idx, w, n_rows: int, n_cols: int):
    tbl = jnp.zeros((n_rows, n_cols), jnp.float32)
    return tbl.at[row_idx, col_idx].add(w)


def contingency_stats(table: np.ndarray) -> Dict[str, float]:
    """chi², p-value proxy, Cramér's V, PMI from a contingency table.

    OpStatistics.contingencyStats parity (utils/stats/OpStatistics.scala:188).
    """
    t = np.asarray(table, np.float64)
    n = t.sum()
    if n <= 0 or t.shape[0] < 2 or t.shape[1] < 2:
        return {"chi2": 0.0, "cramersV": 0.0, "n": float(n)}
    row = t.sum(axis=1, keepdims=True)
    col = t.sum(axis=0, keepdims=True)
    expected = row @ col / n
    with np.errstate(divide="ignore", invalid="ignore"):
        chi2 = np.nansum(np.where(expected > 0,
                                  (t - expected) ** 2 / expected, 0.0))
    k = min(t.shape[0], t.shape[1])
    phi2 = chi2 / n
    cramers = float(np.sqrt(phi2 / max(k - 1, 1)))
    # pointwise mutual information per cell (log2, as in reference)
    with np.errstate(divide="ignore", invalid="ignore"):
        joint = t / n
        pmi = np.where(joint > 0,
                       np.log2(joint / np.maximum(expected / n, 1e-300)), 0.0)
    return {"chi2": float(chi2), "cramersV": min(cramers, 1.0),
            "n": float(n), "pmi": pmi}


def chi_square(labels: np.ndarray, indicator: np.ndarray,
               n_label_classes: int) -> Dict[str, float]:
    """Chi² of a binary indicator column vs the label."""
    tbl = np.asarray(_contingency(
        jnp.asarray(labels, jnp.int32),
        jnp.asarray((indicator > 0).astype(np.int32)),
        jnp.ones(len(labels), jnp.float32), n_label_classes, 2))
    return contingency_stats(tbl)


def cramers_v(labels: np.ndarray, group_indicators: np.ndarray,
              n_label_classes: int) -> Dict[str, float]:
    """Cramér's V for a categorical group given its one-hot indicator block.

    ``group_indicators``: (N, C) one-hot columns of one categorical feature
    (from vector metadata grouping).  The contingency table is a single
    matmul: labels_onehot.T @ indicators.
    """
    # host numpy: the table is tiny (K × C) and an un-jitted device matmul
    # costs several op-by-op dispatches per call; one bincount-style
    # product wins
    L = np.eye(n_label_classes, dtype=np.float32)[np.asarray(labels, np.int64)]
    G = np.asarray(group_indicators, np.float32)
    tbl = L.T @ G
    return contingency_stats(tbl)
