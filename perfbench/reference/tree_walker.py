"""A plain float64 NumPy walker over a fitted tree ensemble.

Independent of the program's scorers (``predict_ensemble`` on the device,
the native C++ kernels): one row at a time it bins the raw features by the
ensemble's quantile edges, walks each heap-laid tree from the root and
combines the leaves as the ensemble's mode says.

Layout (``models/gbdt_kernels.TreeEnsemble``): ``feat``/``thresh`` are
``(T, 2^d - 1)``, node ``i`` has children ``2i+1`` and ``2i+2``; ``leaf`` is
``(T, 2^d, K)``; a row's bin is the number of edges below its value; it goes
right iff ``bin > thresh``; a negative ``thresh`` is a default-direction
split (threshold ``-t-1``, and bin 0 goes right).
"""
from __future__ import annotations

import numpy as np


def leaf_sums(X, edges, feat, thresh, leaf) -> np.ndarray:
    """``(rows, K)``: the sum over the trees of the leaf each row reaches."""
    X = np.asarray(X, np.float32)
    edges = np.asarray(edges, np.float32)
    feat, thresh = np.asarray(feat), np.asarray(thresh)
    leaf = np.asarray(leaf, np.float64)
    n_trees, n_internal = feat.shape
    depth = int(np.log2(n_internal + 1))
    out = np.zeros((len(X), leaf.shape[2]), np.float64)
    for r, x in enumerate(X):
        bins = (x[:, None] > edges).sum(axis=1)
        for t in range(n_trees):
            node = 0
            for _ in range(depth):
                th = int(thresh[t, node])
                b = int(bins[feat[t, node]])
                right = (b > -th - 1 or b == 0) if th < 0 else b > th
                node = 2 * node + 1 + int(right)
            out[r] += leaf[t, node - n_internal]
    return out


def probability_1(X, edges, feat, thresh, leaf, mode: str,
                  base_score: float = 0.0) -> np.ndarray:
    """P(class 1) of every row of ``X`` under a binary ensemble of mode
    ``rf_cls`` or ``gbdt_binary``."""
    if mode not in ("rf_cls", "gbdt_binary"):
        raise ValueError(f"no walker for ensemble mode {mode!r}")
    n_trees = np.asarray(feat).shape[0]
    acc = leaf_sums(X, edges, feat, thresh, leaf)
    out = np.empty(len(acc), np.float64)
    for r, a in enumerate(acc):
        if mode == "rf_cls":
            p = np.clip(a / n_trees, 1e-9, 1.0)
            out[r] = p[1] / p.sum()
        else:
            out[r] = 1.0 / (1.0 + np.exp(-(a[0] + base_score)))
    return out


def prediction(X, edges, feat, thresh, leaf, mode: str,
               base_score: float = 0.0) -> np.ndarray:
    """The prediction of every row of ``X`` under a regression ensemble:
    the mean of the trees' leaves for a forest (``rf_reg``), the base plus
    their sum for boosted trees (``gbdt_reg``)."""
    if mode not in ("rf_reg", "gbdt_reg"):
        raise ValueError(f"no walker for ensemble mode {mode!r}")
    acc = leaf_sums(X, edges, feat, thresh, leaf)[:, 0]
    if mode == "rf_reg":
        return acc / np.asarray(feat).shape[0] + base_score
    return acc + base_score
