"""A plain float64 NumPy histogram GBT / random forest: the reference the
tree cells' quality bands come from.

Greedy level-wise growth over ``max_bins`` quantile bins with the standard
second-order gain ``GL^2/(HL+lam) + GR^2/(HR+lam) - G^2/(H+lam)``; no GOSS,
no chunking, no bf16, no device.  It follows the published descriptions
(XGBoost's histogram method; Spark MLlib's random forest with Poisson
bagging) and this program's stated choices where the descriptions leave
one: a full heap-laid tree, ties to the lowest threshold then the lowest
feature, the feature subset of a forest drawn per tree.  It shares no code
with ``models/gbdt_kernels.py``.

As a script it prints the reference AuPR a traffic file's band is built on:

  python perfbench/reference/hist_gbt.py --kind xgb --rows 50000 --cols 500 \
      --depth 10 --rounds 8 --seed 1
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def quantile_edges(X: np.ndarray, max_bins: int) -> np.ndarray:
    """``(D, max_bins - 1)`` float32 edges at the equally spaced quantiles
    of each column; repeated edges become +inf (unused bins)."""
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    edges = np.quantile(np.asarray(X, np.float32), qs, axis=0).T
    edges = edges.astype(np.float32)
    dup = np.c_[np.zeros(len(edges), bool), np.diff(edges, axis=1) <= 1e-7]
    return np.where(dup, np.inf, edges)


def bin_matrix(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Bin of every value: the number of edges strictly below it."""
    X = np.asarray(X, np.float32)
    out = np.empty(X.shape, np.int16)
    for j in range(X.shape[1]):
        out[:, j] = np.searchsorted(edges[j], X[:, j], side="left")
    return out


def grow_tree(binned, G, H, C, depth: int, max_bins: int, lam: float,
              min_child_weight: float = 0.0, gamma: float = 0.0,
              min_info_gain: float = 0.0, min_instances: float = 0.0,
              features=None):
    """One tree.  ``G``/``H`` are ``(N, K)`` float64, ``C`` the ``(N,)``
    row weights.  Returns ``(feat, thresh, node_of_row)`` with ``feat`` /
    ``thresh`` in heap layout (``thresh == max_bins``: no split)."""
    n, d = binned.shape
    k = G.shape[1]
    B = max_bins
    cols = np.arange(d) if features is None else np.asarray(features)
    feat = np.zeros(2 ** depth - 1, np.int64)
    thresh = np.full(2 ** depth - 1, B, np.int64)
    node = np.zeros(n, np.int64)
    for level in range(depth):
        m = 2 ** level
        gain = np.full((m, B, len(cols)), -np.inf)
        for ci, j in enumerate(cols):
            key = node * B + binned[:, j]
            hG = np.stack([np.bincount(key, G[:, c], m * B)
                           for c in range(k)], -1).reshape(m, B, k)
            hH = np.stack([np.bincount(key, H[:, c], m * B)
                           for c in range(k)], -1).reshape(m, B, k)
            hC = np.bincount(key, C, m * B).reshape(m, B)
            GL, HL, CL = hG.cumsum(1), hH.cumsum(1), hC.cumsum(1)
            Gt, Ht, Ct = GL[:, -1:], HL[:, -1:], CL[:, -1:]
            GR, HR, CR = Gt - GL, Ht - HL, Ct - CL
            g = (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                 - Gt ** 2 / (Ht + lam)).sum(-1)
            ok = ((HL.min(-1) >= min_child_weight)
                  & (HR.min(-1) >= min_child_weight)
                  & (CL >= min_instances) & (CR >= min_instances))
            ok[:, B - 1] = False
            gain[:, :, ci] = np.where(ok, g, -np.inf)
            if ci == 0:
                node_w = np.maximum(Ct[:, 0], 1e-12)
        flat = gain.reshape(m, -1)
        best = flat.argmax(1)
        bg = flat[np.arange(m), best]
        ok = ((bg > 0) & np.isfinite(bg) & (bg / node_w >= min_info_gain)
              & (bg >= gamma))
        f_l = np.where(ok, cols[best % len(cols)], 0)
        t_l = np.where(ok, best // len(cols), B)
        feat[m - 1:2 * m - 1] = f_l
        thresh[m - 1:2 * m - 1] = t_l
        right = binned[np.arange(n), f_l[node]] > t_l[node]
        node = 2 * node + right
    return feat, thresh, node


def fit_gbt(X, y, depth: int, rounds: int, eta: float, max_bins: int = 32,
            lam: float = 1.0, min_child_weight: float = 1.0,
            gamma: float = 0.0):
    """Binary logistic GBT.  Returns ``(edges, base, trees)``, a tree being
    ``(feat, thresh, leaf)`` with ``leaf`` of shape ``(2^depth,)``."""
    y = np.asarray(y, np.float64)
    edges = quantile_edges(X, max_bins)
    binned = bin_matrix(X, edges)
    p0 = float(np.clip(y.mean(), 1e-6, 1 - 1e-6))
    base = float(np.log(p0 / (1 - p0)))
    F = np.full(len(y), base)
    ones = np.ones(len(y))
    trees = []
    for _ in range(rounds):
        p = 1 / (1 + np.exp(-F))
        g, h = p - y, p * (1 - p)
        feat, thresh, node = grow_tree(
            binned, g[:, None], h[:, None], ones, depth, max_bins, lam,
            min_child_weight=min_child_weight, gamma=gamma)
        Gs = np.bincount(node, g, 2 ** depth)
        Hs = np.bincount(node, h, 2 ** depth)
        leaf = -eta * Gs / (Hs + lam)
        F = F + leaf[node]
        trees.append((feat, thresh, leaf))
    return edges, base, trees


def fit_rf(X, y, depth: int, n_trees: int, max_bins: int = 32,
           min_instances: float = 1.0, min_info_gain: float = 0.0,
           seed: int = 0):
    """Binary random forest: Poisson(1) bagging, sqrt(D) features a tree,
    class-histogram leaves.  Returns ``(edges, trees)`` with ``leaf`` of
    shape ``(2^depth, 2)``."""
    y = np.asarray(y, np.int64)
    rng = np.random.default_rng(seed)
    edges = quantile_edges(X, max_bins)
    binned = bin_matrix(X, edges)
    n, d = binned.shape
    Y = np.eye(2)[y]
    trees = []
    for _ in range(n_trees):
        w = rng.poisson(1.0, n).astype(np.float64)
        cols = np.sort(rng.choice(d, max(1, int(np.sqrt(d))), replace=False))
        feat, thresh, node = grow_tree(
            binned, Y * w[:, None], np.repeat(w[:, None], 2, 1), w, depth,
            max_bins, 1e-3, min_info_gain=min_info_gain,
            min_instances=min_instances, features=cols)
        cls = np.stack([np.bincount(node, Y[:, c] * w, 2 ** depth)
                        for c in range(2)], -1)
        leaf = cls / np.maximum(cls.sum(-1, keepdims=True), 1e-12)
        trees.append((feat, thresh, leaf))
    return edges, trees


def route(X, edges, trees, depth: int) -> np.ndarray:
    """``(T, N)`` leaf index of every row in every tree."""
    binned = bin_matrix(X, edges)
    rows = np.arange(len(binned))
    out = []
    for feat, thresh, _ in trees:
        node = np.zeros(len(binned), np.int64)
        for _ in range(depth):
            f, t = feat[node], thresh[node]
            node = 2 * node + 1 + (binned[rows, f] > t)
        out.append(node - (2 ** depth - 1))
    return np.asarray(out)


def predict_gbt(X, edges, base, trees, depth: int) -> np.ndarray:
    leaves = route(X, edges, trees, depth)
    z = base + sum(t[2][leaves[i]] for i, t in enumerate(trees))
    return 1 / (1 + np.exp(-z))


def predict_rf(X, edges, trees, depth: int) -> np.ndarray:
    leaves = route(X, edges, trees, depth)
    p = sum(t[2][leaves[i]] for i, t in enumerate(trees)) / len(trees)
    return p[:, 1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kind", choices=("xgb", "rf"), required=True)
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--hold-rows", type=int, default=20_000)
    ap.add_argument("--cols", type=int, default=500)
    ap.add_argument("--depth", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=8,
                    help="boosting rounds, or trees of the forest")
    ap.add_argument("--eta", type=float, default=0.02)
    ap.add_argument("--gamma", type=float, default=0.8)
    ap.add_argument("--min-child-weight", type=float, default=1.0)
    ap.add_argument("--min-instances", type=float, default=10.0)
    ap.add_argument("--min-info-gain", type=float, default=0.001)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--weights-seed", type=int, default=11)
    a = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from perfbench.generators.planted_linear import generate
    from perfbench.reference.oracle import aupr, oracle_aupr

    frame, beta = generate(a.rows + a.hold_rows, a.cols, a.seed,
                           weights_seed=a.weights_seed)
    A = frame.to_numpy(np.float32)
    X, y = A[:a.rows, 1:], A[:a.rows, 0]
    Xh, yh = A[a.rows:, 1:], A[a.rows:, 0]
    if a.kind == "xgb":
        edges, base, trees = fit_gbt(
            X, y, a.depth, a.rounds, a.eta, gamma=a.gamma,
            min_child_weight=a.min_child_weight)
        p = predict_gbt(Xh, edges, base, trees, a.depth)
    else:
        edges, trees = fit_rf(X, y, a.depth, a.rounds,
                              min_instances=a.min_instances,
                              min_info_gain=a.min_info_gain, seed=a.seed)
        p = predict_rf(Xh, edges, trees, a.depth)
    print(json.dumps({"kind": a.kind, "rows": a.rows, "cols": a.cols,
                      "depth": a.depth, "rounds": a.rounds, "seed": a.seed,
                      "holdout_aupr": aupr(yh, p),
                      "oracle_aupr": oracle_aupr(Xh, yh, beta)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
