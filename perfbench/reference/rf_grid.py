"""A plain float64 NumPy random-forest GRID: every candidate of a
(max_depth x min_info_gain x min_instances_per_node) grid grown at ITS OWN
depth, fold by fold, with nothing shared between candidates.

The program (``selector/grid_groups.RFGridGroup``) grows one base forest a
gate pair at the deepest depth and reads the shallower candidates off
per-level leaf snapshots.  This file is what that sharing must equal: the
straightforward thing, a forest a candidate and fold.  No JAX, no import
from ``transmogrifai_tpu``, no randomness of its own: bags and feature
subsets are INPUTS (``grow_forest`` takes them, the CLI draws them with
NumPy), so the reference shares no generator with the program either.

One tree, level by level, over the tree's ``msub`` subset columns only:

* histograms by ``np.add.at``: per (node, subset slot, bin) the bag-weighted
  count of every class;
* gain of "bin <= t goes left" = sum over classes of ``GL^2/(CL+lam) +
  GR^2/(CR+lam) - G^2/(C+lam)`` (``lam`` 1e-3; the class sums' Gini form up
  to the regulariser), ties to the lowest threshold, then the lowest subset
  SLOT (the subset's order is the caller's);
* the published gates: both children hold at least ``min_instances`` of bag
  weight (Spark ``minInstancesPerNode``), and ``gain / node weight >=
  min_info_gain`` (Spark ``minInfoGain`` on the per-instance impurity);
* the published leaf rule: a leaf's value is its bag-weighted class
  distribution; the forest averages its trees' distributions.

Layout (as ``reference/tree_walker.py`` reads it): ``feat`` / ``thresh`` are
heap-laid ``(T, 2^depth - 1)``, node ``i`` has children ``2i+1``, ``2i+2``; a
row goes right iff ``bin > thresh``; a node that does not split carries
``thresh == n_bins`` (every row goes left) and the subset's first column as
its feature; ``leaf`` is ``(T, 2^depth, K)``, zero where no row lands.

Departures from upstream's forest (Spark MLlib ``RandomForest``), each the
program's own stated choice: (1) Poisson bags multiply the rows' weights
and are drawn once a tree id (upstream: Poisson(subsamplingRate) a row and
tree as well, but from its own generator); (2) growth is level-wise over a
full heap to ``depth`` (upstream grows node groups off a queue, also
breadth-first, and stops at ``maxDepth``; the trees are the same where no
memory limit splits a level); (3) split candidates are ``n_bins - 1``
quantile-bin edges a column computed ONCE for the table
(``hist_gbt.quantile_edges``; upstream's ``findSplits`` samples its
candidate thresholds too), and the feature subset is drawn once a TREE,
not once a node (upstream: ``featureSubsetStrategy`` a node).

As a script it runs the 18-point default grid with 3 stratified folds on
``planted_linear`` rows and prints every candidate's CV AuPR and the
winner's hold-out AuPR (a traffic file's quality band is built on it):

  python perfbench/reference/rf_grid.py --rows 50000 --cols 500 --trees 8 \\
      --seed 1 --weights-seed 11
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

import numpy as np

LAM = 1e-3

#: upstream DefaultSelectorParams.scala:36-75, the random-forest grid
DEFAULT_GRID = {"max_depth": [3, 6, 12],
                "min_info_gain": [0.001, 0.01, 0.1],
                "min_instances_per_node": [10, 100]}


def grow_tree(binned, y, weight, subset, depth: int, min_info_gain: float,
              min_instances: float, n_bins: int, n_classes: int = 2):
    """One tree on rows weighted ``weight`` (fold weight x bag), splitting
    on the columns ``subset`` only.  ``(feat, thresh, leaf)``."""
    subset = np.asarray(subset, np.int64)
    B, K, msub = n_bins, n_classes, len(subset)
    feat = np.full(2 ** depth - 1, subset[0], np.int64)
    thresh = np.full(2 ** depth - 1, B, np.int64)
    # rows of zero weight (held-out fold, Poisson 0) count nowhere
    live = np.nonzero(weight > 0)[0]
    sub = np.asarray(binned)[np.ix_(live, subset)].astype(np.int64)
    w = np.asarray(weight, np.float64)[live]
    cls = np.asarray(y, np.int64)[live]
    node = np.zeros(len(live), np.int64)
    slots = np.arange(msub)[None, :]
    for level in range(depth):
        m = 2 ** level
        hist = np.zeros((K, m * msub * B))
        where = ((node[:, None] * msub + slots) * B + sub).ravel()
        for c in range(K):
            np.add.at(hist[c], where, np.repeat(w * (cls == c), msub))
        # (K, m, msub, B) -> cumulative over bins: "bin <= t goes left"
        GL = hist.reshape(K, m, msub, B).cumsum(-1)
        Gt = GL[..., -1:]
        CL, Ct = GL.sum(0), Gt.sum(0)
        GR, CR = Gt - GL, Ct - CL
        gain = (GL ** 2 / (CL + LAM) + GR ** 2 / (CR + LAM)
                - Gt ** 2 / (Ct + LAM)).sum(0)
        ok = (CL >= min_instances) & (CR >= min_instances)
        ok[..., B - 1] = False
        gain = np.where(ok, gain, -np.inf)
        # candidates in (threshold, slot) order: ties to the lowest
        # threshold, then the lowest slot
        flat = gain.transpose(0, 2, 1).reshape(m, B * msub)
        best = flat.argmax(1)
        best_gain = flat[np.arange(m), best]
        node_w = np.maximum(Ct[:, 0, 0], 1e-12)
        split = ((best_gain > 0) & np.isfinite(best_gain)
                 & (best_gain / node_w >= min_info_gain))
        slot_l = np.where(split, best % msub, 0)
        t_l = np.where(split, best // msub, B)
        feat[m - 1:2 * m - 1] = subset[slot_l]
        thresh[m - 1:2 * m - 1] = t_l
        right = sub[np.arange(len(live)), slot_l[node]] > t_l[node]
        node = 2 * node + right
        if not split.any():
            # nothing splits below a level where nothing split
            node = node << (depth - level - 1)
            break
    sums = np.zeros((2 ** depth, K))
    np.add.at(sums, (node, cls), w)
    leaf = sums / np.maximum(sums.sum(-1, keepdims=True), 1e-12)
    return feat, thresh, leaf


def split_gain(bins, y, weight, t: int, n_classes: int = 2):
    """``(gain, left weight, right weight)`` of sending the rows with
    ``bins <= t`` left, for one node's rows and one column: what
    ``grow_tree`` maximises, by itself (a parity report gives the gains of
    two splits that differ)."""
    bins, y = np.asarray(bins), np.asarray(y, np.int64)
    w = np.asarray(weight, np.float64)
    G = np.array([w[y == c].sum() for c in range(n_classes)])
    GL = np.array([w[(y == c) & (bins <= t)].sum() for c in range(n_classes)])
    GR, C, CL = G - GL, G.sum(), GL.sum()
    gain = (GL ** 2 / (CL + LAM) + GR ** 2 / (C - CL + LAM)
            - G ** 2 / (C + LAM)).sum()
    return float(gain), float(CL), float(C - CL)


def grow_forest(binned, y, fold_weight, bags, subsets, depth: int,
                min_info_gain: float, min_instances: float, n_bins: int,
                n_classes: int = 2):
    """One candidate's forest on one fold, at the candidate's OWN depth:
    tree ``t`` sees ``fold_weight * bags[t]`` and the columns
    ``subsets[t]``.  ``(feat, thresh, leaf)`` stacked over the trees."""
    fold_weight = np.asarray(fold_weight, np.float64)
    trees = [grow_tree(binned, y, fold_weight * np.asarray(bag, np.float64),
                       subset, depth, min_info_gain, min_instances, n_bins,
                       n_classes)
             for bag, subset in zip(bags, subsets)]
    return tuple(np.stack(part) for part in zip(*trees))


def predict(binned, feat, thresh, leaf) -> np.ndarray:
    """P(class 1) of every binned row: the trees' leaf distributions
    averaged, clipped and normalised (the walker's ``rf_cls``)."""
    binned = np.asarray(binned)
    n_trees, internal = feat.shape
    depth = int(np.log2(internal + 1))
    rows = np.arange(len(binned))
    acc = np.zeros((len(binned), leaf.shape[2]))
    for t in range(n_trees):
        node = np.zeros(len(binned), np.int64)
        for _ in range(depth):
            node = 2 * node + 1 + (binned[rows, feat[t, node]]
                                   > thresh[t, node])
        acc += leaf[t, node - internal]
    p = np.clip(acc / n_trees, 1e-9, 1.0)
    return p[:, 1] / p.sum(1)


def grid_points(grid: dict = DEFAULT_GRID) -> list:
    """The grid's points in the order the selector enumerates them: the
    axes in the dict's order, the last axis fastest."""
    return [dict(zip(grid, values))
            for values in itertools.product(*grid.values())]


def cv_grid(binned, y, folds, bags, subsets, points, n_bins: int,
            score) -> np.ndarray:
    """``(candidates, folds)`` of ``score(y, p, eval_weight)`` for every
    candidate grown on every fold: ``folds`` is a list of ``(train_weight,
    eval_weight)`` row-weight pairs."""
    out = np.empty((len(points), len(folds)))
    for ci, p in enumerate(points):
        for fi, (w_train, w_eval) in enumerate(folds):
            forest = grow_forest(
                binned, y, w_train, bags, subsets, p["max_depth"],
                p["min_info_gain"], p["min_instances_per_node"], n_bins)
            out[ci, fi] = score(y, predict(binned, *forest), w_eval)
    return out


def stratified_folds(y, k: int, rng) -> list:
    """``k`` stratified folds as ``(train_weight, eval_weight)`` pairs of
    0/1 row weights."""
    fold = np.empty(len(y), np.int64)
    for c in np.unique(y):
        idx = rng.permutation(np.nonzero(y == c)[0])
        fold[idx] = np.arange(len(idx)) % k
    return [((fold != f).astype(np.float64), (fold == f).astype(np.float64))
            for f in range(k)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=50_000)
    ap.add_argument("--hold-rows", type=int, default=50_000)
    ap.add_argument("--cols", type=int, default=500)
    ap.add_argument("--trees", type=int, default=8)
    ap.add_argument("--folds", type=int, default=3)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--weights-seed", type=int, default=11)
    a = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from perfbench.generators.planted_linear import generate
    from perfbench.reference.hist_gbt import bin_matrix, quantile_edges
    from perfbench.reference.oracle import aupr, oracle_aupr

    frame, beta = generate(a.rows + a.hold_rows, a.cols, a.seed,
                           weights_seed=a.weights_seed)
    A = frame.to_numpy(np.float32)
    X, y = A[:a.rows, 1:], A[:a.rows, 0].astype(np.int64)
    Xh, yh = A[a.rows:, 1:], A[a.rows:, 0]
    edges = quantile_edges(X, a.bins)
    binned, binned_h = bin_matrix(X, edges), bin_matrix(Xh, edges)
    # the caller's randomness: folds, Poisson(1) bags, sqrt(cols) subsets
    rng = np.random.default_rng(a.seed)
    folds = stratified_folds(y, a.folds, rng)
    bags = rng.poisson(1.0, (a.trees, a.rows)).astype(np.float64)
    msub = max(1, int(np.sqrt(a.cols)))
    subsets = [rng.choice(a.cols, msub, replace=False)
               for _ in range(a.trees)]
    points = grid_points()

    def fold_aupr(y_, p, w_eval):
        keep = w_eval > 0
        return aupr(y_[keep], p[keep])

    cv = cv_grid(binned, y, folds, bags, subsets, points, a.bins, fold_aupr)
    mean = cv.mean(1)
    win = int(mean.argmax())
    p = points[win]
    forest = grow_forest(binned, y, np.ones(a.rows), bags, subsets,
                         p["max_depth"], p["min_info_gain"],
                         p["min_instances_per_node"], a.bins)
    print(json.dumps({
        "rows": a.rows, "cols": a.cols, "trees": a.trees, "seed": a.seed,
        "msub": msub,
        "cv_aupr": [[pt, round(float(v), 6)] for pt, v in zip(points, mean)],
        "cv_aupr_lowest": float(mean.min()),
        "cv_aupr_highest": float(mean.max()),
        "winner": p,
        "holdout_aupr": aupr(yh, predict(binned_h, *forest)),
        "positives": float(y.mean()),
        "oracle_aupr": oracle_aupr(Xh, yh, beta)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
