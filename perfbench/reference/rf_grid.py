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

A regression tree (``regression=True``) keeps every rule above with variance
impurity in place of Gini: ONE channel, the bag-weighted sum of the target
CENTERED by the tree's weighted mean, beside the bag weight (the gain
``SL^2/(CL+lam) + SR^2/(CR+lam) - S^2/(C+lam)`` is the node weight times
the variance it removes, so the gate reads Spark's ``minInfoGain`` for
variance too); a leaf is the weighted mean of the target.  Centering makes
the trees independent of the target's offset: a year-valued target (about
1998) grows the trees that the same target minus its mean grows, where raw
sums would cancel the variance reduction away in the offset's square.

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

With ``--problem regression`` it grows regression forests over 3 random
folds and a third of the columns a tree, and prints every candidate's CV
RMSE and the winner's hold-out RMSE beside the oracle's.  The rows are the
generator's that ``--generator`` names (a file, from the repo's root; a
regression label's must define ``oracle_predict``, the planted mean), drawn
with ``--weights-seed`` and the keyword arguments of ``--params`` (JSON).
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
              min_instances: float, n_bins: int, n_classes: int = 2,
              regression: bool = False):
    """One tree on rows weighted ``weight`` (fold weight x bag), splitting
    on the columns ``subset`` only.  ``(feat, thresh, leaf)``; a regression
    tree's ``leaf`` has one value a node, the weighted mean."""
    subset = np.asarray(subset, np.int64)
    B, K, msub = n_bins, n_classes, len(subset)
    feat = np.full(2 ** depth - 1, subset[0], np.int64)
    thresh = np.full(2 ** depth - 1, B, np.int64)
    # rows of zero weight (held-out fold, Poisson 0) count nowhere
    live = np.nonzero(weight > 0)[0]
    sub = np.asarray(binned)[np.ix_(live, subset)].astype(np.int64)
    w = np.asarray(weight, np.float64)[live]
    if regression:
        target = np.asarray(y, np.float64)[live]
        center = np.sum(w * target) / max(w.sum(), 1e-12)
        # the centered target's weighted sum, and the weight
        channels = [w * (target - center), w]
    else:
        cls = np.asarray(y, np.int64)[live]
        channels = [w * (cls == c) for c in range(K)]
    node = np.zeros(len(live), np.int64)
    slots = np.arange(msub)[None, :]
    for level in range(depth):
        m = 2 ** level
        hist = np.zeros((len(channels), m * msub * B))
        where = ((node[:, None] * msub + slots) * B + sub).ravel()
        for c, v in enumerate(channels):
            np.add.at(hist[c], where, np.repeat(v, msub))
        # (channels, m, msub, B) -> cumulative over bins: "bin <= t goes
        # left"
        cum = hist.reshape(len(channels), m, msub, B).cumsum(-1)
        if regression:
            GL, CL = cum[:1], cum[1]
        else:
            GL = cum
            CL = GL.sum(0)
        Gt, Ct = GL[..., -1:], CL[..., -1:]
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
    if regression:
        sums, weights = np.zeros(2 ** depth), np.zeros(2 ** depth)
        np.add.at(sums, node, w * target)
        np.add.at(weights, node, w)
        return feat, thresh, (sums / np.maximum(weights, 1e-12))[:, None]
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
                n_classes: int = 2, regression: bool = False):
    """One candidate's forest on one fold, at the candidate's OWN depth:
    tree ``t`` sees ``fold_weight * bags[t]`` and the columns
    ``subsets[t]``.  ``(feat, thresh, leaf)`` stacked over the trees."""
    fold_weight = np.asarray(fold_weight, np.float64)
    trees = [grow_tree(binned, y, fold_weight * np.asarray(bag, np.float64),
                       subset, depth, min_info_gain, min_instances, n_bins,
                       n_classes, regression)
             for bag, subset in zip(bags, subsets)]
    return tuple(np.stack(part) for part in zip(*trees))


def _leaf_mean(binned, feat, thresh, leaf) -> np.ndarray:
    """``(rows, K)``: every binned row's leaves averaged over the trees."""
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
    return acc / n_trees


def predict(binned, feat, thresh, leaf) -> np.ndarray:
    """P(class 1) of every binned row: the trees' leaf distributions
    averaged, clipped and normalised (the walker's ``rf_cls``)."""
    p = np.clip(_leaf_mean(binned, feat, thresh, leaf), 1e-9, 1.0)
    return p[:, 1] / p.sum(1)


def predict_mean(binned, feat, thresh, leaf) -> np.ndarray:
    """The prediction of every binned row under a regression forest: the
    trees' leaves averaged (the walker's ``rf_reg``)."""
    return _leaf_mean(binned, feat, thresh, leaf)[:, 0]


def grid_points(grid: dict = DEFAULT_GRID) -> list:
    """The grid's points in the order the selector enumerates them: the
    axes in the dict's order, the last axis fastest."""
    return [dict(zip(grid, values))
            for values in itertools.product(*grid.values())]


def cv_grid(binned, y, folds, bags, subsets, points, n_bins: int,
            score, regression: bool = False) -> np.ndarray:
    """``(candidates, folds)`` of ``score(y, p, eval_weight)`` for every
    candidate grown on every fold: ``folds`` is a list of ``(train_weight,
    eval_weight)`` row-weight pairs."""
    walk = predict_mean if regression else predict
    out = np.empty((len(points), len(folds)))
    for ci, p in enumerate(points):
        for fi, (w_train, w_eval) in enumerate(folds):
            forest = grow_forest(
                binned, y, w_train, bags, subsets, p["max_depth"],
                p["min_info_gain"], p["min_instances_per_node"], n_bins,
                regression=regression)
            out[ci, fi] = score(y, walk(binned, *forest), w_eval)
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


def random_folds(n: int, k: int, rng) -> list:
    """``k`` folds of ``n`` rows drawn at random (a real-valued label has
    no classes to stratify by), as ``stratified_folds`` gives them."""
    fold = np.empty(n, np.int64)
    fold[rng.permutation(n)] = np.arange(n) % k
    return [((fold != f).astype(np.float64), (fold == f).astype(np.float64))
            for f in range(k)]


def _load_generator(path: str):
    import importlib.util

    file = importlib.util.spec_from_file_location("generator", path)
    gen = importlib.util.module_from_spec(file)
    file.loader.exec_module(gen)
    return gen


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
    ap.add_argument("--problem", choices=("binary", "regression"),
                    default="binary")
    ap.add_argument("--generator",
                    default="perfbench/generators/planted_linear.py",
                    help="the generator's file, from the repo's root")
    ap.add_argument("--params", default="{}",
                    help="the generator's keyword arguments, as JSON")
    a = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from perfbench.reference import oracle
    from perfbench.reference.hist_gbt import bin_matrix, quantile_edges

    regression = a.problem == "regression"
    gen = _load_generator(os.path.join(root, a.generator))
    if regression and not hasattr(gen, "oracle_predict"):
        ap.error(f"{a.generator} defines no oracle_predict: a regression "
                 f"label needs its planted mean")
    params = json.loads(a.params)
    frame, planted = gen.generate(a.rows + a.hold_rows, a.cols, a.seed,
                                  weights_seed=a.weights_seed, **params)
    A = frame.to_numpy(np.float32)
    X, Xh, yh = A[:a.rows, 1:], A[a.rows:, 1:], A[a.rows:, 0]
    edges = quantile_edges(X, a.bins)
    binned, binned_h = bin_matrix(X, edges), bin_matrix(Xh, edges)
    # the caller's randomness: folds, Poisson(1) bags, column subsets (a
    # third of the columns for a real-valued label, which has no classes
    # to stratify its folds by; sqrt(cols) for a binary one)
    rng = np.random.default_rng(a.seed)
    if regression:
        y, yh = A[:a.rows, 0].astype(np.float64), yh.astype(np.float64)
        folds = random_folds(a.rows, a.folds, rng)
        msub = max(1, a.cols // 3)
        name, score, walk = "rmse", oracle.rmse, predict_mean
    else:
        y = A[:a.rows, 0].astype(np.int64)
        folds = stratified_folds(y, a.folds, rng)
        msub = max(1, int(np.sqrt(a.cols)))
        name, score, walk = "aupr", oracle.aupr, predict
    bags = rng.poisson(1.0, (a.trees, a.rows)).astype(np.float64)
    subsets = [rng.choice(a.cols, msub, replace=False)
               for _ in range(a.trees)]
    points = grid_points()

    def fold_score(y_, p, w_eval):
        keep = w_eval > 0
        return score(y_[keep], p[keep])

    cv = cv_grid(binned, y, folds, bags, subsets, points, a.bins, fold_score,
                 regression=regression)
    mean = cv.mean(1)
    win = int(mean.argmin() if regression else mean.argmax())
    p = points[win]
    forest = grow_forest(binned, y, np.ones(a.rows), bags, subsets,
                         p["max_depth"], p["min_info_gain"],
                         p["min_instances_per_node"], a.bins,
                         regression=regression)
    out = {"rows": a.rows, "cols": a.cols, "trees": a.trees, "seed": a.seed,
           "msub": msub,
           f"cv_{name}": [[pt, round(float(v), 6)]
                          for pt, v in zip(points, mean)],
           f"cv_{name}_lowest": float(mean.min()),
           f"cv_{name}_highest": float(mean.max()),
           "winner": p,
           f"holdout_{name}": score(yh, walk(binned_h, *forest))}
    if regression:
        out.update(label_sd=float(y.std()), params=params,
                   oracle_rmse=oracle.rmse(yh, gen.oracle_predict(
                       frame.iloc[a.rows:], planted)))
    else:
        out.update(positives=float(y.mean()),
                   oracle_aupr=oracle.oracle_aupr(Xh, yh, planted))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
