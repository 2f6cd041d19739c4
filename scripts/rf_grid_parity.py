#!/usr/bin/env python3
"""Split-level parity of the random-forest grid program with the plain
reference, on the chip, at the timed size of the cell ``dense500-rf-grid18``.

  chiprun -- python scripts/rf_grid_parity.py [--seed N --rows R --cols D
                                               --trees T --out FILE]

For fold 0 and min_instances_per_node 10 it grows, in one call of
``gbdt_kernels.grow_rf_grid`` at depth 12 with the pruning outputs on, the
forest of each min_info_gain of the cell's grid (0.001, 0.01, 0.1).  The
first is what ``RFGridGroup.run`` grows as the BASE of all three since PR 35
(gate sharing); the other two are what it grew for their candidates before.
Every candidate (gate x depth 3 / 6 / 12, each of the forest's trees: one
tree of 22 of 500 columns may hold three splits) is then read off the base
the way the group reads it (``gbdt_kernels.prune_rf_grid``: nodes whose gate
ratio fails the candidate's gate cut, the heap truncated at its depth) and
compared with

 (i) the SAME candidate as the program grows it directly under its own gate
     (sliced heap + that level's values, as the group read a truncated
     candidate before PR 35): every heap node must be EQUAL, leaves within
     ``leaf_atol``;
 (ii) ``perfbench/reference/rf_grid.grow_tree`` grown in float64 NumPy
     directly at the candidate's own gate and depth on the same binned
     matrix, fold weights, bag and feature subset.

The rows are ``planted_linear`` at ``--seed`` (the cell's generator and
planted weights); the matrix is the raw float32 columns (what the cell's
vectorizer and SanityChecker hand the selector for all-``Real`` columns
without nulls: the 500 null indicators are constant and dropped); the row
weights and folds are the selector's own (``DataBalancer``,
``make_folds``), built here as ``ModelSelector.fit_columns`` builds them.

Reported for each candidate: the heap nodes equal to direct growth's, the
share of heap nodes with the reference's (feature, threshold), and the
largest absolute leaf difference of each.  Every node that differs from the
reference where all its ancestors agree (so both sides split the same rows)
is LISTED with both choices and the float64 gain of each on that node's
rows: a tolerance never hides a node.  What may differ, and why: on the chip
the histogram dots take bf16 operands (``_accel_bf16()``), so a row's
class weight (fold weight x Poisson bag) is rounded to 8 bits of mantissa
before it is summed in f32; integer weights survive that exactly, the
balancer's non-integer ones do not (relative 2^-9 a row at most), and the
gain itself is f32 where the reference's is f64.  So two splits of one node
whose gains lie within about 1e-3 of each other, relatively, may come out
in the other order; below such a node the two trees see other rows and
everything differs.  ``TOLERANCE`` states what the script accepts (exit 1
beyond it).  On a CPU (``JAX_PLATFORMS=cpu``, a tiny ``--rows``) the
operands are f32 and the result is no device measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: what the script accepts: every node that differs (ancestors agreeing)
#: has gains within GAIN_RTOL of each other, relatively, and where a whole
#: tree agrees its leaves agree to LEAF_ATOL (class shares in [0, 1]: f32
#: sums of bf16-rounded weights against f64 sums of the exact ones)
TOLERANCE = {"gain_rtol": 2e-3, "leaf_atol": 2e-3}
DEPTHS = (3, 6, 12)
#: the cell's min_info_gain values (the lowest is the base) and the
#: min_instances_per_node of the base pair compared
GATES = (0.001, 0.01, 0.1)
MIN_INSTANCES = 10.0


def selector_weights(y: np.ndarray, folds: int, seed: int):
    """The cell's row weights and fold ids, as the selector makes them."""
    from transmogrifai_tpu.models import OpRandomForestClassifier
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            grid)
    from transmogrifai_tpu.selector.validators import make_folds

    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=folds, seed=seed, models_and_parameters=[
            (OpRandomForestClassifier(), grid(max_depth=[3]))])
    splitter = selector._resolved_splitter()
    n = len(y)
    train_idx, _ = splitter.split_indices(n, y)
    mask = np.zeros(n, bool)
    mask[train_idx] = True
    base_w = np.asarray(splitter.train_weights(y, mask), np.float32)
    val = selector.validator
    fold = make_folds(n, val.num_folds, y=y, stratify=val.stratify,
                      seed=val.seed)
    return base_w, fold


def compare(program, reference, binned, y, weight, n_bins: int):
    """One depth: shares, leaf difference, and the nodes that differ under
    agreeing ancestors, each with the float64 gains of both choices."""
    from perfbench.reference import rf_grid

    (pf, pt, pl), (rf, rt, rl) = program, reference
    same = (pf == rf) & (pt == rt)
    # unsplit nodes carry thresh == n_bins on both sides; their feature id
    # is a convention (the subset's first column), compared all the same
    differing = []
    live = np.nonzero(weight > 0)[0]
    node = np.zeros(len(live), np.int64)          # heap index of each row
    agree = np.ones(1, bool)                      # per node of this level
    depth = int(np.log2(len(pf) + 1))
    for level in range(depth):
        lo = 2 ** level - 1
        ids = np.arange(lo, 2 * lo + 1)
        for i in ids[agree & ~same[ids]]:
            rows = live[node == i]
            both = []
            for f, t in ((pf[i], pt[i]), (rf[i], rt[i])):
                if t >= n_bins:
                    both.append({"feature": int(f), "threshold": int(t),
                                 "gain": None})
                    continue
                g, cl, cr = rf_grid.split_gain(binned[rows, f], y[rows],
                                               weight[rows], int(t))
                both.append({"feature": int(f), "threshold": int(t),
                             "gain": g, "left": cl, "right": cr})
            gp, gr = both[0]["gain"], both[1]["gain"]
            differing.append({"node": int(i), "level": level,
                              "rows": int(len(rows)),
                              "node_weight": float(weight[rows].sum()),
                              "program": both[0], "reference": both[1],
                              "gain_rel_diff": (
                                  None if gp is None or gr is None
                                  else abs(gp - gr) / max(abs(gr), 1e-300))})
        # rows follow the REFERENCE's tree; only agreeing nodes are reported
        right = binned[live, rf[node]] > rt[node]
        node = 2 * node + 1 + right
        agree = np.repeat(agree & same[ids], 2)
    return {"depth": depth, "nodes": int(len(pf)),
            "equal_nodes": int(same.sum()),
            "equal_share": float(same.mean()),
            "split_nodes_reference": int((rt < n_bins).sum()),
            "split_nodes_program": int((pt < n_bins).sum()),
            "leaf_max_abs_diff": float(np.abs(pl - rl).max()),
            "first_differing": differing}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147484641)
    ap.add_argument("--rows", type=int, default=250_000)
    ap.add_argument("--cols", type=int, default=500)
    ap.add_argument("--trees", type=int, default=12)
    ap.add_argument("--bins", type=int, default=32)
    ap.add_argument("--folds", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "rf_grid_parity.json"))
    a = ap.parse_args()
    os.environ["TMOG_COST_HISTORY"] = ""
    os.environ["JAX_ENABLE_X64"] = "0"    # the chip path is 32-bit
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp

    from perfbench.generators.planted_linear import generate
    from perfbench.reference import rf_grid
    from transmogrifai_tpu.models.gbdt_kernels import (_accel_bf16,
                                                       grow_rf_grid,
                                                       prune_rf_grid,
                                                       rf_bags_and_features)
    from transmogrifai_tpu.models.trees import (_feature_subset_size,
                                                _prep_tree_inputs_weighted)

    device = jax.devices()[0]
    frame, _ = generate(a.rows, a.cols, a.seed, weights_seed=11)
    A = frame.to_numpy(np.float32)
    del frame
    X, y = np.ascontiguousarray(A[:, 1:]), A[:, 0]
    del A
    base_w, fold = selector_weights(y, a.folds, 42)
    W_tr = np.stack([base_w * (fold != f) for f in range(a.folds)]).astype(
        np.float32)
    msub = _feature_subset_size("auto", a.cols, True)
    seed = 42                                   # the estimator's default
    _, binned_dev = _prep_tree_inputs_weighted(X, a.bins, row_weight=base_w)
    Y = np.eye(2, dtype=np.float32)[y.astype(int)]

    t0 = time.perf_counter()
    G = len(GATES)
    grown = grow_rf_grid(
        binned_dev, jnp.asarray(Y), jnp.asarray(W_tr), seed=seed,
        n_trees=a.trees, pair_fold=np.zeros(G, np.int32),
        pair_min_ig=np.asarray(GATES, np.float32),
        pair_min_inst=np.full(G, MIN_INSTANCES, np.float32),
        pair_depth=np.full(G, max(DEPTHS), np.int32), msub=msub,
        subsample_rate=1.0, n_bins=a.bins, onehot_targets=True,
        prune_outputs=True)
    feats, threshs, leaves, (level_values, _, _) = grown
    jax.block_until_ready(leaves)
    grow_s = time.perf_counter() - t0

    def at_level(depth):
        return leaves if depth == max(DEPTHS) else level_values[depth]

    derived, direct = {}, {}
    for depth in DEPTHS:
        nd = 2 ** depth - 1
        # every gate's candidate off pair 0, the base
        got = prune_rf_grid(
            *grown, np.zeros(G, np.int32), np.asarray(GATES, np.float32),
            depth=depth, n_bins=a.bins)
        for gi, gate in enumerate(GATES):
            derived[gate, depth] = tuple(np.asarray(v[gi]) for v in got)
            direct[gate, depth] = (np.asarray(feats[gi, :, :nd]),
                                   np.asarray(threshs[gi, :, :nd]),
                                   np.asarray(at_level(depth)[gi]))
    bags, subsets = rf_bags_and_features(seed, a.trees, a.rows, a.cols, msub,
                                         1.0)
    binned = np.asarray(binned_dev)
    yi = y.astype(np.int64)

    report = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "bf16_operands": bool(_accel_bf16()), "seed": a.seed,
              "rows": a.rows, "cols": a.cols, "trees": a.trees,
              "msub": msub, "gates": GATES, "base_gate": GATES[0],
              "min_instances": MIN_INSTANCES, "fold": 0,
              "row_weights": sorted(float(v) for v in np.unique(base_w)),
              "program_grow_s": round(grow_s, 3), "tolerance": TOLERANCE,
              "candidates": []}
    ok = True
    for gate in GATES:
        for depth in DEPTHS:
            t0 = time.perf_counter()
            df, dt_, dl = derived[gate, depth]
            pf, pt, pl = direct[gate, depth]
            # (i) the program's own direct growth: equal, node for node
            same_direct = (df == pf) & (dt_ == pt)
            direct_leaf = float(np.abs(dl - pl).max())
            if not same_direct.all() or direct_leaf > TOLERANCE["leaf_atol"]:
                ok = False
            # (ii) the float64 reference at the candidate's gate and depth
            trees = []
            for t in range(a.trees):
                weight = W_tr[0].astype(np.float64) * bags[t]
                ref = rf_grid.grow_tree(binned, yi, weight, subsets[t],
                                        depth, gate, MIN_INSTANCES, a.bins)
                part = compare((df[t], dt_[t], dl[t]), ref, binned, yi,
                               weight, a.bins)
                part["tree"] = t
                # a node where one side does not split has no gain to
                # compare
                if any(node["gain_rel_diff"] is None
                       or node["gain_rel_diff"] > TOLERANCE["gain_rtol"]
                       for node in part["first_differing"]):
                    ok = False
                if (part["equal_share"] == 1.0 and
                        part["leaf_max_abs_diff"] > TOLERANCE["leaf_atol"]):
                    ok = False
                trees.append(part)
            nodes = sum(p["nodes"] for p in trees)
            report["candidates"].append({
                "min_info_gain": gate, "depth": depth,
                "derived_from_base": gate != GATES[0] or depth != max(DEPTHS),
                "nodes": nodes,
                "equal_nodes_direct": int(same_direct.sum()),
                "leaf_max_abs_diff_direct": direct_leaf,
                "equal_nodes": sum(p["equal_nodes"] for p in trees),
                "equal_share": sum(p["equal_nodes"] for p in trees) / nodes,
                "split_nodes_reference": [p["split_nodes_reference"]
                                          for p in trees],
                "split_nodes_program": [p["split_nodes_program"]
                                        for p in trees],
                "trees_equal": sum(p["equal_share"] == 1.0 for p in trees),
                "leaf_max_abs_diff_of_equal_trees": max(
                    [p["leaf_max_abs_diff"] for p in trees
                     if p["equal_share"] == 1.0], default=None),
                "leaf_max_abs_diff": max(p["leaf_max_abs_diff"]
                                         for p in trees),
                "first_differing": [dict(n, tree=p["tree"]) for p in trees
                                    for n in p["first_differing"]],
                "reference_grow_s": round(time.perf_counter() - t0, 3)})
    report["within_tolerance"] = ok
    os.makedirs(os.path.dirname(a.out), exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(report, f, indent=1)
    for part in report["candidates"]:
        part["first_differing"] = part["first_differing"][:12]
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
