"""``RFGridGroup`` grows a fold's forests on the fold's own training rows.

A fold's base pairs train under ``W_tr[fold]``, which is zero on the fold's
validation rows and on every row a balancer or a hold-out reservation
dropped; ``run`` compacts each fold's weighted rows once
(``grid_groups._fold_train_rows``) and ``gbdt_kernels.grow_rf_grid`` grows
the fold's pairs on that matrix, in launches that hold the fold's trees
alone, with the bags still drawn over the table's rows.  The winner's refit
grows on the rows its full weights keep.  Held here against the all-rows
computation, which is the group's own path where no weight is zero: every
pair grown on the whole matrix in one launch stream.

Under whole-number weights every histogram sum, count and leaf sum is an
exact integer in float32 whatever the order it is summed in, so the trees
must be the same to the bit: heaps, leaves, every level value, the gate
ratios and the unsplit features, the metric grid, the winner and the
refit.  Under fractional weights the sums are rounded in another order, so
the metrics are held to a tolerance.

3,000 x 16, 3 trees, depths {1, 3, 6} x two gates: small shapes, the CPU.
"""
import contextlib

import jax
import numpy as np
import pytest

ROWS, COLS, TREES, FOLDS = 3000, 16, 3, 3
GRID = {"max_depth": [1, 3, 6], "min_info_gain": [0.001, 0.02],
        "min_instances_per_node": [10]}
MULTIPLE = 1024


def _table(kind):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    z = X[:, :4] @ np.array([1.0, -0.8, 0.6, 0.4]) + 0.5 * rng.normal(
        size=ROWS)
    if kind == "binary":
        y = (z > 0.3).astype(np.float32)
    elif kind == "multiclass":
        y = np.digitize(z, [-0.6, 0.6]).astype(np.float32)
    elif kind == "regression-integer":
        # whole-number targets: the value channel's sums are exact too
        y = np.round(3 * z).astype(np.float32)
    else:
        y = z.astype(np.float32)
    return X, y


def _integer_drops():
    """A balancer's whole-number weights: 45 % of the rows dropped, some
    up-weighted, so that the refit's rows are compacted too."""
    rng = np.random.default_rng(2)
    return np.where(rng.random(ROWS) < 0.45, 0.0,
                    np.where(rng.random(ROWS) < 0.2, 2.0, 1.0))


def _fractional():
    return np.random.default_rng(4).choice(
        np.array([0.0, 0.37, 1.0, 2.5], np.float32), ROWS)


def _cv(base_w):
    """Fold contexts as ``validators`` builds them: the fold's eval weights
    are the base weights on its rows, its train weights the rest."""
    fold = np.random.default_rng(11).integers(0, FOLDS, ROWS)
    return [((base_w * (fold != f)).astype(np.float32),
             (base_w * (fold == f)).astype(np.float32))
            for f in range(FOLDS)]


def _split_keeping_nearly_all():
    """A train/validation split that validates on 2 % of the rows: the
    training rows, rounded up, are not fewer than the table's."""
    ev = np.random.default_rng(6).random(ROWS) < 0.02
    return [((~ev).astype(np.float32), ev.astype(np.float32))]


@contextlib.contextmanager
def _recorded(all_rows, cap=None):
    """Every ``grow_rf_grid`` call's ``rows`` and outputs (host copies) and
    every launch's pair folds and row count; ``all_rows`` takes the
    compaction away, which leaves the path of a table without a zero
    weight; ``cap`` bounds the chunker's budget."""
    from transmogrifai_tpu.models import gbdt_kernels
    from transmogrifai_tpu.selector import grid_groups

    grows, launches = [], []
    grow = gbdt_kernels.grow_rf_grid
    chunk_fn = gbdt_kernels._grow_chunk_rf_grid
    size = gbdt_kernels.forest_chunk_size

    def recording_grow(*args, **kw):
        out = grow(*args, **kw)
        grows.append({"rows": kw.get("rows"),
                      "out": jax.tree_util.tree_map(np.asarray, out)})
        return out

    def recording_chunk(mat, *args, **kw):
        launches.append({"folds": np.asarray(args[5]),
                         "rows": mat.shape[0],
                         "bag_rows": kw.get("bag_rows") is not None})
        return chunk_fn(mat, *args, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gbdt_kernels, "grow_rf_grid", recording_grow)
        mp.setattr(gbdt_kernels, "_grow_chunk_rf_grid", recording_chunk)
        if cap is not None:
            mp.setattr(gbdt_kernels, "forest_chunk_size",
                       lambda *a, **kw: min(cap, size(*a, **kw)))
        if all_rows:
            mp.setattr(grid_groups, "_fold_train_rows",
                       lambda W_tr: (None, W_tr))
        yield grows, launches


def _run(kind, ctxs, all_rows, grid_kw=GRID, trees=TREES, cap=None,
         refit_rows=(0, 2, 5)):
    from transmogrifai_tpu.models import (OpRandomForestClassifier,
                                          OpRandomForestRegressor)
    from transmogrifai_tpu.models.trees import clear_sweep_caches
    from transmogrifai_tpu.selector import grid, grid_groups
    from transmogrifai_tpu.utils import profiling

    X, y = _table(kind)
    cls = kind in ("binary", "multiclass")
    est = OpRandomForestClassifier if cls else OpRandomForestRegressor
    metric = {"binary": "AuPR", "multiclass": "F1"}.get(
        kind, "RootMeanSquaredError")
    group = grid_groups.RFGridGroup(
        est(num_trees=trees), grid(**grid_kw), metric,
        n_classes=3 if kind == "multiclass" else 2)
    clear_sweep_caches()
    profiling.reset_counters()
    with _recorded(all_rows, cap) as (grows, launches):
        metrics = np.asarray(group.run(X, y, ctxs))
        sweep = dict(profiling.COUNTERS.to_json()["rfGrid"])
        refits = {r: [np.asarray(a) for a in (m.feat, m.thresh, m.leaf)]
                  for r in refit_rows
                  for m in [group.refit_model(r)]}
    best = (np.argmin if metric == "RootMeanSquaredError"
            else np.argmax)(metrics.mean(axis=1))
    return {"metrics": metrics, "best": best, "sweep": sweep,
            "grows": grows, "launches": launches, "refits": refits}


def _weighted_length(W):
    return -(-max(int((w > 0).sum()) for w in W) // MULTIPLE) * MULTIPLE


#: name -> (table, fold contexts)
WHOLE = {
    "binary": ("binary", lambda: _cv(_integer_drops())),
    "three-classes": ("multiclass", lambda: _cv(_integer_drops())),
    "regression": ("regression-integer", lambda: _cv(_integer_drops())),
}


@pytest.fixture(scope="module", params=list(WHOLE), ids=list(WHOLE))
def whole(request):
    kind, make_ctxs = WHOLE[request.param]
    ctxs = make_ctxs()
    return {"ctxs": ctxs, "ours": _run(kind, ctxs, all_rows=False),
            "all": _run(kind, ctxs, all_rows=True)}


def test_integer_weights_grow_the_all_rows_trees_to_the_bit(whole):
    ours, every = whole["ours"], whole["all"]
    assert len(ours["grows"]) == len(every["grows"]) == 4  # sweep + 3 refits
    for got, want in zip(ours["grows"], every["grows"]):
        assert want["rows"] is None and got["rows"] is not None
        got_leaves = jax.tree_util.tree_leaves(got["out"])
        want_leaves = jax.tree_util.tree_leaves(want["out"])
        # feats, threshs, leaves, six level values, ratios, unsplit feats
        assert len(got_leaves) == len(want_leaves) == 3 + 6 + 2
        for g, w in zip(got_leaves, want_leaves):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert np.array_equal(g, w)


def test_integer_weights_give_the_same_metric_grid_winner_and_refit(whole):
    ours, every = whole["ours"], whole["all"]
    assert ours["metrics"].shape == (6, FOLDS)
    assert np.isfinite(every["metrics"]).all()
    assert np.array_equal(ours["metrics"], every["metrics"])
    assert ours["best"] == every["best"]
    # the grid is not flat: the candidates' metrics differ
    assert np.ptp(every["metrics"].mean(axis=1)) > 0
    for row, want in every["refits"].items():
        for g, w in zip(ours["refits"][row], want):
            assert np.array_equal(g, w)


def test_a_fold_grows_on_its_own_rows_and_the_refit_on_the_kept_ones(whole):
    from transmogrifai_tpu.selector.grid_groups import _fold_train_rows

    W_tr = np.stack([w for w, _ in whole["ctxs"]])
    L = _weighted_length(W_tr)
    assert L < ROWS
    ours, every = whole["ours"], whole["all"]
    assert ours["sweep"]["grownRows"] == L
    assert every["sweep"]["grownRows"] == ROWS
    rows, weights = _fold_train_rows(W_tr)
    assert np.array_equal(ours["grows"][0]["rows"], rows)
    # one launch a fold, of that fold's pairs, on its L rows
    sweep = ours["launches"][:FOLDS]
    assert [set(s["folds"].tolist()) for s in sweep] == [{0}, {1}, {2}]
    assert all(s["rows"] == L and s["bag_rows"] for s in sweep)
    # a refit on the rows its full weights keep
    full = whole["ctxs"][0][0] + whole["ctxs"][0][1]
    L_full = _weighted_length(full[None])
    assert L_full < ROWS
    assert [s["rows"] for s in ours["launches"][FOLDS:]] == [L_full] * 3
    assert all(s["rows"] == ROWS and not s["bag_rows"]
               for s in every["launches"])


def test_fractional_weights_keep_the_metrics_and_the_winner():
    """Fractional balancer weights make every sum a rounded one, summed in
    another order on a fold's rows than on the table's: the float32
    accumulation order may move a leaf by an ulp or so, which moves a
    metric by about 1e-7; 1e-5 is a hundred times that."""
    ctxs = _cv(_fractional())
    ours = _run("regression", ctxs, all_rows=False, refit_rows=())
    every = _run("regression", ctxs, all_rows=True, refit_rows=())
    assert ours["sweep"]["grownRows"] < ROWS == every["sweep"]["grownRows"]
    np.testing.assert_allclose(ours["metrics"], every["metrics"], rtol=0,
                               atol=1e-5)
    assert ours["best"] == every["best"]


def test_no_compaction_where_the_training_rows_reach_the_table_s():
    ours = _run("binary", _split_keeping_nearly_all(), all_rows=False,
                refit_rows=(1,))
    assert ours["sweep"]["grownRows"] == ROWS
    assert [g["rows"] for g in ours["grows"]] == [None, None]
    assert all(s["rows"] == ROWS and not s["bag_rows"]
               for s in ours["launches"])


@pytest.fixture(scope="module")
def evened():
    """The cell's arithmetic at a small table: 2 bases x 3 folds x 12
    trees under a budget of 15 trees a launch, and a refit of 12."""
    grid_kw = {"max_depth": [3], "min_info_gain": [0.001],
               "min_instances_per_node": [10, 100]}
    ctxs = _cv(_integer_drops())
    return {k: _run("binary", ctxs, all_rows=(k == "all"), grid_kw=grid_kw,
                    trees=12, cap=15, refit_rows=(1,))
            for k in ("ours", "all")}


def test_chunk_evening_grows_no_more_tree_slots_than_the_all_rows_plan(
        evened):
    ours, every = evened["ours"]["sweep"], evened["all"]["sweep"]
    assert ours["treesGrown"] == every["treesGrown"] == 72
    # a fold's 24 trees in two launches of 12; the table's 72 in 5 of 15
    assert (ours["chunk"], ours["launches"]) == (12, 6)
    assert (every["chunk"], every["launches"]) == (15, 5)
    assert ours["chunk"] * ours["launches"] <= (
        every["chunk"] * every["launches"])
    # every launch holds one fold's trees
    launched = evened["ours"]["launches"]
    assert [set(s["folds"].tolist()) for s in launched[:6]] == [
        {0}, {0}, {1}, {1}, {2}, {2}]
    # the refit's 12 trees: one launch of 12 either way
    assert len(launched) == len(evened["all"]["launches"]) + 1 == 7


def test_evened_launch_streams_grow_the_all_rows_trees_in_pair_order(
        evened):
    ours, every = evened["ours"], evened["all"]
    for got, want in zip(ours["grows"], every["grows"]):
        for g, w in zip(jax.tree_util.tree_leaves(got["out"]),
                        jax.tree_util.tree_leaves(want["out"])):
            assert np.array_equal(g, w)
    assert np.array_equal(ours["metrics"], every["metrics"])


def test_grown_rows_keep_the_largest_like_the_other_shapes():
    """Two grids in one run (a binary and a regression selector, say) leave
    the longer fold matrices' length, as ``scoredRows`` does."""
    from transmogrifai_tpu.utils import profiling

    profiling.reset_counters()
    profiling.count_rf_grid(grownRows=2048, treesGrown=3)
    profiling.count_rf_grid(grownRows=1024, treesGrown=4)
    assert profiling.COUNTERS.to_json()["rfGrid"] == {
        "grownRows": 2048, "treesGrown": 7}
    assert profiling.reset_counters().to_json()["rfGrid"] == {}
