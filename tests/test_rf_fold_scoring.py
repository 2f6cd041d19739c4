"""``RFGridGroup`` scores a candidate pair on its own fold's validation rows.

A pair (candidate, fold) is ranked under ``W_ev[fold]``, which is zero on
the rows the fold trained on and on every row a balancer, a hold-out
reservation or mesh padding dropped; ``run`` compacts each fold's weighted
rows once (``grid_groups._fold_eval_rows``), scores the fold's pairs on
that matrix and ranks them there.  Held here against the all-rows
computation, which is the group's own path where no weight is zero: every
pair scored on the whole matrix by ``_score_pairs_jit`` and ranked under
``W_ev`` by the one-dimensional metric grid.  The scores of the ranked rows
must be the same floats to the bit, the metrics equal to 1e-6 (a float32
``segment_sum`` over a shorter array) and the best candidate the same.

2,000 x 12, 3 trees, depths {1, 3} x two gates: small shapes, the CPU.
"""
import numpy as np
import pytest

ROWS, COLS, TREES = 2000, 12, 3
GRID = {"max_depth": [1, 3], "min_info_gain": [0.001, 0.02],
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
    else:
        y = z.astype(np.float32)
    return X, y


def _cv(base_w, shares=(1, 1, 1)):
    """Fold contexts as ``validators`` builds them: the fold's eval weights
    are the base weights on its rows, its train weights the rest."""
    rng = np.random.default_rng(11)
    cuts = np.cumsum(shares) / np.sum(shares)
    fold = np.searchsorted(cuts, rng.random(ROWS), side="right")
    return [((base_w * (fold != f)).astype(np.float32),
             (base_w * (fold == f)).astype(np.float32))
            for f in range(len(shares))]


def _ones():
    return np.ones(ROWS, np.float32)


def _balancer_drops():
    w = _ones()
    w[np.random.default_rng(2).random(ROWS) < 0.3] = 0.0
    return w


def _pad_block():
    w = _ones()
    w[-200:] = 0.0
    return w


def _fractional():
    return np.random.default_rng(4).choice(
        np.array([0.0, 0.37, 1.0, 2.5], np.float32), ROWS)


def _split(base_w):
    """One train/validation split: a quarter of the rows validate."""
    ev = np.random.default_rng(6).random(ROWS) < 0.25
    return [((base_w * ~ev).astype(np.float32),
             (base_w * ev).astype(np.float32))]


def _every_row_validates():
    """No zero in the eval weights: nothing to leave out."""
    w_tr = (np.random.default_rng(8).random(ROWS) < 0.7).astype(np.float32)
    return [(w_tr, _ones())]


#: name -> (table, metric, grid, fold contexts, the length the scored
#: matrices must have or None where every row is scored)
CASES = {
    "aupr-equal-folds": ("binary", "AuPR", GRID, lambda: _cv(_ones()), 1024),
    "auroc-equal-folds": ("binary", "AuROC", GRID, lambda: _cv(_ones()),
                          1024),
    "unequal-folds": ("binary", "AuPR", GRID,
                      lambda: _cv(_ones(), (9, 6, 4)), 1024),
    "balancer-drops": ("binary", "AuPR", GRID,
                       lambda: _cv(_balancer_drops()), 1024),
    "trailing-pad-rows": ("binary", "AuPR", GRID, lambda: _cv(_pad_block()),
                          1024),
    "fractional-weights": ("binary", "AuROC", GRID,
                           lambda: _cv(_fractional()), 1024),
    "one-split": ("binary", "AuPR", GRID, lambda: _split(_ones()), 1024),
    "one-point-no-pruning": ("binary", "AuPR",
                             {"max_depth": [3], "min_info_gain": [0.001],
                              "min_instances_per_node": [10]},
                             lambda: _cv(_ones()), 1024),
    "no-zero-weight": ("binary", "AuPR", GRID, _every_row_validates, None),
    "three-classes-f1": ("multiclass", "F1", GRID, lambda: _cv(_ones()),
                         1024),
    "regression-rmse": ("regression", "RootMeanSquaredError", GRID,
                        lambda: _cv(_fractional()), 1024),
}


def _run(kind, metric, grid_kw, ctxs, all_rows):
    """``RFGridGroup.run`` with every scoring part recorded; ``all_rows``
    takes the compaction away, which leaves the path of a table without a
    zero weight: every pair on the whole matrix, the one-dimensional
    grid."""
    from transmogrifai_tpu.models import (OpRandomForestClassifier,
                                          OpRandomForestRegressor)
    from transmogrifai_tpu.models.trees import clear_sweep_caches
    from transmogrifai_tpu.selector import grid, grid_groups
    from transmogrifai_tpu.utils import profiling

    X, y = _table(kind)
    est = (OpRandomForestRegressor if kind == "regression"
           else OpRandomForestClassifier)
    group = grid_groups.RFGridGroup(
        est(num_trees=TREES), grid(**grid_kw), metric,
        n_classes=3 if kind == "multiclass" else 2)
    parts = []
    score, rows = grid_groups._score_pairs_jit, grid_groups._fold_eval_rows

    def recording(mats, *rest):
        got = score(mats, *rest)
        parts.append((mats, np.asarray(got)))
        return got

    clear_sweep_caches()
    profiling.reset_counters()
    grid_groups._score_pairs_jit = recording
    if all_rows:
        grid_groups._fold_eval_rows = lambda W_ev: (None, W_ev)
    try:
        metrics = np.asarray(group.run(X, y, ctxs))
    finally:
        grid_groups._score_pairs_jit = score
        grid_groups._fold_eval_rows = rows
    return {"metrics": metrics, "parts": parts,
            "scored": profiling.COUNTERS.to_json()["rfGrid"]["scoredRows"]}


@pytest.fixture(scope="module", params=list(CASES), ids=list(CASES))
def case(request):
    kind, metric, grid_kw, make_ctxs, length = CASES[request.param]
    ctxs = make_ctxs()
    return {"metric": metric, "length": length, "folds": len(ctxs),
            "W_ev": np.stack([w for _, w in ctxs]),
            "ours": _run(kind, metric, grid_kw, ctxs, all_rows=False),
            "whole": _run(kind, metric, grid_kw, ctxs, all_rows=True)}


def test_metrics_are_the_all_rows_computation_s_and_pick_the_same_winner(
        case):
    ours, whole = case["ours"]["metrics"], case["whole"]["metrics"]
    assert ours.shape == whole.shape and ours.shape[1] == case["folds"]
    assert np.isfinite(whole).all()
    np.testing.assert_allclose(ours, whole, rtol=0, atol=1e-6)
    mean = (np.argmin if case["metric"] == "RootMeanSquaredError"
            else np.argmax)
    assert mean(ours.mean(axis=1)) == mean(whole.mean(axis=1))
    # the grid is not flat: the candidates' metrics differ
    assert np.ptp(whole.mean(axis=1)) > 0 or len(whole) == 1


def test_a_pair_s_scores_are_the_all_rows_scores_at_its_fold_s_rows(case):
    F, n = case["folds"], ROWS
    assert case["whole"]["scored"] == n
    assert len(case["ours"]["parts"]) == len(case["whole"]["parts"])
    if case["length"] is None:
        # nothing to leave out: the group took the all-rows path itself
        assert case["ours"]["scored"] == n
        for (mats, got), (_, want) in zip(case["ours"]["parts"],
                                          case["whole"]["parts"]):
            assert [m.shape for m in mats] == [(n, COLS)]
            assert np.array_equal(got, want)
        return
    L = case["length"]
    assert case["ours"]["scored"] == L < n
    for (mats, got), (whole, want) in zip(case["ours"]["parts"],
                                          case["whole"]["parts"]):
        assert [m.shape for m in mats] == [(L, COLS)] * F
        assert [m.shape for m in whole] == [(n, COLS)]
        assert got.shape == (len(want), L) and len(want) % F == 0
        per = len(want) // F     # fold-major: a fold's pairs stand together
        for p in range(len(want)):
            idx = np.flatnonzero(case["W_ev"][p // per] > 0)
            assert 0 < len(idx) <= L
            assert np.array_equal(got[p, :len(idx)], want[p, idx])
            # the padding repeats a row of the fold's own
            assert np.all(got[p, len(idx):] == want[p, idx[-1]])


# -- the compaction by itself ---------------------------------------------------

def _rows(W_ev):
    from transmogrifai_tpu.selector.grid_groups import _fold_eval_rows

    return _fold_eval_rows(np.asarray(W_ev, np.float32))


def test_fold_rows_share_the_longest_fold_s_length_rounded_up():
    W = np.zeros((3, 5000), np.float32)
    W[0, 10:1500] = 1.0          # 1,490 rows
    W[1, 2000:2700] = 2.5        # 700
    W[2, ::4] = 0.5              # 1,250, interior zeros between them
    rows, weights = _rows(W)
    assert rows.shape == weights.shape == (3, 2 * MULTIPLE)
    assert rows.dtype == np.int32 and weights.dtype == np.float32
    for f, count in enumerate((1490, 700, 1250)):
        keep = np.flatnonzero(W[f] > 0)
        assert np.array_equal(rows[f, :count], keep)
        assert np.array_equal(weights[f, :count], W[f, keep])
        # padded with the fold's own last row, under weight 0
        assert np.all(rows[f, count:] == keep[-1])
        assert not weights[f, count:].any()
    assert weights.sum() == W.sum()


@pytest.mark.parametrize("kept, rows_in, compacted", [
    (1024, 1025, True),      # a whole multiple is not rounded further
    (1025, 2048, False),     # 2,048 would not be under the table's rows
    (1025, 2049, True),
    (3000, 3000, False),     # no zero weight at all
], ids=["exact-multiple", "rounds-up-to-the-table", "just-under", "no-zero"])
def test_fold_rows_fall_back_where_nothing_would_be_left_out(kept, rows_in,
                                                             compacted):
    W = np.zeros((1, rows_in), np.float32)
    W[0, :kept] = 1.0
    rows, weights = _rows(W)
    if not compacted:
        assert rows is None and weights is W
        return
    assert rows.shape == (1, -(-kept // MULTIPLE) * MULTIPLE)
    assert rows.shape[1] < rows_in and weights.sum() == kept


def test_a_fold_without_a_weighted_row_is_all_padding():
    W = np.zeros((2, 3000), np.float32)
    W[0, 5:25] = 1.0
    rows, weights = _rows(W)
    assert rows.shape == (2, MULTIPLE)
    assert not rows[1].any() and not weights[1].any()
    assert weights[0].sum() == 20
