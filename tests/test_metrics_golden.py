"""Evaluator metric golden values + host/device parity.

Reference: OpBinaryClassificationEvaluatorTest / OpRegressionEvaluatorTest
coverage (SURVEY §4); values below are hand-computed.
"""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators.metrics import (
    _aupr_dev, _auroc_dev, aupr, auroc, binary_classification_metrics,
    brier_score, log_loss, multiclass_metrics, regression_metrics,
)


class TestBinaryGolden:
    def test_perfect_separation(self):
        y = np.array([0.0, 0, 1, 1])
        s = np.array([0.1, 0.2, 0.8, 0.9])
        assert auroc(y, s) == pytest.approx(1.0)
        assert aupr(y, s) == pytest.approx(1.0)

    def test_reversed_scores(self):
        y = np.array([0.0, 1])
        s = np.array([0.9, 0.1])
        assert auroc(y, s) == pytest.approx(0.0)

    def test_known_auroc(self):
        # 1 positive above 1 of 2 negatives: P(s+ > s-) = 0.5
        y = np.array([0.0, 1, 0])
        s = np.array([0.3, 0.5, 0.7])
        assert auroc(y, s) == pytest.approx(0.5)

    def test_ties_half_credit(self):
        y = np.array([0.0, 1])
        s = np.array([0.5, 0.5])
        assert auroc(y, s) == pytest.approx(0.5)

    def test_weighted_auroc(self):
        # weight-2 negative below the positive, weight-1 negative above:
        # num = 1*2 /(1*3) = 2/3
        y = np.array([0.0, 1, 0])
        s = np.array([0.1, 0.5, 0.9])
        w = np.array([2.0, 1.0, 1.0])
        assert auroc(y, s, w) == pytest.approx(2 / 3)

    def test_aupr_average_precision(self):
        # order by score desc: y=1,0,1 -> precision at positives: 1, 2/3
        # AP = (1 + 2/3)/2
        y = np.array([1.0, 0, 1])
        s = np.array([0.9, 0.8, 0.7])
        assert aupr(y, s) == pytest.approx((1 + 2 / 3) / 2)

    def test_brier_and_logloss(self):
        y = np.array([1.0, 0.0])
        p = np.array([0.8, 0.4])
        assert brier_score(y, p) == pytest.approx((0.04 + 0.16) / 2)
        assert log_loss(y, p) == pytest.approx(
            -(np.log(0.8) + np.log(0.6)) / 2)

    def test_full_metric_dict(self):
        y = np.array([0.0, 0, 1, 1, 1, 0])
        p = np.array([0.2, 0.6, 0.7, 0.9, 0.3, 0.1])
        m = binary_classification_metrics(y, p)
        # threshold 0.5: TP=2 FP=1 FN=1 TN=2
        assert m["Precision"] == pytest.approx(2 / 3)
        assert m["Recall"] == pytest.approx(2 / 3)
        assert m["Error"] == pytest.approx(2 / 6)


class TestHostDeviceParity:
    def test_aupr_auroc_parity_random(self):
        rng = np.random.default_rng(3)
        for n in (10, 257):
            y = (rng.random(n) < 0.3).astype(np.float64)
            s = np.round(rng.random(n), 2)          # force ties
            w = rng.integers(1, 4, n).astype(np.float64)
            assert float(_auroc_dev(y, s, w)) == pytest.approx(
                auroc(y, s, w), abs=1e-5)
            assert float(_aupr_dev(y, s, w)) == pytest.approx(
                aupr(y, s, w), abs=1e-5)


class TestRegressionMulticlassGolden:
    def test_regression_values(self):
        y = np.array([1.0, 2.0, 3.0])
        p = np.array([1.5, 2.0, 2.5])
        m = regression_metrics(y, p)
        assert m["RootMeanSquaredError"] == pytest.approx(
            np.sqrt(0.25 / 1.5))
        assert m["MeanAbsoluteError"] == pytest.approx(1 / 3)
        assert m["R2"] == pytest.approx(1 - 0.5 / 2.0)

    def test_multiclass_f1(self):
        y = np.array([0, 0, 1, 1, 2, 2])
        p = np.array([0, 1, 1, 1, 2, 0])
        m = multiclass_metrics(y, p, 3)
        assert m["Error"] == pytest.approx(2 / 6)
        # per-class precision: c0 1/2, c1 2/3, c2 1/1
        assert m["Precision"] == pytest.approx(
            (0.5 * 2 + 2 / 3 * 2 + 1.0 * 2) / 6)


# -- the batched metric grids --------------------------------------------------

def _grid_inputs(kind, folds=3, cands=4, n=300):
    """(F, C, N) scores with ties, (F, N) weights with zeros, (F, N) labels
    that differ from fold to fold."""
    rng = np.random.default_rng(9)
    if kind == "multiclass":
        y = rng.integers(0, 3, (folds, n)).astype(np.float32)
        s = rng.integers(0, 3, (folds, cands, n)).astype(np.float32)
    elif kind == "binary":
        y = (rng.random((folds, n)) < 0.3).astype(np.float32)
        s = np.round(rng.random((folds, cands, n)), 2).astype(np.float32)
    else:
        y = rng.normal(size=(folds, n)).astype(np.float32)
        s = (y[:, None, :] + rng.normal(size=(folds, cands, n))
             ).astype(np.float32)
    w = rng.choice(np.array([0.0, 0.5, 1.0, 2.0], np.float32), (folds, n))
    return y, s, w


def _grid(kind, metric):
    import functools

    from transmogrifai_tpu.evaluators import metrics as M

    if kind == "multiclass":
        return functools.partial(
            lambda y, s, w, metric: M.multiclass_metric_grid(y, s, w, 3,
                                                             metric),
            metric=metric)
    fn = (M.binary_metric_grid if kind == "binary"
          else M.regression_metric_grid)
    return functools.partial(fn, metric=metric)


GRID_METRICS = [("binary", "AuPR"), ("binary", "AuROC"),
                ("regression", "RootMeanSquaredError"),
                ("regression", "R2"), ("multiclass", "F1"),
                ("multiclass", "Error")]


@pytest.mark.parametrize("kind, metric", GRID_METRICS,
                         ids=[f"{k}-{m}" for k, m in GRID_METRICS])
class TestMetricGridLabels:
    def test_a_label_row_a_fold_is_that_fold_s_own_call(self, kind, metric):
        """(F, N) labels: each fold is ranked against its own row, exactly
        as a call with that row as the shared label vector ranks it."""
        y, s, w = _grid_inputs(kind)
        grid = _grid(kind, metric)
        got = np.asarray(grid(y, s, w))
        assert got.shape == s.shape[:2]
        for f in range(len(y)):
            want = np.asarray(grid(y[f], s[f:f + 1], w[f:f + 1]))
            assert np.array_equal(got[f:f + 1], want)
        # the folds' labels matter: fold 0's row for all is another result
        assert not np.array_equal(got, np.asarray(grid(y[0], s, w)))

    def test_one_shared_label_vector_is_the_kernel_s_value_a_cell(
            self, kind, metric):
        """(N,) labels, the linear and boosted groups' call shape: (F, C,
        N) scores and (F, N) weights give each cell what the metric's
        kernel gives for that score row under that fold's weights."""
        import jax.numpy as jnp

        from transmogrifai_tpu.evaluators import metrics as M

        y, s, w = _grid_inputs(kind)
        y = y[0]
        got = np.asarray(_grid(kind, metric)(y, jnp.asarray(s),
                                             jnp.asarray(w)))
        assert got.shape == s.shape[:2] and np.isfinite(got).all()
        for f in range(s.shape[0]):
            for c in range(s.shape[1]):
                if kind == "binary":
                    kernel = {"AuPR": M._aupr_dev, "AuROC": M._auroc_dev}
                    want = kernel[metric](y, s[f, c], w[f])
                elif kind == "regression":
                    want = M._regression_metric_dev(
                        jnp.asarray(y), jnp.asarray(s[f, c]),
                        jnp.asarray(w[f]), metric)
                else:
                    want = M._multiclass_metric_dev(
                        jnp.asarray(y, jnp.int32),
                        jnp.asarray(s[f, c], jnp.int32), jnp.asarray(w[f]),
                        3, metric)
                assert got[f, c] == pytest.approx(float(want), abs=1e-6)


def test_metric_grids_without_a_device_kernel_decline_either_label_shape():
    from transmogrifai_tpu.evaluators import metrics as M

    y, s, w = _grid_inputs("binary")
    for labels in (y, y[0]):
        assert M.binary_metric_grid(labels, s, w, "F1") is None
        assert M.regression_metric_grid(labels, s, w, "AuPR") is None
        assert M.multiclass_metric_grid(labels, s, w, 3, "AuPR") is None
