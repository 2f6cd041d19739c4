"""Grid-batched sweep groups vs the sequential per-candidate path.

The batched programs must reproduce the sequential path's selection: RF
grids share the exact bag/feature-subset randomness (fold_in(seed, t)), so
their metrics match to float tolerance; the LR group's majorization solver
converges to the same optimum as Newton-IRLS, so metrics agree to ~1e-3 and
the winner agrees.
"""
import numpy as np
import pytest

from transmogrifai_tpu.models.classification import OpLogisticRegression
from transmogrifai_tpu.models.regression import OpLinearRegression
from transmogrifai_tpu.models.trees import (
    OpRandomForestClassifier, OpRandomForestRegressor,
)
from transmogrifai_tpu.selector import grid
from transmogrifai_tpu.selector.grid_groups import make_grid_group
from transmogrifai_tpu.selector.model_selector import ModelSelector
from transmogrifai_tpu.selector.validators import OpCrossValidation


def _binary_data(n=3000, d=12, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d) * (rng.random(d) < 0.5)
    y = (1 / (1 + np.exp(-(X @ beta))) > rng.random(n)).astype(np.float32)
    return X, y


def _run_selector(models_and_params, problem, X, y, metric=None):
    sel = ModelSelector(
        models_and_params, problem_type=problem,
        validator=OpCrossValidation(num_folds=3, seed=7,
                                    stratify=problem != "regression"),
        validation_metric=metric)
    candidates = sel._candidates()
    best_i, results = sel.validator.validate(
        candidates, X, y, np.ones(len(y), np.float32),
        eval_fn=sel._metric, metric_name=sel.validation_metric,
        larger_better=sel.larger_better)
    return best_i, results


class TestGroupConstruction:
    def test_factory_matches_families(self):
        assert make_grid_group(OpLogisticRegression(),
                               grid(reg_param=[0.1]), "binary",
                               "AuPR") is not None
        assert make_grid_group(OpRandomForestClassifier(),
                               grid(max_depth=[3]), "binary",
                               "AuPR") is not None
        assert make_grid_group(OpLinearRegression(), grid(reg_param=[0.1]),
                               "regression",
                               "RootMeanSquaredError") is not None
        assert make_grid_group(OpRandomForestRegressor(),
                               grid(max_depth=[3]), "regression",
                               "RootMeanSquaredError") is not None
        # multiclass families batch too (round-3 softmax/argmax groups)
        assert make_grid_group(OpLogisticRegression(), grid(reg_param=[0.1]),
                               "multiclass", "F1", n_classes=3) is not None
        assert make_grid_group(OpRandomForestClassifier(),
                               grid(max_depth=[3]), "multiclass",
                               "F1", n_classes=3) is not None
        # unsupported metric / problem -> no group
        assert make_grid_group(OpLogisticRegression(), grid(reg_param=[0.1]),
                               "binary", "F1") is None
        assert make_grid_group(OpRandomForestClassifier(),
                               grid(max_depth=[3]), "multiclass",
                               "LogLoss") is None

    def test_non_batchable_params_decline(self):
        X, y = _binary_data(400, 6)
        g = make_grid_group(OpRandomForestClassifier(),
                            grid(max_depth=[3], subsample_rate=[0.5, 1.0]),
                            "binary", "AuPR")
        # subsample_rate differs across candidates -> declines at run time
        assert g.run(X, y, [(np.ones(len(y), np.float32),
                             np.ones(len(y), np.float32))]) is None


class TestRFGridParity:
    def test_rf_group_matches_sequential(self, monkeypatch):
        X, y = _binary_data()
        mp = [(OpRandomForestClassifier(num_trees=8),
               grid(max_depth=[3, 5], min_instances_per_node=[1, 20]))]
        best_g, res_g = _run_selector(mp, "binary", X, y)

        # disable groups -> sequential fitter path
        import transmogrifai_tpu.selector.model_selector as ms
        monkeypatch.setattr(ms, "__grids_off", True, raising=False)
        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "binary", X, y)

        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.error is None and rs.error is None
            # identical bags + identical depth masking -> float-level match
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=2e-3)

    def test_rf_regression_group(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(1500, 8)).astype(np.float32)
        yr = (X @ rng.normal(size=8) + 0.1 * rng.normal(size=1500)
              ).astype(np.float32)
        mp = [(OpRandomForestRegressor(num_trees=6),
               grid(max_depth=[3, 4]))]
        best, res = _run_selector(mp, "regression", X, yr)
        assert all(r.error is None for r in res)
        assert all(np.isfinite(r.metric_value) for r in res)


def _multiclass_data(n=3000, d=10, k=3, seed=2):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    B = rng.normal(size=(d, k)) * 1.5
    Z = X @ B + rng.gumbel(size=(n, k))
    y = Z.argmax(axis=1).astype(np.float32)
    return X, y


class TestMulticlassGridParity:
    def test_softmax_group_matches_sequential(self, monkeypatch):
        X, y = _multiclass_data()
        mp = [(OpLogisticRegression(),
               grid(reg_param=[0.001, 0.1], elastic_net_param=[0.0, 0.5]))]
        best_g, res_g = _run_selector(mp, "multiclass", X, y)
        assert all(r.error is None for r in res_g)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "multiclass", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=1e-2)

    def test_rf_multiclass_group_matches_sequential(self, monkeypatch):
        X, y = _multiclass_data(2000, 8, 4, seed=9)
        mp = [(OpRandomForestClassifier(num_trees=8),
               grid(max_depth=[3, 5]))]
        best_g, res_g = _run_selector(mp, "multiclass", X, y)
        assert all(r.error is None for r in res_g)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "multiclass", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            # identical bags + identical depth masking -> float-level match
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=2e-3)

    def test_multiclass_metric_grid_matches_host(self):
        from transmogrifai_tpu.evaluators.metrics import (
            multiclass_metric_grid, multiclass_metrics,
        )
        rng = np.random.default_rng(4)
        y = rng.integers(0, 3, 500)
        preds = rng.integers(0, 3, (2, 3, 500)).astype(np.float32)
        W = rng.random((2, 500)).astype(np.float32)
        for metric in ("F1", "Error", "Accuracy", "Precision", "Recall"):
            M = np.asarray(multiclass_metric_grid(y, preds, W, 3, metric))
            for f in range(2):
                for c in range(3):
                    ref = multiclass_metrics(
                        y, preds[f, c].astype(int), 3,
                        sample_weight=W[f])[metric]
                    assert M[f, c] == pytest.approx(ref, abs=1e-5)


class TestLinearGridParity:
    def test_logreg_group_matches_sequential_winner(self, monkeypatch):
        X, y = _binary_data(4000, 20, seed=3)
        mp = [(OpLogisticRegression(),
               grid(reg_param=[0.001, 0.1, 0.5],
                    elastic_net_param=[0.1]))]
        best_g, res_g = _run_selector(mp, "binary", X, y)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "binary", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=5e-3)

    def test_linreg_group_matches_sequential(self, monkeypatch):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(3000, 15)).astype(np.float32)
        yr = (X @ rng.normal(size=15) + 0.05 * rng.normal(size=3000)
              ).astype(np.float32)
        mp = [(OpLinearRegression(),
               grid(reg_param=[0.0, 0.01, 0.1], elastic_net_param=[0.0]))]
        best_g, res_g = _run_selector(mp, "regression", X, yr)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "regression", X, yr)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    rel=2e-2)


class TestGBTChainParity:
    def test_gbt_chains_match_sequential(self, monkeypatch):
        X, y = _binary_data(2500, 10, seed=9)
        from transmogrifai_tpu.models.trees import OpGBTClassifier
        mp = [(OpGBTClassifier(max_iter=6),
               grid(max_depth=[3, 4], step_size=[0.1, 0.3]))]
        best_g, res_g = _run_selector(mp, "binary", X, y)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "binary", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=2e-3)

    def test_xgb_early_stopping_chains(self, monkeypatch):
        X, y = _binary_data(2000, 8, seed=11)
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        mp = [(OpXGBoostClassifier(num_round=12, eta=0.3, max_depth=3,
                                   early_stopping_rounds=3),
               grid(min_child_weight=[1.0, 10.0]))]
        best_g, res_g = _run_selector(mp, "binary", X, y)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "binary", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=3e-3)


class TestDepthTruncation:
    """Depth-truncation sharing (round 4): one base forest at the group's
    max depth must reproduce every shallower max_depth candidate EXACTLY —
    splits at a level never depend on deeper levels, and the truncated
    leaves are the level's own histogram totals."""

    def test_truncation_equals_native_depth_growth(self):
        import jax.numpy as jnp

        from transmogrifai_tpu.models.gbdt_kernels import (
            grow_rf_grid, predict_ensemble,
        )
        from transmogrifai_tpu.models.trees import _prep_tree_inputs

        X, y = _binary_data(1200, 8, seed=3)
        _, binned = _prep_tree_inputs(X, 32)
        Y = np.eye(2, dtype=np.float32)[y.astype(int)]
        W = np.ones((1, len(y)), np.float32)     # one fold, unit weights
        kw = dict(seed=42, n_trees=5, msub=8, subsample_rate=1.0,
                  n_bins=32, onehot_targets=True)
        # native growth: two pairs with the same gates, depths 3 and 6
        f_n, t_n, l_n = grow_rf_grid(
            binned, jnp.asarray(Y), jnp.asarray(W),
            pair_fold=np.zeros(2, np.int32),
            pair_min_ig=np.array([0.01, 0.01], np.float32),
            pair_min_inst=np.array([5.0, 5.0], np.float32),
            pair_depth=np.array([3, 6], np.int32), **kw)
        # shared growth: ONE base pair at depth 6, every level's values
        f_s, t_s, l_s, (level_values, _, _) = grow_rf_grid(
            binned, jnp.asarray(Y), jnp.asarray(W),
            pair_fold=np.zeros(1, np.int32),
            pair_min_ig=np.array([0.01], np.float32),
            pair_min_inst=np.array([5.0], np.float32),
            pair_depth=np.array([6], np.int32), prune_outputs=True, **kw)
        # the deep pair is bit-identical to the base pair
        np.testing.assert_array_equal(np.asarray(f_s[0]), np.asarray(f_n[1]))
        np.testing.assert_array_equal(np.asarray(t_s[0]), np.asarray(t_n[1]))
        np.testing.assert_allclose(np.asarray(l_s[0]), np.asarray(l_n[1]))
        # the base trees' first 3 levels ARE the depth-3 pair's splits
        np.testing.assert_array_equal(np.asarray(f_s[0][:, :7]),
                                      np.asarray(f_n[0][:, :7]))
        np.testing.assert_array_equal(np.asarray(t_s[0][:, :7]),
                                      np.asarray(t_n[0][:, :7]))
        # truncated prediction (sliced heap + level-3 values as leaves)
        # == the natively grown depth-3 pair's prediction (integer bag
        # weights -> exact histogram sums in both paths)
        p_native = np.asarray(predict_ensemble(
            binned, f_n[0], t_n[0], l_n[0], 6))
        p_trunc = np.asarray(predict_ensemble(
            binned, f_s[0][:, :7], t_s[0][:, :7], level_values[3][0], 3))
        np.testing.assert_allclose(p_trunc, p_native, atol=1e-6)

    def test_shared_group_matches_sequential_three_depths(self, monkeypatch):
        """End-to-end: a depth-varying RF grid through the shared group
        must select the same winner with the same metrics as the
        sequential per-candidate path."""
        X, y = _binary_data(2000, 8, seed=5)
        mp = [(OpRandomForestClassifier(num_trees=6),
               grid(max_depth=[2, 4, 6], min_info_gain=[0.0, 0.05]))]
        best_g, res_g = _run_selector(mp, "binary", X, y)

        from transmogrifai_tpu.selector import grid_groups
        monkeypatch.setattr(grid_groups, "make_grid_group",
                            lambda *a, **k: None)
        best_s, res_s = _run_selector(mp, "binary", X, y)
        assert best_g == best_s
        for rg, rs in zip(res_g, res_s):
            assert rg.error is None and rs.error is None
            assert rg.metric_value == pytest.approx(rs.metric_value,
                                                    abs=2e-3)

    def test_stump_candidate_in_depth_grid(self):
        """max_depth=0 (stump) candidates are not shared off a deeper
        base: the group grows stumps as their own base (ADVICE r4 — this
        used to KeyError in the scoring loop)."""
        X, y = _binary_data(1500, 6, seed=9)
        g = make_grid_group(OpRandomForestClassifier(num_trees=4),
                            grid(max_depth=[0, 4], min_info_gain=[0.01]),
                            "binary", "AuPR")
        w = np.ones(len(y), np.float32)
        m = g.run(X, y, [(w, w)])
        assert m is not None and tuple(m.shape) == (2, 1)
        assert np.isfinite(np.asarray(m)).all()


class TestWinnerRefitReuse:
    """Round-4 refit reuse: groups solve an appended full-train weight row,
    so the winner's refit model comes from the sweep program itself
    (ModelSelector.scala:145-209 refits from scratch instead)."""

    @staticmethod
    def _fold_ctxs(y, num_folds=3, seed=7):
        from transmogrifai_tpu.selector.validators import make_folds
        folds = make_folds(len(y), num_folds, y=y, stratify=True, seed=seed)
        return [((folds != k).astype(np.float32),
                 (folds == k).astype(np.float32)) for k in range(num_folds)]

    def test_lr_group_refit_matches_sequential(self):
        X, y = _binary_data(2500, 10, seed=8)
        Xh, yh = _binary_data(800, 10, seed=9)
        pts = grid(reg_param=[0.01, 0.1])
        g = make_grid_group(OpLogisticRegression(), pts, "binary", "AuPR")
        assert g.run(X, y, self._fold_ctxs(y)) is not None
        for row, p in enumerate(pts):
            model = g.refit_model(row)
            assert model is not None
            seq = OpLogisticRegression(**p).fit_raw(
                X, y, np.ones(len(y), np.float32))
            pg = model.predict_batch(Xh).probability[:, 1]
            ps = seq.predict_batch(Xh).probability[:, 1]
            # majorization vs Newton-IRLS: same optimum, solver-level tol
            np.testing.assert_allclose(pg, ps, atol=2e-2)
            assert np.corrcoef(pg, ps)[0, 1] > 0.999

    def test_rf_group_refit_matches_direct_full_train(self):
        """RF winner refit reuses the sweep's grid program + randomness:
        at the base depth the refit forest is BIT-IDENTICAL to a direct
        full-train fit_raw; a truncated (shallower) winner matches the
        directly grown shallow forest at prediction level (histogram-
        snapshot leaves vs final leaf dots; exact for integer weights)."""
        X, y = _binary_data(2000, 8, seed=11)
        ctxs = self._fold_ctxs(y)
        full_w = ctxs[0][0] + ctxs[0][1]
        proto = OpRandomForestClassifier(num_trees=5)
        pts = grid(max_depth=[3, 6], min_info_gain=[0.0, 0.05])
        g = make_grid_group(proto, pts, "binary", "AuPR")
        assert g.run(X, y, ctxs) is not None

        row = pts.index({"max_depth": 6, "min_info_gain": 0.05})
        rm = g.refit_model(row)
        assert rm is not None
        direct = proto.copy(max_depth=6, min_info_gain=0.05).fit_raw(
            X, y, w=full_w)
        np.testing.assert_array_equal(np.asarray(rm.feat),
                                      np.asarray(direct.feat))
        np.testing.assert_array_equal(np.asarray(rm.thresh),
                                      np.asarray(direct.thresh))
        np.testing.assert_allclose(np.asarray(rm.leaf),
                                   np.asarray(direct.leaf), atol=1e-6)

        row3 = pts.index({"max_depth": 3, "min_info_gain": 0.05})
        rm3 = g.refit_model(row3)
        direct3 = proto.copy(max_depth=3, min_info_gain=0.05).fit_raw(
            X, y, w=full_w)
        p1 = rm3.predict_batch(X).probability[:, 1]
        p3 = direct3.predict_batch(X).probability[:, 1]
        np.testing.assert_allclose(p1, p3, atol=1e-5)

    def test_gbt_group_declines_refit_reuse(self):
        """GBT groups deliberately do NOT append refit chains (the extra
        chains cost ~C/(C·F) of the whole sweep unconditionally, while the
        sequential refit they replace is paid only when GBT wins) — the
        selector must fall back to the sequential refit path."""
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        X, y = _binary_data(1200, 8, seed=10)
        proto = OpXGBoostClassifier(num_round=5, eta=0.2, max_depth=3,
                                    gamma=0.0, early_stopping_rounds=0)
        pts = grid(min_child_weight=[1.0, 10.0])
        g = make_grid_group(proto, pts, "binary", "AuPR")
        assert g.run(X, y, self._fold_ctxs(y)) is not None
        assert g.refit_model(0) is None

    def test_selector_uses_group_refit(self, monkeypatch):
        """fit_columns must consume the group's refit model (no sequential
        fit_raw call for the winner when the group holds one)."""
        import transmogrifai_tpu.models.classification as cls_mod
        from transmogrifai_tpu.types.columns import FeatureColumn
        from transmogrifai_tpu.types.feature_types import OPVector, RealNN

        X, y = _binary_data(2000, 8, seed=12)
        calls = {"n": 0}
        orig = cls_mod.OpLogisticRegression.fit_raw

        def counting_fit_raw(self, *a, **k):
            calls["n"] += 1
            return orig(self, *a, **k)

        monkeypatch.setattr(cls_mod.OpLogisticRegression, "fit_raw",
                            counting_fit_raw)
        sel = ModelSelector(
            [(OpLogisticRegression(), grid(reg_param=[0.01, 0.1]))],
            problem_type="binary",
            validator=OpCrossValidation(num_folds=3, seed=7, stratify=True))
        model = sel.fit_columns(None, FeatureColumn(RealNN, y),
                                FeatureColumn(OPVector, X))
        assert calls["n"] == 0, (
            "winner refit should reuse the group's full-train solve, not "
            "call fit_raw")
        assert model is not None


class TestGroupFailureIsolation:
    def test_group_exception_falls_back(self, monkeypatch):
        """A raising group must not kill the sweep — members refit
        sequentially (reference per-candidate Future isolation)."""
        X, y = _binary_data(500, 6)
        mp = [(OpRandomForestClassifier(num_trees=4), grid(max_depth=[3]))]
        from transmogrifai_tpu.selector import grid_groups

        class Boom(grid_groups.GridGroup):
            def run(self, *a):
                raise RuntimeError("group exploded")

        monkeypatch.setattr(
            grid_groups, "make_grid_group",
            lambda proto, pts, pt, m, **kw: Boom(proto, pts, m))
        import transmogrifai_tpu.selector.model_selector as ms
        best, res = _run_selector(mp, "binary", X, y)
        assert res[0].error is None
        assert np.isfinite(res[0].metric_value)
