"""The harness's regression half, without a process of its own: the float64
regression forest of ``reference/rf_grid.py``, the walker's and the oracle's
regression branches, the checks' RMSE bands on hand-made records, and the
two forest readers on a regression configuration and, unchanged, on
``dense500-rf-grid18``'s.
"""
import importlib.util
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import checks, spec, trace_reduce  # noqa: E402
from perfbench.reference import (hist_gbt, oracle, rf_grid,  # noqa: E402
                                 tree_walker)

LATER = os.path.join(ROOT, "tests", "perfbench", "later")
TRACE = os.path.join(ROOT, "perfbench", "testdata",
                     "tiny_xgb_v5e.xplane.pb.xz")


def _later_json(name):
    with open(os.path.join(LATER, name)) as f:
        return json.load(f)


def _generator():
    file = importlib.util.spec_from_file_location(
        "planted_regression_probe",
        os.path.join(LATER, "planted_regression_probe.py"))
    gen = importlib.util.module_from_spec(file)
    file.loader.exec_module(gen)
    return gen


# -- the float64 regression forest --------------------------------------------

def test_a_year_valued_target_grows_the_trees_of_the_centered_one():
    """Variance impurity on sums of the CENTERED target: a target offset by
    1998 splits where the same target with mean 0 splits, and its leaves
    lie 1998 above."""
    frame, _ = _generator().generate(2000, 16, 5)
    X = frame.drop(columns=["label"]).to_numpy(np.float32)
    y = frame["label"].to_numpy(np.float64)
    centered = y - y.mean()
    binned = hist_gbt.bin_matrix(X, hist_gbt.quantile_edges(X, 32))
    rng = np.random.default_rng(0)
    bags = rng.poisson(1.0, (3, len(y))).astype(np.float64)
    subsets = [rng.choice(16, 16 // 3, replace=False) for _ in range(3)]
    for gate, inst in ((0.001, 10.0), (0.1, 100.0)):
        f0, t0, l0 = rf_grid.grow_forest(binned, centered, np.ones(len(y)),
                                         bags, subsets, 6, gate, inst, 32,
                                         regression=True)
        f1, t1, l1 = rf_grid.grow_forest(binned, centered + 1998.0,
                                         np.ones(len(y)), bags, subsets, 6,
                                         gate, inst, 32, regression=True)
        assert (f0 == f1).all() and (t0 == t1).all()
        reached = l1 != 0.0
        assert reached.sum() > 3 * 8        # more than depth 3's leaves
        np.testing.assert_allclose(l1[reached] - l0[reached], 1998.0,
                                   rtol=0, atol=1e-9)
        assert (l0[~reached] == 0.0).all()
    # the trees are grown deep: the fifth level splits
    assert (t0[:, 15:31] < 32).any()


@pytest.mark.parametrize("gate,splits", [(3.5, True), (3.6, False)])
def test_a_regression_split_is_the_variance_it_removes_per_instance(
        gate, splits):
    """One column, bins 0-2, targets 1, 1, 1, 1, 5, 5: the split "bin <= 1
    goes left" removes the node's whole variance, 3.556 an instance, which
    the gate reads as Spark's minInfoGain for variance impurity."""
    binned = np.array([[0], [0], [1], [1], [2], [2]])
    y = np.array([1.0, 1.0, 1.0, 1.0, 5.0, 5.0])
    feat, thresh, leaf = rf_grid.grow_tree(binned, y, np.ones(6), [0], 1,
                                           gate, 1.0, 3, regression=True)
    assert np.var(y) == pytest.approx(3.5556, abs=1e-4)
    if splits:
        assert (feat[0], thresh[0]) == (0, 1)
        np.testing.assert_allclose(leaf[:, 0], [1.0, 5.0])
    else:
        assert thresh[0] == 3                 # every row goes left
        np.testing.assert_allclose(leaf[:, 0], [np.mean(y), 0.0])


def test_the_regression_forest_predicts_the_mean_of_its_leaves():
    feat = np.array([[0], [0]])
    thresh = np.array([[0], [1]])
    leaf = np.array([[[1.0], [3.0]], [[2.0], [4.0]]])
    binned = np.array([[0], [1], [2]])
    np.testing.assert_allclose(rf_grid.predict_mean(binned, feat, thresh,
                                                    leaf), [1.5, 2.5, 3.5])


# -- the walker and the oracle ------------------------------------------------

def test_the_walker_gives_a_regression_ensemble_s_prediction():
    edges = np.array([[0.5, 1.5]], np.float32)    # value v lands in bin v
    X = np.array([[0.0], [1.0], [2.0]], np.float32)
    feat = np.array([[0], [0]])
    thresh = np.array([[0], [1]])
    leaf = np.array([[[1.0], [3.0]], [[2.0], [4.0]]])
    np.testing.assert_allclose(
        tree_walker.prediction(X, edges, feat, thresh, leaf, "rf_reg", 0.5),
        [2.0, 3.0, 4.0])
    np.testing.assert_allclose(
        tree_walker.prediction(X, edges, feat, thresh, leaf, "gbdt_reg",
                               10.0), [13.0, 15.0, 17.0])
    with pytest.raises(ValueError):
        tree_walker.prediction(X, edges, feat, thresh, leaf, "rf_cls")
    with pytest.raises(ValueError):
        tree_walker.probability_1(X, edges, feat, thresh, leaf, "rf_reg")


def test_rmse_of_the_oracle():
    assert oracle.rmse([1.0, 2.0, 3.0], [1.0, 2.0, 3.0]) == 0.0
    assert oracle.rmse([0.0, 0.0], [3.0, -4.0]) == pytest.approx(
        np.sqrt(12.5))


# -- the checks' RMSE bands ---------------------------------------------------

def _regression_ctx(rehearsal_shape=False):
    return SimpleNamespace(traffic=_later_json("rf-reg-grid18-probe.json"),
                           config=_later_json("regression-probe.json"),
                           rehearsal_shape=rehearsal_shape)


def test_a_regression_candidate_outside_its_cv_rmse_band_is_a_problem():
    ctx = _regression_ctx()
    lo, hi = ctx.traffic["checks"]["quality_band"]["cv_rmse"][
        "OpRandomForestRegressor"]
    inside = {"model": "OpRandomForestRegressor", "params": {"max_depth": 3},
              "cv": (lo + hi) / 2}
    above = dict(inside, params={"max_depth": 12}, cv=hi + 0.25)
    below = dict(inside, params={"max_depth": 6}, cv=lo - 0.25)
    assert checks.candidate_band_problems(ctx, [inside]) == []
    problems = checks.candidate_band_problems(ctx, [inside, above, below])
    assert len(problems) == 2
    assert all("CV RMSE" in p and "OpRandomForestRegressor" in p
               for p in problems)
    assert "'max_depth': 12" in problems[0] and "'max_depth': 6" in problems[1]
    # each train's lowest and highest beside the band, under cv_rmse
    got = checks.compared_cv(ctx, [{"candidates": [inside, above]}])
    assert got == {"cv_rmse.OpRandomForestRegressor": [
        [inside["cv"], above["cv"]], [lo, hi]],
        "cv_spread.OpRandomForestRegressor": [
            pytest.approx(above["cv"] / inside["cv"] - 1),
            ctx.traffic["checks"]["quality_band"]["cv_spread_min"][
                "OpRandomForestRegressor"]]}
    # no band under a rehearsal shape
    ctx = _regression_ctx(rehearsal_shape=True)
    assert checks.candidate_band_problems(ctx, [above]) == []
    assert checks.compared_cv(ctx, [{"candidates": [above]}]) == {}


def test_each_label_kind_has_its_selector_splitter_and_quality_numbers():
    """One table holds a label kind's facts: upstream's selector and its
    splitter, the CV band's key, the hold-out metric, the metric's name."""
    assert checks.label_kind({"problem": "binary"}) == (
        "BinaryClassificationModelSelector", "DataBalancer", "cv_aupr",
        "holdout_aupr", "AuPR")
    assert checks.label_kind({"problem": "regression"}) == (
        "RegressionModelSelector", "DataSplitter", "cv_rmse", "holdout_rmse",
        "RMSE")
    with pytest.raises(KeyError):
        checks.label_kind({"problem": "multiclass"})
    from transmogrifai_tpu import selector as selectors
    for kind in checks.LABEL_KINDS.values():
        assert hasattr(getattr(selectors, kind.selector),
                       "with_cross_validation")


def _spread_candidates(cvs):
    return [{"model": "OpRandomForestRegressor", "params": {"max_depth": d},
             "cv": cv} for d, cv in zip((3, 6, 12), cvs)]


def test_a_grid_whose_candidates_all_read_one_cv_rmse_is_a_problem():
    """A forest that stops growing reads one CV RMSE at every depth, inside
    the band: the grid's spread catches it, in the warm-up and in every
    train of the window, and the result line carries the least spread
    beside its limit."""
    ctx = _regression_ctx()
    band = ctx.traffic["checks"]["quality_band"]
    least = band["cv_spread_min"]["OpRandomForestRegressor"]
    lo, hi = band["cv_rmse"]["OpRandomForestRegressor"]
    grown = _spread_candidates([6.2, 5.9, 5.9 * (1 + 2 * least)])
    flat = _spread_candidates([6.0, 6.0 * (1 + least / 4), 6.0])
    assert all(lo <= c["cv"] <= hi for c in grown + flat)
    assert checks.cv_spreads(grown)["OpRandomForestRegressor"] == (
        pytest.approx(6.2 / 5.9 - 1))
    assert checks.candidate_band_problems(ctx, grown) == []
    (problem,) = checks.candidate_band_problems(ctx, flat)
    assert "OpRandomForestRegressor" in problem and "one model" in problem
    got = checks.compared_cv(ctx, [{"candidates": grown},
                                   {"candidates": flat}])
    assert got["cv_spread.OpRandomForestRegressor"] == [
        pytest.approx(least / 4), least]
    # an estimator the band gives no least spread is not held to one
    del ctx.traffic["checks"]["quality_band"]["cv_spread_min"]
    assert checks.candidate_band_problems(ctx, flat) == []
    assert "cv_spread.OpRandomForestRegressor" not in checks.compared_cv(
        ctx, [{"candidates": flat}])


def test_the_probe_s_bands_hold_the_reference_s_readings():
    band = _later_json("rf-reg-grid18-probe.json")["checks"]["quality_band"]
    ref = band["reference"]["OpRandomForestRegressor"]
    assert "perfbench/reference/rf_grid.py --problem regression" in (
        ref["command"])
    lo, hi = band["cv_rmse"]["OpRandomForestRegressor"]
    assert lo < ref["cv_rmse_lowest"] < ref["cv_rmse_highest"] < hi
    h_lo, h_hi = band["holdout_rmse"]
    assert h_lo < ref["holdout_rmse"] < h_hi
    # and the planted mean's own RMSE lies below every band: a fit that
    # matched the oracle would be a finding, not a pass
    assert ref["oracle_rmse"] < min(lo, h_lo)
    # the reference's grid spreads well past the least spread held
    least = band["cv_spread_min"]["OpRandomForestRegressor"]
    assert ref["cv_rmse_highest"] / ref["cv_rmse_lowest"] - 1 > 3 * least


# -- the forest readers -------------------------------------------------------

#: ``rf_score_device_s``'s pattern as the parent benchmark had it
PARENT_SCORE_PATTERN = (r"score_pairs|score_ensemble|predict_ensemble"
                        r"|predict_tree|predict_round|aupr_dev|auroc_dev"
                        r"|metric_grid")
EAGER = {"jit__reduce_sum": 0.002, "jit_subtract": 0.003,
         "jit_integer_pow": 0.004, "jit_multiply": 0.005,
         "jit_maximum": 0.006, "jit_true_divide": 0.007, "jit_sqrt": 0.008}
MODULES = dict({"jit__grow_chunk_rf_grid": 5.8, "jit__score_ensemble_jit": 1.2,
                "jit__aupr_dev": 0.23, "jit_predict_ensemble": 0.05,
                "jit__fold_rows_jit": 0.004, "jit_add": 0.009}, **EAGER)
RF_GRID = {"treesGrown": 84, "levels": 12, "msub": 22, "launches": 6}


def _sources(config, modules=MODULES, rows=250_000, platform="tpu"):
    return {"counters": {"rfGrid": RF_GRID}, "device_kind": "TPU v5 lite",
            "cell": {"rows": rows, "cols": 500, "config": config},
            "trace": {"platform": platform, "devices": {
                "/device:TPU:0": {"module_s": modules}}}}


def _read(name, sources):
    return spec.load_module("metrics", name).read(sources)


def test_the_forest_grid_cell_reads_what_the_parent_s_readers_read():
    """``dense500-rf-grid18``'s configuration: on the recorded trace and on
    hand-made modules with the eager names in them, ``rf_score_device_s`` is
    the parent's pattern's sum to the last digit, and ``rf_hist_roofline``
    the parent's formula."""
    binary = spec.load_cell("dense500-rf-grid18")["config"]
    recorded = dict(_sources(binary), trace=trace_reduce.reduce_trace(TRACE))
    for sources in (recorded, _sources(binary)):
        assert _read("rf_score_device_s", sources) == (
            trace_reduce.module_seconds(sources["trace"],
                                        PARENT_SCORE_PATTERN) or None)
    assert _read("rf_score_device_s", _sources(binary)) == pytest.approx(
        1.2 + 0.23 + 0.05)
    assert _read("rf_hist_roofline", _sources(binary)) == (
        100.0 * (84.0 * 12 * 250_000 * (22 + 8)) / 5.8 / (819.0 * 1e9))


def test_a_regression_cell_s_scoring_leaves_out_modules_named_by_a_primitive():
    """The eager metric grid's modules carry a primitive's name that any
    eager op of the train may carry: a regression configuration reads the
    scoring modules alone, as a binary one does."""
    regression = _later_json("regression-probe.json")
    binary = spec.load_cell("dense500-rf-grid18")["config"]
    got = _read("rf_score_device_s", _sources(regression))
    assert got == _read("rf_score_device_s", _sources(binary))
    assert got == pytest.approx(1.2 + 0.23 + 0.05)
    # a metric grid jitted as one program would be counted by its name
    assert _read("rf_score_device_s", _sources(regression, modules=dict(
        MODULES, jit_regression_metric_grid=0.01))) == pytest.approx(
            1.2 + 0.23 + 0.05 + 0.01)
    # never on a CPU trace, and nothing where no forest was scored
    assert _read("rf_score_device_s",
                 _sources(regression, platform="cpu")) is None
    assert _read("rf_score_device_s", _sources(
        regression, modules={"jit__gbt_chain_rounds_jit": 2.6})) is None


def test_a_one_channel_target_s_histogram_reads_the_binary_target_s_bytes():
    """``_grow_tree_traced`` builds two float32 channels either way: a
    binary target's class weight and the bag weight ("onehot"), a
    regression target's weighted value and the bag weight ("bagged")."""
    from perfbench.metrics import rf_hist_roofline

    regression = _later_json("regression-probe.json")
    binary = spec.load_cell("dense500-rf-grid18")["config"]
    assert rf_hist_roofline.histogram_bytes(84, 12, 12_000, 10) == (
        84 * 12 * 12_000 * (10 + 8))
    assert _read("rf_hist_roofline", _sources(regression)) == _read(
        "rf_hist_roofline", _sources(binary))
