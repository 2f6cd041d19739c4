#!/usr/bin/env python
"""Scale benchmark — BASELINE.md config 4: synthetic wide tabular binary
AutoML sweep (default 1M rows x 100 features; --full for the 1M x 500
headline shape).

Reproduces the reference's BinaryClassificationModelSelector sweep (LR + RF
grids, 3-fold CV, AuPR) on synthetic data with planted signal, end to end
through OpWorkflow.train() — feature engineering, SanityChecker, CV sweep,
final refit.

Prints ONE JSON line like bench.py.  Baseline: 32-core Spark-local runs of
the same selector on 1M rows take tens of minutes (no published number —
SURVEY §6); the 1800 s figure below is our recorded assumption, stated in
the output.

Usage: python examples/bench_scale.py [--rows N] [--cols D] [--full]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from transmogrifai_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()

SPARK_LOCAL_BASELINE_S = 1800.0


def make_data(rows: int, cols: int, seed: int = 11):
    from transmogrifai_tpu.testkit import planted_linear_frame

    return planted_linear_frame(rows, cols, seed)


def default_grid_models():
    """The reference's ACTUAL default binary grid — 28 candidates: the
    library's own LR+RF defaults (model_selector._binary_defaults, the one
    source of truth) plus the XGB block the reference's modelTypesToUse
    enables (BinaryClassificationModelSelector.scala:54-108,
    DefaultSelectorParams.scala:36-75; NumRound=200 x 2 minChildWeight)."""
    from transmogrifai_tpu.models import OpXGBoostClassifier
    from transmogrifai_tpu.selector import DefaultSelectorParams as D
    from transmogrifai_tpu.selector import grid
    from transmogrifai_tpu.selector.model_selector import _binary_defaults

    return _binary_defaults() + [
        (OpXGBoostClassifier(), grid(
            min_child_weight=D.MIN_CHILD_WEIGHT_XGB)),
    ]


def light_grid_models():
    """The r1/r2 longitudinal light grid (6 candidates, 20-tree RF)."""
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier,
    )
    from transmogrifai_tpu.selector import grid

    return [
        (OpLogisticRegression(), grid(reg_param=[0.01, 0.1])),
        (OpRandomForestClassifier(num_trees=20),
         grid(max_depth=[4, 6], min_instances_per_node=[10, 100])),
    ]


def run(rows: int, cols: int, folds: int = 3, warmup: bool = False,
        baseline_s: float = SPARK_LOCAL_BASELINE_S,
        which_grid: str = "light") -> dict:
    """One measured sweep at (rows, cols); importable by bench.py.

    ``which_grid``: 'light' (r1/r2-comparable 6 candidates) or 'default'
    (the reference's true 28-candidate default grid incl. XGB@200)."""

    from transmogrifai_tpu import FeatureBuilder, OpWorkflow, transmogrify
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.preparators import SanityChecker
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector

    t0 = time.perf_counter()
    df = make_data(rows, cols)
    gen_s = time.perf_counter() - t0

    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns[1:]]
    features = transmogrify(preds)
    checked = SanityChecker(max_correlation=0.99).set_input(
        label, features).get_output()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=folds,
        models_and_parameters=(default_grid_models()
                               if which_grid == "default"
                               else light_grid_models()))
    prediction = selector.set_input(label, checked).get_output()
    wf = OpWorkflow().set_result_features(prediction).set_input_data(df)

    warmup_s = 0.0
    if warmup:
        t0 = time.perf_counter()
        wf.train()
        warmup_s = time.perf_counter() - t0

    from transmogrifai_tpu.utils import profiling

    profiling.reset_counters()
    collector = profiling.MetricsCollector(run_type="bench_scale")
    with profiling.install_collector(collector):
        t0 = time.perf_counter()
        model = wf.train()
        train_s = time.perf_counter() - t0
    steps = {m.step: round(m.duration_secs, 1)
             for m in collector.metrics.step_metrics.values()}
    steps.update(collector.metrics.custom_tags)

    _, metrics = model.score_and_evaluate(
        Evaluators.BinaryClassification.auPR())
    summ = next((s.metadata["model_selector_summary"] for s in model.stages
                 if "model_selector_summary" in s.metadata), {})
    n_err = sum(1 for rrow in summ.get("validationResults", [])
                if rrow.get("error"))
    transfers = profiling.COUNTERS.to_json()
    # drainFracOfWall: true dispatch stalls (drainSecs excludes overlapped
    # lagged fetches) over the measured train wall — the async-sweep gate
    # tracks this at < 0.3 on the smoke shape
    drain_frac = (transfers.get("drainSecs", 0.0) / train_s
                  if train_s > 0 else 0.0)
    return {
        "candidates": len(summ.get("validationResults", [])),
        "candidate_errors": n_err,
        "grid": which_grid,
        "metric": "scale_automl_train_wall_clock",
        "rows": rows, "cols": cols,
        "value": round(train_s, 1), "unit": "s",
        "vs_baseline": round(baseline_s / train_s, 2),
        "aupr": round(float(metrics["AuPR"]), 4),
        "auroc": round(float(metrics["AuROC"]), 4),
        "datagen_s": round(gen_s, 1),
        "baseline_s_assumed": baseline_s,
        "warmup_s": round(warmup_s, 1),
        "phases": steps,
        "transfers": transfers,
        "drainFracOfWall": round(drain_frac, 4),
        "winner": {"model": summ.get("bestModelType"),
                   "params": summ.get("bestModelParams")},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--cols", type=int, default=100)
    ap.add_argument("--full", action="store_true",
                    help="BASELINE config 4 headline shape (1M x 500)")
    ap.add_argument("--folds", type=int, default=3)
    ap.add_argument("--warmup", action="store_true",
                    help="train once untimed first (exclude compile costs)")
    ap.add_argument("--grid", default="light",
                    choices=["light", "default"],
                    help="light (r1/r2-comparable 6 candidates) or the "
                         "reference's true 28-candidate default grid")
    ap.add_argument("--baseline-s", type=float,
                    default=SPARK_LOCAL_BASELINE_S,
                    help="baseline seconds for the vs_baseline ratio "
                         "(bench.py passes benchmarks/baselines.json's "
                         "value when it runs this as the headline child)")
    args = ap.parse_args()
    if args.full:
        args.rows, args.cols = 1_000_000, 500
    print(json.dumps(run(args.rows, args.cols, folds=args.folds,
                         warmup=args.warmup, which_grid=args.grid,
                         baseline_s=args.baseline_s)))


if __name__ == "__main__":
    main()
