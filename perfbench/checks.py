"""The comparisons that decide ``correct`` for a fitted workflow model.

They run once, in set-up, on the model of the warm-up train; the CV half of
the quality band is applied again to every train of the window.  What they
compare follows the configuration's ``problem``:

oracle        the planted model scores the hold-out in float64 NumPy.
              Binary: the generator's own ``oracle_score(frame, planted)``
              where it defines one (a planted model that is not linear in
              the raw columns, a frame with strings or nulls), else ``X @
              beta`` on the float32 matrix (``reference/oracle.py``, which
              holds the one AuPR for both); no model may come out above the
              oracle by more than ``oracle_slack``, and a traffic file that
              gives ``oracle_gap_max`` also holds the winner within that much
              BELOW it.  Regression: the generator's ``oracle_predict(frame,
              planted)``, the planted mean; no model's hold-out RMSE may come
              out below the oracle's by more than ``oracle_slack``, a
              fraction of the oracle's RMSE.
tree scorer   a tree winner's ``(feat, thresh, leaf)`` arrays, walked by
              the float64 NumPy walker of ``reference/tree_walker.py`` on
              the first ``tree_scorer_rows`` hold-out rows, agree with
              ``model.score`` to ``tree_scorer_atol``: P(class 1) for a
              binary label, the prediction for a regression one.
quality band  every candidate's CV metric (a band per estimator class:
              ``cv_aupr`` or ``cv_rmse``) and the winner's hold-out metric
              (``holdout_aupr`` or ``holdout_rmse``) lie inside the bands the
              traffic file records.  A band is a QUALITY band around the
              plain reference's result (``reference/hist_gbt.py``,
              ``reference/rf_grid.py``), not parity with it.
grid spread   where the band gives ``cv_spread_min`` for an estimator
              class, its candidates' CV metrics in one train lie at least
              that far apart, ``(highest - lowest) / lowest``: a grid whose
              depths and gates all read one number grew one forest, however
              well that forest scores (a forest that stops splitting at
              depth 2 sits inside a band set for 4 trees).

The bands and the oracle gap are properties of the configuration's own
shape; a rehearsal at overridden rows or columns skips them and says so.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np


class LabelKind(NamedTuple):
    """What a label kind is trained and judged with."""
    selector: str    # the ModelSelector class of ``transmogrifai_tpu.selector``
    splitter: str    # the splitter that selector takes upstream
    cv: str          # the CV band's key in the traffic file
    holdout: str     # the hold-out metric: an end-to-end metric of
    #                  BENCHMARK.json and the hold-out band's key
    metric: str      # the metric's name in a problem line


LABEL_KINDS = {
    "binary": LabelKind("BinaryClassificationModelSelector", "DataBalancer",
                        "cv_aupr", "holdout_aupr", "AuPR"),
    "regression": LabelKind("RegressionModelSelector", "DataSplitter",
                            "cv_rmse", "holdout_rmse", "RMSE")}


def label_kind(config: dict) -> LabelKind:
    """``LABEL_KINDS`` of the configuration's ``problem``."""
    return LABEL_KINDS[config["problem"]]


def _predictions(scored):
    from transmogrifai_tpu.types.feature_types import Prediction

    for name in scored.names():
        col = scored[name]
        if issubclass(col.ftype, Prediction):
            return col.values
    raise RuntimeError("model.score returned no Prediction column")


def _prob1(scored) -> np.ndarray:
    return np.asarray(_predictions(scored).probability)[:, 1]


def _tree_stage(model):
    from transmogrifai_tpu.models.trees import TreeEnsembleModel

    for s in model.stages:
        inner = getattr(s, "inner", s)
        if isinstance(inner, TreeEnsembleModel):
            return inner
    return None


def holdout_aupr(model, hold) -> float:
    from transmogrifai_tpu.evaluators import Evaluators

    _, metrics = model.score_and_evaluate(
        Evaluators.BinaryClassification.auPR(), data=hold)
    return float(metrics["AuPR"])


def holdout_rmse(model, hold) -> float:
    from transmogrifai_tpu.evaluators import Evaluators

    _, metrics = model.score_and_evaluate(
        Evaluators.Regression.rmse(), data=hold)
    return float(metrics["RootMeanSquaredError"])


def check_tree_scorer(model, hold, checked, rows: int, atol: float,
                      problem: str = "binary") -> dict:
    """Max abs difference between ``model.score`` and the NumPy walker over
    the winner's trees (of P(class 1), or of the prediction for a
    regression label), or ``None`` for no tree winner."""
    from perfbench.reference import tree_walker

    trees = _tree_stage(model)
    if trees is None:
        return {"max_abs_diff": None, "ok": True}
    head = hold.head(rows)
    scored = model.score(data=head, keep_raw_features=True,
                         keep_intermediate_features=True)
    X = np.asarray(scored[checked.name].values, np.float32)
    if problem == "regression":
        want = np.asarray(_predictions(scored).prediction, np.float64)
        walk = tree_walker.prediction
    else:
        want = _prob1(scored)
        walk = tree_walker.probability_1
    got = walk(X, trees.edges, trees.feat, trees.thresh, trees.leaf,
               trees.mode, float(trees.base_score))
    diff = float(np.max(np.abs(got - want)))
    return {"max_abs_diff": diff, "ok": diff <= atol, "rows": len(head),
            "trees": int(np.asarray(trees.feat).shape[0])}


def candidate_band_problems(ctx, candidates: list) -> list:
    """Every candidate's CV metric against its estimator's band in the
    traffic file; nothing under a rehearsal shape or where the file has no
    band; and the spread of each estimator's candidates where the band gives
    its ``cv_spread_min``."""
    band = ctx.traffic["checks"].get("quality_band")
    if ctx.rehearsal_shape or band is None:
        return []
    kind = label_kind(ctx.config)
    problems = []
    for c in candidates:
        lo, hi = band[kind.cv][c["model"]]
        if not lo <= c["cv"] <= hi:
            problems.append(f"candidate {c['model']} {c['params']} CV "
                            f"{kind.metric} {c['cv']:.4f} outside "
                            f"[{lo}, {hi}]")
    least = band.get("cv_spread_min", {})
    for m, spread in cv_spreads(candidates).items():
        if m in least and not spread >= least[m]:
            problems.append(f"the candidates of {m} read CV {kind.metric}s "
                            f"{spread:.4g} apart, under {least[m]}: the "
                            f"grid's points grew one model")
    return problems


def cv_spreads(candidates: list) -> dict:
    """``{Estimator: (highest - lowest) / lowest}`` of the candidates' CV
    metrics, an estimator class at a time; a class of one candidate has no
    spread."""
    seen: dict = {}
    for c in candidates:
        seen.setdefault(c["model"], []).append(c["cv"])
    return {m: (max(v) - min(v)) / min(v) for m, v in seen.items()
            if len(v) > 1}


def compared_cv(ctx, trains: list) -> dict:
    """``{"cv_aupr.<Estimator>": [[lowest, highest], band]}`` (``cv_rmse``
    for a regression label) over the candidates of ``trains`` (records of
    ``train_loop.one_train``), where ``candidate_band_problems`` holds them
    to a band; and ``{"cv_spread.<Estimator>": [least, cv_spread_min]}``,
    the least spread of one train, where it holds that."""
    band = ctx.traffic["checks"].get("quality_band")
    if ctx.rehearsal_shape or band is None:
        return {}
    cv_key = label_kind(ctx.config).cv
    seen: dict = {}
    for rec in trains:
        for c in rec["candidates"]:
            seen.setdefault(c["model"], []).append(c["cv"])
    out = {f"{cv_key}.{m}": [[min(v), max(v)], band[cv_key][m]]
           for m, v in seen.items()}
    least = band.get("cv_spread_min", {})
    spreads = [cv_spreads(rec["candidates"]) for rec in trains]
    for m in seen:
        if m in least:
            out[f"cv_spread.{m}"] = [min(s[m] for s in spreads if m in s),
                                     least[m]]
    return out


def _holdout_band(ctx, value: float, compared: dict, problems: list) -> None:
    """The winner's hold-out metric against the traffic file's band."""
    band = ctx.traffic["checks"].get("quality_band")
    if band is None:
        return
    kind = label_kind(ctx.config)
    key, metric = kind.holdout, kind.metric
    lo, hi = band[key]
    compared[key] = [value, [lo, hi]]
    if not lo <= value <= hi:
        problems.append(f"hold-out {metric} {value:.4f} outside [{lo}, {hi}]")


def _walker_problems(ctx, model, checked, compared: dict) -> tuple:
    spec = ctx.traffic["checks"]
    walker = check_tree_scorer(model, ctx.hold, checked,
                               spec["tree_scorer_rows"],
                               spec["tree_scorer_atol"],
                               ctx.config["problem"])
    if walker["max_abs_diff"] is not None:
        compared["tree_scorer_diff"] = [walker["max_abs_diff"],
                                        spec["tree_scorer_atol"]]
    problems = [] if walker["ok"] else [
        f"model.score and the NumPy tree walker differ by "
        f"{walker['max_abs_diff']:.3g} > {spec['tree_scorer_atol']}"]
    return walker, problems


def check_model(ctx, model, candidates: list, checked) -> dict:
    """``candidates`` are the warm-up train's, as ``train_loop.one_train``
    records them (``model``, ``params``, ``cv``)."""
    if ctx.config["problem"] == "regression":
        return _check_regression(ctx, model, candidates, checked)
    from perfbench.reference import oracle

    spec = ctx.traffic["checks"]
    hold = ctx.hold
    label = ctx.config["schema"]["label"]["name"]
    problems = []

    aupr = holdout_aupr(model, hold)
    yh = hold[label].to_numpy()
    oracle_score = getattr(ctx.generator, "oracle_score", None)
    if oracle_score is None:
        Xh = hold.drop(columns=[label]).to_numpy(np.float32)
        best = oracle.oracle_aupr(Xh, yh, ctx.planted)
    else:
        best = oracle.aupr(yh, oracle_score(hold, ctx.planted))
    # each number compared goes beside its limit into ``compared``, for the
    # run's last lines
    compared = {"aupr_over_oracle": [aupr - best, spec["oracle_slack"]]}
    if not math.isfinite(aupr):
        problems.append("hold-out AuPR is not finite")
    if aupr > best + spec["oracle_slack"]:
        problems.append(f"hold-out AuPR {aupr:.4f} is above the oracle's "
                        f"{best:.4f} by more than {spec['oracle_slack']}")

    walker, walker_problems = _walker_problems(ctx, model, checked, compared)
    problems += walker_problems

    banded = not ctx.rehearsal_shape
    if banded:
        gap = spec.get("oracle_gap_max")
        if gap is not None:
            compared["aupr_under_oracle"] = [best - aupr, gap]
            if aupr < best - gap:
                problems.append(f"hold-out AuPR {aupr:.4f} is more than "
                                f"{gap} below the oracle's {best:.4f}")
        problems += candidate_band_problems(ctx, candidates)
        _holdout_band(ctx, aupr, compared, problems)
    verdict = {"holdout_aupr": aupr, "oracle_aupr": best,
               "oracle_from": ("X @ beta" if oracle_score is None
                               else "oracle_score"),
               "tree_scorer": walker, "banded": banded,
               "compared": compared, "problems": problems}
    ctx.say("checks", **verdict)
    return verdict


def _check_regression(ctx, model, candidates: list, checked) -> dict:
    from perfbench.reference import oracle

    spec = ctx.traffic["checks"]
    hold = ctx.hold
    label = ctx.config["schema"]["label"]["name"]
    problems = []

    rmse = holdout_rmse(model, hold)
    best = oracle.rmse(hold[label].to_numpy(),
                       ctx.generator.oracle_predict(hold, ctx.planted))
    slack = spec["oracle_slack"]
    # the ratio may not fall under 1 - slack: no fit beats the planted mean
    # by more than the hold-out's sampling noise
    compared = {"rmse_over_oracle": [rmse / best, 1.0 - slack]}
    if not math.isfinite(rmse):
        problems.append("hold-out RMSE is not finite")
    if rmse < best * (1.0 - slack):
        problems.append(f"hold-out RMSE {rmse:.4f} is below the oracle's "
                        f"{best:.4f} by more than {slack} of it")

    walker, walker_problems = _walker_problems(ctx, model, checked, compared)
    problems += walker_problems

    banded = not ctx.rehearsal_shape
    if banded:
        problems += candidate_band_problems(ctx, candidates)
        _holdout_band(ctx, rmse, compared, problems)
    verdict = {"holdout_rmse": rmse, "oracle_rmse": best,
               "oracle_from": "oracle_predict", "tree_scorer": walker,
               "banded": banded, "compared": compared, "problems": problems}
    ctx.say("checks", **verdict)
    return verdict
