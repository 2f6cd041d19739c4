"""The comparisons that decide ``correct`` for a fitted workflow model.

They run once, in set-up, on the model of the warm-up train; the CV half of
the quality band is applied again to every train of the window:

oracle        the planted model scores the hold-out in float64 NumPy: the
              generator's own ``oracle_score(frame, planted)`` where it
              defines one (a planted model that is not linear in the raw
              columns, a frame with strings or nulls), else ``X @ beta`` on
              the float32 matrix (``reference/oracle.py``, which holds the
              one AuPR for both).  No model may come out above the oracle
              by more than ``oracle_slack``; a traffic file that gives
              ``oracle_gap_max`` also holds the winner within that much
              BELOW it.
tree scorer   a tree winner's ``(feat, thresh, leaf)`` arrays, walked by
              the float64 NumPy walker of ``reference/tree_walker.py`` on
              the first ``tree_scorer_rows`` hold-out rows, agree with
              ``model.score`` to ``tree_scorer_atol``.
quality band  every candidate's CV AuPR (a band per estimator class) and the
              winner's hold-out AuPR lie inside the bands the traffic file
              records.  A band is a
              QUALITY band around the plain reference's result
              (``reference/hist_gbt.py``), not parity with it.

The bands and the oracle gap are properties of the configuration's own
shape; a rehearsal at overridden rows or columns skips them and says so.
"""
from __future__ import annotations

import math

import numpy as np


def _prob1(scored) -> np.ndarray:
    from transmogrifai_tpu.types.feature_types import Prediction

    for name in scored.names():
        col = scored[name]
        if issubclass(col.ftype, Prediction):
            return np.asarray(col.values.probability)[:, 1]
    raise RuntimeError("model.score returned no Prediction column")


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


def check_tree_scorer(model, hold, checked, rows: int, atol: float) -> dict:
    """Max abs difference of P(class 1) between ``model.score`` and the
    NumPy walker over the winner's trees, or ``None`` for no tree winner."""
    from perfbench.reference import tree_walker

    trees = _tree_stage(model)
    if trees is None:
        return {"max_abs_diff": None, "ok": True}
    head = hold.head(rows)
    scored = model.score(data=head, keep_raw_features=True,
                         keep_intermediate_features=True)
    X = np.asarray(scored[checked.name].values, np.float32)
    want = _prob1(scored)
    got = tree_walker.probability_1(
        X, trees.edges, trees.feat, trees.thresh, trees.leaf, trees.mode,
        float(trees.base_score))
    diff = float(np.max(np.abs(got - want)))
    return {"max_abs_diff": diff, "ok": diff <= atol, "rows": len(head),
            "trees": int(np.asarray(trees.feat).shape[0])}


def candidate_band_problems(ctx, candidates: list) -> list:
    """Every candidate's CV AuPR against its estimator's band in the traffic
    file; nothing under a rehearsal shape or where the file has no band."""
    band = ctx.traffic["checks"].get("quality_band")
    if ctx.rehearsal_shape or band is None:
        return []
    problems = []
    for c in candidates:
        lo, hi = band["cv_aupr"][c["model"]]
        if not lo <= c["cv"] <= hi:
            problems.append(f"candidate {c['model']} {c['params']} CV AuPR "
                            f"{c['cv']:.4f} outside [{lo}, {hi}]")
    return problems


def compared_cv(ctx, trains: list) -> dict:
    """``{"cv_aupr.<Estimator>": [[lowest, highest], band]}`` over the
    candidates of ``trains`` (records of ``train_loop.one_train``), where
    ``candidate_band_problems`` holds them to a band."""
    band = ctx.traffic["checks"].get("quality_band")
    if ctx.rehearsal_shape or band is None:
        return {}
    seen: dict = {}
    for rec in trains:
        for c in rec["candidates"]:
            seen.setdefault(c["model"], []).append(c["cv"])
    return {f"cv_aupr.{m}": [[min(v), max(v)], band["cv_aupr"][m]]
            for m, v in seen.items()}


def check_model(ctx, model, candidates: list, checked) -> dict:
    """``candidates`` are the warm-up train's, as ``train_loop.one_train``
    records them (``model``, ``params``, ``cv``)."""
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

    walker = check_tree_scorer(model, hold, checked,
                               spec["tree_scorer_rows"],
                               spec["tree_scorer_atol"])
    if walker["max_abs_diff"] is not None:
        compared["tree_scorer_diff"] = [walker["max_abs_diff"],
                                        spec["tree_scorer_atol"]]
    if not walker["ok"]:
        problems.append(f"model.score and the NumPy tree walker differ by "
                        f"{walker['max_abs_diff']:.3g} > "
                        f"{spec['tree_scorer_atol']}")

    banded = not ctx.rehearsal_shape
    if banded:
        gap = spec.get("oracle_gap_max")
        if gap is not None:
            compared["aupr_under_oracle"] = [best - aupr, gap]
            if aupr < best - gap:
                problems.append(f"hold-out AuPR {aupr:.4f} is more than "
                                f"{gap} below the oracle's {best:.4f}")
        problems += candidate_band_problems(ctx, candidates)
        band = spec.get("quality_band")
        if band is not None:
            lo, hi = band["holdout_aupr"]
            compared["holdout_aupr"] = [aupr, [lo, hi]]
            if not lo <= aupr <= hi:
                problems.append(f"hold-out AuPR {aupr:.4f} outside "
                                f"[{lo}, {hi}]")
    verdict = {"holdout_aupr": aupr, "oracle_aupr": best,
               "oracle_from": ("X @ beta" if oracle_score is None
                               else "oracle_score"),
               "tree_scorer": walker, "banded": banded,
               "compared": compared, "problems": problems}
    ctx.say("checks", **verdict)
    return verdict
