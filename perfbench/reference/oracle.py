"""The oracle of a planted generator: the model that drew the labels
scores the rows, in float64 NumPy.

Binary: no model fitted on the features can have a higher expected AuPR than
the planted logit itself (the label is Bernoulli in it, plus 0.5-sigma logit
noise no model can see), so a logistic regression on hundreds of thousands
of rows must come close to it and nothing may come out far above it.

Regression: no model can have a lower expected squared error than the
planted mean (the label is that mean plus noise no model can see), so no
hold-out RMSE may come out far below the oracle's.
"""
from __future__ import annotations

import numpy as np


def aupr(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the precision-recall curve by the step-wise sum over
    distinct thresholds (average precision), float64."""
    y = np.asarray(y, np.float64)
    score = np.asarray(score, np.float64)
    order = np.argsort(-score, kind="stable")
    y, score = y[order], score[order]
    last = np.r_[np.nonzero(np.diff(score))[0], len(y) - 1]
    tp = np.cumsum(y)[last]
    precision = tp / (last + 1.0)
    recall = tp / max(y.sum(), 1.0)
    return float(np.sum(np.diff(np.r_[0.0, recall]) * precision))


def oracle_aupr(X: np.ndarray, y: np.ndarray, beta: np.ndarray) -> float:
    """AuPR of the planted logit ``X @ beta`` against ``y``."""
    z = np.asarray(X, np.float64) @ np.asarray(beta, np.float64)
    return aupr(y, z)


def rmse(y: np.ndarray, prediction: np.ndarray) -> float:
    """Root mean squared error, float64."""
    err = np.asarray(prediction, np.float64) - np.asarray(y, np.float64)
    return float(np.sqrt(np.mean(err ** 2)))
