"""Planted linear regression frame: a real-valued label whose mean is a
linear function of standard-normal columns.

``cols`` standard-normal ``Real`` columns ``f0..f{cols-1}`` and a ``label``
drawn as ``mean + X @ beta + noise_sd * N(0, 1)``.  ``beta`` is non-zero on
``max(3, cols // 2)`` columns, with N(0, 1.5) weights; the columns and the
weights come from ``weights_seed`` alone, so every ``--seed`` draws new rows
of ONE planted model and two runs' hold-out RMSEs differ by sampling noise
only.  Half the columns carry the mean so that every tree of a forest, which
sees a third of the columns, sees several of them: with a few informative
columns a forest of a few trees is as good as its luck in drawing them.

``oracle_predict`` is the planted mean itself, in float64: no model fitted on
the columns has a lower expected squared error, so its hold-out RMSE (about
``noise_sd``) is the floor a fitted model may not fall below by more than
sampling noise.

``mean`` moves the label without changing what a model can learn: a
release year, as in the public YearPredictionMSD table, is about 1998 with
a spread of about 11.
"""
import numpy as np


def _plant(rng, cols: int) -> np.ndarray:
    beta = np.zeros(cols)
    informative = rng.choice(cols, max(3, cols // 2), replace=False)
    beta[informative] = rng.normal(size=len(informative)) * 1.5
    return beta


def oracle_predict(frame, planted: dict) -> np.ndarray:
    """The planted mean of each row of a frame ``generate`` drew."""
    cols = [f"f{j}" for j in range(len(planted["beta"]))]
    X = frame[cols].to_numpy(np.float64)
    return planted["mean"] + X @ planted["beta"]


def generate(rows: int, cols: int, seed: int, weights_seed: int = 13,
             mean: float = 0.0, noise_sd: float = 2.0):
    """``(frame, planted)``: ``label`` first, then ``f0..f{cols-1}``
    (float32); ``planted`` holds ``beta``, ``mean`` and ``noise_sd``."""
    import pandas as pd

    planted = {"beta": _plant(np.random.default_rng(weights_seed), cols),
               "mean": float(mean), "noise_sd": float(noise_sd)}
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (planted["mean"] + X.astype(np.float64) @ planted["beta"]
         + noise_sd * rng.normal(size=rows))
    df = pd.DataFrame(X, columns=[f"f{j}" for j in range(cols)])
    df.insert(0, "label", y.astype(np.float32))
    return df, planted
