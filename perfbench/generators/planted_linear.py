"""Planted sparse linear binary-classification frame.

A copy of ``transmogrifai_tpu.testkit.planted_linear_frame`` (the
benchmark's inputs must not move when the program's test kit does), which
also returns the planted weights: they score any row set exactly as the
model that drew the labels would, which is the oracle of
``reference/oracle.py``.

``cols`` standard-normal Real columns ``f0..f{cols-1}`` and a ``label``
drawn from a logistic model over ``max(3, cols // 20)`` informative
columns with N(0, 1.5) weights and 0.5-sigma logit noise.

With ``weights_seed=None`` the frame is byte-equal to the test kit's at the
same ``(rows, cols, seed)``.  With ``weights_seed`` set, the informative
columns and their weights come from that seed alone, so every ``--seed``
draws new rows of ONE planted model: the deployment's relationship is
fixed by the configuration, and the quality metrics of two runs differ by
sampling noise only, not by how hard a freshly drawn problem happens to be.
"""
from __future__ import annotations

import numpy as np


def _plant(rng, cols: int) -> np.ndarray:
    beta = np.zeros(cols, np.float32)
    informative = rng.choice(cols, max(3, cols // 20), replace=False)
    beta[informative] = rng.normal(size=len(informative)) * 1.5
    return beta


def generate(rows: int, cols: int, seed: int, weights_seed=None):
    """``(frame, beta)``: a pandas frame of ``rows`` rows with ``label``
    first, and the planted float32 weights.  Deterministic in its
    arguments."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    beta = _plant(rng if weights_seed is None
                  else np.random.default_rng(weights_seed), cols)
    z = X @ beta + 0.5 * rng.normal(size=rows).astype(np.float32)
    y = (1 / (1 + np.exp(-z)) > rng.random(rows)).astype(np.float32)
    df = pd.DataFrame(X, columns=[f"f{j}" for j in range(cols)])
    df.insert(0, "label", y)
    return df, beta
