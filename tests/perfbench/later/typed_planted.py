"""Planted typed binary-classification frame: nullable numerics beside
categoricals, rare positives, a model that is NOT linear in the raw columns.

The shape of the public card-fraud tables at a probe's size, 15 columns:

``r0..r5``  Real; ``r0..r2`` are missing together in 30 % of the rows and
            ``r3..r5`` in another 15 % (numerics that are missing in blocks)
``i0..i3``  Integral: Poisson counts with means 1, 3, 10, 30, never null
``c0..c4``  PickList of 3, 8, 40, 300 and 5,000 values ``v0, v1, ...`` with
            Zipf counts (value k has weight 1 / (k + 1)) and 5 % nulls

The label is Bernoulli in a logit over the values (NaN counts as 0), the two
block-null flags, ``log1p`` of the counts and one effect per category value
(and per null category), with the intercept set so that 5 % are positive.
The model comes from ``weights_seed`` alone (its intercept from 20,000 rows
drawn from that seed), so every ``--seed`` draws new rows of ONE model.
``oracle_score`` is that logit: no fitted model has a higher expected AuPR.
"""
import numpy as np

REAL, INTEGRAL, CARD = 6, 4, (3, 8, 40, 300, 5000)
NULL_BLOCKS = ((0, 3, 0.30), (3, 6, 0.15))   # (first, past last, share)
INT_MEANS = (1.0, 3.0, 10.0, 30.0)
CAT_NULL, POSITIVES = 0.05, 0.05


def _draw(rng, rows: int) -> dict:
    real = rng.normal(size=(rows, REAL)).astype(np.float32)
    for lo, hi, share in NULL_BLOCKS:
        real[rng.random(rows) < share, lo:hi] = np.nan
    ints = rng.poisson(INT_MEANS, size=(rows, INTEGRAL))
    cats = np.empty((rows, len(CARD)), np.int64)
    for j, card in enumerate(CARD):
        p = 1.0 / np.arange(1, card + 1)
        cats[:, j] = rng.choice(card, size=rows, p=p / p.sum())
    cats[rng.random(cats.shape) < CAT_NULL] = -1   # -1 is the null category
    return {"real": real, "ints": ints, "cats": cats}


def _logit(cols: dict, planted: dict) -> np.ndarray:
    real = cols["real"].astype(np.float64)
    z = np.nan_to_num(real) @ planted["real"]
    z += np.isnan(real[:, [lo for lo, _, _ in NULL_BLOCKS]]) @ planted["null"]
    z += np.log1p(cols["ints"]) @ planted["ints"]
    for j, effect in enumerate(planted["cats"]):
        z += effect[cols["cats"][:, j]]            # effect[-1]: the null's
    return z + planted["intercept"]


def _plant(rng) -> dict:
    planted = {"real": rng.normal(size=REAL), "null": rng.normal(size=2) * 1.5,
               "ints": rng.normal(size=INTEGRAL) * 0.5,
               "cats": [rng.normal(size=card + 1) for card in CARD],
               "intercept": 0.0}
    z = _logit(_draw(rng, 20_000), planted)
    lo, hi = -30.0, 30.0                           # bisect the intercept
    for _ in range(60):
        mid = (lo + hi) / 2
        if np.mean(1 / (1 + np.exp(-(z + mid)))) > POSITIVES:
            hi = mid
        else:
            lo = mid
    planted["intercept"] = (lo + hi) / 2
    return planted


def _frame_cols(frame) -> dict:
    cats = np.stack([frame[f"c{j}"].str[1:].fillna(-1).astype(np.int64)
                     for j in range(len(CARD))], axis=1)
    return {"real": frame[[f"r{j}" for j in range(REAL)]].to_numpy(),
            "ints": frame[[f"i{j}" for j in range(INTEGRAL)]].to_numpy(),
            "cats": cats}


def oracle_score(frame, planted: dict) -> np.ndarray:
    """The planted logit of each row of a frame ``generate`` drew."""
    return _logit(_frame_cols(frame), planted)


def generate(rows: int, cols: int, seed: int, weights_seed: int = 7):
    """``(frame, planted)``: ``label`` first, then the 15 typed columns."""
    import pandas as pd

    if cols != REAL + INTEGRAL + len(CARD):
        raise ValueError(f"typed_planted draws {REAL + INTEGRAL + len(CARD)} "
                         f"columns, not {cols}")
    planted = _plant(np.random.default_rng(weights_seed))
    rng = np.random.default_rng(seed)
    drawn = _draw(rng, rows)
    p = 1 / (1 + np.exp(-_logit(drawn, planted)))
    data = {"label": (p > rng.random(rows)).astype(np.float32)}
    data.update({f"r{j}": drawn["real"][:, j] for j in range(REAL)})
    data.update({f"i{j}": drawn["ints"][:, j] for j in range(INTEGRAL)})
    for j in range(len(CARD)):
        k = drawn["cats"][:, j]
        data[f"c{j}"] = np.where(k < 0, None, np.char.add("v", k.astype(str)))
    return pd.DataFrame(data), planted
