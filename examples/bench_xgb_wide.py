#!/usr/bin/env python
"""XGBoost-parity benchmark — BASELINE.md config 5: wide sparse binary
classification stressing the GBDT histogram build.

Synthetic stand-in for the Criteo sample (the real data is not in the
image): wide, mostly-zero features with planted signal.  One
``OpXGBoostClassifier`` fit at the reference's default selector
parameterisation (DefaultSelectorParams.scala: NumRound=200, Eta=0.02,
MaxDepth=10, Gamma=0.8, aucpr early stopping after 20 rounds).

Default shape 1M x 2000 @ 5% (r5: grown from 250k x 1000 until the
analytic HBM high-water genuinely pressures a 16 GB v5e chip — VERDICT r4
#5; XGBoost's C++ core is routinely run at this scale).

Prints ONE JSON line like bench.py.  The CPU reference figures in
``benchmarks/baselines.json`` come from running this same script at a
subscale ``--rows`` under ``JAX_PLATFORMS=cpu`` (the derivation notes
were deleted with the other pre-PR-21 records).

Usage: python examples/bench_xgb_wide.py [--rows N] [--cols D]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from transmogrifai_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()


def make_sparse_data(rows: int, cols: int, density: float = 0.05,
                     seed: int = 17):
    """Wide mostly-zero matrix with signal in a few dense-ish columns."""
    import numpy as np

    rng = np.random.default_rng(seed)
    X = np.zeros((rows, cols), np.float32)
    nnz_per_row = max(1, int(cols * density))
    cols_idx = rng.integers(0, cols, size=(rows, nnz_per_row))
    vals = rng.exponential(1.0, size=(rows, nnz_per_row)).astype(np.float32)
    rows_idx = np.repeat(np.arange(rows), nnz_per_row)
    X[rows_idx, cols_idx.ravel()] = vals.ravel()
    informative = rng.choice(cols, 25, replace=False)
    z = X[:, informative] @ rng.normal(size=25).astype(np.float32)
    y = (z + 0.5 * rng.normal(size=rows) > np.median(z)).astype(np.float32)
    return X, y


def run(rows: int = 1_000_000, cols: int = 2000, density: float = 0.05,
        num_round: int = 200, max_depth: int = 10,
        warmup: bool = False) -> dict:
    """One measured wide-sparse XGB fit; importable by bench.py."""
    import numpy as np

    from transmogrifai_tpu.evaluators.metrics import aupr
    from transmogrifai_tpu.models import OpXGBoostClassifier

    t0 = time.perf_counter()
    X, y = make_sparse_data(rows, cols, density)
    gen_s = time.perf_counter() - t0

    def fit_once():
        # reference XGB defaults for binary selection
        # (DefaultSelectorParams.scala:36-75)
        est = OpXGBoostClassifier(
            num_round=num_round, eta=0.02, max_depth=max_depth,
            min_child_weight=1.0, gamma=0.8, early_stopping_rounds=20,
            seed=13)
        t0 = time.perf_counter()
        model = est.fit_raw(X, y)
        fit_s = time.perf_counter() - t0
        return model, fit_s

    warmup_s = 0.0
    if warmup:
        from transmogrifai_tpu.models.trees import clear_sweep_caches
        _, warmup_s = fit_once()
        clear_sweep_caches()
    model, fit_s = fit_once()

    n_trees = int(np.asarray(model.feat).shape[0])
    score = model.predict_batch(X).probability[:, 1]
    quality = float(aupr(y, score))

    hbm_peak_mb = None
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak:
            hbm_peak_mb = round(peak / 1e6)
    except Exception:
        pass
    # where the backend reports no memory_stats(), the analytic high-water
    # from the known shapes stands in (VERDICT r3 Weak #7): binned int8 +
    # the per-block (ROW_BLOCK, B·D) bins one-hot (the dominant transient,
    # bf16) + histogram accumulators + margins/trees.
    from transmogrifai_tpu.models.gbdt_kernels import ROW_BLOCK
    B = 32
    n_chan = 2                      # newton mode: G + H
    slots = min(2 ** (max_depth - 1), 1 << (rows - 1).bit_length())
    transient = (min(rows, ROW_BLOCK) * B * cols * 2   # bins onehot bf16
                 + min(rows, ROW_BLOCK) * slots * 2)   # node onehot bf16
    analytic = (rows * cols                       # binned int8
                + transient
                + n_chan * slots * B * cols * 4         # hist accumulator
                + 4 * rows * 4                          # margins/grads
                + 8 * (2 ** max_depth) * 12)            # chunk tree stacks
    hbm_peak_mb_analytic = round(analytic / 1e6)
    return {
        "metric": "xgb_wide_sparse_fit_wall_clock",
        "note": "synthetic Criteo stand-in (no real data in image)",
        "rows": rows, "cols": cols, "density": density,
        "value": round(fit_s, 1), "unit": "s",
        "boosted_rounds": n_trees,
        "per_round_s": round(fit_s / max(n_trees, 1), 3),
        "train_aupr": round(quality, 4),
        "hbm_peak_mb": hbm_peak_mb,
        "hbm_peak_mb_analytic": hbm_peak_mb_analytic,
        "datagen_s": round(gen_s, 1),
        "warmup_s": round(warmup_s, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--cols", type=int, default=2000)
    ap.add_argument("--density", type=float, default=0.05)
    ap.add_argument("--num-round", type=int, default=200)
    ap.add_argument("--max-depth", type=int, default=10)
    ap.add_argument("--warmup", action="store_true",
                    help="fit once untimed first (exclude compile costs)")
    args = ap.parse_args()
    print(json.dumps(run(args.rows, args.cols, args.density, args.num_round,
                         args.max_depth, args.warmup)))


if __name__ == "__main__":
    main()
