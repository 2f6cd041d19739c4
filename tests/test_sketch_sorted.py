"""The host quantile sketch sorts column blocks of its sample (PR 32); its
edges must stay those of ``np.quantile`` down the sample's rows, bit for
bit.  The plain forms below are the bodies the package had until then,
kept here as the reference.
"""
import warnings

import numpy as np
import pytest

from perfbench.reference import hist_gbt
from transmogrifai_tpu.models import gbdt_kernels as gk


def plain_quantile_bins(X, max_bins=32, sample_rows=200_000, seed=7):
    X = np.asarray(X)
    n, d = X.shape
    if n > sample_rows:
        rng = np.random.default_rng(seed)
        X = X[rng.choice(n, sample_rows, replace=False)]
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    edges = np.quantile(X, qs, axis=0).T.astype(np.float32)  # (D, B-1)
    eps = 1e-7
    for j in range(d):
        e = edges[j]
        dup = np.concatenate([[False], np.diff(e) <= eps])
        edges[j] = np.where(dup, np.inf, e)
    return edges


def plain_sparse_aware(X, max_bins=32, sample_rows=200_000, seed=7):
    X = np.asarray(X)
    n, d = X.shape
    if n > sample_rows:
        rng = np.random.default_rng(seed)
        X = X[rng.choice(n, sample_rows, replace=False)]
        n = sample_rows
    edges = np.full((d, max_bins - 1), np.inf, np.float32)
    qs_dense = np.linspace(0, 1, max_bins + 1)[1:-1]
    qs_sparse = np.linspace(0, 1, max_bins)[1:-1]
    eps = 1e-7
    for j in range(d):
        col = X[:, j]
        nz = col[(col != 0) & ~np.isnan(col)]
        if len(nz) and 1.0 - len(nz) / n >= gk.SPARSE_SKETCH_ZERO_FRAC:
            e = np.unique(np.concatenate(
                [[0.0], np.quantile(nz, qs_sparse)]).astype(np.float32))
        else:
            e = np.nanquantile(col, qs_dense).astype(np.float32)
            e = e[np.isfinite(e)]
            dup = np.concatenate([[False], np.diff(e) <= eps]) \
                if len(e) else np.zeros(0, bool)
            e = e[~dup]
        edges[j, :len(e)] = e[:max_bins - 1]
    return edges


def _normal(n, d, seed=0, dtype=np.float32):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(dtype)


def _special_columns(n=3_000):
    """Columns the collapse and the NaN rule were written for."""
    rng = np.random.default_rng(3)
    X = _normal(n, 9, seed=3)
    X[:, 1] = 2.5                                    # constant
    X[:, 2] = rng.choice([-1.0, 0.0, 7.0], n)        # three values
    X[:, 3] = np.nan                                 # all NaN
    X[rng.random(n) < 0.05, 4] = np.nan              # some NaN
    X[:5, 5] = np.inf                                # a few +inf
    X[5:9, 5] = -np.inf
    X[:, 6] = np.where(rng.random(n) < 0.5, np.inf, -np.inf)
    X[:, 7] = np.where(rng.random(n) < 0.9, 0.0, X[:, 7])   # mostly zero
    X[:, 8] = rng.choice([-0.0, 0.0], n)             # both zeros
    return X


def _sparse(n=6_000, d=23, zero=0.96):
    rng = np.random.default_rng(5)
    X = _normal(n, d, seed=5) * 3
    X[rng.random((n, d)) < zero] = 0.0
    X[rng.random((n, d)) < 0.01] = np.nan
    X[:, 1] = 0.0                                    # all zero
    X[:, 2] = np.nan                                 # all NaN
    X[:, 3] = _normal(n, 1, seed=6)[:, 0]            # dense
    X[:, 4] = np.abs(X[:, 4])                        # nonzeros on one side
    X[:7, 5] = [np.inf, -np.inf, 1, 2, 3, 4, 5]
    return X


CASES = {
    # rows under, at and over sample_rows
    "n50": lambda: (_normal(50, 5), {}),
    "n3000": lambda: (_normal(3_000, 40), {}),
    "n_over_sample": lambda: (_normal(250_000, 7), {}),
    "n_equals_sample": lambda: (_normal(4_000, 6), {"sample_rows": 4_000}),
    "n_over_small_sample": lambda: (_normal(4_001, 6),
                                    {"sample_rows": 4_000, "seed": 3}),
    "one_row": lambda: (_normal(1, 4), {}),
    # widths: one column, under a block, a block boundary, past two blocks
    "d1": lambda: (_normal(500, 1), {}),
    "d16": lambda: (_normal(500, 16), {}),
    "d33": lambda: (_normal(500, 33), {}),
    "d_block": lambda: (_normal(300, gk.SKETCH_BLOCK_COLS), {}),
    "d_two_blocks_and_one": lambda: (
        _normal(300, 2 * gk.SKETCH_BLOCK_COLS + 1), {}),
    # constant, three-valued, NaN, +-inf, both zeros
    "special_columns": lambda: (_special_columns(), {}),
    "special_columns_sampled": lambda: (_special_columns(),
                                        {"sample_rows": 1_000}),
    # other dtypes and layouts
    "float64": lambda: (_normal(2_000, 9, dtype=np.float64) * 1e-3, {}),
    "int32": lambda: (np.random.default_rng(1).integers(
        -50, 50, (2_000, 9)).astype(np.int32), {}),
    "int32_sampled": lambda: (np.random.default_rng(1).integers(
        -2**31, 2**31 - 1, (2_000, 5)).astype(np.int32),
        {"sample_rows": 700}),
    "strided_view": lambda: (_normal(1_500, 50)[::3, 1::2], {}),
    "fortran_order": lambda: (np.asfortranarray(_normal(800, 24)), {}),
    "fortran_one_column": lambda: (np.asfortranarray(_normal(800, 1)), {}),
    # bins
    "bins2": lambda: (_normal(1_000, 8), {"max_bins": 2}),
    "bins3": lambda: (_normal(1_000, 8), {"max_bins": 3}),
    "bins32_lowcard": lambda: (np.random.default_rng(2).integers(
        0, 12, (1_000, 8)).astype(np.float32), {"max_bins": 32}),
    "bins200": lambda: (_normal(1_000, 8), {"max_bins": 200}),
    "bins200_few_rows": lambda: (_normal(90, 8), {"max_bins": 200}),
    # the sparse-aware sketch reads the same sorted blocks
    "sparse96": lambda: (_sparse(), {"sparse": True}),
    "sparse96_sampled": lambda: (_sparse(), {"sparse": True,
                                             "sample_rows": 2_500}),
    "sparse_bins200": lambda: (_sparse(), {"sparse": True, "max_bins": 200}),
    "sparse_int32": lambda: (np.random.default_rng(4).integers(
        -3, 4, (2_000, 6)).astype(np.int32)
        * (np.random.default_rng(4).random((2_000, 6)) < 0.3),
        {"sparse": True}),
    "sparse_special_columns": lambda: (_special_columns(), {"sparse": True}),
    "sparse_dense_matrix": lambda: (_normal(700, 21), {"sparse": True}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_edges_equal_np_quantile(case):
    X, kw = CASES[case]()
    sparse = kw.pop("sparse", False)
    before = X.copy()
    with warnings.catch_warnings():
        # inf - inf in the interpolation and in the collapse, an all-NaN
        # column: the plain form warns where the package may not
        warnings.simplefilter("ignore", RuntimeWarning)
        if sparse:
            want = plain_sparse_aware(X, **kw)
            got = gk.quantile_bins_sparse_aware(X, **kw)
        else:
            want = plain_quantile_bins(X, **kw)
            got = gk.quantile_bins(X, **kw)
        no_sample = X.shape[0] <= kw.get("sample_rows", 200_000)
        ref = None
        if not sparse and no_sample and X.dtype != np.float64:
            # the benchmark's reference sketches every row, in float32
            ref = hist_gbt.quantile_edges(before, kw.get("max_bins", 32))
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.array_equal(got, want, equal_nan=True)
    assert ref is None or np.array_equal(got, ref, equal_nan=True)
    # the blocks are sorted in copies, whatever the input's layout
    assert np.array_equal(X, before, equal_nan=True)
