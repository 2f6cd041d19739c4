"""The level histogram's bins one-hot, in the layout the dot contracts.

``_grow_tree_traced`` builds a row block's full-width bins one-hot as
(bins, columns, rows) from the TRANSPOSED block and contracts rows with
bins and columns as two free axes (PR 29; docs/performance.md).  Every
case below holds that form, on the CPU in f32, to two independent
references: each level's histograms to a NumPy scatter-add (``np.add.at``)
over the same rows, and the whole trees to the plain float64 reference of
the benchmark (``perfbench/reference/hist_gbt.py``, imported as it is).

Gradients are multiples of 1/64 and hessians of 1/64 in (0, 1], so every
histogram sum is exact in f32 and in f64 alike: two candidates' gains then
differ by far more than f32 rounding or are exactly equal in both (and both
break ties by the lowest threshold, then the lowest column), which is what
lets features and thresholds be compared with ``==``.

It is the ONLY formulation since PR 30 (the segmented Pallas form and the
CSR form lost every measurement and were deleted): ``LONE_TREES`` holds it,
to the same two references, on the shapes their parity tests used, one fit
of ``OpGBTClassifier`` is held to the reference's boosted trees, and the
last test pins the ``TMOG_*`` names the package reads, so that the next
switch is a visible diff.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import transmogrifai_tpu.models.gbdt_kernels as gk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench.reference import hist_gbt  # noqa: E402

B, DEPTH, LAM, MCW, GAMMA, LR = 32, 3, 1.0, 1.0, 0.5, 0.1

# id: (rows, columns, ROW_BLOCK or None, chains, goss, bundle, all_reduce,
#      dtype of the binned matrix)
CASES = {
    "d5-hoisted-tree": (700, 5, None, 1, False, False, False, np.int8),
    "d16-hoisted-tree": (700, 16, None, 1, False, False, False, np.int32),
    "d100-hoisted-tree": (700, 100, None, 1, False, False, False, np.int8),
    "d500-hoisted-tree": (700, 500, None, 1, False, False, False, np.int8),
    "d16-hoisted-chains": (700, 16, None, 2, False, False, False, np.int8),
    "d5-blocked-tree": (700, 5, 256, 1, False, False, False, np.int8),
    "d16-blocked-chains": (700, 16, 256, 2, False, False, False, np.int8),
    "d100-blocked-tree": (700, 100, 256, 1, False, False, False, np.int32),
    "d500-blocked-chains": (700, 500, 256, 2, False, False, False, np.int8),
    "d100-hoisted-goss-tree": (1000, 100, None, 1, True, False, False,
                               np.int8),
    "d16-blocked-goss-chains": (1000, 16, 256, 2, True, False, False,
                                np.int8),
    "d16-hoisted-bundle-tree": (700, 16, None, 1, False, True, False,
                                np.int8),
    "d100-blocked-bundle-chains": (700, 100, 256, 2, False, True, False,
                                   np.int8),
    "d16-hoisted-allreduce-tree": (700, 16, None, 1, False, False, True,
                                   np.int8),
    "d500-blocked-allreduce-chains": (700, 500, 256, 2, False, False, True,
                                      np.int8),
    "d5-blocked-goss-allreduce-tree": (1000, 5, 256, 1, True, False, True,
                                       np.int8),
}


@pytest.fixture
def row_block(monkeypatch):
    """Set ``ROW_BLOCK`` for one test; the jitted entry reads it under
    trace, so its cache is dropped on both sides."""
    def set_to(rows):
        if rows is not None:
            monkeypatch.setattr(gk, "ROW_BLOCK", rows)
        gk._grow_chunk._clear_cache()

    yield set_to
    gk._grow_chunk._clear_cache()


def _table(n, d, bundle, seed):
    """``(binned int16, edges)``: Gaussian columns; under ``bundle`` the
    last eight are ONE one-hot group (mutually exclusive indicators, which
    ``bundle_features`` packs into one histogram column)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    if bundle:
        X[:, -8:] = 0.0
        X[np.arange(n), d - 8 + rng.integers(0, 8, n)] = 1.0
    edges = hist_gbt.quantile_edges(X, B)
    return X, hist_gbt.bin_matrix(X, edges), edges


def _gradients(X, chains, seed):
    """(chains, n, 1) g and h on the 1/64 grid, with signal in the first
    columns so that the trees split."""
    rng = np.random.default_rng(seed)
    n, d = X.shape
    G, H = [], []
    for c in range(chains):
        s = X[:, c % d] - 0.7 * X[:, (c + 1) % d] * X[:, (c + 2) % d]
        if d >= 16:                      # the one-hot group carries signal too
            s = s + 1.5 * X[:, -1] - 1.0 * X[:, -3]
        g = np.tanh(s) + 0.3 * rng.normal(size=n)
        G.append(np.round(np.clip(g, -2, 2) * 64) / 64)
        H.append((1 + np.round(rng.random(n) * 63)) / 64)
    return (np.asarray(G, np.float32)[..., None],
            np.asarray(H, np.float32)[..., None])


def _route(binned, feat, thresh, level, end_bin=None):
    """Node of every row at ``level`` (0-based within the level)."""
    node = np.zeros(len(binned), np.int64)
    rows = np.arange(len(binned))
    for l in range(level):
        heap = 2 ** l - 1 + node
        f, t = feat[heap], thresh[heap]
        x = binned[rows, f]
        right = x > t
        if end_bin is not None:
            right &= x <= end_bin[np.clip(t, 0, B - 1), f]
        node = 2 * node + right
    return node


def _scatter_hist(binned, node, m, w):
    """(m, B, d) float64 histogram of ``w`` by np.add.at."""
    n, d = binned.shape
    out = np.zeros((m, B, d))
    np.add.at(out, (node[:, None], binned, np.arange(d)[None, :]),
              w[:, None])
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_dense_histograms_and_trees_match_the_references(case, row_block):
    n, d, block, chains, goss, bundle, use_all_reduce, dtype = CASES[case]
    row_block(block)
    X, binned, edges = _table(n, d, bundle, seed=len(case) + d)
    G, H = _gradients(X, chains, seed=n + d)
    C = np.ones((chains, n), np.float32)

    grown, end_bin, bundles = binned, None, None
    if bundle:
        # (at 100 columns one group shrinks the width too little to pay
        # for the encode pass; the test wants the bundled form all the same)
        bundles = gk.bundle_features(binned, edges, B, min_width_ratio=1.0)
        assert bundles is not None and bundles.width == d - 7
        grown = gk.bundle_matrix(bundles, binned)
        end_bin = bundles.end_bin
    d_hist = grown.shape[1]
    plan = (n // 5, n // 5) if goss else None     # amplification 4: exact
    key = jax.random.PRNGKey(7)

    # the rows and channels each chain's tree really sees
    seen = []
    for c in range(chains):
        if goss:
            idx, mult = gk._goss_select(
                jnp.abs(jnp.asarray(G[c, :, 0])),
                jax.random.fold_in(key, c), *plan)
            idx, mult = np.asarray(idx), np.asarray(mult, np.float64)
            assert set(np.unique(mult)) == {1.0, 4.0}
        else:
            idx, mult = np.arange(n), np.ones(n)
        seen.append((idx, G[c, idx, 0] * mult, H[c, idx, 0] * mult,
                     C[c, idx] * mult))
    if block is not None:         # blocked, and the last block is padded
        assert len(seen[0][0]) > block and len(seen[0][0]) % block

    bj = jnp.asarray(grown.astype(dtype))
    eb = jnp.asarray(end_bin) if end_bin is not None else None
    scal = dict(lam=jnp.float32(LAM), min_child_weight=jnp.float32(MCW),
                min_info_gain=jnp.float32(0.0),
                min_instances=jnp.float32(1.0), newton_leaf=jnp.bool_(True),
                learning_rate=jnp.float32(LR))
    mask = jnp.ones(d_hist, bool)

    # -- a level's histograms: the identity all_reduce is the one place
    # where the program hands them out --------------------------------------
    taken = []

    def record(h):
        jax.debug.callback(lambda a: taken.append(np.asarray(a)), h,
                           ordered=True)
        return h

    idx0, g0, h0, c0 = seen[0]
    f0, t0, leaf0, _ = jax.jit(lambda b, g, h, c: gk._grow_tree_traced(
        b, g, h, c, mask, jnp.int32(DEPTH), max_depth=DEPTH, n_bins=B,
        min_gain_raw=jnp.float32(GAMMA), all_reduce=record, bundle_end=eb,
        **scal))(bj[idx0], jnp.asarray(g0, jnp.float32)[:, None],
                 jnp.asarray(h0, jnp.float32)[:, None],
                 jnp.asarray(c0, jnp.float32))
    jax.effects_barrier()
    f0, t0 = np.asarray(f0), np.asarray(t0)
    assert len(taken) == 3 * DEPTH + 3          # (G, H, C) a level + leaves
    rows0 = grown[idx0]
    for level in range(DEPTH):
        node = _route(rows0, f0, t0, level, end_bin)
        for got, w in zip(taken[3 * level:3 * level + 3], (g0, h0, c0)):
            assert got.shape == (2 ** level, B, d_hist)
            np.testing.assert_allclose(
                got, _scatter_hist(rows0, node, 2 ** level, w),
                rtol=0, atol=1e-5)

    # -- whole trees, through the jitted entry the fitters use ---------------
    if use_all_reduce:
        def one(g, h, c, t):
            if goss:
                i, m = gk._goss_select(jnp.abs(g[:, 0]),
                                       jax.random.fold_in(key, t), *plan)
                b, g, h, c = bj[i], g[i] * m[:, None], h[i] * m[:, None], \
                    c[i] * m
            else:
                b = bj
            return gk._grow_tree_traced(
                b, g, h, c, mask, jnp.int32(DEPTH), max_depth=DEPTH,
                n_bins=B, min_gain_raw=jnp.float32(GAMMA),
                all_reduce=lambda x: x, bundle_end=eb, **scal)[:3]

        feat, thresh, leaf = jax.jit(jax.vmap(one))(
            jnp.asarray(G), jnp.asarray(H), jnp.asarray(C),
            jnp.arange(chains))
    else:
        feat, thresh, leaf = gk._grow_chunk(
            bj, jnp.asarray(G), jnp.asarray(H), jnp.asarray(C),
            jnp.ones((chains, d_hist), bool),
            jnp.full((chains,), DEPTH, jnp.int32), DEPTH, B,
            scal["lam"], scal["min_child_weight"], scal["min_info_gain"],
            scal["min_instances"], scal["newton_leaf"],
            scal["learning_rate"], min_gain_raw=jnp.float32(GAMMA),
            bundle_end=eb, goss=plan, goss_key=key if goss else None)
    feat, thresh, leaf = (np.asarray(a) for a in (feat, thresh, leaf))
    np.testing.assert_array_equal(feat[0], f0)
    np.testing.assert_array_equal(thresh[0], t0)
    if bundle:
        feat, thresh = gk.unbundle_ensemble(bundles, feat, thresh)

    for c, (idx, g, h, w) in enumerate(seen):
        want_f, want_t, node = hist_gbt.grow_tree(
            binned[idx], g[:, None], h[:, None], w, DEPTH, B, LAM,
            min_child_weight=MCW, gamma=GAMMA, min_instances=1.0)
        assert (want_t < B).sum() >= 3, "a tree that hardly splits"
        np.testing.assert_array_equal(feat[c], want_f)
        np.testing.assert_array_equal(thresh[c], want_t)
        Gs = np.bincount(node, g, 2 ** DEPTH)
        Hs = np.bincount(node, h, 2 ** DEPTH)
        np.testing.assert_allclose(leaf[c, :, 0], -LR * Gs / (Hs + LAM),
                                   rtol=0, atol=3e-7)


# -- single trees on the shapes the deleted forms' parity tests used ---------

def _mostly_zero(n, d, seed):
    """One exponential value a row in a random column: 96 % zeros."""
    rng = np.random.default_rng(seed)
    X = np.zeros((n, d), np.float32)
    X[np.arange(n), rng.integers(0, d, n)] = rng.exponential(1.0, n)
    return X


def _lone_tree(case):
    """``(binned, g, h, depth, min_instances, gamma, ROW_BLOCK or None)``."""
    if case.startswith("mostly-zero"):
        # sparse-aware edges (a pinned 0.0 edge, the other bins on the
        # nonzero values): what the CSR parity test held the kernel to
        X = _mostly_zero(3000, 24, seed=5)
        edges = gk.quantile_bins_sparse_aware(X, B)
        assert (edges[:, 0] == 0.0).all()
        G, H = _gradients(X, 1, seed=24)
        depth, block = (3, None) if case.endswith("d3") else (6, 1024)
        return (hist_gbt.bin_matrix(X, edges), G[0, :, 0], H[0, :, 0],
                depth, 5.0, 0.0, block)
    if case == "empty-slots-d4":
        # no gradient where column 0 is low: the root splits there and its
        # left child cannot split, so the level below has an EMPTY slot
        X, binned, _ = _table(2000, 16, False, seed=7)
        low = binned[:, 0] <= 15
        g = np.where(low, 0.0, 1 + np.round(np.tanh(X[:, 1]) * 32) / 64)
        return binned, g, np.full(2000, 0.25), 4, 1.0, GAMMA, None
    assert case == "n4000-d24-depth5"
    rng = np.random.default_rng(3)
    X = rng.normal(size=(4000, 24)).astype(np.float32)
    y = X[:, 0] - 0.5 * X[:, 3] + 0.3 * rng.normal(size=4000) > 0
    binned = hist_gbt.bin_matrix(X, hist_gbt.quantile_edges(X, B))
    return binned, 0.5 - y, np.full(4000, 0.25), 5, 1.0, 0.0, None


LONE_TREES = ["mostly-zero-d3", "mostly-zero-d6-blocked", "empty-slots-d4",
              "n4000-d24-depth5"]


@pytest.mark.parametrize("case", LONE_TREES)
def test_lone_tree_histograms_and_splits_match_the_references(case,
                                                              row_block):
    binned, g, h, depth, min_inst, gamma, block = _lone_tree(case)
    row_block(block)
    n, d = binned.shape
    c = np.ones(n)
    bj = jnp.asarray(binned.astype(np.int8))
    gj, hj = (jnp.asarray(a, jnp.float32)[:, None] for a in (g, h))
    taken = []

    def record(x):
        jax.debug.callback(lambda a: taken.append(np.asarray(a)), x,
                           ordered=True)
        return x

    f0, t0, _, _ = jax.jit(lambda b, gg, hh, cc: gk._grow_tree_traced(
        b, gg, hh, cc, jnp.ones(d, bool), jnp.int32(depth), max_depth=depth,
        n_bins=B, lam=jnp.float32(LAM), min_child_weight=jnp.float32(MCW),
        min_info_gain=jnp.float32(0.0), min_instances=jnp.float32(min_inst),
        newton_leaf=jnp.bool_(True), learning_rate=jnp.float32(LR),
        min_gain_raw=jnp.float32(gamma), all_reduce=record))(
            bj, gj, hj, jnp.ones(n, jnp.float32))
    jax.effects_barrier()
    f0, t0 = np.asarray(f0), np.asarray(t0)
    assert len(taken) == 3 * depth + 3
    empty = 0
    for level in range(depth):
        node = _route(binned, f0, t0, level)
        rows_in = np.bincount(node, minlength=2 ** level)
        for got, w in zip(taken[3 * level:3 * level + 3], (g, h, c)):
            np.testing.assert_allclose(
                got, _scatter_hist(binned, node, 2 ** level, w),
                rtol=0, atol=1e-5)
            assert (got[rows_in == 0] == 0).all()       # exact zeros
        empty += int((rows_in == 0).sum())
    if case == "empty-slots-d4":
        assert t0[0] < B and t0[1] == B and t0[2] < B and empty >= 3

    # the same tree through the entry the fitters use, against the reference
    feat, thresh, leaf = gk.grow_tree(
        bj, gj, hj, jnp.ones(n, jnp.float32), max_depth=depth, n_bins=B,
        lam=LAM, min_child_weight=MCW, min_instances=min_inst,
        learning_rate=LR, min_gain_raw=gamma)
    np.testing.assert_array_equal(np.asarray(feat), f0)
    np.testing.assert_array_equal(np.asarray(thresh), t0)
    want_f, want_t, node = hist_gbt.grow_tree(
        binned, np.asarray(g, float)[:, None], np.asarray(h, float)[:, None],
        c, depth, B, LAM, min_child_weight=MCW, gamma=gamma,
        min_instances=min_inst)
    assert (want_t < B).sum() >= 3, "a tree that hardly splits"
    np.testing.assert_array_equal(f0, want_f)
    np.testing.assert_array_equal(t0, want_t)
    Gs = np.bincount(node, g, 2 ** depth)
    Hs = np.bincount(node, h, 2 ** depth)
    np.testing.assert_allclose(np.asarray(leaf)[:, 0], -LR * Gs / (Hs + LAM),
                               rtol=0, atol=3e-7)


def test_gbt_fit_grows_the_reference_s_boosted_trees():
    """Six rounds of depth 4 on 3,000 x 16 through ``fit_raw`` (one chain
    of ``_gbt_chain_rounds_jit``, f32 operands): the reference's features
    and thresholds, round by round."""
    from transmogrifai_tpu.models import OpGBTClassifier

    rng = np.random.default_rng(5)
    X = rng.normal(size=(3000, 16)).astype(np.float32)
    y = (X @ rng.normal(size=16) > 0).astype(np.float32)
    model = OpGBTClassifier(max_iter=6, max_depth=4, step_size=0.3,
                            hist_precision="f32").fit_raw(X, y)
    edges, base, trees = hist_gbt.fit_gbt(X, y, depth=4, rounds=6, eta=0.3,
                                          lam=1.0, min_child_weight=1.0)
    assert np.array_equal(np.asarray(model.edges), edges)
    for t, (feat, thresh, leaf) in enumerate(trees):
        assert np.array_equal(np.asarray(model.feat)[t], feat), t
        assert np.array_equal(np.asarray(model.thresh)[t], thresh), t
        np.testing.assert_allclose(np.asarray(model.leaf)[t, :, 0], leaf,
                                   atol=1e-5)


#: every ``TMOG_*`` environment variable the package names.  A new one is
#: a new configuration for tests and benchmarks to cover: add it here in
#: the PR that adds it, with the two callers that need different values.
TMOG_NAMES = {
    "TMOG_AOT_CACHE_DIR", "TMOG_BLOCK_KERNELS", "TMOG_CHECK",
    "TMOG_COLLECTIVE_TIMEOUT", "TMOG_COST_HISTORY", "TMOG_DISABLE_NATIVE",
    "TMOG_EFB", "TMOG_FAULTS", "TMOG_GOSS", "TMOG_HOST_BUDGET_MB",
    "TMOG_MATRIX_PRECISION", "TMOG_PLAN_PARALLEL_MIN_ROWS",
    "TMOG_PLAN_WORKERS", "TMOG_POD_COORDINATOR", "TMOG_POD_LOCAL_DEVICES",
    "TMOG_POD_NUM_PROCESSES", "TMOG_POD_PROCESS_ID",
    "TMOG_SEQUENTIAL_EXECUTOR", "TMOG_STREAM_RETAIN_MB", "TMOG_SYNC_SWEEP",
}


def test_the_package_names_no_other_switch():
    pkg = os.path.dirname(os.path.dirname(gk.__file__))
    found = set()
    for root, _, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn), encoding="utf-8") as f:
                    found |= set(re.findall(r"TMOG_[A-Z0-9_]+", f.read()))
    found.discard("TMOG_POD_")          # the prose's "TMOG_POD_*"
    assert found == TMOG_NAMES
