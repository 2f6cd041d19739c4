"""Sparse-aware quantile sketch — XGBoost-core sparsity parity.

The reference's only native component (xgboost4j's C++ hist core,
OpXGBoostClassifier.scala:47) runs its quantile sketch on present values.
``quantile_bins_sparse_aware`` is the equivalent here: mostly-zero features
spend their bins on the nonzeros (an all-values sketch collapses to ~2
usable bins), with an edge pinned at 0.0.  The histograms of such a matrix
are built like any other's (tests/test_hist_onehot_layout.py holds them to
the references on sparse-aware edges).
"""
import numpy as np

from transmogrifai_tpu.models.gbdt_kernels import (
    quantile_bins, quantile_bins_sparse_aware,
)


def _sparse_data(n=4000, d=40, density=0.05, seed=5):
    rng = np.random.default_rng(seed)
    X = np.zeros((n, d), np.float32)
    nnz = max(1, int(d * density))
    cols = rng.integers(0, d, size=(n, nnz))
    vals = rng.exponential(1.0, size=(n, nnz)).astype(np.float32)
    X[np.repeat(np.arange(n), nnz), cols.ravel()] = vals.ravel()
    z = X[:, :8] @ rng.normal(size=8).astype(np.float32)
    y = (z > np.median(z)).astype(np.float32)
    return X, y


class TestSparseSketch:
    def test_sparse_aware_sketch_keeps_resolution(self):
        X, _ = _sparse_data(6000, 10, density=0.05)
        e_plain = quantile_bins(X, 32)
        e_sparse = quantile_bins_sparse_aware(X, 32)
        # all-values sketch of a 95%-zero feature: nearly every edge
        # collapses; nonzero-aware sketch keeps most of the 31 edges
        assert np.isfinite(e_plain[0]).sum() <= 5
        assert np.isfinite(e_sparse[0]).sum() >= 20
        # an edge at 0 separates the zeros from positive values
        assert 0.0 in e_sparse[0]

    def test_dense_features_unchanged(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(5000, 4)).astype(np.float32)
        np.testing.assert_allclose(quantile_bins_sparse_aware(X, 16),
                                   quantile_bins(X, 16), atol=1e-6)


class TestSparseEndToEnd:
    def test_xgb_sparse_fit_engages_and_learns(self, monkeypatch):
        """A wide mostly-zero fit takes the sparse-aware sketch end to end
        (prep detection -> ``edges_sp`` memo -> scan-chunk rounds) and
        learns the signal."""
        import transmogrifai_tpu.models.trees as trees_mod
        from transmogrifai_tpu.evaluators.metrics import aupr
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier
        from transmogrifai_tpu.utils import profiling

        # drop the size floor so the small test matrix qualifies
        monkeypatch.setattr(trees_mod, "_SPARSE_MIN_ELEMS", 1)
        X, y = _sparse_data(6000, 50, density=0.08, seed=9)
        trees_mod.clear_sweep_caches()
        profiling.reset_counters()
        est = OpXGBoostClassifier(num_round=15, eta=0.3, max_depth=4,
                                  gamma=0.0, early_stopping_rounds=0)
        model = est.fit_raw(X, y)
        memo = profiling.COUNTERS.to_json()["memoTags"]
        assert memo["edges_sp"]["builds"] == 1 and "edges" not in memo, memo
        assert (np.asarray(model.edges) == 0.0).any(axis=1).all(), \
            "an edge pinned at 0.0 in every mostly-zero feature"
        score = model.predict_batch(X).probability[:, 1]
        assert float(aupr(y, score)) > 0.80
