"""Tests for the native (C++) runtime kernels.

Parity model: the native scorer must agree bit-for-bit in routing (and to
float tolerance in accumulation) with the JAX kernels in
models/gbdt_kernels.py; the streaming histogram mirrors the reference's Java
StreamingHistogram semantics (utils/.../stats/StreamingHistogram.java).
"""
import subprocess
import sys

import numpy as np
import pytest

from transmogrifai_tpu import native
from transmogrifai_tpu.models.gbdt_kernels import (
    apply_bins as jax_apply_bins, predict_ensemble as jax_predict_ensemble,
    quantile_bins,
)

pytestmark = pytest.mark.skipif(
    not native.AVAILABLE, reason="g++ unavailable; native lib not built")


@pytest.fixture(scope="module")
def ensemble():
    rng = np.random.default_rng(3)
    n, d, T, depth, K, B = 1000, 24, 16, 4, 1, 16
    binned = rng.integers(0, B, (n, d)).astype(np.int32)
    feat = rng.integers(0, d, (T, 2 ** depth - 1)).astype(np.int32)
    thresh = rng.integers(0, B, (T, 2 ** depth - 1)).astype(np.int32)
    leaf = rng.normal(size=(T, 2 ** depth, K)).astype(np.float32)
    return binned, feat, thresh, leaf, depth


class TestNativeScoring:
    def test_ensemble_matches_jax(self, ensemble):
        binned, feat, thresh, leaf, depth = ensemble
        got = native.predict_ensemble(binned, feat, thresh, leaf, depth)
        want = np.asarray(jax_predict_ensemble(binned, feat, thresh, leaf,
                                               depth))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_ensemble_multithreaded(self, ensemble):
        binned, feat, thresh, leaf, depth = ensemble
        big = np.tile(binned, (8, 1))
        got = native.predict_ensemble(big, feat, thresh, leaf, depth,
                                      n_threads=4)
        single = native.predict_ensemble(big, feat, thresh, leaf, depth,
                                         n_threads=1)
        np.testing.assert_array_equal(got, single)

    def test_apply_bins_matches_jax(self):
        rng = np.random.default_rng(4)
        X = rng.normal(size=(500, 12)).astype(np.float32)
        edges = quantile_bins(X, 16)
        np.testing.assert_array_equal(
            native.apply_bins(X, edges), np.asarray(jax_apply_bins(X, edges)))

    def test_linear_sigmoid_softmax(self):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(200, 8)).astype(np.float32)
        beta = rng.normal(size=9).astype(np.float32)
        np.testing.assert_allclose(native.linear_margin(X, beta),
                                   X @ beta[:-1] + beta[-1],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            native.sigmoid(np.zeros(3, np.float32)), [0.5] * 3)
        sm = native.softmax(rng.normal(size=(9, 4)).astype(np.float32))
        np.testing.assert_allclose(sm.sum(axis=1), np.ones(9), rtol=1e-5)
        assert (sm >= 0).all()


class TestNativeHistogram:
    def test_bounded_and_conserves_counts(self):
        rng = np.random.default_rng(6)
        h = native.NativeStreamingHistogram(32)
        h.update(rng.normal(size=5000))
        centers, counts = h.bins
        assert len(centers) <= 32
        assert abs(counts.sum() - 5000) < 1e-6
        assert (np.diff(centers) > 0).all()

    def test_sum_is_cdf_estimate(self):
        rng = np.random.default_rng(7)
        data = rng.normal(size=10000)
        h = native.NativeStreamingHistogram(64).update(data)
        med = float(np.median(data))
        assert abs(h.sum(med) - 5000) < 200
        assert h.sum(-np.inf if False else -1e9) == 0.0
        assert abs(h.sum(1e9) - 10000) < 1e-6

    def test_merge(self):
        rng = np.random.default_rng(8)
        a = native.NativeStreamingHistogram(32).update(rng.normal(size=1000))
        b = native.NativeStreamingHistogram(32).update(
            rng.normal(size=1000) + 5)
        a.merge(b)
        centers, counts = a.bins
        assert abs(counts.sum() - 2000) < 1e-6
        assert len(centers) <= 32

    def test_nan_inf_ignored(self):
        h = native.NativeStreamingHistogram(8)
        h.update([1.0, np.nan, np.inf, -np.inf, 2.0])
        _, counts = h.bins
        assert counts.sum() == 2


class TestFallback:
    def test_disable_env_uses_numpy_fallback(self):
        """With TMOG_DISABLE_NATIVE set, kernels still agree with JAX —
        including the negative default-direction thresholds
        OpXGBoostClassifier emits by default and the no-split sentinel
        (thresh == B), which the fallback used to route all-right."""
        code = """
import os
os.environ["TMOG_DISABLE_NATIVE"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
assert jax.default_backend() == "cpu"
import numpy as np
from transmogrifai_tpu import native
from transmogrifai_tpu.models.gbdt_kernels import (
    predict_ensemble as jpe, apply_bins as jab, quantile_bins)
assert not native.AVAILABLE
rng = np.random.default_rng(9)
n, d, T, depth, B = 100, 6, 4, 3, 8
binned = rng.integers(0, B, (n, d)).astype(np.int32)
feat = rng.integers(0, d, (T, 2**depth - 1)).astype(np.int32)
leaf = rng.normal(size=(T, 2**depth, 1)).astype(np.float32)
for lo in (0, -B):
    thresh = rng.integers(lo, B + 1, (T, 2**depth - 1)).astype(np.int32)
    assert lo == 0 or (thresh < 0).any()
    got = native.predict_ensemble(binned, feat, thresh, leaf, depth)
    want = np.asarray(jpe(binned, feat, thresh, leaf, depth))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
X = rng.normal(size=(50, d)).astype(np.float32)
edges = quantile_bins(X, 8)
np.testing.assert_array_equal(native.apply_bins(X, edges),
                              np.asarray(jab(X, edges)))
print("FALLBACK_OK")
"""
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=240)
        assert "FALLBACK_OK" in out.stdout, out.stderr


class TestLocalScorerUsesNative:
    def test_tree_model_host_path(self):
        """TreeEnsembleModel._raw routes through native on small batches and
        matches the JAX path."""
        from transmogrifai_tpu.models.trees import TreeEnsembleModel
        rng = np.random.default_rng(10)
        d, T, depth = 6, 5, 3
        X = rng.normal(size=(300, d)).astype(np.float32)
        edges = quantile_bins(X, 8)
        model = TreeEnsembleModel(
            mode="gbdt_binary", edges=edges,
            feat=rng.integers(0, d, (T, 2 ** depth - 1)).astype(np.int32),
            thresh=rng.integers(0, 8, (T, 2 ** depth - 1)).astype(np.int32),
            leaf=(rng.normal(size=(T, 2 ** depth, 1)) * 0.1).astype(np.float32))
        pb = model.predict_batch(X)
        binned = np.asarray(jax_apply_bins(X, edges))
        raw = np.asarray(jax_predict_ensemble(
            binned, model.feat, model.thresh, model.leaf, depth))[:, 0]
        p1 = 1.0 / (1.0 + np.exp(-raw))
        np.testing.assert_allclose(pb.probability[:, 1], p1, rtol=1e-5,
                                   atol=1e-5)
