"""Scale-path kernels: streamed (row-blocked) histograms, device binning.

SURVEY §7 step 9 / hard part (a): the histogram build must stream rows once
data outgrows the hoisted one-hot (1M×500×32 bins = 64 GB if materialized).
"""
import numpy as np
import pytest

import transmogrifai_tpu.models.gbdt_kernels as gk
from transmogrifai_tpu.models import trees as tr
from transmogrifai_tpu.models.trees import (
    _prep_tree_inputs, OpRandomForestClassifier,
)
from transmogrifai_tpu.utils import profiling


@pytest.fixture
def small_row_block(monkeypatch):
    monkeypatch.setattr(gk, "ROW_BLOCK", 128)
    gk._grow_chunk_bagged._clear_cache()
    gk._grow_chunk_rf._clear_cache()
    yield
    gk._grow_chunk_bagged._clear_cache()
    gk._grow_chunk_rf._clear_cache()


class TestStreamedHistograms:
    def test_blocked_equals_hoisted(self, small_row_block):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        n, d, T = 700, 10, 6
        X = rng.normal(size=(n, d)).astype(np.float32)
        Y = jnp.asarray(np.eye(2, dtype=np.float32)[
            (X[:, 0] > 0).astype(int)])
        bw = jnp.asarray(np.ones(n, np.float32))
        edges = gk.quantile_bins(X, 16)
        binned = gk.apply_bins(jnp.asarray(X), jnp.asarray(edges, np.float32))

        def grow():
            return gk.grow_forest_rf(binned, Y, bw, seed=3, n_trees=T,
                                     msub=d, subsample_rate=1.0,
                                     max_depth=5, n_bins=16)

        f2, t2, l2 = grow()                    # ROW_BLOCK=128 -> streamed
        gk.ROW_BLOCK = 1 << 16                 # hoisted path
        gk._grow_chunk_bagged._clear_cache()
        f1, t1, l1 = grow()
        assert bool(jnp.all(f1 == f2)) and bool(jnp.all(t1 == t2))
        assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-4

    def test_rf_quality_on_streamed_path(self, small_row_block):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(600, 6)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        m = OpRandomForestClassifier(num_trees=10, max_depth=4,
                                     seed=2).fit_raw(X, y)
        proba = np.asarray(m.predict_batch(X).probability)
        acc = ((proba[:, 1] > 0.5) == y).mean()
        assert acc > 0.85


class TestSiblingSubtraction:
    def test_sibling_matches_direct_histograms(self, monkeypatch):
        """Left-child-only histograms + (parent − left) derivation must
        reproduce the direct per-node build EXACTLY: RF channels are
        integer-valued (bag weights × one-hot targets), so f32 (and the
        f32-accumulated bf16 dots) is exact arithmetic on both paths."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        n, d, T = 900, 8, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        Y = jnp.asarray(np.eye(2, dtype=np.float32)[
            (X[:, 0] + X[:, 1] > 0).astype(int)])
        bw = jnp.asarray(np.ones(n, np.float32))
        edges = gk.quantile_bins(X, 16)
        binned = gk.apply_bins(jnp.asarray(X), jnp.asarray(edges, np.float32))

        def grow():
            gk._grow_chunk_rf._clear_cache()
            jax.clear_caches()
            return gk.grow_forest_rf(binned, Y, bw, seed=11, n_trees=T,
                                     msub=d, subsample_rate=1.0,
                                     max_depth=6, n_bins=16)

        monkeypatch.setattr(gk, "SIBLING_MIN_SLOTS", 4)   # engage at lvl 2+
        f_sib, t_sib, l_sib = [np.asarray(a) for a in grow()]
        monkeypatch.setattr(gk, "SIBLING_MIN_SLOTS", 1 << 30)  # disabled
        f_dir, t_dir, l_dir = [np.asarray(a) for a in grow()]
        assert (f_sib == f_dir).all()
        assert (t_sib == t_dir).all()
        assert np.max(np.abs(l_sib - l_dir)) < 1e-5


def _searchsorted_bins(X, edges):
    """The reference, kept here: per column, ``searchsorted`` (left) of the
    f32 values in the sorted f32 edges, NaN pinned to bin 0."""
    X = np.asarray(X, np.float32)
    edges = np.asarray(edges, np.float32)
    out = np.empty(X.shape, np.int64)
    for j in range(X.shape[1]):
        b = np.searchsorted(np.sort(edges[j]), X[:, j], side="left")
        out[:, j] = np.where(np.isnan(X[:, j]), 0, b)
    return out


def _awkward_matrix(n, d=6, seed=0):
    """Normal draws with a low-cardinality column (duplicate quantiles ->
    ``+inf`` sentinel edges), NaN, both infinities, and values that sit
    exactly on an edge; returns the matrix and its 32-bin edges."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, 2] = np.round(X[:, 2])
    edges = gk.quantile_bins(X, 32)
    assert np.isinf(edges[2]).any()            # the sentinels are there
    X[1::97, 0] = np.nan
    X[2::89, 1] = np.inf
    X[3::83, 1] = -np.inf
    for k in range(0, edges.shape[1], 3):      # exactly on an edge
        X[(5 + 7 * k) % n, 4] = edges[4, k]
    X[n - 1, 5] = np.nan                       # the very last row, too
    return X, edges


class TestDeviceBinning:
    """``trees._device_bins``: the one binning path, on the device, walked
    in row blocks (ISSUE 25)."""

    @pytest.fixture(autouse=True)
    def _fresh(self):
        tr.clear_sweep_caches()
        tr._bin_block_into._clear_cache()
        yield
        tr.clear_sweep_caches()
        tr._bin_block_into._clear_cache()

    @pytest.mark.parametrize("n,block_rows,launches", [
        (1000, 32768, 1),       # one block, the matrix itself
        (1024, 128, 8),         # several, the rows a multiple of the block
        (1000, 128, 8),         # a tail of 104 rows: the last block overlaps
        (1000, 999, 2),         # a tail of one row
        (129, 128, 2),
        (128, 128, 1),
        (1, 128, 1),
    ])
    def test_blocks_equal_searchsorted(self, monkeypatch, n, block_rows,
                                       launches):
        import jax

        monkeypatch.setattr(gk, "ROW_BLOCK", block_rows)
        X, edges = _awkward_matrix(max(n, 200))
        X = np.ascontiguousarray(X[:n])
        profiling.reset_counters()
        got = tr._device_bins(X, edges)
        assert isinstance(got, jax.Array)      # the result is on the device
        assert got.dtype == np.int8 and got.shape == X.shape
        np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                      _searchsorted_bins(X, edges))
        tags = profiling.COUNTERS.to_json()["launchTags"]
        assert tags == {"device_bin": launches}
        # ONE program for all the blocks of a shape
        assert tr._bin_block_into._cache_size() == 1

    @pytest.mark.parametrize("value,column,want", [
        ("nan", 0, "zero"), ("neginf", 0, "zero"), ("posinf", 0, "finite"),
        # the low-cardinality column's +inf sentinels never trigger, not
        # even for +inf itself
        ("posinf", 2, "finite"), ("below_all", 3, "zero"),
        ("above_all", 3, "finite"),
    ])
    def test_special_values(self, value, column, want):
        X, edges = _awkward_matrix(500)
        x = {"nan": np.nan, "neginf": -np.inf, "posinf": np.inf,
             "below_all": -1e30, "above_all": 1e30}[value]
        X[7, column] = x
        got = np.asarray(tr._device_bins(X, edges))
        n_finite = int(np.isfinite(edges[column]).sum())
        assert got[7, column] == (0 if want == "zero" else n_finite)
        assert got.max() <= edges.shape[1]

    def test_value_on_an_edge_takes_the_bin_below_it(self):
        """Count of edges STRICTLY below x: x == edges[k] lands in bin k,
        the next f32 above it in bin k + 1."""
        X, edges = _awkward_matrix(500)
        k = 4
        X[0, 4] = edges[4, k]
        X[1, 4] = np.nextafter(edges[4, k], np.float32(np.inf))
        got = np.asarray(tr._device_bins(X, edges))
        assert (got[0, 4], got[1, 4]) == (k, k + 1)

    def test_int32_result_from_127_edges_up(self, monkeypatch):
        monkeypatch.setattr(gk, "ROW_BLOCK", 64)
        rng = np.random.default_rng(5)
        X = rng.normal(size=(300, 3)).astype(np.float32)
        edges = gk.quantile_bins(X, 200)
        got = tr._device_bins(X, edges)
        assert got.dtype == np.int32
        assert np.asarray(got).max() > 127
        np.testing.assert_array_equal(np.asarray(got),
                                      _searchsorted_bins(X, edges))

    def test_empty_matrix(self):
        _, edges = _awkward_matrix(200)
        got = tr._device_bins(np.zeros((0, 6), np.float32), edges)
        assert got.shape == (0, 6) and got.dtype == np.int8

    def test_second_build_of_a_shape_builds_no_program(self, monkeypatch):
        """What the warm-up train built serves the window's trains: the
        memo is dropped between trains, the programs are not."""
        from transmogrifai_tpu import obs

        monkeypatch.setattr(gk, "ROW_BLOCK", 128)
        X, edges = _awkward_matrix(1000)
        first = np.asarray(_prep_tree_inputs(X, 32)[1])
        tr.clear_sweep_caches()
        profiling.reset_counters()
        with obs.tracing(capture_hlo=False) as tracer:
            again = np.asarray(_prep_tree_inputs(X, 32)[1])
        np.testing.assert_array_equal(first, again)
        spans = tracer.snapshot()
        assert not [s.name for s in spans
                    if s.name.startswith(("jit.lower:", "jit.compile:"))]
        assert profiling.COUNTERS.to_json()["memoTags"]["bins"] == {
            "hits": 0, "builds": 1, "waits": 0}
        # the block launches and uploads are children of the build's span
        (build,) = [s for s in spans if s.name == "tree.prep.bin"]
        launches = [s for s in spans if s.name == "launch:device_bin"]
        assert len(launches) == 8
        assert all(s.parent_id == build.span_id for s in launches)
        uploads = [s for s in spans if s.name == "tree.prep.upload"
                   and s.parent_id == build.span_id]
        assert len(uploads) == 8

    def test_prep_bins_int8_at_any_size_and_trains(self):
        rng = np.random.default_rng(2)
        X = rng.normal(size=(300, 4)).astype(np.float32)
        _, binned = _prep_tree_inputs(X, 32)
        assert binned.dtype == np.int8
        # int8 binned trains fine end-to-end
        y = (X[:, 0] > 0).astype(np.float32)
        m = OpRandomForestClassifier(num_trees=5, max_depth=3,
                                     seed=3).fit_raw(X, y)
        assert np.isfinite(np.asarray(m.predict_batch(X).probability)).all()

    def test_resident_f32_matrix_is_binned_where_it_lies(self):
        """The exact ``X_f32`` upload of the sweep is binned in one launch,
        without a second upload, to the same bins."""
        X, edges = _awkward_matrix(700)
        tr._dev_f32(X)
        profiling.reset_counters()
        got = tr._binned_for_edges(X, edges)
        counters = profiling.COUNTERS.to_json()
        assert counters["launchTags"] == {"device_bin": 1}
        assert counters["uploadBytes"] == 0
        assert got.dtype == np.int8
        np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                      _searchsorted_bins(X, edges))

    def test_a_bf16_copy_is_never_binned(self):
        """The same table bins the same whichever models share the
        selector: a linear group's bf16 upload is left alone."""
        import jax.numpy as jnp

        X, edges = _awkward_matrix(700)
        Xf = tr._as_f32(X)
        tr._memo(("X_bf16", tr._content_hash(Xf), Xf.shape),
                 lambda: jnp.zeros(Xf.shape, jnp.bfloat16))
        got = tr._binned_for_edges(X, edges)
        np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                      _searchsorted_bins(X, edges))

    def test_no_host_binning_path_is_left(self):
        assert not hasattr(tr, "_host_bins")
        assert not hasattr(tr, "_HOST_BIN_ELEMS")


class TestXGBoostGammaSemantics:
    def test_default_gamma_still_splits(self):
        """XGBoost's gamma thresholds RAW loss-reduction; mapping it onto
        Spark's per-node-weight minInfoGain silently produced all-leaf trees
        (regression guard)."""
        from transmogrifai_tpu.models import OpXGBoostClassifier
        from transmogrifai_tpu.evaluators.metrics import aupr

        rng = np.random.default_rng(4)
        n, d = 2000, 30
        X = np.where(rng.random((n, d)) < 0.2,
                     rng.normal(size=(n, d)), 0.0).astype(np.float32)
        beta = np.zeros(d)
        beta[rng.choice(d, 5, replace=False)] = rng.normal(size=5) * 3
        y = (1 / (1 + np.exp(-(X @ beta))) > rng.random(n)).astype(np.float32)
        m = OpXGBoostClassifier(num_round=30, max_depth=4, eta=0.2,
                                early_stopping_rounds=0).fit_raw(X, y)
        # default gamma=0.8: trees must actually split and learn
        assert int((np.asarray(m.thresh) < m.edges.shape[1] + 1).sum()) > 0
        p = np.asarray(m.predict_batch(X).probability)[:, 1]
        assert aupr(y, p) > 0.75
