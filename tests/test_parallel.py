"""Mesh-sharded training paths over the 8-virtual-device CPU mesh.

Mirrors the reference's test strategy of local-mode Spark as the fake
cluster (TestSparkContext.scala:36-80, SURVEY §4): distributed semantics
exercised single-host, here via XLA virtual devices.
"""
import jax
import numpy as np
import pytest

from transmogrifai_tpu.models.linear import fit_logistic_regression
from transmogrifai_tpu.parallel import (
    fit_logreg_sharded, make_mesh, pad_to_multiple, shard_dataset,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8, model_parallelism=2)


def _toy(n=257, d=13, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d)
    y = (1 / (1 + np.exp(-(X @ beta))) > rng.random(n)).astype(np.float32)
    return X, y


def test_make_mesh_shape(mesh):
    assert mesh.shape == {"data": 4, "model": 2}


def test_pad_to_multiple():
    a = np.ones((5, 3))
    p, npad = pad_to_multiple(a, 4, axis=0)
    assert p.shape == (8, 3) and npad == 3
    assert (p[5:] == 0).all()
    same, z = pad_to_multiple(p, 4, axis=0)
    assert z == 0 and same.shape == (8, 3)


def test_shard_dataset_masks_padding(mesh):
    X, y = _toy()
    X_dev, y_dev, w_dev = shard_dataset(X, y, mesh)
    assert X_dev.shape[0] % 4 == 0 and X_dev.shape[1] % 2 == 0
    w = np.asarray(w_dev)
    assert w[:257].sum() == 257 and w[257:].sum() == 0


def test_sharded_logreg_matches_single_device(mesh):
    X, y = _toy()
    ref = fit_logistic_regression(X, y, reg_param=0.01)
    fit = fit_logreg_sharded(X, y, mesh, reg_param=0.01)
    coef = np.asarray(fit.coef)
    assert coef.shape == (X.shape[1],)  # column padding stripped
    np.testing.assert_allclose(coef, np.asarray(ref.coef), atol=1e-3)
    np.testing.assert_allclose(float(fit.intercept), float(ref.intercept),
                               atol=1e-3)


def test_graft_entry_single_chip():
    import __graft_entry__ as ge

    fn, example_args = ge.entry()
    out = jax.jit(fn)(*example_args)
    out = np.asarray(out)
    assert out.shape == (example_args[0].shape[0], 2)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-5)


@pytest.mark.parametrize("n", [4, 8])
def test_graft_dryrun_multichip(n):
    import __graft_entry__ as ge

    ge.dryrun_multichip(n)


class TestShardedForest:
    def test_sharded_equals_single_device(self):
        import jax.numpy as jnp
        import numpy as np

        from transmogrifai_tpu.models import gbdt_kernels as gk
        from transmogrifai_tpu.parallel import make_mesh
        from transmogrifai_tpu.parallel.sharded import grow_forest_sharded

        rng = np.random.default_rng(0)
        n, d, T = 512, 8, 4
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        Y = np.eye(2, dtype=np.float32)[y.astype(int)]
        edges = gk.quantile_bins(X, 16)
        binned = np.asarray(gk.apply_bins(jnp.asarray(X),
                                          jnp.asarray(edges, np.float32)))
        BW = rng.poisson(1.0, (T, n)).astype(np.float32)
        mask = np.ones((T, d), bool)

        mesh = make_mesh(8, model_parallelism=2)
        f_s, t_s, l_s = grow_forest_sharded(binned, Y, BW, mask, mesh,
                                            max_depth=4, n_bins=16)
        limit = jnp.full((T,), 4, jnp.int32)
        f_1, t_1, l_1 = gk._grow_chunk_bagged(
            jnp.asarray(binned), jnp.asarray(Y), jnp.asarray(BW),
            jnp.asarray(mask), limit, 4, 16, jnp.float32(1e-3),
            jnp.float32(0.0), jnp.float32(0.0), jnp.float32(1.0),
            jnp.bool_(False), jnp.float32(1.0))
        assert bool(jnp.all(f_s == f_1)) and bool(jnp.all(t_s == t_1))
        assert float(jnp.max(jnp.abs(l_s - l_1))) < 1e-4

    def test_rf_estimator_with_mesh_trains_and_predicts(self):
        import numpy as np

        from transmogrifai_tpu.models import OpRandomForestClassifier
        from transmogrifai_tpu.parallel import make_mesh

        rng = np.random.default_rng(3)
        X = rng.normal(size=(500, 6)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        mesh = make_mesh(8, model_parallelism=1)
        m = OpRandomForestClassifier(num_trees=16, max_depth=5,
                                     seed=5).with_mesh(mesh).fit_raw(X, y)
        proba = np.asarray(m.predict_batch(X).probability)
        acc = ((proba[:, 1] > 0.5) == y).mean()
        assert acc > 0.85


class TestShardedSketch:
    def test_sharded_quantile_bins_match_host(self):
        """Pooled-sample sharded sketch == host sketch when the sample
        covers every row (same linear-interpolation quantiles + dedup);
        the ICI all_gather is the executor-distributed analogue of the
        reference's RawFeatureFilter distribution pass (VERDICT r3
        Missing #5)."""
        import numpy as np

        from transmogrifai_tpu.models.gbdt_kernels import quantile_bins
        from transmogrifai_tpu.parallel import make_mesh
        from transmogrifai_tpu.parallel.sharded import quantile_bins_sharded

        rng = np.random.default_rng(3)
        X = rng.normal(size=(4096, 12)).astype(np.float32)
        X[:, 3] = np.round(X[:, 3])          # low-cardinality: dedup path
        mesh = make_mesh(8, model_parallelism=1)
        e_sharded = quantile_bins_sharded(X, mesh, max_bins=16,
                                          sample_rows=len(X))
        e_host = quantile_bins(X, 16, sample_rows=len(X))
        np.testing.assert_allclose(
            np.where(np.isfinite(e_sharded), e_sharded, 0.0),
            np.where(np.isfinite(e_host), e_host, 0.0), atol=2e-5)
        np.testing.assert_array_equal(np.isfinite(e_sharded),
                                      np.isfinite(e_host))

    def test_sharded_sketch_with_padding_rows(self):
        """Row counts that don't tile the mesh still sketch correctly
        (padding rows are NaN-masked out of the pooled quantiles)."""
        import numpy as np

        from transmogrifai_tpu.models.gbdt_kernels import quantile_bins
        from transmogrifai_tpu.parallel import make_mesh
        from transmogrifai_tpu.parallel.sharded import quantile_bins_sharded

        rng = np.random.default_rng(4)
        X = rng.uniform(size=(1013, 5)).astype(np.float32)   # prime rows
        mesh = make_mesh(8, model_parallelism=1)
        e = quantile_bins_sharded(X, mesh, max_bins=8, sample_rows=len(X))
        eh = quantile_bins(X, 8, sample_rows=len(X))
        np.testing.assert_allclose(e, eh, atol=5e-2)


class TestShardedProfile:
    def test_profile_numeric_sharded_matches_host(self):
        """The one-program sharded numeric profile (RawFeatureFilter's
        distribution pass) reproduces host counts/moments exactly and the
        histogram conserves mass (VERDICT r4 #5)."""
        import numpy as np

        from transmogrifai_tpu.parallel import make_mesh
        from transmogrifai_tpu.parallel.sharded import profile_numeric_sharded

        rng = np.random.default_rng(9)
        n, d = 5003, 6                        # prime rows: padding path
        X = rng.normal(size=(n, d)).astype(np.float32)
        mask = rng.random((n, d)) > 0.2
        mesh = make_mesh(8, model_parallelism=1)
        nulls, valid, s, s2, mn, mx, hist, edges = profile_numeric_sharded(
            X, mask, mesh, n_bins=25)
        mf = mask & np.isfinite(X)
        np.testing.assert_array_equal(nulls.astype(int), (~mask).sum(0))
        np.testing.assert_array_equal(valid.astype(int), mf.sum(0))
        Xm = np.where(mf, X, 0.0)
        np.testing.assert_allclose(s, Xm.sum(0), rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(s2, (Xm * Xm).sum(0), rtol=1e-4,
                                   atol=1e-3)
        np.testing.assert_array_equal(hist.sum(0).astype(int), mf.sum(0))
        for j in range(d):
            np.testing.assert_allclose(mn[j], X[mf[:, j], j].min(),
                                       rtol=1e-6)
            np.testing.assert_allclose(mx[j], X[mf[:, j], j].max(),
                                       rtol=1e-6)

    def test_rff_mesh_profiles_match_host_decisions(self):
        """RawFeatureFilter with a mesh must reach the SAME drop decisions
        as the host pass (fill rates exact; JS on the grid-loaded
        histogram within tolerance)."""
        import numpy as np

        from transmogrifai_tpu.filters.raw_feature_filter import (
            RawFeatureFilter,
        )
        from transmogrifai_tpu.parallel import make_mesh

        rng = np.random.default_rng(11)
        n = 4000
        import pandas as pd

        df = pd.DataFrame({
            "good": rng.normal(size=n),
            "mostly_null": np.where(rng.random(n) < 0.999, np.nan,
                                    rng.normal(size=n)),
            "label": (rng.random(n) < 0.4).astype(float),
        })
        from transmogrifai_tpu import FeatureBuilder
        from transmogrifai_tpu.readers.base import reader_for

        feats = [FeatureBuilder.Real("good").as_predictor(),
                 FeatureBuilder.Real("mostly_null").as_predictor(),
                 FeatureBuilder.RealNN("label").as_response()]
        data = reader_for(df).generate_dataset(feats)
        host = RawFeatureFilter(min_fill_rate=0.01)
        _, res_h = host.filter_raw_data(data, feats)
        mesh = make_mesh(8, model_parallelism=1)
        meshed = RawFeatureFilter(min_fill_rate=0.01).with_mesh(mesh)
        _, res_m = meshed.filter_raw_data(data, feats)
        assert res_m.dropped_features == res_h.dropped_features
        fills_h = {d.full_name: d.fill_rate()
                   for d in res_h.train_distributions}
        fills_m = {d.full_name: d.fill_rate()
                   for d in res_m.train_distributions}
        assert fills_h.keys() == fills_m.keys()
        for k in fills_h:
            assert abs(fills_h[k] - fills_m[k]) < 1e-9


# -- one preparation per train on a mesh (ISSUE 25) ---------------------------------

class TestMeshFitJoinsTheSweepsPreparation:
    """A selector train on a mesh prepares its matrix ONCE: the winner's
    ``fit_raw`` finds the sweep's host sketch in the memo and with it the
    sweep's binned matrix; only a fit with no sweep before it sketches over
    the mesh."""

    @pytest.fixture(scope="class")
    def mesh4(self):
        from transmogrifai_tpu.parallel.mesh import make_sweep_mesh

        return make_sweep_mesh(1, n_devices=4)

    @pytest.fixture(scope="class")
    def trained(self, mesh4):
        """The second, warm train of a one-group XGB selector on the mesh,
        under the tracer, with what each preparation returned."""
        from transmogrifai_tpu import (FeatureBuilder, OpWorkflow, models,
                                       obs, transmogrify)
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.selector import (
            BinaryClassificationModelSelector, grid)
        from transmogrifai_tpu.testkit import planted_linear_frame
        from transmogrifai_tpu.utils import profiling

        # 3002 rows: the last row is a training row of the seed-1 split (a
        # trailing reserved row is the miss case below), and the rows do
        # not tile the data axis
        df = planted_linear_frame(3002, 8, 5)
        label = FeatureBuilder.RealNN("label").as_response()
        preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns
                 if c != "label"]
        selector = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=2, seed=1, models_and_parameters=[
                (models.OpXGBoostClassifier(num_round=2),
                 grid(max_depth=[3]))])
        prediction = selector.set_input(
            label, transmogrify(preds)).get_output()
        wf = (OpWorkflow().set_result_features(prediction)
              .set_input_data(df).with_mesh(mesh4))
        wf.train()                      # builds the programs
        seen = {"sweep": [], "refit": []}
        patch = pytest.MonkeyPatch()
        for name, kind in (("_prep_tree_inputs_weighted", "sweep"),
                           ("_prep_tree_inputs_mesh", "refit")):
            def recorder(*a, _fn=getattr(trees, name), _kind=kind, **kw):
                out = _fn(*a, **kw)
                seen[_kind].append(out)
                return out
            patch.setattr(trees, name, recorder)
        try:
            profiling.reset_counters()
            with obs.tracing(capture_hlo=False) as tracer:
                model = wf.train()
        finally:
            patch.undo()
        return {"spans": tracer.snapshot(), "seen": seen, "model": model,
                "memo": profiling.COUNTERS.to_json()["memoTags"],
                "launches": profiling.COUNTERS.to_json()["launchTags"]}

    def test_the_winner_is_refitted_by_fit_raw_on_the_mesh(self, trained):
        assert len(trained["seen"]["sweep"]) == 1
        assert len(trained["seen"]["refit"]) == 1
        assert "gbt_chain_rounds_sharded" in trained["launches"]

    def test_one_sketch_and_one_binning_a_train(self, trained):
        memo = trained["memo"]
        assert memo.get("edges_mesh", {"builds": 0})["builds"] == 0
        assert memo["edges"]["builds"] == 1 and memo["edges"]["hits"] >= 1
        assert memo["bins"]["builds"] == 1 and memo["bins"]["hits"] >= 1
        sketches = [s for s in trained["spans"]
                    if s.name == "tree.prep.sketch"]
        bins = [s for s in trained["spans"] if s.name == "tree.prep.bin"]
        assert len(sketches) == 1 and len(bins) == 1

    def test_the_refit_grows_on_the_sweep_s_edges_and_bins(self, trained):
        (sweep,), (refit,) = (trained["seen"]["sweep"],
                              trained["seen"]["refit"])
        assert refit[0] is sweep[0]          # the memo's own edges
        assert refit[1] is sweep[1]          # and its binned matrix
        stage = next(s for s in trained["model"].stages
                     if hasattr(s, "inner"))
        np.testing.assert_array_equal(np.asarray(stage.inner.edges),
                                      np.asarray(sweep[0]))

    def test_no_program_is_built_inside_the_refit(self, trained):
        by_id = {s.span_id: s for s in trained["spans"]}
        (refit,) = [s for s in trained["spans"]
                    if s.name == "selector.refit"]

        def inside(s):
            while s.parent_id in by_id:
                s = by_id[s.parent_id]
                if s is refit:
                    return True
            return False

        built = [s.name for s in trained["spans"]
                 if s.name.startswith(("jit.lower:", "jit.compile:"))
                 and inside(s)]
        assert built == []
        assert not [s.name for s in trained["spans"] if inside(s)
                    and s.name in ("tree.prep.sketch", "tree.prep.bin")]

    @pytest.mark.parametrize("family", ["xgb", "rf"])
    def test_a_stand_alone_mesh_fit_sketches_over_the_mesh(self, mesh4,
                                                           family):
        from transmogrifai_tpu import models
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.parallel.sharded import quantile_bins_sharded
        from transmogrifai_tpu.utils import profiling

        rng = np.random.default_rng(8)
        X = rng.normal(size=(1001, 6)).astype(np.float32)
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        est = (models.OpXGBoostClassifier(num_round=2, max_depth=3)
               if family == "xgb" else
               models.OpRandomForestClassifier(num_trees=2, max_depth=3))
        trees.clear_sweep_caches()
        profiling.reset_counters()
        model = est.with_mesh(mesh4).fit_raw(X, y)
        memo = profiling.COUNTERS.to_json()["memoTags"]
        assert memo["edges_mesh"]["builds"] == 1 and "edges" not in memo
        assert memo["bins"]["builds"] == 1
        np.testing.assert_array_equal(
            np.asarray(model.edges),
            quantile_bins_sharded(X, mesh4, est.max_bins))
        trees.clear_sweep_caches()

    def test_a_sweep_that_sketched_a_truncated_matrix_is_a_miss(self, mesh4):
        """Trailing zero-weight rows make the sweep sketch the rows before
        them, under that matrix's hash: the mesh fit of the whole matrix
        does not find it and sketches over the mesh, as before."""
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.utils import profiling

        rng = np.random.default_rng(10)
        X = rng.normal(size=(1001, 6)).astype(np.float32)
        w = np.ones(1001, np.float32)
        w[-3:] = 0.0
        trees.clear_sweep_caches()
        swept, _ = trees._prep_tree_inputs_weighted(X, 32, row_weight=w)
        profiling.reset_counters()
        edges, _ = trees._prep_tree_inputs_mesh(X, 32, mesh4)
        memo = profiling.COUNTERS.to_json()["memoTags"]
        assert memo["edges_mesh"]["builds"] == 1 and "edges" not in memo
        assert edges is not swept
        trees.clear_sweep_caches()

    def test_a_host_sketch_in_the_memo_is_what_a_mesh_fit_takes(self,
                                                                mesh4):
        """What the code observes is the memo's content: the same fit
        after a host preparation of the same matrix takes that one."""
        from transmogrifai_tpu import models
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.utils import profiling

        rng = np.random.default_rng(9)
        X = rng.normal(size=(1001, 6)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        trees.clear_sweep_caches()
        edges, binned = trees._prep_tree_inputs_weighted(X, 32)
        profiling.reset_counters()
        model = (models.OpXGBoostClassifier(num_round=2, max_depth=3)
                 .with_mesh(mesh4).fit_raw(X, y))
        memo = profiling.COUNTERS.to_json()["memoTags"]
        assert "edges_mesh" not in memo
        assert memo["edges"] == {"hits": 1, "builds": 0, "waits": 0}
        assert memo["bins"] == {"hits": 1, "builds": 0, "waits": 0}
        assert model.edges is edges
        trees.clear_sweep_caches()
