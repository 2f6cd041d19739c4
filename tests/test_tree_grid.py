"""PR 11 — tree families on the grid axis + the EFB/GOSS/bf16 fast path.

Covers the ISSUE 11 contracts: EFB bundle/unbundle invertibility (bundled
fit == unbundled fit BIT-FOR-TREE on conflict-free matrices, AuPR within
2e-2 under bounded conflicts), GOSS seed-determinism and its depth gate,
TreeGridGroup pad-invariance over ``n_rows mod 8`` and parity against the
sequential mesh-sharded fits, SIGKILL-mid-rung resume with a tree grid
group, the tree-prep prefetch drain on elastic teardown, the new
``*:fit-grid`` cost-model stage kinds (+ old-history back-compat), and the
TM028 bf16-accumulation tolerance probe.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from transmogrifai_tpu.models.gbdt_kernels import (
    apply_bins, bundle_features, bundle_matrix, goss_plan, grow_tree,
    quantile_bins_sparse_aware, unbundle_ensemble,
)
from transmogrifai_tpu.models.trees import (
    OpGBTClassifier, OpRandomForestClassifier, clear_sweep_caches,
)
from transmogrifai_tpu.parallel.mesh import make_sweep_mesh
from transmogrifai_tpu.selector.grid_groups import (
    GBTGridGroup, RFGridGroup,
)

import jax.numpy as jnp


def _onehot_data(n=320, groups=4, card=8, dense=3, seed=9):
    """A transmogrify-shaped matrix: dense numerics + mutually exclusive
    one-hot blocks (the EFB target), with a learnable label."""
    rng = np.random.default_rng(seed)
    cats = rng.integers(0, card, size=(n, groups))
    oh = np.zeros((n, groups * card), np.float32)
    for i in range(groups):
        oh[np.arange(n), i * card + cats[:, i]] = 1.0
    dn = rng.normal(size=(n, dense)).astype(np.float32)
    X = np.concatenate([dn, oh], axis=1)
    y = ((dn[:, 0] + (cats[:, 0] == 3) - (cats[:, 1] == 5)
          + rng.normal(size=n) * 0.3) > 0).astype(np.float32)
    return X, y


def _toy(n=300, d=10, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d) * (rng.random(d) < 0.6)
    y = (1 / (1 + np.exp(-(X @ beta))) > rng.random(n)).astype(np.float32)
    return X, y


def _ctxs(n, seed=3, folds=2):
    rng = np.random.default_rng(seed)
    f = rng.integers(0, folds, n)
    return [((f != k).astype(np.float32), (f == k).astype(np.float32))
            for k in range(folds)]


def _binned(X, mb=32):
    edges = quantile_bins_sparse_aware(X, mb)
    b = np.asarray(apply_bins(jnp.asarray(X), jnp.asarray(edges)), np.int8)
    return edges, b


@pytest.fixture(autouse=True)
def _fresh_memos():
    clear_sweep_caches()
    yield
    clear_sweep_caches()
    for var in ("TMOG_EFB", "TMOG_GOSS"):
        os.environ.pop(var, None)


class TestEFB:
    def test_bundle_width_and_decode(self):
        X, _ = _onehot_data()
        edges, binned = _binned(X)
        b = bundle_features(binned, edges, 32)
        assert b is not None
        # 4 one-hot blocks of 8 pack into far fewer histogram columns
        assert b.width <= 0.5 * b.n_orig
        Xb = bundle_matrix(b, binned)
        assert Xb.shape == (X.shape[0], b.width)
        # conflict-free encode is fully invertible per member
        for c, spec in enumerate(b.plan):
            if isinstance(spec, (int, np.integer)):
                assert (Xb[:, c] == binned[:, spec]).all()
            else:
                for orig, base, end in spec:
                    vals = Xb[:, c].astype(np.int32)
                    active = (vals >= base) & (vals <= end)
                    dec = np.where(active, vals - base + 1, 0)
                    assert (dec == binned[:, orig]).all()

    def test_bundled_tree_bit_identical(self):
        """Conflict-free: a tree grown on the bundled matrix, unbundled,
        equals the tree grown on the original matrix node-for-node.

        ONE one-hot group + dense numerics, continuous gradients: within
        a single mutually exclusive group no two members can produce an
        identical node partition (their active row sets are disjoint), so
        every gain is unique and argmax order cannot matter.  With
        SEVERAL groups (or discrete gradients), distinct indicator
        columns CAN tie with exactly equal gains at small nodes and the
        two column spaces legitimately break the tie differently — that
        regime is functionally identical and covered by the
        prediction-parity test below."""
        rng = np.random.default_rng(21)
        n, card = 400, 8
        cats = rng.integers(0, card, size=n)
        oh = np.zeros((n, card), np.float32)
        oh[np.arange(n), cats] = 1.0
        dn = rng.normal(size=(n, 3)).astype(np.float32)
        X = np.concatenate([dn, oh], axis=1)
        edges, binned = _binned(X)
        b = bundle_features(binned, edges, 32)
        Xb = bundle_matrix(b, binned)
        G = jnp.asarray(rng.normal(size=n).astype(np.float32)[:, None])
        H = jnp.asarray(np.full((n, 1), 0.25, np.float32))
        C = jnp.asarray(np.ones(n, np.float32))
        # depth 3: level-2 nodes hold ~100 rows, where a dense-feature
        # cut and an indicator coinciding on the exact same partition
        # (the remaining tie source) does not occur (verified over 40
        # seeds); deeper/tinier nodes are covered by prediction parity
        f0, t0, l0 = grow_tree(jnp.asarray(binned.astype(np.int32)), G, H,
                               C, max_depth=3, n_bins=32, lam=1.0)
        f1, t1, l1 = grow_tree(jnp.asarray(Xb.astype(np.int32)), G, H, C,
                               max_depth=3, n_bins=32, lam=1.0,
                               bundle_end=jnp.asarray(b.end_bin))
        fu, tu = unbundle_ensemble(b, np.asarray(f1)[None],
                                   np.asarray(t1)[None])
        np.testing.assert_array_equal(np.asarray(f0), fu[0])
        np.testing.assert_array_equal(np.asarray(t0), tu[0])
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=1e-6)

    def test_bundled_deep_tree_prediction_parity(self):
        """Depth 6 (tiny tie-prone nodes): the unbundled tree may differ
        node-for-node at identical-partition ties, but it must route the
        training matrix IDENTICALLY — same leaf values, same scores."""
        from transmogrifai_tpu.models.gbdt_kernels import predict_tree

        X, y = _onehot_data(seed=23)
        n = len(y)
        edges, binned = _binned(X)
        b = bundle_features(binned, edges, 32)
        Xb = bundle_matrix(b, binned)
        rng = np.random.default_rng(24)
        G = jnp.asarray(((0.5 - y) + 0.01 * rng.normal(size=n)
                         ).astype(np.float32)[:, None])
        H = jnp.asarray(np.full((n, 1), 0.25, np.float32))
        C = jnp.asarray(np.ones(n, np.float32))
        f0, t0, l0 = grow_tree(jnp.asarray(binned.astype(np.int32)), G, H,
                               C, max_depth=6, n_bins=32, lam=1.0)
        f1, t1, l1 = grow_tree(jnp.asarray(Xb.astype(np.int32)), G, H, C,
                               max_depth=6, n_bins=32, lam=1.0,
                               bundle_end=jnp.asarray(b.end_bin))
        fu, tu = unbundle_ensemble(b, np.asarray(f1)[None],
                                   np.asarray(t1)[None])
        p0 = np.asarray(predict_tree(jnp.asarray(binned.astype(np.int32)),
                                     f0, t0, l0, 6))
        p1 = np.asarray(predict_tree(
            jnp.asarray(binned.astype(np.int32)),
            jnp.asarray(fu[0]), jnp.asarray(tu[0]), l1, 6))
        np.testing.assert_allclose(p0, p1, atol=1e-6)

    def test_gbt_fit_efb_bit_for_tree(self):
        """The estimator-level round trip: TMOG_EFB on vs off grows the
        SAME boosted trees on a conflict-free matrix."""
        X, y = _onehot_data(seed=1)
        models = {}
        for efb in ("0", "1"):
            os.environ["TMOG_EFB"] = efb
            clear_sweep_caches()
            models[efb] = OpGBTClassifier(max_iter=8, max_depth=4,
                                          seed=3).fit_raw(X, y)
        np.testing.assert_array_equal(np.asarray(models["0"].feat),
                                      np.asarray(models["1"].feat))
        np.testing.assert_array_equal(np.asarray(models["0"].thresh),
                                      np.asarray(models["1"].thresh))
        np.testing.assert_allclose(np.asarray(models["0"].leaf),
                                   np.asarray(models["1"].leaf), atol=1e-6)

    def test_bounded_conflicts_aupr_close(self):
        """With a nonzero conflict budget the encode is lossy for the
        conflicted rows only — fit quality stays within 2e-2 AuPR."""
        from transmogrifai_tpu.evaluators.metrics import aupr

        X, y = _onehot_data(n=400, seed=2)
        # inject ~2% conflicts: make a few rows activate TWO members of
        # the first block
        rng = np.random.default_rng(0)
        rows = rng.choice(len(y), size=8, replace=False)
        X = X.copy()
        X[rows, 3] = 1.0
        X[rows, 4] = 1.0
        edges, binned = _binned(X)
        b = bundle_features(binned, edges, 32, max_conflict_rate=0.05)
        assert b is not None

        def fit_aupr(efb):
            os.environ["TMOG_EFB"] = efb
            clear_sweep_caches()
            m = OpGBTClassifier(max_iter=8, max_depth=4,
                                seed=3).fit_raw(X, y)
            p = m.predict_batch(X).probability[:, 1]
            return aupr(y, p)

        a0, a1 = fit_aupr("0"), fit_aupr("1")
        assert abs(a0 - a1) < 2e-2

    def test_efb_declines_dense(self):
        X, _ = _toy(n=200, d=8)
        edges, binned = _binned(X)
        assert bundle_features(binned, edges, 32) is None

    def test_dd_mask_blocks_bundles(self):
        X, _ = _onehot_data()
        edges, binned = _binned(X)
        b = bundle_features(binned, edges, 32)
        dd = b.bundled_dd_mask(np.ones(b.n_orig, bool))
        for c, spec in enumerate(b.plan):
            if isinstance(spec, (int, np.integer)):
                assert dd[c]
            else:
                assert not dd[c]


class TestGOSS:
    def _fit(self, X, y, seed, depth=8, rounds=6):
        clear_sweep_caches()
        return OpGBTClassifier(max_iter=rounds, max_depth=depth,
                               seed=seed).fit_raw(X, y)

    def test_plan_gates(self):
        assert goss_plan(100_000, 10) is not None
        assert goss_plan(100_000, 7) is None          # depth gate
        assert goss_plan(1_000, 10) is None           # row gate (auto)
        os.environ["TMOG_GOSS"] = "1"
        assert goss_plan(1_000, 10) is not None       # forced: row gate off
        assert goss_plan(1_000, 7) is None            # depth gate holds
        os.environ["TMOG_GOSS"] = "0"
        assert goss_plan(100_000, 10) is None

    def test_chain_chunk_counts_the_one_hot_per_chain(self):
        """Under GOSS nothing is shared between chains (each gathers its
        own rows), so the launch budget must count the bins one-hot per
        chain: the smoke's XGB group — 6 chains, depth 10, 250k x 500 x 32
        bins — asked the v5e compiler for 24.85 GB in one launch when it
        was counted once (PR 21 chip run)."""
        from transmogrifai_tpu.models.gbdt_kernels import gbt_chain_chunk

        os.environ.pop("TMOG_GOSS", None)
        for n in (250_000, 1_000_000):
            shared = gbt_chain_chunk(6, 10, 500, 32, n)
            goss = gbt_chain_chunk(6, 10, 500, 32, n,
                                   goss_rows=sum(goss_plan(n, 10)))
            assert shared == 6
            assert 1 <= goss <= 2
        # small problems still launch every chain at once
        assert gbt_chain_chunk(6, 10, 32, 32, 30_000,
                               goss_rows=sum(goss_plan(30_000, 10))) == 6

    def test_seed_determinism(self):
        os.environ["TMOG_EFB"] = "0"
        os.environ["TMOG_GOSS"] = "1"
        X, y = _toy(n=400, d=8, seed=7)
        a = self._fit(X, y, seed=3)
        b = self._fit(X, y, seed=3)
        c = self._fit(X, y, seed=4)
        np.testing.assert_array_equal(np.asarray(a.feat),
                                      np.asarray(b.feat))
        np.testing.assert_array_equal(np.asarray(a.thresh),
                                      np.asarray(b.thresh))
        assert not (np.asarray(a.feat) == np.asarray(c.feat)).all()

    def test_off_below_depth_threshold(self):
        """Depth-7 candidates grow identically whether GOSS is forced or
        disabled — the depth gate is part of the contract."""
        os.environ["TMOG_EFB"] = "0"
        X, y = _toy(n=400, d=8, seed=8)
        os.environ["TMOG_GOSS"] = "1"
        a = self._fit(X, y, seed=3, depth=7)
        os.environ["TMOG_GOSS"] = "0"
        b = self._fit(X, y, seed=3, depth=7)
        np.testing.assert_array_equal(np.asarray(a.feat),
                                      np.asarray(b.feat))

    def test_quality_stays_useful(self):
        from transmogrifai_tpu.evaluators.metrics import aupr

        os.environ["TMOG_GOSS"] = "1"
        X, y = _toy(n=500, d=8, seed=9)
        m = self._fit(X, y, seed=3, rounds=10)
        p = m.predict_batch(X).probability[:, 1]
        assert aupr(y, p) > 0.8


class TestTreeGridMesh:
    """Tentpole gates: batched tree groups on the ("data", "grid") sweep
    mesh agree with the single-chip batched programs (documented 2e-2
    tolerance) and are invariant to ``n_rows mod 8``."""

    @pytest.mark.parametrize("n", [297, 300, 304])
    def test_rf_group_mesh_parity_residues(self, n):
        X, y = _toy(n=n, d=10, seed=n)
        ctxs = _ctxs(n)
        proto = OpRandomForestClassifier(num_trees=6, seed=3)
        pts = [{"max_depth": 3}, {"max_depth": 5}]
        a = np.asarray(RFGridGroup(proto, pts, "AuPR").run(X, y, ctxs))
        clear_sweep_caches()
        mesh = make_sweep_mesh(6, n_devices=8)
        b = np.asarray(RFGridGroup(proto, pts, "AuPR")
                       .with_mesh(mesh).run(X, y, ctxs))
        np.testing.assert_allclose(a, b, atol=2e-2)

    def test_gbt_group_mesh_parity_with_es(self):
        from transmogrifai_tpu.models.trees import OpXGBoostClassifier

        X, y = _toy(n=260, d=8, seed=7)
        ctxs = _ctxs(len(y), seed=7)
        proto = OpXGBoostClassifier(num_round=12, eta=0.3, max_depth=3,
                                    early_stopping_rounds=5, seed=3)
        pts = [{"max_depth": 3}, {"max_depth": 4}]
        a = np.asarray(GBTGridGroup(proto, pts, "AuPR").run(X, y, ctxs))
        clear_sweep_caches()
        mesh = make_sweep_mesh(4, n_devices=8)
        b = np.asarray(GBTGridGroup(proto, pts, "AuPR")
                       .with_mesh(mesh).run(X, y, ctxs))
        np.testing.assert_allclose(a, b, atol=2e-2)

    def test_gbt_group_mesh_efb_parity(self):
        X, y = _onehot_data(n=310, seed=9)
        ctxs = _ctxs(len(y), seed=9)
        proto = OpGBTClassifier(max_iter=6, seed=3)
        pts = [{"max_depth": 3}, {"max_depth": 4}]
        os.environ["TMOG_EFB"] = "0"
        a = np.asarray(GBTGridGroup(proto, pts, "AuPR").run(X, y, ctxs))
        clear_sweep_caches()
        os.environ["TMOG_EFB"] = "1"
        mesh = make_sweep_mesh(4, n_devices=8)
        b = np.asarray(GBTGridGroup(proto, pts, "AuPR")
                       .with_mesh(mesh).run(X, y, ctxs))
        np.testing.assert_allclose(a, b, atol=2e-2)

    def test_sharding_contracts_on_tree_group(self):
        """TM024 pad-invariance + TM025 mesh-parity run clean on the GBT
        grid group — the contracts the multichip smoke gates on now have
        a TREE program under them.  (The RF group's Poisson bag stream is
        shaped (n_rows,), so STRICT pad-invariance cannot apply to it —
        its contract is the documented 2e-2 parity over row residues,
        covered by test_rf_group_mesh_parity_residues.)"""
        from transmogrifai_tpu.analysis.contracts import (
            check_mesh_parity, check_pad_invariance,
        )

        X, y = _toy(n=280, d=8, seed=4)
        ctxs = _ctxs(len(y), seed=4)
        mesh = make_sweep_mesh(6, n_devices=8)
        proto = OpGBTClassifier(max_iter=5, seed=3)
        pts = [{"max_depth": 3}, {"max_depth": 4}]

        def make_group():
            clear_sweep_caches()
            return GBTGridGroup(proto, pts, "AuPR")

        findings = check_pad_invariance(make_group, X, y, ctxs, mesh)
        check_mesh_parity(make_group, X, y, ctxs, mesh, findings=findings)
        assert not findings, findings.format()

    def test_selector_sweep_uses_batched_tree_groups(self):
        """A tree-only sweep on the mesh keeps its grid groups (no
        sequential stripping) and picks the single-chip winner."""
        from transmogrifai_tpu.selector.model_selector import ModelSelector
        from transmogrifai_tpu.selector.validators import OpCrossValidation

        X, y = _toy(n=300, d=10, seed=5)
        w = np.ones(len(y), np.float32)

        def selector():
            return ModelSelector(
                models_and_params=[
                    (OpRandomForestClassifier(num_trees=6, seed=3), [
                        {"max_depth": 3}, {"max_depth": 5}]),
                    (OpGBTClassifier(max_iter=6, seed=3), [
                        {"max_depth": 3}, {"max_depth": 4}]),
                ],
                problem_type="binary",
                validator=OpCrossValidation(num_folds=2, stratify=True))

        sel_s = selector()
        cands_s = sel_s._candidates()
        best_s, res_s = sel_s.validator.validate(
            cands_s, X, y, w, eval_fn=sel_s._metric,
            metric_name=sel_s.validation_metric,
            larger_better=sel_s.larger_better)

        clear_sweep_caches()
        mesh = make_sweep_mesh(4, n_devices=8)
        sel_m = selector().with_mesh(mesh)
        cands_m = sel_m._candidates()
        # tree groups attach the mesh and are mesh-capable now
        assert cands_m[0][3] is not None and cands_m[0][3].mesh is mesh
        assert cands_m[2][3] is not None and cands_m[2][3].mesh is mesh
        assert cands_m[0][3].supports_mesh and cands_m[2][3].supports_mesh
        best_m, res_m = sel_m.validator.validate(
            cands_m, X, y, w, eval_fn=sel_m._metric,
            metric_name=sel_m.validation_metric,
            larger_better=sel_m.larger_better)
        assert all(r.error is None for r in res_m)
        assert best_m == best_s
        np.testing.assert_allclose(
            [r.metric_value for r in res_m],
            [r.metric_value for r in res_s], atol=2e-2)

    def test_halving_regroup_packs_tree_rungs(self):
        """Halving on the mesh re-batches each rung's tree survivors onto
        the grid axis (the regroup callback) — same ladder and winner as
        the single-chip halving sweep."""
        from transmogrifai_tpu.selector.model_selector import ModelSelector
        from transmogrifai_tpu.selector.validators import OpCrossValidation
        from transmogrifai_tpu.tuning import HalvingConfig
        from transmogrifai_tpu.tuning.halving import halving_validate

        X, y = _toy(n=600, d=8, seed=11)
        w = np.ones(len(y), np.float32)
        cfg = HalvingConfig(eta=2, min_rows=128, seed=7)

        def run(mesh):
            clear_sweep_caches()
            sel = ModelSelector(
                models_and_params=[
                    (OpRandomForestClassifier(num_trees=5, seed=3), [
                        {"max_depth": 3}, {"max_depth": 4},
                        {"max_depth": 5}]),
                ],
                problem_type="binary",
                validator=OpCrossValidation(num_folds=2, stratify=True),
                strategy="halving", halving=cfg)
            if mesh is not None:
                sel.with_mesh(mesh)
            cands = sel._candidates(with_groups=False)
            return halving_validate(
                sel.validator, cands, X, y, w, eval_fn=sel._metric,
                metric_name=sel.validation_metric,
                larger_better=sel.larger_better, config=cfg,
                stratify=True, regroup=sel._make_rung_regroup(cands))

        best_m, res_m, sched_m = run(make_sweep_mesh(3, n_devices=8))
        best_s, res_s, sched_s = run(None)
        assert best_m == best_s
        assert ([r["rows"] for r in sched_m["rungs"]]
                == [r["rows"] for r in sched_s["rungs"]])


_TREE_KILL_SCRIPT = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    sys.path.insert(0, {root!r})
    from transmogrifai_tpu.models import OpRandomForestClassifier
    from transmogrifai_tpu.selector.model_selector import ModelSelector
    from transmogrifai_tpu.selector.validators import OpCrossValidation
    from transmogrifai_tpu.parallel.mesh import make_sweep_mesh
    from transmogrifai_tpu.tuning import HalvingConfig

    rng = np.random.default_rng(5)
    X = rng.normal(size=(600, 8)).astype(np.float32)
    beta = rng.normal(size=8) * (rng.random(8) < 0.6)
    y = (1/(1+np.exp(-(X @ beta))) > rng.random(600)).astype(np.float32)

    sel = ModelSelector(
        models_and_params=[
            (OpRandomForestClassifier(num_trees=5, seed=3), [
                {{"max_depth": 3}}, {{"max_depth": 4}},
                {{"max_depth": 5}}]),
        ],
        problem_type="binary",
        validator=OpCrossValidation(num_folds=2, stratify=True),
        strategy="halving",
        halving=HalvingConfig(eta=2, min_rows=128, seed=7),
    ).with_mesh(make_sweep_mesh(3, n_devices=8))
    sel.with_sweep_checkpoint({ckdir!r})
    from transmogrifai_tpu.types.columns import FeatureColumn
    from transmogrifai_tpu.types.feature_types import OPVector, RealNN
    label = FeatureColumn(RealNN, y.astype(np.float64))
    feats = FeatureColumn(OPVector, X)
    sel.fit_columns(None, label, feats)
    summ = sel.metadata["model_selector_summary"]
    print(json.dumps({{"best": summ["bestModelParams"],
                       "metrics": [r["metricValue"] for r in
                                   summ["validationResults"]]}}))
""")


@pytest.mark.faults
class TestKillResumeTreeGrid:
    """Satellite: SIGKILL mid-RUNG with a TREE grid group packed onto the
    mesh, then a rerun against the same checkpoint dir, reproduces the
    uninterrupted run's winner."""

    def _spawn(self, ckdir, faults_spec=None):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        if faults_spec is not None:
            env["TMOG_FAULTS"] = json.dumps(faults_spec)
        else:
            env.pop("TMOG_FAULTS", None)
        script = _TREE_KILL_SCRIPT.format(
            root=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ckdir=str(ckdir))
        return subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True, env=env,
                              timeout=900)

    def test_sigkill_mid_rung_resumes_same_winner(self, tmp_path):
        ref = self._spawn(tmp_path / "ck_ref")
        assert ref.returncode == 0, ref.stderr[-2000:]
        ref_out = json.loads(ref.stdout.splitlines()[-1])

        ckdir = tmp_path / "ck"
        killed = self._spawn(ckdir, faults_spec={
            "faults": [{"point": "sweep.checkpoint", "action": "kill",
                        "at": 2}]})
        assert killed.returncode == -signal.SIGKILL
        resumed = self._spawn(ckdir)
        assert resumed.returncode == 0, resumed.stderr[-2000:]
        out = json.loads(resumed.stdout.splitlines()[-1])
        assert out["best"] == ref_out["best"]
        np.testing.assert_allclose(out["metrics"], ref_out["metrics"],
                                   atol=2e-2)


class TestPrefetchDrain:
    """Satellite: the tree-prep prefetch daemon never outlives the sweep
    — joined on normal completion AND on the elastic teardown path."""

    def _selector(self):
        from transmogrifai_tpu.selector.model_selector import ModelSelector
        from transmogrifai_tpu.selector.validators import OpCrossValidation

        return ModelSelector(
            models_and_params=[
                (OpRandomForestClassifier(num_trees=4, seed=3), [
                    {"max_depth": 3}, {"max_depth": 4}]),
            ],
            problem_type="binary",
            validator=OpCrossValidation(num_folds=2, stratify=True))

    def _fit(self, sel, X, y):
        from transmogrifai_tpu.types.columns import FeatureColumn
        from transmogrifai_tpu.types.feature_types import OPVector, RealNN

        label = FeatureColumn(RealNN, y.astype(np.float64))
        feats = FeatureColumn(OPVector, X)
        return sel.fit_columns(None, label, feats)

    def test_drained_after_normal_fit(self, monkeypatch):
        from transmogrifai_tpu.selector.model_selector import ModelSelector

        monkeypatch.setattr(ModelSelector, "_PREFETCH_MIN_ELEMS", 0)
        X, y = _toy(n=240, d=6, seed=13)
        sel = self._selector()
        self._fit(sel, X, y)
        assert getattr(sel, "_prep_thread", None) is None

    def test_drained_on_device_loss_teardown(self, monkeypatch):
        """An injected device.loss fires the elastic shrink hook, which
        must cancel+join the prefetch thread BEFORE re-pointing the mesh
        — and the fit's teardown leaves no live daemon either way."""
        from transmogrifai_tpu.selector.model_selector import ModelSelector
        from transmogrifai_tpu.utils import faults

        monkeypatch.setattr(ModelSelector, "_PREFETCH_MIN_ELEMS", 0)
        X, y = _toy(n=240, d=6, seed=14)
        sel = self._selector()
        with faults.inject(faults.FaultSpec(
                point="device.loss", action="device_loss", at=1,
                times=1)):
            self._fit(sel, X, y)
        assert getattr(sel, "_prep_thread", None) is None

    def test_drain_cancels_and_joins(self):
        import threading

        sel = self._selector()
        done = threading.Event()

        class _T(threading.Thread):
            def run(self):
                done.wait(5.0)

        t = _T(daemon=True)
        sel._prep_thread = t
        sel._prep_cancel = done        # drain sets it -> thread exits
        t.start()
        sel._drain_tree_prefetch(timeout_s=10.0)
        assert not t.is_alive()
        assert sel._prep_thread is None


class TestGridStageKinds:
    """Satellite: tree grid units register their own cost-model stage
    kinds, advise_mesh consults them, and OLD histories (no grid kinds,
    no nDevices) still load."""

    def test_rf_group_records_fit_grid_kind(self, tmp_path, monkeypatch):
        from transmogrifai_tpu.tuning.costmodel import load_observations

        hist = tmp_path / "hist.json"
        monkeypatch.setenv("TMOG_COST_HISTORY", str(hist))
        X, y = _toy(n=220, d=6, seed=15)
        RFGridGroup(OpRandomForestClassifier(num_trees=4, seed=3),
                    [{"max_depth": 3}], "AuPR").run(X, y, _ctxs(len(y)))
        kinds = {o.stage_kind for o in load_observations(str(hist))}
        assert "RandomForest:fit-grid" in kinds

    def test_advise_mesh_consults_tree_grid_kind(self):
        from transmogrifai_tpu.tuning.costmodel import (
            CostModel, StageObservation,
        )
        from transmogrifai_tpu.tuning.planner import advise_mesh

        obs = []
        for nd, wall in ((1, 8.0), (2, 4.2), (4, 2.4), (8, 1.5)):
            for rows in (1000, 10_000, 100_000):
                obs.append(StageObservation(
                    "GBT:fit-grid", rows=rows, cols=64, dtype="float32",
                    backend="cpu", wall_s=wall * rows / 10_000,
                    n_devices=nd))
        cm = CostModel().fit(obs)
        adv = advise_mesh(50_000, 64, queue_width=8,
                          devices_available=8, cost_model=cm,
                          backend="cpu")
        assert adv.predicted_wall_s            # measured tier engaged
        assert adv.n_devices == 8              # scaling history says wider

    def test_old_history_backcompat(self, tmp_path):
        from transmogrifai_tpu.tuning.costmodel import (
            CostModel, load_observations,
        )
        from transmogrifai_tpu.tuning.planner import advise_mesh

        hist = tmp_path / "cost_history.json"
        hist.write_text(json.dumps({
            "stage_observations": [
                {"stageKind": "ModelSelector:fit", "rows": 1000,
                 "cols": 10, "dtype": "float32", "backend": "cpu",
                 "wallSecs": 1.5, "t": 0},      # pre-mesh record shape
            ],
            "some_bench_config": {"measured_s": 2.0},
        }))
        obs = load_observations(str(hist))
        assert len(obs) == 1 and obs[0].n_devices == 1
        cm = CostModel.from_history(str(hist))
        adv = advise_mesh(1000, 10, queue_width=4, devices_available=8,
                          cost_model=cm, backend="cpu")
        assert adv.n_devices >= 1              # no KeyError on old shapes


class TestAccumToleranceProbe:
    def test_probe_clean_at_reference_shape(self):
        from transmogrifai_tpu.analysis.contracts import (
            check_accum_tolerance,
        )

        X, y = _toy(n=400, d=12, seed=16)
        findings = check_accum_tolerance(X, y)
        assert not findings, findings.format()

    def test_probe_fires_on_impossible_tolerance(self):
        from transmogrifai_tpu.analysis.contracts import (
            check_accum_tolerance,
        )

        X, y = _toy(n=200, d=6, seed=17)
        findings = check_accum_tolerance(X, y, tol=-1.0, n_rounds=2,
                                         max_depth=3)
        assert [d.rule for d in findings.diagnostics] == ["TM028"]
