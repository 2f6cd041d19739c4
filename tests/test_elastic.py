"""Elastic sweep execution — device-loss recovery, straggler watchdog,
mesh-portable checkpoints, and the leak-proof sharded-ingest abort path.

Everything here runs on the conftest's 8 virtual CPU devices; device
losses and stragglers are injected seed-deterministically through the
``device.loss`` / ``unit.slow`` fault points (utils/faults.py), so the
whole escalation matrix — retry on a shrunk mesh, degraded re-run,
quarantine — executes without a chip ever actually dying.
"""
import numpy as np
import pytest

from transmogrifai_tpu.parallel import make_sweep_mesh
from transmogrifai_tpu.parallel.elastic import (
    ElasticContext, ElasticCounters, classify_sweep_error, is_device_loss,
    mesh_device_count, run_with_deadline, shrink_mesh,
)
from transmogrifai_tpu.utils import faults


def _toy(n=300, d=12, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d) * (rng.random(d) < 0.6)
    y = (1 / (1 + np.exp(-(X @ beta))) > rng.random(n)).astype(np.float32)
    return X, y


def _selector(n_folds=2, watchdog=None):
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier,
    )
    from transmogrifai_tpu.selector.model_selector import ModelSelector, grid
    from transmogrifai_tpu.selector.validators import OpCrossValidation

    return ModelSelector(
        models_and_params=[
            (OpLogisticRegression(), grid(
                reg_param=[0.001, 0.01, 0.1, 1.0],
                elastic_net_param=[0.0])),
            (OpRandomForestClassifier(num_trees=6, seed=3), [
                {"max_depth": 3}, {"max_depth": 5}]),
        ],
        problem_type="binary",
        validator=OpCrossValidation(num_folds=n_folds, stratify=True),
        watchdog=watchdog)


def _validate(sel, X, y, w=None, elastic=None, with_groups=True,
              checkpoint=None):
    w = w if w is not None else np.ones(len(y), np.float32)
    cands = sel._candidates(with_groups=with_groups)
    best, results = sel.validator.validate(
        cands, X, y, w, eval_fn=sel._metric,
        metric_name=sel.validation_metric,
        larger_better=sel.larger_better, checkpoint=checkpoint,
        elastic=elastic)
    return best, results


class TestClassifier:
    """The shared device-loss classifier (parallel/elastic.py)."""

    def test_recognizes_backend_loss_shapes(self):
        for msg in ("Unable to initialize backend 'tpu'",
                    "UNAVAILABLE: TPU backend setup/compile error",
                    "No visible TPU devices",
                    "the device is lost"):
            assert is_device_loss(RuntimeError(msg)), msg
            assert classify_sweep_error(RuntimeError(msg)) == "device_loss"

    def test_injected_form_and_workload_errors(self):
        assert is_device_loss(faults.DeviceLossError("anything"))
        for e in (ValueError("shape mismatch"), RuntimeError("nan loss"),
                  faults.FaultError("injected fault")):
            assert classify_sweep_error(e) == "workload"

    def test_init_failure_is_a_device_loss_not_a_workload_error(self):
        # the shape bench.py's old shim pinned, now straight at the
        # classifier (the shim and its CPU re-exec are gone)
        assert is_device_loss(
            RuntimeError("UNAVAILABLE: TPU backend setup/compile error"))
        assert not is_device_loss(ValueError("nope"))


class TestShrinkLadder:
    def test_shrink_halves_until_single_device(self):
        mesh = make_sweep_mesh(6, n_devices=8)
        m4 = shrink_mesh(mesh)
        assert dict(m4.shape) == {"data": 4, "grid": 1}
        m2 = shrink_mesh(m4)
        assert dict(m2.shape) == {"data": 2, "grid": 1}
        assert shrink_mesh(m2) is None          # the single-device floor
        assert shrink_mesh(None) is None
        assert mesh_device_count(None) == 1
        assert mesh_device_count(mesh) == 8


class TestDeviceLossRecovery:
    def test_loss_retries_on_shrunk_mesh_same_winner(self):
        # with_groups=False: since PR 11 the TREE families batch on the
        # mesh too, so a grouped sweep runs NO per-unit attempts (the
        # device.loss point fires per unit attempt) — the unit-level
        # recovery ladder under test needs sequential units
        X, y = _toy()
        best0, res0 = _validate(_selector(), X, y, with_groups=False)
        sel = _selector().with_mesh(make_sweep_mesh(6, n_devices=8))
        ctx = sel._elastic_context(len(y), X.shape[1], 6)
        with faults.inject(faults.FaultSpec(
                point="device.loss", action="device_loss", at=4, times=1)):
            best, res = _validate(sel, X, y, elastic=ctx,
                                  with_groups=False)
        assert all(r.error is None for r in res)
        c = ctx.counters
        assert (c.device_losses, c.retries, c.quarantined) == (1, 1, 0)
        assert c.mesh_shrinks >= 1
        assert best == best0
        np.testing.assert_allclose(
            [r.metric_value for r in res],
            [r.metric_value for r in res0], atol=2e-2)

    def test_persistent_loss_quarantines_candidate_not_sweep(self):
        """A unit whose every attempt dies lands in the summary as
        ``failed: device_loss`` — the sweep still selects a winner."""
        X, y = _toy()
        sel = _selector().with_mesh(make_sweep_mesh(6, n_devices=8))
        ctx = sel._elastic_context(len(y), X.shape[1], 6)
        with faults.inject(faults.FaultSpec(
                point="device.loss", action="device_loss", at=4,
                times=None)):
            best, res = _validate(sel, X, y, elastic=ctx,
                                  with_groups=False)
        assert res[4].error is not None
        assert res[4].error.startswith("failed: device_loss")
        assert sum(r.error is not None for r in res) == 1
        assert ctx.counters.quarantined == 1
        # retry budget respected: initial attempt + max_unit_retries
        assert ctx.counters.device_losses == sel.elastic_max_retries + 1

    def test_group_device_loss_strips_to_sequential(self):
        """A loss inside the batched LR grid-group program shrinks the
        mesh and strips the group — its members refit sequentially on
        the survivors, and the sweep completes with parity."""
        X, y = _toy(n=420, d=10)
        best0, res0 = _validate(_selector(), X, y)
        sel = _selector().with_mesh(make_sweep_mesh(6, n_devices=8))
        ctx = sel._elastic_context(len(y), X.shape[1], 6)
        cands = sel._candidates()
        assert cands[0][3] is not None          # LR group present
        orig_run = cands[0][3].run
        calls = {"n": 0}

        def dying_run(*a, **k):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError(
                    "UNAVAILABLE: TPU backend setup/compile error")
            return orig_run(*a, **k)

        cands[0][3].run = dying_run
        with pytest.warns(RuntimeWarning, match="falling back"):
            best, res = sel.validator.validate(
                cands, X, y, np.ones(len(y), np.float32),
                eval_fn=sel._metric, metric_name=sel.validation_metric,
                larger_better=sel.larger_better, elastic=ctx)
        assert all(r.error is None for r in res)
        assert ctx.counters.device_losses == 1
        assert ctx.counters.mesh_shrinks == 1
        assert best == best0
        np.testing.assert_allclose(
            [r.metric_value for r in res],
            [r.metric_value for r in res0], atol=2e-2)

    def test_elastic_counters_land_in_selector_metadata(self):
        from transmogrifai_tpu.types.columns import FeatureColumn
        from transmogrifai_tpu.types.feature_types import OPVector, RealNN

        X, y = _toy(n=240, d=8)
        sel = _selector()
        label = FeatureColumn(RealNN, y.astype(np.float64))
        feats = FeatureColumn(OPVector, X)
        sel.fit_columns(None, label, feats)
        el = sel.metadata["elastic"]
        assert el == {"retries": 0, "meshShrinks": 0, "meshRepacks": 0,
                      "quarantined": 0, "watchdogFires": 0,
                      "deviceLosses": 0}


class TestWatchdog:
    def test_overrun_degrades_then_succeeds(self):
        X, y = _toy(n=200, d=8, seed=7)
        # warm-up: cache the compiled fit programs so only the injected
        # sleep can overrun the deadline
        _validate(_selector(), X, y, with_groups=False)
        sel = _selector()
        ctx = ElasticContext(unit_deadline_s=1.5)
        with faults.inject(faults.FaultSpec(
                point="unit.slow", action="slow", at=2, times=1,
                delay_s=4.0)):
            best, res = _validate(sel, X, y, elastic=ctx,
                                  with_groups=False)
        assert all(r.error is None for r in res)
        assert ctx.counters.watchdog_fires == 1
        assert ctx.counters.retries == 1
        assert not ctx.abandoned                # drained at sweep end

    def test_repeat_overrun_quarantines_straggler(self):
        X, y = _toy(n=200, d=8, seed=7)
        _validate(_selector(), X, y, with_groups=False)
        sel = _selector()
        ctx = ElasticContext(unit_deadline_s=0.8)
        with faults.inject(faults.FaultSpec(
                point="unit.slow", action="slow", at=2, times=2,
                delay_s=4.0)):
            best, res = _validate(sel, X, y, elastic=ctx,
                                  with_groups=False)
        assert res[2].error is not None
        assert res[2].error.startswith("failed: straggler")
        assert ctx.counters.watchdog_fires == 2
        assert ctx.counters.quarantined == 1
        assert not ctx.abandoned

    def test_cold_cost_tier_keeps_watchdog_off(self):
        from transmogrifai_tpu.tuning.costmodel import CostModel

        sel = _selector().with_watchdog(3.0, cost_model=CostModel())
        assert sel._watchdog_deadline(200, 8, 6) is None

    def test_fitted_tier_arms_per_unit_deadline(self):
        from transmogrifai_tpu.tuning.costmodel import (
            CostModel, StageObservation,
        )
        from transmogrifai_tpu.utils.profiling import backend_name

        obs = [StageObservation("ModelSelector:fit", r, 8, "float32",
                                backend_name(), 0.5 + r / 1e5)
               for r in (1000, 2000, 4000, 8000)]
        sel = _selector().with_watchdog(
            3.0, cost_model=CostModel().fit(obs))
        d = sel._watchdog_deadline(2000, 8, 6)
        assert d is not None and d > 0

    def test_run_with_deadline_reraises_worker_errors(self):
        def boom():
            raise ValueError("worker error")

        with pytest.raises(ValueError, match="worker error"):
            run_with_deadline(boom, 5.0)
        val, timed_out = run_with_deadline(lambda: 42, 5.0)
        assert (val, timed_out) == (42, False)


class TestMeshPortableCheckpointDiff:
    """Satellite: CheckpointMismatchError carries a key-level diff."""

    def test_streaming_fingerprint_diff_names_keys(self, tmp_path):
        from transmogrifai_tpu.workflow.checkpoint import (
            CheckpointMismatchError, StreamingCheckpointManager,
        )

        fp1 = {"chunkRows": 64, "reader": {"class": "CSVReader",
                                           "rows": 100},
               "stages": ["a", "b"]}
        m1 = StreamingCheckpointManager(str(tmp_path), fp1)
        m1.complete_pass(0, "fit", 100, {})
        fp2 = {"chunkRows": 128, "reader": {"class": "CSVReader",
                                            "rows": 100},
               "stages": ["a", "b"]}
        m2 = StreamingCheckpointManager(str(tmp_path), fp2)
        with pytest.raises(CheckpointMismatchError) as ei:
            m2.load()
        msg = str(ei.value)
        assert "chunkRows" in msg and "64" in msg and "128" in msg
        # unchanged keys are NOT dumped
        assert "CSVReader" not in msg

    def test_fingerprint_diff_truncates(self):
        from transmogrifai_tpu.workflow.checkpoint import fingerprint_diff

        a = {str(i): i for i in range(40)}
        b = {str(i): i + 1 for i in range(40)}
        lines = fingerprint_diff(a, b)
        assert lines[-1] == "... (diff truncated)"
        assert len(lines) <= 13

    def test_resume_counts_mesh_shrink(self, tmp_path):
        """Resuming an 8-device checkpoint on a 4-device mesh lands
        ``meshShrinks``/``meshRepacks`` on the elastic counters via the
        selector's checkpoint plumbing."""
        from transmogrifai_tpu.workflow.checkpoint import (
            SweepCheckpointManager,
        )

        X, y = _toy(n=200, d=6)
        mesh8 = make_sweep_mesh(6, n_devices=8)
        sel1 = _selector().with_mesh(mesh8)
        sel1.with_sweep_checkpoint(str(tmp_path))
        cands1 = sel1._candidates(with_groups=False)
        m1 = sel1._sweep_checkpoint(cands1, len(y))
        m1.record_unit(0, [0.5, 0.6], None)

        mesh4 = make_sweep_mesh(6, n_devices=4)
        sel2 = _selector().with_mesh(mesh4)
        sel2.with_sweep_checkpoint(str(tmp_path))
        ctx = sel2._elastic_context(len(y), 6, 6)
        cands2 = sel2._candidates(with_groups=False)
        m2 = sel2._sweep_checkpoint(cands2, len(y), elastic=ctx)
        assert isinstance(m2, SweepCheckpointManager)
        assert ctx.counters.mesh_shrinks == 1
        assert ctx.counters.mesh_repacks == 1


class TestShardedWriterClose:
    """Satellite: ShardedMatrixWriter releases device + host buffers on
    an aborted ingest (mirrors the _BlockStore spill cleanup)."""

    def test_close_releases_buffers_mid_shard(self):
        from transmogrifai_tpu.parallel.ingest import ShardedMatrixWriter

        mesh = make_sweep_mesh(4, n_devices=8)
        w = ShardedMatrixWriter(mesh, 403, 7)
        rng = np.random.default_rng(0)
        w.append(rng.normal(size=(250, 7)).astype(np.float32))
        assert w._committed            # some shards already on device
        assert w._buf is not None
        w.close()
        assert w._committed == {} and w._buf is None
        w.close()                      # idempotent
        with pytest.raises(ValueError, match="closed"):
            w.finish()

    def test_stream_to_mesh_releases_on_abort(self):
        from transmogrifai_tpu.parallel.ingest import stream_to_mesh

        mesh = make_sweep_mesh(4, n_devices=8)

        def chunks():
            yield np.zeros((100, 5), np.float32)
            raise OSError("reader died mid-shard")

        with pytest.raises(OSError):
            stream_to_mesh(chunks(), mesh, 400, 5)
        # no leak regression assert is possible on the local writer, but
        # the finally path is the one under test: a second full stream
        # in the same process must work cleanly
        X_dev, valid = stream_to_mesh(
            iter([np.ones((400, 5), np.float32)]), mesh, 400, 5)
        assert int(valid.sum()) == 400

    def test_column_writer_close_releases_shard_writers(self):
        from transmogrifai_tpu.workflow.streaming import _ColumnWriter
        from transmogrifai_tpu.types.columns import FeatureColumn
        from transmogrifai_tpu.types.feature_types import OPVector
        from transmogrifai_tpu.types.columns import ColumnarDataset

        mesh = make_sweep_mesh(4, n_devices=8)
        cw = _ColumnWriter(400, shard_onto=mesh, shard_columns={"m"})
        chunk = ColumnarDataset(
            {"m": FeatureColumn(OPVector,
                                np.ones((100, 3), np.float32))},
            _validated=True)
        cw.append(chunk, ["m"])
        sw = cw.cols["m"]["swriter"]
        assert sw is not None and not sw._closed
        cw.close()
        assert sw._closed and sw._buf is None and sw._committed == {}

    def test_block_spill_close_releases_buffers_and_disk(self, tmp_path):
        """Block-spill mode extends the abort contract: close() mid-block
        must also unlink the partial spill file (RSS AND disk bounded)."""
        import os

        from transmogrifai_tpu.parallel.ingest import ShardedMatrixWriter

        w = ShardedMatrixWriter(None, 403, 7, block_rows=64,
                                spill_dir=str(tmp_path))
        rng = np.random.default_rng(0)
        w.append(rng.normal(size=(250, 7)).astype(np.float32))
        spill = w._spill_path
        assert spill is not None and os.path.exists(spill)
        w.close()
        assert w._buf is None and not os.path.exists(spill)
        w.close()                      # idempotent
        with pytest.raises(ValueError, match="closed"):
            w.finish()

    def test_block_spill_handle_owns_file_after_finish(self, tmp_path):
        """After finish() the handle owns the spill file: the writer's
        finally-close must NOT unlink it under the reader's feet."""
        import os

        from transmogrifai_tpu.parallel.ingest import ShardedMatrixWriter

        rng = np.random.default_rng(1)
        X = rng.normal(size=(130, 4)).astype(np.float32)
        w = ShardedMatrixWriter(None, 130, 4, block_rows=64,
                                spill_dir=str(tmp_path))
        w.append(X)
        handle = w.finish()
        try:
            w.close()                  # the stream_to_mesh finally path
            assert os.path.exists(handle.path)
            assert handle.block_bounds == [(0, 64), (64, 128), (128, 130)]
            assert handle.read_all().tobytes() == X.tobytes()
        finally:
            handle.close()
        assert not os.path.exists(handle.path)

    def test_block_spill_zero_row_host(self):
        from transmogrifai_tpu.parallel.ingest import ShardedMatrixWriter

        w = ShardedMatrixWriter(None, 0, 5, block_rows=64)
        handle = w.finish()
        assert handle.n_blocks == 0
        assert handle.read_all().shape == (0, 5)
        assert list(handle.iter_blocks()) == []
        handle.close()


class TestElasticSmokeHalvingResume:
    """The in-process half of the ELASTIC_SMOKE matrix: a halving sweep
    checkpointed on one mesh resumes on another mesh shape with its rung
    survivors re-batched there (the subprocess SIGKILL half lives in
    examples/bench_elastic.py, run by scripts/tier1.sh)."""

    def test_halving_rung_state_resumes_across_mesh(self, tmp_path):
        from transmogrifai_tpu.tuning import HalvingConfig
        from transmogrifai_tpu.tuning.halving import halving_validate
        from transmogrifai_tpu.workflow.checkpoint import (
            SweepCheckpointManager, sweep_fingerprint,
        )

        X, y = _toy(n=900, d=8, seed=9)
        w = np.ones(len(y), np.float32)
        cfg = HalvingConfig(eta=3, min_rows=128, seed=7)

        def run(mesh, manager):
            sel = _selector()
            sel.strategy = "halving"
            sel.halving = cfg
            if mesh is not None:
                sel.with_mesh(mesh)
            cands = sel._candidates(with_groups=False)
            return halving_validate(
                sel.validator, cands, X, y, w, eval_fn=sel._metric,
                metric_name=sel.validation_metric,
                larger_better=sel.larger_better, config=cfg,
                stratify=True, checkpoint=manager,
                regroup=sel._make_rung_regroup(cands))

        def fingerprint(mesh):
            sel = _selector()
            cands = sel._candidates(with_groups=False)
            return sweep_fingerprint(cands, "AuPR", "cv2", mesh=mesh,
                                     strategy="halving", n_rows=len(y))

        # uninterrupted 8-device run (the reference)
        mesh8 = make_sweep_mesh(6, n_devices=8)
        m_ref = SweepCheckpointManager(str(tmp_path / "ref"),
                                       fingerprint(mesh8))
        best_ref, res_ref, sched_ref = run(mesh8, m_ref)

        # 8-device run's checkpoint after rung 0, resumed on 4 devices
        ckdir = tmp_path / "ck"
        m1 = SweepCheckpointManager(str(ckdir), fingerprint(mesh8))
        run(mesh8, m1)
        # rewind to "killed after rung 0": keep rung state + rung0 units
        st = m1.rung_state()
        m2_prep = SweepCheckpointManager(str(ckdir), fingerprint(mesh8))
        m2_prep.load()
        m2_prep._units = {k: v for k, v in m2_prep._units.items()
                          if k.startswith("rung0:")}
        m2_prep.save_rung_state({**st, "nextRung": 1,
                                 "rungJson": st["rungJson"][:1]}
                                if st else None)

        mesh4 = make_sweep_mesh(6, n_devices=4)
        m2 = SweepCheckpointManager(str(ckdir), fingerprint(mesh4))
        assert m2.load() is True and m2.mesh_changed
        best2, res2, sched2 = run(mesh4, m2)
        assert best2 == best_ref
        assert sched2["survivors"] == sched_ref["survivors"]
        np.testing.assert_allclose(
            [r.metric_value for r in res2],
            [r.metric_value for r in res_ref], atol=2e-2)
