"""ModelSelector — automated model selection with validation.

Reference: ``ModelSelector`` estimator (core/.../impl/selector/ModelSelector.scala:72,
fit :145-209), ``ModelSelectorSummary`` (impl/selector/ModelSelectorSummary.scala),
factories ``BinaryClassificationModelSelector``
(impl/classification/BinaryClassificationModelSelector.scala:49,54-108,260-266),
``MultiClassificationModelSelector`` (:49,231-235),
``RegressionModelSelector`` (impl/regression/RegressionModelSelector.scala:49,237-242),
grid values ``DefaultSelectorParams`` (impl/selector/DefaultSelectorParams.scala:36-75),
``ModelSelectorFactory``, ``RandomParamBuilder``
(impl/selector/RandomParamBuilder.scala:52,169), ``SelectedModelCombiner``.

Flow (ModelSelector.fit parity): splitter reserves a holdout and computes
training weights -> validator scores every (model, params) candidate on CV
folds (weight-masked, single resident matrix) -> best estimator refit on the
full training split -> holdout + training metrics evaluated -> everything
recorded as ``model_selector_summary`` metadata.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..evaluators.metrics import (
    aupr, auroc, multiclass_metrics, regression_metrics,
    binary_classification_metrics,
)
from ..models.prediction import (
    PredictionBatch, PredictorEstimator, PredictorModel,
)
from ..types.columns import ColumnarDataset, FeatureColumn
from .splitters import DataBalancer, DataCutter, DataSplitter
from .validators import (
    OpCrossValidation, OpTrainValidationSplit, ValidationResult,
)

__all__ = [
    "ModelSelector", "SelectedModel", "ModelSelectorSummary",
    "BinaryClassificationModelSelector", "MultiClassificationModelSelector",
    "RegressionModelSelector", "DefaultSelectorParams", "RandomParamBuilder",
]


class DefaultSelectorParams:
    """Default grid values (DefaultSelectorParams.scala:36-75)."""

    MAX_DEPTH = [3, 6, 12]
    MAX_BIN = [32]
    MIN_INSTANCES_PER_NODE = [10, 100]
    MIN_INFO_GAIN = [0.001, 0.01, 0.1]
    REGULARIZATION = [0.001, 0.01, 0.1, 0.2]
    MAX_ITER_LIN = [50]
    MAX_ITER_TREE = [20]
    STEP_SIZE = [0.1]
    ELASTIC_NET = [0.1, 0.5]
    MAX_TREES = [50]
    TOL = [1e-6]
    NB_SMOOTHING = [1.0]
    NUM_ROUND_XGB = [200]
    ETA_XGB = [0.02]
    MIN_CHILD_WEIGHT_XGB = [1.0, 10.0]
    MAX_DEPTH_XGB = [10]
    EARLY_STOPPING_XGB = [20]
    GAMMA_XGB = [0.8]


def grid(**axes) -> List[Dict[str, Any]]:
    """Cartesian parameter grid."""
    keys = list(axes)
    out = []
    for combo in itertools.product(*(axes[k] for k in keys)):
        out.append(dict(zip(keys, combo)))
    return out


class ModelSelectorSummary:
    """Validation results + best model + metrics (ModelSelectorSummary parity)."""

    def __init__(self, validation_results: List[ValidationResult],
                 best_model_name: str, best_params: Dict[str, Any],
                 validation_type: str, holdout_metrics: Dict[str, float],
                 train_metrics: Dict[str, float],
                 splitter_summary: Optional[dict],
                 problem_type: Optional[str] = None):
        self.validation_results = validation_results
        self.best_model_name = best_model_name
        self.best_params = best_params
        self.validation_type = validation_type
        self.holdout_metrics = holdout_metrics
        self.train_metrics = train_metrics
        self.splitter_summary = splitter_summary
        self.problem_type = problem_type

    def to_json(self):
        return {
            "validationType": self.validation_type,
            "problemType": self.problem_type,
            "validationResults": [r.to_json() for r in self.validation_results],
            "bestModelType": self.best_model_name,
            "bestModelParams": self.best_params,
            "holdoutMetrics": self.holdout_metrics,
            "trainEvaluationMetrics": self.train_metrics,
            "dataPrepResults": self.splitter_summary,
        }


class ModelSelector(PredictorEstimator):
    """Generic selector over (estimator prototype, param grid) candidates.

    ``problem_type``: 'binary' | 'multiclass' | 'regression' — drives the
    validation score extraction and default metrics.
    """

    def __init__(self,
                 models_and_params: Sequence[Tuple[PredictorEstimator,
                                                   List[Dict[str, Any]]]],
                 problem_type: str,
                 validator=None,
                 splitter=None,
                 validation_metric: Optional[str] = None,
                 holdout_evaluators: Sequence = (),
                 uid: Optional[str] = None,
                 strategy: str = "full",
                 halving=None,
                 parallel=None,
                 watchdog: Optional[float] = None):
        super().__init__(operation_name="modelSelector", uid=uid)
        self.models_and_params = list(models_and_params)
        self.problem_type = problem_type
        self.validator = validator or OpCrossValidation(
            num_folds=3, stratify=problem_type != "regression")
        self.splitter = splitter
        self.validation_metric = validation_metric or {
            "binary": "AuPR", "multiclass": "F1",
            "regression": "RootMeanSquaredError"}[problem_type]
        self.holdout_evaluators = list(holdout_evaluators)
        # sweep scheduling: "full" fits every grid candidate to completion
        # (the historical path, byte-identical); "halving" runs successive
        # halving over the candidate grid (tuning/halving.py) — subsampled
        # rows/rounds for early rungs, full-data final rung.  ``halving``
        # takes a tuning.HalvingConfig.
        if strategy not in ("full", "halving"):
            raise ValueError(
                f"unknown selector strategy {strategy!r}; expected "
                f"'full' or 'halving'")
        self.strategy = strategy
        self.halving = halving
        # set by find_best_estimator (workflow-level CV): when present,
        # fit_columns skips validation and refits this winner directly
        # (reference BestEstimator, ModelSelector.scala:116-145)
        self.best_estimator: Optional[Tuple[str, Dict[str, Any],
                                            List[ValidationResult]]] = None
        self.mesh = None
        # pod-scale dispatch (ROADMAP item 1): None = single chip unless
        # with_mesh was called; an int = that many devices on an
        # auto-shaped ("data", "grid") sweep mesh; "auto" = let the cost
        # planner (tuning/planner.advise_mesh) decide from measured
        # scaling history; a jax Mesh = use it directly.
        self.parallel = parallel
        self.sweep_checkpoint_dir: Optional[str] = None
        self.sweep_checkpoint_every: int = 1
        # elastic execution (parallel/elastic.py): device-loss recovery is
        # always on — a classified backend loss shrinks the mesh and
        # retries the unit within this budget before quarantining the
        # candidate.  The straggler watchdog is OPT-IN: ``watchdog`` is
        # the deadline factor over the cost model's per-unit prediction
        # (None = off; also off while the cost-model tier is cold).
        self.watchdog = watchdog
        self.watchdog_cost_model = None   # test seam (with_watchdog)
        self.elastic_max_retries: int = 2

    def with_mesh(self, mesh) -> "ModelSelector":
        """Multi-chip selection.  With a ("data", "grid") sweep mesh
        (``parallel.make_sweep_mesh``), runs of same-family candidates
        batch as ONE pjit/NamedSharding program — rows sharded over the
        data axis, the candidate batch over the grid axis — and the
        remaining families fall back to sequential fits that are
        themselves mesh-sharded (each estimator's own ``with_mesh`` path).
        With a legacy ("data", "model") mesh every candidate fit runs
        mesh-sharded sequentially.  The single-chip device-resident sweep
        shortcut (``fit_device``) is bypassed either way — its programs
        are compiled for one chip's memory space."""
        self.mesh = mesh
        return self

    def with_watchdog(self, factor: float,
                      cost_model=None) -> "ModelSelector":
        """Arm the straggler watchdog: each sweep unit gets a deadline of
        ``factor x (CostModel.predict(sweep kind) / queue width)``.  A
        unit that overruns escalates timeout -> degraded re-run (mesh
        shrunk, deadline doubled) -> quarantine as ``failed: straggler``.
        Only engages when the cost model's tier for the sweep's stage
        kind is FITTED — a cold tier's analytic guess would produce
        garbage deadlines (``cost_model`` overrides the history-fitted
        model; a test seam)."""
        self.watchdog = float(factor)
        self.watchdog_cost_model = cost_model
        return self

    def with_sweep_checkpoint(self, directory: str,
                              every_units: int = 1) -> "ModelSelector":
        """Mid-sweep checkpoint/resume: completed sweep units' fold
        metrics (and the halving rung state) persist atomically under
        ``directory`` as the sweep advances, and a re-run against the
        same directory resumes at the cursor instead of refitting every
        candidate (workflow/checkpoint.SweepCheckpointManager)."""
        self.sweep_checkpoint_dir = directory
        self.sweep_checkpoint_every = int(every_units)
        return self

    def _resolve_parallel(self, n_rows: int, n_cols: int,
                          queue_width: int):
        """Resolve ``parallel`` into a sweep mesh for THIS fit (an
        explicit ``with_mesh`` wins; None means single-chip)."""
        if self.mesh is not None or self.parallel is None:
            return self.mesh
        import jax

        from ..parallel.mesh import make_sweep_mesh

        p = self.parallel
        if hasattr(p, "axis_names"):          # a prebuilt Mesh
            return p
        n_avail = len(jax.devices())
        if p == "auto":
            from ..tuning.planner import advise_mesh

            adv = advise_mesh(n_rows, n_cols, queue_width=queue_width,
                              devices_available=n_avail)
            self.metadata["mesh_advice"] = adv.to_json()
            if adv.n_devices <= 1:
                return None
            return make_sweep_mesh(queue_width, n_devices=adv.n_devices,
                                   grid_parallelism=adv.grid_axis)
        n = min(int(p), n_avail)
        if n <= 1:
            return None
        return make_sweep_mesh(queue_width, n_devices=n)

    # -- elastic execution ---------------------------------------------------

    def _elastic_context(self, n_rows: int, n_cols: int, queue_width: int):
        """The per-fit elastic policy (parallel/elastic.py): a shrink
        hook that re-points this stage's LIVE ``mesh`` attribute at a
        smaller sweep mesh built from surviving devices (the unit fitters
        read it per fit, so the retried unit lands on the shrunk mesh —
        ultimately ``None``, the single-device CPU-fallback path), plus
        the opt-in watchdog deadline."""
        from ..parallel.elastic import ElasticContext, shrink_mesh

        def shrink() -> bool:
            # the tree-prep prefetch thread must not outlive the mesh it
            # may be uploading against: cancel + join BEFORE re-pointing
            # the live mesh at the shrunk one (ISSUE 11 satellite — an
            # aborting sweep used to leave the daemon running)
            self._drain_tree_prefetch()
            new = shrink_mesh(self.mesh)
            changed = (new is not self.mesh
                       and (new is None or self.mesh is None
                            or new.shape != self.mesh.shape))
            self.mesh = new
            return changed

        ctx = ElasticContext(shrink=shrink,
                             max_unit_retries=self.elastic_max_retries,
                             unit_deadline_s=self._watchdog_deadline(
                                 n_rows, n_cols, queue_width))
        # live-mesh peek for the sweep spans (obs/): unit spans record the
        # mesh each attempt actually ran on, which a shrink re-points
        ctx.mesh_provider = lambda: self.mesh
        return ctx

    def _watchdog_deadline(self, n_rows: int, n_cols: int,
                           queue_width: int) -> Optional[float]:
        """``factor x predicted sweep wall / queue width``, or None when
        the watchdog is unarmed or the cost-model tier is cold (an
        analytic cold-start guess would quarantine healthy units)."""
        if not self.watchdog:
            return None
        from ..utils.profiling import backend_name

        cm = self.watchdog_cost_model
        if cm is None:
            from ..tuning.costmodel import CostModel

            cm = CostModel.from_history()
        kind = ("ModelSelector:fit-halving" if self.strategy == "halving"
                else "ModelSelector:fit")
        backend = backend_name()
        # tree grid units record their own stage kinds (RandomForest:
        # fit-grid / GBT:fit-grid) — when those tiers are warm the
        # watchdog sees tree grid units even before the selector-level
        # tier is; deadlines sum over whichever kinds are fitted
        kinds = [kind] + [k for k in self._tree_grid_kinds()]
        fitted = [k for k in kinds if cm.source(k, backend) == "fitted"]
        if not fitted:
            return None               # all tiers cold: watchdog stays off
        from ..parallel.elastic import mesh_device_count

        total = sum(cm.predict(k, n_rows, n_cols, backend=backend,
                               n_devices=mesh_device_count(self.mesh))
                    for k in fitted)
        return max(float(self.watchdog) * total / max(queue_width, 1),
                   1e-3)

    def _tree_grid_kinds(self) -> List[str]:
        """The tree-grid cost-model stage kinds present in this grid."""
        from ..models.trees import _GBTBase, _RandomForestBase

        kinds = []
        for proto, _pts in self.models_and_params:
            if isinstance(proto, _RandomForestBase):
                kinds.append("RandomForest:fit-grid")
            elif isinstance(proto, _GBTBase):
                kinds.append("GBT:fit-grid")
        return sorted(set(kinds))

    # -- validation plumbing -------------------------------------------------

    def _score_fn(self, model: PredictorModel, X: np.ndarray):
        dev = model.score_device(X, self.problem_type)
        if dev is not None:
            return dev                     # device array; metric stays lazy
        batch = model.predict_batch(X)
        if self.problem_type == "binary":
            if batch.probability is not None:
                return np.asarray(batch.probability)[:, 1]
            return np.asarray(batch.raw_prediction)[:, 1]
        return np.asarray(batch.prediction)

    def _metric(self, y, scores, w):
        """Fold metric; returns a DEVICE scalar when scores are device-
        resident and the metric has a device kernel (validators fetch all
        fold scalars in one stacked transfer), else a host float."""
        import jax

        m = self.validation_metric
        if isinstance(scores, jax.Array):
            dev = self._metric_device(y, scores, w, m)
            if dev is not None:
                return dev
            scores = np.asarray(scores)
        if self.problem_type == "binary":
            if m == "AuPR":
                return float(aupr(y, scores, w))
            if m == "AuROC":
                return float(auroc(y, scores, w))
            return binary_classification_metrics(y, scores, w)[m]
        if self.problem_type == "multiclass":
            n_classes = self._class_count(y, scores)
            return multiclass_metrics(y.astype(int), scores.astype(int),
                                      n_classes, w)[m]
        return regression_metrics(y, scores, w)[m]

    def _capture_class_space(self, y) -> None:
        """Record the class space from the FULL labels before any split —
        validation folds missing the top class must not shrink it."""
        if self.problem_type == "multiclass" and len(y):
            self._n_classes = max(int(np.nanmax(y)) + 1, 2)

    def _class_count(self, y, pred=None) -> int:
        """Class space size: the FULL-training-label count captured at fit
        time wins — a validation fold missing the top class must not shrink
        the class space (the reference reads it from the label indexer
        metadata; here fit captures it before any split)."""
        n = getattr(self, "_n_classes", 0)
        if y is not None and len(y):
            n = max(n, int(np.nanmax(y)) + 1)
        if pred is not None and len(pred):
            n = max(n, int(np.nanmax(np.asarray(pred))) + 1)
        return max(n, 2)

    def _metric_device(self, y, scores, w, m: str):
        import jax.numpy as jnp

        from ..evaluators.metrics import _aupr_dev, _auroc_dev

        if self.problem_type == "binary":
            if m == "AuPR":
                return _aupr_dev(y, scores, w)
            if m == "AuROC":
                return _auroc_dev(y, scores, w)
            return None
        if self.problem_type == "regression":
            if m not in ("RootMeanSquaredError", "MeanSquaredError",
                         "MeanAbsoluteError", "R2"):
                return None
            from ..evaluators.metrics import _regression_metric_dev

            yj = jnp.asarray(y, jnp.float32)
            wj = (jnp.ones_like(yj) if w is None
                  else jnp.asarray(w, jnp.float32))
            return _regression_metric_dev(yj, scores, wj, m)
        if self.problem_type == "multiclass":
            from ..evaluators.metrics import _multiclass_core

            n_classes = self._class_count(y)
            res = _multiclass_core(np.asarray(y, np.int32), scores,
                                   n_classes, w)
            return res.get(m)
        return None

    @property
    def larger_better(self) -> bool:
        from ..evaluators.metrics import MINIMIZE_METRICS
        return self.validation_metric not in MINIMIZE_METRICS

    def _candidates(self, with_groups: bool = True):
        from ..models.gbdt_kernels import compile_depth_hint
        from ..parallel.mesh import has_grid_axis
        from .grid_groups import make_grid_group

        grid_mesh = has_grid_axis(self.mesh)
        out = []
        for proto, grid_points in self.models_and_params:
            # one batched program for the whole (folds x grid) product when
            # the family supports it.  Single chip by default; on a
            # ("data", "grid") sweep mesh the mesh-capable families run
            # the SAME batched program sharded (rows over data, candidate
            # batch over grid), while a legacy ("data", "model") mesh
            # keeps the historical per-candidate sharded fits.
            # ``with_groups=False`` is the halving scheduler's path: rung
            # subsets fit per-candidate (a group always computes its WHOLE
            # family grid, which would pay for eliminated candidates) —
            # the sharded halving sweep re-batches each rung's survivors
            # via ``_make_rung_regroup`` instead.
            group = (make_grid_group(proto, grid_points, self.problem_type,
                                     self.validation_metric,
                                     n_classes=self._class_count(None),
                                     mesh=self.mesh if grid_mesh else None)
                     if ((self.mesh is None or grid_mesh) and with_groups)
                     else None)
            fam_depth = self._family_depth(proto, grid_points)
            for params in grid_points:
                def fitter(X, y, w, p, proto=proto, fam_depth=fam_depth):
                    # heap shapes sized to THIS family's deepest candidate —
                    # a sweep-wide hint made shallow families (XGB depth 6)
                    # pay the deep family's (RF depth 12) compacted-slot
                    # histogram cost, ~20x on the default grid
                    with compile_depth_hint(fam_depth):
                        est = proto.copy(**p)
                        if self.mesh is not None:
                            if hasattr(est, "with_mesh"):
                                est.with_mesh(self.mesh)
                        else:
                            dev_score = est.fit_device(X, y, w,
                                                       self.problem_type)
                            if dev_score is not None:
                                return dev_score  # device fit+score, no sync
                        model = est.fit_raw(X, y, w)
                    return lambda Xe: self._score_fn(model, Xe)
                out.append((type(proto).__name__, params, fitter, group))
        return out

    def _resolved_splitter(self):
        if self.splitter is not None:
            return self.splitter
        return {"binary": DataBalancer(),
                "multiclass": DataCutter(),
                "regression": DataSplitter()}[self.problem_type]

    def _sweep_checkpoint(self, candidates, n_rows: int, elastic=None):
        """Mid-sweep cursor manager for this fit, or None.  Primed from
        disk (resume); a checkpoint for a LOGICALLY different sweep
        raises CheckpointMismatchError instead of blending runs, while a
        mesh-shape change resumes — the remaining units re-batch onto
        this process's mesh, and the re-pack/shrink lands on the elastic
        counters."""
        if self.sweep_checkpoint_dir is None:
            return None
        from ..workflow.checkpoint import (SweepCheckpointManager,
                                           mesh_record, sweep_fingerprint)

        v = self.validator
        vdesc = (f"{type(v).__name__}("
                 f"folds={getattr(v, 'num_folds', None)},"
                 f"ratio={getattr(v, 'train_ratio', None)},"
                 f"seed={getattr(v, 'seed', None)},"
                 f"stratify={getattr(v, 'stratify', None)})")
        fp = sweep_fingerprint(candidates, self.validation_metric, vdesc,
                               mesh=self.mesh, strategy=self.strategy,
                               n_rows=n_rows)
        manager = SweepCheckpointManager(
            self.sweep_checkpoint_dir, fp,
            every_units=self.sweep_checkpoint_every)
        if manager.load() and manager.mesh_changed and elastic is not None:
            elastic.note_resumed_mesh(manager.resumed_mesh,
                                      mesh_record(self.mesh))
        return manager

    def _make_rung_regroup(self, candidates):
        """Per-rung grid-group factory for the SHARDED halving sweep: a
        rung's surviving same-family candidates re-batch (at their
        rung-scaled fit params) into one mesh-sharded program packed onto
        the grid axis.  None on single-chip / legacy meshes — the rungs
        keep their per-candidate fits."""
        from ..parallel.mesh import has_grid_axis

        if not has_grid_axis(self.mesh):
            return None
        from .grid_groups import make_grid_group

        protos = [proto for proto, pts in self.models_and_params
                  for _ in pts]

        def regroup(indices, fit_params_list):
            out = []
            pos = 0
            while pos < len(indices):
                proto = protos[indices[pos]]
                end = pos
                while end < len(indices) and protos[indices[end]] is proto:
                    end += 1
                pts = [dict(fit_params_list[p]) for p in range(pos, end)]
                group = make_grid_group(
                    proto, pts, self.problem_type, self.validation_metric,
                    n_classes=self._class_count(None), mesh=self.mesh)
                for p in range(pos, end):
                    name, _params, fitter, *_ = candidates[indices[p]]
                    out.append((name, fit_params_list[p], fitter, group))
                pos = end
            return out

        return regroup

    @staticmethod
    def _family_depth(proto, grid_points):
        """Deepest tree depth within ONE estimator family's grid: that
        family's sequential fits then share ONE compiled tree-growth
        program, each candidate's true max_depth applied as a traced depth
        limit (gbdt_kernels.compile_depth_hint).  Per FAMILY, not sweep-
        wide: families never share growth programs, so a global hint only
        inflates the shallow family's heap shapes."""
        proto_d = getattr(proto, "max_depth", None)
        depths = [int(params.get("max_depth", proto_d))
                  for params in grid_points
                  if params.get("max_depth", proto_d) is not None]
        return max(depths) if depths else None

    def find_best_estimator(self, data: ColumnarDataset,
                            during_dag) -> Tuple[str, Dict[str, Any]]:
        """Workflow-level CV (ModelSelector.findBestEstimator
        ModelSelector.scala:116): validate candidates with the
        feature-engineering ``during_dag`` refit inside every fold, and
        remember the winner so the subsequent ``fit`` skips validation."""
        label_name = self.label_feature.name
        if label_name not in data:
            raise RuntimeError(
                f"label column {label_name!r} not materialized before the "
                f"CV cut — it must be produced by the before-DAG")
        y = np.nan_to_num(np.asarray(data[label_name].values,
                                     dtype=np.float32))
        n = len(y)
        self._capture_class_space(y)
        splitter = self._resolved_splitter()
        train_idx, _ = splitter.split_indices(n, y)
        train_mask = np.zeros(n, dtype=bool)
        train_mask[train_idx] = True
        base_w = splitter.train_weights(y, train_mask)

        sub = data.take(train_idx)
        candidates = self._candidates()
        best_i, results = self.validator.validate_with_dag(
            candidates, sub, during_dag,
            label_name=label_name,
            features_name=self.features_feature.name,
            y=y[train_idx], base_weights=base_w[train_idx],
            eval_fn=self._metric, metric_name=self.validation_metric,
            larger_better=self.larger_better)
        best_name, best_params, *_ = candidates[best_i]
        self.best_estimator = (best_name, best_params, results)
        # introspectable record of the fold-refit validation (survives the
        # consume-on-fit of best_estimator)
        self.metadata["workflow_cv_results"] = [r.to_json() for r in results]
        return best_name, best_params

    def find_best_estimator_prefold(self, per_fold, y=None,
                                    n_rows: int = 0
                                    ) -> Tuple[str, Dict[str, Any]]:
        """Workflow-level CV over PRE-BUILT fold matrices — the streaming
        path's ``find_best_estimator`` (workflow/streaming_cv.py builds
        the matrices from merged fold-tagged monoid states).  Same
        contract: the winner is remembered so the subsequent ``fit``
        skips validation; the fold-validated results land in
        ``metadata["workflow_cv_results"]``.

        Unlike the in-core DAG variant this one runs through the full
        sweep machinery: ``parallel=``/mesh resolution, the mid-sweep
        checkpoint cursor (``with_sweep_checkpoint`` — a SIGKILLed CV
        sweep resumes at its unit cursor, on whatever mesh the resuming
        process has), and the elastic device-loss ladder with its
        counters in ``metadata["workflow_cv_elastic"]``.
        """
        if y is not None:
            self._capture_class_space(np.asarray(y, np.float32))
        n_cols = int(per_fold[0][0].shape[1]) if per_fold else 0
        queue_width = sum(len(g) for _, g in self.models_and_params)
        prev_mesh = self.mesh
        self.mesh = self._resolve_parallel(n_rows, n_cols, queue_width)
        try:
            elastic = self._elastic_context(n_rows, n_cols, queue_width)
            # per-fold matrices differ per context, so family grid
            # groups (which batch over ONE shared matrix) don't apply
            candidates = self._candidates(with_groups=False)
            ckpt = self._sweep_checkpoint(candidates, n_rows,
                                          elastic=elastic)
            best_i, results = self.validator.validate_prefold(
                candidates, per_fold, eval_fn=self._metric,
                metric_name=self.validation_metric,
                larger_better=self.larger_better,
                checkpoint=ckpt, elastic=elastic)
            if ckpt is not None:
                ckpt.finish()
            self.metadata["workflow_cv_elastic"] = (
                elastic.counters.to_json())
        finally:
            self._drain_tree_prefetch()
            self.mesh = prev_mesh
        best_name, best_params, *_ = candidates[best_i]
        self.best_estimator = (best_name, best_params, results)
        self.metadata["workflow_cv_results"] = [r.to_json() for r in results]
        return best_name, best_params

    # -- fit -----------------------------------------------------------------

    def _grid_has_linear(self) -> bool:
        """True when a candidate will consume the full-precision device
        matrix (the binary-LR / linear-regression device fit paths)."""
        from ..models.classification import OpLogisticRegression
        from ..models.regression import OpLinearRegression

        if self.problem_type == "binary":
            return any(isinstance(p, OpLogisticRegression)
                       for p, _ in self.models_and_params)
        if self.problem_type == "regression":
            return any(isinstance(p, OpLinearRegression)
                       for p, _ in self.models_and_params)
        return False

    def _prepare_matrix(self, values) -> np.ndarray:
        """One C-contiguous f32 matrix for the whole sweep (every candidate
        probes the upload/binning memos with this same object), plus the
        shared device upload up front when a linear-family candidate will
        consume the full matrix — tree candidates then quantize on device
        from it instead of a host binning pass.  Large matrices upload as
        bf16 (see ``trees._dev_f32``; TMOG_MATRIX_PRECISION=f32 forces
        exact uploads at twice the bytes).

        A mesh-sharded ``jax.Array`` (the streaming→sharded ingest
        hand-off, ``parallel.ingest``) is kept device-resident when a
        mesh sweep will consume it; single-chip fits pull it to host."""
        import jax

        from ..models.trees import _as_f32, _dev_f32, _host_copy

        if isinstance(values, jax.Array) and not isinstance(values,
                                                            np.ndarray):
            if self.mesh is not None:
                return values             # committed row-sharded already
            # (a copy ``_as_f32`` makes of it is booked there)
            values = _host_copy(values, "selector.matrix")
        X = _as_f32(np.asarray(values))
        if self.mesh is None and self._grid_has_linear() and X.size > (1 << 24):
            _dev_f32(X)
        return X

    #: below this element count prefetching the tree prep in a thread buys
    #: nothing (the sketch is sub-second)
    _PREFETCH_MIN_ELEMS = 1 << 24

    def _start_tree_prep_prefetch(self, X: np.ndarray):
        """Overlap the host quantile sketch / binning with the sweep's
        queued device work (VERDICT r3 Missing #5): the linear groups
        dispatch async and only sync at the stacked metric fetch, so a
        daemon thread can run the tree families' ~seconds of host prep in
        that shadow.  The memo's in-flight dedup (trees._memo) hands the
        result to the tree group — or blocks it until ready — so there is
        no duplicated sketch work."""
        import threading

        from ..models.trees import _prep_tree_inputs_sparse
        from ..obs.trace import current_span
        from ..obs.trace import span as _span

        if self.mesh is not None or X.size < self._PREFETCH_MIN_ELEMS:
            return None
        bins = sorted({int(getattr(p, "max_bins", 0))
                       for p, _ in self.models_and_params
                       if getattr(p, "max_bins", None)})
        if not bins:
            return None

        cancel = threading.Event()
        # the span stack is thread-local: the thread's spans hang under the
        # span current HERE, where it is started
        parent = current_span()

        def work():
            with _span("tree.prep.prefetch", cat="prep", parent=parent):
                for mb in bins:
                    if cancel.is_set():   # elastic teardown: stop here
                        return
                    try:
                        _prep_tree_inputs_sparse(X, mb)
                    except Exception:   # prep errors surface on the sweep
                        return

        t = threading.Thread(target=work, name="tree-prep-prefetch",
                             daemon=True)
        # retained so the elastic teardown / end-of-fit paths can join it:
        # a daemon prep thread must never outlive a shrunk mesh (its
        # device work would land on dead devices) or the fit itself
        self._prep_thread = t
        self._prep_cancel = cancel
        t.start()
        return t

    def _drain_tree_prefetch(self, timeout_s: float = 30.0) -> None:
        """Cancel + join the tree-prep prefetch thread (no-op when none
        is running).  Called from the elastic shrink hook BEFORE the mesh
        is re-pointed and from the fit's teardown, so no daemon prep work
        outlives the sweep that started it.  The join wait is booked into
        the transfer ledger (``tree_prefetch.join`` drain) — it used to
        disappear into fit wall, making prefetch stalls unattributable."""
        t = getattr(self, "_prep_thread", None)
        if t is None:
            return
        cancel = getattr(self, "_prep_cancel", None)
        if cancel is not None:
            cancel.set()
        if t.is_alive():
            import time as _time

            from ..utils.profiling import count_drain

            t0 = _time.perf_counter()
            t.join(timeout_s)
            count_drain(_time.perf_counter() - t0,
                        tag="tree_prefetch.join")
        self._prep_thread = None
        self._prep_cancel = None

    def fit_columns(self, data: ColumnarDataset, label_col: FeatureColumn,
                    features_col: FeatureColumn):
        # cost-model bucket refinement (workflow/plan.py reads it): a
        # halving sweep's wall follows a different law than a full sweep's
        from ..obs.trace import span as _span

        self._cost_kind = ("fit-halving" if self.strategy == "halving"
                           else None)
        with _span("selector.prepare", cat="selector"):
            X = self._prepare_matrix(features_col.values)
            y = np.nan_to_num(np.asarray(label_col.values,
                                         dtype=np.float32))
            n = len(y)
            self._capture_class_space(y)
            splitter = self._resolved_splitter()
            train_idx, holdout_idx = splitter.split_indices(n, y)
            train_mask = np.zeros(n, dtype=bool)
            train_mask[train_idx] = True
            base_w = splitter.train_weights(y, train_mask)

            # ``parallel=`` dispatch: resolve an int/"auto" request into a
            # ("data", "grid") sweep mesh for THIS fit only (with_mesh
            # wins, and the attribute is restored on the way out — the
            # same scoping contract the workflow applies to with_mesh)
            queue_width = sum(len(g) for _, g in self.models_and_params)
            prev_mesh = self.mesh
            self.mesh = self._resolve_parallel(n, int(X.shape[1]),
                                               queue_width)
        try:
            return self._fit_columns_inner(
                X, y, n, splitter, train_mask, holdout_idx, base_w)
        finally:
            # join the tree-prep prefetch daemon whether the sweep
            # finished or aborted (device loss, checkpoint mismatch,
            # every-candidate failure): no prep work may outlive the fit
            self._drain_tree_prefetch()
            self.mesh = prev_mesh

    def _fit_columns_inner(self, X, y, n, splitter, train_mask,
                           holdout_idx, base_w):
        from ..obs.trace import span as _span
        from ..utils.profiling import count_fresh

        # a mesh-padded device matrix (the streaming→sharded ingest
        # hand-off) carries pad rows: labels/weights pad with ZEROS so the
        # pad rows are inert through every weighted fit and metric
        n_x = int(X.shape[0])
        if n_x != n:
            y_v = np.pad(y, (0, n_x - n))
            base_w_v = np.pad(base_w, (0, n_x - n))
        else:
            y_v, base_w_v = y, base_w

        # elastic execution context for this fit: device-loss recovery
        # (shrink + bounded retry + quarantine) always armed, watchdog
        # per configuration.  The counters land in metadata["elastic"]
        # whether or not anything fired, so the numbers are always there
        # to read (and always zero on a healthy sweep).
        queue_width = sum(len(g) for _, g in self.models_and_params)
        elastic = self._elastic_context(n, int(X.shape[1]), queue_width)

        best_group = None
        if self.best_estimator is not None:
            # consume the workflow-CV winner: a later fit on new data must
            # validate afresh, not reuse a stale selection
            best_name, best_params, results = self.best_estimator
            self.best_estimator = None
        elif self.strategy == "halving":
            # successive halving (tuning/halving.py): early rungs rank
            # candidates on stratified row subsamples + scaled rounds,
            # only survivors pay full-data fits.  No WHOLE-grid groups (a
            # group batches its whole family — eliminated candidates
            # would still be paid for): on a sweep mesh each rung's
            # survivors re-batch onto the grid axis via the regroup
            # callback instead.  No tree-prep prefetch (sized for the
            # full matrix, not the rungs).
            from ..tuning.halving import halving_validate

            candidates = self._candidates(with_groups=False)
            ckpt = self._sweep_checkpoint(candidates, n, elastic=elastic)
            with _span("selector.validate", cat="selector"):
                best_i, results, schedule = halving_validate(
                    self.validator, candidates, X, y_v, base_w_v,
                    eval_fn=self._metric,
                    metric_name=self.validation_metric,
                    larger_better=self.larger_better, config=self.halving,
                    stratify=self.problem_type != "regression",
                    checkpoint=ckpt,
                    regroup=self._make_rung_regroup(candidates),
                    elastic=elastic)
            if ckpt is not None:
                ckpt.finish()
            self.metadata["halving_schedule"] = schedule
            best_name, best_params, *_ = candidates[best_i]
        else:
            # host tree-prep (sketch/binning) overlaps the linear
            # groups' async device work in a daemon thread
            self._start_tree_prep_prefetch(X)
            candidates = self._candidates()
            ckpt = self._sweep_checkpoint(candidates, n, elastic=elastic)
            with _span("selector.validate", cat="selector"):
                best_i, results = self.validator.validate(
                    candidates, X, y_v, base_w_v,
                    eval_fn=self._metric,
                    metric_name=self.validation_metric,
                    larger_better=self.larger_better, checkpoint=ckpt,
                    elastic=elastic)
            if ckpt is not None:
                ckpt.finish()
            best_name, best_params, *rest = candidates[best_i]
            best_group = rest[1] if len(rest) > 1 else None
        self.metadata["elastic"] = elastic.counters.to_json()

        # refit best on the full training split (ModelSelector.fit :180).
        # Grid groups that solved an appended full-train weight row hold the
        # winner's refit model already (refit_model) — sweep artifacts are
        # reused instead of paying a fresh sequential fit (the reference
        # refits from scratch, ModelSelector.scala:145-209).  Known
        # divergence (ADVICE r4, intentional): for the LINEAR groups the
        # deployed coefficients come from the batched majorization/prox
        # solver's full-train row, which agrees with a sequential
        # Newton/IRLS refit to METRIC level (~2e-3 AuPR; parity-tested in
        # test_lr_group_refit_matches_sequential) but not per-coefficient —
        # tighten the solver tol if exact reference refit-from-scratch
        # coefficient parity is ever required.  Fallback: a
        # sequential fit at the winner's OWN depth (family hints live in
        # the fitters; nothing outside the winner's family shares its
        # growth program).
        best_model = None
        with _span("selector.refit", cat="selector", model=best_name):
            if best_group is not None and not elastic.groups_invalid:
                # (a mid-sweep mesh shrink invalidates group refit
                # artifacts — their device arrays target the dead mesh;
                # refit sequentially)
                try:
                    row = best_group.grid_points.index(best_params)
                except ValueError:
                    row = None
                if row is not None:
                    best_model = best_group.refit_model(row)
            if best_model is None:
                best_proto = next(p for p, _ in self.models_and_params
                                  if type(p).__name__ == best_name)
                best_est = best_proto.copy(**best_params)
                if self.mesh is not None and hasattr(best_est, "with_mesh"):
                    best_est.with_mesh(self.mesh)
                best_model = best_est.fit_raw(X, y_v, base_w_v)

        # ONE batched predict over the full matrix (hits the sweep's binning
        # and upload memos) — slicing rows first would re-bin and re-upload
        # a fresh holdout matrix per metric set
        with _span("selector.predict", cat="selector"):
            full_batch = best_model.predict_batch(X)
            for a in (full_batch.prediction, full_batch.raw_prediction,
                      full_batch.probability):
                if a is not None:
                    count_fresh("selector.predict", a.nbytes)
        with _span("selector.metrics", cat="selector"):
            train_metrics = self._full_metrics(full_batch, y, train_mask)
            holdout_metrics = (
                self._full_metrics(full_batch, y, ~train_mask)
                if len(holdout_idx) else {})

            summary = ModelSelectorSummary(
                validation_results=results, best_model_name=best_name,
                best_params=best_params,
                validation_type=type(self.validator).__name__,
                holdout_metrics=holdout_metrics,
                train_metrics=train_metrics,
                splitter_summary=(splitter.summary.to_json()
                                  if splitter.summary else None),
                problem_type=self.problem_type)
            self.metadata["model_selector_summary"] = summary.to_json()
        selected = SelectedModel(inner=best_model, best_name=best_name,
                                 best_params=best_params)
        return selected

    def _full_metrics(self, full_batch: PredictionBatch, y,
                      mask: np.ndarray) -> Dict[str, float]:
        """Metrics over the masked rows of a full-matrix prediction batch."""
        idx = np.where(mask)[0]
        if not len(idx):
            return {}
        yy = y[idx]
        if self.problem_type == "binary":
            score = (np.asarray(full_batch.probability)[idx, 1]
                     if full_batch.probability is not None
                     else np.asarray(full_batch.prediction)[idx])
            return binary_classification_metrics(yy, score)
        if self.problem_type == "multiclass":
            pred = np.asarray(full_batch.prediction)[idx].astype(int)
            n_classes = self._class_count(yy, pred)
            out = multiclass_metrics(yy.astype(int), pred, n_classes)
            out.pop("confusion", None)
            return out
        return regression_metrics(yy, np.asarray(full_batch.prediction)[idx])


class SelectedModel(PredictorModel):
    """The winning fitted model (SelectedModel parity)."""

    def __init__(self, inner: PredictorModel, best_name: str = "",
                 best_params: Optional[Dict[str, Any]] = None,
                 uid: Optional[str] = None):
        super().__init__(operation_name="modelSelector", uid=uid)
        self.inner = inner
        self.best_name = best_name
        self.best_params = best_params or {}

    def predict_batch(self, X: np.ndarray) -> PredictionBatch:
        return self.inner.predict_batch(X)

    def aot_scoring_spec(self):
        return self.inner.aot_scoring_spec()


# ---------------------------------------------------------------------------
# Factories with default model grids
# ---------------------------------------------------------------------------

def _binary_defaults() -> List[Tuple[PredictorEstimator, List[Dict[str, Any]]]]:
    """Default binary models: LR + RF (+ GBT/XGB-equivalent when enabled)
    (BinaryClassificationModelSelector.scala:54-108)."""
    from ..models.classification import OpLogisticRegression
    from ..models.trees import OpGBTClassifier, OpRandomForestClassifier

    D = DefaultSelectorParams
    return [
        (OpLogisticRegression(), grid(
            reg_param=D.REGULARIZATION, elastic_net_param=D.ELASTIC_NET,
            max_iter=D.MAX_ITER_LIN)),
        (OpRandomForestClassifier(), grid(
            max_depth=D.MAX_DEPTH, min_instances_per_node=D.MIN_INSTANCES_PER_NODE,
            min_info_gain=D.MIN_INFO_GAIN, num_trees=D.MAX_TREES)),
    ]


def _multiclass_defaults():
    from ..models.classification import OpLogisticRegression
    from ..models.trees import OpRandomForestClassifier

    D = DefaultSelectorParams
    return [
        (OpLogisticRegression(), grid(
            reg_param=D.REGULARIZATION, elastic_net_param=D.ELASTIC_NET,
            max_iter=D.MAX_ITER_LIN)),
        (OpRandomForestClassifier(), grid(
            max_depth=D.MAX_DEPTH, min_instances_per_node=D.MIN_INSTANCES_PER_NODE,
            min_info_gain=D.MIN_INFO_GAIN, num_trees=D.MAX_TREES)),
    ]


def _regression_defaults():
    from ..models.regression import OpLinearRegression
    from ..models.trees import OpGBTRegressor, OpRandomForestRegressor

    D = DefaultSelectorParams
    return [
        (OpLinearRegression(), grid(
            reg_param=D.REGULARIZATION, elastic_net_param=D.ELASTIC_NET,
            max_iter=[200])),
        (OpRandomForestRegressor(), grid(
            max_depth=D.MAX_DEPTH, min_instances_per_node=D.MIN_INSTANCES_PER_NODE,
            min_info_gain=D.MIN_INFO_GAIN, num_trees=D.MAX_TREES)),
        (OpGBTRegressor(), grid(
            max_depth=D.MAX_DEPTH, max_iter=D.MAX_ITER_TREE,
            step_size=D.STEP_SIZE)),
    ]


class BinaryClassificationModelSelector:
    @staticmethod
    def with_cross_validation(
        num_folds: int = 3, validation_metric: str = "AuPR",
        splitter=None, seed: int = 42,
        models_and_parameters=None, parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _binary_defaults(),
            problem_type="binary",
            validator=OpCrossValidation(num_folds=num_folds, seed=seed,
                                        stratify=True,
                                        parallelism=parallelism,
                                        max_wait=max_wait),
            splitter=splitter if splitter is not None else DataBalancer(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)

    @staticmethod
    def with_train_validation_split(
        train_ratio: float = 0.75, validation_metric: str = "AuPR",
        splitter=None, seed: int = 42, models_and_parameters=None,
        parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _binary_defaults(),
            problem_type="binary",
            validator=OpTrainValidationSplit(train_ratio=train_ratio,
                                             seed=seed, stratify=True,
                                             parallelism=parallelism,
                                             max_wait=max_wait),
            splitter=splitter if splitter is not None else DataBalancer(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)


class MultiClassificationModelSelector:
    @staticmethod
    def with_cross_validation(
        num_folds: int = 3, validation_metric: str = "F1",
        splitter=None, seed: int = 42, models_and_parameters=None,
        parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _multiclass_defaults(),
            problem_type="multiclass",
            validator=OpCrossValidation(num_folds=num_folds, seed=seed,
                                        stratify=True,
                                        parallelism=parallelism,
                                        max_wait=max_wait),
            splitter=splitter if splitter is not None else DataCutter(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)

    @staticmethod
    def with_train_validation_split(
        train_ratio: float = 0.75, validation_metric: str = "F1",
        splitter=None, seed: int = 42, models_and_parameters=None,
        parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _multiclass_defaults(),
            problem_type="multiclass",
            validator=OpTrainValidationSplit(train_ratio=train_ratio,
                                             seed=seed, stratify=True,
                                             parallelism=parallelism,
                                             max_wait=max_wait),
            splitter=splitter if splitter is not None else DataCutter(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)


class RegressionModelSelector:
    @staticmethod
    def with_cross_validation(
        num_folds: int = 3, validation_metric: str = "RootMeanSquaredError",
        splitter=None, seed: int = 42, models_and_parameters=None,
        parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _regression_defaults(),
            problem_type="regression",
            validator=OpCrossValidation(num_folds=num_folds, seed=seed,
                                        parallelism=parallelism,
                                        max_wait=max_wait),
            splitter=splitter if splitter is not None else DataSplitter(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)

    @staticmethod
    def with_train_validation_split(
        train_ratio: float = 0.75,
        validation_metric: str = "RootMeanSquaredError",
        splitter=None, seed: int = 42, models_and_parameters=None,
        parallelism: int = 8,
        max_wait: Optional[float] = None,
        strategy: str = "full", halving=None, parallel=None,
        watchdog: Optional[float] = None,
    ) -> ModelSelector:
        return ModelSelector(
            models_and_params=models_and_parameters or _regression_defaults(),
            problem_type="regression",
            validator=OpTrainValidationSplit(train_ratio=train_ratio,
                                             seed=seed,
                                             parallelism=parallelism,
                                             max_wait=max_wait),
            splitter=splitter if splitter is not None else DataSplitter(seed=seed),
            validation_metric=validation_metric,
            strategy=strategy, halving=halving, parallel=parallel,
            watchdog=watchdog)


class RandomParamBuilder:
    """Random-search grids (RandomParamBuilder.scala:52,169)."""

    def __init__(self, seed: int = 42):
        self._rng = np.random.default_rng(seed)
        self._axes: Dict[str, Callable[[], Any]] = {}

    def uniform(self, name: str, lo: float, hi: float) -> "RandomParamBuilder":
        self._axes[name] = lambda: float(self._rng.uniform(lo, hi))
        return self

    def log_uniform(self, name: str, lo: float, hi: float) -> "RandomParamBuilder":
        self._axes[name] = lambda: float(np.exp(
            self._rng.uniform(np.log(lo), np.log(hi))))
        return self

    def choice(self, name: str, options: Sequence[Any]) -> "RandomParamBuilder":
        opts = list(options)
        self._axes[name] = lambda: opts[int(self._rng.integers(len(opts)))]
        return self

    def build(self, n: int) -> List[Dict[str, Any]]:
        return [{k: fn() for k, fn in self._axes.items()} for _ in range(n)]
