"""Workflow engine — the user-facing train/score orchestration.

Reference: ``OpWorkflow`` (core/.../OpWorkflow.scala — train :347, fitStages
:376-455, generateRawData :235), ``OpWorkflowModel`` (OpWorkflowModel.scala —
score :259, evaluate :324, summary :187-221, save :223), shared core state
``OpWorkflowCore`` (OpWorkflowCore.scala:53-324).

The TPU substitution: rather than launching Spark jobs per estimator, the DAG
executes in-process — host columnar transforms feed a device-resident feature
matrix, and every estimator's fit is a compiled XLA program.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..evaluators.evaluators import OpEvaluatorBase
from ..features.feature import Feature
from ..readers.base import Reader, reader_for
from ..stages.base import Estimator, Model, PipelineStage, Transformer
from ..stages.generator import FeatureGeneratorStage
from ..types.columns import ColumnarDataset
from .dag import (StagesDAG, compute_dag, cut_dag_cv, fit_and_transform_dag,
                  transform_dag)

__all__ = ["OpWorkflow", "OpWorkflowModel"]


class _WorkflowCore:
    """State shared by workflow and fitted model (OpWorkflowCore parity)."""

    def __init__(self):
        self.result_features: List[Feature] = []
        self.reader: Optional[Reader] = None
        self.blocklisted: List[str] = []
        self.parameters: Dict[str, Dict[str, Any]] = {}

    def set_reader(self, reader) -> "_WorkflowCore":
        self.reader = reader_for(reader)
        return self

    def set_input_data(self, data) -> "_WorkflowCore":
        """Ad-hoc dataset wrapped into a reader (setInputDataset parity)."""
        self.reader = reader_for(data)
        return self

    def raw_features(self) -> List[Feature]:
        out: List[Feature] = []
        seen = set()
        for rf in self.result_features:
            for f in rf.raw_features():
                if f.uid not in seen:
                    seen.add(f.uid)
                    out.append(f)
        return out

    def generate_raw_data(self) -> ColumnarDataset:
        if self.reader is None:
            raise RuntimeError("no reader set — call set_reader/set_input_data")
        return self.reader.generate_dataset(self.raw_features())


class OpWorkflow(_WorkflowCore):
    def __init__(self):
        super().__init__()
        self._raw_feature_filter = None
        self._model_stages: Dict[str, Model] = {}
        self._workflow_cv = False
        self._allow_non_serializable = False
        self.mesh = None

    def allow_non_serializable(self) -> "OpWorkflow":
        """Opt out of the train-time serializability gate: train with
        lambda/callable stage params anyway (saving will stub them with a
        warning; the loaded model falls back to default behavior)."""
        self._allow_non_serializable = True
        return self

    def with_mesh(self, mesh) -> "OpWorkflow":
        """Train the WHOLE workflow on a device mesh: every mesh-capable
        stage in the DAG (SanityChecker stats, the ModelSelector sweep and
        refit, each tree/linear trainer) receives the mesh at train time —
        the equivalent of the reference distributing every fit over Spark
        executors (SURVEY §2.12 row 1)."""
        self.mesh = mesh
        return self

    # -- wiring -------------------------------------------------------------

    def set_result_features(self, *features: Feature) -> "OpWorkflow":
        self.result_features = list(features)
        return self

    def set_parameters(self, params: Dict[str, Dict[str, Any]]) -> "OpWorkflow":
        """Per-stage param injection by class name or uid (OpParams parity,
        OpWorkflow.setStageParameters OpWorkflow.scala:179-201)."""
        self.parameters = dict(params)
        return self

    def with_raw_feature_filter(self, **kwargs) -> "OpWorkflow":
        """Enable RawFeatureFilter (OpWorkflow.withRawFeatureFilter :537)."""
        from ..filters.raw_feature_filter import RawFeatureFilter

        self._raw_feature_filter = RawFeatureFilter(**kwargs)
        return self

    def with_workflow_cv(self) -> "OpWorkflow":
        """Move label-aware feature-engineering estimators inside the CV
        loop (OpWorkflow.withWorkflowCV; SURVEY §3.2): the DAG is cut at the
        ModelSelector and the leakage-prone segment refits per fold."""
        self._workflow_cv = True
        return self

    def with_model_stages(self, model: "OpWorkflowModel") -> "OpWorkflow":
        """Warm-start: reuse fitted models for matching estimator uids
        (OpWorkflow.withModelStages OpWorkflow.scala:468)."""
        for s in model.stages:
            if isinstance(s, Model):
                self._model_stages[s.uid] = s
        return self

    # -- training -----------------------------------------------------------

    def _inject_params(self, dag: StagesDAG) -> None:
        if not self.parameters:
            return
        for stage in dag.all_stages():
            for key in (stage.uid, type(stage).__name__):
                if key in self.parameters:
                    stage.set_params(**self.parameters[key])

    def _apply_blocklist(self, dropped: Sequence[str]) -> None:
        """Prune dropped raw features out of stage inputs
        (OpWorkflow.setBlocklist semantics): variadic stages simply lose the
        input; a stage whose inputs all drop propagates the drop; a result
        feature that becomes unreachable is an error."""
        if not dropped:
            return
        self.blocklisted = list(dropped)
        gone = set(dropped)
        dag = compute_dag(self.result_features)
        for layer in dag.layers:
            for stage in layer:
                if isinstance(stage, FeatureGeneratorStage):
                    continue
                remaining = [f for f in stage.input_features
                             if f.name not in gone]
                if len(remaining) == len(stage.input_features):
                    continue
                lo, _ = stage.input_arity
                out = stage.get_output()
                if remaining and len(remaining) >= max(lo, 1):
                    stage.input_features = remaining
                    out.parents = list(remaining)
                else:
                    gone.add(out.name)
        bad = [f.name for f in self.result_features if f.name in gone]
        if bad:
            raise ValueError(
                f"RawFeatureFilter dropped features required by result "
                f"features {bad}; protect them via protected_features")

    def _train_keep_columns(self) -> List[str]:
        """Columns ``train()`` must retain through the DAG run — everything
        else is liveness-pruned by the execution plan as soon as its last
        consumer stage has run.  Kept: the result features, the raw
        response(s) (evaluation + ModelInsights label summary), and the
        result stages' direct inputs (the selector's feature vector backs
        ModelInsights/train_data introspection)."""
        keep = {f.name for f in self.result_features}
        keep |= {f.name for f in self.raw_features() if f.is_response}
        for f in self.result_features:
            s = f.origin_stage
            if s is not None:
                keep |= {ff.name for ff in s.input_features}
        return sorted(keep)

    def train(self, profile: bool = False,
              chunk_rows: Optional[int] = None,
              prefetch_chunks: int = 2,
              validate: bool = True,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every_chunks: int = 16,
              tuner=None) -> "OpWorkflowModel":
        """Fit the workflow.  ``profile=True`` additionally records a
        per-stage execution profile (wall time, rows, columns
        added/dropped, device launches) on the returned model as
        ``train_profile`` (a PlanProfiler; ``.format()`` for the summary,
        ``.to_json()`` for the raw numbers).

        ``validate=True`` (default) runs the static DAG lint
        (analysis/linter.py — dangling/shadowed/duplicate columns,
        feature-type mismatches, label leakage) before any stage fits and
        raises :class:`~transmogrifai_tpu.analysis.PipelineLintError` on
        error-severity findings; warnings (e.g. dead stages) are recorded
        on the returned model as ``lint_snapshot`` together with the lint
        wall time.  The lint is pure graph traversal — sub-millisecond on
        the demo DAGs, <1% of train wall by bench contract.

        ``chunk_rows=k`` switches to the OUT-OF-CORE path
        (workflow/streaming.py): the reader streams bounded k-row chunks,
        streamable estimators fit via mergeable sketch states, and only
        the keep-set columns (the packed feature matrix, the response)
        ever materialize full-length — peak host memory stops scaling
        with the intermediate featurization width.  ``chunk_rows=None``
        (default) keeps today's in-core path byte-identical.
        ``prefetch_chunks`` bounds the reader thread's parse-ahead depth
        (chunk k+1 parses while chunk k transforms).

        ``checkpoint_dir`` enables checkpoint/resume.  On the out-of-core
        path (with ``chunk_rows``): chunk-level — streaming-fit states +
        a chunks-consumed cursor persist atomically every
        ``checkpoint_every_chunks`` chunks, and re-running the same train
        against the same directory after a crash resumes from the last
        durable point instead of refitting (docs/robustness.md;
        workflow/checkpoint.py for what resumes where).  On the in-core
        path: sweep-level — the directory routes to every ModelSelector
        stage as a MID-SWEEP cursor (completed sweep units + halving rung
        state; docs/multichip.md resume semantics).  A checkpoint from a
        different reader/pipeline/chunk geometry (or a different sweep)
        raises ``CheckpointMismatchError`` rather than silently blending
        runs.

        ``tuner`` (a :class:`transmogrifai_tpu.tuning.Tuner`) opts THIS
        train into the adaptive machinery (docs/tuning.md): every
        ModelSelector stage runs under the tuner's sweep ``strategy``
        ("halving" = successive halving over the candidate grid; the
        stages' own settings are restored afterwards, the ``with_mesh``
        contract), and with ``auto_plan=True`` the cost planner picks
        stream-vs-in-core and the chunk geometry when ``chunk_rows`` is
        not given and the reader can estimate its rows.  ``tuner=None``
        (default) keeps today's paths byte-identical.

        Every train additionally appends its per-stage (rows, cols,
        dtype, backend, stage-kind, wall) observations to the shared cost
        history (``benchmarks/cost_history.json``; ``TMOG_COST_HISTORY``
        redirects or disables) — the learned cost model's training data.
        """
        from ..obs.trace import begin_span, end_span
        from ..utils.profiling import (OpStep, mark_run_start,
                                       with_job_group)

        retain_mb = None
        if (tuner is not None and getattr(tuner, "auto_plan", False)
                and chunk_rows is None and self.reader is not None):
            advice = self._plan_advice(tuner)
            if advice is not None and advice.mode == "stream":
                chunk_rows = advice.chunk_rows
                prefetch_chunks = advice.prefetch_chunks
                retain_mb = advice.retain_mb
        tuned_stages = self._apply_tuner(tuner)
        from ..distributed.runtime import current_pod

        if current_pod().declared and chunk_rows is None:
            raise ValueError(
                "pod trains run out-of-core only — pass chunk_rows=k "
                "(the pod protocol is built on host-sharded chunk "
                "streams and mergeable fit states; docs/distributed.md)")
        mark_run_start()    # RunCounters.first_launch_s counts from here
        root = begin_span("workflow.train", cat="workflow",
                          chunked=chunk_rows is not None,
                          chunk_rows=chunk_rows)
        try:
            if chunk_rows is not None:
                return self._train_chunked(
                    chunk_rows, prefetch_chunks, profile,
                    validate=validate, checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every_chunks,
                    retain_mb=retain_mb)
            if checkpoint_dir is not None:
                # in-core path: the checkpointable unit is the SELECTOR
                # SWEEP — route the directory to every ModelSelector stage
                # as a mid-sweep cursor (completed SweepUnits + halving
                # rung state, workflow/checkpoint.SweepCheckpointManager),
                # so an 8-chip sweep killed mid-flight resumes at its
                # cursor.  Without a selector there is nothing durable to
                # cut at, and the historical error stands.
                from ..selector.model_selector import ModelSelector

                dag = compute_dag(self.result_features)
                sels = [s for s in dag.all_stages()
                        if isinstance(s, ModelSelector)]
                if not sels:
                    raise ValueError(
                        "checkpoint_dir requires the out-of-core path — "
                        "pass chunk_rows=k as well (the in-core fit only "
                        "checkpoints ModelSelector sweeps, and this DAG "
                        "has none)")
                prev = [(s, s.sweep_checkpoint_dir) for s in sels]
                for s in sels:
                    s.sweep_checkpoint_dir = checkpoint_dir
                try:
                    return self._train_in_core(profile, validate=validate)
                finally:
                    for s, d in prev:
                        s.sweep_checkpoint_dir = d
            return self._train_in_core(profile, validate=validate)
        finally:
            end_span(root)
            for s, prev_strategy, prev_halving in tuned_stages:
                s.strategy = prev_strategy
                s.halving = prev_halving

    def _plan_advice(self, tuner):
        """Cost-planner advice for an auto_plan train, or None when the
        reader cannot estimate its rows (nothing to decide from)."""
        rows = self.reader.estimate_rows()
        if not rows:
            return None
        from ..tuning.planner import advise_plan

        cols = max(len(self.raw_features()), 1)
        return advise_plan(rows, cols,
                           cost_model=tuner.resolved_cost_model(),
                           host_budget_bytes=tuner.host_budget_bytes)

    def _apply_tuner(self, tuner):
        """Set the tuner's sweep strategy on every ModelSelector stage for
        this train; returns (stage, previous strategy, previous halving)
        records for the caller's restore."""
        if tuner is None:
            return []
        from ..selector.model_selector import ModelSelector

        dag = compute_dag(self.result_features)
        tuned = []
        for s in dag.all_stages():
            if isinstance(s, ModelSelector):
                tuned.append((s, s.strategy, s.halving))
                s.strategy = tuner.strategy
                if tuner.halving is not None:
                    s.halving = tuner.halving
        return tuned

    def _train_in_core(self, profile: bool,
                       validate: bool = True) -> "OpWorkflowModel":
        from ..utils.profiling import OpStep, with_job_group

        with with_job_group(OpStep.DataReadingAndFiltering):
            data = self.generate_raw_data()
            filter_results = None
            if self._raw_feature_filter is not None:
                prev_mesh = self._raw_feature_filter.mesh
                if self.mesh is not None:
                    # numeric distribution passes run row-sharded (psum) —
                    # the executor-distributed profile of the reference
                    self._raw_feature_filter.with_mesh(self.mesh)
                try:
                    data, filter_results = (
                        self._raw_feature_filter.filter_raw_data(
                            data, self.raw_features()))
                finally:
                    self._raw_feature_filter.with_mesh(prev_mesh)
                self._apply_blocklist(filter_results.dropped_features)
        dag = compute_dag(self.result_features)
        self._validate_stages(dag)
        lint_snap = self._lint_dag(dag) if validate else None
        self._inject_params(dag)
        # hand the mesh to every mesh-capable stage for THIS train only —
        # stages are user-owned objects shared across workflows, so the
        # previous mesh (usually None) is restored afterwards
        meshed_stages = []
        if self.mesh is not None:
            for s in dag.all_stages():
                if hasattr(s, "with_mesh"):
                    meshed_stages.append((s, getattr(s, "mesh", None)))
                    s.with_mesh(self.mesh)
        try:
            model = self._train_inner(data, dag, filter_results,
                                      profile=profile)
        finally:
            for s, prev in meshed_stages:
                s.with_mesh(prev)
        model.lint_snapshot = lint_snap
        if model.train_profile is not None:
            model.train_profile.lint = lint_snap
        return model

    def _lint_dag(self, dag: StagesDAG):
        """The train(validate=True) gate: static DAG lint; errors raise
        PipelineLintError before any data moves, warnings come back as a
        LintSnapshot (with the lint's wall time, so the always-on cost
        stays auditable next to train wall)."""
        import time

        from ..analysis.diagnostics import PipelineLintError
        from ..analysis.linter import lint_dag
        from ..utils.profiling import LintSnapshot

        t0 = time.perf_counter()
        findings = lint_dag(dag, result_features=self.result_features,
                            reader=self.reader)
        wall = time.perf_counter() - t0
        if findings.errors:
            raise PipelineLintError(findings)
        return LintSnapshot.from_findings(findings, wall)

    def _train_chunked(self, chunk_rows: int, prefetch: int,
                       profile: bool,
                       validate: bool = True,
                       checkpoint_dir: Optional[str] = None,
                       checkpoint_every: int = 16,
                       retain_mb: Optional[float] = None
                       ) -> "OpWorkflowModel":
        """The out-of-core train: chunked ingestion + streaming two-pass
        fit + in-core tail (see workflow/streaming.py).

        RawFeatureFilter composes: its distribution pass runs CHUNKED
        over the train reader (and the scoring reader, when given) as a
        mergeable-monoid profile (filters/raw_feature_filter.py
        ``filter_streaming``) before the fit passes — drop decisions are
        identical to the in-core pass, dropped features never parse
        again, and dropped map keys are cleaned per chunk.

        Workflow-level CV composes: during-DAG estimators accumulate
        fold-tagged mergeable states (one per fold, assigned per global
        row id) and the fold validation runs on merged complement states
        between prefix and tail (workflow/streaming_cv.py) — every
        during-DAG estimator must support streaming fit.
        """
        import os as _os

        from ..utils.profiling import OpStep, PlanProfiler, with_job_group
        from .streaming import fit_dag_streaming

        if self.reader is None:
            raise RuntimeError("no reader set — call set_reader/set_input_data")

        rcfg = getattr(self.reader, "resilience", None)
        sink = (rcfg.sink() if (rcfg is not None and rcfg.quarantines)
                else None)
        q0 = (sink.count, sink.rows) if sink is not None else (0, 0)

        # -- pod context: this process is ONE MEMBER of a multi-process
        #    train (distributed/podstream.py) — host-sharded ingest,
        #    state merges at pass boundaries, coordinator-only durables
        from ..distributed.runtime import current_pod

        pod = current_pod()
        pod_ctx = None
        if pod.declared:
            from ..distributed.podstream import PodStreamContext

            pod_ctx = PodStreamContext(pod, self.reader,
                                       self.raw_features(), chunk_rows)

        # -- RawFeatureFilter: chunked distribution pass + per-chunk clean
        filter_results = None
        rff_stats = None
        chunk_filter = None
        if self._raw_feature_filter is not None:
            with with_job_group(OpStep.DataReadingAndFiltering):
                # pod_ctx mirrors pod.active — uniform across the pod
                if pod_ctx is not None:  # tmog: disable=TM071
                    # each process profiles its own host ranges; the
                    # monoid accumulators allgather-merge inside, so
                    # every process makes identical drop decisions
                    filter_results, rff_stats = (
                        self._raw_feature_filter.filter_streaming(
                            pod_ctx.local_reader(), self.raw_features(),
                            chunk_rows, pod=pod))
                else:
                    filter_results, rff_stats = (
                        self._raw_feature_filter.filter_streaming(
                            self.reader, self.raw_features(), chunk_rows))
            self._apply_blocklist(filter_results.dropped_features)
            chunk_filter = self._rff_chunk_filter(filter_results)

        dag = compute_dag(self.result_features)
        self._validate_stages(dag)
        lint_snap = self._lint_dag(dag) if validate else None
        self._inject_params(dag)

        cv_ctx = self._streaming_cv_context(dag)
        fingerprint_extra = (cv_ctx.fingerprint()
                             if cv_ctx is not None else None)

        # chunked trains checkpoint at TWO granularities under one
        # directory: the streaming manager owns the prefix passes, and
        # every ModelSelector in the (in-core) tail gets a mid-sweep
        # cursor under <dir>/sweep — a SIGKILL anywhere resumes at the
        # finest durable point
        sel_prev = []
        if checkpoint_dir is not None:
            from ..selector.model_selector import ModelSelector

            for s in dag.all_stages():
                if (isinstance(s, ModelSelector)
                        and s.sweep_checkpoint_dir is None):
                    sel_prev.append((s, s.sweep_checkpoint_dir))
                    s.sweep_checkpoint_dir = _os.path.join(
                        checkpoint_dir, "sweep")
        meshed_stages = []
        shard_cols = None
        if self.mesh is not None:
            for s in dag.all_stages():
                if hasattr(s, "with_mesh"):
                    meshed_stages.append((s, getattr(s, "mesh", None)))
                    s.with_mesh(self.mesh)
            from ..parallel.mesh import has_grid_axis

            if pod_ctx is not None:
                pass  # pod trains gather on host; no device hand-off yet
            elif has_grid_axis(self.mesh):
                # streaming→sharded hand-off: each ModelSelector's packed
                # feature matrix streams straight into per-shard device
                # buffers (parallel/ingest.py) — the (N, D) matrix never
                # materializes on one host before the sharded sweep
                from ..selector.model_selector import ModelSelector

                shard_cols = {s.features_feature.name
                              for s in dag.all_stages()
                              if isinstance(s, ModelSelector)}
        # a profiler always runs (its per-stage timings feed the learned
        # cost model's history); it lands on the model only when asked for
        profiler = PlanProfiler()
        try:
            with with_job_group(OpStep.FeatureEngineering):
                fitted, transformed, ingest, fit_states = fit_dag_streaming(
                    dag, self.reader, self.raw_features(), chunk_rows,
                    keep=self._train_keep_columns(),
                    fitted_substitutes=dict(self._model_stages),
                    profiler=profiler, prefetch=prefetch,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                    retain_mb=retain_mb,
                    shard_onto=None if pod_ctx is not None else self.mesh,
                    shard_columns=shard_cols,
                    fingerprint_extra=fingerprint_extra,
                    cv_ctx=cv_ctx, chunk_filter=chunk_filter,
                    pod_ctx=pod_ctx)
        finally:
            for s, prev in meshed_stages:
                s.with_mesh(prev)
            for s, prev in sel_prev:
                s.sweep_checkpoint_dir = prev
        model = OpWorkflowModel(
            result_features=self.result_features,
            stages=fitted,
            train_data=transformed,
        )
        model.reader = self.reader
        model.raw_feature_filter_results = filter_results
        model.train_profile = profiler if profile else None
        model.ingest_profile = ingest
        ingest.rff = rff_stats
        if sink is not None:
            # totals over EVERY pass of this train, the RFF distribution
            # pass included — the sidecar dedupes on (source, location),
            # so a row hit by all three passes still counts once
            ingest.quarantined_records = sink.count - q0[0]
            ingest.quarantined_rows = sink.rows - q0[1]
        model.fit_states = fit_states
        model.lint_snapshot = lint_snap
        profiler.lint = lint_snap
        from ..models.trees import clear_sweep_caches
        clear_sweep_caches()
        from ..tuning.costmodel import record_train_observations
        record_train_observations(profiler)
        return model

    def _rff_chunk_filter(self, filter_results):
        """Per-chunk cleaner applying the filter's already-made drop
        decisions (map-key removal; dropped features never parse again
        because the blocklist pruned them out of the raw feature set)."""
        if not filter_results.dropped_map_keys:
            return None
        rff = self._raw_feature_filter
        dropped = list(filter_results.dropped_features)
        keys = dict(filter_results.dropped_map_keys)
        return lambda ds: rff.clean_chunk(ds, dropped, keys)

    def _streaming_cv_context(self, dag: StagesDAG):
        """The fold-tagged CV context for a chunked train/refresh, or
        None when workflow CV is off (or the DAG has no CV cut).  Raises
        a precise error naming the offending stage when a during-DAG
        estimator cannot stream — the one genuinely unsupported
        combination left."""
        if not self._workflow_cv:
            return None
        from .streaming_cv import StreamingCVContext

        cut = cut_dag_cv(dag)
        if cut.selector is None or not cut.during.layers:
            return None
        for s in cut.during.all_stages():
            if (isinstance(s, Estimator) and s.uid not in self._model_stages
                    and not s.supports_streaming_fit):
                raise ValueError(
                    f"chunk_rows with workflow-level CV requires every "
                    f"fold-refit (during-DAG) estimator to support "
                    f"streaming fit; stage {s.uid} "
                    f"({type(s).__name__}) does not — fit it in-core or "
                    f"make its state a mergeable monoid "
                    f"(stages/base.py streaming-fit protocol)")
        return StreamingCVContext(cut.selector, cut.during,
                                  dict(self._model_stages))

    def refresh(self, model: "OpWorkflowModel", data=None,
                chunk_rows: int = 512, prefetch_chunks: int = 2,
                profile: bool = False,
                checkpoint_dir: Optional[str] = None,
                checkpoint_every_chunks: int = 16) -> "OpWorkflowModel":
        """Warm-start refresh: partial_fit ``model`` from NEW data only.

        Every ``supports_streaming_fit`` estimator whose exported fit
        state rides on ``model`` (``fit_states`` — chunked trains and
        refreshes record them) resumes from that state and merges the
        new chunks via the streaming-fit protocol, so the result matches
        a full streaming retrain over old+new within each stage's
        declared ``streaming_fit_tol`` (contract TM027) while reading
        only the refresh window.  Estimators without a state — or whose
        upstream feature GEOMETRY changed (vocab rotation, keep-decision
        flip; see workflow/refresh.py) — refit from the new data alone,
        and non-streamable tails refit in-core on the materialized
        window; the returned model's ``refresh_report`` says which path
        each estimator took.

        ``data`` defaults to this workflow's reader (point either at the
        new window).  ``checkpoint_dir`` reuses the streaming checkpoint
        manager with a refresh-scoped fingerprint: a SIGKILLed refresh
        resumes mid-pass, and a refresh checkpoint can never resume into
        a plain train or a refresh of a different base model.

        The refreshed model carries freshly merged ``fit_states`` —
        refreshes chain.  Deployment belongs behind the guarded swap
        (``serving.GuardedSwap``): a refresh is a CANDIDATE, not a
        rollout.
        """
        from ..obs.flight import record_event
        from ..obs.trace import begin_span, end_span
        from ..utils.profiling import OpStep, PlanProfiler, with_job_group
        from .refresh import RefreshContext
        from .streaming import fit_dag_streaming

        if data is not None:
            self.set_input_data(data)
        if self.reader is None:
            raise RuntimeError(
                "no refresh data — pass data= or set a reader")
        from ..distributed.runtime import current_pod

        if current_pod().declared:
            raise ValueError(
                "warm-start refresh does not yet compose with the pod "
                "runtime — run the refresh single-process "
                "(docs/distributed.md)")
        # RawFeatureFilter composes by REUSING the base model's recorded
        # drop decisions (re-profiling mid-refresh could change the DAG
        # geometry under the warm-started states — never silently);
        # workflow CV composes via the same fold-tagged context as a
        # chunked train (the re-selection runs on the refresh window).
        filter_results = None
        chunk_filter = None
        if self._raw_feature_filter is not None:
            filter_results = getattr(model, "raw_feature_filter_results",
                                     None)
            if filter_results is None:
                raise ValueError(
                    "refresh with RawFeatureFilter requires the base "
                    "model's recorded filter results "
                    "(model.raw_feature_filter_results — train with the "
                    "filter first); re-profiling inside a refresh would "
                    "change the feature geometry under the warm-started "
                    "states")
            self._apply_blocklist(filter_results.dropped_features)
            chunk_filter = self._rff_chunk_filter(filter_results)
        dag = compute_dag(self.result_features)
        self._validate_stages(dag)
        lint_snap = self._lint_dag(dag)
        self._inject_params(dag)
        cv_ctx = self._streaming_cv_context(dag)
        ctx = RefreshContext(model, dag)
        fingerprint_extra = ctx.base_digest()
        if cv_ctx is not None:
            fingerprint_extra = {**fingerprint_extra,
                                 **cv_ctx.fingerprint()}
        profiler = PlanProfiler()
        root = begin_span("workflow.refresh", cat="workflow",
                          chunk_rows=chunk_rows)
        record_event("refresh.start", chunk_rows=chunk_rows)
        try:
            with with_job_group(OpStep.FeatureEngineering):
                fitted, transformed, ingest, fit_states = fit_dag_streaming(
                    dag, self.reader, self.raw_features(), chunk_rows,
                    keep=self._train_keep_columns(),
                    profiler=profiler, prefetch=prefetch_chunks,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every_chunks,
                    refresh_ctx=ctx, fingerprint_extra=fingerprint_extra,
                    cv_ctx=cv_ctx, chunk_filter=chunk_filter)
        finally:
            end_span(root)
        refreshed = OpWorkflowModel(
            result_features=self.result_features,
            stages=fitted,
            train_data=transformed,
        )
        refreshed.reader = self.reader
        refreshed.raw_feature_filter_results = filter_results
        refreshed.train_profile = profiler if profile else None
        refreshed.ingest_profile = ingest
        refreshed.fit_states = fit_states
        refreshed.refresh_report = ctx.report.to_json()
        refreshed.lint_snapshot = lint_snap
        from ..models.trees import clear_sweep_caches
        clear_sweep_caches()
        from ..tuning.costmodel import record_train_observations
        record_train_observations(profiler)
        return refreshed

    def _train_inner(self, data, dag, filter_results,
                     profile: bool = False) -> "OpWorkflowModel":
        from ..utils.profiling import OpStep, PlanProfiler, with_job_group

        # a profiler always runs (the per-stage wall/rows/cols/dtype
        # records feed the learned cost model's shared history,
        # tuning/costmodel.py); it lands on the model only when asked for
        profiler = PlanProfiler()
        substitutes = dict(self._model_stages)
        if self._workflow_cv:
            # OpWorkflow.fitStages CV path (OpWorkflow.scala:403-453):
            # fit the leakage-free prefix once, run fold-refitting validation
            # to pick the winner, then fit the full DAG (the selector skips
            # validation because its best_estimator is already set).
            cut = cut_dag_cv(dag)
            if cut.selector is not None and cut.during.layers:
                with with_job_group(OpStep.CrossValidation):
                    # no keep-set here: before_data must retain every column
                    # the during-DAG and selector read downstream
                    before_fitted, before_data, _ = fit_and_transform_dag(
                        cut.before, data, fitted_substitutes=substitutes)
                    cut.selector.find_best_estimator(before_data, cut.during)
                    substitutes.update(
                        {m.uid: m for m in before_fitted
                         if isinstance(m, Model)})
        with with_job_group(OpStep.FeatureEngineering):
            fitted, transformed, _ = fit_and_transform_dag(
                dag, data, fitted_substitutes=substitutes,
                keep=self._train_keep_columns(), profiler=profiler)
        model = OpWorkflowModel(
            result_features=self.result_features,
            stages=fitted,
            train_data=transformed,
        )
        model.reader = self.reader
        model.raw_feature_filter_results = filter_results
        model.train_profile = profiler if profile else None
        # drop the sweep's upload/binning memos: their device buffers are
        # only useful within one train and holding them pressures HBM on
        # subsequent trains (measured a 6x slowdown at 1M rows)
        from ..models.trees import clear_sweep_caches
        clear_sweep_caches()
        from ..tuning.costmodel import record_train_observations
        record_train_observations(profiler)
        return model

    def _validate_stages(self, dag: StagesDAG) -> None:
        """Distinct-uid + serializability checks (the reference fails fast
        at train time too — OpWorkflow.checkSerializable,
        OpWorkflow.scala:280-338)."""
        seen = set()
        for s in dag.all_stages():
            if s.uid in seen:
                raise ValueError(f"duplicate stage uid {s.uid}")
            seen.add(s.uid)
        if not self._allow_non_serializable:
            from .persistence import check_serializable

            check_serializable(dag.all_stages())

    def compute_data_up_to(self, feature: Feature,
                           data=None) -> ColumnarDataset:
        """Materialize features up to (and including) ``feature``
        (OpWorkflow.computeDataUpTo :491).  Estimators above are fit."""
        if data is not None:
            self.set_input_data(data)
        raw = self.generate_raw_data()
        dag = compute_dag([feature])
        _, transformed, _ = fit_and_transform_dag(dag, raw)
        return transformed

    def load_model(self, path: str) -> "OpWorkflowModel":
        from .persistence import load_workflow_model

        return load_workflow_model(path)


class OpWorkflowModel(_WorkflowCore):
    def __init__(self, result_features: Sequence[Feature],
                 stages: Sequence[PipelineStage],
                 train_data: Optional[ColumnarDataset] = None):
        super().__init__()
        self.result_features = list(result_features)
        self.stages = list(stages)
        self.train_data = train_data
        self.raw_feature_filter_results = None
        #: PlanProfiler from ``OpWorkflow.train(profile=True)`` else None
        self.train_profile = None
        #: IngestProfiler from ``OpWorkflow.train(chunk_rows=k)`` else None
        self.ingest_profile = None
        #: LintSnapshot from ``OpWorkflow.train(validate=True)`` else None
        self.lint_snapshot = None
        #: exported streaming fit states by estimator uid (the warm-start
        #: capital ``OpWorkflow.refresh`` resumes from) — populated by
        #: chunked trains and refreshes, persisted with the model
        self.fit_states: Optional[Dict[str, Any]] = None
        #: RefreshReport JSON when this model came from a refresh
        self.refresh_report: Optional[Dict[str, Any]] = None
        self._scoring_dag_memo: Optional[StagesDAG] = None

    def _scoring_dag(self) -> StagesDAG:
        # rebuild feature DAG over fitted stages (copyWithNewStages parity);
        # memoized: the stage list is fixed after construction, and callers
        # (score_function per call site, save, serving-registry hot-swaps)
        # would otherwise redo DAG construction per call
        if self._scoring_dag_memo is None:
            stage_map = {s.uid: s for s in self.stages}
            feats = [f.copy_with_new_stages(stage_map)
                     for f in self.result_features]
            self._scoring_dag_memo = compute_dag(feats)
        return self._scoring_dag_memo

    def invalidate_scoring_dag(self) -> None:
        """Drop the memoized scoring DAG (only needed if ``stages`` is
        mutated in place after construction)."""
        self._scoring_dag_memo = None

    def score(self, data=None,
              keep_raw_features: bool = False,
              keep_intermediate_features: bool = False) -> ColumnarDataset:
        """Batched scoring over the fitted transformer DAG
        (OpWorkflowModel.score :259 / applyTransformationsDAG)."""
        if data is not None:
            self.set_input_data(data)
        raw = self.generate_raw_data()
        # the memoized per-DAG execution plan prunes intermediates as soon
        # as their last consumer stage has run (transform() is COW — raw is
        # never mutated, so no defensive copy needed)
        plan_keep = None
        if not keep_intermediate_features:
            plan_keep = {f.name for f in self.result_features}
            plan_keep |= {f.name for f in self.raw_features()
                          if f.is_response}
            if keep_raw_features:
                plan_keep |= {f.name for f in self.raw_features()}
        scored = transform_dag(self._scoring_dag(), raw,
                               keep=sorted(plan_keep)
                               if plan_keep is not None else None)
        if keep_raw_features and keep_intermediate_features:
            return scored
        keep = [f.name for f in self.result_features if f.name in scored]
        if keep_raw_features:
            keep = [f.name for f in self.raw_features()] + keep
        # always keep the response(s) for evaluation
        responses = [f.name for f in self.raw_features() if f.is_response]
        keep = responses + [k for k in keep if k not in responses]
        return scored.select([k for k in keep if k in scored])

    def evaluate(self, evaluator: OpEvaluatorBase, data=None,
                 scored: Optional[ColumnarDataset] = None) -> Dict[str, float]:
        if scored is None:
            scored = self.score(data)
        label, pred = self._eval_columns(scored)
        evaluator.label_col = evaluator.label_col or label
        evaluator.prediction_col = evaluator.prediction_col or pred
        return evaluator.evaluate(scored)

    def score_and_evaluate(self, evaluator: OpEvaluatorBase, data=None):
        scored = self.score(data)
        return scored, self.evaluate(evaluator, scored=scored)

    def _eval_columns(self, scored: ColumnarDataset):
        from ..types.feature_types import Prediction

        label = next((f.name for f in self.raw_features() if f.is_response), None)
        pred = next(
            (f.name for f in self.result_features
             if issubclass(f.ftype, Prediction) and f.name in scored), None)
        if pred is None:
            pred = next(
                (n for n in scored.names()
                 if issubclass(scored[n].ftype, Prediction)), None)
        return label, pred

    # -- introspection ------------------------------------------------------

    def get_fitted_stage(self, uid_or_name: str) -> PipelineStage:
        for s in self.stages:
            if s.uid == uid_or_name or type(s).__name__ == uid_or_name:
                return s
        raise KeyError(uid_or_name)

    def summary(self) -> Dict[str, Any]:
        """Merged stage metadata (OpWorkflowModel.summary :187)."""
        out: Dict[str, Any] = {}
        for s in self.stages:
            if s.metadata:
                out[s.uid] = _jsonable(s.metadata)
        return out

    def summary_json(self) -> str:
        return json.dumps(self.summary(), indent=2, default=str)

    def summary_pretty(self) -> str:
        """Human-readable training summary (summaryPretty :221)."""
        from ..selector.model_selector import ModelSelectorSummary

        lines: List[str] = []
        for s in self.stages:
            summ = s.metadata.get("model_selector_summary")
            if summ:
                lines.append("Evaluated models:")
                for row in summ.get("validationResults", [])[:20]:
                    lines.append(
                        f"  {row['modelType']} {row['params']} -> "
                        f"{row['metricName']}={row['metricValue']:.4f}")
                lines.append(
                    f"Best model: {summ.get('bestModelType')} "
                    f"{summ.get('bestModelParams')}")
                hold = summ.get("holdoutMetrics")
                if hold:
                    lines.append("Holdout metrics: " + json.dumps(hold))
            sc = s.metadata.get("summary")
            if sc and "dropped" in sc:
                lines.append(
                    f"SanityChecker dropped {len(sc['dropped'])} columns: "
                    f"{sc['dropped'][:10]}")
        return "\n".join(lines) if lines else "(no fitted summaries)"

    def model_insights(self, feature: Optional[Feature] = None):
        from ..insights.model_insights import extract_model_insights

        return extract_model_insights(self, feature)

    def save(self, path: str, overwrite: bool = True) -> None:
        from .persistence import save_workflow_model

        save_workflow_model(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "OpWorkflowModel":
        from .persistence import load_workflow_model

        return load_workflow_model(path)


def _jsonable(obj):
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        if isinstance(obj, dict):
            return {k: _jsonable(v) for k, v in obj.items()}
        if isinstance(obj, (list, tuple)):
            return [_jsonable(v) for v in obj]
        if isinstance(obj, np.ndarray):
            return obj.tolist()
        return str(obj)
