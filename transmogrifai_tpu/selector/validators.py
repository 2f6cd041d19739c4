"""Hyperparameter validators — cross-validation and train/validation split.

Reference: ``OpValidator`` (impl/tuning/OpValidator.scala:94,214,363),
``OpCrossValidation`` (OpCrossValidation.scala:87-148, stratified folds
:158-200), ``OpTrainValidationSplit``.

TPU redesign of the reference's folds×models JVM thread pool: every fold is a
0/1 *weight mask* over the single device-resident matrix (no per-fold copies),
so one XLA-compiled trainer program serves all folds × all hyperparameter
points; runs of same-family candidates additionally fit as ONE batched
program over the (folds, candidates) grid via ``selector.grid_groups``
(LR majorization grid, RF tree streams, GBT lockstep chains — SURVEY
§2.12 row 2), with transparent per-candidate fallback.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["ValidationResult", "OpCrossValidation", "OpTrainValidationSplit",
           "make_folds", "SweepUnit", "SweepWorkQueue"]


@dataclasses.dataclass
class ValidationResult:
    model_name: str
    params: Dict[str, Any]
    metric_name: str
    metric_value: float
    fold_values: List[float]
    #: fit/eval failure or budget-skip note; a failed candidate scores -inf
    #: instead of aborting the sweep (OpValidator.scala:94-214 isolates
    #: candidates in Futures bounded by maxWait)
    error: Optional[str] = None

    def to_json(self):
        out = {"modelType": self.model_name, "params": self.params,
               "metricName": self.metric_name,
               "metricValue": self.metric_value,
               "foldValues": self.fold_values}
        if self.error is not None:
            out["error"] = self.error
        return out

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "ValidationResult":
        """Inverse of ``to_json`` (sweep checkpoint resume)."""
        return ValidationResult(
            model_name=str(d.get("modelType", "")),
            params=dict(d.get("params", {})),
            metric_name=str(d.get("metricName", "")),
            metric_value=float(d.get("metricValue", float("-inf"))),
            fold_values=list(d.get("foldValues", [])),
            error=d.get("error"))


def make_folds(n: int, num_folds: int, y: Optional[np.ndarray] = None,
               stratify: bool = False, seed: int = 42) -> np.ndarray:
    """Fold id per row; stratified assignment keeps label ratios per fold
    (OpCrossValidation stratified folds :158-200)."""
    rng = np.random.default_rng(seed)
    fold = np.zeros(n, dtype=np.int32)
    if stratify and y is not None:
        for lbl in np.unique(y):
            idx = np.where(y == lbl)[0]
            perm = rng.permutation(len(idx))
            fold[idx[perm]] = np.arange(len(idx)) % num_folds
    else:
        perm = rng.permutation(n)
        fold[perm] = np.arange(n) % num_folds
    return fold


class _ValidatorBase:
    """fit_fn(X, y, w_train, params) -> predict_fn(X) -> scores;
    eval_fn(y, scores, w_eval) -> float metric."""

    larger_better: bool = True
    #: this validator's sweep runs through SweepWorkQueue and honors
    #: ``validate(..., defer=True)`` (raw deferred results instead of a
    #: collected ranking) — the halving scheduler checks this before
    #: deferring a rung's materialization to its on-device promotion
    supports_defer: bool = True

    def validate(
        self,
        candidates: Sequence[Tuple[str, Dict[str, Any],
                                   Callable[..., Callable]]],
        X: np.ndarray,
        y: np.ndarray,
        base_weights: np.ndarray,
        eval_fn: Callable[[np.ndarray, Any, np.ndarray], float],
        metric_name: str,
        larger_better: bool = True,
        checkpoint=None,
        elastic=None,
        defer: bool = False,
    ) -> Tuple[int, List[ValidationResult]]:
        raise NotImplementedError

    def validate_with_dag(
        self,
        candidates,
        data,
        during_dag,
        label_name: str,
        features_name: str,
        y: np.ndarray,
        base_weights: np.ndarray,
        eval_fn,
        metric_name: str,
        larger_better: bool = True,
    ) -> Tuple[int, List[ValidationResult]]:
        """Workflow-level CV (OpValidator.applyDAG OpValidator.scala:250):
        the feature-engineering ``during_dag`` is refit on every fold's train
        split and applied to its eval split, so label-aware estimators
        (SanityChecker, supervised bucketizers) cannot leak fold labels."""
        raise NotImplementedError

    def validate_prefold(
        self,
        candidates,
        per_fold: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray, np.ndarray]],
        eval_fn,
        metric_name: str,
        larger_better: bool = True,
        checkpoint=None,
        elastic=None,
        defer: bool = False,
    ) -> Tuple[int, List[ValidationResult]]:
        """Validate candidates over PRE-BUILT fold matrices — each context
        a ``(X_tr, y_tr, w_tr, X_ev, y_ev, w_ev)`` tuple.  The streaming
        workflow-CV path (workflow/streaming_cv.py) builds these from
        merged fold-tagged monoid states instead of refitting the during
        DAG per fold; the candidate fits and metric extraction are
        byte-for-byte the ``validate_with_dag`` bodies, and the sweep
        runs through the same work queue (mid-sweep checkpoint cursor +
        elastic device-loss ladder both compose)."""

        def run_fold(fitter, params, ctx):
            X_tr, y_tr, w_tr, X_ev, y_ev, w_ev = ctx
            predict = fitter(X_tr, y_tr, w_tr, params)
            return eval_fn(y_ev, predict(X_ev), w_ev)

        return _run_sweep(candidates, list(per_fold), run_fold, metric_name,
                          larger_better, getattr(self, "max_wait", None),
                          checkpoint=checkpoint, elastic=elastic, defer=defer)

    @staticmethod
    def _fold_matrices(data, during_dag, label_name, features_name,
                       tr_idx: np.ndarray, ev_idx: np.ndarray):
        """Refit during_dag on the fold's train rows, apply to eval rows,
        and extract the (X, y) matrices for both sides.

        The keep-set names exactly what this function reads afterwards, so
        the DAG's memoized ExecutionPlan (derived once, reused by every
        fold — plan_for caches on the dag object) liveness-prunes all other
        intermediates per fold, and the eval side rides the lazy
        plan-driven ``apply_to`` pass.  The per-fold row gather is also
        plan-bounded: only columns the during-DAG actually reads are
        ``take``-copied, instead of fancy-indexing every raw/intermediate
        column (object columns cost ~µs/row to gather) twice per fold."""
        from ..workflow.dag import (fit_and_transform_dag,
                                    sequential_executor_forced)
        from ..workflow.plan import plan_for

        if sequential_executor_forced():
            # pre-plan behavior: gather every column, refit sequentially
            train_ds = data.take(tr_idx)
            eval_ds = data.take(ev_idx)
            _, train_t, eval_t = fit_and_transform_dag(
                during_dag, train_ds, apply_to=eval_ds, sequential=True)
        else:
            keep = [features_name, label_name]
            plan = plan_for(during_dag, keep=keep)
            req = plan.required_input_columns()
            base = data.select([n for n in data.names() if n in req])
            train_ds = base.take(tr_idx)
            eval_ds = base.take(ev_idx)
            _, train_t, eval_t = fit_and_transform_dag(
                during_dag, train_ds, apply_to=eval_ds, keep=keep)
        X_tr = np.ascontiguousarray(
            np.asarray(train_t[features_name].values, dtype=np.float32))
        X_ev = np.ascontiguousarray(
            np.asarray(eval_t[features_name].values, dtype=np.float32))
        y_tr = np.nan_to_num(
            np.asarray(train_t[label_name].values, dtype=np.float32))
        y_ev = np.nan_to_num(
            np.asarray(eval_t[label_name].values, dtype=np.float32))
        return X_tr, y_tr, X_ev, y_ev


class OpCrossValidation(_ValidatorBase):
    def __init__(self, num_folds: int = 3, seed: int = 42,
                 stratify: bool = False, parallelism: int = 8,
                 max_wait: Optional[float] = None):
        self.num_folds = num_folds
        self.seed = seed
        self.stratify = stratify
        # parallelism is accepted for API parity; on TPU the folds×grid loop
        # runs as sequential launches of one cached compiled program (or
        # vmapped where the trainer supports it) — no thread pool needed.
        self.parallelism = parallelism
        # wall-clock sweep budget in seconds (reference maxWait,
        # OpValidator.scala:108): candidates not yet started when the budget
        # runs out are skipped with a recorded error instead of hanging the
        # train. None = unbounded.
        self.max_wait = max_wait

    def validate(self, candidates, X, y, base_weights, eval_fn, metric_name,
                 larger_better=True, checkpoint=None, elastic=None,
                 defer=False):
        n = X.shape[0]
        folds = make_folds(n, self.num_folds, y=y, stratify=self.stratify,
                           seed=self.seed)
        fold_ctxs = []
        for k in range(self.num_folds):
            w_train = base_weights * (folds != k)
            w_eval = base_weights * (folds == k)
            if w_train.sum() == 0 or w_eval.sum() == 0:
                continue
            fold_ctxs.append((w_train, w_eval))

        def run_fold(fitter, params, ctx):
            w_train, w_eval = ctx
            predict = fitter(X, y, w_train, params)
            return eval_fn(y, predict(X), w_eval)

        def run_group(group):
            return group.run(X, y, fold_ctxs)

        return _run_sweep(candidates, fold_ctxs, run_fold, metric_name,
                          larger_better, self.max_wait, run_group=run_group,
                          checkpoint=checkpoint, elastic=elastic, defer=defer)

    def validate_with_dag(self, candidates, data, during_dag, label_name,
                          features_name, y, base_weights, eval_fn,
                          metric_name, larger_better=True):
        n = len(y)
        folds = make_folds(n, self.num_folds, y=y, stratify=self.stratify,
                           seed=self.seed)
        # one DAG refit per fold, shared across every candidate (the
        # reference refits per fold too — OpCrossValidation.scala:87-148)
        per_fold = []
        for k in range(self.num_folds):
            tr_idx = np.where(folds != k)[0]
            ev_idx = np.where(folds == k)[0]
            if not len(tr_idx) or not len(ev_idx):
                continue
            X_tr, y_tr, X_ev, y_ev = self._fold_matrices(
                data, during_dag, label_name, features_name, tr_idx, ev_idx)
            w_tr = base_weights[tr_idx]
            w_ev = base_weights[ev_idx]
            if w_tr.sum() == 0 or w_ev.sum() == 0:
                continue
            per_fold.append((X_tr, y_tr, w_tr, X_ev, y_ev, w_ev))

        def run_fold(fitter, params, ctx):
            X_tr, y_tr, w_tr, X_ev, y_ev, w_ev = ctx
            predict = fitter(X_tr, y_tr, w_tr, params)
            return eval_fn(y_ev, predict(X_ev), w_ev)

        return _run_sweep(candidates, per_fold, run_fold, metric_name,
                          larger_better, self.max_wait)


class OpTrainValidationSplit(_ValidatorBase):
    def __init__(self, train_ratio: float = 0.75, seed: int = 42,
                 stratify: bool = False, parallelism: int = 8,
                 max_wait: Optional[float] = None):
        self.train_ratio = train_ratio
        self.seed = seed
        self.stratify = stratify
        self.parallelism = parallelism
        self.max_wait = max_wait

    def _split_mask(self, n: int, y: np.ndarray) -> np.ndarray:
        rng = np.random.default_rng(self.seed)
        if self.stratify:
            # per-class permutation keeps label ratios on both sides, so an
            # imbalanced eval slice can't end up without positives
            in_train = np.zeros(n, bool)
            for cls in np.unique(y[np.isfinite(y)]):
                idx = np.where(y == cls)[0]
                perm = rng.permutation(idx)
                in_train[perm[: max(1, int(round(
                    len(idx) * self.train_ratio)))]] = True
        else:
            in_train = rng.random(n) < self.train_ratio
        return in_train

    def validate(self, candidates, X, y, base_weights, eval_fn, metric_name,
                 larger_better=True, checkpoint=None, elastic=None,
                 defer=False):
        n = X.shape[0]
        in_train = self._split_mask(n, y)
        w_train = base_weights * in_train
        w_eval = base_weights * (~in_train)

        def run_fold(fitter, params, ctx):
            predict = fitter(X, y, w_train, params)
            return eval_fn(y, predict(X), w_eval)

        def run_group(group):
            return group.run(X, y, [(w_train, w_eval)])

        return _run_sweep(candidates, [None], run_fold, metric_name,
                          larger_better, self.max_wait, run_group=run_group,
                          checkpoint=checkpoint, elastic=elastic, defer=defer)

    def validate_with_dag(self, candidates, data, during_dag, label_name,
                          features_name, y, base_weights, eval_fn,
                          metric_name, larger_better=True):
        n = len(y)
        in_train = self._split_mask(n, y)
        tr_idx = np.where(in_train)[0]
        ev_idx = np.where(~in_train)[0]
        X_tr, y_tr, X_ev, y_ev = self._fold_matrices(
            data, during_dag, label_name, features_name, tr_idx, ev_idx)
        w_tr, w_ev = base_weights[tr_idx], base_weights[ev_idx]

        def run_fold(fitter, params, ctx):
            predict = fitter(X_tr, y_tr, w_tr, params)
            return eval_fn(y_ev, predict(X_ev), w_ev)

        return _run_sweep(candidates, [None], run_fold, metric_name,
                          larger_better, self.max_wait)


def _mesh_attr(elastic) -> str:
    """The mesh a sweep attempt runs on, as a span attribute ("" = single
    device / unknown) — read through the elastic context's live-mesh peek
    so shrink ladders show the mesh each RETRY actually landed on."""
    provider = getattr(elastic, "mesh_provider", None)
    if provider is None:
        return ""
    try:
        from ..utils.profiling import mesh_desc

        return mesh_desc(provider())[1]
    except Exception:
        return ""


@dataclasses.dataclass
class SweepUnit:
    """One schedulable unit of sweep work: a candidate's (folds x fit)
    execution.  ``fit_params`` lets a scheduler run the unit with
    different resources than the candidate's identity (successive-halving
    rung scaling, tuning/halving.py) — results always report ``params``.
    """

    index: int                   # position in the original candidate list
    name: str
    params: Dict[str, Any]
    fitter: Any
    group: Any = None            # shared GridGroup for batched device fits
    fit_params: Optional[Dict[str, Any]] = None

    @property
    def run_params(self) -> Dict[str, Any]:
        return self.fit_params if self.fit_params is not None else self.params


class SweepWorkQueue:
    """The selector sweep as an explicitly schedulable work queue.

    The candidates×folds loop used to be a closed ``while`` inside
    ``_run_sweep``; it is now a queue of :class:`SweepUnit` whose
    execution, failure isolation, ``max_wait`` budgeting and grid-group
    batching live HERE, while schedulers decide which units run — the
    default full sweep (``run_all``), successive halving
    (tuning/halving.py, which schedules rung-sized subsets through fresh
    queues), and the coming sharded-sweep scheduler (ROADMAP item 1) all
    drive the same unit semantics.

    Semantics (reference parity, OpValidator.scala:94-214): each unit's
    fits are isolated — an exception scores the unit worst and records the
    error; the wall-clock budget is checked before each dispatch (an
    already-dispatched XLA program cannot be interrupted, but the queue
    stops enqueuing); a run of consecutive units sharing a ``GridGroup``
    fits as ONE batched device program with transparent per-unit fallback.
    """

    def __init__(self, candidates, fold_ctxs, run_fold, run_group=None):
        self.units = [
            SweepUnit(i, c[0], c[1], c[2],
                      group=(c[3] if len(c) >= 4 else None),
                      fit_params=(c[4] if len(c) >= 5 else None))
            for i, c in enumerate(tuple(c) for c in candidates)]
        self.fold_ctxs = fold_ctxs
        self._run_fold = run_fold
        self._run_group = run_group

    # -- unit execution ------------------------------------------------------

    def _unit_attempt(self, unit: SweepUnit) -> List[Any]:
        """One execution attempt of a unit's (folds x fit) body.  The
        ``unit.slow`` / ``device.loss`` fault points fire here — once per
        ATTEMPT, keyed by the unit's queue index — so the elastic
        escalation ladder (retry on a shrunk mesh, then quarantine) is
        seed-deterministically testable."""
        from ..utils import faults

        faults.fire("unit.slow", index=unit.index, tag=unit.name)
        faults.fire("device.loss", index=unit.index, tag=unit.name)
        fold_vals: List[Any] = []
        for ctx in self.fold_ctxs:
            fold_vals.append(
                self._run_fold(unit.fitter, unit.run_params, ctx))
        return fold_vals

    def run_unit(self, unit: SweepUnit,
                 elastic=None) -> Tuple[List[Any], Optional[str]]:
        """One candidate across every fold context, failure-isolated.

        With an :class:`~transmogrifai_tpu.parallel.elastic.
        ElasticContext` attached, two degradation ladders wrap the
        attempt: classified DEVICE LOSSES re-run the unit (the context
        shrinks the owner's mesh between attempts, ultimately to the
        single-device path) within a bounded retry budget before
        quarantining the candidate as ``failed: device_loss``; and the
        opt-in STRAGGLER WATCHDOG bounds each attempt at the context's
        deadline (escalating timeout -> degraded re-run at 2x the
        deadline -> ``failed: straggler`` quarantine).  Workload failures
        keep the historical behavior: score worst, record the error."""
        from ..obs.trace import begin_span, end_span

        loss_attempt = 0
        slow_attempt = 0
        sp = begin_span(f"sweep.unit[{unit.index}]", cat="sweep",
                        candidate=unit.name, index=unit.index,
                        mesh=_mesh_attr(elastic))
        try:
            while True:
                try:
                    deadline = (elastic.unit_deadline_s
                                if elastic is not None else None)
                    if deadline is None:
                        return self._unit_attempt(unit), None
                    from ..parallel.elastic import run_with_deadline

                    fold_vals, timed_out = run_with_deadline(
                        lambda: self._unit_attempt(unit),
                        deadline * (2 ** slow_attempt),
                        abandoned=elastic.abandoned)
                    if not timed_out:
                        return fold_vals, None
                    if elastic.on_watchdog_timeout(unit.index,
                                                   slow_attempt):
                        slow_attempt += 1
                        continue   # degraded re-run on the shrunk mesh
                    return [], (f"failed: straggler (unit exceeded its "
                                f"{deadline:.3f}s watchdog deadline "
                                f"{slow_attempt + 1}x)")
                except Exception as e:  # noqa: BLE001 - candidate
                    # isolation, routed through the shared device-loss
                    # classifier
                    if elastic is not None and elastic.classify(e):
                        if elastic.on_device_loss(unit.index, e,
                                                  loss_attempt):
                            loss_attempt += 1
                            continue   # re-run on the shrunk mesh
                        return [], (f"failed: device_loss "
                                    f"({type(e).__name__}: {e})")
                    return [], f"{type(e).__name__}: {e}"
        finally:
            end_span(sp, retries=loss_attempt,
                     watchdog_retries=slow_attempt,
                     mesh_after=_mesh_attr(elastic))

    def group_span(self, i: int) -> int:
        """End index (exclusive) of the run of units sharing units[i]'s
        group."""
        group = self.units[i].group
        j = i
        while j < len(self.units) and self.units[j].group is group:
            j += 1
        return j

    def group_start(self, i: int) -> int:
        """Start index of the run of units sharing units[i]'s group — a
        checkpoint resume can enter a group MID-SPAN (earlier members
        restored from the cursor), and the group's metric-matrix rows are
        indexed from the group's first unit, not from the resume point."""
        group = self.units[i].group
        j = i
        while j > 0 and self.units[j - 1].group is group:
            j -= 1
        return j

    def run_group_block(self, i: int, j: int, elastic=None):
        """Batched fit for units[i:j] (one shared GridGroup): the group's
        (C_g, F) metric matrix, or None when the group declines/fails —
        in which case the units are stripped to the sequential path.  A
        failure the shared classifier recognizes as a DEVICE LOSS
        additionally shrinks the mesh (the stripped members then refit
        sequentially on the surviving devices)."""
        from ..obs.trace import span as _span

        group = self.units[i].group
        try:
            # the per-unit fault points fire for every member, so a fault
            # plan written against unit indices keeps working when those
            # units pack into ONE batched block (since PR 11 the tree
            # families batch too — a grouped sweep may run no
            # per-unit attempts at all)
            from ..utils import faults

            for k in range(i, j):
                faults.fire("device.loss", index=self.units[k].index,
                            tag=self.units[k].name)
            # the second span carries the estimator family in its NAME:
            # a reader that is handed names and intervals only can then
            # tell the XGB group from the RF group
            with _span(f"sweep.group[{i}:{j}]", cat="sweep",
                       group=type(group).__name__, units=j - i,
                       mesh=_mesh_attr(elastic)), \
                    _span(f"sweep.group:{type(group.proto).__name__}",
                          cat="sweep"):
                return self._run_group(group)
        except Exception as e:  # noqa: BLE001 - fall back per-candidate,
            # routed through the shared device-loss classifier
            if elastic is not None and elastic.classify(e):
                elastic.on_group_device_loss(e)
            import warnings
            warnings.warn(
                f"grid group {type(group).__name__} failed "
                f"({type(e).__name__}: {e}); falling back to "
                f"sequential candidate fits", RuntimeWarning)
            return None

    def strip_groups(self, i: int, j: int) -> None:
        for k in range(i, j):
            self.units[k].group = None

    # -- the default scheduler: full sweep in stable order -------------------

    def run_all(self, metric_name: str, larger_better: bool,
                max_wait: Optional[float], checkpoint=None, elastic=None,
                defer: bool = False
                ) -> Tuple[int, List[ValidationResult]]:
        """Every unit in stable order — the classic full sweep.

        The default scheduler is ASYNC (``_run_all_async``): group blocks
        and unit programs dispatch back-to-back with no device sync
        between them, checkpoint flushes lag one dispatch behind the
        queue head (the flushed block's drain overlaps the block just
        enqueued), and per-candidate metrics stay device-resident until
        one end-of-sweep fetch in ``collect``.  ``TMOG_SYNC_SWEEP=1``
        (read here, at sweep time) restores the historical synchronous
        loop ``_run_all_inner`` byte-identically.

        ``checkpoint`` (a workflow.checkpoint.SweepCheckpointManager view)
        enables the mid-sweep cursor: units whose fold metrics are already
        durable are restored instead of re-run, and each finished unit's
        metrics persist as the sweep advances — an 8-chip sweep killed
        mid-flight resumes at its cursor, ON WHATEVER MESH the resuming
        process has (restored records are host fold metrics; the
        remaining units were re-batched when this queue was built).
        On the sync path checkpointing materializes each unit's device
        metrics at completion; on the async path the flush is LAGGED one
        dispatch (booked as an overlapped wait, not a drain) — at most
        the final in-flight block's durability is lost to a kill, and a
        resume re-runs exactly that block.

        ``elastic`` (parallel.elastic.ElasticContext) arms device-loss
        retry/quarantine and the straggler watchdog — see ``run_unit``.

        ``defer=True`` (async only — the halving scheduler) returns the
        RAW ``(all_vals, errors)`` with device values still deferred,
        skipping ``collect``: the caller ranks on device and materializes
        once at end of sweep.

        Raises only when EVERY candidate failed — there is no model to
        select otherwise."""
        import time

        from ..obs.trace import begin_span, end_span
        from .async_dispatch import sync_sweep_forced

        if elastic is not None:
            elastic.checkpoint = checkpoint
        sync = sync_sweep_forced() and not defer
        sweep_span = begin_span(
            "sweep.run", cat="sweep", units=len(self.units),
            folds=len(self.fold_ctxs), mesh=_mesh_attr(elastic),
            mode=("sync" if sync else "async"))
        try:
            if sync:
                return self._run_all_inner(metric_name, larger_better,
                                           max_wait, checkpoint, elastic)
            return self._run_all_async(metric_name, larger_better,
                                       max_wait, checkpoint, elastic,
                                       defer=defer)
        finally:
            end_span(sweep_span,
                     elastic=(elastic.counters.to_json()
                              if elastic is not None else None))

    def _run_all_inner(self, metric_name: str, larger_better: bool,
                       max_wait: Optional[float], checkpoint=None,
                       elastic=None
                       ) -> Tuple[int, List[ValidationResult]]:
        import time

        t0 = time.monotonic()
        all_vals: List[Any] = []
        errors: List[Optional[str]] = []
        i = 0
        while i < len(self.units):
            unit = self.units[i]
            if checkpoint is not None:
                rec = checkpoint.restore(unit.index)
                # a restored record must match THIS sweep's fold geometry
                # (the fingerprint pins candidates/validator, but a
                # hand-edited or truncated cursor could still desync);
                # mismatched records are re-run instead of misaligning
                # the metric means silently
                if rec is not None and (
                        rec[1] is not None
                        or len(rec[0]) == len(self.fold_ctxs)):
                    all_vals.append(rec[0])
                    errors.append(rec[1])
                    i += 1
                    continue
            elapsed = time.monotonic() - t0
            if max_wait is not None and elapsed > max_wait and all_vals:
                all_vals.append([])
                errors.append(
                    f"skipped: validation budget max_wait={max_wait}s "
                    f"exceeded after {elapsed:.1f}s")
                i += 1
                continue
            if unit.group is not None and self._run_group is not None:
                j = self.group_span(i)
                if elastic is not None and elastic.groups_invalid:
                    # a mesh shrink invalidated the remaining batched
                    # programs (compiled for the dead mesh): strip to
                    # sequential fits on the surviving devices
                    self.strip_groups(i, j)
                    continue
                # row offset into the group's (C_g, F) metric matrix: the
                # block may start mid-group after a checkpoint restore
                base = i - self.group_start(i)
                M = self.run_group_block(i, j, elastic=elastic)
                if M is not None:
                    if checkpoint is not None:
                        # the sync path's per-block durability sync — the
                        # async scheduler books the same flush lagged;
                        # this loop IS the kill-switch baseline
                        rows = _materialize(  # tmog: disable=TM042
                            [_GroupRow(M, base + r) for r in range(j - i)])
                        for r, vals in enumerate(rows):
                            all_vals.append(vals)
                            errors.append(None)
                            checkpoint.record_unit(self.units[i + r].index,
                                                   vals, None)
                        i = j
                        continue
                    for r in range(j - i):
                        # deferred row marker: fetched once per group
                        # matrix in _materialize (no per-row device
                        # slicing launches)
                        all_vals.append(_GroupRow(M, base + r))
                        errors.append(None)
                    i = j
                    continue
                # declined/failed: strip so members fit sequentially
                self.strip_groups(i, j)
                continue
            fold_vals, err = self.run_unit(unit, elastic=elastic)
            if checkpoint is not None:
                fold_vals = _materialize([fold_vals])[0]  # tmog: disable=TM042
                checkpoint.record_unit(unit.index, fold_vals, err)
            all_vals.append(fold_vals)
            errors.append(err)
            i += 1
        if elastic is not None:
            # watchdog-abandoned workers must not outlive the sweep (a
            # straggler finishing into interpreter teardown crashes XLA)
            elastic.drain()
        return self.collect(all_vals, errors, metric_name, larger_better)

    def _run_all_async(self, metric_name: str, larger_better: bool,
                       max_wait: Optional[float], checkpoint=None,
                       elastic=None, defer: bool = False):
        """The double-buffered scheduler: same unit semantics as
        ``_run_all_inner`` (restore cursor, budget skip, group batching
        with sequential fallback, elastic ladders), but NO device sync
        inside the dispatch loop.  Group metric matrices and per-fold
        device scalars accumulate as deferred values; a checkpointed
        sweep flushes the PREVIOUS block's records right after the next
        block is enqueued, so the flush's ``block_until_ready`` overlaps
        live device work (booked into ``overlapSecs``, tag
        ``sweep.checkpoint``) instead of stalling the accelerator.  The
        one genuine drain is the end-of-sweep fetch in ``collect``
        (``overlap_tail=True``: only the LAST deferred value's wait is a
        stall — everything fetched before it drains behind still-enqueued
        later blocks)."""
        import time

        from ..obs.trace import span as _span

        t0 = time.monotonic()
        all_vals: List[Any] = []
        errors: List[Optional[str]] = []
        #: queue positions (== unit positions) dispatched but not yet
        #: durable — the lagged checkpoint window, at most one block deep
        pending: List[int] = []

        def flush_pending(overlapped: bool) -> None:
            if checkpoint is None or not pending:
                return
            with _span("sweep.checkpoint.flush", cat="sweep",
                       units=len(pending), overlapped=overlapped):
                rows = _materialize([all_vals[p] for p in pending],
                                    tag="sweep.checkpoint",
                                    overlapped=overlapped)
                for p, vals in zip(pending, rows):
                    all_vals[p] = vals
                    checkpoint.record_unit(self.units[p].index, vals,
                                           errors[p])
            pending.clear()

        i = 0
        while i < len(self.units):
            unit = self.units[i]
            if checkpoint is not None:
                rec = checkpoint.restore(unit.index)
                # geometry check as in the sync loop: a restored record
                # must match THIS sweep's fold count or it re-runs
                if rec is not None and (
                        rec[1] is not None
                        or len(rec[0]) == len(self.fold_ctxs)):
                    all_vals.append(rec[0])
                    errors.append(rec[1])
                    i += 1
                    continue
            elapsed = time.monotonic() - t0
            if max_wait is not None and elapsed > max_wait and all_vals:
                all_vals.append([])
                errors.append(
                    f"skipped: validation budget max_wait={max_wait}s "
                    f"exceeded after {elapsed:.1f}s")
                i += 1
                continue
            if unit.group is not None and self._run_group is not None:
                j = self.group_span(i)
                if elastic is not None and elastic.groups_invalid:
                    self.strip_groups(i, j)
                    continue
                base = i - self.group_start(i)
                M = self.run_group_block(i, j, elastic=elastic)
                if M is not None:
                    block = []
                    for r in range(j - i):
                        block.append(len(all_vals))
                        all_vals.append(_GroupRow(M, base + r))
                        errors.append(None)
                    # this block is now ENQUEUED: the previous block's
                    # flush drains behind it (overlapped), then this
                    # block becomes the lagged window
                    flush_pending(overlapped=True)
                    pending.extend(block)
                    i = j
                    continue
                self.strip_groups(i, j)
                continue
            fold_vals, err = self.run_unit(unit, elastic=elastic)
            pos = len(all_vals)
            all_vals.append(fold_vals)
            errors.append(err)
            flush_pending(overlapped=True)
            pending.append(pos)
            i += 1
        # the final in-flight block: nothing is enqueued behind it, so
        # its flush is a genuine (booked) drain — the explicit durability
        # sync point.  On a pod the sync is barrier-fenced: the cursor
        # write is the coordinator's (TM047), and non-coordinators must
        # not run past the sweep's last durable write before it lands
        flush_pending(overlapped=False)
        if checkpoint is not None:
            sync = getattr(checkpoint, "sync_durability", None)
            if sync is not None:
                sync()
        if elastic is not None:
            elastic.drain()
        if defer:
            return all_vals, errors
        with _span("sweep.drain", cat="sweep", units=len(all_vals)):
            return self.collect(all_vals, errors, metric_name,
                                larger_better, overlap_tail=True)

    # -- result assembly -----------------------------------------------------

    def collect(self, all_vals, errors, metric_name: str,
                larger_better: bool, overlap_tail: bool = False
                ) -> Tuple[int, List[ValidationResult]]:
        # the losing sentinel depends on the metric direction: -inf only
        # loses when larger is better; minimize metrics (RMSE, LogLoss)
        # need +inf
        worst = float("-inf") if larger_better else float("inf")
        results: List[ValidationResult] = []
        host_vals = _materialize(
            all_vals, tag="sweep.final" if overlap_tail else None,
            overlap_tail=overlap_tail)
        for unit, fold_vals, err in zip(self.units, host_vals, errors):
            # mean over FINITE folds only: a single faulted fold (NaN from
            # the per-value _materialize fallback) should not zero out the
            # folds that did complete — the reference likewise averages
            # whichever fold Futures finished
            finite = [v for v in fold_vals if np.isfinite(v)]
            if fold_vals and not finite and err is None:
                err = "all fold metrics non-finite"
            mean = float(np.mean(finite)) if finite and err is None else worst
            results.append(ValidationResult(unit.name, unit.params,
                                            metric_name, mean,
                                            fold_vals, error=err))
        if all(r.error is not None for r in results):
            raise RuntimeError(
                "model selection failed: every candidate errored; "
                f"first error: {results[0].error}")
        best = _argbest([r.metric_value if r.error is None else worst
                         for r in results], larger_better)
        return best, results


def _run_sweep(candidates, fold_ctxs, run_fold, metric_name: str,
               larger_better: bool, max_wait: Optional[float],
               run_group=None, checkpoint=None, elastic=None,
               defer: bool = False
               ) -> Tuple[int, List[ValidationResult]]:
    """The full-sweep scheduler over the work queue (see SweepWorkQueue
    for the execution semantics — this wrapper is the historical entry
    point every validator calls).  ``defer=True`` skips ``collect`` and
    returns ``(queue, all_vals, errors)`` with device values deferred —
    the halving scheduler's on-device rung promotion consumes these."""
    queue = SweepWorkQueue(candidates, fold_ctxs, run_fold,
                           run_group=run_group)
    out = queue.run_all(metric_name, larger_better, max_wait,
                        checkpoint=checkpoint, elastic=elastic, defer=defer)
    if defer:
        all_vals, errors = out
        return queue, all_vals, errors
    return out


def _argbest(vals: List[float], larger_better: bool) -> int:
    arr = np.asarray(vals, np.float64)
    if not larger_better:
        arr = -arr
    arr = np.where(np.isnan(arr), -np.inf, arr)
    return int(np.argmax(arr))


class _GroupRow:
    """Deferred row of a grid group's (C, F) metric matrix — resolved in
    ``_materialize`` with one fetch per matrix."""

    __slots__ = ("matrix", "row")

    def __init__(self, matrix, row: int):
        self.matrix = matrix
        self.row = row


def _materialize(nested: List[Any], tag: Optional[str] = None,
                 overlapped: bool = False, overlap_tail: bool = False
                 ) -> List[List[float]]:
    """Fetch all fold metric values in ONE device transfer.

    ``eval_fn`` returns device scalars on the device-resident sweep path
    (ModelSelector._metric); every host sync stalls the dispatch queue, so
    the whole candidates×folds sweep is dispatched async and this single
    stacked fetch replaces per-fold ``float()`` calls.
    Grid-group rows (``_GroupRow``) resolve with one fetch per group matrix.

    Ledger attribution: ``tag`` names the call site in ``drain_tags``;
    ``overlapped=True`` books EVERY wait here as overlapped (the async
    scheduler's lagged checkpoint flush — later work is already enqueued
    behind these values); ``overlap_tail=True`` is the end-of-sweep mode:
    waits are overlapped while LATER deferred values still have enqueued
    programs draining behind them, and only the final wait (the last
    group matrix, or the stacked scalar fetch when there is one) is a
    genuine drain — the accelerator is busy until that last value lands."""
    # resolve group matrices first (one transfer each, NaN rows on failure);
    # fetch_timed books queue-drain separately from the byte transfer
    from ..utils.profiling import fetch_timed

    try:
        import jax
        has_scalar_tail = any(
            not isinstance(vals, _GroupRow)
            and any(isinstance(v, jax.Array) for v in vals)
            for vals in nested)
    except Exception:  # pragma: no cover
        has_scalar_tail = False
    mat_ids = []
    for v in nested:
        if isinstance(v, _GroupRow) and id(v.matrix) not in mat_ids:
            mat_ids.append(id(v.matrix))
    mats: dict = {}
    for v in nested:
        if isinstance(v, _GroupRow) and id(v.matrix) not in mats:
            # in tail mode a matrix wait overlaps the still-enqueued
            # fetches behind it; the LAST one (with no scalar fetch to
            # follow) is the sweep's terminal stall
            is_last = (id(v.matrix) == mat_ids[-1]) and not has_scalar_tail
            ovl = overlapped or (overlap_tail and not is_last)
            try:
                mats[id(v.matrix)] = fetch_timed(
                    v.matrix, np.float64, tag=tag, overlapped=ovl)
            except Exception as e:  # async device fault in the group program
                import warnings
                warnings.warn(
                    f"group metric fetch failed ({type(e).__name__}: "
                    f"{str(e)[:300]}); recording NaN rows", RuntimeWarning)
                mats[id(v.matrix)] = None
    if mats:
        resolved: List[Any] = []
        for v in nested:
            if not isinstance(v, _GroupRow):
                resolved.append(v)
            elif mats[id(v.matrix)] is None:
                resolved.append([float("nan")] * int(v.matrix.shape[1]))
            else:
                resolved.append([float(x) for x in mats[id(v.matrix)][v.row]])
        nested = resolved
    try:
        import jax
        import jax.numpy as jnp
        dev = [v for vals in nested for v in vals
               if isinstance(v, jax.Array)]
    except Exception:  # pragma: no cover
        dev = []
    if not dev:
        return [[float(v) for v in vals] for vals in nested]
    # jitted stack: un-jitted jnp.stack dispatches one expand_dims per
    # scalar; jitted it is ONE launch
    try:
        stacked = _stack_jit(*dev)
        fetched = fetch_timed(stacked, np.float64, tag=tag,
                              overlapped=overlapped)
        host = iter(fetched)
        return [[float(next(host)) if isinstance(v, jax.Array) else float(v)
                 for v in vals] for vals in nested]
    except Exception:
        # an async device error (e.g. a diverging candidate whose metric
        # program faults at execution time) poisons the stacked fetch;
        # fall back to per-value fetches so only the faulty values go NaN
        def fetch(v):
            try:
                return float(np.asarray(v)) if isinstance(v, jax.Array) \
                    else float(v)
            except Exception:
                return float("nan")
        return [[fetch(v) for v in vals] for vals in nested]


def _stack_jit(*xs):
    # module-level jit so the executable caches per arity (a fresh lambda
    # per call would re-trace and re-compile every validate)
    global _STACK_JIT
    if _STACK_JIT is None:
        import jax
        import jax.numpy as jnp
        _STACK_JIT = jax.jit(lambda *ys: jnp.stack(ys))
    return _STACK_JIT(*xs)


_STACK_JIT = None
