"""Grid-batched candidate groups — the sweep's concurrency axis.

The reference runs its (model, fold) fits on a JVM thread pool
(``OpCrossValidation.scala:113-138``); the TPU equivalent is batching: a run
of candidates from the same estimator family fits as ONE XLA program over a
(folds, candidates) grid of traced hyperparameters, and the per-fold
validation metrics come back as one (C, F) device array.  ``_run_sweep``
consumes groups transparently — a group that declines (returns None) or
raises falls back to the per-candidate fitter path, which keeps the
reference's per-candidate failure isolation.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["GridGroup", "LogRegGridGroup", "LinRegGridGroup",
           "SoftmaxGridGroup", "TreeGridGroup", "RFGridGroup",
           "GBTGridGroup", "make_grid_group"]


class GridGroup:
    """Base: one batched fit+score+metric program for C candidates.

    ``run(X, y, weight_ctxs)`` returns a device/host (C, F) metric matrix —
    row order matching the group's ``grid_points`` — or None to decline
    (callers then fit those candidates sequentially).

    With a ("data", "grid") sweep mesh attached (``with_mesh``), families
    that declare ``supports_mesh`` run the SAME batched program with rows
    sharded over the data axis and the candidate batch sharded over the
    grid axis (pjit/NamedSharding — GSPMD partitions the (F, C, N) solve
    and psums the per-shard Gram partials over ICI); families that don't
    decline, so their units fall back to sequential per-candidate fits
    whose estimators carry the mesh themselves.
    """

    #: whether this family's batched program partitions over a sweep mesh
    supports_mesh: bool = False

    def __init__(self, proto, grid_points: Sequence[Dict[str, Any]],
                 metric: str):
        self.proto = proto
        self.grid_points = list(grid_points)
        self.metric = metric
        self.mesh = None

    def with_mesh(self, mesh) -> "GridGroup":
        if mesh is not None:
            from ..parallel.mesh import has_grid_axis

            # fail at attach time, not three layers down in _place_sweep:
            # a (data, model) mesh here would shard candidate vectors over
            # feature lanes (the TM041 axis-confusion hazard at runtime)
            if not has_grid_axis(mesh):
                raise ValueError(
                    f"GridGroup needs a ('data', 'grid') sweep mesh; got "
                    f"axes {tuple(getattr(mesh, 'axis_names', ()))}")
        self.mesh = mesh
        return self

    def run(self, X: np.ndarray, y: np.ndarray,
            weight_ctxs: Sequence[Tuple[np.ndarray, np.ndarray]]):
        raise NotImplementedError

    def refit_model(self, row: int):
        """Fitted full-train model for candidate ``row``, or None.

        Groups that solve an appended full-train weight row alongside the
        folds hold every candidate's refit artifacts on device after
        ``run`` — the selector asks for the WINNER's here instead of paying
        a fresh sequential fit (the reference refits from scratch,
        ModelSelector.scala:145-209)."""
        return None

    # -- helpers -------------------------------------------------------------

    @staticmethod
    def _full_weights(weight_ctxs) -> np.ndarray:
        """Full-train weights from any one fold context: fold train + eval
        masks partition the selector's base weights, so w_tr + w_ev is the
        refit weighting for CV folds and TVS splits alike."""
        w_tr, w_ev = weight_ctxs[0]
        return (np.asarray(w_tr, np.float32)
                + np.asarray(w_ev, np.float32))

    def _param(self, params: Dict[str, Any], name: str):
        return params.get(name, getattr(self.proto, name))

    def _uniform(self, names: Sequence[str]) -> bool:
        """True when every candidate agrees on each of ``names`` (those
        params are static in the batched program)."""
        for n in names:
            vals = {self._param(p, n) for p in self.grid_points}
            if len(vals) > 1:
                return False
        return True

    @staticmethod
    def _stack_weights(weight_ctxs):
        W_tr = np.ascontiguousarray(
            np.stack([np.asarray(w, np.float32) for w, _ in weight_ctxs]))
        W_ev = np.ascontiguousarray(
            np.stack([np.asarray(w, np.float32) for _, w in weight_ctxs]))
        return W_tr, W_ev


class _LinearGridGroup(GridGroup):
    """Shared plumbing for the linear-family groups."""

    supports_mesh = True

    _batchable = ("reg_param", "elastic_net_param")
    _static = ("max_iter", "tol", "fit_intercept", "standardization")

    def _place_sweep(self, X, y_h: np.ndarray, W_solve: np.ndarray,
                     W_ev: np.ndarray, regs, alphas):
        """Device placement for the batched solve.

        Single chip (``mesh is None``): the memoized whole-array uploads.
        Sweep mesh: rows zero-pad to tile the data axis (pad rows carry
        zero weight in every fold row — inert through the weighted Grams,
        gradients and metrics, so results are invariant to pad amount),
        the matrix/fold-weights commit row-sharded, and the candidate
        vectors commit on the GRID axis (padded to tile it by repeating
        the last candidate) so GSPMD partitions the (F, C, N) solve over
        data x grid.  Returns ``(X_in, y_in, W_solve_in, W_ev_in, regs_in,
        alphas_in, strip)`` where ``strip`` trims candidate padding off an
        axis-1 candidate-batched array (None when no padding).
        """
        if self.mesh is None:
            from ..models.trees import _dev_f32
            return (_dev_f32(X), y_h, _dev_f32(W_solve, tag="W_tr"),
                    W_ev, regs, alphas, None)
        import jax
        import jax.numpy as jnp

        from ..models.trees import _dev_memo_sharded
        from ..parallel.mesh import (fold_weight_sharding, grid_sharding,
                                     pad_to_multiple, sweep_matrix_sharding)

        mesh = self.mesh
        ndata = mesh.shape[mesh.axis_names[0]]
        g = mesh.shape[mesh.axis_names[1]]
        if isinstance(X, jax.Array) and not isinstance(X, np.ndarray):
            # already committed row-sharded (the streaming→sharded ingest
            # hand-off); its rows are pre-padded to tile the data axis,
            # and the caller pre-padded y/weights to match
            X_dev = X
        else:
            Xp, _ = pad_to_multiple(np.asarray(X, np.float32), ndata,
                                    axis=0)
            X_dev = None
        yp, _ = pad_to_multiple(y_h, ndata)
        Wsp, _ = pad_to_multiple(np.ascontiguousarray(
            np.asarray(W_solve, np.float32)), ndata, axis=1)
        Wep, _ = pad_to_multiple(np.ascontiguousarray(
            np.asarray(W_ev, np.float32)), ndata, axis=1)
        C = int(regs.shape[0])
        c_pad = (-C) % g
        if c_pad:
            regs = jnp.concatenate([regs, jnp.repeat(regs[-1:], c_pad)])
            alphas = jnp.concatenate(
                [alphas, jnp.repeat(alphas[-1:], c_pad)])
        gs = grid_sharding(mesh)
        if X_dev is None:
            X_dev = _dev_memo_sharded(Xp, sweep_matrix_sharding(mesh),
                                      "sweep_X")
        Ws_dev = _dev_memo_sharded(Wsp, fold_weight_sharding(mesh),
                                   "sweep_Wtr")
        We_dev = _dev_memo_sharded(Wep, fold_weight_sharding(mesh),
                                   "sweep_Wev")
        strip = (lambda a: a[:, :C]) if c_pad else None
        return (X_dev, yp, Ws_dev, We_dev, jax.device_put(regs, gs),
                jax.device_put(alphas, gs), strip)

    def _regs_alphas(self):
        import jax.numpy as jnp

        regs = jnp.asarray([float(self._param(p, "reg_param"))
                            for p in self.grid_points], jnp.float32)
        alphas = jnp.asarray([float(self._param(p, "elastic_net_param"))
                              for p in self.grid_points], jnp.float32)
        return regs, alphas

    def _batchable_params(self) -> bool:
        allowed = set(self._batchable) | set(self._static)
        if any(set(p) - allowed for p in self.grid_points):
            return False
        return self._uniform(self._static)

    def _metric_rows(self, y, scores, W_ev, binary: bool):
        """(F, C, N) device scores + (F, N) eval weights -> (C, F) device
        metrics (weights broadcast over candidates, never replicated), or
        None when the metric lacks a device kernel."""
        import jax.numpy as jnp

        from ..evaluators.metrics import (binary_metric_grid,
                                          regression_metric_grid)

        fn = binary_metric_grid if binary else regression_metric_grid
        m = fn(y, scores, jnp.asarray(W_ev), self.metric)
        if m is None:
            return None
        return m.T


class LogRegGridGroup(_LinearGridGroup):
    """All binary-LR (fold x candidate) fits in one majorization program
    (``linear.fit_logreg_grid``)."""

    def run(self, X, y, weight_ctxs):
        if not self._batchable_params():
            return None
        if len(y) and np.nanmax(y) > 1:          # binary device path only
            return None
        from ..models.linear import fit_logreg_grid

        W_tr, W_ev = self._stack_weights(weight_ctxs)
        regs, alphas = self._regs_alphas()
        F = W_tr.shape[0]
        # appended full-train row: the winner's refit coefficients come out
        # of the SAME program (+1/F of the solve; saves the sequential
        # Newton refit over the full matrix)
        W_aug = np.ascontiguousarray(
            np.vstack([W_tr, self._full_weights(weight_ctxs)[None]]))
        max_iter = int(self._param(self.grid_points[0], "max_iter"))
        tol = float(self._param(self.grid_points[0], "tol"))
        X_in, y_in, W_in, W_ev_in, regs_in, alphas_in, strip = \
            self._place_sweep(X, np.nan_to_num(np.asarray(y, np.float32)),
                              W_aug, W_ev, regs, alphas)
        scores, _, coef, icpt = fit_logreg_grid(
            X_in, y_in, W_in, regs_in, alphas_in,
            # majorization steps are ~D^2/N cheaper than Newton steps;
            # give the solver a proportionally larger budget at a metric-
            # sufficient tolerance
            max_iter=max(150, 4 * max_iter), tol=max(tol, 1e-5),
            fit_intercept=bool(self._param(self.grid_points[0],
                                           "fit_intercept")),
            standardization=bool(self._param(self.grid_points[0],
                                             "standardization")))
        if strip is not None:
            scores, coef, icpt = strip(scores), strip(coef), strip(icpt)
        self._refit_coef, self._refit_icpt = coef[F], icpt[F]  # device (C, D)
        return self._metric_rows(y_in, scores[:F], W_ev_in, binary=True)

    def refit_model(self, row: int):
        if getattr(self, "_refit_coef", None) is None:
            return None
        from ..models.classification import LogisticRegressionModel

        return LogisticRegressionModel(
            coef=np.asarray(self._refit_coef[row]).tolist(),
            intercept=float(np.asarray(self._refit_icpt[row])))


class LinRegGridGroup(_LinearGridGroup):
    """All linear-regression (fold x candidate) fits in one Gram-sharing
    program (``linear.fit_linreg_grid``)."""

    def run(self, X, y, weight_ctxs):
        if not self._batchable_params():
            return None
        from ..models.linear import fit_linreg_grid

        W_tr, W_ev = self._stack_weights(weight_ctxs)
        regs, alphas = self._regs_alphas()
        F = W_tr.shape[0]
        W_aug = np.ascontiguousarray(
            np.vstack([W_tr, self._full_weights(weight_ctxs)[None]]))
        X_in, y_in, W_in, W_ev_in, regs_in, alphas_in, strip = \
            self._place_sweep(X, np.nan_to_num(np.asarray(y, np.float32)),
                              W_aug, W_ev, regs, alphas)
        preds, coef, icpt = fit_linreg_grid(
            X_in, y_in, W_in, regs_in, alphas_in,
            max_iter=int(self._param(self.grid_points[0], "max_iter")),
            tol=float(self._param(self.grid_points[0], "tol")),
            fit_intercept=bool(self._param(self.grid_points[0],
                                           "fit_intercept")),
            standardization=bool(self._param(self.grid_points[0],
                                             "standardization")))
        if strip is not None:
            preds, coef, icpt = strip(preds), strip(coef), strip(icpt)
        self._refit_coef, self._refit_icpt = coef[F], icpt[F]
        return self._metric_rows(y_in, preds[:F], W_ev_in, binary=False)

    def refit_model(self, row: int):
        if getattr(self, "_refit_coef", None) is None:
            return None
        from ..models.regression import LinearRegressionModel

        return LinearRegressionModel(
            coef=np.asarray(self._refit_coef[row]).tolist(),
            intercept=float(np.asarray(self._refit_icpt[row])))


class SoftmaxGridGroup(_LinearGridGroup):
    """All multiclass-LR (fold x candidate) fits in one Böhning-majorization
    program (``linear.fit_softmax_grid``); metrics via the argmax-label
    multiclass grid kernel."""

    #: decline above this many (F, C, K, N) logit elements — the solver
    #: holds ~3 such tensors transiently (16 GB HBM headroom)
    MAX_LOGIT_ELEMS = 2e8

    def __init__(self, proto, grid_points, metric, n_classes: int = 2):
        super().__init__(proto, grid_points, metric)
        self.n_classes = n_classes

    def run(self, X, y, weight_ctxs):
        if not self._batchable_params():
            return None
        n_classes = self.n_classes
        if len(y):
            n_classes = max(n_classes, int(np.nanmax(y)) + 1)
        F, C, n = len(weight_ctxs), len(self.grid_points), len(y)
        if F * C * n * n_classes > self.MAX_LOGIT_ELEMS:
            return None
        import jax.numpy as jnp

        from ..evaluators.metrics import multiclass_metric_grid
        from ..models.linear import fit_softmax_grid

        W_tr, W_ev = self._stack_weights(weight_ctxs)
        regs, alphas = self._regs_alphas()
        max_iter = int(self._param(self.grid_points[0], "max_iter"))
        tol = float(self._param(self.grid_points[0], "tol"))
        y_h = np.nan_to_num(np.asarray(y, np.float32))
        X_in, y_in, W_in, W_ev_in, regs_in, alphas_in, strip = \
            self._place_sweep(X, y_h, W_tr, W_ev, regs, alphas)
        yi = np.asarray(y_in).astype(np.int32)
        logits, _ = fit_softmax_grid(
            X_in, yi, n_classes, W_in, regs_in, alphas_in,
            max_iter=max(150, 4 * max_iter), tol=max(tol, 1e-5),
            fit_intercept=bool(self._param(self.grid_points[0],
                                           "fit_intercept")),
            standardization=bool(self._param(self.grid_points[0],
                                             "standardization")))
        if strip is not None:
            logits = strip(logits)
        preds = jnp.argmax(logits, axis=2)                 # (F, C, N)
        m = multiclass_metric_grid(yi, preds, jnp.asarray(W_ev_in),
                                   n_classes, self.metric)
        if m is None:
            return None
        return m.T


class TreeGridGroup(GridGroup):
    """Shared mesh plumbing for the TREE-family batched groups (RF tree
    streams, GBT lockstep chains): with a ("data", "grid") sweep mesh
    attached, the SAME batched programs run sharded — the binned int8
    matrix row-sharded ``P("data", None)``, per-candidate hyperparameter
    vectors (num_trees cap via bag masking, depth limit,
    min_child_weight, lambda, gate params) riding ``P("grid")`` with
    last-candidate padding stripped, and per-level histograms psum'd over
    the data axis (parallel/sharded.py ``grow_rf_grid_sharded`` /
    ``gbt_chain_rounds_sharded``).  Until PR 11 tree families declined
    the mesh and fell back to sequential mesh-sharded fits."""

    supports_mesh = True

    #: cost-model stage kind recorded per batched run (tuning/planner's
    #: ``advise_mesh`` and the straggler watchdog consult these)
    grid_stage_kind = ""

    def _mesh_axes(self):
        mesh = self.mesh
        return (int(mesh.shape[mesh.axis_names[0]]),
                int(mesh.shape[mesh.axis_names[1]]))

    def _record_grid_observation(self, wall_s: float, rows: int,
                                 cols: int) -> None:
        """Append a ``<family>:fit-grid`` stage observation to the shared
        cost history so ``advise_mesh`` / the watchdog learn measured
        tree-grid scaling.  Best-effort — telemetry must not break a
        sweep."""
        if not self.grid_stage_kind or wall_s <= 0:
            return
        try:
            import time

            from ..parallel.elastic import mesh_device_count
            from ..tuning.costmodel import (StageObservation,
                                            append_observations,
                                            default_history_path)
            from ..utils.profiling import backend_name

            mesh_shape = ""
            if self.mesh is not None:
                mesh_shape = ",".join(
                    f"{a}={int(self.mesh.shape[a])}"
                    for a in self.mesh.axis_names)
            append_observations(default_history_path(), [StageObservation(
                stage_kind=self.grid_stage_kind, rows=int(rows),
                cols=max(int(cols), 1), dtype="float32",
                backend=backend_name(), wall_s=float(wall_s),
                t=int(time.time()),
                n_devices=mesh_device_count(self.mesh),
                mesh_shape=mesh_shape)])
        except Exception:
            pass


class RFGridGroup(TreeGridGroup):
    """Every (candidate x fold) random-forest fit as ONE chunked tree
    stream (``gbdt_kernels.grow_rf_grid``): per-tree traced
    (min_info_gain, min_instances, depth_limit) + fold-weight selection,
    identical randomness to the sequential per-candidate fits.  Candidates
    that differ only in max_depth and min_info_gain share ONE grown base
    forest a fold (the deepest, under the lowest gate) and are read off it
    by truncation and pruning (``gbdt_kernels.prune_rf_grid``).  A fold's
    base forests are grown on the fold's own training rows, and a pair is
    scored and ranked on its own fold's validation rows, each compacted
    once a sweep (``_fold_train_rows``, ``_fold_eval_rows``), not on every
    row of the table; the bags are still drawn over the table's rows, so
    the trees are those the table's rows grow.  The winner's refit grows
    on the rows its full weights keep.  Covers
    binary, multiclass (one-hot targets, argmax scores against the
    multiclass metric grid) and regression sweeps.  On a sweep mesh the
    same pair stream runs sharded (``grow_rf_grid_sharded``) with
    PRE-GENERATED bags from the identical ``fold_in(seed, tree_id)``
    generator, so mesh and single-chip sweeps grow the same forests."""

    grid_stage_kind = "RandomForest:fit-grid"

    _batchable = ("max_depth", "min_info_gain", "min_instances_per_node")
    _static = ("num_trees", "max_bins", "subsample_rate",
               "feature_subset_strategy", "seed")

    def __init__(self, proto, grid_points, metric, n_classes: int = 2):
        super().__init__(proto, grid_points, metric)
        self.n_classes = n_classes

    def _batchable_params(self) -> bool:
        allowed = set(self._batchable) | set(self._static)
        if any(set(p) - allowed for p in self.grid_points):
            return False
        return self._uniform(self._static)

    def run(self, X, y, weight_ctxs):
        if not self._batchable_params():
            return None
        import time as _time

        import jax.numpy as jnp

        from ..evaluators.metrics import (_MULTI_GRID_METRICS,
                                          binary_metric_grid,
                                          multiclass_metric_grid,
                                          regression_metric_grid)
        from ..models.gbdt_kernels import (_fold_rows_jit, grow_rf_grid,
                                           prune_rf_grid)
        from ..models.trees import _dev_memo, _feature_subset_size
        from ..obs.trace import span as _span
        from ..utils.profiling import count_rf_grid

        cls = self.proto._classification
        n_classes = self.n_classes
        if cls and len(y):
            n_classes = max(n_classes, int(np.nanmax(y)) + 1)
        multiclass = cls and n_classes > 2
        # decline BEFORE growing anything when the observed label space and
        # the metric family disagree (e.g. problem_type='binary'/AuPR with a
        # stray label > 1) — the forest sweep is the dominant cost
        if multiclass and self.metric not in _MULTI_GRID_METRICS:
            return None
        if cls and not multiclass and self.metric not in ("AuPR", "AuROC"):
            return None

        proto = self.proto
        y = np.nan_to_num(np.asarray(y, np.float32))
        # the CANDIDATES' max_bins (uniform across the grid — _static), not
        # the proto's: a grid overriding max_bins must bin with the value it
        # grows with, or bins past n_bins silently vanish from histograms
        mb = int(self._param(self.grid_points[0], "max_bins"))
        # sparse-aware prep: same sketch/memo keys as the GBT group and
        # the selector's prefetch thread, so one host sketch serves the
        # whole sweep.  Weight-aware: zero-total-weight rows
        # (mesh padding, balancer drops) never move the bin edges (TM024)
        from ..models.trees import _prep_tree_inputs_weighted

        edges, binned = _prep_tree_inputs_weighted(
            X, mb, row_weight=self._full_weights(weight_ctxs))
        n, d = X.shape
        if cls:
            Y = np.eye(n_classes, dtype=np.float32)[y.astype(int)]
        else:
            Y = y[:, None].astype(np.float32)
        msub = _feature_subset_size(proto.feature_subset_strategy, d, cls)
        W_tr, W_ev = self._stack_weights(weight_ctxs)
        F = W_tr.shape[0]
        C = len(self.grid_points)
        T = int(self._param(self.grid_points[0], "num_trees"))
        ev_rows, W_ev_c = _fold_eval_rows(W_ev)
        # a fold's forests grow on the rows its train weights keep (on the
        # mesh, whose rows are sharded, on every row)
        tr_rows, W_tr_c = ((None, W_tr) if self.mesh is not None
                           else _fold_train_rows(W_tr))

        # Depth and gate sharing: candidates that differ ONLY in max_depth
        # and min_info_gain share bags/folds by construction (bags key on
        # tree id), and both are read off ONE grown tree exactly.  For
        # level-wise greedy growth a shallower candidate is the deeper tree
        # truncated at its depth (splits at level l never depend on deeper
        # levels); and min_info_gain is a pure gate (it takes no part in
        # choosing a node's split, and a node that fails it keeps its rows
        # in one child, which fails again), so a candidate of a higher gate
        # is the tree of the lower gate with the nodes that fail it cut.
        # Grow ONE base forest per distinct min_instances at that group's
        # max depth and min gate and read every other candidate off the
        # base trees' level values and gate ratios — the r3 default grid
        # (3 depths x 3 gates x 2 min_instances) grew 9x the trees this
        # needs.  The reference pays the full redundancy on its thread
        # pool (OpCrossValidation.scala:113-138).
        # clamp at 0: any non-positive requested depth IS a stump
        cand_depth = [max(0, int(self._param(p, "max_depth")))
                      for p in self.grid_points]
        cand_ig = [float(self._param(p, "min_info_gain"))
                   for p in self.grid_points]
        # depth <= 0 (stump) candidates get their OWN base, keyed by their
        # gate too: a stump needs no sharing (depth_limit=0 grows it
        # directly; ADVICE r4)
        cand_key = [(float(self._param(p, "min_instances_per_node")),)
                    if cand_depth[i] > 0 else
                    (float(self._param(p, "min_instances_per_node")),
                     cand_ig[i], cand_depth[i])
                    for i, p in enumerate(self.grid_points)]
        # keys lead with min_instances: consumers below read k[0] only
        base_keys: List[tuple] = []
        key2base: Dict[tuple, int] = {}
        for key in cand_key:
            if key not in key2base:
                key2base[key] = len(base_keys)
                base_keys.append(key)
        Cb = len(base_keys)
        cand_base = [key2base[key] for key in cand_key]
        base_depth = [max(cand_depth[ci] for ci in range(C)
                          if cand_base[ci] == bi) for bi in range(Cb)]
        base_ig = [min(cand_ig[ci] for ci in range(C)
                       if cand_base[ci] == bi) for bi in range(Cb)]
        truncated = [cand_depth[ci] < base_depth[cand_base[ci]]
                     for ci in range(C)]
        gate_shared = [cand_ig[ci] > base_ig[cand_base[ci]]
                       for ci in range(C)]
        # the growth emits what pruning reads only where a candidate is
        # not its own base
        prune = any(truncated) or any(gate_shared)

        # base pair p = bi * F + f
        pair_fold = np.tile(np.arange(F, dtype=np.int32), Cb)
        pair_ig = np.repeat(base_ig, F)
        pair_inst = np.repeat([k[0] for k in base_keys], F)
        pair_depth = np.repeat(base_depth, F)
        count_rf_grid(candidates=C, bases=Cb, pairs=Cb * F,
                      truncated=sum(truncated), gateShared=sum(gate_shared),
                      grownRows=W_tr_c.shape[1])
        t0 = _time.perf_counter()
        subsample = float(self._param(self.grid_points[0],
                                      "subsample_rate"))
        # the rf.grid.* spans time the host's dispatches; the device runs on
        # after each ends (nothing here waits for it)
        with _span("rf.grid.grow", cat="sweep", bases=Cb, pairs=Cb * F):
            if self.mesh is not None:
                grown = self._grow_pairs_sharded(
                    binned, Y, W_tr, seed=int(proto.seed), T=T,
                    pair_fold=pair_fold, pair_ig=pair_ig,
                    pair_inst=pair_inst, pair_depth=pair_depth, msub=msub,
                    subsample=subsample, mb=mb, cls=cls,
                    prune_outputs=prune)
            else:
                grown = grow_rf_grid(
                    binned, _dev_memo(Y, "rf_Y"),
                    _dev_memo(W_tr_c, "rf_Wtr"), seed=int(proto.seed),
                    n_trees=T, pair_fold=pair_fold, pair_min_ig=pair_ig,
                    pair_min_inst=pair_inst, pair_depth=pair_depth,
                    msub=msub, subsample_rate=subsample, n_bins=mb,
                    onehot_targets=cls, prune_outputs=prune, rows=tr_rows)
        self._record_grid_observation(_time.perf_counter() - t0, n, d)
        feats, threshs, leaves = grown[:3]
        heap_depth = int(np.log2(feats.shape[2] + 1))
        mode = "rf_cls" if cls else "rf_reg"
        ptype = ("multiclass" if multiclass
                 else "binary" if cls else "regression")

        # candidate-pair cp = c * F + f -> base pair, depth and gate
        cp_base = np.repeat(cand_base, F) * F + np.tile(
            np.arange(F, dtype=np.int32), C)
        cp_depth = np.repeat(cand_depth, F)
        cp_ig = np.repeat(cand_ig, F).astype(np.float32)
        cp_full = ~np.repeat(truncated, F)

        def part_trees(idx, depth):
            """The forests of candidate-pairs ``idx`` as heaps of ``depth``
            levels: the base's own where no candidate of the grid is
            derived, else read off the bases (a candidate that IS its base
            comes out as it went in)."""
            sel = cp_base[idx]            # numpy: indexes device OR host
            if not prune:
                return feats[sel], threshs[sel], leaves[sel]
            return prune_rf_grid(*grown, sel, cp_ig[idx], depth=depth,
                                 n_bins=mb)

        # one scoring part the full-depth candidates (heaps of the
        # program's depth, the f32 leaf sums), one a shallower depth (its
        # leaves are the level's histogram totals)
        order: List[int] = []
        parts = []
        part_depths = [(np.where(cp_full)[0], heap_depth)] + [
            (np.where(~cp_full & (cp_depth == dt))[0], dt)
            for dt in sorted(set(cp_depth[~cp_full].tolist()))]
        # a pair is ranked under its fold's eval weights alone, so it is
        # scored on the rows those weigh and on no other: one compacted
        # matrix a fold, all of one length (gathered here, behind the
        # growth's launches, not held while they are made)
        if ev_rows is None:
            scored, y_ev = [binned], y
        else:
            scored = [_fold_rows_jit(binned, r) for r in ev_rows]
            y_ev = y[ev_rows]
        count_rf_grid(scoredRows=W_ev_c.shape[1])
        for idx, depth in part_depths:
            if not len(idx):
                continue
            # fold-major: a fold's pairs stand together in the part
            idx = idx[np.argsort(idx % F, kind="stable")]
            with _span(f"rf.grid.score:d{depth}", cat="sweep",
                       pairs=len(idx)):
                parts.append(_score_pairs_jit(
                    scored, *part_trees(idx, depth), depth, mode, ptype))
            order.extend(idx.tolist())
        scores = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if order != list(range(C * F)):
            inv = np.empty(C * F, np.int32)
            inv[np.asarray(order, np.int32)] = np.arange(C * F, dtype=np.int32)
            scores = scores[jnp.asarray(inv)]
        # (F, C, L): L the folds' compacted length, or every row
        scores = scores.reshape(C, F, -1).transpose(1, 0, 2)
        # release the grown forests, the folds' matrices and per-part score
        # buffers before the metric grid dispatches: at 1M-row sweeps the
        # groups run back to back and holding every phase's device
        # intermediates to the end of the sweep needlessly raises
        # cumulative HBM pressure
        del grown, feats, threshs, leaves, parts, scored
        # context for refit_model: the winner's full-train forest grows as
        # ONE more base pair through the same (cached) grid program, with
        # identical randomness to a sequential full fit.  Single-chip
        # only: on a mesh the selector refits the winner sequentially
        # with its own mesh attached (the sharded grid program's chunk
        # shapes are sized for the whole pair stream, not one pair).
        if self.mesh is None:
            self._refit_ctx = dict(
                binned=binned, Y=Y, edges=edges, msub=msub, mb=mb, T=T,
                cls=cls, k=Y.shape[1], heap_depth=heap_depth,
                cand_base=cand_base, cand_depth=cand_depth,
                cand_ig=cand_ig, base_depth=base_depth,
                base_keys=base_keys, prune=prune,
                full_w=self._full_weights(weight_ctxs),
                seed=int(proto.seed), subsample=subsample)
        with _span("rf.grid.metrics", cat="sweep", rows=C * F):
            if multiclass:
                m = multiclass_metric_grid(y_ev, scores, jnp.asarray(W_ev_c),
                                           n_classes, self.metric)
            else:
                fn = binary_metric_grid if cls else regression_metric_grid
                m = fn(y_ev, scores, jnp.asarray(W_ev_c), self.metric)
        if m is None:
            return None
        return m.T

    def _grow_pairs_sharded(self, binned, Y, W_tr, *, seed: int, T: int,
                            pair_fold, pair_ig, pair_inst, pair_depth,
                            msub: int, subsample: float, mb: int,
                            cls: bool, prune_outputs: bool):
        """The mesh leg of ``run``: rows padded + sharded over the data
        axis, the flat (pair x tree) stream over the grid axis, bags
        pre-generated from the SAME fold_in(seed, tree_id) stream as the
        on-device single-chip generator (``rf_bags_and_features``)."""
        from ..models.gbdt_kernels import (_resolve_compile_depth,
                                           rf_bags_and_features)
        from ..models.trees import _binned_sharded, _dev_memo_sharded
        from ..parallel.mesh import fold_weight_sharding, pad_to_multiple
        from ..parallel.sharded import grow_rf_grid_sharded

        mesh = self.mesh
        ndata, _g = self._mesh_axes()
        n = int(np.asarray(W_tr).shape[1])
        d = int(binned.shape[1])
        binned_dev, _n_pad = _binned_sharded(binned, self.mesh)
        Y_p, _ = pad_to_multiple(np.asarray(Y, np.float32), ndata, axis=0)
        Wtr_p, _ = pad_to_multiple(
            np.ascontiguousarray(np.asarray(W_tr, np.float32)), ndata,
            axis=1)
        BWr, feat_idx = rf_bags_and_features(seed, T, n, d, msub,
                                             subsample)
        BWr_p, _ = pad_to_multiple(np.asarray(BWr, np.float32), ndata,
                                   axis=1)
        from ..parallel.mesh import sweep_matrix_sharding

        Y_dev = _dev_memo_sharded(Y_p, sweep_matrix_sharding(mesh),
                                  "rf_grid_Y")
        fw = fold_weight_sharding(mesh)
        Wtr_dev = _dev_memo_sharded(Wtr_p, fw, "rf_grid_Wtr")
        BWr_dev = _dev_memo_sharded(BWr_p, fw, "rf_grid_BWr")
        heap_depth = _resolve_compile_depth(
            max(int(np.asarray(pair_depth).max()), 1))
        return grow_rf_grid_sharded(
            binned_dev, Y_dev, Wtr_dev, BWr_dev, feat_idx,
            pair_fold, pair_ig, pair_inst, pair_depth, mesh,
            n_trees=T, msub=msub, n_bins=mb, heap_depth=heap_depth,
            onehot_targets=cls, prune_outputs=prune_outputs)

    def refit_model(self, row: int):
        """Full-train refit of candidate ``row`` as ONE extra base pair.

        Reuses the sweep's compiled grid program (``compile_depth_hint``
        pins the sweep's heap depth), its binned-matrix/target memos, and
        the SAME per-tree randomness as a sequential full fit
        (``fold_in(seed, t)`` keys on tree id, not on fold) — so the
        deployed forest is what ``fit_raw`` on the full split would grow,
        at ~1/(bases x folds) of the sweep's cost instead of a fresh
        sequential fit + compile (ModelSelector.scala:145-209 refits from
        scratch).  The pair grows under the winner's OWN min_info_gain (a
        traced value: nothing to prune) at its base's depth; a
        shallower-than-base winner comes off the pair's level values
        (exact for level-wise growth)."""
        ctx = getattr(self, "_refit_ctx", None)
        if ctx is None:
            return None
        import jax.numpy as jnp

        from ..models.gbdt_kernels import compile_depth_hint, grow_rf_grid
        from ..models.trees import TreeEnsembleModel, _dev_memo
        from ..obs.trace import span as _span
        from ..utils.profiling import count_rf_grid

        bi = ctx["cand_base"][row]
        dt = ctx["cand_depth"][row]
        bd = ctx["base_depth"][bi]
        count_rf_grid(pairs=1)
        rows, w_full = _fold_train_rows(ctx["full_w"][None])
        with _span("rf.grid.refit", cat="sweep", depth=dt, base_depth=bd), \
                compile_depth_hint(ctx["heap_depth"]):
            grown = grow_rf_grid(
                ctx["binned"], _dev_memo(ctx["Y"], "rf_Y"),
                _dev_memo(w_full, "rf_Wfull"),
                seed=ctx["seed"], n_trees=ctx["T"],
                pair_fold=np.zeros(1, np.int32),
                pair_min_ig=np.asarray([ctx["cand_ig"][row]], np.float32),
                pair_min_inst=np.asarray([ctx["base_keys"][bi][0]],
                                         np.float32),
                pair_depth=np.asarray([bd], np.int32), msub=ctx["msub"],
                subsample_rate=ctx["subsample"], n_bins=ctx["mb"],
                onehot_targets=ctx["cls"], prune_outputs=ctx["prune"],
                rows=rows)
        feats, threshs, leaves = grown[:3]
        if dt < bd:
            nd = 2 ** dt - 1
            level_values = grown[3][0]
            feat, thresh, leaf = (feats[0][:, :nd], threshs[0][:, :nd],
                                  level_values[dt][0])
        else:
            feat, thresh, leaf = feats[0], threshs[0], leaves[0]
        return TreeEnsembleModel(
            mode="rf_cls" if ctx["cls"] else "rf_reg", edges=ctx["edges"],
            feat=feat, thresh=thresh, leaf=leaf,
            n_classes=ctx["k"] if ctx["cls"] else 2)


#: the folds' compacted validation rows share one length, the longest
#: fold's rounded up to this many rows: one program shape a sweep whatever
#: the folds' sizes
_FOLD_ROWS_MULTIPLE = 1024


def _weighted_fold_rows(W: np.ndarray):
    """``(rows, weights)``, both (F, L): for each fold the rows its weights
    ``W[f]`` keep (above 0) and those weights.  A shorter fold is padded
    with its own last row under weight 0, which adds nothing to a metric,
    a histogram or a leaf.  ``(None, W)`` where L would reach the table's
    rows: nothing to leave out."""
    keep = [np.flatnonzero(w > 0) for w in W]
    L = -(-max(len(k) for k in keep) // _FOLD_ROWS_MULTIPLE
          ) * _FOLD_ROWS_MULTIPLE
    if L >= W.shape[1]:
        return None, W
    rows = np.zeros((len(keep), L), np.int32)
    weights = np.zeros((len(keep), L), np.float32)
    for f, k in enumerate(keep):
        rows[f, :len(k)] = k
        rows[f, len(k):] = k[-1] if len(k) else 0
        weights[f, :len(k)] = W[f, k]
    return rows, weights


def _fold_eval_rows(W_ev: np.ndarray):
    """The rows a fold's metric reads (eval weight above 0: not the rows it
    trained on, nor what a balancer or a hold-out reservation dropped),
    compacted by ``_weighted_fold_rows``."""
    return _weighted_fold_rows(W_ev)


def _fold_train_rows(W_tr: np.ndarray):
    """The rows a fold's forests are grown on (train weight above 0: not
    its validation rows, nor what a balancer or a hold-out reservation
    dropped), compacted by ``_weighted_fold_rows``."""
    return _weighted_fold_rows(W_tr)


def _score_pairs_jit(mats, feats, threshs, leaves, heap_depth: int,
                     mode: str, ptype: str):
    """Pair validation scores in memory-bounded vmapped launches (12
    separate predict+transform launches measured ~8 s at 200k x 500; a
    single unbounded vmap OOMs on the (pairs, trees, rows) leaf values).
    ``mats`` lists the binned matrices the pairs are scored on: one for
    every pair, or F of one shape (``_fold_eval_rows``) with the pairs
    fold-major, the f-th F-th of them scored on the f-th matrix."""
    import functools

    import jax
    import jax.numpy as jnp

    from ..models.trees import _score_ensemble_jit

    fn = functools.partial(_score_ensemble_jit, depth=heap_depth, mode=mode,
                           problem_type=ptype)
    P, T = feats.shape[0] // len(mats), feats.shape[1]
    n = mats[0].shape[0]
    k = leaves.shape[-1]
    per_pair = T * n * k * 4
    chunk = int(max(1, min(P, (64 << 20) // max(per_pair, 1))))
    parts = []
    for f, mat in enumerate(mats):
        for s in range(f * P, (f + 1) * P, chunk):
            e = min(s + chunk, (f + 1) * P)
            parts.append(jax.vmap(lambda f, t, lf: fn(mat, f, t, lf,
                                                      jnp.float32(0.0)))(
                feats[s:e], threshs[s:e], leaves[s:e]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


class GBTGridGroup(TreeGridGroup):
    """Every (candidate x fold) boosting chain advanced in lockstep.

    Each round grows ALL chains' trees in one vmapped launch — the
    (rows, bins*features) one-hot that dominates wide-data histogram cost
    is chain-invariant, so XLA builds it once per row block and every
    chain's dots share it (measured ~1.5x over sequential chains at 6
    chains, plus the removal of per-chain Python dispatch).  Per-chain
    hyperparameters (depth limit, eta, lambda, min_child_weight, gamma)
    are traced per-tree vectors; early stopping replays the reference's
    patience logic per chain from chunked metric fetches
    (OpXGBoostClassifier.scala:47 ES semantics).

    The tree fast path composes here: EFB shrinks the shared histogram
    width before any launch (splits unbundle before scoring), GOSS
    engages for all-deep single-chip grids, and on a sweep mesh the SAME
    lockstep rounds run sharded (``gbt_chain_rounds_sharded`` — chains
    over the grid axis, rows over data, psum'd histograms).
    """

    grid_stage_kind = "GBT:fit-grid"

    def _chains(self):
        """Resolved per-candidate estimator copies (attribute-level params,
        robust to ctor-name aliases like XGB's eta -> step_size)."""
        return [self.proto.copy(**p) for p in self.grid_points]

    def run(self, X, y, weight_ctxs):
        from ..obs.trace import phases

        # a traced run splits the group's host time in four: up to the
        # first chain launch, the rounds' launches, the fold scoring's, the
        # metric grid with the fetch of its rows
        with phases("gbt.grid.prepare", cat="sweep") as ph:
            return self._run_phased(X, y, weight_ctxs, ph)

    def _run_phased(self, X, y, weight_ctxs, ph):
        """``run``'s body; ``ph`` holds the ``gbt.grid.*`` span that is
        open (``prepare`` on entry)."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from ..evaluators.metrics import (_aupr_dev, binary_metric_grid,
                                          regression_metric_grid)
        from ..models.gbdt_kernels import predict_ensemble, predict_tree
        from ..models.trees import _dev_memo
        from ..utils.profiling import launch

        ests = self._chains()
        e0 = ests[0]
        obj = e0._objective
        if obj not in ("binary", "regression"):
            return None
        if obj == "binary" and len(y) and np.nanmax(y) > 1:
            return None
        # static across chains; decline otherwise (sequential fallback)
        for attr in ("max_iter", "max_bins", "early_stopping_rounds",
                     "validation_fraction", "seed", "subsample_rate",
                     "colsample", "hist_precision",
                     "sparse_default_direction"):
            if len({getattr(e, attr) for e in ests}) > 1:
                return None
        if e0.subsample_rate < 1.0 or e0.colsample < 1.0:
            return None                     # per-round host RNG: sequential

        y = np.nan_to_num(np.asarray(y, np.float32))
        n = len(y)
        t0 = _time.perf_counter()
        # weight-aware sketch: zero-total-weight rows (mesh padding under
        # the TM024 contract, balancer drops) must not move the bin edges
        from ..models.trees import _prep_tree_inputs_weighted

        edges, binned = _prep_tree_inputs_weighted(
            X, e0.max_bins, row_weight=self._full_weights(weight_ctxs))
        # EFB: pack the mutually exclusive one-hot/picklist columns into
        # shared histogram columns BEFORE any launch (both the single-chip
        # and the sharded path grow in bundled space; splits unbundle
        # before scoring, which routes on the original matrix)
        binned_orig = binned
        bundles = None
        bend = None
        from ..models.trees import (_as_f32, _content_hash, _efb_enabled,
                                    _maybe_bundle)

        if _efb_enabled():
            eb = _maybe_bundle(_content_hash(_as_f32(X)), edges, binned,
                               int(e0.max_bins))
            if eb is not None:
                bundles, binned, bend = eb
        d_hist = int(binned.shape[1])
        W_tr, W_ev = self._stack_weights(weight_ctxs)
        F = W_tr.shape[0]
        C = len(ests)
        # No appended full-train refit chains here, deliberately: measured
        # per-round cost is ~(shared one-hot + per-chain histogram dots),
        # so +C chains cost ~C/(C·F) of the whole sweep UNCONDITIONALLY,
        # while the sequential refit they would replace is paid only when
        # a GBT candidate actually wins — negative expected value for the
        # default grid (LR groups, whose extra row is ~free, do reuse).
        S_val = C * F
        S = S_val
        chain_fold = np.tile(np.arange(F, dtype=np.int32), C)
        chain_est = np.repeat(np.arange(C), F)

        def vec(attr, dtype=np.float32):
            return jnp.asarray(
                np.asarray([getattr(ests[c], attr) for c in chain_est],
                           dtype))
        depth_lim = vec("max_depth", np.int32)
        lams = vec("reg_lambda")
        mcws = vec("min_child_weight")
        migs = vec("min_info_gain")
        mins_ = jnp.asarray(np.asarray(
            [float(ests[c].min_instances_per_node) for c in chain_est],
            np.float32))
        lrs = vec("step_size")
        mgrs = vec("min_split_gain_raw")
        # heap shapes sized to THIS group's deepest chain — never an outer
        # sweep-wide hint (a depth-12 RF grid elsewhere in the sweep would
        # inflate these depth-6 chains' compacted-slot histograms ~20x)
        heap_depth = int(max(e.max_depth for e in ests))

        use_es = e0.early_stopping_rounds > 0
        rng = np.random.default_rng(e0.seed)
        val = (rng.random(n) < e0.validation_fraction) if use_es \
            else np.zeros(n, bool)
        # per-chain weights: full fold weights for the base score, ES-train
        # weights for gradients (sequential fit_raw parity)
        W_full = W_tr[chain_fold]                         # (S, N) host
        W_train = W_full * (~val)[None, :]
        if obj == "binary":
            pos = (W_full * y[None, :]).sum(axis=1)
            tot = np.maximum(W_full.sum(axis=1), 1e-9)
            p0 = np.clip(pos / tot, 1e-6, 1 - 1e-6)
            base = np.log(p0 / (1 - p0)).astype(np.float32)
        else:
            base = ((W_full @ y) / np.maximum(W_full.sum(axis=1), 1e-9)
                    ).astype(np.float32)

        base_j = jnp.asarray(base)
        if self.mesh is None:
            yj = _dev_memo(y, "gbt_y")
            Wj = _dev_memo(W_train, "gbt_Wtr")
            Fm = jnp.broadcast_to(base_j[:, None],
                                  (S, n)).astype(jnp.float32)
        else:
            yj = Wj = Fm = None            # placed sharded below
        vi = (jnp.asarray(np.where(val)[0], jnp.int32)
              if use_es and val.any() else None)

        lagged: list = []
        best_metric = np.full(S, -np.inf)
        best_len = np.zeros(S, np.int32)
        stall = np.zeros(S, np.int32)
        stopped = np.zeros(S, bool)
        es_chunk = max(1, min(8, e0.early_stopping_rounds or 8))
        from ..models.gbdt_kernels import (_gbt_chain_rounds_jit,
                                           default_dir_mask, gbt_chain_chunk,
                                           goss_plan, hist_accum_bf16)

        # default-direction splits only on features whose bin 0 is a real
        # missing/zero bucket (sparse-aware pinned edge); bundle columns
        # never learn a default direction (no single-feature map-back)
        dd_host = (default_dir_mask(edges)
                   if e0.sparse_default_direction else None)
        if bundles is not None and dd_host is not None:
            dd_host = bundles.bundled_dd_mask(dd_host)
        dd = jnp.asarray(dd_host) if dd_host is not None else None

        # GOSS for all-deep single-chip grids (the sharded path keeps all
        # rows — a distributed |grad| top-k is not worth the collectives)
        goss = (goss_plan(n, min(int(e.max_depth) for e in ests))
                if self.mesh is None else None)
        acc = hist_accum_bf16()

        chunk = gbt_chain_chunk(
            S, heap_depth, d_hist, int(e0.max_bins), n,
            goss_rows=sum(goss) if goss is not None else None)
        run_es = use_es and vi is not None
        vi_arr = vi if vi is not None else jnp.zeros(1, jnp.int32)
        bf16 = e0._hist_bf16()   # backend-resolved: part of the jit key
        # count channel inert under pure XGB gating -> 2-channel
        # histograms; integer fold/train weights only (the count channel
        # is weighted — fractional weights could make 'CL >= 1' bite)
        skip_counts = (all(float(e.min_instances_per_node) <= 1
                           and float(e.min_info_gain) == 0.0 for e in ests)
                       and bool((W_train == np.floor(W_train)).all()))
        # es_chunk rounds per LAUNCH (lax.scan over rounds): one dispatch
        # and one early-stopping fetch per chunk, not per round (the chunk
        # length is a choice a chip measurement must re-decide — ROADMAP
        # Queue 3).  Chunks always run full length — the ≤ es_chunk-1
        # overshoot rounds past max_iter or past a chain's stop are masked
        # out of the final scoring, exactly like the ES trim; patience
        # replay only ever sees rounds ≤ max_iter, so selection matches the
        # per-round loop.
        if self.mesh is not None:
            # sweep-mesh placement: binned P("data", None), per-chain
            # row state P("grid", "data"), hyperparameter vectors
            # P("grid") padded by repeating the last chain (stripped
            # from every consumer below).  Chains are NOT sub-chunked on
            # the mesh path: per-device histogram memory is already
            # divided by the data axis, and a chain slice would have to
            # re-tile the grid axis per block.
            from ..parallel.mesh import (chain_sharding, data_sharding,
                                         pad_to_multiple)
            from ..parallel.sharded import gbt_chain_rounds_sharded
            from ..models.trees import _binned_sharded, _dev_memo_sharded

            mesh = self.mesh
            ndata, g_ax = self._mesh_axes()
            c_pad = (-S) % g_ax

            def padc(a):
                a = np.asarray(a)
                if not c_pad:
                    return a
                return np.concatenate([a, np.repeat(a[-1:], c_pad,
                                                    axis=0)])

            binned_sh, n_pad = _binned_sharded(binned, self.mesh)
            y_p, _ = pad_to_multiple(y, ndata)
            y_sh = _dev_memo_sharded(y_p, data_sharding(mesh),
                                     "gbt_grid_y")
            Wp, _ = pad_to_multiple(
                np.ascontiguousarray(padc(W_train)), ndata, axis=1)
            cs = chain_sharding(mesh)
            Wj = _dev_memo_sharded(Wp, cs, "gbt_grid_W")
            Fm = jax.device_put(np.ascontiguousarray(np.broadcast_to(
                padc(base)[:, None], Wp.shape).astype(np.float32)), cs)
            from ..parallel.mesh import grid_sharding

            gs = grid_sharding(mesh)

            def gvec(a):
                return jax.device_put(
                    np.ascontiguousarray(padc(np.asarray(a))), gs)

            vecs_sh = tuple(gvec(v) for v in (depth_lim, lams, mcws,
                                              migs, mins_, lrs, mgrs))
            yv_dev = (jnp.asarray(y[np.asarray(vi)]) if run_es
                      else jnp.zeros(1, jnp.float32))
        feats_b, threshs_b, leaves_b = [], [], []
        n_rounds = 0
        ph.to("gbt.grid.rounds")
        for ci in range(-(-e0.max_iter // es_chunk)):
            if self.mesh is not None:
                with launch("gbt_chain_rounds_sharded"):
                    Fm, fs, ts, lfs, ms = gbt_chain_rounds_sharded(
                        binned_sh, y_sh, Wj, Fm, yv_dev, vi_arr, *vecs_sh,
                        self.mesh, n_rounds=es_chunk, max_depth=heap_depth,
                        n_bins=int(e0.max_bins), obj=obj, hist_bf16=bf16,
                        use_es=run_es, skip_counts=skip_counts,
                        bundle_end=(bundles.end_bin if bundles is not None
                                    else None), acc_bf16=acc)
            elif chunk >= S:
                with launch("gbt_chain_rounds"):
                    Fm, fs, ts, lfs, ms = _gbt_chain_rounds_jit(
                        binned, yj, Wj, Fm, vi_arr, depth_lim, lams, mcws,
                        migs, mins_, lrs, mgrs, es_chunk, heap_depth,
                        int(e0.max_bins), obj, bf16, run_es,
                        skip_counts=skip_counts,
                        default_dir=e0.sparse_default_direction,
                        dd_mask=dd, bundle_end=bend, acc_bf16=acc,
                        goss=goss, goss_seed=jnp.int32(e0.seed),
                        chain_ids=jnp.arange(S, dtype=jnp.int32),
                        round_offset=jnp.int32(n_rounds))
            else:
                parts = []
                for s0 in range(0, S, chunk):
                    s1 = min(s0 + chunk, S)
                    with launch("gbt_chain_rounds"):
                        parts.append(_gbt_chain_rounds_jit(
                            binned, yj, Wj[s0:s1], Fm[s0:s1], vi_arr,
                            depth_lim[s0:s1], lams[s0:s1], mcws[s0:s1],
                            migs[s0:s1], mins_[s0:s1], lrs[s0:s1],
                            mgrs[s0:s1], es_chunk, heap_depth,
                            int(e0.max_bins), obj, bf16, run_es,
                            skip_counts=skip_counts,
                            default_dir=e0.sparse_default_direction,
                            dd_mask=dd, bundle_end=bend, acc_bf16=acc,
                            goss=goss, goss_seed=jnp.int32(e0.seed),
                            chain_ids=jnp.arange(s0, s1, dtype=jnp.int32),
                            round_offset=jnp.int32(n_rounds)))
                Fm = jnp.concatenate([p[0] for p in parts])
                fs = jnp.concatenate([p[1] for p in parts], axis=1)
                ts = jnp.concatenate([p[2] for p in parts], axis=1)
                lfs = jnp.concatenate([p[3] for p in parts], axis=1)
                ms = jnp.concatenate([p[4] for p in parts], axis=1)
            feats_b.append(fs)
            threshs_b.append(ts)
            leaves_b.append(lfs)
            start = n_rounds
            n_rounds += es_chunk
            if run_es:
                # LAGGED fetch: replay the chunk enqueued ONE launch ago
                # (its device values are long since finished, so the sync
                # is ~free); decisions lag one chunk, the extra rounds are
                # trimmed by the masked scoring below.
                pending = [(start + j + 1, ms[j][:S])
                           for j in range(es_chunk)
                           if start + j + 1 <= e0.max_iter]
                if _replay_es(lagged, stopped, best_metric, best_len,
                              stall, e0.early_stopping_rounds,
                              overlapped=True):
                    break
                lagged = pending
        if run_es and not stopped.all():
            # drain the in-flight chunk so the final best_len is exact
            _replay_es(lagged, stopped, best_metric, best_len, stall,
                       e0.early_stopping_rounds)
        if not use_es:
            best_len[:] = e0.max_iter
        else:
            best_len[best_len == 0] = min(n_rounds, e0.max_iter)

        ph.to("gbt.grid.score")
        # final per-chain scores over ALL rows: ONE (rounds, chains) restack
        # + per-chain masked-leaf predicts.  Trimming by zeroing the leaves
        # of rounds >= best_len keeps every chain on the SAME (R, nodes)
        # shapes — per-chain trimmed stacks meant up to S distinct
        # predict_ensemble compiles plus R*S per-round device slices
        R = n_rounds
        if self.mesh is not None or bundles is not None:
            # host tree stacks: grid-sharded chain axes gather to host
            # (bounded — trees are tens of MB), and EFB splits unbundle
            # back to ORIGINAL columns so the scoring predicts route on
            # the original binned matrix
            feats_all = np.concatenate(
                [np.asarray(f) for f in feats_b]).transpose(1, 0, 2)[:S_val]
            threshs_all = np.concatenate(
                [np.asarray(t) for t in threshs_b]
            ).transpose(1, 0, 2)[:S_val]
            leaves_all = np.concatenate(
                [np.asarray(lv) for lv in leaves_b]
            ).transpose(1, 0, 2, 3)[:S_val]
            if bundles is not None:
                from ..models.gbdt_kernels import unbundle_ensemble

                feats_all, threshs_all = unbundle_ensemble(
                    bundles, feats_all, threshs_all)
            keep = np.arange(R)[None, :] < best_len[:S_val, None]
            leaves_m = leaves_all * keep[:, :, None, None]
            binned_sc = binned_orig
        else:
            feats_all = jnp.concatenate(feats_b).transpose(1, 0, 2)
            threshs_all = jnp.concatenate(threshs_b).transpose(1, 0, 2)
            leaves_all = jnp.concatenate(leaves_b).transpose(1, 0, 2, 3)
            keep = (jnp.arange(R)[None, :]
                    < jnp.asarray(best_len)[:, None])           # (S, R)
            leaves_m = leaves_all * keep[:, :, None, None]
            binned_sc = binned
        scores = []
        for s in range(S_val):
            with launch("gbt_chain_score"):
                raw = predict_ensemble(
                    binned_sc, feats_all[s], threshs_all[s], leaves_m[s],
                    heap_depth)[:, 0]
            z = raw + base_j[s]
            scores.append(jax.nn.sigmoid(z) if obj == "binary" else z)
        scores = jnp.stack(scores).reshape(C, F, n).transpose(1, 0, 2)
        self._record_grid_observation(_time.perf_counter() - t0, n,
                                      int(X.shape[1]))
        ph.to("gbt.grid.metrics", rows=C * F)
        # release the per-round tree stacks, margins and masked leaves
        # before the metric grid runs (see RFGridGroup.run note); the last
        # chunk's loop locals pin device buffers too
        del feats_all, threshs_all, leaves_all, leaves_m, keep, Fm
        del feats_b, threshs_b, leaves_b
        fs = ts = lfs = ms = None  # noqa: F841 — drop last chunk's buffers
        fn = binary_metric_grid if obj == "binary" else regression_metric_grid
        m = fn(y, scores, jnp.asarray(W_ev), self.metric)
        if m is None:
            return None
        return m.T


def _replay_es(chunk_rows, stopped, best_metric, best_len, stall,
               patience: int, overlapped: bool = False) -> bool:
    """Replay one fetched chunk of per-chain ES metrics against the
    host-side patience state (in place); True when every chain stopped.
    The rule itself is ``trees.es_patience_vec`` — the same code the
    sequential single-chain fits run.  ``overlapped=True`` at the lagged
    call site (the next chunk's launch is already enqueued, so this wait
    books as overlap, not drain — utils/profiling.py)."""
    if not chunk_rows:
        return bool(stopped.all())
    from ..models.trees import _materialize_es, es_patience_vec

    return es_patience_vec(_materialize_es(chunk_rows,
                                           overlapped=overlapped),
                           stopped,
                           best_metric, best_len, stall, patience)


def make_grid_group(proto, grid_points, problem_type: str,
                    metric: str, n_classes: int = 2,
                    mesh=None) -> Optional[GridGroup]:
    """Group factory: returns a batched group when the estimator family,
    problem type, and metric support one — else None (sequential fits).
    ``n_classes`` is the selector's fit-time-captured class-space size
    (multiclass groups take the max of it and the observed labels).
    ``mesh`` (a ("data", "grid") sweep mesh) runs mesh-capable families'
    batched programs sharded — rows over data, candidates over grid."""
    if len(grid_points) == 0:
        return None
    group = _make_grid_group(proto, grid_points, problem_type, metric,
                             n_classes)
    if group is not None and mesh is not None:
        group.with_mesh(mesh)
    return group


def _make_grid_group(proto, grid_points, problem_type: str,
                     metric: str, n_classes: int = 2
                     ) -> Optional[GridGroup]:
    from ..evaluators.metrics import _MULTI_GRID_METRICS
    from ..models.classification import OpLogisticRegression
    from ..models.regression import OpLinearRegression

    from ..models.trees import (OpRandomForestClassifier,
                                OpRandomForestRegressor)

    _REG_METRICS = ("RootMeanSquaredError", "MeanSquaredError",
                    "MeanAbsoluteError", "R2")
    if problem_type == "binary" and type(proto) is OpLogisticRegression \
            and metric in ("AuPR", "AuROC"):
        return LogRegGridGroup(proto, grid_points, metric)
    if problem_type == "multiclass" \
            and type(proto) is OpLogisticRegression \
            and metric in _MULTI_GRID_METRICS:
        return SoftmaxGridGroup(proto, grid_points, metric,
                                n_classes=n_classes)
    if problem_type == "regression" and type(proto) is OpLinearRegression \
            and metric in _REG_METRICS:
        return LinRegGridGroup(proto, grid_points, metric)
    if problem_type in ("binary", "multiclass") \
            and type(proto) is OpRandomForestClassifier \
            and metric in (("AuPR", "AuROC") if problem_type == "binary"
                           else _MULTI_GRID_METRICS):
        return RFGridGroup(proto, grid_points, metric, n_classes=n_classes)
    if problem_type == "regression" \
            and type(proto) is OpRandomForestRegressor \
            and metric in _REG_METRICS:
        return RFGridGroup(proto, grid_points, metric)
    from ..models.trees import _GBTBase

    if isinstance(proto, _GBTBase):
        if problem_type == "binary" and proto._objective == "binary" \
                and metric in ("AuPR", "AuROC"):
            return GBTGridGroup(proto, grid_points, metric)
        if problem_type == "regression" \
                and proto._objective == "regression" \
                and metric in _REG_METRICS:
            return GBTGridGroup(proto, grid_points, metric)
    return None
