"""SanityChecker — automated feature validation against the label.

Reference: ``SanityChecker`` (core/.../impl/preparators/SanityChecker.scala:232,
fitFn :367-470, model :544-560), drop logic
``DerivedFeatureFilterUtils.getFeaturesToDrop``
(impl/preparators/DerivedFeatureFilterUtils.scala), summary metadata
``SanityCheckerMetadata`` (impl/preparators/SanityCheckerMetadata.scala), and
``MinVarianceFilter`` (impl/preparators/MinVarianceFilter.scala).

TPU design: colStats + label correlations are two matmul-reductions over the
device-resident (N, D) matrix (ops.stats); Cramér's V per categorical group is
a one-hot matmul contingency.  The fitted model is an index-gather on the
vector — the same "filter the slots" semantics as the reference.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..ops.stats import (
    col_stats, cramers_v, pearson_with_label, spearman_with_label,
)
from ..ops.vector_metadata import VectorMetadata
from ..stages.base import BinaryEstimator, BinaryModel
from ..types.columns import ColumnarDataset, FeatureColumn
from ..types.feature_types import OPNumeric, OPVector
from ..utils.profiling import count_fresh

__all__ = ["SanityChecker", "SanityCheckerModel", "SanityCheckerSummary",
           "MinVarianceFilter"]


@dataclasses.dataclass
class ColumnStat:
    name: str
    parent_feature: str
    mean: float
    variance: float
    min: float
    max: float
    corr_label: float
    cramers_v: Optional[float]
    dropped: bool
    reasons: List[str]

    def to_json(self):
        return dataclasses.asdict(self)


class SanityCheckerSummary:
    """Structured fit summary (SanityCheckerSummary metadata parity)."""

    def __init__(self, stats: List[ColumnStat], dropped: List[str],
                 correlation_type: str, sample_size: float):
        self.stats = stats
        self.dropped = dropped
        self.correlation_type = correlation_type
        self.sample_size = sample_size

    def to_json(self):
        return {
            "correlationType": self.correlation_type,
            "sampleSize": self.sample_size,
            "dropped": self.dropped,
            "columnStats": [s.to_json() for s in self.stats],
        }


def _matrix_f32(values) -> np.ndarray:
    """The feature matrix as float32 WITHOUT re-packing when the upstream
    vectorizer already produced a float32 ndarray (VectorsCombiner emits
    C-contiguous float32); everything else (float64, device arrays, lists)
    still converts.  Callers must treat the result as read-only — it may
    alias the live column buffer."""
    if isinstance(values, np.ndarray) and values.dtype == np.float32:
        return values
    return np.asarray(values, dtype=np.float32)


def _select_columns(X, keep: np.ndarray) -> np.ndarray:
    """The columns ``keep`` (an intp index array) of the matrix ``X``, in
    ``keep``'s order, float32 and C-contiguous, in ONE pass: the layout
    every consumer reads (the selector's ``trees._as_f32`` copies anything
    else).  Not ``X[:, keep].astype(np.float32)``: indexing a row-major
    matrix with a list is numpy's slowest gather and its result is
    Fortran-ordered, which ``astype`` copies and keeps (docs/performance.md,
    "Handing a matrix on").

    When nothing is dropped and ``X`` is row-major float32 already, ``X``
    itself is handed on: no stage may write to a column it is given
    (contract TM020, analysis/contracts.py), so nobody writes through the
    alias."""
    X = np.asarray(X)
    if (X.dtype == np.float32 and X.flags.c_contiguous
            and keep.size == X.shape[1]
            and np.array_equal(keep, np.arange(keep.size))):
        return X
    out = np.take(X, keep, axis=1).astype(np.float32, copy=False)
    count_fresh("sanity.filter", out.nbytes)
    return out


class SanityChecker(BinaryEstimator):
    """Inputs: (label RealNN, features OPVector) -> cleaned OPVector."""

    # the stats pass is a big BLAS/XLA program; the execution plan
    # (workflow/plan.py) runs it serially, not on the host stage pool
    device_heavy = True

    # input schema (SchemaError at wiring, TM004 statically); the label
    # slot is declared for the leakage lint (TM006)
    input_types = (OPNumeric, OPVector)
    label_input_positions = (0,)

    def __init__(self,
                 check_sample: float = 1.0,
                 sample_seed: int = 42,
                 min_variance: float = 1e-5,
                 min_correlation: float = 0.0,
                 max_correlation: float = 0.95,
                 max_cramers_v: float = 0.95,
                 correlation_type: str = "pearson",
                 remove_bad_features: bool = True,
                 remove_feature_group: bool = True,
                 categorical_label: Optional[bool] = None,
                 max_label_classes: int = 100,
                 uid: Optional[str] = None):
        super().__init__(operation_name="sanityCheck", output_type=OPVector,
                         uid=uid)
        self.check_sample = check_sample
        self.sample_seed = sample_seed
        self.min_variance = min_variance
        self.min_correlation = min_correlation
        self.max_correlation = max_correlation
        self.max_cramers_v = max_cramers_v
        self.correlation_type = correlation_type
        self.remove_bad_features = remove_bad_features
        self.remove_feature_group = remove_feature_group
        self.categorical_label = categorical_label
        self.max_label_classes = max_label_classes
        self.mesh = None

    def with_mesh(self, mesh) -> "SanityChecker":
        """Multi-chip stats: colStats + label correlations run as one
        row-sharded program with GSPMD ICI reductions
        (parallel/sharded.colstats_corr_sharded) — the reference distributes
        exactly these over executors (SanityChecker.scala:380-470).
        Spearman needs a global rank sort and stays single-device."""
        self.mesh = mesh
        return self

    def fit_columns(self, data: ColumnarDataset, label_col: FeatureColumn,
                    features_col: FeatureColumn):
        X = _matrix_f32(features_col.values)
        y = np.nan_to_num(np.asarray(label_col.values, dtype=np.float32))
        n, d = X.shape
        if self.check_sample < 1.0:
            rng = np.random.default_rng(self.sample_seed)
            idx = rng.random(n) < self.check_sample
            X, y = X[idx], y[idx]
            n = len(y)
        vmeta = features_col.vmeta or VectorMetadata(
            "features", [])

        if (self.mesh is not None and self.correlation_type != "spearman"
                and X.size <= (1 << 24)):
            # mesh stats for data that is not yet past the host-BLAS
            # threshold; above it, host-resident matrices stay on the host
            # path below — shipping GBs to the device for a one-pass stat
            # costs more than the stat (a genuinely multi-host deployment
            # would feed device-resident shards instead)
            from ..parallel.sharded import colstats_corr_sharded

            mean_h, variance, min_h, max_h, corr = colstats_corr_sharded(
                X, y, self.mesh)
            corr = np.nan_to_num(corr)
        elif X.size > (1 << 24) and self.correlation_type != "spearman":
            # big host matrices (> 2^24 elements): means/variance/Pearson
            # are one BLAS pass on host instead of an upload of the whole
            # matrix for a single reduction.  The host/device split is a
            # choice a chip measurement must re-decide (ROADMAP Queue 3).
            mean_h = X.mean(axis=0, dtype=np.float64)
            variance = X.var(axis=0, ddof=1, dtype=np.float64)
            min_h, max_h = X.min(axis=0), X.max(axis=0)
            yc = (y - y.mean()).astype(np.float64)
            # center X before the dot: an uncentered f32 product cancels
            # catastrophically for large-offset columns (e.g. timestamps)
            num = yc @ (X - mean_h)
            den = (np.sqrt(np.maximum(variance, 1e-30) * (n - 1))
                   * np.sqrt(max(float(yc @ yc), 1e-30)))
            with np.errstate(invalid="ignore", divide="ignore"):
                corr = np.nan_to_num(num / den)
        else:
            import jax.numpy as jnp

            stats = col_stats(X)
            corr_dev = (spearman_with_label(X, y)
                        if self.correlation_type == "spearman"
                        else pearson_with_label(X, y))
            # ONE stacked fetch for all per-column stats + correlations —
            # each separate np.asarray costs a full device round trip
            packed = np.asarray(jnp.stack([
                jnp.asarray(stats.mean), jnp.asarray(stats.variance),
                jnp.asarray(stats.min), jnp.asarray(stats.max),
                jnp.asarray(corr_dev)]))
            mean_h, variance, min_h, max_h, corr = packed
            corr = np.nan_to_num(corr)

        # label categorical? -> Cramér's V per categorical group
        uniq = np.unique(y)
        is_cat_label = (self.categorical_label
                        if self.categorical_label is not None
                        else len(uniq) <= min(self.max_label_classes, n // 2))
        group_cv: Dict[Tuple[str, Optional[str]], float] = {}
        if is_cat_label and vmeta.size == d:
            labels_int = np.searchsorted(uniq, y)
            for key, idxs in self._indicator_groups(vmeta).items():
                res = cramers_v(labels_int, X[:, idxs], len(uniq))
                group_cv[key] = res["cramersV"]

        return self._finalize(mean_h, variance, min_h, max_h, corr,
                              group_cv, vmeta, n, d)

    @staticmethod
    def _indicator_groups(vmeta) -> Dict[Tuple[str, Optional[str]], List[int]]:
        groups: Dict[Tuple[str, Optional[str]], List[int]] = {}
        for i, c in enumerate(vmeta.columns):
            if c.indicator_value is not None:
                groups.setdefault((c.parent_feature, c.grouping), []).append(i)
        return groups

    def _finalize(self, mean_h, variance, min_h, max_h, corr, group_cv,
                  vmeta, n: int, d: int) -> "SanityCheckerModel":
        """Drop rules + summary + model from computed column statistics
        (DerivedFeatureFilterUtils.getFeaturesToDrop parity) — shared by
        the in-core fit and the streaming finish_fit."""
        to_drop = np.zeros(d, dtype=bool)
        reasons: List[List[str]] = [[] for _ in range(d)]
        for j in range(d):
            if variance[j] < self.min_variance:
                to_drop[j] = True
                reasons[j].append("low variance")
            a = abs(corr[j])
            if a > self.max_correlation:
                to_drop[j] = True
                reasons[j].append(
                    f"label correlation {a:.3f} > {self.max_correlation} (leakage)")
            elif 0 < self.min_correlation and a < self.min_correlation:
                to_drop[j] = True
                reasons[j].append("correlation below minimum")
        if vmeta.size == d:
            for j, c in enumerate(vmeta.columns):
                cv = group_cv.get((c.parent_feature, c.grouping))
                if cv is not None and cv > self.max_cramers_v:
                    to_drop[j] = True
                    reasons[j].append(
                        f"group Cramér's V {cv:.3f} > {self.max_cramers_v}")

        col_names = (vmeta.column_names() if vmeta.size == d
                     else [f"f_{j}" for j in range(d)])
        parents = ([c.parent_feature for c in vmeta.columns]
                   if vmeta.size == d else ["features"] * d)
        col_stats_out = [
            ColumnStat(
                name=col_names[j], parent_feature=parents[j],
                mean=float(mean_h[j]), variance=float(variance[j]),
                min=float(min_h[j]),
                max=float(max_h[j]),
                corr_label=float(corr[j]),
                cramers_v=(group_cv.get((vmeta.columns[j].parent_feature,
                                         vmeta.columns[j].grouping))
                           if vmeta.size == d else None),
                dropped=bool(to_drop[j]), reasons=reasons[j])
            for j in range(d)
        ]

        if not self.remove_bad_features:
            keep = list(range(d))
        else:
            keep = [j for j in range(d) if not to_drop[j]]
        summary = SanityCheckerSummary(
            stats=col_stats_out,
            dropped=[col_names[j] for j in range(d) if to_drop[j]],
            correlation_type=self.correlation_type, sample_size=float(n))
        self.metadata["summary"] = summary.to_json()
        # vector-level moment baseline over the KEPT slots — the drift
        # monitor's feature-space view (serving/drift.py compares scored
        # traffic's slot moments via z-scores; raw-feature baselines come
        # from the vectorizers).  ndarrays so persistence externalizes
        # them bit-exactly into arrays.npz.
        self.metadata["drift_baseline_vector"] = {
            "names": [col_names[j] for j in keep],
            "n": float(n),
            "mean": np.asarray(mean_h, np.float64)[keep],
            "variance": np.asarray(variance, np.float64)[keep],
        }
        new_meta = vmeta.select(keep) if vmeta.size == d else None
        model = SanityCheckerModel(keep_indices=keep)
        model._new_vmeta = new_meta
        return model

    # -- streaming fit: moment + co-moment + contingency accumulators -------
    #
    # Column stats and label correlation accumulate via PearsonSketch
    # (Chan-merged float64 moments: matches in-core to ~1e-6, limited by the
    # in-core float32 stat paths; KEEP decisions are threshold comparisons
    # and match exactly on non-degenerate data).  Cramér's V contingency
    # sums are exact (integer-valued one-hot sums).  Spearman needs a
    # global rank sort and cannot stream — supports_streaming_fit is False
    # then and the two-pass driver materializes instead.

    @property
    def supports_streaming_fit(self) -> bool:  # type: ignore[override]
        return self.correlation_type != "spearman"

    class _StreamState:
        __slots__ = ("pearson", "label_values", "label_sums", "vmeta",
                     "d", "rng")

        def __init__(self, rng):
            from ..utils.sketches import PearsonSketch

            self.pearson = PearsonSketch()
            self.label_values = np.zeros(0, np.float64)
            self.label_sums: Optional[Dict[float, np.ndarray]] = {}
            self.vmeta = None
            self.d: Optional[int] = None
            self.rng = rng

    def begin_fit(self):
        if self.correlation_type == "spearman":
            raise ValueError(
                "SanityChecker streaming fit requires a streamable "
                "correlation (spearman needs a global rank sort)")
        rng = (np.random.default_rng(self.sample_seed)
               if self.check_sample < 1.0 else None)
        return SanityChecker._StreamState(rng)

    # -- checkpoint hooks: _StreamState <-> codec-safe dict -----------------
    # The rng round-trips through the bit generator's exact state, so a
    # resumed sampled fit draws the SAME row-selection stream it would
    # have drawn uninterrupted.

    def export_fit_state(self, state):
        return {"pearson": state.pearson,
                "label_values": state.label_values,
                "label_sums": state.label_sums,
                "vmeta": state.vmeta,
                "d": state.d,
                "rng": state.rng}

    def import_fit_state(self, payload):
        state = SanityChecker._StreamState(payload["rng"])
        state.pearson = payload["pearson"]
        state.label_values = np.asarray(payload["label_values"],
                                        dtype=np.float64)
        sums = payload["label_sums"]
        state.label_sums = (None if sums is None
                            else {float(k): np.asarray(v, np.float64)
                                  for k, v in sums.items()})
        state.vmeta = payload["vmeta"]
        state.d = None if payload["d"] is None else int(payload["d"])
        return state

    #: streaming Cramér's V tracks per-label column sums; past this many
    #: distinct label values the label cannot be categorical for any
    #: reasonable config and the contingency accumulator is abandoned
    _STREAM_LABEL_CAP_HARD = 4096

    def update_chunk(self, state, data, label_col, features_col):
        X = _matrix_f32(features_col.values)
        y = np.nan_to_num(np.asarray(label_col.values, dtype=np.float32))
        if state.rng is not None:
            # the SAME rng stream as the in-core sample: successive
            # chunk-length draws continue one PCG64 sequence, so the
            # selected rows match the monolithic fit's row-for-row
            sel = state.rng.random(len(y)) < self.check_sample
            X, y = X[sel], y[sel]
        if state.d is None:
            state.d = X.shape[1]
            state.vmeta = features_col.vmeta
        if len(y) == 0:
            return state
        state.pearson.update(X, y)
        uniq = np.unique(y)
        state.label_values = np.union1d(state.label_values, uniq)
        cap = (self._STREAM_LABEL_CAP_HARD if self.categorical_label
               else self.max_label_classes)
        if self.categorical_label is False \
                or len(state.label_values) > cap:
            state.label_sums = None
        if state.label_sums is not None:
            for uv in uniq:
                # gather stays float32 (no full f64 copy); the per-column
                # accumulation is float64 and exact for one-hot indicators
                sums = X[y == uv].sum(axis=0, dtype=np.float64)
                key = float(uv)
                prev = state.label_sums.get(key)
                state.label_sums[key] = (sums if prev is None
                                         else prev + sums)
        return state

    def merge_states(self, a, b):
        if b.d is None:
            return a
        if a.d is None:
            return b
        a.pearson.merge(b.pearson)
        a.label_values = np.union1d(a.label_values, b.label_values)
        if a.label_sums is None or b.label_sums is None:
            a.label_sums = None
        else:
            for k, v in b.label_sums.items():
                prev = a.label_sums.get(k)
                a.label_sums[k] = v if prev is None else prev + v
        return a

    def finish_fit(self, state) -> "SanityCheckerModel":
        from ..ops.stats import contingency_stats

        d = state.d or 0
        n = int(state.pearson.x.n) if state.pearson.c is not None else 0
        if n == 0 or d == 0:
            raise ValueError("SanityChecker streaming fit saw no rows")
        vmeta = state.vmeta or VectorMetadata("features", [])
        mean_h = np.asarray(state.pearson.x.mean)
        variance = np.asarray(state.pearson.x.variance(ddof=1))
        min_h = np.asarray(state.pearson.x.min)
        max_h = np.asarray(state.pearson.x.max)
        corr = state.pearson.correlation()

        uniq = state.label_values
        is_cat_label = (self.categorical_label
                        if self.categorical_label is not None
                        else len(uniq) <= min(self.max_label_classes,
                                              n // 2))
        group_cv: Dict[Tuple[str, Optional[str]], float] = {}
        if (is_cat_label and vmeta.size == d
                and state.label_sums is not None):
            tbl_full = np.stack([state.label_sums[float(v)] for v in uniq])
            for key, idxs in self._indicator_groups(vmeta).items():
                group_cv[key] = contingency_stats(
                    tbl_full[:, idxs])["cramersV"]

        return self._finalize(mean_h, variance, min_h, max_h, corr,
                              group_cv, vmeta, n, d)


class _ColumnFilterState:
    """What the two fitted column filters share: ``keep_indices`` with its
    index array (built once per model, not from the list in every call) and
    the persistence of the filtered vector metadata (_new_vmeta)."""

    @property
    def keep_indices(self) -> List[int]:
        return self._keep_indices

    @keep_indices.setter
    def keep_indices(self, keep):
        self._keep_indices = list(keep)
        self._keep = np.asarray(self._keep_indices, dtype=np.intp)

    def extra_state(self):
        return ({"new_vmeta": self._new_vmeta.to_json()}
                if self._new_vmeta is not None else {})

    def set_extra_state(self, state):
        if "new_vmeta" in state:
            self._new_vmeta = VectorMetadata.from_json(state["new_vmeta"])


class SanityCheckerModel(_ColumnFilterState, BinaryModel):
    input_types = (OPNumeric, OPVector)
    label_input_positions = (0,)

    """Index-filter on the feature vector (SanityChecker.scala:544-560)."""

    def __init__(self, keep_indices: List[int], uid: Optional[str] = None):
        super().__init__(operation_name="sanityCheck", output_type=OPVector,
                         uid=uid)
        self.keep_indices = keep_indices
        self._new_vmeta: Optional[VectorMetadata] = None

    def transform_columns(self, label_col, features_col) -> FeatureColumn:
        out = _select_columns(features_col.values, self._keep)
        vmeta = self._new_vmeta
        if vmeta is None and features_col.vmeta is not None:
            vmeta = features_col.vmeta.select(self.keep_indices)
            self._new_vmeta = vmeta
        return FeatureColumn(OPVector, out, vmeta=vmeta)


class MinVarianceFilter(BinaryEstimator):
    """Unlabeled variance-only filter (MinVarianceFilter.scala parity).

    Accepts (anything, features OPVector); the first input is ignored so the
    stage shape matches SanityChecker and DAG wiring stays uniform.
    """

    input_arity = (1, 2)
    # first input may be anything (ignored, SanityChecker shape parity) and
    # may legitimately be the label
    label_input_positions = (0,)

    def __init__(self, min_variance: float = 1e-5, uid: Optional[str] = None):
        super().__init__(operation_name="minVariance", output_type=OPVector,
                         uid=uid)
        self.min_variance = min_variance

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        features_col = cols[-1]
        X = _matrix_f32(features_col.values)
        variance = np.asarray(col_stats(X).variance)
        keep = [j for j in range(X.shape[1])
                if variance[j] >= self.min_variance]
        vmeta = features_col.vmeta
        self.metadata["summary"] = {
            "dropped": ([vmeta.column_names()[j] for j in range(X.shape[1])
                         if j not in set(keep)]
                        if vmeta and vmeta.size == X.shape[1] else []),
        }
        model = MinVarianceFilterModel(keep_indices=keep)
        model._new_vmeta = (vmeta.select(keep)
                            if vmeta and vmeta.size == X.shape[1] else None)
        return model

    # -- streaming fit: variance via Welford moments ------------------------

    supports_streaming_fit = True

    def begin_fit(self):
        from ..utils.sketches import WelfordMoments

        return {"moments": WelfordMoments(), "vmeta": None, "d": None}

    def update_chunk(self, state, data, *cols):
        features_col = cols[-1]
        X = _matrix_f32(features_col.values)
        if state["d"] is None:
            state["d"] = X.shape[1]
            state["vmeta"] = features_col.vmeta
        state["moments"].update(X)
        return state

    def merge_states(self, a, b):
        if b["d"] is None:
            return a
        if a["d"] is None:
            return b
        a["moments"].merge(b["moments"])
        return a

    def finish_fit(self, state) -> "MinVarianceFilterModel":
        if state["d"] is None:
            raise ValueError("MinVarianceFilter streaming fit saw no rows")
        d = state["d"]
        variance = np.asarray(state["moments"].variance(ddof=1))
        keep = [j for j in range(d) if variance[j] >= self.min_variance]
        vmeta = state["vmeta"]
        self.metadata["summary"] = {
            "dropped": ([vmeta.column_names()[j] for j in range(d)
                         if j not in set(keep)]
                        if vmeta and vmeta.size == d else []),
        }
        model = MinVarianceFilterModel(keep_indices=keep)
        model._new_vmeta = (vmeta.select(keep)
                            if vmeta and vmeta.size == d else None)
        return model


class MinVarianceFilterModel(_ColumnFilterState, BinaryModel):
    input_arity = (1, 2)
    label_input_positions = (0,)

    def __init__(self, keep_indices: List[int], uid: Optional[str] = None):
        super().__init__(operation_name="minVariance", output_type=OPVector,
                         uid=uid)
        self.keep_indices = keep_indices
        self._new_vmeta = None

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        features_col = cols[-1]
        vmeta = self._new_vmeta
        if vmeta is None and features_col.vmeta is not None:
            vmeta = features_col.vmeta.select(self.keep_indices)
        return FeatureColumn(OPVector,
                             _select_columns(features_col.values, self._keep),
                             vmeta=vmeta)
