"""Default vectorizers — the building blocks of ``transmogrify()``.

Reference stages (core/.../stages/impl/feature/):
 * numeric fills + null tracking — ``RealVectorizer``/``IntegralVectorizer``
   via ``VectorizerDefaults`` (Transmogrifier defaults :52-90)
 * ``OpOneHotVectorizer``/``OneHotEstimator`` — TopK pivot with minSupport,
   OTHER and null-indicator columns (OpOneHotVectorizer.scala)
 * ``OPCollectionHashingVectorizer`` — murmur3 feature hashing
   (OPCollectionHashingVectorizer.scala:59)
 * ``SmartTextVectorizer`` — cardinality-driven strategy per text field
   (SmartTextVectorizer.scala:60,79,207-247,323)
 * ``VectorsCombiner`` — concatenates OPVectors and merges their metadata
   (VectorsCombiner.scala)

All emit float32 (N, D) matrices (device-ready; bf16 conversion happens at
model ingestion) plus a ``VectorMetadata`` recording slot provenance.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import phases
from ..stages.base import (
    SequenceEstimator, SequenceModel, SequenceTransformer,
)
from ..types.columns import ColumnarDataset, FeatureColumn
from ..types.feature_types import (
    Binary, MultiPickList, OPNumeric, OPSet, OPVector, Text, TextList,
)
from ..utils.hashing import murmur3_32
from ..utils.profiling import count_fresh
from .vector_metadata import (
    NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMetadata, VectorMetadata,
)

__all__ = [
    "RealVectorizer", "RealVectorizerModel",
    "IntegralVectorizer", "IntegralVectorizerModel",
    "BinaryVectorizer",
    "OneHotVectorizer", "OneHotVectorizerModel",
    "TextHashingVectorizer",
    "SmartTextVectorizer", "SmartTextVectorizerModel", "TextStats",
    "MultiPickListVectorizer", "MultiPickListVectorizerModel",
    "VectorsCombiner",
]


def _vec_column(arr: np.ndarray, meta: VectorMetadata) -> FeatureColumn:
    return FeatureColumn(OPVector, np.asarray(arr, dtype=np.float32), vmeta=meta)


# ---------------------------------------------------------------------------
# Drift baselines — the train-side distribution snapshot a serving-side
# DriftMonitor (serving/drift.py) compares sampled traffic against.  Each
# fitting vectorizer exports ``metadata["drift_baseline"]`` =
# {raw feature name -> baseline dict}; numeric baselines carry Welford
# moments + StreamingHistogram bins (ndarrays -> persistence externalizes
# them into arrays.npz bit-exactly), categorical baselines carry the top
# category counts.  Baselines ride on the fitted model's metadata, so they
# survive save/load and registry hot-swaps with no extra artifact.
# ---------------------------------------------------------------------------

#: histogram bin budget for numeric baselines (the PSI grid source)
_BASELINE_BINS = 32
#: stride-sample cap for the IN-CORE baseline histogram: moments stay
#: exact; the histogram only needs the distribution's shape, and an
#: unbounded np.unique over 1M-row columns would tax the headline bench
_BASELINE_SAMPLE = 65536
#: categorical baselines keep at most this many categories (rest = OTHER)
_BASELINE_CATEGORIES = 64


def _numeric_baseline(mom, hist) -> Dict[str, Any]:
    """Codec-safe numeric baseline from a WelfordMoments + histogram."""
    empty = mom.mean is None
    return {
        "kind": "numeric", "n": float(mom.n),
        "mean": 0.0 if empty else float(mom.mean),
        "m2": 0.0 if empty else float(mom.m2),
        "min": 0.0 if empty else float(mom.min),
        "max": 0.0 if empty else float(mom.max),
        "histCentroids": np.asarray(hist.centroids, np.float64),
        "histCounts": np.asarray(hist.counts, np.float64),
    }


def _categorical_baseline(values, counts, total) -> Dict[str, Any]:
    return {"kind": "categorical", "n": float(total),
            "values": [str(v) for v in values],
            "counts": np.asarray(counts, np.float64)}


def _numeric_baseline_from_values(vals: np.ndarray) -> Dict[str, Any]:
    """In-core numeric baseline: exact moments + stride-sampled histogram."""
    from ..utils.sketches import WelfordMoments
    from ..utils.streaming_histogram import StreamingHistogram

    mom = WelfordMoments().update(vals)
    stride = max(1, int(len(vals)) // _BASELINE_SAMPLE)
    hist = StreamingHistogram(_BASELINE_BINS).update(vals[::stride])
    return _numeric_baseline(mom, hist)


def _numeric_baseline_from_counts(counts: Dict[float, int]) -> Dict[str, Any]:
    """Exact numeric baseline from a value->count map (the mode fitters)."""
    from ..utils.streaming_histogram import StreamingHistogram

    if not counts:
        return _numeric_baseline_from_values(np.zeros(0, np.float64))
    v = np.asarray(list(counts.keys()), np.float64)
    c = np.asarray(list(counts.values()), np.float64)
    n = float(c.sum())
    mean = float((v * c).sum() / n)
    hist = StreamingHistogram.from_value_counts(v, c, _BASELINE_BINS)
    return {
        "kind": "numeric", "n": n, "mean": mean,
        "m2": float((c * (v - mean) ** 2).sum()),
        "min": float(v.min()), "max": float(v.max()),
        "histCentroids": np.asarray(hist.centroids, np.float64),
        "histCounts": np.asarray(hist.counts, np.float64),
    }


def _categorical_baseline_from_sketch(sk) -> Dict[str, Any]:
    """Baseline from a TopKSketch: top categories by (count, first-seen)."""
    ordered = sorted(sk.counts.items(),
                     key=lambda kv: (-kv[1][0], kv[1][1]))
    top = ordered[:_BASELINE_CATEGORIES]
    return _categorical_baseline([k for k, _ in top],
                                 [ent[0] for _, ent in top], sk.offset)


def _pivot_fit(values, top_k: int, min_support: int):
    """(vocab, baseline) in ONE vectorized ``np.unique`` pass.

    The vocab half replaces the per-row Python ``Counter`` loop (the hot
    part of the OneHot/MultiPickList fit at scale) while reproducing
    ``Counter.most_common(top_k)`` EXACTLY, including its tie order: keys
    tie-break by insertion order = first occurrence, so rank by
    ``(-count, first_index)``.  Falls back to the Counter loop for values
    ``np.unique`` cannot sort (mixed/unhashable-by-comparison cells).
    The baseline half reuses the same pass for the drift snapshot.
    """
    arr = np.asarray(list(values) if not isinstance(values, np.ndarray)
                     else values, dtype=object)
    total = int(arr.size)
    if total == 0:
        return [], _categorical_baseline([], [], 0)
    try:
        uniq, first, cnt = np.unique(arr, return_index=True,
                                     return_counts=True)
    except TypeError:  # non-comparable mix: keep the legacy loop semantics
        counts = Counter(arr.tolist())
        vocab = [v for v, n in counts.most_common(top_k) if n >= min_support]
        top = counts.most_common(_BASELINE_CATEGORIES)
        return vocab, _categorical_baseline(
            [v for v, _ in top], [n for _, n in top], total)
    order = np.lexsort((first, -cnt))
    vocab = [uniq[i] for i in order[:top_k] if cnt[i] >= min_support]
    keep = order[:_BASELINE_CATEGORIES]
    return vocab, _categorical_baseline(uniq[keep], cnt[keep], total)


def _pivot_vocab(values, top_k: int, min_support: int) -> List:
    """TopK pivot vocabulary (see ``_pivot_fit`` for the semantics)."""
    return _pivot_fit(values, top_k, min_support)[0]


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

class RealVectorizer(SequenceEstimator):
    """Fill missing reals (mean or constant) + optional null-indicator slots.

    Transmogrifier default for Real/Percent/Currency: FillWithMean + null
    tracking (Transmogrifier.scala:52-90).
    """

    input_types = (OPNumeric,)
    # Welford-merged means are order-insensitive up to float noise
    streaming_order_insensitive = True

    def __init__(self, fill_with_mean: bool = True, fill_value: float = 0.0,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="vecReal", output_type=OPVector, uid=uid)
        self.fill_with_mean = fill_with_mean
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        fills = []
        baseline = {}
        for f, c in zip(self.input_features, cols):
            vals = np.asarray(c.values, dtype=np.float64)
            m = np.asarray(c.mask)
            present = np.nan_to_num(vals)[m]
            if self.fill_with_mean:
                fills.append(float(present.mean()) if m.any()
                             else self.fill_value)
            else:
                fills.append(float(self.fill_value))
            baseline[f.name] = _numeric_baseline_from_values(present)
        self.metadata["drift_baseline"] = baseline
        return RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)

    # -- streaming fit: Welford moments + histogram bins per column ---------
    # Chunked means match the in-core fit to ~1e-12 relative (documented:
    # chunked float64 summation order vs numpy's pairwise sum).  The
    # histogram feeds only the drift baseline, never the fill.

    supports_streaming_fit = True

    def begin_fit(self):
        from ..utils.sketches import WelfordMoments
        from ..utils.streaming_histogram import StreamingHistogram

        return [{"mom": WelfordMoments(),
                 "hist": StreamingHistogram(_BASELINE_BINS)}
                for _ in self.input_features]

    def update_chunk(self, state, data, *cols):
        for st, c in zip(state, cols):
            vals = np.nan_to_num(np.asarray(c.values, dtype=np.float64))
            present = vals[np.asarray(c.mask)]
            st["mom"].update(present)
            st["hist"].update(present)
        return state

    def merge_states(self, a, b):
        return [{"mom": sa["mom"].merge(sb["mom"]),
                 "hist": sa["hist"].merge(sb["hist"])}
                for sa, sb in zip(a, b)]

    def finish_fit(self, state):
        fills = [float(st["mom"].mean)
                 if self.fill_with_mean and st["mom"].n > 0
                 else float(self.fill_value) for st in state]
        self.metadata["drift_baseline"] = {
            f.name: _numeric_baseline(st["mom"], st["hist"])
            for f, st in zip(self.input_features, state)}
        return RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)


#: bytes of ``RealVectorizerModel``'s transposed group buffer
_GROUP_BUF_BYTES = 128 << 20


class RealVectorizerModel(SequenceModel):
    input_types = (OPNumeric,)

    def __init__(self, fills: List[float], track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(operation_name="vecReal", output_type=OPVector, uid=uid)
        self.fills = fills
        self.track_nulls = track_nulls

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        n = len(cols[0])
        width = len(cols) * (2 if self.track_nulls else 1)
        # Build through a small TRANSPOSED group buffer: writing column j of
        # a C-order (n, width) matrix directly strides `width` floats per
        # element — 500 wide columns at 1M rows turn into all-cache-miss
        # writes (measured 67 s host time at the 1M×500 bench).  Contiguous
        # buffer-row writes + grouped transpose flushes (destination runs of
        # GROUP floats per row) are ~10x faster, and the buffer bounds the
        # extra peak memory to ~128 MB instead of a full second matrix.
        out = np.empty((n, width), dtype=np.float32)
        group = int(np.clip(_GROUP_BUF_BYTES // max(n * 4, 1), 1, width))
        buf = np.empty((group, n), dtype=np.float32)
        count_fresh("vectorize.out", out.nbytes)
        count_fresh("vectorize.buf", buf.nbytes)
        meta = []
        j = 0
        flushed = 0
        # a traced run splits the transform by column group g: the columns'
        # own work up to the group's flush (``vectorize.fill[g]``), then the
        # transposed write, where the result is first touched
        # (``vectorize.flush[g]``)
        ph = phases("vectorize.fill[0]", cat="vectorize")

        def flush(upto):
            nonlocal flushed
            if upto > flushed:
                g = flushed // group
                ph.to(f"vectorize.flush[{g}]", cols=upto - flushed)
                out[:, flushed:upto] = buf[: upto - flushed].T
                flushed = upto
                if upto < width:
                    ph.to(f"vectorize.fill[{g + 1}]")

        def put(row_vals):
            nonlocal j
            if j - flushed == group:
                flush(j)
            np.copyto(buf[j - flushed], row_vals)
            j += 1

        with ph:
            for f, fill, c in zip(self.input_features, self.fills, cols):
                vals = np.asarray(c.values, dtype=np.float32)
                m = np.asarray(c.mask)
                row = np.where(m, vals, np.float32(fill))
                # clamp non-finite survivors (producers that don't fold
                # isfinite into the mask, or float32-cast overflow):
                # NaN -> 0, inf -> max
                np.nan_to_num(row, copy=False)
                put(row)
                meta.append(VectorColumnMetadata(f.name,
                                                 f.ftype.type_name()))
                if self.track_nulls:
                    put(~m)
                    meta.append(VectorColumnMetadata(
                        f.name, f.ftype.type_name(),
                        indicator_value=NULL_INDICATOR))
            flush(j)
        return _vec_column(out, VectorMetadata(self.get_output().name if self._output_feature else "real_vec", meta))


class IntegralVectorizer(SequenceEstimator):
    """Fill missing integrals with mode + null tracking (Transmogrifier default)."""

    input_types = (OPNumeric,)
    # merged mode counts are exact; ties break by smallest value, not order
    streaming_order_insensitive = True

    def __init__(self, fill_with_mode: bool = True, fill_value: int = 0,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="vecIntegral", output_type=OPVector, uid=uid)
        self.fill_with_mode = fill_with_mode
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        fills = []
        baseline = {}
        for f, c in zip(self.input_features, cols):
            vals = np.asarray(c.values)[np.asarray(c.mask)]
            counts: Dict[float, int] = {}
            if len(vals):
                uniq, cnt = np.unique(vals, return_counts=True)
                counts = {float(v): int(n) for v, n in zip(uniq, cnt)}
            if self.fill_with_mode and counts:
                fills.append(float(uniq[np.argmax(cnt)]))
            else:
                fills.append(float(self.fill_value))
            baseline[f.name] = _numeric_baseline_from_counts(counts)
        self.metadata["drift_baseline"] = baseline
        return RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)

    # -- streaming fit: mergeable value counts per column (mode fill) -------
    # EXACT vs in-core: the in-core argmax over ascending-sorted uniques
    # picks the smallest value among tied modes, replicated in finish_fit.

    supports_streaming_fit = True

    def begin_fit(self):
        return [dict() for _ in self.input_features]

    def update_chunk(self, state, data, *cols):
        for counts, c in zip(state, cols):
            vals = np.asarray(c.values)[np.asarray(c.mask)]
            if len(vals):
                uniq, cnt = np.unique(vals, return_counts=True)
                for v, n in zip(uniq, cnt):
                    counts[float(v)] = counts.get(float(v), 0) + int(n)
        return state

    def merge_states(self, a, b):
        for ca, cb in zip(a, b):
            for v, n in cb.items():
                ca[v] = ca.get(v, 0) + n
        return a

    def finish_fit(self, state):
        fills = []
        for counts in state:
            if self.fill_with_mode and counts:
                best = max(counts.items(), key=lambda kv: (kv[1], -kv[0]))
                fills.append(float(best[0]))
            else:
                fills.append(float(self.fill_value))
        self.metadata["drift_baseline"] = {
            f.name: _numeric_baseline_from_counts(counts)
            for f, counts in zip(self.input_features, state)}
        return RealVectorizerModel(fills=fills, track_nulls=self.track_nulls)


class BinaryVectorizer(SequenceTransformer):
    """Binary -> {0,1} with fill + null tracking (stateless)."""

    input_types = (OPNumeric,)

    def __init__(self, fill_value: bool = False, track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(operation_name="vecBinary", output_type=OPVector, uid=uid)
        self.fill_value = fill_value
        self.track_nulls = track_nulls

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        parts, meta = [], []
        for f, c in zip(self.input_features, cols):
            vals = np.nan_to_num(np.asarray(c.values, dtype=np.float64))
            m = np.asarray(c.mask)
            parts.append(np.where(m, vals, float(self.fill_value)))
            meta.append(VectorColumnMetadata(f.name, f.ftype.type_name()))
            if self.track_nulls:
                parts.append(~m)
                meta.append(VectorColumnMetadata(
                    f.name, f.ftype.type_name(), indicator_value=NULL_INDICATOR))
        return _vec_column(np.stack(parts, axis=1),
                           VectorMetadata("binary_vec", meta))


# ---------------------------------------------------------------------------
# Categorical pivot (one-hot)
# ---------------------------------------------------------------------------

class OneHotVectorizer(SequenceEstimator):
    """TopK pivot of categorical text with OTHER + null indicator columns.

    Reference OpOneHotVectorizer.scala; defaults TopK=20, minSupport=10
    (Transmogrifier.scala:55-60).
    """

    input_types = (Text,)

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, unseen_to_other: bool = True,
                 uid: Optional[str] = None):
        super().__init__(operation_name="pivotText", output_type=OPVector, uid=uid)
        self.top_k = top_k
        self.min_support = min_support
        self.track_nulls = track_nulls
        self.unseen_to_other = unseen_to_other

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        vocabs: List[List[str]] = []
        baseline = {}
        for f, c in zip(self.input_features, cols):
            # vectorized count (one np.unique) instead of the per-row
            # Counter loop; _pivot_fit reproduces most_common exactly and
            # yields the drift baseline from the same pass
            vals = c.values[np.not_equal(c.values, None)]
            vocab, base = _pivot_fit(vals, self.top_k, self.min_support)
            vocabs.append(vocab)
            baseline[f.name] = base
        self.metadata["drift_baseline"] = baseline
        return OneHotVectorizerModel(
            vocabs=vocabs, track_nulls=self.track_nulls,
            unseen_to_other=self.unseen_to_other)

    # -- streaming fit: mergeable top-k counting per column -----------------

    supports_streaming_fit = True

    def begin_fit(self):
        from ..utils.sketches import TopKSketch

        return [TopKSketch() for _ in self.input_features]

    def update_chunk(self, state, data, *cols):
        for sk, c in zip(state, cols):
            sk.add_chunk(c.values[np.not_equal(c.values, None)])
        return state

    def merge_states(self, a, b):
        return [sa.merge(sb) for sa, sb in zip(a, b)]

    def finish_fit(self, state):
        vocabs = [sk.top_k(self.top_k, self.min_support) for sk in state]
        self.metadata["drift_baseline"] = {
            f.name: _categorical_baseline_from_sketch(sk)
            for f, sk in zip(self.input_features, state)}
        return OneHotVectorizerModel(
            vocabs=vocabs, track_nulls=self.track_nulls,
            unseen_to_other=self.unseen_to_other)


class OneHotVectorizerModel(SequenceModel):
    input_types = (Text,)

    def __init__(self, vocabs: List[List[str]], track_nulls: bool = True,
                 unseen_to_other: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="pivotText", output_type=OPVector, uid=uid)
        self.vocabs = vocabs
        self.track_nulls = track_nulls
        self.unseen_to_other = unseen_to_other

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        n = len(cols[0])
        parts, meta = [], []
        for f, vocab, c in zip(self.input_features, self.vocabs, cols):
            index = {v: i for i, v in enumerate(vocab)}
            k = len(vocab)
            width = k + (1 if self.unseen_to_other else 0) + (1 if self.track_nulls else 0)
            block = np.zeros((n, width), dtype=np.float32)
            for row, v in enumerate(c.values):
                if v is None:
                    if self.track_nulls:
                        block[row, width - 1] = 1.0
                elif v in index:
                    block[row, index[v]] = 1.0
                elif self.unseen_to_other:
                    block[row, k] = 1.0
            parts.append(block)
            tname = f.ftype.type_name()
            for v in vocab:
                meta.append(VectorColumnMetadata(
                    f.name, tname, grouping=f.name, indicator_value=v))
            if self.unseen_to_other:
                meta.append(VectorColumnMetadata(
                    f.name, tname, grouping=f.name, indicator_value=OTHER_INDICATOR))
            if self.track_nulls:
                meta.append(VectorColumnMetadata(
                    f.name, tname, grouping=f.name, indicator_value=NULL_INDICATOR))
        out = np.concatenate(parts, axis=1) if parts else np.zeros((n, 0), np.float32)
        return _vec_column(out, VectorMetadata("onehot_vec", meta))


class MultiPickListVectorizer(SequenceEstimator):
    """TopK multi-hot pivot of MultiPickList sets (OpSetVectorizer parity)."""

    input_types = (OPSet,)

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, uid: Optional[str] = None):
        super().__init__(operation_name="pivotSet", output_type=OPVector, uid=uid)
        self.top_k = top_k
        self.min_support = min_support
        self.track_nulls = track_nulls

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        vocabs = []
        baseline = {}
        for f, c in zip(self.input_features, cols):
            # multi-valued cells: flatten once, then one vectorized
            # np.unique — the flattened order equals Counter.update(s)'s
            # insertion order, so ties still break identically
            flat = [v for s in c.values for v in s]
            vocab, base = _pivot_fit(flat, self.top_k, self.min_support)
            vocabs.append(vocab)
            baseline[f.name] = base
        self.metadata["drift_baseline"] = baseline
        return MultiPickListVectorizerModel(vocabs=vocabs, track_nulls=self.track_nulls)

    # -- streaming fit: mergeable top-k over flattened set elements ---------

    supports_streaming_fit = True

    def begin_fit(self):
        from ..utils.sketches import TopKSketch

        return [TopKSketch() for _ in self.input_features]

    def update_chunk(self, state, data, *cols):
        for sk, c in zip(state, cols):
            sk.add_chunk([v for s in c.values for v in s])
        return state

    def merge_states(self, a, b):
        return [sa.merge(sb) for sa, sb in zip(a, b)]

    def finish_fit(self, state):
        vocabs = [sk.top_k(self.top_k, self.min_support) for sk in state]
        self.metadata["drift_baseline"] = {
            f.name: _categorical_baseline_from_sketch(sk)
            for f, sk in zip(self.input_features, state)}
        return MultiPickListVectorizerModel(vocabs=vocabs,
                                            track_nulls=self.track_nulls)


class MultiPickListVectorizerModel(SequenceModel):
    input_types = (OPSet,)

    def __init__(self, vocabs: List[List[str]], track_nulls: bool = True,
                 uid: Optional[str] = None):
        super().__init__(operation_name="pivotSet", output_type=OPVector, uid=uid)
        self.vocabs = vocabs
        self.track_nulls = track_nulls

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        n = len(cols[0])
        parts, meta = [], []
        for f, vocab, c in zip(self.input_features, self.vocabs, cols):
            index = {v: i for i, v in enumerate(vocab)}
            k = len(vocab)
            width = k + 1 + (1 if self.track_nulls else 0)
            block = np.zeros((n, width), dtype=np.float32)
            for row, s in enumerate(c.values):
                if not s:
                    if self.track_nulls:
                        block[row, width - 1] = 1.0
                    continue
                hit = False
                for v in s:
                    if v in index:
                        block[row, index[v]] = 1.0
                        hit = True
                if not hit:
                    block[row, k] = 1.0
            parts.append(block)
            tname = f.ftype.type_name()
            for v in vocab:
                meta.append(VectorColumnMetadata(f.name, tname, grouping=f.name,
                                                 indicator_value=v))
            meta.append(VectorColumnMetadata(f.name, tname, grouping=f.name,
                                             indicator_value=OTHER_INDICATOR))
            if self.track_nulls:
                meta.append(VectorColumnMetadata(f.name, tname, grouping=f.name,
                                                 indicator_value=NULL_INDICATOR))
        return _vec_column(np.concatenate(parts, axis=1),
                           VectorMetadata("set_vec", meta))


# ---------------------------------------------------------------------------
# Hashing
# ---------------------------------------------------------------------------

def _tokenize(v: Optional[str]) -> List[str]:
    if v is None:
        return []
    return [t for t in _TOKEN_SPLIT.split(v.lower()) if t]


import re
_TOKEN_SPLIT = re.compile(r"[^\w']+", re.UNICODE)


def _row_tokens(v) -> List[str]:
    """Tokens for one cell: strings are word-tokenized; collection cells
    (lists/sets of arbitrary values, e.g. DateList epoch ints) hash their
    elements' string forms."""
    if v is None:
        return []
    if isinstance(v, str):
        return _tokenize(v)
    return [str(t) for t in v]


def _hash_rows(values, block: np.ndarray, offset: int, nf: int, seed: int,
               binary_freq: bool = False) -> np.ndarray:
    """Scatter token counts of one column into ``block[:, offset:offset+nf]``;
    returns a bool array marking rows with no tokens (null rows).
    Shared by TextHashingVectorizer and SmartTextVectorizerModel."""
    cache: Dict[str, int] = {}
    empty = np.zeros(len(values), dtype=bool)
    for row, v in enumerate(values):
        toks = _row_tokens(v)
        if not toks:
            empty[row] = True
            continue
        for t in toks:
            b = cache.get(t)
            if b is None:
                b = murmur3_32(t, seed) % nf
                cache[t] = b
            if binary_freq:
                block[row, offset + b] = 1.0
            else:
                block[row, offset + b] += 1.0
    return empty


class TextHashingVectorizer(SequenceTransformer):
    """Murmur3 feature hashing of tokenized text (stateless).

    Reference OPCollectionHashingVectorizer / hashed text path of
    SmartTextVectorizer; default 512 buckets (Transmogrifier.scala:55).
    ``shared_hash_space``: one bucket space for all inputs vs per-feature
    (HashSpaceStrategy parity).
    """

    def __init__(self, num_features: int = 512, binary_freq: bool = False,
                 shared_hash_space: bool = False, track_nulls: bool = True,
                 seed: int = 42, uid: Optional[str] = None):
        super().__init__(operation_name="textHash", output_type=OPVector, uid=uid)
        self.num_features = num_features
        self.binary_freq = binary_freq
        self.shared_hash_space = shared_hash_space
        self.track_nulls = track_nulls
        self.seed = seed

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        n = len(cols[0])
        nf = self.num_features
        n_spaces = 1 if self.shared_hash_space else len(cols)
        hashed = np.zeros((n, n_spaces * nf), dtype=np.float32)
        nulls = np.zeros((n, len(cols)), dtype=np.float32)
        for ci, c in enumerate(cols):
            offset = 0 if self.shared_hash_space else ci * nf
            empty = _hash_rows(c.values, hashed, offset, nf, self.seed,
                               self.binary_freq)
            nulls[:, ci] = empty
        meta: List[VectorColumnMetadata] = []
        if self.shared_hash_space:
            pf = ",".join(f.name for f in self.input_features)
            for b in range(nf):
                meta.append(VectorColumnMetadata(pf, "Text", grouping=None,
                                                 descriptor_value=f"hash_{b}"))
        else:
            for f in self.input_features:
                for b in range(nf):
                    meta.append(VectorColumnMetadata(f.name, f.ftype.type_name(),
                                                     descriptor_value=f"hash_{b}"))
        parts = [hashed]
        if self.track_nulls:
            parts.append(nulls)
            for f in self.input_features:
                meta.append(VectorColumnMetadata(f.name, f.ftype.type_name(),
                                                 indicator_value=NULL_INDICATOR))
        return _vec_column(np.concatenate(parts, axis=1),
                           VectorMetadata("hash_vec", meta))


# ---------------------------------------------------------------------------
# SmartTextVectorizer
# ---------------------------------------------------------------------------

class TextStats:
    """Streaming text statistics monoid (SmartTextVectorizer.scala:207-247)."""

    def __init__(self, max_card: int = 100):
        self.max_card = max_card
        self.value_counts: Counter = Counter()
        self.length_counts: Counter = Counter()
        self.n = 0
        self.n_null = 0
        self.saturated = False

    def update(self, v: Optional[str]):
        self.n += 1
        if v is None:
            self.n_null += 1
            return
        self.length_counts[len(v)] += 1
        if not self.saturated:
            self.value_counts[v] += 1
            if len(self.value_counts) > self.max_card:
                self.saturated = True

    @property
    def cardinality(self) -> int:
        return len(self.value_counts)

    def merge(self, other: "TextStats") -> "TextStats":
        out = TextStats(self.max_card)
        out.value_counts = self.value_counts + other.value_counts
        out.length_counts = self.length_counts + other.length_counts
        out.n = self.n + other.n
        out.n_null = self.n_null + other.n_null
        out.saturated = (
            self.saturated or other.saturated
            or len(out.value_counts) > out.max_card
        )
        return out

    # -- checkpoint codec hooks (workflow/checkpoint.py) --------------------

    def to_state(self) -> dict:
        """Counter insertion order is the ``most_common`` tie order, so
        keys/counts persist as parallel ordered lists."""
        return {"max_card": self.max_card,
                "values": list(self.value_counts.keys()),
                "value_ns": list(self.value_counts.values()),
                "lengths": list(self.length_counts.keys()),
                "length_ns": list(self.length_counts.values()),
                "n": self.n, "n_null": self.n_null,
                "saturated": self.saturated}

    @classmethod
    def from_state(cls, state: dict) -> "TextStats":
        out = cls(int(state["max_card"]))
        out.value_counts = Counter(dict(zip(state["values"],
                                            state["value_ns"])))
        out.length_counts = Counter(dict(zip(
            (int(k) for k in state["lengths"]), state["length_ns"])))
        out.n = int(state["n"])
        out.n_null = int(state["n_null"])
        out.saturated = bool(state["saturated"])
        return out


class SmartTextVectorizer(SequenceEstimator):
    """Cardinality-driven text strategy: pivot / hash / ignore per field.

    Reference SmartTextVectorizer.scala:60,79,323 — computes TextStats per
    field then chooses: categorical pivot when cardinality <= max_cardinality,
    murmur3 hashing otherwise, ignore when the field is effectively empty.
    """

    input_types = (Text,)

    PIVOT, HASH, IGNORE = "pivot", "hash", "ignore"

    def __init__(self, max_cardinality: int = 100, top_k: int = 20,
                 min_support: int = 10, num_hash_features: int = 512,
                 auto_detect_languages: bool = False,
                 min_fill_rate: float = 0.001, track_nulls: bool = True,
                 track_text_len: bool = False, seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(operation_name="smartTxtVec", output_type=OPVector, uid=uid)
        self.max_cardinality = max_cardinality
        self.top_k = top_k
        self.min_support = min_support
        self.num_hash_features = num_hash_features
        self.auto_detect_languages = auto_detect_languages
        self.min_fill_rate = min_fill_rate
        self.track_nulls = track_nulls
        self.track_text_len = track_text_len
        self.seed = seed

    def _decide(self, stats_list: List[TextStats]):
        """Strategy + vocab per field from fitted TextStats (shared by the
        in-core fit and the streaming finish — TextStats is already a
        mergeable monoid, SmartTextVectorizer.scala:207-247)."""
        strategies, vocabs = [], []
        baseline = {}
        for f, stats in zip(self.input_features, stats_list):
            fill = (stats.n - stats.n_null) / max(stats.n, 1)
            if fill < self.min_fill_rate:
                strategies.append(self.IGNORE)
                vocabs.append([])
            elif not stats.saturated and stats.cardinality <= self.max_cardinality:
                strategies.append(self.PIVOT)
                vocabs.append([
                    v for v, cnt in stats.value_counts.most_common(self.top_k)
                    if cnt >= self.min_support
                ])
            else:
                strategies.append(self.HASH)
                vocabs.append([])
            if not stats.saturated and stats.value_counts:
                # low-cardinality fields get a categorical drift baseline;
                # hashed/saturated text has no bounded category space
                top = stats.value_counts.most_common(_BASELINE_CATEGORIES)
                baseline[f.name] = _categorical_baseline(
                    [v for v, _ in top], [cnt for _, cnt in top],
                    stats.n - stats.n_null)
        self.metadata["text_strategies"] = dict(
            zip([f.name for f in self.input_features], strategies))
        self.metadata["drift_baseline"] = baseline
        return SmartTextVectorizerModel(
            strategies=strategies, vocabs=vocabs,
            num_hash_features=self.num_hash_features,
            track_nulls=self.track_nulls, track_text_len=self.track_text_len,
            seed=self.seed)

    def fit_columns(self, data: ColumnarDataset, *cols: FeatureColumn):
        stats_list = []
        for c in cols:
            stats = TextStats(self.max_cardinality)
            for v in c.values:
                stats.update(v)
            stats_list.append(stats)
        return self._decide(stats_list)

    # -- streaming fit: per-chunk TextStats merged left-to-right ------------
    # Exact vs in-core: saturation/decision logic only consults complete
    # counts (any chunk that saturates forces HASH in both paths), and
    # Counter.__add__ preserves global first-occurrence tie order.

    supports_streaming_fit = True

    def begin_fit(self):
        return [TextStats(self.max_cardinality) for _ in self.input_features]

    def update_chunk(self, state, data, *cols):
        new = []
        for stats, c in zip(state, cols):
            chunk_stats = TextStats(self.max_cardinality)
            for v in c.values:
                chunk_stats.update(v)
            new.append(stats.merge(chunk_stats))
        return new

    def merge_states(self, a, b):
        return [sa.merge(sb) for sa, sb in zip(a, b)]

    def finish_fit(self, state):
        return self._decide(state)


class SmartTextVectorizerModel(SequenceModel):
    input_types = (Text,)

    def __init__(self, strategies: List[str], vocabs: List[List[str]],
                 num_hash_features: int = 512, track_nulls: bool = True,
                 track_text_len: bool = False, seed: int = 42,
                 uid: Optional[str] = None):
        super().__init__(operation_name="smartTxtVec", output_type=OPVector, uid=uid)
        self.strategies = strategies
        self.vocabs = vocabs
        self.num_hash_features = num_hash_features
        self.track_nulls = track_nulls
        self.track_text_len = track_text_len
        self.seed = seed

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        n = len(cols[0])
        parts: List[np.ndarray] = []
        meta: List[VectorColumnMetadata] = []
        nf = self.num_hash_features
        for f, strat, vocab, c in zip(
            self.input_features, self.strategies, self.vocabs, cols
        ):
            tname = f.ftype.type_name()
            if strat == SmartTextVectorizer.IGNORE:
                pass
            elif strat == SmartTextVectorizer.PIVOT:
                index = {v: i for i, v in enumerate(vocab)}
                k = len(vocab)
                block = np.zeros((n, k + 1), dtype=np.float32)
                for row, v in enumerate(c.values):
                    if v is None:
                        continue
                    j = index.get(v)
                    if j is None:
                        block[row, k] = 1.0
                    else:
                        block[row, j] = 1.0
                parts.append(block)
                for v in vocab:
                    meta.append(VectorColumnMetadata(f.name, tname,
                                                     grouping=f.name,
                                                     indicator_value=v))
                meta.append(VectorColumnMetadata(f.name, tname, grouping=f.name,
                                                 indicator_value=OTHER_INDICATOR))
            else:  # HASH
                block = np.zeros((n, nf), dtype=np.float32)
                _hash_rows(c.values, block, 0, nf, self.seed)
                parts.append(block)
                for b in range(nf):
                    meta.append(VectorColumnMetadata(f.name, tname,
                                                     descriptor_value=f"hash_{b}"))
            if self.track_text_len:
                lens = np.array([
                    0.0 if v is None else float(len(v)) for v in c.values
                ], dtype=np.float32)[:, None]
                parts.append(lens)
                meta.append(VectorColumnMetadata(f.name, tname,
                                                 descriptor_value="textLen"))
            if self.track_nulls:
                nulls = np.array([v is None for v in c.values],
                                 dtype=np.float32)[:, None]
                parts.append(nulls)
                meta.append(VectorColumnMetadata(f.name, tname, grouping=f.name,
                                                 indicator_value=NULL_INDICATOR))
        out = (np.concatenate(parts, axis=1)
               if parts else np.zeros((n, 0), np.float32))
        return _vec_column(out, VectorMetadata("smart_text_vec", meta))


# ---------------------------------------------------------------------------
# Combiner
# ---------------------------------------------------------------------------

class VectorsCombiner(SequenceTransformer):
    """Concatenate OPVector inputs + merge metadata (VectorsCombiner.scala)."""

    input_types = (OPVector,)

    def __init__(self, uid: Optional[str] = None):
        super().__init__(operation_name="combineVecs", output_type=OPVector, uid=uid)

    def transform_columns(self, *cols: FeatureColumn) -> FeatureColumn:
        mats = [np.asarray(c.values, dtype=np.float32) for c in cols]
        metas = []
        for c, f in zip(cols, self.input_features):
            if c.vmeta is not None:
                metas.append(c.vmeta)
            else:
                metas.append(VectorMetadata(f.name, [
                    VectorColumnMetadata(f.name, f.ftype.type_name(),
                                         descriptor_value=f"slot_{i}")
                    for i in range(mats[len(metas)].shape[1])
                ]))
        out_name = self._output_feature.name if self._output_feature else "features"
        vm = VectorMetadata.flatten(out_name, metas)
        self.metadata["vector_metadata"] = vm.to_json()
        return _vec_column(np.concatenate(mats, axis=1), vm)
