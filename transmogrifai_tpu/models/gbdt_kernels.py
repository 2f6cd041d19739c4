"""Histogram decision-tree kernels in pure JAX — the TPU replacement for
XGBoost's C++ histogram GBDT core.

Reference dependency being replaced: xgboost4j JNI (SURVEY §2.11 — the one
genuinely native component of the reference; wrappers
OpXGBoostClassifier.scala:47 / OpXGBoostRegressor.scala:48) and Spark MLlib's
RandomForest/GBT (OpRandomForestClassifier.scala:58, OpGBTClassifier.scala:46).

Design (gpu_hist-style, adapted to XLA):
 * features pre-quantized to ``max_bins`` integer bins (quantile sketch on a
   sample, host-side; binned matrix lives in HBM as int8/int32)
 * trees grow level-wise; every level is one jitted kernel:
     - histogram: [grad(K), hess(K), count] summed into (nodes, B, D) by
       ONE formulation, a one-hot ``dot`` over rows (node one-hot times
       channels against the bins one-hot; ``_grow_tree_traced``), hoisted
       or row-blocked, full width or feature subset
     - split search: cumulative sums over bins -> best (feature, bin) per
       node by the standard gain formula  GL²/(HL+λ) + GR²/(HR+λ) − G²/(H+λ)
     - partition: rows move to ``2*node + go_right`` (no data movement — just
       an int vector update)
 * the tree is a *full* binary tree of ``max_depth`` levels in heap layout;
   nodes that fail min-gain/min-weight constraints emit an "always left"
   split (threshold = B), which keeps every shape static — no ragged trees,
   no recompilation across rounds/trees (SURVEY §7 hard part a).
 * multi-output targets (K>1) support multiclass GBDT (softmax, K trees'
   worth of leaf values per round in one pass) and RF classification
   (leaf = class histogram; variance gain over one-hot targets ≡ Gini gain).
"""
from __future__ import annotations

import contextlib
import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["TreeEnsemble", "quantile_bins", "apply_bins", "grow_tree",
           "grow_forest", "grow_forest_rf", "forest_chunk_size",
           "predict_tree", "predict_ensemble", "compile_depth_hint",
           "FeatureBundles", "bundle_features", "bundle_matrix",
           "unbundle_ensemble", "goss_plan", "hist_accum_bf16"]

# Shared compile-depth hint: a model-selection sweep compiles ONE tree-growth
# program at the grid's deepest max_depth and runs every candidate through it
# with a traced per-tree depth_limit, instead of one ~5-16 s XLA compile per
# distinct depth (the depth sets the static heap shapes).  Set via the
# ``compile_depth_hint`` context manager (ModelSelector does this around its
# candidate sweep).
_COMPILE_DEPTH_HINT: Optional[int] = None


@contextlib.contextmanager
def compile_depth_hint(depth: Optional[int]):
    """Grow trees with heap shapes sized for ``depth`` within the context."""
    global _COMPILE_DEPTH_HINT
    prev = _COMPILE_DEPTH_HINT
    _COMPILE_DEPTH_HINT = depth
    try:
        yield
    finally:
        _COMPILE_DEPTH_HINT = prev


def _resolve_compile_depth(max_depth: int) -> int:
    if _COMPILE_DEPTH_HINT is not None and _COMPILE_DEPTH_HINT >= max_depth:
        return _COMPILE_DEPTH_HINT
    return max_depth


def hist_accum_bf16() -> bool:
    """bf16 histogram ACCUMULATION (not just bf16 operands): the level's
    partial gradient/hessian sums accumulate in bf16 and upcast to f32
    only at the level cumsum.  Opt-in via ``TMOG_MATRIX_PRECISION=bf16``
    (the same knob that governs the bf16 matrix upload; ``f32`` is the
    escape hatch for both) and accelerator-gated like the operand flag —
    XLA-CPU emulates bf16 scalar-slow, and there is no bandwidth to save
    there.  The quality contract is the TM028 tolerance probe
    (``analysis.contracts.check_accum_tolerance``): accumulation drift
    must stay within 1e-3 of the f32-accumulated metric, proven under
    TMOG_CHECK=1 next to the TM024 pad-invariance gate."""
    import os

    return (os.environ.get("TMOG_MATRIX_PRECISION", "auto") == "bf16"
            and _accel_bf16())


@functools.lru_cache(maxsize=1)
def _accel_bf16() -> bool:
    """bf16 histogram operands only help on accelerators: XLA-CPU emulates
    bf16 dots scalar-slow (measured ~30x on the config-5 fit — 78.7 s f32
    vs 2556 s bf16 at 25k×1000 on one core), so CPU execution keeps f32
    regardless of the requested hist precision."""
    import jax

    return jax.default_backend() not in ("cpu",)


#: rows per histogram block in the streamed build; the per-block bins
#: one-hot is ROW_BLOCK × B·D values per tree under vmap — on the chip
#: (bf16 operands) 1.05 GB at 500 features × 32 bins, 0.2 GB at 100
#: features, twice that in f32.  forest_chunk_size and gbt_chain_chunk
#: budget it; since PR 29 the full-width form is fused into the histogram
#: dot on the chip and holds none of it in HBM (the budgets are unchanged)
ROW_BLOCK = 32768

#: engage sibling subtraction (left-child histograms only; right = parent −
#: left) at levels with at least this many slots — below it the bins one-hot
#: stream dominates and halving the node term buys nothing
SIBLING_MIN_SLOTS = 1024


class TreeEnsemble(NamedTuple):
    """Stacked trees: feat (T, 2^d-1) int32, thresh (T, 2^d-1) int32,
    leaf (T, 2^d, K) float32.  Heap layout: node i children 2i+1, 2i+2."""
    feat: jnp.ndarray
    thresh: jnp.ndarray
    leaf: jnp.ndarray

    @property
    def max_depth(self) -> int:
        # feat has 2^d - 1 internal nodes
        return int(np.log2(self.feat.shape[1] + 1))


# ---------------------------------------------------------------------------
# Quantile binning
# ---------------------------------------------------------------------------

#: columns a block of the host sketch sorts at once: 20 columns of the
#: 200,000-row sample are 16 MB of float32, a (columns, rows) array whose
#: rows the sort walks contiguously.  Step 0 of PR 32 (docs/performance.md,
#: "The host sketch") read 16 to 32 columns within 0.03 s of each other on
#: the chip's host and 50 and more a half slower, and one thread at 1.14 s,
#: under the 1.5 s that would have asked for a pool.
SKETCH_BLOCK_COLS = 20


def _sorted_column_blocks(X: np.ndarray, sample_rows: int, seed: int):
    """The sketch's sample, ``SKETCH_BLOCK_COLS`` columns at a time, as
    ``(first column, (columns, rows) array with every row SORTED)``; NaN
    sort last.  The sample is the rows ``default_rng(seed).choice`` draws
    when ``X`` has more than ``sample_rows`` (taken in row order: a
    quantile does not see their order), else every row.

    ``X`` is never sorted in place and no copy of the whole sample is made:
    the walk owns two buffers, the block's columns over all rows and over
    the sampled rows, and every block it yields is a view of the second
    that the next step overwrites.  (Allocating 16 MB a block instead cost
    0.1 to 0.5 s of page faults on the chip's host, by the state of the
    heap.)"""
    n, d = X.shape
    idx = None
    if n > sample_rows:
        idx = np.random.default_rng(seed).choice(n, sample_rows, replace=False)
        idx.sort()
    from ..utils.profiling import count_fresh

    wide = np.empty((min(SKETCH_BLOCK_COLS, d), n), X.dtype)
    sample = wide if idx is None else np.empty((len(wide), sample_rows),
                                               X.dtype)
    count_fresh("tree.sketch.buf", wide.nbytes)
    for j0 in range(0, d, SKETCH_BLOCK_COLS):
        cols = X[:, j0:j0 + SKETCH_BLOCK_COLS].T
        all_rows, block = wide[:len(cols)], sample[:len(cols)]
        np.copyto(all_rows, cols)
        if idx is not None:
            # every index is in range; "clip" only spares take the
            # buffering of ``out`` that its default mode does
            np.take(all_rows, idx, axis=1, out=block, mode="clip")
        block.sort(axis=1)
        yield j0, block


def _linear_ranks(m: int, qs: np.ndarray):
    """Floor rank, ceiling rank and weight of the quantiles ``qs`` of ``m``
    sorted values, as ``np.quantile``'s ``linear`` method takes them."""
    virtual = (m - 1) * qs
    lo = np.floor(virtual)
    lo[virtual >= m - 1] = -1
    hi = np.where(lo < 0, -1, lo + 1).astype(np.intp)
    lo = lo.astype(np.intp)
    return lo, hi, virtual - lo


def _lerp(a: np.ndarray, b: np.ndarray, t: np.ndarray) -> np.ndarray:
    """``np.quantile``'s interpolation, operation for operation (the
    difference in the values' own dtype, the rest in float64, the upper
    neighbour's form from ``t`` 0.5 up), so that the edges are bit-equal to
    ``np.quantile(..., method="linear")``'s."""
    diff = np.subtract(b, a)
    out = np.add(a, diff * t)
    np.subtract(b, diff * (1 - t), out=out, where=t >= 0.5,
                casting="unsafe", dtype=out.dtype)
    return out


def quantile_bins(X: np.ndarray, max_bins: int = 32,
                  sample_rows: int = 200_000, seed: int = 7) -> np.ndarray:
    """Per-feature quantile bin edges, shape (D, max_bins-1).

    Host-side on a row sample (the analogue of XGBoost's sketch); edges are
    deduplicated so constant/low-cardinality features waste no bins.  A
    column with a NaN has NaN for every edge.

    The edges are those of ``np.quantile(sample, qs, axis=0)`` bit for bit,
    computed a block of columns at a time: the block's sample rows gathered
    into a (columns, rows) array, each row sorted, the two neighbours of
    every rank ``q * (rows - 1)`` interpolated as numpy does: a fifth of
    the time of ``np.quantile`` down the strided axis of the whole sample.
    The span ``tree.prep.sketch`` around the callers' builds covers the
    gather, the sorts and the interpolation.
    """
    X = np.asarray(X)
    d = X.shape[1]
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    lo, hi, t = _linear_ranks(min(X.shape[0], sample_rows), qs)
    quantiles = np.empty((d, len(qs)), np.float64)
    for j0, block in _sorted_column_blocks(X, sample_rows, seed):
        q = _lerp(block[:, lo], block[:, hi], t)
        nan = np.isnan(block[:, -1])
        q[nan] = block[nan, -1:]
        quantiles[j0:j0 + len(block)] = q
    edges = quantiles.astype(np.float32)  # (D, B-1)
    # strictly increasing edges; collapse duplicates to +inf (unused bins)
    dup = np.diff(edges, axis=1) <= 1e-7
    edges[:, 1:][dup] = np.inf
    return edges


def quantile_bins_streaming(hists, max_bins: int = 32) -> np.ndarray:
    """Per-feature quantile bin edges from streamed histogram sketches.

    The out-of-core analogue of ``quantile_bins``: each feature's values
    were absorbed chunk-by-chunk into a ``StreamingHistogram``
    (utils/streaming_histogram.py — Ben-Haim/Tom-Tov bounded sketch, the
    design of XGBoost's external-memory quantile sketch, arXiv:1806.11248),
    and edges come from the sketch's quantiles.  Same output contract as
    ``quantile_bins``: (D, max_bins-1) float32, duplicate edges collapsed
    to +inf.

    Accuracy (documented tolerance, asserted in tests): with the default
    sketch budget of ``8 * max_bins`` histogram bins, each edge's empirical
    quantile rank is within ~0.05 of the exact rank — bin-edge placement
    noise on the order of one bin, immaterial to quantile-bin trees (the
    same argument as the reference sketch's eps).
    """
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    d = len(hists)
    edges = np.empty((d, max_bins - 1), np.float32)
    for j, h in enumerate(hists):
        edges[j] = np.array([h.quantile(q) for q in qs], np.float32)
    eps = 1e-7
    for j in range(d):
        e = edges[j]
        dup = np.concatenate([[False], np.diff(e) <= eps])
        edges[j] = np.where(dup | ~np.isfinite(e), np.inf, e)
    return edges


def streaming_histograms_for(chunks, hist_bins: int = 256):
    """Per-feature ``StreamingHistogram`` sketches over (n, D) chunk
    matrices — the sketch pass of a two-pass external-memory tree fit."""
    from ..utils.streaming_histogram import StreamingHistogram

    hists = None
    for chunk in chunks:
        M = np.asarray(chunk, np.float64)
        if hists is None:
            hists = [StreamingHistogram(hist_bins) for _ in range(M.shape[1])]
        for j in range(M.shape[1]):
            hists[j].update(M[:, j])
    return hists or []


@jax.jit
def apply_bins(X: jnp.ndarray, edges: jnp.ndarray) -> jnp.ndarray:
    """Quantized matrix (N, D) int32 in [0, B)."""
    X = jnp.asarray(X, jnp.float32)
    # count of edges <= x  (edges padded with +inf never trigger)
    return jnp.sum(X[:, :, None] > edges[None, :, :], axis=2).astype(jnp.int32)


#: features at least this fraction zero sketch their quantiles over the
#: NONZERO values (with an edge pinned at 0): an all-values sketch of a 95%-
#: zero feature collapses every sub-0.95 quantile to 0, leaving ~2 usable
#: bins — XGBoost's sparsity-aware sketch (the C++ core behind
#: OpXGBoostClassifier.scala:47) keeps full resolution on the nonzeros
SPARSE_SKETCH_ZERO_FRAC = 0.5


def quantile_bins_sparse_aware(X: np.ndarray, max_bins: int = 32,
                               sample_rows: int = 200_000,
                               seed: int = 7) -> np.ndarray:
    """Per-feature bin edges like ``quantile_bins``, but features that are
    mostly zero spend their quantiles on the nonzero values (plus a pinned
    0.0 edge separating the zeros)."""
    X = np.asarray(X)
    d = X.shape[1]
    n = min(X.shape[0], sample_rows)
    edges = np.full((d, max_bins - 1), np.inf, np.float32)
    qs_dense = np.linspace(0, 1, max_bins + 1)[1:-1]
    qs_sparse = np.linspace(0, 1, max_bins)[1:-1]       # B-2 qs + the 0 edge
    eps = 1e-7
    floats = np.issubdtype(X.dtype, np.inexact)
    for j0, block in _sorted_column_blocks(X, sample_rows, seed):
        for j, col in enumerate(block, start=j0):
            # NaN entries are excluded from the sketch (the binning
            # convention pins NaN to bin 0 — trees._device_bins), which
            # keeps a NaN-containing feature from poisoning every edge; in
            # the sorted column they are the tail, and the zeros one run
            if floats:
                col = col[:np.searchsorted(col, np.nan)]
            z0, z1 = np.searchsorted(col, 0), np.searchsorted(col, 0, "right")
            n_nz = len(col) - (z1 - z0)
            if n_nz and 1.0 - n_nz / n >= SPARSE_SKETCH_ZERO_FRAC:
                nz = np.concatenate([col[:z0], col[z1:]])
                lo, hi, t = _linear_ranks(n_nz, qs_sparse)
                e = np.unique(np.concatenate(
                    [[0.0], _lerp(nz[lo], nz[hi], t)]).astype(np.float32))
            elif len(col):
                lo, hi, t = _linear_ranks(len(col), qs_dense)
                e = _lerp(col[lo], col[hi], t).astype(np.float32)
                e = e[np.isfinite(e)]
                if len(e):
                    e = e[~np.concatenate([[False], np.diff(e) <= eps])]
            else:
                continue
            edges[j, :len(e)] = e[:max_bins - 1]
            # keep strictly increasing (dedup collapsed to +inf tail already)
    return edges


# ---------------------------------------------------------------------------
# Exclusive feature bundling (EFB) — histogram-width reduction
# ---------------------------------------------------------------------------
#
# transmogrify() emits wide one-hot / picklist indicator blocks: groups of
# mutually exclusive, mostly-zero columns.  The histogram kernels stream a
# (rows, B·D) bins one-hot per level — their bandwidth floor — and pay it
# for every indicator column even though at most one per group is nonzero
# in any row.  ``bundle_features`` packs mutually exclusive columns into
# shared histogram columns with per-member bin offsets (the LightGBM EFB
# algorithm applied to the already-binned matrix), shrinking D before any
# device work.
#
# Invertibility: the split search on a bundled column enumerates only
# PER-MEMBER splits.  Member m occupies bundle bins [base_m, e_m] (its
# original nonzero bins shifted by base_m - 1; bundle bin 0 = every
# member at its default/zero bin), and threshold t with end table
# ``E(t) = min{e_m : e_m > t}`` opens the interval split "bundle bin in
# (t, E(t)]" — exactly "member m's ORIGINAL bin > t - base_m + 1", a
# single original (feature, threshold) pair.  Grown trees therefore map
# back losslessly (``unbundle_ensemble``): the persisted TreeEnsemble
# routes on the ORIGINAL binned matrix and feature importances land on
# original column ids.  On conflict-free matrices the bundled fit is
# bit-for-tree identical to the unbundled fit (property-tested in
# tests/test_tree_grid.py); under bounded conflicts (two members nonzero
# in one row, admitted by ``max_conflict_rate``) the smaller encoded
# value loses that row — an approximation bounded by the conflict budget.

#: a column qualifies for bundling when at most this fraction of sampled
#: rows is nonzero (indicator blocks sit far below this)
EFB_MAX_ACTIVE_FRAC = 0.5
#: rows sampled for the exclusivity scan — the bundle DECISION is made on
#: the sample; the full matrix is re-encoded exactly
EFB_SAMPLE_ROWS = 65536
#: bundling must shrink the histogram width to at most this ratio to pay
#: for the re-encode pass (singleton-heavy matrices decline)
EFB_MIN_WIDTH_RATIO = 0.85


class FeatureBundles(NamedTuple):
    """The invertible bundling plan ``bundle_features`` produces.

    ``plan``: one entry per BUNDLED column — an ``int`` original column
    id (verbatim copy) or a tuple of ``(orig_id, base, end)`` member
    triples (member's original nonzero bins shifted to bundle bins
    [base, end]).  ``col_feat``/``col_thresh`` are the (D_b, B) split
    map back to original (feature, threshold); ``end_bin`` is the (B,
    D_b) per-threshold member-end table the growth kernel consumes.
    """

    plan: Tuple
    col_feat: np.ndarray      # (D_b, B) int32
    col_thresh: np.ndarray    # (D_b, B) int32
    end_bin: np.ndarray       # (B, D_b) int32
    n_orig: int
    n_bins: int

    @property
    def width(self) -> int:
        return int(self.col_feat.shape[0])

    @property
    def width_ratio(self) -> float:
        return self.width / max(self.n_orig, 1)

    def bundled_dd_mask(self, dd_mask: Optional[np.ndarray]) -> np.ndarray:
        """Default-direction eligibility in BUNDLED column space: bundle
        columns never learn a default direction (their bin 0 is 'every
        member default' — variant-b routing would not map back to a
        single original feature); singleton columns keep their flag."""
        out = np.zeros(self.width, bool)
        if dd_mask is None:
            return out
        dd = np.asarray(dd_mask, bool)
        for c, spec in enumerate(self.plan):
            if isinstance(spec, (int, np.integer)):
                out[c] = bool(dd[int(spec)])
        return out


def bundle_features(binned: np.ndarray, edges: np.ndarray, max_bins: int,
                    max_conflict_rate: float = 0.0,
                    sample_rows: int = EFB_SAMPLE_ROWS,
                    min_width_ratio: float = EFB_MIN_WIDTH_RATIO,
                    ) -> Optional[FeatureBundles]:
    """Greedy exclusive-feature-bundling plan over a binned matrix, or
    None when bundling would not shrink the histogram width enough.

    Host-side and sample-based like the quantile sketch: exclusivity is
    decided on a strided row sample (``max_conflict_rate`` bounds the
    admitted conflicts per bundle, as a fraction of sampled rows); the
    encode pass (:func:`bundle_matrix`) then runs exactly over all rows.
    Only columns whose zeros bin to bin 0 qualify — the bundle's shared
    bin 0 must mean "this member is at its default".
    """
    binned = np.asarray(binned)
    n, d = binned.shape
    if d < 3 or max_bins > 127:
        return None
    e = np.asarray(edges, np.float32)
    finite = np.isfinite(e)
    used_bins = finite.sum(axis=1) + 1                 # bins 0..u-1 occur
    # zeros must land in bin 0: the smallest finite edge is >= 0
    first_edge = np.where(finite, e, np.inf).min(axis=1)
    zero_ok = first_edge >= 0.0

    step = max(1, n // sample_rows)
    samp = binned[::step][:sample_rows]
    ns = samp.shape[0]
    active = samp != 0                                  # (ns, d)
    act_frac = active.mean(axis=0)
    cand = (used_bins >= 2) & zero_ok & (act_frac <= EFB_MAX_ACTIVE_FRAC)
    cand_ids = np.where(cand)[0]
    if len(cand_ids) < 2:
        return None

    budget = int(max_conflict_rate * ns)
    # greedy pack, densest candidate first (the LightGBM ordering)
    order = cand_ids[np.argsort(-act_frac[cand_ids], kind="stable")]
    bundles: List[dict] = []
    for j in order:
        uj = int(used_bins[j])
        aj = active[:, j]
        placed = False
        for b in bundles:
            if b["bins"] + (uj - 1) > max_bins:
                continue
            conflicts = int(np.count_nonzero(aj & b["active"]))
            if b["conflicts"] + conflicts > budget:
                continue
            b["members"].append(int(j))
            b["bins"] += uj - 1
            b["conflicts"] += conflicts
            b["active"] |= aj
            placed = True
            break
        if not placed:
            bundles.append({"members": [int(j)], "bins": 1 + (uj - 1),
                            "conflicts": 0, "active": aj.copy()})
    multi = {}
    for b in bundles:
        if len(b["members"]) >= 2:
            ms = sorted(b["members"])
            multi[ms[0]] = ms
    if not multi:
        return None
    in_multi = {j for ms in multi.values() for j in ms}
    width = d - len(in_multi) + len(multi)
    if width > min_width_ratio * d:
        return None

    B = int(max_bins)
    plan: List = []
    for j in range(d):
        if j in in_multi:
            if j in multi:                    # bundle sits at first member
                specs, base = [], 1
                for m in multi[j]:
                    um = int(used_bins[m])
                    specs.append((m, base, base + um - 2))
                    base += um - 1
                plan.append(tuple(specs))
        else:
            plan.append(j)
    d_b = len(plan)
    col_feat = np.zeros((d_b, B), np.int32)
    col_thresh = np.zeros((d_b, B), np.int32)
    end_bin = np.empty((B, d_b), np.int32)
    ts = np.arange(B, dtype=np.int32)
    for c, spec in enumerate(plan):
        if isinstance(spec, (int, np.integer)):
            col_feat[c] = int(spec)
            col_thresh[c] = ts
            end_bin[:, c] = B - 1
        else:
            ends = np.asarray([s[2] for s in spec], np.int32)
            # owner(t): the member whose end is the smallest end > t;
            # past the last member the interval (t, t] is empty (no split)
            owner = np.searchsorted(ends, ts, side="right")
            tail = owner >= len(spec)
            owner = np.minimum(owner, len(spec) - 1)
            end_bin[:, c] = np.where(tail, ts, ends[owner])
            feats = np.asarray([s[0] for s in spec], np.int32)
            bases = np.asarray([s[1] for s in spec], np.int32)
            col_feat[c] = feats[owner]
            col_thresh[c] = np.maximum(ts - bases[owner] + 1, 0)
    return FeatureBundles(plan=tuple(plan), col_feat=col_feat,
                          col_thresh=col_thresh, end_bin=end_bin,
                          n_orig=d, n_bins=B)


def bundle_matrix(bundles: FeatureBundles, binned: np.ndarray) -> np.ndarray:
    """Encode the (N, D) binned matrix into (N, D_b) bundled columns.

    Bundle bin = base_m + orig_bin - 1 for the active member; 0 when every
    member sits at its zero bin.  Conflicting rows (several members
    active — only possible under a nonzero conflict budget) keep the
    LARGEST encoded value, deterministically."""
    binned = np.asarray(binned)
    n = binned.shape[0]
    out = np.zeros((n, bundles.width), binned.dtype)
    for c, spec in enumerate(bundles.plan):
        if isinstance(spec, (int, np.integer)):
            out[:, c] = binned[:, int(spec)]
        else:
            enc = np.zeros(n, np.int32)
            for orig, base, _end in spec:
                v = binned[:, orig].astype(np.int32)
                np.maximum(enc, np.where(v > 0, base + v - 1, 0), out=enc)
            out[:, c] = enc.astype(binned.dtype)
    return out


def unbundle_ensemble(bundles: FeatureBundles, feat, thresh):
    """Map grown (T, nodes) split arrays from bundled column space back to
    ORIGINAL (feature, threshold) pairs — exact for every per-member
    interval split the bundled gain search emits.  No-split sentinels
    (thresh == B) and default-direction splits (negative thresholds, only
    ever emitted on singleton columns) pass through unchanged."""
    feat = np.asarray(feat)
    thresh = np.asarray(thresh)
    B = bundles.n_bins
    t_id = np.clip(thresh, 0, B - 1)
    f_orig = bundles.col_feat[feat, t_id]
    t_orig = bundles.col_thresh[feat, t_id]
    passthrough = (thresh >= B) | (thresh < 0)
    f_out = np.where(passthrough, bundles.col_feat[feat, 0], f_orig)
    t_out = np.where(passthrough, thresh, t_orig)
    return f_out.astype(np.int32), t_out.astype(np.int32)


# ---------------------------------------------------------------------------
# GOSS — gradient-based one-side sampling (deep boosted candidates)
# ---------------------------------------------------------------------------

#: GOSS only engages at/above this tree depth: shallow trees are cheap
#: and the sampling noise isn't worth it (the ISSUE 11 contract)
GOSS_MIN_DEPTH = 8
#: below this many rows the gather outweighs the histogram savings
GOSS_MIN_ROWS = 20000
#: keep fraction by |gradient| / uniform-sample fraction of the rest —
#: the LightGBM defaults' neighbourhood (a=0.2, b=0.2, amp=(1-a)/b)
GOSS_TOP_FRAC = 0.2
GOSS_REST_FRAC = 0.2


def goss_plan(n_rows: int, min_depth: int) -> Optional[Tuple[int, int]]:
    """Static (k_top, k_rest) GOSS row budget for a launch whose
    shallowest candidate has ``min_depth``, or None when GOSS stays off.
    ``TMOG_GOSS``: '1' forces on (row gate bypassed; the depth gate is
    part of the contract and always holds), '0' forces off, 'auto'
    (default) engages at depth >= 8 and n >= GOSS_MIN_ROWS.  Resolved by
    the non-jitted callers so the budget is a static jit-cache-key arg."""
    import os

    v = os.environ.get("TMOG_GOSS", "auto")
    if v == "0" or min_depth < GOSS_MIN_DEPTH:
        return None
    if v != "1" and n_rows < GOSS_MIN_ROWS:
        return None
    k_top = max(1, int(round(GOSS_TOP_FRAC * n_rows)))
    k_rest = max(1, int(round(GOSS_REST_FRAC * n_rows)))
    if k_top + k_rest >= n_rows:
        return None
    return k_top, k_rest


def _goss_select(ga, key, k_top: int, k_rest: int):
    """One chain/tree's GOSS row selection: the ``k_top`` rows of largest
    |gradient| kept at weight 1, ``k_rest`` uniform samples of the rest
    amplified by (N - k_top)/k_rest — the standard unbiasedness weights.
    Returns (row indices (k_top+k_rest,), per-row multipliers);
    deterministic in ``key``."""
    with jax.named_scope("goss.select"):
        _, top_idx = lax.top_k(ga, k_top)
        r = jax.random.uniform(key, ga.shape)
        r = r.at[top_idx].set(-1.0)             # exclude kept rows
        _, rest_idx = lax.top_k(r, k_rest)
        idx = jnp.concatenate([top_idx, rest_idx])
        amp = (ga.shape[0] - k_top) / k_rest
        mult = jnp.concatenate([jnp.ones(k_top, jnp.float32),
                                jnp.full(k_rest, amp, jnp.float32)])
    return idx, mult


def default_dir_mask(edges) -> np.ndarray:
    """(D,) bool: features whose bin 0 is a GENUINE missing/absent bucket —
    their smallest finite bin edge is the sparse-aware sketch's pinned 0.0
    (zeros and NaNs land in bin 0, real values in bins >= 1).  Only these
    features may learn a default direction: on a dense feature bin 0 is
    merely the lowest quantile."""
    e = np.asarray(edges, np.float64)
    first = np.where(np.isfinite(e), e, np.inf).min(axis=1)
    return first == 0.0


def _route_right(x, t):
    """THE split routing rule, shared by growth and prediction.

    ``t`` in [0, B-1): go right iff bin > t.  ``t == B``: no-split
    sentinel (always left).  ``t < 0``: default-direction split (XGBoost
    missing-value semantics) — effective threshold -t-1, and the bin-0
    (missing/absent) bucket routes RIGHT instead of left."""
    dr = t < 0
    te = jnp.where(dr, -t - 1, t)
    return (x > te) | (dr & (x == 0))


#: a level goes by the select (``route_level``, ``leaf_by_slot``) while it
#: has at most this many slots, and at most four a row; past that the select's
#: M compares a row cost more than the gathers they replace.  One v5e, 8
#: trees over 250,000 x 500 int8 (PERF.md §6, PR 31): a (slot, row) pair of
#: the select costs 12 ps, a (row, level) of the three one-element gathers
#: 22 ns, so a level breaks even at some 1,800 slots (depth 10: 0.048 s
#: against 0.48 as gathers; 12: 0.19 | 0.57; 14: 0.75 | 0.67).  A 1-row
#: call gathers one element a SLOT in the select form (200 trees of depth
#: 12: 7.5 ms against 0.8), so there the slots are held to four a row.
SELECT_MAX_SLOTS = 1024
SELECT_MAX_SLOTS_A_ROW = 4

#: bytes of the binned matrix's rows one step of a level's slot loop holds
#: per tree: a level's M slots go through the select in blocks of
#: ``ROUTE_BLOCK_BYTES // (rows x itemsize)`` slots, so that nothing of size
#: M x rows reaches HBM (under ``vmap`` the block is held once per tree of
#: the launch).  16 MiB, of 1 MiB ... the whole level: a chain launch of the
#: cell 0.98 s at 4 MiB, 0.91 at 16, 0.89 at 64; a scoring call 0.095 s at 1,
#: 0.054 at 4, 0.048 at 16, 0.061 at 64, 0.048 whole (one v5e, PR 31).
ROUTE_BLOCK_BYTES = 16 << 20


def _by_select(m: int, rows: int) -> bool:
    """Does a level of ``m`` slots over ``rows`` rows go by the select?
    Static: a function of shapes only."""
    return m <= min(SELECT_MAX_SLOTS, SELECT_MAX_SLOTS_A_ROW * rows)


def _over_slot_blocks(block, m: int, rows: int, pair_bytes: int, init, merge):
    """Fold ``block(first slot, slots)`` over a level's slot blocks of
    ``pair_bytes`` a (slot, row) (``m`` is a power of two and so is the
    block)."""
    sb = max(1, min(m, ROUTE_BLOCK_BYTES // max(rows * pair_bytes, 1)))
    sb = 1 << (sb.bit_length() - 1)
    if sb == m:
        return merge(init, block(0, m))
    return lax.fori_loop(
        0, m // sb, lambda i, acc: merge(acc, block(i * sb, sb)), init)


def _slot_ends(bundle_end, thresh_l, fid):
    """(M,) end bin of each slot's bundled split, the owner member's (rows
    past it belong to OTHER members of the bundle and route left); None
    where the matrix is not bundled."""
    if bundle_end is None:
        return None
    return bundle_end[jnp.clip(thresh_l, 0, bundle_end.shape[0] - 1), fid]


def route_level(binned_T, slot, fid, thresh_l, end_l=None):
    """Which rows go right at one level: a select over the level's slots.

    ``binned_T`` (d, rows) is the binned matrix ROWS-MINOR; ``slot`` (rows,)
    each row's slot in [0, M); ``fid`` / ``thresh_l`` (M,) the feature a
    slot tests (a row of ``binned_T``) and its threshold; ``end_l`` (M,)
    the owner member's end bin of a bundled split (``bundle_end``) or None.
    Returns (rows,) bool by ``_route_right``, the one routing rule.

    A row does not ask "which node am I in, which feature does it test,
    what is my bin there" (three dependent gathers of ONE element a row:
    22-40 ns a row and level on a v5e, whatever the table's size).  The
    level's few slots each fetch their WHOLE feature row (``binned_T[fid]``:
    M contiguous rows), every row is compared under every slot's threshold
    and keeps the answer of its own slot: M byte-compares a row in place of
    the gathers, and no element gather is left.  Past ``_by_select``'s
    bound (a level of 2,048 slots and more, or more than four slots a row)
    the compares cost more than the gathers, and the level asks a row at a
    time as before."""
    m = fid.shape[0]
    rows = slot.shape[0]
    if not _by_select(m, rows):
        f = fid[slot]
        x = jnp.take_along_axis(binned_T, f[None, :], 0)[0]
        go = _route_right(x, thresh_l[slot])
        return go if end_l is None else go & (x <= end_l[slot])
    whole_rows = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))

    def block(s, sb):
        f = lax.dynamic_slice_in_dim(fid, s, sb).reshape(sb, 1)
        t = lax.dynamic_slice_in_dim(thresh_l, s, sb).reshape(sb, 1)
        cols = lax.gather(binned_T, f, whole_rows, (1, rows),
                          mode=lax.GatherScatterMode.CLIP)   # (sb, rows)
        go = _route_right(cols, t)
        if end_l is not None:
            go = go & (cols <= lax.dynamic_slice_in_dim(
                end_l, s, sb).reshape(sb, 1))
        mine = slot == s + lax.broadcasted_iota(slot.dtype, (sb, rows), 0)
        return jnp.any(go & mine, axis=0)

    return _over_slot_blocks(block, m, rows, binned_T.dtype.itemsize,
                             jnp.zeros(rows, bool), jnp.logical_or)


def leaf_by_slot(leaf, node):
    """``leaf[node].T``: (K, rows) from ``leaf`` (L, K) and ``node`` (rows,)
    in [0, L).  Within ``_by_select``'s bound by a select, as
    ``route_level`` goes: every row keeps its own leaf's value out of all L
    (one non-zero term a sum, so the result is exact)."""
    n_leaves, k = leaf.shape
    rows = node.shape[0]
    if not _by_select(n_leaves, rows):
        return leaf[node].T

    def block(s, sb):
        v = lax.dynamic_slice_in_dim(leaf, s, sb).T.reshape(k, sb, 1)
        mine = node == s + lax.broadcasted_iota(node.dtype, (sb, rows), 0)
        return jnp.where(mine, v, 0.0).sum(axis=1)

    return _over_slot_blocks(block, n_leaves, rows, 4 * k,
                             jnp.zeros((k, rows), leaf.dtype), jnp.add)


def _grow_tree_traced(binned, G, H, C, feat_mask, depth_limit,
                      max_depth: int, n_bins: int, lam, min_child_weight,
                      min_info_gain, min_instances, newton_leaf,
                      learning_rate, hist_bf16: bool = False,
                      all_reduce=None, min_gain_raw=None,
                      bag_mode: str = "none", feat_idx=None,
                      default_dir: bool = False, dd_mask=None,
                      bundle_end=None, acc_bf16: bool = False,
                      binned_T=None, prune_outputs: bool = False):
    """One whole tree under trace: Python-unrolled loop over levels.

    ``binned_T``: the (d, N) transpose of ``binned`` that the level routing
    reads (``route_level``), from a caller for whom it is loop-invariant
    (the chain scan on all rows); taken here, once a tree, otherwise (a
    GOSS tree's own ``binned[idx]``).

    ``bundle_end``: optional (B, D) int32 per-(threshold, feature) member
    END-bin table from :func:`bundle_features` — the matrix is then in
    BUNDLED column space and every split candidate becomes the per-member
    interval split "bin in (t, E(t)]" (right) vs everything else (left),
    which maps back to a single ORIGINAL (feature, threshold) pair.
    Unbundled columns carry E = B-1, making the interval form bit-
    identical to the standard "bin > t" split.  Incompatible with
    ``feat_idx`` (callers guard); ``default_dir`` composes only through a
    ``dd_mask`` that excludes bundle columns (FeatureBundles.
    bundled_dd_mask).

    ``acc_bf16``: accumulate the histogram partials in bf16 (operands
    already ride ``hist_bf16``) and upcast to f32 at the level cumsum —
    the TMOG_MATRIX_PRECISION=bf16 opt-in, quality-gated by the TM028
    tolerance probe.

    ``prune_outputs`` (static): ALSO emit, as the 4th element (an empty
    tuple otherwise), what reads a candidate of a lower ``max_depth`` or a
    higher ``min_info_gain`` off this tree (``prune_rf_grid``):
    ``(level_values, gate_ratio, unsplit_feat)``.

    * ``level_values``: one (2^l, K) array a level 0..max_depth-1, the value
      of every node of that level were it a leaf.  For level-wise greedy
      growth, splits at level l are independent of deeper levels, so a
      shallower ``max_depth`` candidate is exactly this tree truncated at
      its depth; a node's value sums come FREE from the level's own
      histogram totals (the sum over the bins of any feature's column).
    * ``gate_ratio``: the heap of per-node ``best_gain / node_w`` in f32
      (``-inf`` where no valid split has a positive finite gain), the very
      number ``ok`` compares with ``min_info_gain``.  The gate takes no
      part in choosing a node's split (``valid`` holds every other
      constraint, the argmax is taken, only then is the ratio compared),
      and a node that fails it keeps all its rows in its left child, which
      sees the same histogram and fails again: the tree of a higher gate is
      this tree with every node of a ratio under that gate cut.
    * ``unsplit_feat``: the feature id this tree writes at a node it does
      not split (its first subset column).

    So one grown tree serves every depth and every gate of a hyperparameter
    grid (the r3 default grid grew the 3-gate x 3-depth product 9x
    redundantly).

    This is the dispatch-collapsing design: a per-level kernel approach
    costs depth×trees host dispatches and as many programs to compile;
    here a full tree (and, via vmap, a whole chunk of trees) is ONE XLA
    program.  Two scaling decisions keep deep trees cheap:

    * **Node compaction**: a level has at most ``min(2^level, N)`` populated
      nodes, so when ``2^level`` exceeds the row count the level's node ids
      are compacted (sort + first-occurrence ranks) into ``next_pow2(N)``
      slots.  Histogram/split work therefore scales with the DATA, not with
      ``2^depth`` — a depth-12 tree on 891 rows does 1024-slot levels, not
      2048-slot ones, and depth 16+ stays flat.
    * **Tile-friendly layout**: per-channel histograms are shaped
      ``(slots, bins, features)`` so the minor axis is the wide feature
      dimension (pads to the 128-lane tile at ~1.2×), not the 32-bin axis
      (which pads 4×, and OOMed a 6-tree chunk at depth 12).  The bins
      one-hot that feeds them is ``(bins, features, rows)``: rows, the
      axis the dot contracts, on the lanes, bins and features as two major
      axes that are never merged (``bins_onehot`` below says what the
      merged form cost).
    * **MXU histograms**: the histogram is a one-hot matmul —
      ``(slots, N) · (bins, features, N)`` contracted over N, all channels
      in one dot — instead of a scatter-add.  XLA lowers TPU scatters to
      sorts (measured ~5 ms per (N, D) scatter; ~1800 of them per 50-tree
      depth-12 fit ≈ 8 s), while the matmul form rides the systolic array
      and the full-width bins one-hot is a broadcast-compare of the int8
      block that the compiler fuses into the dot, so it is built in
      registers and never reaches HBM (a level of a 32,768-row block of two
      500-column chains: 27 ms before, 1.4 ms at one slot and 13 ms at 512
      slots a channel after, where the MXU bounds it; one v5e, PR 29).
    """
    # Feature-subset fast path (RF's featureSubsetStrategy): when the tree
    # uses only ``msub`` of D features, build histograms at width msub
    # instead of D.  The per-level (rows, B·msub) bins one-hot is the
    # kernel's bandwidth bottleneck (measured: per-level cost is flat in
    # slot count and linear in D at 100k×500), so sqrt-D subsetting cuts
    # the histogram traffic ~D/msub (≈23x at D=500).  The one-hot is
    # gathered DIRECTLY into its flat (rows, B·msub) layout from the
    # full-width matrix (``col_idx`` repeats the subset ids per bin):
    # materializing a (rows, msub)-gathered copy and a (rows, B, msub)
    # one-hot put msub=22 on the minor axis, padding every row to the
    # 128-lane tile (5.8x wasted stream — VERDICT r4 #3); the flat minor
    # axis B·msub (704 at 32 bins) pads only ~1.09x.
    # (hist_bf16 is resolved by the non-jitted callers — grow_tree,
    # grow_forest_rf, grow_rf_grid, the GBT fitters — as
    # ``requested and _accel_bf16()`` so the backend gate participates in
    # the jit cache key; resolving it here at trace time let a CPU-traced
    # f32 executable be silently reused under a bf16 key and vice versa.)
    binned_full = binned
    if binned_T is None:
        binned_T = binned.T
    n = binned.shape[0]
    if feat_idx is not None:
        feat_idx = feat_idx.astype(jnp.int32)
        d = feat_idx.shape[0]
        # flat one-hot column c = b*msub + j  <->  (bin b, subset slot j):
        # the SAME b-major/j-minor order as the reshape form, so histogram
        # numerics are bit-identical to the gathered formulation
        col_idx = jnp.tile(feat_idx, n_bins)               # (B·msub,)
        bin_vec = jnp.repeat(jnp.arange(n_bins, dtype=binned.dtype), d)
        feat_mask = jnp.ones(d, bool)
    else:
        d = binned.shape[1]
        col_idx = None
        bin_vec = None
    k = G.shape[1]
    B = n_bins
    n_cap = 1 << int(np.ceil(np.log2(max(n, 2))))   # static pow2 ≥ N
    if all_reduce is not None:
        # sharded growth: shards see different rows, so shard-local node
        # compaction would produce inconsistent slot<->node mappings; grow
        # with the full 2^level slot layout and psum the histograms
        n_cap = 1 << 62
    # Bagged forests have structurally redundant channels: H_i == C (hessian
    # IS the bag weight), and for one-hot classification targets the class
    # gradients sum to the counts (Σ_i G_i == C).  Building only the
    # irreducible channels cuts the histogram matmul count from 2K+1 to K
    # ("onehot": K-1 grads + counts) or K+1 ("bagged" regression: K grads +
    # counts) — a 2.5x FLOP cut for binary RF, the sweep's hot op.  The
    # dropped histograms are reconstructed exactly below (same partial sums,
    # one extra subtraction of rounding-level error).
    if bag_mode == "onehot":
        chans = [G[:, i] for i in range(k - 1)] + [C]
    elif bag_mode == "bagged":
        chans = [G[:, i] for i in range(k)] + [C]
    elif bag_mode == "newton":
        # count channel dropped (XGBoost semantics): callers guarantee
        # min_instances <= 1 and min_info_gain == 0 — XGB's own gating is
        # min_child_weight + gamma, both hessian/raw-gain based — so count
        # gating and per-node-weight gain normalization are inert, and 2K
        # channels instead of 2K+1 cut the per-chain histogram dot and
        # one-hot stream by a third (binary GBT: 3 -> 2)
        chans = [G[:, i] for i in range(k)] + [H[:, i] for i in range(k)]
        min_instances = jnp.float32(0.0)   # CL proxy is hessian mass
    else:
        chans = [G[:, i] for i in range(k)] \
            + [H[:, i] for i in range(k)] + [C]
    nchan = len(chans)
    # RF grad/hess are bag-weight × one-hot class values — exact in bf16
    # for integer weights, ≲1e-3 relative under fractional balancer weights,
    # either way immaterial to split selection; DEFAULT precision (bf16 in,
    # f32 accumulate) runs the histogram dots at ~2x MXU throughput.  GBT
    # gradients are continuous and compound across rounds: keep HIGHEST.
    dot_prec = (jax.lax.Precision.DEFAULT if hist_bf16
                else jax.lax.Precision.HIGHEST)

    # One-hot operands materialize in bf16 under ``hist_bf16`` — the 0/1
    # one-hots are exact in bf16 and the stream (the kernel's bandwidth
    # floor) halves; channel values ride the already-accepted hist_bf16
    # precision contract.
    hdt = jnp.bfloat16 if hist_bf16 else jnp.float32
    # histogram ACCUMULATION dtype (preferred_element_type of the dots and
    # the row-block scan carry); f32 unless the TM028-gated opt-in is on
    adt = jnp.bfloat16 if acc_bf16 else jnp.float32

    # Row-blocked histogram build: the bins one-hot is B·D values a row — at
    # 1M×500×32 bins that is 32 GB in bf16 if materialized whole, so rows
    # stream through in blocks with the (nchan, M, B, D) accumulators carried
    # by lax.scan.  Small inputs keep the single hoisted one-hot (no scan
    # overhead).
    def bins_onehot(rows_b):
        """Bins one-hot of a block of rows, in the layout the histogram dot
        contracts.

        Full width: the one-hot of the (rows, d) block is (B, d, rows) —
        rows on the minor (lane) axis, the axis the dot contracts, and B
        and d left as two major axes that nothing merges (the transpose
        costs nothing in the loop: the compiler lays the scanned blocks out
        rows-minor, one copy a tree).  The former flat form, ``(rows, B, d) -> (rows,
        B·d)``, is no bitcast in tiled memory unless d fills whole tiles:
        at d = 500 the chip wrote the bins broadcast over B (int32, 4.2 GB
        a call of two chains), copied all of it into the flat layout
        (9.2 ms, 0.44 s a level a train) and only then read it in the dot.
        Written this way it is a pure broadcast-compare of the int8
        block, which XLA fuses INTO the dot: the one-hot never reaches HBM
        (PERF.md §6, PR 29).

        Subset path: one gather from the (well-tiled) full-width matrix
        straight into the flat (rows, B·msub) layout — no msub-minor
        intermediate."""
        if col_idx is not None:
            return (rows_b[:, col_idx] == bin_vec[None, :]).astype(hdt)
        return (rows_b.T[None, :, :]
                == jnp.arange(B, dtype=rows_b.dtype)[:, None, None]
                ).astype(hdt)

    # per-slot histogram layout of the dots below: (B, d) straight from the
    # full-width dot's two free axes, flat B·msub on the subset path (its
    # minor axis msub would pad every (slot, bin) row to the 128-lane tile)
    hist_dims = (B, d) if col_idx is None else (B * d,)

    def hist_dot(wnode, oh_bins):
        """(nchan·Mh, *hist_dims): every (channel, slot) column of ``wnode``
        (rows, nchan·Mh) against the bins one-hot, contracted over rows."""
        rows_axis = 2 if col_idx is None else 0
        return jax.lax.dot_general(
            wnode.T, oh_bins, (((1,), (rows_axis,)), ((), ())),
            precision=dot_prec, preferred_element_type=adt)

    blocked = n > ROW_BLOCK
    if blocked:
        n_blocks = -(-n // ROW_BLOCK)
        n_pad = n_blocks * ROW_BLOCK
        pad = n_pad - n
        binned_blk = jnp.pad(binned_full, ((0, pad), (0, 0))).reshape(
            n_blocks, ROW_BLOCK, binned_full.shape[1])
        # padded rows carry zero channel weight: they land in slot 0 bin 0
        # and contribute nothing
        chans_blk = jnp.pad(jnp.stack(chans, 1), ((0, pad), (0, 0))).reshape(
            n_blocks, ROW_BLOCK, nchan)
    else:
        # hoisted: (B, d, N), or flat (N, B·msub) on the subset path
        onehot_bins = bins_onehot(binned_full)

    node = jnp.zeros(n, jnp.int32)
    heap_feat_levels, heap_thresh_levels, heap_ratio_levels = [], [], []
    level_values = []  # (2^l, K) a level: each node's value were it a leaf
    prev_cums = None   # previous level's per-channel bin cumsums (M, B, d)

    for level in range(max_depth):
        with jax.named_scope("tree.hist"):
            level_nodes = 2 ** level
            compact = level_nodes > n_cap
            M = n_cap if compact else level_nodes        # static slot count

            # Sibling subtraction: at wide non-compact levels build histograms
            # for LEFT children only (slot 2j -> column j; right-child rows
            # contribute zero) and derive the right child's cumsums from the
            # retained parent cumsums (right = parent − left) — halves the
            # (rows, M) node one-hot stream and the histogram dots exactly
            # where M makes them dominant.  Non-compact level l implies
            # non-compact l−1, so the parent cumsums are always full-layout.
            # Integer-channel bag modes only (RF one-hot/bagged): the bagged
            # channels are integer-valued so parent − left is exact, while
            # continuous GBT gradient/hessian channels suffer cancellation —
            # tiny negative hessian residuals could flip min_child_weight /
            # min_instances gating vs the direct build (ADVICE r3).
            sib = (level >= 1 and not compact and M >= SIBLING_MIN_SLOTS
                   and prev_cums is not None
                   and bag_mode in ("onehot", "bagged"))
            Mh = M // 2 if sib else M

            if compact:
                # rows occupy ≤ N distinct nodes: rank their sorted ids
                sorted_ids = jnp.sort(node)
                first = jnp.concatenate(
                    [jnp.ones(1, bool), sorted_ids[1:] != sorted_ids[:-1]])
                uniq = jnp.sort(jnp.where(first, sorted_ids,
                                          jnp.int32(2**31 - 1)))
                # (M,) padded with INT32_MAX (n ≤ M = next_pow2(n) by
                # construction)
                uniq = jnp.full(M, jnp.int32(2**31 - 1)).at[:n].set(uniq)
                # compare_all: the default 'scan' method lowers to a sequential
                # log(M) loop — poor fit for the TPU's wide vector units
                slot = jnp.searchsorted(uniq, node,
                                        method="compare_all").astype(jnp.int32)
            else:
                uniq = jnp.arange(M, dtype=jnp.int32)
                slot = node

            def node_onehot(slot_v, rows: int):
                """(rows, Mh) one-hot — full slots, or left children only."""
                if sib:
                    oh = (((slot_v // 2)[:, None] == jnp.arange(Mh)[None, :])
                          & (slot_v % 2 == 0)[:, None])
                else:
                    oh = slot_v[:, None] == jnp.arange(Mh)[None, :]
                return oh.astype(hdt)

            if blocked:
                slot_blk = jnp.pad(slot, (0, n_pad - n)).reshape(
                    n_blocks, ROW_BLOCK)

                def hist_block(acc, xs):
                    slot_b, binned_b, ch_b = xs
                    oh_bins = bins_onehot(binned_b)
                    oh_node = node_onehot(slot_b, ROW_BLOCK)   # (RB, Mh)
                    ch_h = ch_b.astype(hdt)
                    # all channels in ONE dot: separate per-channel dots
                    # build (or, on the subset path, re-read from HBM) the
                    # block's bins one-hot nchan times
                    wnode = jnp.concatenate(
                        [oh_node * ch_h[:, c][:, None] for c in range(nchan)],
                        axis=1)                            # (RB, nchan·Mh)
                    part = hist_dot(wnode, oh_bins)
                    return acc + part.reshape((nchan, Mh) + hist_dims), None

                acc0 = jnp.zeros((nchan, Mh) + hist_dims, adt)
                hist_stack, _ = lax.scan(
                    hist_block, acc0, (slot_blk, binned_blk, chans_blk))
                hists = [hist_stack[c].reshape(Mh, B, d)
                         for c in range(nchan)]
            else:
                onehot_node = node_onehot(slot, n)            # (N, Mh)
                wnode = jnp.concatenate(
                    [onehot_node * ch.astype(hdt)[:, None] for ch in chans],
                    axis=1)                               # (N, nchan·Mh)
                hist_all = hist_dot(wnode, onehot_bins)   # (nchan·Mh, ...)
                hists = [hist_all[c * Mh:(c + 1) * Mh].reshape(Mh, B, d)
                         for c in range(nchan)]           # 2K+1 × (Mh, B, D)
            if acc_bf16:
                # upcast once per level: gain search / gating stay f32
                hists = [h.astype(jnp.float32) for h in hists]
            if all_reduce is not None:
                # ICI collective replaces Spark's treeAggregate / Rabit
                # allreduce
                # (channel reduction also means fewer collectives per level)
                hists = [all_reduce(h) for h in hists]
        with jax.named_scope("tree.split"):
            cums_h = [jnp.cumsum(h, axis=1) for h in hists]
            if sib:
                # interleave left cumsums with (parent − left) right cumsums
                cums = [jnp.stack([lc, pc - lc], axis=1).reshape(M, B, d)
                        for lc, pc in zip(cums_h, prev_cums)]
            else:
                cums = cums_h
            # retain for the next level only when it will engage the
            # sibling path
            prev_cums = cums if (level + 1 < max_depth
                                 and 2 * level_nodes <= n_cap
                                 and 2 * M >= SIBLING_MIN_SLOTS) else None
            if bag_mode == "onehot":
                CL = cums[-1]
                GLs = list(cums[: k - 1])
                GLs.append(CL - sum(GLs) if GLs else CL)
                HLs = [CL] * k
            elif bag_mode == "bagged":
                CL = cums[-1]
                GLs = list(cums[:k])
                HLs = [CL] * k
            elif bag_mode == "newton":
                GLs = list(cums[:k])
                HLs = list(cums[k:2 * k])
                # hessian mass stands in; gating inert (min_inst 0)
                CL = HLs[0]
            else:
                CL = cums[-1]
                GLs = list(cums[:k])
                HLs = list(cums[k:2 * k])

            if prune_outputs:
                # depth-``level`` truncation leaves: per-node value sums are
                # the histograms' full-bin totals (feature 0's column — every
                # row of a node lands in exactly one bin of any feature), so
                # the snapshot costs no extra data pass
                # (M, K)
                Gs_n = jnp.stack([GL[:, -1, 0] for GL in GLs], axis=1)
                Hs_n = jnp.stack([HL[:, -1, 0] for HL in HLs], axis=1)
                Cs_n = cums[-1][:, -1, 0]                               # (M,)
                snap = jnp.where(newton_leaf,
                                 -learning_rate * Gs_n / (Hs_n + lam),
                                 Gs_n / jnp.maximum(Cs_n, 1e-12)[:, None])
                if compact:
                    snap = jnp.zeros((level_nodes, k), jnp.float32
                                     ).at[uniq].set(snap, mode="drop")
                level_values.append(snap)

            gain = 0.0
            HLmin = jnp.inf
            HRmin = jnp.inf
            if bundle_end is not None:
                # EFB interval splits: right = bins in (t, E(t)] — the owner
                # member's remaining bins; left = everything else (other
                # members + the shared default bin).  Unbundled columns carry
                # E = B-1, collapsing to the standard form bit-for-bit.
                # Entries with E = B-1 (unbundled columns, and a bundle's
                # LAST member) compute the STANDARD arithmetic (Gtot - GL)
                # rather than GL[E] - GL: the two agree exactly in real
                # arithmetic but differ by f32 cumsum rounding, and that
                # last-ulp noise would break gain-PLATEAU ties (thresholds
                # spanning empty bins) differently from the unbundled
                # program — the bit-for-tree contract hinges on it.
                Eb = jnp.broadcast_to(bundle_end[None], (M, B, d))
                is_std = Eb == (B - 1)

                def right_interval(A):
                    return jnp.take_along_axis(A, Eb, axis=1) - A

                for GL, HL in zip(GLs, HLs):
                    Gtot = GL[:, -1:, :1]
                    Htot = HL[:, -1:, :1]
                    GR = jnp.where(is_std, Gtot - GL, right_interval(GL))
                    HR = jnp.where(is_std, Htot - HL, right_interval(HL))
                    GLft = jnp.where(is_std, GL, Gtot - GR)
                    HLft = jnp.where(is_std, HL, Htot - HR)
                    gain = gain + (GLft ** 2 / (HLft + lam)
                                   + GR ** 2 / (HR + lam)
                                   - Gtot ** 2 / (Htot + lam))
                    HLmin = jnp.minimum(HLmin, HLft)
                    HRmin = jnp.minimum(HRmin, HR)
                Ctot = CL[:, -1:, :1]
                CR = jnp.where(is_std, Ctot - CL, right_interval(CL))
                CLft = jnp.where(is_std, CL, Ctot - CR)
            else:
                for GL, HL in zip(GLs, HLs):
                    Gtot = GL[:, -1:, :1]
                    Htot = HL[:, -1:, :1]
                    GR, HR = Gtot - GL, Htot - HL
                    gain = gain + (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                                   - Gtot ** 2 / (Htot + lam))
                    HLmin = jnp.minimum(HLmin, HL)
                    HRmin = jnp.minimum(HRmin, HR)
                Ctot = CL[:, -1:, :1]
                CR = Ctot - CL
                CLft = CL

            valid = ((HLmin >= min_child_weight) & (HRmin >= min_child_weight)
                     & (CLft >= min_instances) & (CR >= min_instances)
                     & (jnp.arange(B)[None, :, None] < B - 1)
                     & feat_mask[None, None, :])
            node_w = jnp.maximum(Ctot[:, 0, 0], 1e-12)
            gain = jnp.where(valid, gain, -jnp.inf)      # (M, B, D)
            flat_gain = gain.reshape(M, B * d)

            if default_dir:
                # XGBoost default-direction (missing/sparse) splits: variant b
                # routes the bin-0 (missing/absent) mass RIGHT — its cumsums
                # are the plain ones minus the bin-0 row — a per-(node, t,
                # feature) 2-way gain compare, exactly the C++ core's
                # enumerate-both-directions loop (OpXGBoostClassifier.scala:47
                # wraps those semantics).  Encoded as a NEGATIVE threshold
                # -(t+1) so heap shapes/persistence are unchanged.  ``dd_mask``
                # (from the caller's bin edges) limits variant b to features
                # whose bin 0 IS a genuine missing/zero bucket (first edge
                # pinned at 0.0 by the sparse-aware sketch): on a dense
                # feature, bin 0 is just the lowest quantile, and routing it
                # with the high side would fabricate non-contiguous splits real
                # XGBoost cannot produce (code-review r5).
                gain_b = 0.0
                HLbmin = jnp.inf
                HRbmin = jnp.inf
                for GL, HL in zip(GLs, HLs):
                    Gtot = GL[:, -1:, :1]
                    Htot = HL[:, -1:, :1]
                    GLb, HLb = GL - GL[:, 0:1, :], HL - HL[:, 0:1, :]
                    GRb, HRb = Gtot - GLb, Htot - HLb
                    gain_b = gain_b + (GLb ** 2 / (HLb + lam)
                                       + GRb ** 2 / (HRb + lam)
                                       - Gtot ** 2 / (Htot + lam))
                    HLbmin = jnp.minimum(HLbmin, HLb)
                    HRbmin = jnp.minimum(HRbmin, HRb)
                c0 = CL[:, 0:1, :]
                CLb = CL - c0
                CRb = Ctot - CLb
                valid_b = ((HLbmin >= min_child_weight)
                           & (HRbmin >= min_child_weight)
                           & (CLb >= min_instances) & (CRb >= min_instances)
                           & (jnp.arange(B)[None, :, None] < B - 1)
                           & feat_mask[None, None, :]
                           & (c0 > 0))        # no bin-0 mass -> b duplicates a
                if dd_mask is not None:
                    valid_b = valid_b & dd_mask[None, None, :]
                gain_b = jnp.where(valid_b, gain_b, -jnp.inf)
                flat_gain = jnp.concatenate(
                    [flat_gain, gain_b.reshape(M, B * d)], axis=1)  # (M, 2Bd)

            best = jnp.argmax(flat_gain, axis=1)
            best_gain = jnp.take_along_axis(flat_gain, best[:, None], 1)[:, 0]
            # depth_limit is a TRACED scalar: trees of different requested
            # depths share one compiled program (one XLA compile per sweep,
            # not one per
            # distinct max_depth); levels at/past the limit emit no splits
            ok = ((best_gain > 0) & (best_gain / node_w >= min_info_gain)
                  & jnp.isfinite(best_gain) & (level < depth_limit))
            if min_gain_raw is not None:
                # XGBoost's gamma thresholds the RAW loss-reduction, unlike
                # Spark's per-node-weight minInfoGain
                ok = ok & (best_gain >= min_gain_raw)
            if default_dir:
                is_b = best >= B * d
                bloc = best - jnp.where(is_b, B * d, 0)
                t_raw = (bloc // d).astype(jnp.int32)
                feat_l = jnp.where(ok, bloc % d, 0).astype(jnp.int32)
                thresh_l = jnp.where(
                    ok, jnp.where(is_b, -(t_raw + 1), t_raw), B
                ).astype(jnp.int32)
            else:
                feat_l = jnp.where(ok, best % d, 0).astype(jnp.int32)
                thresh_l = jnp.where(ok, best // d, B).astype(jnp.int32)

            if compact:
                # write per-slot results back to the level's heap segment at
                # the slots' true node ids; INT32_MAX padding slots drop out of
                # range
                seg_feat = jnp.zeros(level_nodes, jnp.int32)
                seg_thresh = jnp.full(level_nodes, B, jnp.int32)
                seg_feat = seg_feat.at[uniq].set(feat_l, mode="drop")
                seg_thresh = seg_thresh.at[uniq].set(thresh_l, mode="drop")
            else:
                seg_feat, seg_thresh = feat_l, thresh_l
            heap_feat_levels.append(seg_feat)
            heap_thresh_levels.append(seg_thresh)
            if prune_outputs:
                # the operands of ``ok``'s own gate comparison
                ratio_l = jnp.where((best_gain > 0) & jnp.isfinite(best_gain),
                                    best_gain / node_w, -jnp.inf)
                if compact:
                    ratio_l = jnp.full(level_nodes, -jnp.inf, jnp.float32
                                       ).at[uniq].set(ratio_l, mode="drop")
                heap_ratio_levels.append(ratio_l)

        with jax.named_scope("tree.route"):
            # routing reads the FULL-width matrix, rows-minor: subset-local
            # split ids map through feat_idx once a SLOT
            fid = feat_idx[feat_l] if feat_idx is not None else feat_l
            go_right = route_level(binned_T, slot, fid, thresh_l,
                                   _slot_ends(bundle_end, thresh_l, fid))
            node = 2 * node + go_right.astype(jnp.int32)

    # heap layout: level l occupies slots [2^l - 1, 2^{l+1} - 1)
    heap_feat = jnp.concatenate(heap_feat_levels)
    heap_thresh = jnp.concatenate(heap_thresh_levels)
    if feat_idx is not None:
        # map subset-local feature ids back to the full feature space
        # (no-split nodes keep thresh == B, which routes every row left
        # regardless of the mapped feature id)
        heap_feat = feat_idx[heap_feat]

    with jax.named_scope("tree.leaf"):
        n_leaves = 2 ** max_depth
        if n * n_leaves <= (64 << 20):
            # leaf sums as one-hot matmuls (same scatter-avoidance as
            # histograms)
            onehot_leaf = (node[:, None] == jnp.arange(n_leaves)[None, :]
                           ).astype(jnp.float32)          # (N, 2^d)
            stacked = jnp.concatenate([G, H, C[:, None]], axis=1)  # (N, 2K+1)
            sums = jax.lax.dot(onehot_leaf.T, stacked,
                               precision=jax.lax.Precision.HIGHEST)
            Gs, Hs, Cs = sums[:, :k], sums[:, k:2 * k], sums[:, 2 * k]
        else:  # one-hot too large for very deep trees; scatter scales with N
            Gs = jnp.zeros((n_leaves, k), jnp.float32).at[node].add(G)
            Hs = jnp.zeros((n_leaves, k), jnp.float32).at[node].add(H)
            Cs = jnp.zeros((n_leaves,), jnp.float32).at[node].add(C)
        if all_reduce is not None:
            Gs, Hs, Cs = all_reduce(Gs), all_reduce(Hs), all_reduce(Cs)
        newton_val = -learning_rate * Gs / (Hs + lam)
        mean_val = Gs / jnp.maximum(Cs, 1e-12)[:, None]
        leaf = jnp.where(newton_leaf, newton_val, mean_val)
    if not prune_outputs:
        return heap_feat, heap_thresh, leaf, ()
    unsplit_feat = feat_idx[0] if feat_idx is not None else jnp.int32(0)
    return (heap_feat, heap_thresh, leaf,
            (tuple(level_values), jnp.concatenate(heap_ratio_levels),
             unsplit_feat))


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "n_bins", "hist_bf16",
                                    "default_dir", "goss", "acc_bf16"))
def _grow_chunk(binned, G, H, C, feat_mask, depth_limit, max_depth: int,
                n_bins: int, lam, min_child_weight, min_info_gain,
                min_instances, newton_leaf, learning_rate,
                hist_bf16: bool = False, min_gain_raw=0.0,
                default_dir: bool = False, dd_mask=None, bundle_end=None,
                acc_bf16: bool = False, goss=None, goss_key=None):
    """Grow a chunk of trees in one XLA program.

    binned (N, D) shared; G/H (T, N, K), C (T, N), feat_mask (T, D),
    depth_limit (T,) traced per-tree effective depth.
    Returns (feat (T, 2^d-1), thresh (T, 2^d-1), leaf (T, 2^d, K)).
    ``goss``: static (k_top, k_rest) GOSS budget — each tree then grows
    on its own gradient-selected row gather (``goss_key`` folded per
    tree).
    """
    kw = dict(max_depth=max_depth, n_bins=n_bins,
              lam=lam, min_child_weight=min_child_weight,
              min_info_gain=min_info_gain, min_instances=min_instances,
              newton_leaf=newton_leaf, learning_rate=learning_rate,
              hist_bf16=hist_bf16, min_gain_raw=min_gain_raw,
              default_dir=default_dir, dd_mask=dd_mask,
              bundle_end=bundle_end, acc_bf16=acc_bf16)
    if goss is not None:
        k_top, k_rest = goss

        def one(g, h, c, m, lim, tid):
            ga = jnp.sum(jnp.abs(g), axis=1)
            idx, mult = _goss_select(ga, jax.random.fold_in(goss_key, tid),
                                     k_top, k_rest)
            f, t, lf, _ = _grow_tree_traced(
                binned[idx], g[idx] * mult[:, None],
                h[idx] * mult[:, None], c[idx] * mult, m, lim, **kw)
            return f, t, lf

        return jax.vmap(one)(G, H, C, feat_mask, depth_limit,
                             jnp.arange(G.shape[0]))
    fn = functools.partial(_grow_tree_traced, binned, **kw)
    f, t, lf, _ = jax.vmap(fn)(G, H, C, feat_mask, depth_limit)
    return f, t, lf


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "n_bins", "hist_bf16",
                                    "onehot_targets"))
def _grow_chunk_bagged(binned, Y, BW, feat_mask, depth_limit, max_depth: int,
                       n_bins: int, lam, min_child_weight, min_info_gain,
                       min_instances, newton_leaf, learning_rate,
                       hist_bf16: bool = False,
                       onehot_targets: bool = False, feat_idx=None):
    """Bagged-forest chunk: G/H derived from the (C, N) bag weights and the
    shared (N, K) targets *inside* the jit, so the (C, N, K) gradient
    tensors exist only transiently per launch (fused by XLA), never as
    host-built arrays — peak memory stays bounded by the chunk budget.
    ``onehot_targets`` (classification) activates the reduced-channel
    histogram path (see _grow_tree_traced bag_mode)."""
    G = BW[:, :, None] * Y[None, :, :]
    H = jnp.broadcast_to(BW[:, :, None], G.shape)
    kw = dict(max_depth=max_depth, n_bins=n_bins,
              lam=lam, min_child_weight=min_child_weight,
              min_info_gain=min_info_gain, min_instances=min_instances,
              newton_leaf=newton_leaf, learning_rate=learning_rate,
              hist_bf16=hist_bf16,
              bag_mode="onehot" if onehot_targets else "bagged")
    if feat_idx is not None:
        f, t, lf, _ = jax.vmap(lambda g, h, c, m, lim, fi: _grow_tree_traced(
            binned, g, h, c, m, lim, feat_idx=fi, **kw))(
            G, H, BW, feat_mask, depth_limit, feat_idx)
        return f, t, lf
    fn = functools.partial(_grow_tree_traced, binned, **kw)
    f, t, lf, _ = jax.vmap(fn)(G, H, BW, feat_mask, depth_limit)
    return f, t, lf


#: HBM budget for a chunk's histogram buffers — bounds vmap width.  Sized for
#: a 16 GB v5e chip: deep trees must still batch several per launch, because
#: small-data deep forests are bound by launches, not FLOPs.
HIST_BYTES_BUDGET = 4 << 30


def forest_chunk_size(n_trees: int, max_depth: int, d: int, n_bins: int,
                      k: int, budget: int = HIST_BYTES_BUDGET,
                      n_rows: Optional[int] = None,
                      compact: bool = True,
                      n_channels: Optional[int] = None,
                      d_full: Optional[int] = None,
                      onehot_bytes: int = 4) -> int:
    # node compaction caps a level's histogram slots at next_pow2(n_rows);
    # 1.3x covers the 128-lane padding of the minor (feature) axis.
    # compact=False is the all-reduce (mesh-sharded) path, which keeps the
    # full 2^level slot layout so every shard agrees on histogram indices.
    # ``d`` is the HISTOGRAM width (= msub on the feature-subset path);
    # ``n_channels`` overrides the default 2K+1 when the reduced-channel
    # bagged path is active; ``d_full`` adds the per-tree gathered binned
    # copy the subset path materializes; ``onehot_bytes`` is 2 when the
    # one-hot operands materialize bf16 (hist_bf16).
    nchan = n_channels if n_channels is not None else 2 * k + 1
    slots = 2 ** (max_depth - 1)
    if n_rows is not None and compact:
        slots = min(slots, 1 << int(np.ceil(np.log2(max(n_rows, 2)))))
    # sibling subtraction retains the parent level's cumsums alongside the
    # current level's: ~1.5x the histogram-buffer peak at engaged depths
    sib_factor = 1.5 if slots >= SIBLING_MIN_SLOTS else 1.0
    per_tree = int(slots * d * n_bins * nchan * 4 * 1.3 * sib_factor)
    if n_rows is not None:
        # matmul-histogram operands live per tree under vmap: the per-block
        # (rows, slots) node one-hot and (rows, B·D) bins one-hot (rows
        # streamed in ROW_BLOCK chunks past that size), plus the (rows, K)
        # G/H gradient channels and bag-weight row derived per tree
        rows = min(n_rows, ROW_BLOCK)
        per_tree += int(rows * slots * onehot_bytes * 1.3)
        if n_rows > ROW_BLOCK:
            per_tree += int(rows * n_bins * d * onehot_bytes * 1.3)
        per_tree += int(n_rows * (2 * k + 1) * 4)
        if d_full is not None and d_full != d:
            # the per-tree (rows, msub) int32 gather of the binned matrix
            per_tree += int(n_rows * d * 4)
    return int(np.clip(budget // max(per_tree, 1), 1, n_trees))


def grow_forest(binned: jnp.ndarray, Y: np.ndarray, BW: np.ndarray,
                feat_mask: np.ndarray, max_depth: int,
                n_bins: int, lam: float = 1.0,
                min_child_weight: float = 0.0, min_info_gain: float = 0.0,
                min_instances: float = 1.0, newton_leaf: bool = False,
                learning_rate: float = 1.0, as_numpy: bool = True,
                onehot_targets: bool = False,
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Grow ``T`` independent bagged trees in ceil(T/chunk) XLA launches.

    ``Y`` (N, K) shared targets; ``BW`` (T, N) per-tree bag weights;
    gradients are derived per chunk inside the jit (``_grow_chunk_bagged``)
    so peak HBM is bounded by ``HIST_BYTES_BUDGET`` regardless of T.  The
    trailing partial chunk is zero-weight padded to the same shape so every
    launch reuses one compiled program; padded trees are sliced off.
    """
    T, n = BW.shape
    d = binned.shape[1]
    Yj = jnp.asarray(Y, jnp.float32)
    k = Yj.shape[1]
    heap_depth = _resolve_compile_depth(max_depth)
    chunk = forest_chunk_size(T, heap_depth, d, n_bins, k, n_rows=n)
    args = (jnp.float32(lam), jnp.float32(min_child_weight),
            jnp.float32(min_info_gain), jnp.float32(min_instances),
            jnp.bool_(newton_leaf), jnp.float32(learning_rate))
    BW = np.asarray(BW, np.float32)
    feat_mask = np.asarray(feat_mask, bool)
    limit = jnp.full((chunk,), max_depth, jnp.int32)
    feats, threshs, leaves = [], [], []
    from ..utils.profiling import launch

    for s in range(0, T, chunk):
        with launch("forest_chunk"):
            e = min(s + chunk, T)
            pad = chunk - (e - s)
            BWc = jnp.asarray(np.pad(BW[s:e], ((0, pad), (0, 0))))
            Mc = jnp.asarray(np.pad(feat_mask[s:e], ((0, pad), (0, 0))))
            f, t, lf = _grow_chunk_bagged(
                binned, Yj, BWc, Mc, limit, heap_depth, n_bins, *args,
                onehot_targets=onehot_targets)
        if as_numpy:
            f, t, lf = np.asarray(f), np.asarray(t), np.asarray(lf)
        feats.append(f[:e - s])
        threshs.append(t[:e - s])
        leaves.append(lf[:e - s])
    if as_numpy:
        # host-side concat: a device concatenate would be one more program
        # to compile
        return (np.concatenate(feats), np.concatenate(threshs),
                np.concatenate(leaves))
    if len(feats) == 1:
        return feats[0], threshs[0], leaves[0]
    return (jnp.concatenate(feats), jnp.concatenate(threshs),
            jnp.concatenate(leaves))


def _rf_bag_and_features(tid, seed, n: int, d: int, msub: int,
                         subsample_rate):
    """Per-tree Poisson bag weights + feature-subset indices from
    ``fold_in(seed, tree_id)`` — THE single definition of RF randomness,
    shared by the single-device on-device generator and the mesh path so
    both grow identical forests."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), tid)
    kb, km = jax.random.split(key)
    bw = jax.random.poisson(kb, subsample_rate, (n,)).astype(jnp.float32)
    r = jax.random.uniform(km, (d,))
    # the msub smallest ranks — the same SET as the mask form (r <= kth),
    # as indices so the histogram runs at width msub
    idx = jnp.argsort(r)[:msub].astype(jnp.int32)
    return bw, idx


def rf_bags_and_features(seed: int, n_trees: int, n: int, d: int, msub: int,
                         subsample_rate: float):
    """Host copies of every tree's bag weights and feature subset (the mesh
    path shards precomputed bags).  Same generator on the same backend as
    the on-device path, so mesh and single-chip sweeps draw the same
    bags."""
    gen = jax.jit(jax.vmap(
        lambda tid: _rf_bag_and_features(tid, jnp.int32(seed), n, d, msub,
                                         jnp.float32(subsample_rate))))
    from ..utils.profiling import count_fresh

    BW, idx = gen(jnp.arange(n_trees))
    BW = np.asarray(BW)
    count_fresh("tree.bags", BW.nbytes)
    return BW, np.asarray(idx)


@functools.partial(jax.jit, static_argnames=("chunk", "msub", "max_depth",
                                             "n_bins", "onehot_targets",
                                             "hist_bf16"))
def _grow_chunk_rf(binned, Y, base_w, seed, start, n_trees, depth_limit_val,
                   subsample_rate, chunk: int, msub: int, max_depth: int,
                   n_bins: int, lam, min_child_weight, min_info_gain,
                   min_instances, learning_rate,
                   onehot_targets: bool = False, hist_bf16: bool = False):
    """RF chunk with ON-DEVICE bag-weight + feature-mask generation.

    No per-tree (T, N) Poisson weights or (T, D) masks are uploaded: the
    caller ships only ``seed``/``start`` scalars and the memoized fold
    data, and each tree derives its bag from ``fold_in(seed, tree_id)``
    inside the program.
    """
    n, d = binned.shape
    tree_ids = start + jnp.arange(chunk)
    BWr, feat_idx = jax.vmap(
        lambda tid: _rf_bag_and_features(tid, seed, n, d, msub,
                                         subsample_rate))(tree_ids)
    BW = base_w[None, :] * BWr * (tree_ids < n_trees)[:, None]
    masks = jnp.ones((chunk, d), bool)  # unused on the feat_idx path
    limit = jnp.full((chunk,), depth_limit_val, jnp.int32)
    return _grow_chunk_bagged(
        binned, Y, BW, masks, limit, max_depth, n_bins, lam,
        min_child_weight, min_info_gain, min_instances,
        jnp.bool_(False), learning_rate, hist_bf16=hist_bf16,
        onehot_targets=onehot_targets, feat_idx=feat_idx)


@functools.partial(jax.jit, static_argnames=("chunk", "msub", "max_depth",
                                             "n_bins", "onehot_targets",
                                             "t_per", "prune_outputs",
                                             "hist_bf16"))
def _grow_chunk_rf_grid(binned, Y, W_tr, seed, flat_start, total,
                        pair_fold, pair_min_ig, pair_min_inst, pair_depth,
                        subsample_rate, chunk: int, msub: int,
                        max_depth: int, n_bins: int, lam,
                        min_child_weight, t_per: int,
                        onehot_targets: bool = False,
                        prune_outputs: bool = False,
                        hist_bf16: bool = False, bag_rows=None):
    """RF chunk spanning the WHOLE (candidate x fold) grid.

    Flat tree index i = pair * t_per + t: tree t of grid pair ``i // t_per``
    draws the SAME on-device bag/feature-subset stream as a sequential
    per-candidate fit (``fold_in(seed, t)``), trains against that pair's
    fold weights (``W_tr[pair_fold]``) and its traced (min_info_gain,
    min_instances, depth_limit) — so one launch stream grows every
    candidate's forest for every fold with results identical to the
    per-candidate path (same randomness, same split masking).

    ``bag_rows`` (L,): the table rows that ``binned`` holds, where the
    launch grows one fold's pairs on that fold's own rows
    (``grow_rf_grid``'s ``rows``); ``W_tr`` then holds the folds' weights
    at their own rows.  The bags are still drawn over the table's rows
    (``Y``'s) and taken at these, and so are the targets.

    ``prune_outputs`` additionally emits each tree's level values, gate
    ratios and unsplit feature (see ``_grow_tree_traced``), which lets the
    caller run only the unique min_instances x fold pairs at their deepest
    grid depth and lowest min_info_gain and derive every shallower and
    every higher-gated candidate for free (``prune_rf_grid``).
    """
    n, d = Y.shape[0], binned.shape[1]
    flat = flat_start + jnp.arange(chunk)
    t_loc = (flat % t_per).astype(jnp.int32)
    p_idx = jnp.minimum(flat // t_per, pair_fold.shape[0] - 1)
    BWr, feat_idx = jax.vmap(
        lambda tid: _rf_bag_and_features(tid, seed, n, d, msub,
                                         subsample_rate))(t_loc)
    if bag_rows is not None:
        BWr, Y = BWr[:, bag_rows], Y[bag_rows]
    base_w = W_tr[pair_fold[p_idx]]                       # (chunk, rows)
    BW = base_w * BWr * (flat < total)[:, None]
    kw = dict(max_depth=max_depth, n_bins=n_bins, lam=lam,
              min_child_weight=min_child_weight, newton_leaf=jnp.bool_(False),
              learning_rate=jnp.float32(1.0), hist_bf16=hist_bf16,
              bag_mode="onehot" if onehot_targets else "bagged",
              prune_outputs=prune_outputs)

    def one(bw_row, mig, mins, lim, fi):
        g = bw_row[:, None] * Y
        h = jnp.broadcast_to(bw_row[:, None], g.shape)
        return _grow_tree_traced(
            binned, g, h, bw_row, jnp.ones(d, bool), lim,
            min_info_gain=mig, min_instances=mins, feat_idx=fi, **kw)

    return jax.vmap(one)(BW, pair_min_ig[p_idx], pair_min_inst[p_idx],
                         pair_depth[p_idx], feat_idx)


@jax.jit
def _fold_rows_jit(binned, rows):
    """``binned[rows]``, whole rows of the matrix: one fold's rows for the
    forest grid, its training rows to grow on (``grow_rf_grid``) or its
    validation rows to score on (``selector/grid_groups._fold_eval_rows``);
    every index is a row's."""
    return jnp.take(binned, rows, axis=0, mode="clip")


def grow_rf_grid(binned, Y, W_tr, seed: int, n_trees: int,
                 pair_fold: np.ndarray, pair_min_ig: np.ndarray,
                 pair_min_inst: np.ndarray, pair_depth: np.ndarray,
                 msub: int, subsample_rate: float, n_bins: int,
                 lam: float = 1e-3, min_child_weight: float = 0.0,
                 onehot_targets: bool = False, prune_outputs: bool = False,
                 rows: Optional[np.ndarray] = None):
    """Grow every (candidate x fold) pair's forest as chunked launch
    streams; returns device (P, T, nodes...) stacked ensembles.

    ``rows`` (F, L): each fold's weighted table rows, padded to one length
    (``selector/grid_groups._fold_train_rows``), with ``W_tr`` (F, L) the
    folds' weights at them (0 on the padding).  A fold's pairs are then
    grown on ``binned[rows[f]]``, gathered once a fold, in launches that
    hold that fold's trees alone; their bags are drawn over the table's
    rows and taken at the fold's, so where every histogram sum is exact
    (whole-number weights) the trees are those grown on the table's rows.
    Without ``rows`` every pair grows on the table's rows in one stream.
    Either way the chunk is evened over a stream's trees: no more padding
    trees than its launches need.

    With ``prune_outputs``, additionally returns a 4th element
    ``(level_values, gate_ratio, unsplit_feat)``: one (P, T, 2^l, K) array a
    level of the heap, (P, T, nodes) and (P, T).  The caller then needs only
    the unique min_instances x fold pairs, grown at their deepest grid depth
    and lowest min_info_gain: ``prune_rf_grid`` reads each shallower
    max_depth candidate off them by truncation (exact for level-wise growth;
    splits at a level never depend on deeper ones) and each candidate of a
    higher gate by cutting the nodes whose ratio fails it (exact because the
    gate takes no part in choosing a split).
    """
    d = binned.shape[1]
    k = Y.shape[1]
    pair_fold = np.asarray(pair_fold, np.int32)
    P = int(pair_fold.shape[0])
    # >= 1: an all-stump grid (every max_depth <= 0) still needs one heap
    # level to emit leaf arrays (depth_limit 0 keeps the trees split-free)
    heap_depth = _resolve_compile_depth(max(int(pair_depth.max()), 1))
    hist_bf16 = _accel_bf16()
    # (fold or None, its pairs): one stream a fold on the fold's rows, or
    # one stream of every pair on the table's
    if rows is None:
        streams = [(None, np.arange(P))]
        n_rows = binned.shape[0]
    else:
        streams = [(f, np.flatnonzero(pair_fold == f))
                   for f in range(rows.shape[0])]
        streams = [(f, pairs) for f, pairs in streams if len(pairs)]
        n_rows = rows.shape[1]
    # every stream's pair arrays padded to the longest's: one program shape
    width = max(len(pairs) for _, pairs in streams)
    per = n_trees * width
    cap = forest_chunk_size(
        per, heap_depth, msub, n_bins, k, n_rows=n_rows,
        n_channels=(k if onehot_targets else k + 1), d_full=d,
        onehot_bytes=2 if hist_bf16 else 4)
    chunk = -(-per // -(-per // cap))
    launches = [-(-len(pairs) * n_trees // chunk) for _, pairs in streams]
    from ..utils.profiling import count_rf_grid, launch

    count_rf_grid(treesGrown=n_trees * P, launches=sum(launches),
                  chunk=chunk, msub=msub, levels=heap_depth)
    parts = []
    for f, pairs in streams:
        sel = np.concatenate([pairs, np.repeat(pairs[-1:],
                                               width - len(pairs))])
        if f is None:
            mat, bag_rows = binned, None
        else:
            bag_rows = jnp.asarray(rows[f], jnp.int32)
            mat = _fold_rows_jit(binned, bag_rows)
        pf, pg, pi, pd_ = (jnp.asarray(np.asarray(a)[sel], t) for a, t in (
            (pair_fold, jnp.int32), (pair_min_ig, jnp.float32),
            (pair_min_inst, jnp.float32), (pair_depth, jnp.int32)))
        total = n_trees * len(pairs)
        for s in range(0, total, chunk):
            with launch("rf_grid_chunk"):
                out = _grow_chunk_rf_grid(
                    mat, Y, W_tr, jnp.int32(seed), jnp.int32(s),
                    jnp.int32(total), pf, pg, pi, pd_,
                    jnp.float32(subsample_rate), chunk, msub,
                    heap_depth, n_bins, jnp.float32(lam),
                    jnp.float32(min_child_weight), n_trees,
                    onehot_targets=onehot_targets,
                    prune_outputs=prune_outputs, hist_bf16=hist_bf16,
                    bag_rows=bag_rows)
            parts.append(out)
    stacked = np.concatenate([pairs for _, pairs in streams])
    order = (None if np.array_equal(stacked, np.arange(P))
             else tuple(np.argsort(stacked).tolist()))
    out = _stack_rf_grid_chunks(
        parts, tuple((n, n_trees * len(pairs))
                     for n, (_, pairs) in zip(launches, streams)),
        n_trees, order)
    return out if prune_outputs else out[:3]


@functools.partial(jax.jit, static_argnames=("streams", "n_trees", "order"))
def _stack_rf_grid_chunks(parts, streams, n_trees: int, order):
    """The launches' outputs as (pairs, trees, ...) arrays: ONE program for
    all of a grid's output arrays (a slice, a concatenation and a reshape
    each would be three programs an array to load, and as many dispatches
    a train).  ``streams`` gives each stream's (launches, trees), in the
    order of ``parts``; a stream's last launch's padding trees are
    dropped.  ``order``: where the streams' pairs stand in the output's
    order, the row of each output pair in the stacked streams."""
    def stack(*chunks):
        flats, at = [], 0
        for launches, trees in streams:
            flats.append(jnp.concatenate(chunks[at:at + launches])[:trees])
            at += launches
        flat = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        pairs = flat.reshape(-1, n_trees, *flat.shape[1:])
        return pairs if order is None else pairs[np.asarray(order)]

    return jax.tree_util.tree_map(stack, *parts)


@functools.partial(jax.jit, static_argnames=("depth", "n_bins"))
def prune_rf_grid(feats, threshs, leaves, prune_outputs, sel, gates,
                  depth: int, n_bins: int):
    """Candidates of max_depth ``depth`` (1 to the heap's) and min_info_gain
    ``gates`` read off base forests grown as deep or deeper and under the
    same gate or a lower one.

    The first four arguments are ``grow_rf_grid``'s four results for the P
    base pairs; ``sel`` (S,) names each candidate's base pair, ``gates``
    (S,) its min_info_gain.  Returns (S, T, 2^depth - 1) features and
    thresholds and (S, T, 2^depth, K) leaves, each what
    ``_grow_tree_traced`` writes when it grows the candidate directly, dead
    nodes included: a node splits iff every ancestor split, the base split
    it and its ratio passes the candidate's gate (the kernel's own f32
    comparison); every other node holds the unsplit feature and threshold
    ``n_bins``.  A node the base split and the gate cuts keeps all its rows
    down its LEFT spine, so its own level's value lands on its leftmost
    descendant at level ``depth`` and zero on the rest of its subtree;
    everywhere else the base's own values of level ``depth`` stand, bit for
    bit (its grown leaves, the f32 leaf sums, where ``depth`` is the heap's,
    else that level's histogram totals): a candidate of its base's gate
    comes out as truncation alone.
    """
    level_values, gate_ratio, unsplit_feat = prune_outputs
    full = 2 ** depth - 1 == feats.shape[-1]
    feats, threshs, values, gate_ratio, unsplit_feat = (
        a[sel] for a in (feats, threshs,
                         leaves if full else level_values[depth],
                         gate_ratio, unsplit_feat))
    S, T = unsplit_feat.shape
    gate = gates.astype(jnp.float32)[:, None, None]
    unsplit = unsplit_feat[:, :, None]
    alive = jnp.ones((S, T, 1), bool)
    cut_below = jnp.zeros((S, T, 1), bool)     # under a node the gate cut
    carried = jnp.zeros((S, T, 1, values.shape[-1]), values.dtype)
    out_feat, out_thresh = [], []
    for level in range(depth):
        seg = slice(2 ** level - 1, 2 ** (level + 1) - 1)
        base_split = alive & (threshs[..., seg] < n_bins)
        passes = gate_ratio[..., seg] >= gate
        split = base_split & passes
        cut = base_split & ~passes
        out_feat.append(jnp.where(split, feats[..., seg], unsplit))
        out_thresh.append(jnp.where(split, threshs[..., seg], n_bins))
        carried = jnp.where(cut[..., None], level_values[level][sel], carried)
        cut_below = cut_below | cut
        # children: (left, right) interleaved; a carried value goes left
        alive = jnp.repeat(split, 2, axis=-1)
        cut_below = jnp.repeat(cut_below, 2, axis=-1)
        carried = jnp.stack([carried, jnp.zeros_like(carried)],
                            axis=3).reshape(S, T, 2 ** (level + 1), -1)
    return (jnp.concatenate(out_feat, axis=-1),
            jnp.concatenate(out_thresh, axis=-1),
            jnp.where(cut_below[..., None], carried, values))


def grow_forest_rf(binned, Y, base_w, seed: int, n_trees: int, msub: int,
                   subsample_rate: float, max_depth: int, n_bins: int,
                   lam: float = 1e-3, min_child_weight: float = 0.0,
                   min_info_gain: float = 0.0, min_instances: float = 1.0,
                   onehot_targets: bool = False):
    """Bagged random forest, bags generated on device (see _grow_chunk_rf).

    Returns device (T, 2^hd-1) feat/thresh and (T, 2^hd, K) leaves, where hd
    is the shared compile depth (``compile_depth_hint``)."""
    n, d = binned.shape
    k = Y.shape[1]
    heap_depth = _resolve_compile_depth(max_depth)
    hist_bf16 = _accel_bf16()
    # feat_idx path: histograms at width msub with the reduced channel
    # count (K for one-hot classification, K+1 for bagged regression)
    chunk = forest_chunk_size(
        n_trees, heap_depth, msub, n_bins, k, n_rows=n,
        n_channels=(k if onehot_targets else k + 1), d_full=d,
        onehot_bytes=2 if hist_bf16 else 4)
    args = (jnp.float32(lam), jnp.float32(min_child_weight),
            jnp.float32(min_info_gain), jnp.float32(min_instances),
            jnp.float32(1.0))
    from ..utils.profiling import launch

    feats, threshs, leaves = [], [], []
    for s in range(0, n_trees, chunk):
        with launch("rf_chunk"):
            f, t, lf = _grow_chunk_rf(
                binned, Y, base_w, jnp.int32(seed), jnp.int32(s),
                jnp.int32(n_trees), jnp.int32(max_depth),
                jnp.float32(subsample_rate), chunk, msub, heap_depth,
                n_bins, *args, onehot_targets=onehot_targets,
                hist_bf16=hist_bf16)
        e = min(s + chunk, n_trees)
        if e - s < chunk:
            f, t, lf = f[:e - s], t[:e - s], lf[:e - s]
        feats.append(f)
        threshs.append(t)
        leaves.append(lf)
    if len(feats) == 1:
        return feats[0], threshs[0], leaves[0]
    return (jnp.concatenate(feats), jnp.concatenate(threshs),
            jnp.concatenate(leaves))


@functools.partial(jax.jit, static_argnames=("max_depth", "n_bins", "obj",
                                             "hist_bf16"))
def _gbt_chain_round_jit(binned, y, W, Fm, depth_lim, lams, mcws, migs,
                         mins_, lrs, mgrs, max_depth: int, n_bins: int,
                         obj: str, hist_bf16: bool = False):
    """One boosting round for a chunk of chains: gradients from each
    chain's margins + ONE vmapped growth (the bins one-hot is chain-
    invariant, so XLA builds it once per row block for every chain's
    histogram dots)."""
    n, d = binned.shape
    if obj == "binary":
        P = jax.nn.sigmoid(Fm)                       # (S, N)
        G = W * (P - y[None, :])
        H = W * jnp.maximum(P * (1 - P), 1e-6)
    else:
        G = W * (Fm - y[None, :])
        H = W
    mask = jnp.ones(d, bool)

    def one(g, h, c, lim, lam, mcw, mig, mi, lr, mgr):
        return _grow_tree_traced(
            binned, g[:, None], h[:, None], c, mask, lim,
            max_depth=max_depth, n_bins=n_bins, lam=lam,
            min_child_weight=mcw, min_info_gain=mig, min_instances=mi,
            newton_leaf=jnp.bool_(True), learning_rate=lr,
            hist_bf16=hist_bf16, min_gain_raw=mgr)[:3]

    return jax.vmap(one)(G, H, W, depth_lim, lams, mcws, migs, mins_,
                         lrs, mgrs)


@functools.partial(jax.jit, static_argnames=("n_rounds", "max_depth",
                                             "n_bins", "obj", "hist_bf16",
                                             "use_es", "skip_counts",
                                             "default_dir", "goss",
                                             "acc_bf16"))
def _gbt_chain_rounds_jit(binned, y, W, Fm0, vi, depth_lim, lams, mcws,
                          migs, mins_, lrs, mgrs, n_rounds: int,
                          max_depth: int, n_bins: int, obj: str,
                          hist_bf16: bool = False, use_es: bool = False,
                          skip_counts: bool = False,
                          default_dir: bool = False, dd_mask=None,
                          bundle_end=None,
                          acc_bf16: bool = False, goss=None,
                          goss_seed=None, chain_ids=None,
                          round_offset=None):
    """``n_rounds`` boosting rounds for a chunk of chains in ONE launch.

    ``lax.scan`` over rounds (body compiled once) carries the (S, N)
    margins and stacks each round's trees + per-chain ES metric: ONE
    dispatch (and one lagged metric fetch) per ``es_chunk`` of rounds
    instead of one per round.  Returns (Fm_end, feats (R, S, nodes), threshs,
    leaves (R, S, L, K), metrics (R, S)).

    ``bundle_end``: EFB member-end table — ``binned`` is then the BUNDLED
    matrix; growth, routing and margin updates all run in bundled space
    (the caller unbundles the returned trees before persisting/scoring
    outside this launch).  ``goss`` (static (k_top, k_rest)): each chain
    grows its round tree on a gradient-selected row gather, seeded
    ``fold_in(fold_in(PRNGKey(goss_seed), round_id), chain_id)`` with
    GLOBAL chain ids (``chain_ids``) and the global round offset
    (``round_offset``), so results are invariant to chunking."""
    n, d = binned.shape
    mask = jnp.ones(d, bool)
    # rows-minor, once a launch: what the margin update of every round
    # routes on, and the growth of chains that see all rows
    binned_T = binned.T
    grow_kw = dict(max_depth=max_depth, n_bins=n_bins,
                   newton_leaf=jnp.bool_(True), hist_bf16=hist_bf16,
                   bag_mode="newton" if skip_counts else "none",
                   default_dir=default_dir, dd_mask=dd_mask,
                   bundle_end=bundle_end, acc_bf16=acc_bf16)

    def round_step(Fm, rid):
        with jax.named_scope("gbt.grad"):
            if obj == "binary":
                P = jax.nn.sigmoid(Fm)                   # (S, N)
                G = W * (P - y[None, :])
                H = W * jnp.maximum(P * (1 - P), 1e-6)
            else:
                G = W * (Fm - y[None, :])
                H = W

        if goss is not None:
            k_top, k_rest = goss

            def one(g, h, c, lim, lam, mcw, mig, mi, lr, mgr, cid):
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(goss_seed), rid), cid)
                idx, mult = _goss_select(jnp.abs(g), key, k_top, k_rest)
                return _grow_tree_traced(
                    binned[idx], (g[idx] * mult)[:, None],
                    (h[idx] * mult)[:, None], c[idx] * mult, mask, lim,
                    lam=lam, min_child_weight=mcw, min_info_gain=mig,
                    min_instances=mi, learning_rate=lr, min_gain_raw=mgr,
                    **grow_kw)[:3]

            f, t, lf = jax.vmap(one)(G, H, W, depth_lim, lams, mcws, migs,
                                     mins_, lrs, mgrs, chain_ids)
        else:
            def one(g, h, c, lim, lam, mcw, mig, mi, lr, mgr):
                return _grow_tree_traced(
                    binned, g[:, None], h[:, None], c, mask, lim,
                    lam=lam, min_child_weight=mcw, min_info_gain=mig,
                    min_instances=mi, learning_rate=lr, min_gain_raw=mgr,
                    binned_T=binned_T, **grow_kw)[:3]

            f, t, lf = jax.vmap(one)(G, H, W, depth_lim, lams, mcws, migs,
                                     mins_, lrs, mgrs)
        with jax.named_scope("gbt.update"), jax.named_scope("tree.predict"):
            Fm = Fm + jax.vmap(lambda ff, tt, ll: _predict_tree_T(
                binned_T, ff, tt, ll, max_depth, bundle_end)[0])(f, t, lf)
        with jax.named_scope("gbt.es_metric"):
            if use_es:
                m = _chain_es_metric(Fm, y, vi, obj)
            else:
                m = jnp.zeros(Fm.shape[0], jnp.float32)
        return Fm, (f, t, lf, m)

    rounds = jnp.arange(n_rounds, dtype=jnp.int32)
    if round_offset is not None:
        rounds = rounds + round_offset
    Fm_end, (fs, ts, lfs, ms) = lax.scan(round_step, Fm0, rounds)
    return Fm_end, fs, ts, lfs, ms


def _chain_es_metric(Fm, y, vi, obj: str):
    """Per-chain early-stopping metric on the validation rows (trace-safe:
    shared by the standalone jit below and the in-scan round body)."""
    return _chain_es_metric_val(Fm[:, vi], y[vi], obj)


def _chain_es_metric_val(Z, yv, obj: str):
    """The metric half of ``_chain_es_metric``, over already-gathered
    (S, V) validation margins — the sharded chain kernel psum-gathers
    each shard's owned validation rows first and feeds them here, so
    both paths score with identical code."""
    if obj == "binary":
        from ..evaluators.metrics import _aupr_dev

        return jax.vmap(lambda z: _aupr_dev(yv, jax.nn.sigmoid(z)))(Z)
    return -jnp.mean((Z - yv[None, :]) ** 2, axis=1)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_round_jit(binned, feat, thresh, leaf, max_depth: int):
    """(S, N) margin increments for one round's chain trees."""
    binned_T = binned.T
    return jax.vmap(lambda f, t, lf: _predict_tree_T(
        binned_T, f, t, lf, max_depth)[0])(feat, thresh, leaf)


_chain_es_metric_jit = jax.jit(_chain_es_metric,
                               static_argnames=("obj",))


def gbt_chain_chunk(n_chains: int, max_depth: int, d: int, n_bins: int,
                    n_rows: int, budget: int = 2 * HIST_BYTES_BUDGET,
                    full_slots: bool = False,
                    goss_rows: Optional[int] = None) -> int:
    """Chains per round launch: the (ROW_BLOCK, B*D) bins one-hot is shared
    (counted once), per-chain terms are the slot one-hot + the 3-channel
    histogram accumulator.  The budget is deliberately larger than the
    forest chunker's — splitting a round across launches re-materializes
    the shared one-hot stream, the round's dominant cost.

    ``full_slots``: the mesh-sharded chain path disables node compaction
    (shards must agree on the full 2^level slot layout), so its budget
    uses the uncompacted slot count.

    ``goss_rows`` (k_top + k_rest): under GOSS every chain grows on its
    OWN row gather, so nothing is shared — the per-block bins one-hot and
    the gathered binned copy are per-chain terms (counting the one-hot
    once let a 6-chain depth-10 launch at 250k x 500 ask the compiler for
    more HBM than a v5e has)."""
    slots = 2 ** (max_depth - 1)
    if n_rows is not None and not full_slots:
        slots = min(slots, 1 << int(np.ceil(np.log2(max(n_rows, 2)))))
    rows = min(n_rows if goss_rows is None else goss_rows, ROW_BLOCK)
    onehot = int(rows * n_bins * d * 4 * 1.3)
    per_chain = int(slots * n_bins * d * 3 * 4 * 1.3
                    + rows * slots * 4 * 1.3
                    + n_rows * 4 * 4)
    if goss_rows is None:
        shared = onehot
    else:
        shared = 0
        per_chain += onehot + goss_rows * d          # gathered binned copy
    return int(np.clip((budget - shared) // max(per_chain, 1), 1, n_chains))


def grow_tree(binned: jnp.ndarray, G: jnp.ndarray, H: jnp.ndarray,
              C: jnp.ndarray, max_depth: int, n_bins: int,
              lam: float = 1.0, min_child_weight: float = 0.0,
              min_info_gain: float = 0.0, min_instances: float = 1.0,
              feat_mask: Optional[jnp.ndarray] = None,
              newton_leaf: bool = True, learning_rate: float = 1.0,
              min_gain_raw: float = 0.0, hist_bf16: bool = False,
              default_dir: bool = False, dd_mask=None, bundle_end=None,
              acc_bf16: Optional[bool] = None,
              goss: Optional[Tuple[int, int]] = None, goss_key=None,
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Grow one tree (single-tree view of ``grow_forest``): one XLA launch.

    ``bundle_end``: EFB member-end table — the matrix is then in bundled
    column space and the returned splits need ``unbundle_ensemble``.
    ``goss``/``goss_key``: static GOSS row budget + PRNG key (see
    ``goss_plan``).
    """
    if feat_mask is None:
        feat_mask = jnp.ones(binned.shape[1], bool)
    heap_depth = _resolve_compile_depth(max_depth)
    hist_bf16 = hist_bf16 and _accel_bf16()
    if acc_bf16 is None:
        acc_bf16 = hist_accum_bf16()
    if goss is not None and goss_key is None:
        goss_key = jax.random.PRNGKey(0)
    limit = jnp.full((1,), max_depth, jnp.int32)
    f, t, lf = _grow_chunk(
        binned, G[None], H[None], C[None], feat_mask[None], limit,
        heap_depth, n_bins, jnp.float32(lam), jnp.float32(min_child_weight),
        jnp.float32(min_info_gain), jnp.float32(min_instances),
        jnp.bool_(newton_leaf), jnp.float32(learning_rate),
        hist_bf16=hist_bf16, min_gain_raw=jnp.float32(min_gain_raw),
        default_dir=default_dir, dd_mask=dd_mask, bundle_end=bundle_end,
        acc_bf16=acc_bf16, goss=goss, goss_key=goss_key)
    return f[0], t[0], lf[0]


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------

def _predict_tree_T(binned_T, feat, thresh, leaf, max_depth: int,
                    bundle_end=None):
    """One tree over the ROWS-MINOR matrix ``binned_T`` (d, N): (K, N) leaf
    values.

    The loop over levels is unrolled over the static ``max_depth`` (a
    level's slot count 2^l must be static for ``route_level``).  With
    ``bundle_end`` the tree is in BUNDLED column space: splits are per-
    member intervals, so routing right additionally requires the bin to sit
    at or below the owner member's end bin (the in-launch margin updates of
    EFB growth and the sharded chains; persisted trees are unbundled and
    route without it)."""
    node = jnp.zeros(binned_T.shape[1], jnp.int32)
    for level in range(max_depth):
        lo, m = 2 ** level - 1, 2 ** level
        f, t = feat[lo:lo + m], thresh[lo:lo + m]
        go = route_level(binned_T, node, f, t, _slot_ends(bundle_end, t, f))
        node = 2 * node + go.astype(jnp.int32)
    return leaf_by_slot(leaf, node)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_tree(binned: jnp.ndarray, feat: jnp.ndarray, thresh: jnp.ndarray,
                 leaf: jnp.ndarray, max_depth: int) -> jnp.ndarray:
    """Route rows through one tree; returns (N, K) leaf values."""
    with jax.named_scope("tree.predict"):
        return _predict_tree_T(binned.T, feat, thresh, leaf, max_depth).T


#: bytes one step of ``predict_ensemble`` holds for its chunk of trees (a
#: tree's slot block of ``route_level`` and its leaf ids and values): all
#: trees at once for a 1-row request, a few dozen at 250,000 rows
ENSEMBLE_CHUNK_BYTES = 128 << 20


def _add_lanes(lanes, vals, off):
    """Add ``vals`` (c, K, N), the values of c consecutive trees, into the
    eight ``lanes`` (8, K, N), tree i into lane i mod 8.  ``off`` is the
    first tree's lane: 0 where c is 8 or more (whole groups of eight, then
    the rest from lane 0), else ``off + c`` stays within the eight."""
    c = vals.shape[0]
    if c >= 8:
        q = c // 8
        lanes = lax.scan(
            lambda a, v: (a + v, None), lanes,
            vals[:8 * q].reshape((q, 8) + vals.shape[1:]))[0]
        vals, c = vals[8 * q:], c - 8 * q
        if c == 0:
            return lanes
    cur = lax.dynamic_slice_in_dim(lanes, off, c)
    return lax.dynamic_update_slice_in_dim(lanes, cur + vals, off, 0)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_ensemble(binned: jnp.ndarray, feat: jnp.ndarray,
                     thresh: jnp.ndarray, leaf: jnp.ndarray,
                     max_depth: int) -> jnp.ndarray:
    """Sum of all trees' outputs: feat/thresh (T, 2^d-1), leaf (T, 2^d, K);
    returns (N, K).

    The matrix is transposed once a call; every tree then walks its levels
    by ``route_level`` (whole feature rows compared under a level's
    thresholds, no gather of one element a (tree, row)) and reads its leaf
    values by ``leaf_by_slot``.  Trees go in chunks of
    ``ENSEMBLE_CHUNK_BYTES``, each chunk one ``vmap`` and the chunks one
    ``scan``.  On one v5e a call of 8 trees of depth 10 over 250,000 x 500
    took 0.48 s as three element gathers a level and takes 0.048 s so
    (random trees; PERF.md §6, PR 31).

    The sum over trees is a float's, so its ORDER is part of the result:
    tree i adds into lane i mod 8 of eight partial sums, and the lanes add
    by halves, (0+4)+(2+6) and (1+5)+(3+7).  That is the order the chip's
    own reduce over the eight sublanes of a tile took when this function
    summed a gathered (T, N) array, so scores stay bit-equal with those;
    here it is written out and holds on every backend."""
    n = binned.shape[0]
    T = feat.shape[0]
    k = leaf.shape[2]
    per_tree = (min(ROUTE_BLOCK_BYTES,
                    2 ** (max_depth - 1) * n * binned.dtype.itemsize)
                + n * (4 + 4 * k))
    chunk = max(1, min(T, ENSEMBLE_CHUNK_BYTES // max(per_tree, 1)))
    # whole groups of eight trees a chunk, or a divisor of eight
    chunk = chunk - chunk % 8 if chunk >= 8 else 1 << (chunk.bit_length() - 1)
    with jax.named_scope("tree.predict"):
        binned_T = binned.T

        def add_trees(lanes, trees, first):
            vals = jax.vmap(lambda f, t, lf: _predict_tree_T(
                binned_T, f, t, lf, max_depth))(*trees)      # (c, K, N)
            return _add_lanes(lanes, vals, first % 8)

        n_full = T // chunk
        lanes = jnp.zeros((8, k, n), jnp.float32)
        if n_full:
            head = [a[:n_full * chunk].reshape((n_full, chunk) + a.shape[1:])
                    for a in (feat, thresh, leaf)]
            lanes, _ = lax.scan(
                lambda acc, it: (add_trees(acc, it[1:], it[0] * chunk), None),
                lanes, [jnp.arange(n_full)] + head)
        if T % chunk:
            lanes = add_trees(lanes, [a[n_full * chunk:]
                                      for a in (feat, thresh, leaf)],
                              n_full * chunk)
        lanes = lanes[:4] + lanes[4:]
        lanes = lanes[:2] + lanes[2:]
        return (lanes[0] + lanes[1]).T
