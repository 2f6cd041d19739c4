"""Mesh-sharded training programs.

These are the multi-chip paths of the XLA trainers in ``models/``: identical
math, but inputs committed to a (data, model) mesh so GSPMD partitions the
matmuls/scatters and inserts the ICI collectives that replace Spark's
``treeAggregate`` (SanityChecker.scala:407-470) and XGBoost's Rabit
allreduce (SURVEY §2.11-2.12).

``full_train_step`` is the single compiled program the driver dry-runs on an
N-virtual-device mesh: one AutoML macro-step =
  column stats (SanityChecker pass)            — psum over data axis
  Newton-IRLS logistic-regression update       — (D,N)@(N,D) sharded matmul
  one histogram GBDT level (hist+split+route)  — sharded scatter-add + argmax
all under one jit, with explicit sharding constraints on the carried state.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import (
    data_sharding, make_mesh, matrix_sharding, replicated, shard_dataset,
)

__all__ = ["TrainStepState", "full_train_step", "make_train_step",
           "fit_logreg_sharded", "grow_forest_sharded",
           "colstats_corr_sharded", "colstats_psum",
           "fit_logreg_newton_psum", "histogram_psum",
           "gbt_chain_rounds_sharded", "grow_rf_grid_sharded",
           "block_kernels_enabled", "block_rows_for", "block_grid",
           "colstats_block_fold", "colstats_from_acc",
           "newton_block_pass", "newton_solve_host",
           "fit_logreg_newton_blocked", "histogram_block_fold",
           "logloss_block_fold"]


class TrainStepState(NamedTuple):
    """Carried state for one AutoML macro-step (all replicated)."""
    beta: jnp.ndarray       # (D+1,) logreg coefficients + intercept
    col_mean: jnp.ndarray   # (D,)
    col_var: jnp.ndarray    # (D,)
    tree_feat: jnp.ndarray  # (2^depth - 1,) int32 — split feature per node
    tree_thresh: jnp.ndarray  # (2^depth - 1,) int32


def _colstats(X, w):
    wsum = jnp.maximum(w.sum(), 1.0)
    mean = (w @ X) / wsum
    var = (w @ (X * X)) / wsum - mean ** 2
    return mean, var


def _newton_step(X, y, w, beta, l2=1e-3):
    from ..models.linear import _damped_solve, _finite_or

    n, d = X.shape
    wsum = jnp.maximum(w.sum(), 1.0)
    z = X @ beta[:d] + beta[d]
    p = jax.nn.sigmoid(z)
    g_z = w * (p - y) / wsum
    s = jnp.maximum(w * p * (1 - p) / wsum, 1e-10)
    Xa = jnp.concatenate([X, jnp.ones((n, 1), X.dtype)], axis=1)
    grad = Xa.T @ g_z
    grad = grad.at[:d].add(l2 * beta[:d])
    H = (Xa * s[:, None]).T @ Xa
    H = H.at[jnp.arange(d), jnp.arange(d)].add(l2)
    return _finite_or(beta - _damped_solve(H, grad), beta)


def full_train_step(X, binned, y, w, state: TrainStepState, *,
                    n_bins: int = 32) -> TrainStepState:
    """One AutoML macro-step over sharded data (see module docstring).

    The tree component runs the REAL matmul-histogram kernel
    (``gbdt_kernels._grow_tree_traced`` — the exact program production fits
    compile), not a simplified stand-in: GSPMD partitions its histogram
    matmuls over the mesh just like the logreg Gram products.
    """
    from ..models.gbdt_kernels import _grow_tree_traced

    mean, var = _colstats(X, w)
    beta = _newton_step(X, y, w, state.beta)
    g = jax.nn.sigmoid(X @ beta[:-1] + beta[-1]) - y     # logloss grads
    h = jnp.maximum(g + y, 1e-6) * jnp.maximum(1.0 - g - y, 1e-6)
    n_nodes = state.tree_feat.shape[0]
    if n_nodes & (n_nodes + 1):
        raise ValueError(
            f"tree_feat must hold a full heap (2^depth - 1 nodes), got "
            f"{n_nodes}")
    depth = int(np.log2(n_nodes + 1))
    feat, thresh, _leaf, _ = _grow_tree_traced(
        binned, (g * w)[:, None], (h * w)[:, None], w,
        jnp.ones(binned.shape[1], bool), jnp.int32(depth),
        max_depth=depth, n_bins=n_bins, lam=jnp.float32(1.0),
        min_child_weight=jnp.float32(0.0), min_info_gain=jnp.float32(0.0),
        min_instances=jnp.float32(1.0), newton_leaf=jnp.bool_(False),
        learning_rate=jnp.float32(1.0))
    return TrainStepState(beta, mean, var, feat, thresh)


def make_train_step(mesh: Mesh, n_bins: int = 32):
    """Jit ``full_train_step`` with replicated state in/out on ``mesh``."""
    rep = replicated(mesh)
    step = functools.partial(full_train_step, n_bins=n_bins)
    return jax.jit(step, in_shardings=(matrix_sharding(mesh),
                                       matrix_sharding(mesh),
                                       data_sharding(mesh),
                                       data_sharding(mesh), rep),
                   out_shardings=rep)


def grow_forest_sharded(binned: np.ndarray, Y: np.ndarray, BW: np.ndarray,
                        feat_mask: np.ndarray, mesh: Mesh, *,
                        max_depth: int, n_bins: int, lam: float = 1e-3,
                        min_child_weight: float = 0.0,
                        min_info_gain: float = 0.0,
                        min_instances: float = 1.0,
                        newton_leaf: bool = False,
                        learning_rate: float = 1.0,
                        onehot_targets: bool = False):
    """Bagged forest growth with rows sharded over the mesh's data axis.

    Each shard builds partial gradient/hessian/count histograms on its rows;
    one ``psum`` per level over ICI replaces Spark's ``treeAggregate`` and
    XGBoost's Rabit allreduce (SURVEY §2.12 rows 1, 4).  Split decisions are
    computed identically on every shard from the reduced histograms, so row
    routing needs no further communication; leaf sums psum once at the end.

    Rows must tile the data axis (pad with zero bag weights).  Returns
    replicated (T, 2^d-1) feat/thresh and (T, 2^d, K) leaves — identical to
    single-device ``grow_forest`` output for the same inputs.

    Trees are grown in HBM-budgeted chunks: the all-reduce path disables
    node compaction (full 2^level histogram slots so every shard agrees on
    slot layout), so the per-tree working set is 2^depth × bins × features —
    ``forest_chunk_size(compact=False)`` with this shard's row count bounds
    how many trees one launch vmaps over (ADVICE r1).
    """
    from .mesh import shard_map_compat

    from ..models.gbdt_kernels import _grow_tree_traced

    data_axis = mesh.axis_names[0]
    T, n = BW.shape
    d = binned.shape[1]
    k = Y.shape[1]
    psum = functools.partial(lax.psum, axis_name=data_axis)

    def shard_fn(binned_s, Y_s, BW_s, mask_r, limit_r):
        G = BW_s[:, :, None] * Y_s[None, :, :]
        H = jnp.broadcast_to(BW_s[:, :, None], G.shape)
        fn = functools.partial(
            _grow_tree_traced, binned_s, max_depth=max_depth, n_bins=n_bins,
            lam=jnp.float32(lam),
            min_child_weight=jnp.float32(min_child_weight),
            min_info_gain=jnp.float32(min_info_gain),
            min_instances=jnp.float32(min_instances),
            newton_leaf=jnp.bool_(newton_leaf),
            learning_rate=jnp.float32(learning_rate),
            all_reduce=psum,
            bag_mode="onehot" if onehot_targets else "bagged")
        f, t, lf, _ = jax.vmap(fn)(G, H, BW_s, mask_r, limit_r)
        return f, t, lf

    fn = shard_map_compat(
        shard_fn, mesh,
        (P(data_axis, None), P(data_axis, None), P(None, data_axis),
         P(None, None), P(None)),
        (P(None, None), P(None, None), P(None, None, None)))
    # compact=False: the all-reduce path keeps the full 2^level slot layout
    # (no node compaction — shards must agree on histogram indices), so the
    # budget uses the uncompacted slot count with this shard's row count.
    from ..models.gbdt_kernels import forest_chunk_size
    n_shard = max(n // mesh.shape[data_axis], 1)
    chunk = forest_chunk_size(T, max_depth, d, n_bins, k,
                              n_rows=n_shard, compact=False)
    jfn = jax.jit(fn)
    binned_d = jnp.asarray(binned)
    Y_d = jnp.asarray(Y, jnp.float32)
    BW_h = np.asarray(BW, np.float32)
    mask_h = np.asarray(feat_mask, bool)
    limit = jnp.full((chunk,), max_depth, jnp.int32)
    fs, ts, ls = [], [], []
    with mesh:
        for s in range(0, T, chunk):
            e = min(s + chunk, T)
            BWc, Mc = BW_h[s:e], mask_h[s:e]
            if e - s < chunk:  # zero-weight pad keeps one compiled shape
                pad = chunk - (e - s)
                BWc = np.concatenate(
                    [BWc, np.zeros((pad, n), np.float32)], axis=0)
                Mc = np.concatenate([Mc, np.ones((pad, d), bool)], axis=0)
            f, t, lf = jfn(binned_d, Y_d, jnp.asarray(BWc),
                           jnp.asarray(Mc), limit)
            fs.append(f[: e - s])
            ts.append(t[: e - s])
            ls.append(lf[: e - s])
    if len(fs) == 1:
        return fs[0], ts[0], ls[0]
    return (jnp.concatenate(fs), jnp.concatenate(ts), jnp.concatenate(ls))


# ---------------------------------------------------------------------------
# Batched TREE sweeps on the ("data", "grid") mesh (ROADMAP item 2 / PR 11):
# same-shape RF/GBT candidates ride the grid axis while rows shard over the
# data axis — the tree analogue of the linear grid groups.  shard_map
# bodies with EXPLICIT per-level histogram psums (the all_reduce path of
# ``_grow_tree_traced``, which disables node compaction so every shard
# agrees on the full 2^level slot layout); per-chain hyperparameter
# vectors (depth limit, lambda, min_child_weight, eta, gamma / RF gate
# params) commit P("grid"), the binned int8 matrix commits P("data",
# None), and tree outputs replicate over data (identical split decisions
# per shard — the grow_forest_sharded contract, extended to the grid).
# Zero-weight pad rows/chains are inert, so results are invariant to both
# paddings (TM024) and agree with the single-device batched programs
# (TM025).
# ---------------------------------------------------------------------------

#: compiled shard_map programs per (mesh, static-config) — the sweep
#: re-enters these once per es_chunk launch / tree chunk, and rebuilding
#: the shard_map wrapper per call would re-trace every time
_TREE_SWEEP_JITS: dict = {}


def _mesh_cache_key(mesh: Mesh):
    return (tuple(mesh.axis_names), tuple(sorted(mesh.shape.items())),
            tuple(int(d.id) for d in np.asarray(mesh.devices).flat))


def gbt_chain_rounds_sharded(binned, y, W, Fm0, yv, vi, depth_lim, lams,
                             mcws, migs, mins_, lrs, mgrs, mesh: Mesh, *,
                             n_rounds: int, max_depth: int, n_bins: int,
                             obj: str, hist_bf16: bool = False,
                             use_es: bool = False,
                             skip_counts: bool = False, bundle_end=None,
                             acc_bf16: bool = False):
    """``n_rounds`` boosting rounds for S chains, chains sharded over the
    grid axis and rows over the data axis — the mesh form of
    ``gbdt_kernels._gbt_chain_rounds_jit`` with per-level histogram psums.

    Inputs are COMMITTED device arrays: ``binned`` (N_pad, D) at
    P("data", None), ``y`` (N_pad,) at P("data"), ``W``/``Fm0``
    (S_pad, N_pad) at P("grid", "data"), the per-chain vectors (S_pad,)
    at P("grid"); ``vi`` holds GLOBAL validation row indices (replicated)
    whose margins each owning shard contributes and one psum gathers, so
    the early-stopping metric sees exactly the single-device rows.
    ``bundle_end`` is the host EFB end-bin table or None (the identity
    table is used — bit-identical to the standard split form).  Returns
    the same 5-tuple as the single-device kernel, chains still sharded.
    """
    from ..models.gbdt_kernels import (_chain_es_metric_val,
                                       _grow_tree_traced,
                                       _predict_tree_T)
    from .mesh import shard_map_compat

    data_axis, grid_axis = mesh.axis_names
    be_host = (np.asarray(bundle_end, np.int32) if bundle_end is not None
               else np.full((n_bins, int(binned.shape[1])), n_bins - 1,
                            np.int32))
    key = ("gbt", _mesh_cache_key(mesh), n_rounds, max_depth, n_bins, obj,
           hist_bf16, use_es, skip_counts, acc_bf16)
    fn = _TREE_SWEEP_JITS.get(key)
    if fn is None:
        psum_d = functools.partial(lax.psum, axis_name=data_axis)

        def shard_fn(binned_s, y_s, W_s, Fm_s, yv_r, vi_r, be_r,
                     dl, la, mc, mg, mi, lr_, mgr_):
            nl, d = binned_s.shape
            mask = jnp.ones(d, bool)
            lo = lax.axis_index(data_axis) * nl
            # the shard's rows, rows-minor, once a launch (route_level)
            binned_T = binned_s.T

            def round_step(Fm, _):
                if obj == "binary":
                    Pm = jax.nn.sigmoid(Fm)
                    G = W_s * (Pm - y_s[None, :])
                    H = W_s * jnp.maximum(Pm * (1 - Pm), 1e-6)
                else:
                    G = W_s * (Fm - y_s[None, :])
                    H = W_s

                def one(g, h, c, lim, lam_, mcw, mig, mi_, lrr, mgr):
                    return _grow_tree_traced(
                        binned_s, g[:, None], h[:, None], c, mask, lim,
                        max_depth=max_depth, n_bins=n_bins, lam=lam_,
                        min_child_weight=mcw, min_info_gain=mig,
                        min_instances=mi_, newton_leaf=jnp.bool_(True),
                        learning_rate=lrr, hist_bf16=hist_bf16,
                        min_gain_raw=mgr, all_reduce=psum_d,
                        bag_mode="newton" if skip_counts else "none",
                        bundle_end=be_r, acc_bf16=acc_bf16,
                        binned_T=binned_T)[:3]

                f, t, lf = jax.vmap(one)(G, H, W_s, dl, la, mc, mg, mi,
                                         lr_, mgr_)
                Fm = Fm + jax.vmap(lambda ff, tt, ll: _predict_tree_T(
                    binned_T, ff, tt, ll, max_depth, be_r)[0])(f, t, lf)
                if use_es:
                    owned = (vi_r >= lo) & (vi_r < lo + nl)
                    lvi = jnp.clip(vi_r - lo, 0, nl - 1)
                    Z = psum_d(jnp.where(owned[None, :], Fm[:, lvi], 0.0))
                    m = _chain_es_metric_val(Z, yv_r, obj)
                else:
                    m = jnp.zeros(Fm.shape[0], jnp.float32)
                return Fm, (f, t, lf, m)

            Fm_end, (fs, ts, lfs, ms) = lax.scan(round_step, Fm_s, None,
                                                 length=n_rounds)
            return Fm_end, fs, ts, lfs, ms

        # out_shardings pinned to the shard_map out_specs: the async sweep
        # dispatches block N+1 while block N's outputs are still in flight,
        # and an explicit output layout keeps GSPMD from inserting a
        # resharding (or worse, a host round-trip) between chained launches
        # that feed one block's Fm/metrics into the next chunk's inputs.
        out_specs = (P(grid_axis, data_axis), P(None, grid_axis, None),
                     P(None, grid_axis, None), P(None, grid_axis, None, None),
                     P(None, grid_axis))
        fn = jax.jit(
            shard_map_compat(
                shard_fn, mesh,
                (P(data_axis, None), P(data_axis),
                 P(grid_axis, data_axis), P(grid_axis, data_axis),
                 P(None), P(None), P(None, None),
                 P(grid_axis), P(grid_axis), P(grid_axis), P(grid_axis),
                 P(grid_axis), P(grid_axis), P(grid_axis)),
                out_specs),
            out_shardings=tuple(NamedSharding(mesh, p) for p in out_specs))
        _TREE_SWEEP_JITS[key] = fn
    return fn(binned, y, W, Fm0, yv, vi, jnp.asarray(be_host), depth_lim,
              lams, mcws, migs, mins_, lrs, mgrs)


def grow_rf_grid_sharded(binned, Y, W_tr, BWr, feat_idx, pair_fold,
                         pair_min_ig, pair_min_inst, pair_depth,
                         mesh: Mesh, *, n_trees: int, msub: int,
                         n_bins: int, heap_depth: int, lam: float = 1e-3,
                         min_child_weight: float = 0.0,
                         onehot_targets: bool = False,
                         prune_outputs: bool = False):
    """The mesh form of ``gbdt_kernels.grow_rf_grid``: every (candidate x
    fold) pair's forest grown as chunked shard_map launches — the flat
    tree axis (pair * n_trees + t) sharded over the GRID axis, rows over
    the data axis, per-level histograms psum'd (node compaction off so
    shards agree on slot layout — the ``grow_forest_sharded`` contract).

    Bags come PRE-GENERATED (``rf_bags_and_features`` — the same
    fold_in(seed, tree_id) stream as the on-device single-chip path, so
    both grow identical forests): ``BWr`` (T, N_pad) Poisson bags with
    zero on pad rows, committed P(None, "data") alongside the (F, N_pad)
    fold weights; ``feat_idx`` (T, msub) replicated.  Returns HOST
    (P, T, nodes)/(P, T, leaves, K) arrays (+ the level values, gate
    ratios and unsplit features when ``prune_outputs``), matching
    ``grow_rf_grid``.
    """
    from ..models.gbdt_kernels import (_accel_bf16, _grow_tree_traced,
                                       forest_chunk_size)
    from ..utils.profiling import count_rf_grid, launch
    from .mesh import grid_sharding, shard_map_compat

    data_axis, grid_axis = mesh.axis_names
    g = int(mesh.shape[grid_axis])
    n_pad, d = binned.shape
    nl = n_pad // int(mesh.shape[data_axis])
    k = Y.shape[1]
    P_pairs = int(pair_fold.shape[0])
    total = n_trees * P_pairs
    hist_bf16 = _accel_bf16()
    chunk = forest_chunk_size(
        total, heap_depth, msub, n_bins, k, n_rows=nl, compact=False,
        n_channels=(k if onehot_targets else k + 1), d_full=d,
        onehot_bytes=2 if hist_bf16 else 4)
    chunk = max(g, (chunk // g) * g)
    count_rf_grid(treesGrown=total, launches=-(-total // chunk), chunk=chunk,
                  msub=msub, levels=heap_depth)

    key = ("rf", _mesh_cache_key(mesh), chunk, heap_depth, n_bins, msub,
           float(lam), float(min_child_weight), onehot_targets,
           prune_outputs, hist_bf16)
    fn = _TREE_SWEEP_JITS.get(key)
    if fn is None:
        psum_d = functools.partial(lax.psum, axis_name=data_axis)

        def shard_fn(binned_s, Y_s, Wtr_s, BWr_s, fi, t_loc, fold,
                     mig, mi, dep, valid):
            bw = (Wtr_s[fold] * BWr_s[t_loc]
                  * valid[:, None].astype(jnp.float32))
            fi_l = fi[t_loc]

            def one(bw_row, mig_, mi_, lim, fidx):
                gm = bw_row[:, None] * Y_s
                h = jnp.broadcast_to(bw_row[:, None], gm.shape)
                return _grow_tree_traced(
                    binned_s, gm, h, bw_row,
                    jnp.ones(binned_s.shape[1], bool), lim,
                    max_depth=heap_depth, n_bins=n_bins,
                    lam=jnp.float32(lam),
                    min_child_weight=jnp.float32(min_child_weight),
                    min_info_gain=mig_, min_instances=mi_,
                    newton_leaf=jnp.bool_(False),
                    learning_rate=jnp.float32(1.0),
                    hist_bf16=hist_bf16, all_reduce=psum_d,
                    bag_mode="onehot" if onehot_targets else "bagged",
                    feat_idx=fidx, prune_outputs=prune_outputs)

            return jax.vmap(one)(bw, mig, mi, dep, fi_l)

        # explicit out_shardings matching the shard_map out_specs — chunked
        # async launches keep a fixed grid-sharded output layout, so the
        # dispatch loop never forces a resharding between in-flight chunks
        out_specs = (P(grid_axis, None), P(grid_axis, None),
                     P(grid_axis, None, None),
                     (tuple(P(grid_axis, None, None)
                            for _ in range(heap_depth)),
                      P(grid_axis, None), P(grid_axis))
                     if prune_outputs else ())
        fn = jax.jit(
            shard_map_compat(
                shard_fn, mesh,
                (P(data_axis, None), P(data_axis, None), P(None, data_axis),
                 P(None, data_axis), P(None, None),
                 P(grid_axis), P(grid_axis), P(grid_axis), P(grid_axis),
                 P(grid_axis), P(grid_axis)),
                out_specs),
            out_shardings=jax.tree_util.tree_map(
                lambda p: NamedSharding(mesh, p), out_specs,
                is_leaf=lambda x: isinstance(x, P)))
        _TREE_SWEEP_JITS[key] = fn

    gs = grid_sharding(mesh)
    parts = []
    fi_dev = jnp.asarray(np.asarray(feat_idx, np.int32))
    for s in range(0, total, chunk):
        with launch("rf_grid_chunk_sharded"):
            flat = np.arange(s, s + chunk)
            t_loc = (flat % n_trees).astype(np.int32)
            p_idx = np.minimum(flat // n_trees, P_pairs - 1)
            args = [jax.device_put(np.ascontiguousarray(a), gs) for a in (
                t_loc, np.asarray(pair_fold, np.int32)[p_idx],
                np.asarray(pair_min_ig, np.float32)[p_idx],
                np.asarray(pair_min_inst, np.float32)[p_idx],
                np.asarray(pair_depth, np.int32)[p_idx],
                (flat < total).astype(np.int32))]
            out = fn(binned, Y, W_tr, BWr, fi_dev, *args)
        e = min(s + chunk, total)
        parts.append(jax.tree_util.tree_map(
            lambda a: np.asarray(a)[: e - s], out))
    out = (jax.tree_util.tree_map(lambda *a: np.concatenate(a), *parts)
           if len(parts) > 1 else parts[0])
    out = jax.tree_util.tree_map(
        lambda a: a.reshape(P_pairs, n_trees, *a.shape[1:]), out)
    return out if prune_outputs else out[:3]


# ---------------------------------------------------------------------------
# Explicit-collective rewrites of the sweep's inner steps (ROADMAP item 1):
# shard_map programs where each device reduces ITS rows and one psum over
# the data axis replaces the driver-side reduce — the hand-written form of
# what GSPMD derives for the whole-array paths above, kept explicit so the
# per-shard partial/psum contract (zero-weight pad rows are inert, results
# invariant to pad amount) is directly testable.
#
# These bodies run with check_rep/check_vma OFF (jax 0.4.x has no
# replication rule for the while_loop inside the Newton body), so the
# runtime never verifies that a replicated out_spec really is replicated.
# Two guards stand in: the shard-safety lint (analysis/shard_lint.py,
# TM040 — a reduction of sharded data with no collective in the body is
# flagged statically; this module is its regression corpus) and the
# TMOG_CHECK=1 pad-invariance/parity contracts (analysis/contracts.py,
# TM024/TM025) exercised by the tier-1 multichip smoke.
# ---------------------------------------------------------------------------

def colstats_psum(X, w, mesh: Mesh):
    """Weighted per-column (mean, var) with explicit per-shard partials.

    Each shard computes (sum w, w@X, w@X^2) over its rows; one ``psum``
    over the data axis merges them — the shard_map rewrite of
    ``_colstats`` (numerically identical: the reduction order over shards
    is fixed by the mesh).  Zero-weight rows (padding) contribute exactly
    nothing to every partial.
    """
    from .mesh import shard_map_compat

    data_axis = mesh.axis_names[0]

    def shard_fn(X_s, w_s):
        part = jnp.stack([jnp.concatenate([w_s.sum()[None], w_s @ X_s]),
                          jnp.concatenate([jnp.zeros((1,), X_s.dtype),
                                           w_s @ (X_s * X_s)])])
        tot = lax.psum(part, axis_name=data_axis)
        wsum = jnp.maximum(tot[0, 0], 1.0)
        mean = tot[0, 1:] / wsum
        var = tot[1, 1:] / wsum - mean ** 2
        return mean, var

    fn = shard_map_compat(shard_fn, mesh,
                          (P(data_axis, None), P(data_axis)),
                          (P(None), P(None)))
    return jax.jit(fn)(X, w)


def fit_logreg_newton_psum(X, y, mesh: Mesh, w=None, reg_param: float = 0.0,
                           max_iter: int = 50, tol: float = 1e-6):
    """Newton-IRLS logistic regression with per-shard Gram/gradient
    partials ``psum``-merged over the data axis — the explicit shard_map
    form of ``models.linear.fit_logistic_regression``'s L2 path (L1
    callers use the whole-array ``fit_logreg_sharded``).

    Each iteration: every shard computes its rows' (D+1, D+1) weighted
    Gram and (D+1,) gradient partials, one psum each merges them, and the
    replicated (D+1) solve runs identically on every device.  Zero-weight
    pad rows are inert in both partials, so the fit is invariant to the
    row-padding used to tile the mesh.  Returns host (coef, intercept).
    """
    from .mesh import shard_map_compat

    from ..models.linear import _damped_solve, _finite_or
    from .mesh import data_sharding, pad_to_multiple, sweep_matrix_sharding

    X = np.asarray(X, np.float32)
    n, d = X.shape
    if w is None:
        w = np.ones(n, np.float32)
    ndata = mesh.shape[mesh.axis_names[0]]
    Xp, _ = pad_to_multiple(X, ndata, axis=0)
    yp, _ = pad_to_multiple(np.asarray(y, np.float32), ndata)
    wp, _ = pad_to_multiple(np.asarray(w, np.float32), ndata)
    data_axis = mesh.axis_names[0]
    l2 = float(reg_param)

    def shard_fn(X_s, y_s, w_s):
        m = X_s.shape[0]
        Xa = jnp.concatenate([X_s, jnp.ones((m, 1), X_s.dtype)], axis=1)
        wsum = jnp.maximum(lax.psum(w_s.sum(), axis_name=data_axis), 1.0)

        def step(state):
            beta, _, it = state
            z = Xa @ beta
            p = jax.nn.sigmoid(z)
            g_part = Xa.T @ (w_s * (p - y_s) / wsum)
            s = jnp.maximum(w_s * p * (1 - p) / wsum, 1e-10) \
                * (w_s > 0)                       # pad rows: exactly zero
            H_part = (Xa * s[:, None]).T @ Xa
            grad = lax.psum(g_part, axis_name=data_axis)
            H = lax.psum(H_part, axis_name=data_axis)
            grad = grad.at[:d].add(l2 * beta[:d])
            H = H.at[jnp.arange(d), jnp.arange(d)].add(l2)
            nb = _finite_or(beta - _damped_solve(H, grad), beta)
            return nb, jnp.max(jnp.abs(nb - beta)), it + 1

        def cond(state):
            _, dn, it = state
            return (dn > tol) & (it < max_iter)

        beta0 = jnp.zeros(d + 1, jnp.float32)
        beta, _, _ = lax.while_loop(
            cond, step, (beta0, jnp.float32(jnp.inf), jnp.int32(0)))
        return beta

    fn = shard_map_compat(shard_fn, mesh,
                          (P(data_axis, None), P(data_axis), P(data_axis)),
                          P(None))
    xs = sweep_matrix_sharding(mesh)
    ds = data_sharding(mesh)
    beta = np.asarray(jax.jit(fn)(jax.device_put(Xp, xs),
                                  jax.device_put(yp, ds),
                                  jax.device_put(wp, ds)))
    return beta[:d], float(beta[d])


def histogram_psum(binned, g, h, w, mesh: Mesh, n_bins: int = 32):
    """Per-feature gradient/hessian/count histograms with per-shard
    partials ``psum``-merged over the data axis — the standalone form of
    the histogram build inside the sharded tree grower (the per-level
    ``all_reduce=psum`` in ``grow_forest_sharded``), exposed so the
    sweep's histogram step has a directly testable collective contract.

    ``binned``: (N, D) int bin ids; ``g``/``h``/``w``: (N,) per-row
    gradient / hessian / sample weight.  Returns replicated host
    (n_bins, D, 3) stacks of [g*w, h*w, w] sums per bin — zero-weight
    (padding) rows contribute nothing.
    """
    from .mesh import shard_map_compat

    from .mesh import data_sharding, pad_to_multiple, sweep_matrix_sharding

    binned = np.asarray(binned)
    n, d = binned.shape
    ndata = mesh.shape[mesh.axis_names[0]]
    bp, _ = pad_to_multiple(binned, ndata, axis=0)
    gp, _ = pad_to_multiple(np.asarray(g, np.float32), ndata)
    hp, _ = pad_to_multiple(np.asarray(h, np.float32), ndata)
    wp, _ = pad_to_multiple(np.asarray(w, np.float32), ndata)
    data_axis = mesh.axis_names[0]

    def shard_fn(b_s, g_s, h_s, w_s):
        oh = (b_s[:, None, :] == jnp.arange(n_bins)[None, :, None])
        oh = oh.astype(jnp.float32)                       # (m, B, D)
        vals = jnp.stack([g_s * w_s, h_s * w_s, w_s], axis=1)  # (m, 3)
        part = jnp.einsum("mbd,mk->bdk", oh, vals)
        return lax.psum(part, axis_name=data_axis)

    fn = shard_map_compat(
        shard_fn, mesh,
        (P(data_axis, None), P(data_axis), P(data_axis), P(data_axis)),
        P(None, None, None))
    xs = sweep_matrix_sharding(mesh)
    ds = data_sharding(mesh)
    out = jax.jit(fn, static_argnames=())(
        jax.device_put(bp, xs), jax.device_put(gp, ds),
        jax.device_put(hp, ds), jax.device_put(wp, ds))
    return np.asarray(out)


# ---------------------------------------------------------------------------
# Block-decomposed reductions (ROADMAP item 3 / the 10M-row pod data plane):
# the same inner sums as colstats_psum / fit_logreg_newton_psum /
# histogram_psum, decomposed into fixed-size row blocks folded through a
# DEVICE-RESIDENT accumulator — per-host memory scales with the block
# budget (TMOG_STREAM_RETAIN_MB), not the shard.  Each fold call is one
# async jit launch (acc' = acc + partial(block)), so JAX's async dispatch
# overlaps the next block's host prep/upload with the in-flight fold, the
# grid-group pattern from PR 17.  Cross-host combination happens ONCE per
# pass at the accumulator level (distributed/podstream.py gathers the
# per-host partials and sums them in host order — the allgather analogue
# of the resident kernels' lax.psum), so a pass over any number of hosts
# costs one exchange.
#
# Accumulation order is FIXED by the block grid (a pure function of
# (rows, cols, budget)), so two runs over the same rows fold bit-
# identically regardless of where the blocks live — the property the
# bench_scale10m parity and resume gates assert.  TMOG_BLOCK_KERNELS=0
# (read at call time, like TMOG_SYNC_SWEEP) collapses the grid to ONE
# whole-shard block: a single resident-style reduction, byte-identical to
# the pre-block path.
# ---------------------------------------------------------------------------

_BLOCK_KERNELS_ENV = "TMOG_BLOCK_KERNELS"
_BLOCK_ROWS_MIN = 1024


def block_kernels_enabled() -> bool:
    """Kill-switch, read at call time so tests/benches flip it per run:
    ``TMOG_BLOCK_KERNELS=0`` restores the resident (single whole-shard
    block) path byte-identically."""
    return os.environ.get(_BLOCK_KERNELS_ENV, "") != "0"


def block_rows_for(cols: int, dtype_bytes: int = 4,
                   retain_mb: Optional[int] = None) -> int:
    """Rows per block from the streaming retain budget.

    One quarter of the ``TMOG_STREAM_RETAIN_MB`` budget (default: the
    streaming driver's 256MB) — the block itself, its transient device
    copy, the accumulators, and chunk-parse headroom share the envelope,
    the same 1/4 rule as ``tuning.planner.advise_plan``'s retain_mb.
    Deterministic in (cols, dtype_bytes, env) only, so every host, every
    pass, and every resume derives the identical block grid without an
    exchange."""
    if retain_mb is None:
        from ..workflow.streaming import (_RETAIN_MB_DEFAULT,
                                          _RETAIN_MB_ENV)

        try:
            retain_mb = int(os.environ.get(_RETAIN_MB_ENV, "") or
                            _RETAIN_MB_DEFAULT)
        except ValueError:
            retain_mb = _RETAIN_MB_DEFAULT
    row_bytes = max(int(cols), 1) * int(dtype_bytes)
    target = (max(int(retain_mb), 1) << 20) // 4
    return max(target // row_bytes, _BLOCK_ROWS_MIN)


def block_grid(rows: int, cols: int, dtype_bytes: int = 4,
               retain_mb: Optional[int] = None) -> List[Tuple[int, int]]:
    """The [start, stop) row blocks one host folds, in fold order.

    With the kill-switch off the grid is one whole-range block (the
    resident path); otherwise fixed-size blocks with a short tail."""
    rows = int(rows)
    if rows <= 0:
        return []
    if not block_kernels_enabled():
        return [(0, rows)]
    br = block_rows_for(cols, dtype_bytes, retain_mb)
    return [(s, min(s + br, rows)) for s in range(0, rows, br)]


@jax.jit
def _colstats_fold_jit(acc, X_b, w_b):
    part = jnp.stack([jnp.concatenate([w_b.sum()[None], w_b @ X_b]),
                      jnp.concatenate([jnp.zeros((1,), X_b.dtype),
                                       w_b @ (X_b * X_b)])])
    return acc + part


def colstats_block_fold(blocks: Iterable[Tuple[np.ndarray, np.ndarray]],
                        cols: int) -> np.ndarray:
    """Fold (X_block, w_block) pairs into the (2, cols+1) colstats
    accumulator ``[[sum w, w@X], [0, w@X^2]]`` — THIS host's partial.
    Blocks stay on device only one at a time; the accumulator is device
    resident across the whole pass.  Returns the host partial (the
    caller cross-host combines, then ``colstats_from_acc``)."""
    acc = jnp.zeros((2, int(cols) + 1), jnp.float32)
    for X_b, w_b in blocks:
        acc = _colstats_fold_jit(acc, jnp.asarray(X_b, jnp.float32),
                                 jnp.asarray(w_b, jnp.float32))
    return np.asarray(acc)


def colstats_from_acc(acc: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(mean, var) from a COMBINED colstats accumulator — the replicated
    epilogue of ``colstats_psum``, identical formulas."""
    wsum = max(float(acc[0, 0]), 1.0)
    mean = acc[0, 1:] / wsum
    var = acc[1, 1:] / wsum - mean ** 2
    return mean, var


@jax.jit
def _newton_fold_jit(acc_g, acc_H, X_b, y_b, w_b, beta, inv_wsum):
    m = X_b.shape[0]
    Xa = jnp.concatenate([X_b, jnp.ones((m, 1), X_b.dtype)], axis=1)
    z = Xa @ beta
    p = jax.nn.sigmoid(z)
    g_part = Xa.T @ (w_b * (p - y_b) * inv_wsum)
    s = jnp.maximum(w_b * p * (1 - p) * inv_wsum, 1e-10) \
        * (w_b > 0)                           # zero-weight rows: inert
    H_part = (Xa * s[:, None]).T @ Xa
    return acc_g + g_part, acc_H + H_part


def newton_block_pass(blocks: Iterable[
        Tuple[np.ndarray, np.ndarray, np.ndarray]],
        beta: np.ndarray, wsum: float,
        d: int) -> Tuple[np.ndarray, np.ndarray]:
    """ONE Newton-IRLS pass over (X, y, w) blocks at the current ``beta``:
    per-block Gram/gradient partials folded into device-resident (D+1,)
    / (D+1, D+1) accumulators.  Returns the host partials; the caller
    combines across hosts and solves (``newton_solve_host``)."""
    inv = jnp.float32(1.0 / max(float(wsum), 1.0))
    beta_d = jnp.asarray(beta, jnp.float32)
    acc_g = jnp.zeros(d + 1, jnp.float32)
    acc_H = jnp.zeros((d + 1, d + 1), jnp.float32)
    for X_b, y_b, w_b in blocks:
        acc_g, acc_H = _newton_fold_jit(
            acc_g, acc_H, jnp.asarray(X_b, jnp.float32),
            jnp.asarray(y_b, jnp.float32), jnp.asarray(w_b, jnp.float32),
            beta_d, inv)
    return np.asarray(acc_g), np.asarray(acc_H)


@functools.partial(jax.jit, static_argnames=("d",))
def _newton_solve_jit(grad, H, beta, l2, d: int):
    from ..models.linear import _damped_solve, _finite_or

    grad = grad.at[:d].add(l2 * beta[:d])
    H = H.at[jnp.arange(d), jnp.arange(d)].add(l2)
    nb = _finite_or(beta - _damped_solve(H, grad), beta)
    return nb, jnp.max(jnp.abs(nb - beta))


def newton_solve_host(grad: np.ndarray, H: np.ndarray, beta: np.ndarray,
                      l2: float, d: int) -> Tuple[np.ndarray, float]:
    """The replicated (D+1) damped solve on COMBINED partials — the same
    ``_damped_solve``/``_finite_or`` step the resident kernel runs inside
    its while_loop.  Returns (new beta, max |step|)."""
    nb, dn = _newton_solve_jit(jnp.asarray(grad, jnp.float32),
                               jnp.asarray(H, jnp.float32),
                               jnp.asarray(beta, jnp.float32),
                               jnp.float32(l2), d)
    return np.asarray(nb), float(dn)


def fit_logreg_newton_blocked(blocks_fn: Callable[[], Iterable[
        Tuple[np.ndarray, np.ndarray, np.ndarray]]],
        d: int, *, reg_param: float = 0.0, max_iter: int = 50,
        tol: float = 1e-6, wsum: Optional[float] = None,
        combine: Optional[Callable[[np.ndarray], np.ndarray]] = None
        ) -> Tuple[np.ndarray, float, int]:
    """Newton-IRLS logistic regression over row blocks that never
    co-reside: the block-streaming rewrite of
    ``fit_logreg_newton_psum``'s Gram/grad inner step.

    ``blocks_fn()`` yields a FRESH (X, y, w) block iterator per call (one
    pass per Newton iteration — spilled blocks re-read from disk);
    ``combine`` merges a host-partial array across hosts (identity when
    single-host; the pod driver sums gathered partials in host order).
    One combine per pass: the g/H partials ride one stacked exchange.
    Returns host (coef, intercept, n_iter)."""
    if combine is None:
        combine = lambda a: a  # noqa: E731 - single-host identity
    if wsum is None:
        acc = np.zeros(1, np.float32)
        for _X_b, _y_b, w_b in blocks_fn():
            acc = acc + np.asarray(w_b, np.float32).sum(dtype=np.float32)
        wsum = float(combine(acc)[0])
    wsum = max(float(wsum), 1.0)
    beta = np.zeros(d + 1, np.float32)
    it = 0
    while it < max_iter:
        g, H = newton_block_pass(blocks_fn(), beta, wsum, d)
        # ONE cross-host exchange per pass: gradient + Gram stacked
        packed = combine(np.concatenate([g[None, :], H], axis=0))
        g, H = packed[0], packed[1:]
        beta, dn = newton_solve_host(g, H, beta, float(reg_param), d)
        it += 1
        if dn <= tol:
            break
    return beta[:d], float(beta[d]), it


@functools.partial(jax.jit, static_argnames=("n_bins",))
def _histogram_fold_jit(acc, b_b, g_b, h_b, w_b, n_bins: int):
    oh = (b_b[:, None, :] == jnp.arange(n_bins)[None, :, None])
    oh = oh.astype(jnp.float32)                        # (m, B, D)
    vals = jnp.stack([g_b * w_b, h_b * w_b, w_b], axis=1)   # (m, 3)
    return acc + jnp.einsum("mbd,mk->bdk", oh, vals)


def histogram_block_fold(blocks: Iterable[Tuple[
        np.ndarray, np.ndarray, np.ndarray, np.ndarray]],
        d: int, n_bins: int = 32) -> np.ndarray:
    """Fold (binned, g, h, w) blocks into the (n_bins, D, 3) histogram
    accumulator — the block-streaming form of ``histogram_psum``'s
    per-shard partial.  Returns this host's partial; the caller combines
    across hosts (same [g*w, h*w, w] stacking)."""
    acc = jnp.zeros((n_bins, int(d), 3), jnp.float32)
    for b_b, g_b, h_b, w_b in blocks:
        acc = _histogram_fold_jit(
            acc, jnp.asarray(b_b, jnp.int32),
            jnp.asarray(g_b, jnp.float32), jnp.asarray(h_b, jnp.float32),
            jnp.asarray(w_b, jnp.float32), n_bins)
    return np.asarray(acc)


@jax.jit
def _logloss_fold_jit(acc, X_b, y_b, w_b, beta):
    m = X_b.shape[0]
    Xa = jnp.concatenate([X_b, jnp.ones((m, 1), X_b.dtype)], axis=1)
    z = Xa @ beta
    # numerically stable weighted logloss partial: [sum w*loss, sum w]
    loss = jnp.maximum(z, 0.0) - z * y_b + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return acc + jnp.stack([(w_b * loss).sum(), w_b.sum()])


def logloss_block_fold(blocks: Iterable[
        Tuple[np.ndarray, np.ndarray, np.ndarray]],
        beta: np.ndarray) -> np.ndarray:
    """Fold (X, y, w) blocks into the (2,) ``[sum w*logloss, sum w]``
    accumulator for a fixed ``beta`` — the candidate-scoring pass of the
    blocked linear sweep (winner = argmin combined loss/weight)."""
    acc = jnp.zeros(2, jnp.float32)
    beta_d = jnp.asarray(beta, jnp.float32)
    for X_b, y_b, w_b in blocks:
        acc = _logloss_fold_jit(acc, jnp.asarray(X_b, jnp.float32),
                                jnp.asarray(y_b, jnp.float32),
                                jnp.asarray(w_b, jnp.float32), beta_d)
    return np.asarray(acc)


@jax.jit
def _colstats_corr_jit(X, y, w):
    """Weighted column stats + Pearson-with-label, formulas matching the
    SanityChecker host path exactly (variance ddof=1, label centered over
    real rows) so mesh and single-device runs drop the same features."""
    wsum = jnp.maximum(w.sum(), 2.0)
    mean = (w @ X) / wsum
    var = (w @ ((X - mean) ** 2)) / (wsum - 1.0)
    big = jnp.float32(3.0e38)
    mn = jnp.min(jnp.where(w[:, None] > 0, X, big), axis=0)
    mx = jnp.max(jnp.where(w[:, None] > 0, X, -big), axis=0)
    ymean = (w @ y) / wsum
    yc = (y - ymean) * w
    num = yc @ (X - mean)
    den = (jnp.sqrt(jnp.maximum(var, 1e-30) * (wsum - 1.0))
           * jnp.sqrt(jnp.maximum(yc @ yc, 1e-30)))
    corr = jnp.nan_to_num(num / den)
    return mean, var, mn, mx, corr


def colstats_corr_sharded(X: np.ndarray, y: np.ndarray, mesh: Mesh):
    """SanityChecker statistics over a row-sharded matrix: one jitted
    program whose column reductions GSPMD psums over ICI — the TPU
    replacement for the reference's executor-distributed
    ``Statistics.colStats``/``corr`` (SanityChecker.scala:380-470).

    Returns host (mean, variance, min, max, corr_with_label) numpy arrays;
    padded rows carry zero weight so results match the host formulas.
    """
    from .mesh import data_sharding, pad_to_multiple

    n = X.shape[0]
    ndata = mesh.shape[mesh.axis_names[0]]
    Xp, _ = pad_to_multiple(np.asarray(X, np.float32), ndata, axis=0)
    yp, _ = pad_to_multiple(np.asarray(y, np.float32), ndata)
    w = np.zeros(Xp.shape[0], np.float32)
    w[:n] = 1.0
    ds = data_sharding(mesh)
    out = _colstats_corr_jit(jax.device_put(Xp, ds),
                             jax.device_put(yp, ds), jax.device_put(w, ds))
    packed = np.asarray(jnp.stack(out))  # one host fetch
    return tuple(packed)


#: row block for the sharded numeric-profile histogram build (bounds the
#: transient (rows, bins, D) one-hot)
_PROFILE_ROW_BLOCK = 32768


@functools.partial(jax.jit, static_argnames=("n_bins",))
def _profile_numeric_jit(X, m, n_bins: int):
    """Per-column count/nulls/moments/min/max + fixed-grid histogram in ONE
    program; on sharded inputs GSPMD psums every reduction over ICI.

    Moments are accumulated about a per-column ANCHOR (the column's
    midrange): raw f32 sums of e.g. ms-epoch date values (~1.7e12) are
    pure rounding noise, while centered deviations keep full relative
    precision — the host reconstructs the raw f64 moments from (anchor,
    centered sums)."""
    n, d = X.shape
    mf = m & jnp.isfinite(X)
    cnt = m.sum(axis=0).astype(jnp.float32)
    valid = mf.sum(axis=0).astype(jnp.float32)
    big = jnp.float32(3.0e38)
    mn = jnp.min(jnp.where(mf, X, big), axis=0)
    mx = jnp.max(jnp.where(mf, X, -big), axis=0)
    anchor = jnp.where(valid > 0, 0.5 * (mn + mx), 0.0)
    Xc = jnp.where(mf, X - anchor[None, :], 0.0)
    s = Xc.sum(axis=0)
    s2 = (Xc * Xc).sum(axis=0)
    w = jnp.maximum(mx - mn, 1e-30)
    b = jnp.clip(((X - mn[None, :]) / w[None, :] * n_bins).astype(jnp.int32),
                 0, n_bins - 1)
    n_blk = -(-n // _PROFILE_ROW_BLOCK)
    pad = n_blk * _PROFILE_ROW_BLOCK - n
    b_p = jnp.pad(b, ((0, pad), (0, 0))).reshape(n_blk, -1, d)
    m_p = jnp.pad(mf, ((0, pad), (0, 0))).reshape(n_blk, -1, d)

    def block(acc, xs):
        bb, mm = xs
        oh = ((bb[:, None, :] == jnp.arange(n_bins)[None, :, None])
              & mm[:, None, :]).astype(jnp.float32)
        return acc + oh.sum(axis=0), None

    hist, _ = lax.scan(block, jnp.zeros((n_bins, d), jnp.float32),
                       (b_p, m_p))
    return cnt, valid, s, s2, mn, mx, hist, anchor


def profile_numeric_sharded(X: np.ndarray, mask: np.ndarray, mesh: Mesh,
                            n_bins: int = 100):
    """RawFeatureFilter's numeric distribution pass over a row-sharded
    matrix: ONE jitted program whose column reductions (counts, moments,
    min/max, fixed-grid histogram) GSPMD psums over ICI — the TPU analogue
    of the reference's executor-distributed per-partition profile +
    monoid reduce (RawFeatureFilter.scala:489-545,
    FeatureDistribution.scala:187-192).

    Returns host arrays (nulls, valid, sum, sum2, min, max,
    hist (n_bins, D), edges (n_bins+1, D)); padded rows carry mask=False
    so results match an unsharded pass."""
    from .mesh import data_sharding, pad_to_multiple

    n = X.shape[0]
    ndata = mesh.shape[mesh.axis_names[0]]
    Xp, _ = pad_to_multiple(np.asarray(X, np.float32), ndata, axis=0)
    mp = np.zeros(Xp.shape, bool)
    mp[:n] = np.asarray(mask, bool)
    ds = data_sharding(mesh)
    out = _profile_numeric_jit(jax.device_put(Xp, ds),
                               jax.device_put(mp, ds), n_bins)
    nonnull, valid, s_c, s2_c, mn, mx = (np.asarray(v, np.float64)
                                         for v in out[:6])
    hist = np.asarray(out[6])
    anchor = np.asarray(out[7], np.float64)
    nulls = n - nonnull
    # all-null/non-finite columns keep the +-big sentinels: collapse to 0
    # so the edge grid below stays finite (their histograms are all-zero)
    empty = valid == 0
    mn = np.where(empty, 0.0, mn)
    mx = np.where(empty, 0.0, mx)
    # reconstruct raw f64 moments from the anchor-centered device sums:
    # sum(x) = sum(x-a) + n*a ; sum(x^2) = sum((x-a)^2) + 2a*sum(x-a) + n*a^2
    s = s_c + valid * anchor
    s2 = s2_c + 2.0 * anchor * s_c + valid * anchor * anchor
    edges = np.linspace(mn, mx, n_bins + 1)          # (n_bins+1, D)
    return nulls, valid, s, s2, mn, mx, hist, edges


def fit_logreg_sharded(X: np.ndarray, y: np.ndarray, mesh: Mesh,
                       w: Optional[np.ndarray] = None, **kwargs):
    """Data/model-parallel logistic regression: shard inputs on the mesh and
    run the standard jitted IRLS trainer — GSPMD partitions the per-iteration
    (D,N)@(N,D) Gram matmuls and psums partial Hessians over ICI.

    The returned fit is sliced back to the caller's feature count (column
    padding used to tile the model axis is stripped)."""
    from ..models.linear import LinearFit, fit_logistic_regression
    d = X.shape[1]
    X_dev, y_dev, w_dev = shard_dataset(X, y, mesh, w)
    fit = fit_logistic_regression(X_dev, y_dev, w_dev, **kwargs)
    coef = fit.coef[..., :d] if fit.coef.shape[-1] != d else fit.coef
    return LinearFit(coef, fit.intercept, fit.n_iter, fit.converged)


def quantile_bins_sharded(X: np.ndarray, mesh: Mesh, max_bins: int = 32,
                          sample_rows: int = 200_000) -> np.ndarray:
    """Mesh-sharded quantile sketch — the distributed analogue of
    ``gbdt_kernels.quantile_bins`` (the reference computes its feature
    distributions executor-distributed, RawFeatureFilter.scala:489-545;
    XGBoost sketches with Rabit allreduce).

    Each shard stride-samples its local rows, the per-shard samples
    ``all_gather`` over ICI into one pooled (S·k, D) sample, and the
    per-feature quantiles compute replicated on every device — one
    program, one collective, no host pass over the matrix.  With
    ``sample_rows >= N`` the pooled sample is exactly the whole matrix, so
    the edges match the host sketch bit-for-bit (same linear-interpolation
    quantiles); under sampling they agree to sketch tolerance.
    """
    from .mesh import data_sharding, pad_to_multiple

    X = np.asarray(X, np.float32)
    n, d = X.shape
    data_axis = mesh.axis_names[0]
    n_shards = mesh.shape[data_axis]
    Xp, _ = pad_to_multiple(X, n_shards, axis=0)
    rows_valid = np.zeros(Xp.shape[0], np.float32)
    rows_valid[:n] = 1.0
    local = Xp.shape[0] // n_shards
    k = max(1, min(local, -(-min(sample_rows, n) // n_shards)))
    qs = np.linspace(0, 1, max_bins + 1)[1:-1].astype(np.float32)

    from .mesh import shard_map_compat

    def shard_fn(X_s, valid_s):
        # stride-sample k local rows; pad rows re-sample row 0 of the
        # shard but carry weight 0 via +inf sentinel replacement below
        stride = max(1, X_s.shape[0] // k)
        idx = (jnp.arange(k) * stride) % X_s.shape[0]
        samp = X_s[idx]                                  # (k, D)
        ok = valid_s[idx] > 0
        # invalid (padding) rows -> NaN, excluded by nanquantile
        samp = jnp.where(ok[:, None], samp, jnp.nan)
        pooled = lax.all_gather(samp, data_axis).reshape(-1, samp.shape[1])
        return jnp.nanquantile(pooled, jnp.asarray(qs), axis=0).T  # (D, B-1)

    ds = data_sharding(mesh)
    fn = shard_map_compat(shard_fn, mesh,
                          (P(data_axis, None), P(data_axis)),
                          P(None, None))
    edges = np.array(fn(jax.device_put(Xp, ds),
                        jax.device_put(rows_valid, ds)),
                     np.float32)   # np.array: writable host copy
    # same dedup rule as the host sketch: collapse non-increasing edges
    eps = 1e-7
    for j in range(d):
        e = edges[j]
        dup = np.concatenate([[False], np.diff(e) <= eps])
        edges[j] = np.where(dup, np.inf, e)
    return edges
