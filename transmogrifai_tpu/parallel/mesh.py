"""Device-mesh utilities — the distributed substrate of the framework.

Reference mapping (SURVEY §2.12, §5.8): the reference's distributed backend is
Apache Spark — RDD row partitions across executors, driver-coordinated
``treeAggregate`` reductions inside MLlib (SanityChecker.scala:407-470,
FeatureDistribution.scala:187), JVM-thread parallel model fits
(OpCrossValidation.scala:113-138) and Rabit allreduce inside XGBoost's C++
core.  The TPU-native equivalent built here is single-controller JAX:

 * rows (Spark partitions)        -> ``data`` mesh axis (batch sharding)
 * feature-dim / wide vectors     -> ``model`` mesh axis (the tabular
                                     analogue of tensor parallelism)
 * treeAggregate / Rabit allreduce-> XLA collectives (psum/all_gather) that
                                     GSPMD inserts from sharding annotations,
                                     riding ICI within a slice and DCN across
 * driver thread-pool over grid   -> vmap/stacked fits over the mesh

Nothing in this module issues explicit collectives: trainers are written as
whole-array programs and the partitioner derives the communication, which is
exactly the "pick a mesh, annotate shardings, let XLA insert collectives"
recipe.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh", "make_sweep_mesh", "auto_grid_axis", "has_grid_axis",
    "data_sharding", "feature_sharding", "matrix_sharding",
    "sweep_matrix_sharding", "grid_sharding", "fold_weight_sharding",
    "chain_sharding", "replicated", "shard_dataset", "pad_to_multiple",
    "shard_sweep_inputs", "shard_map_compat", "next_shard_pad",
    "pod_default_devices", "global_mesh",
]


def pod_default_devices():
    """The device set mesh construction defaults to: under an active
    multi-process pod, the LOCALLY ADDRESSABLE devices (each process's
    sweep/fit machinery replicates deterministically on its own slice —
    the host-level pod protocol, distributed/podstream.py); otherwise
    every device jax can see.  Cross-process GLOBAL meshes (the
    ShardedMatrixWriter process-local ingest path) are built explicitly
    via :func:`global_mesh`."""
    import jax as _jax

    from ..distributed.runtime import current_pod

    if current_pod().active:
        return list(_jax.local_devices())
    return list(_jax.devices())


def global_mesh(axis_name: str = "data") -> Mesh:
    """A 1-D mesh over EVERY device of the pod (all processes), in
    process-major order — row shards land contiguously per process, which
    is exactly the layout host-sharded ingest fills.  In a single
    process this is just a 1-D mesh over the local devices."""
    import jax as _jax

    return Mesh(np.asarray(_jax.devices()), (axis_name,))


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Tuple[str, str] = ("data", "model"),
              model_parallelism: Optional[int] = None,
              queue_width: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a 2-D mesh over the available devices.

    The default is the (data, model) mesh: ``model_parallelism`` defaults
    to 1 (pure data parallel) unless the device count is not a
    power-of-two multiple of it.  Tabular workloads are row-dominated; the
    model axis exists for wide-feature sharding of histogram builds and
    (D,D) normal-equation work.

    ``axis_names=("data", "grid")`` builds the SWEEP mesh instead: the
    second axis packs hyperparameter-grid candidates (vmapped same-family
    batches, selector.grid_groups) rather than feature columns.
    ``model_parallelism`` then names the grid-axis size; when omitted it
    is auto-selected from ``queue_width`` — the number of schedulable
    sweep units — via :func:`auto_grid_axis`.
    """
    devs = list(devices) if devices is not None else pod_default_devices()
    n = n_devices if n_devices is not None else len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    devs = devs[:n]
    mp = model_parallelism
    if mp is None:
        mp = (auto_grid_axis(n, queue_width)
              if axis_names[1] == "grid" and queue_width is not None else 1)
    if n % mp != 0:
        raise ValueError(
            f"n_devices={n} not divisible by "
            f"{axis_names[1]}_parallelism={mp}")
    arr = np.asarray(devs).reshape(n // mp, mp)
    return Mesh(arr, axis_names)


def auto_grid_axis(n_devices: int, queue_width: Optional[int]) -> int:
    """Grid-axis size for a (data, grid) sweep mesh.

    Rows dominate tabular sweep cost, so the data axis keeps at least
    half the devices; the grid axis takes power-of-two lanes up to the
    queue width (lanes beyond the candidate count would only hold
    padding candidates).  Deterministic in (n_devices, queue_width).
    """
    if not queue_width or queue_width <= 1 or n_devices <= 1:
        return 1
    g = 1
    while (g * 2 <= max(n_devices // 2, 1) and g * 2 <= queue_width
           and n_devices % (g * 2) == 0):
        g *= 2
    return g


def make_sweep_mesh(queue_width: int, n_devices: Optional[int] = None,
                    grid_parallelism: Optional[int] = None) -> Mesh:
    """The ("data", "grid") mesh for a selector sweep of ``queue_width``
    schedulable units (SweepWorkQueue) — shape auto-selected unless
    ``grid_parallelism`` pins the grid axis."""
    return make_mesh(n_devices, axis_names=("data", "grid"),
                     model_parallelism=grid_parallelism,
                     queue_width=queue_width)


def has_grid_axis(mesh) -> bool:
    """True for a sweep mesh (second axis packs grid candidates)."""
    return mesh is not None and "grid" in getattr(mesh, "axis_names", ())


def shard_map_compat(fn, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default (these
    kernels psum explicitly)."""
    from jax import shard_map as _sm
    return _sm(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
               check_vma=check)


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Rows sharded over the data axis — a (N,) label/weight vector."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


def feature_sharding(mesh: Mesh) -> NamedSharding:
    """A (D,) or (D, D) object sharded over the model axis."""
    return NamedSharding(mesh, P(mesh.axis_names[1]))


def matrix_sharding(mesh: Mesh) -> NamedSharding:
    """The (N, D) feature matrix: rows over data axis, columns over model."""
    return NamedSharding(mesh, P(mesh.axis_names[0], mesh.axis_names[1]))


def sweep_matrix_sharding(mesh: Mesh) -> NamedSharding:
    """The (N, D) matrix on a SWEEP mesh: rows over the data axis, columns
    replicated (the grid axis packs candidates, not features)."""
    return NamedSharding(mesh, P(mesh.axis_names[0], None))


def grid_sharding(mesh: Mesh) -> NamedSharding:
    """A per-candidate (C, ...) batch sharded over the grid axis."""
    return NamedSharding(mesh, P(mesh.axis_names[1]))


def fold_weight_sharding(mesh: Mesh) -> NamedSharding:
    """A stacked (F, N) fold-weight matrix: folds replicated, rows over
    the data axis (matches the row sharding of the matrix it masks)."""
    return NamedSharding(mesh, P(None, mesh.axis_names[0]))


def chain_sharding(mesh: Mesh) -> NamedSharding:
    """A per-chain (S, N) row-state matrix (boosting margins, chain
    weights) on a SWEEP mesh: chains over the grid axis, rows over the
    data axis — the tree grid groups' placement."""
    return NamedSharding(mesh, P(mesh.axis_names[1], mesh.axis_names[0]))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def next_shard_pad(mesh: Mesh, n_rows: int) -> int:
    """Rows to append so ``n_rows`` lands exactly on the NEXT data-axis
    tile boundary — guaranteeing the internal ``pad_to_multiple`` amount
    CHANGES, which is what the TM024 pad-invariance contract
    (``analysis/contracts.check_pad_invariance``) perturbs: results must
    not move when the padding does."""
    ndata = int(mesh.shape[mesh.axis_names[0]])
    rem = n_rows % ndata
    return (ndata - rem) if rem else ndata


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int = 0,
                    fill=0.0) -> Tuple[np.ndarray, int]:
    """Pad ``axis`` up to a multiple so it tiles evenly over a mesh axis.

    Static-shape substitute for Spark's arbitrary row partitioning; returns
    (padded, n_pad).  Callers carry a weight mask so padding rows are inert
    in every reduction.
    """
    size = arr.shape[axis]
    target = int(math.ceil(size / multiple)) * multiple if size else multiple
    n_pad = target - size
    if n_pad == 0:
        return arr, 0
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, n_pad)
    return np.pad(arr, widths, constant_values=fill), n_pad


def shard_dataset(X: np.ndarray, y: Optional[np.ndarray], mesh: Mesh,
                  w: Optional[np.ndarray] = None):
    """Place (X, y, w) onto the mesh: rows×cols sharded X, row-sharded y/w.

    Rows are zero-padded to tile the data axis and masked out via ``w``;
    columns are zero-padded to tile the model axis (inert: zero columns
    contribute nothing to matmuls and get zero weights back).
    Returns (X_dev, y_dev, w_dev) committed device arrays.
    """
    from ..models.trees import _dev_memo_sharded

    ndata = mesh.shape[mesh.axis_names[0]]
    grid_mesh = has_grid_axis(mesh)
    # a sweep mesh's second axis packs candidates, never feature columns
    nmodel = 1 if grid_mesh else mesh.shape[mesh.axis_names[1]]
    n_rows = X.shape[0]
    if w is None:
        w = np.ones(n_rows, np.float32)
    X, _ = pad_to_multiple(np.asarray(X, np.float32), ndata, axis=0)
    X, _ = pad_to_multiple(X, nmodel, axis=1)
    w, _ = pad_to_multiple(np.asarray(w, np.float32), ndata, axis=0)
    # content-memoized: the selector sweep re-shards the same fold matrices
    # for every grid candidate; one sharded upload serves them all
    xs = sweep_matrix_sharding(mesh) if grid_mesh else matrix_sharding(mesh)
    X_dev = _dev_memo_sharded(X, xs, "shard_X")
    w_dev = _dev_memo_sharded(w, data_sharding(mesh), "shard_w")
    y_dev = None
    if y is not None:
        y_pad, _ = pad_to_multiple(np.asarray(y, np.float32), ndata, axis=0)
        y_dev = _dev_memo_sharded(y_pad, data_sharding(mesh), "shard_y")
    return X_dev, y_dev, w_dev


def shard_sweep_inputs(X: np.ndarray, y: np.ndarray, mesh: Mesh,
                       fold_weights: Optional[np.ndarray] = None):
    """Commit a sweep's shared inputs onto a (data, grid) mesh.

    Rows zero-pad to tile the data axis; the pad rows carry ZERO weight in
    every stacked fold row, which makes them inert through the weighted
    column stats, the Newton/majorization Gram products and the histogram
    builds — sharded sweep results are invariant to the pad amount
    (property-tested in tests/test_parallel_mesh.py).

    Returns ``(X_dev, y_dev, W_dev)`` where ``W_dev`` is the (F, N_pad)
    stacked fold-weight matrix (None when ``fold_weights`` is None).
    """
    from ..models.trees import _dev_memo_sharded

    ndata = mesh.shape[mesh.axis_names[0]]
    Xp, _ = pad_to_multiple(np.asarray(X, np.float32), ndata, axis=0)
    yp, _ = pad_to_multiple(
        np.nan_to_num(np.asarray(y, np.float32)), ndata, axis=0)
    X_dev = _dev_memo_sharded(Xp, sweep_matrix_sharding(mesh), "sweep_X")
    y_dev = _dev_memo_sharded(yp, data_sharding(mesh), "sweep_y")
    W_dev = None
    if fold_weights is not None:
        Wp, _ = pad_to_multiple(
            np.ascontiguousarray(np.asarray(fold_weights, np.float32)),
            ndata, axis=1)
        W_dev = _dev_memo_sharded(Wp, fold_weight_sharding(mesh), "sweep_W")
    return X_dev, y_dev, W_dev
