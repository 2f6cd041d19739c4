"""Gate sharing of the random-forest grid against the program's OWN direct
growth.

``min_info_gain`` takes no part in choosing a node's split: it only decides
whether the chosen split is kept, and a node that fails keeps its rows in
its left child, which fails again.  So ``RFGridGroup`` grows ONE base forest
a ``min_instances_per_node`` value (deepest depth, lowest gate) and
``gbdt_kernels.prune_rf_grid`` reads every other candidate off the base's
level values and gate ratios.  ``tests/test_rf_grid_reference.py`` holds the
group to a float64 reference; here every derived candidate is held to the
SAME candidate grown directly by ``grow_rf_grid`` under its own gate and
depth: heaps ``array_equal`` (dead nodes included), leaves to 1e-6 (f32
histograms and integer weights on the CPU: every sum is exact).
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.generators.planted_linear import generate  # noqa: E402
from perfbench.reference import rf_grid  # noqa: E402

ROWS, COLS, TREES, FOLDS, BINS, SEED = 2000, 16, 4, 2, 32, 42
GATES = (0.001, 0.01, 0.1)
DEPTHS = (1, 2, 3)
INSTS = (10.0, 100.0)
BASE_DEPTH = max(DEPTHS)


def _table():
    frame, _ = generate(ROWS, COLS, 7)
    A = frame.to_numpy(np.float32)
    return np.ascontiguousarray(A[:, 1:]), A[:, 0]


def _fold_weights(y, base_w):
    fold = np.random.default_rng(3).integers(0, FOLDS, ROWS)
    return np.stack([base_w * (fold != f) for f in range(FOLDS)]
                    ).astype(np.float32)


def _grow(binned, Y, W, gates, insts, depths, folds, cls, **kw):
    import jax.numpy as jnp

    from transmogrifai_tpu.models.gbdt_kernels import grow_rf_grid

    return grow_rf_grid(
        binned, jnp.asarray(Y), jnp.asarray(W), seed=SEED, n_trees=TREES,
        pair_fold=np.asarray(folds, np.int32),
        pair_min_ig=np.asarray(gates, np.float32),
        pair_min_inst=np.asarray(insts, np.float32),
        pair_depth=np.asarray(depths, np.int32), msub=4,
        subsample_rate=1.0, n_bins=BINS, onehot_targets=cls, **kw)


def _derive(base, sel, gates, depth):
    from transmogrifai_tpu.models.gbdt_kernels import prune_rf_grid

    return [np.asarray(a) for a in prune_rf_grid(
        *base, np.asarray(sel, np.int32), np.asarray(gates, np.float32),
        depth=depth, n_bins=BINS)]


def _assert_same(got, want, leaf_atol=1e-6):
    assert np.array_equal(got[0], np.asarray(want[0]))
    assert np.array_equal(got[1], np.asarray(want[1]))
    np.testing.assert_allclose(got[2], np.asarray(want[2]), atol=leaf_atol)


class _Sharing:
    """Bases (one a ``min_instances`` x fold, lowest gate, deepest depth) and
    every (gate, depth, min_instances) candidate grown directly, one call a
    depth so that a candidate's heap has its own depth."""

    def __init__(self, binned, Y, W, cls, gates):
        self.gates = gates
        self.pairs = [(i, f) for i in INSTS for f in range(FOLDS)]
        self.base = _grow(
            binned, Y, W, [min(gates)] * len(self.pairs),
            [i for i, _ in self.pairs], [BASE_DEPTH] * len(self.pairs),
            [f for _, f in self.pairs], cls, prune_outputs=True)
        self.direct = {}
        for depth in DEPTHS:
            cands = [(g, i, f) for g in gates for i, f in self.pairs]
            out = _grow(binned, Y, W, [c[0] for c in cands],
                        [c[1] for c in cands], [depth] * len(cands),
                        [c[2] for c in cands], cls)
            for p, (g, i, f) in enumerate(cands):
                self.direct[(g, depth, i, f)] = [np.asarray(a[p])
                                                 for a in out]

    def check(self, gate, depth, inst, leaf_atol=1e-6):
        sel = [p for p, (i, _) in enumerate(self.pairs) if i == inst]
        got = _derive(self.base, sel, [gate] * len(sel), depth)
        for j, p in enumerate(sel):
            want = self.direct[(gate, depth, inst, self.pairs[p][1])]
            assert want[0].shape == (TREES, 2 ** depth - 1)
            _assert_same([a[j] for a in got], want, leaf_atol)
        return got


@pytest.fixture(scope="module")
def binary():
    from transmogrifai_tpu.models.trees import _prep_tree_inputs_weighted

    X, y = _table()
    rng = np.random.default_rng(3)
    base_w = np.where((y > 0) & (rng.random(ROWS) < 0.3), 2.0, 1.0)
    _, binned = _prep_tree_inputs_weighted(X, BINS, row_weight=base_w)
    Y = np.eye(2, dtype=np.float32)[y.astype(int)]
    return _Sharing(binned, Y, _fold_weights(y, base_w), True, GATES)


@pytest.mark.parametrize("inst", INSTS, ids=lambda v: f"n{int(v)}")
@pytest.mark.parametrize("depth", DEPTHS, ids=lambda v: f"d{v}")
@pytest.mark.parametrize("gate", GATES, ids=lambda v: f"ig{v}")
def test_derived_candidate_is_the_directly_grown_one(binary, gate, depth,
                                                     inst):
    binary.check(gate, depth, inst)


def test_the_gates_cut_nodes_the_base_split(binary):
    """The comparison above is not one of equal trees: each higher gate
    leaves fewer splits, and the highest some but not all."""
    splits = []
    for gate in GATES:
        got = binary.check(gate, BASE_DEPTH, INSTS[0])
        splits.append(int((got[1] < BINS).sum()))
    assert splits[0] > splits[1] > splits[2] > 0


def test_fractional_weights_hold_at_the_histogram_s_tolerance():
    """A balancer's non-integer weights: a cut node's value is its level's
    histogram total where direct growth sums the leaf's rows, so the two
    differ by the rounding of f32 sums taken in another order."""
    from transmogrifai_tpu.models.trees import _prep_tree_inputs_weighted

    X, y = _table()
    base_w = np.where(y > 0, 1.7320508, 0.5773503)
    _, binned = _prep_tree_inputs_weighted(X, BINS, row_weight=base_w)
    Y = np.eye(2, dtype=np.float32)[y.astype(int)]
    sharing = _Sharing(binned, Y, _fold_weights(y, base_w), True,
                       (0.001, 0.02))
    for depth in DEPTHS:
        sharing.check(0.02, depth, INSTS[0], leaf_atol=1e-5)


# -- regression and multi-class forests ---------------------------------------

def _other(kind):
    from transmogrifai_tpu.models.trees import _prep_tree_inputs

    X, y = _table()
    rng = np.random.default_rng(5)
    score = X[:, :4] @ np.asarray([1.0, -0.7, 0.5, 0.3], np.float32)
    _, binned = _prep_tree_inputs(X, BINS)
    base_w = np.ones(ROWS)
    if kind == "regression":
        # integer targets keep every histogram sum exact
        Y = np.round(3 * score + rng.normal(size=ROWS))[:, None].astype(
            np.float32)
        gates = (0.01, 0.3, 1.5)
    else:
        cls = np.digitize(score + 0.5 * rng.normal(size=ROWS), [-0.6, 0.6])
        Y = np.eye(3, dtype=np.float32)[cls]
        gates = (0.001, 0.01, 0.05)
    return _Sharing(binned, Y, _fold_weights(y, base_w), kind != "regression",
                    gates)


@pytest.fixture(scope="module", params=["regression", "multiclass"])
def other(request):
    return _other(request.param)


@pytest.mark.parametrize("depth", DEPTHS, ids=lambda v: f"d{v}")
@pytest.mark.parametrize("which", [0, 1, 2], ids=["base", "mid", "high"])
def test_regression_and_three_class_candidates_alike(other, which, depth):
    got = other.check(other.gates[which], depth, INSTS[0])
    if depth == BASE_DEPTH and which:
        base = other.check(other.gates[0], depth, INSTS[0])
        assert 0 < (got[1] < BINS).sum() < (base[1] < BINS).sum()


# -- the group ----------------------------------------------------------------

def _run_group(grid_kw, mesh=None, refit_rows=()):
    """``RFGridGroup.run`` on the binary table with every scoring part
    recorded; returns metrics, counters, the parts and the refits."""
    from transmogrifai_tpu.models import OpRandomForestClassifier
    from transmogrifai_tpu.models.trees import clear_sweep_caches
    from transmogrifai_tpu.selector import grid, grid_groups
    from transmogrifai_tpu.utils import profiling

    X, y = _table()
    rng = np.random.default_rng(3)
    base_w = np.where((y > 0) & (rng.random(ROWS) < 0.3), 2.0, 1.0)
    W = _fold_weights(y, base_w)
    ctxs = [(W[f], (base_w - W[f]).astype(np.float32)) for f in range(FOLDS)]
    points = grid(**grid_kw)
    group = grid_groups.RFGridGroup(
        OpRandomForestClassifier(num_trees=TREES), points, "AuPR")
    if mesh is not None:
        group.with_mesh(mesh)
    parts = []
    score = grid_groups._score_pairs_jit

    def recording(binned, feats, threshs, leaves, depth, mode, ptype):
        parts.append((depth, np.asarray(feats), np.asarray(threshs),
                      np.asarray(leaves)))
        return score(binned, feats, threshs, leaves, depth, mode, ptype)

    clear_sweep_caches()
    profiling.reset_counters()
    grid_groups._score_pairs_jit = recording
    try:
        metrics = np.asarray(group.run(X, y, ctxs))
        refits = {row: group.refit_model(row) for row in refit_rows}
    finally:
        grid_groups._score_pairs_jit = score
    return {"points": points, "metrics": metrics, "parts": parts,
            "refits": refits, "X": X, "y": y, "base_w": base_w, "W": W,
            "counters": profiling.COUNTERS.to_json()["rfGrid"]}


def _reference_forest(run, point, weight):
    from transmogrifai_tpu.models.gbdt_kernels import rf_bags_and_features
    from transmogrifai_tpu.models.trees import (_feature_subset_size,
                                                _prep_tree_inputs_weighted)

    msub = _feature_subset_size("auto", COLS, True)
    bags, subsets = rf_bags_and_features(SEED, TREES, ROWS, COLS, msub, 1.0)
    _, binned = _prep_tree_inputs_weighted(run["X"], BINS,
                                           row_weight=run["base_w"])
    return rf_grid.grow_forest(
        np.asarray(binned), run["y"].astype(np.int64), weight, bags, subsets,
        point["max_depth"], point["min_info_gain"],
        point["min_instances_per_node"], BINS)


@pytest.fixture(scope="module")
def two_gates():
    return _run_group({"max_depth": [1, 3], "min_info_gain": [0.001, 0.02],
                       "min_instances_per_node": [10]},
                      refit_rows=(1, 2, 3))


def test_two_gate_grid_counts_one_base(two_gates):
    got = dict(two_gates["counters"])
    for shape in ("chunk", "launches", "msub", "levels", "scoredRows"):
        got.pop(shape)
    # one base at (depth 3, gate 0.001) x folds, and a pair a refit; the
    # longer fold trains on 1,028 rows, which round up to the table's 2,000:
    # grown on every row
    assert got == {"candidates": 4, "bases": 1, "pairs": FOLDS + 3,
                   "truncated": 2, "gateShared": 2,
                   "treesGrown": (FOLDS + 3) * TREES, "grownRows": ROWS}


@pytest.mark.parametrize("row", [1, 2, 3],
                         ids=["pruned-and-truncated", "base", "pruned"])
def test_refit_of_a_shared_winner_is_the_reference_s_forest(two_gates, row):
    point = two_gates["points"][row]
    assert (point["max_depth"], point["min_info_gain"]) == [
        None, (1, 0.02), (3, 0.001), (3, 0.02)][row]
    model = two_gates["refits"][row]
    want = _reference_forest(two_gates, point, two_gates["base_w"])
    assert want[0].shape == (TREES, 2 ** point["max_depth"] - 1)
    _assert_same([np.asarray(model.feat), np.asarray(model.thresh),
                  np.asarray(model.leaf)], want)


def test_sharded_leg_reads_the_same_candidates_off_its_bases(two_gates):
    """Four virtual devices, 2 x 2: rows over ``data``, trees over ``grid``,
    histograms psum'd; the same base, ratios and level values, so the same
    parts and the same metric rows."""
    from transmogrifai_tpu.parallel import make_sweep_mesh

    mesh = make_sweep_mesh(2, n_devices=4)
    assert dict(mesh.shape) == {"data": 2, "grid": 2}
    sharded = _run_group({"max_depth": [1, 3],
                          "min_info_gain": [0.001, 0.02],
                          "min_instances_per_node": [10]}, mesh=mesh)
    assert sharded["counters"]["gateShared"] == 2
    assert sharded["counters"]["bases"] == 1
    assert sharded["counters"]["treesGrown"] == FOLDS * TREES
    assert [p[0] for p in sharded["parts"]] == [
        p[0] for p in two_gates["parts"]] == [3, 1]
    for got, want in zip(sharded["parts"], two_gates["parts"]):
        _assert_same(got[1:], want[1:])
    np.testing.assert_allclose(sharded["metrics"], two_gates["metrics"],
                               atol=1e-6)


def test_one_gate_grid_shares_by_depth_alone_and_grows_the_same_trees():
    run = _run_group({"max_depth": [1, 2, 3], "min_info_gain": [0.01],
                      "min_instances_per_node": [10, 100]})
    got = run["counters"]
    assert (got["candidates"], got["bases"], got["truncated"],
            got["gateShared"], got["treesGrown"]) == (
        6, 2, 4, 0, 2 * FOLDS * TREES)
    assert [p[0] for p in run["parts"]] == [3, 1, 2]
    for depth, feats, threshs, leaves in run["parts"]:
        # fold-major: a fold's pairs are scored together
        cps = [(c, f) for f in range(FOLDS)
               for c, p in enumerate(run["points"])
               if p["max_depth"] == depth]
        assert len(cps) == len(feats)
        for i, (c, f) in enumerate(cps):
            want = _reference_forest(run, run["points"][c], run["W"][f])
            _assert_same([feats[i], threshs[i], leaves[i]], want)


def test_one_point_grid_asks_the_kernel_for_nothing(monkeypatch):
    """A candidate that is its own base: the growth program is the one
    without the pruning outputs, and nothing is derived."""
    from transmogrifai_tpu.models import gbdt_kernels

    asked = []
    grow = gbdt_kernels.grow_rf_grid

    def recording(*a, **kw):
        asked.append(kw["prune_outputs"])
        out = grow(*a, **kw)
        assert len(out) == 3
        return out

    monkeypatch.setattr(gbdt_kernels, "grow_rf_grid", recording)
    monkeypatch.setattr(gbdt_kernels, "prune_rf_grid", None)
    run = _run_group({"max_depth": [3], "min_info_gain": [0.01],
                      "min_instances_per_node": [10]}, refit_rows=(0,))
    assert asked == [False, False]
    assert (run["counters"]["bases"], run["counters"]["truncated"],
            run["counters"]["gateShared"]) == (1, 0, 0)
    want = _reference_forest(run, run["points"][0], run["base_w"])
    model = run["refits"][0]
    _assert_same([np.asarray(model.feat), np.asarray(model.thresh),
                  np.asarray(model.leaf)], want)


# -- the kernel's other callers -----------------------------------------------

def _tree_jaxpr(**kw):
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models import gbdt_kernels as gk

    n, d, depth = 64, 5, 3

    def fn(binned, g, h, c):
        return gk._grow_tree_traced(
            binned, g, h, c, jnp.ones(d, bool), jnp.int32(depth),
            max_depth=depth, n_bins=8, lam=jnp.float32(1.0),
            min_child_weight=jnp.float32(0.0),
            min_info_gain=jnp.float32(0.0), min_instances=jnp.float32(1.0),
            newton_leaf=jnp.bool_(True), learning_rate=jnp.float32(0.1),
            min_gain_raw=jnp.float32(0.0), **kw)

    return jax.make_jaxpr(fn)(jnp.zeros((n, d), jnp.int8),
                              jnp.zeros((n, 1)), jnp.ones((n, 1)),
                              jnp.ones(n)), depth


def test_a_chain_s_tree_is_untouched_when_the_outputs_are_not_asked_for():
    """The boosting chains call the kernel without the opt-in: three
    arrays out and no equation more than naming the flag off gives; asked
    for, the level values, the ratio heap and the unsplit feature come on
    top."""
    plain, depth = _tree_jaxpr()
    off, _ = _tree_jaxpr(prune_outputs=False)
    on, _ = _tree_jaxpr(prune_outputs=True)
    assert str(plain) == str(off)
    assert [v.aval.shape for v in plain.jaxpr.outvars] == [
        (2 ** depth - 1,), (2 ** depth - 1,), (2 ** depth, 1)]
    extra = on.jaxpr.outvars[3:]
    assert [v.aval.shape for v in extra] == [
        (2 ** lv, 1) for lv in range(depth)] + [(2 ** depth - 1,), ()]
    assert len(on.jaxpr.eqns) > len(plain.jaxpr.eqns)


def test_gbt_chain_program_returns_what_it_returned():
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.models.gbdt_kernels import _gbt_chain_rounds_jit

    n, d, S, R, depth = 96, 4, 2, 2, 3
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, 8, (n, d)), jnp.int8)
    y = jnp.asarray(rng.random(n) < 0.4, jnp.float32)
    vec = lambda v, dt=jnp.float32: jnp.full(S, v, dt)  # noqa: E731
    out = jax.eval_shape(
        lambda: _gbt_chain_rounds_jit(
            binned, y, jnp.ones((S, n)), jnp.zeros((S, n)),
            jnp.zeros(1, jnp.int32), vec(depth, jnp.int32), vec(1.0),
            vec(1.0), vec(0.0), vec(1.0), vec(0.1), vec(0.0), R, depth, 8,
            "binary"))
    assert [o.shape for o in out] == [
        (S, n), (R, S, 2 ** depth - 1), (R, S, 2 ** depth - 1),
        (R, S, 2 ** depth, 1), (R, S)]
