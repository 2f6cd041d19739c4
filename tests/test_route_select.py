"""A level is routed by a select over its slots, not by a gather a row.

``route_level`` fetches the WHOLE feature row of each of a level's slots
from the rows-minor matrix, compares every row under every slot's
threshold and keeps the answer of the row's own slot; ``leaf_by_slot``
reads the leaf values the same way (PR 31; docs/performance.md).  Past
``_by_select``'s bound (levels of 2,048 slots and more, or more than four
slots a row) a level asks a row at a time, as all did before.  The cases
below hold the four sites that use them (growth's ``tree.route``, the
in-launch margin update, ``predict_tree``, ``predict_ensemble``) to a plain
NumPy walker, a loop over levels that reads ``binned[row, feat[node]]``:
node ids and outputs bit-equal, on both sides of the bound.  Growth is held
to PR 29's references besides (``perfbench/reference/hist_gbt.py``; the
level histograms to ``np.add.at`` in ``test_hist_onehot_layout.py``), and
the last tests walk the jaxprs: within the bound no ``gather`` of one
element a row is left in them.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import transmogrifai_tpu.models.gbdt_kernels as gk

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perfbench.reference import hist_gbt  # noqa: E402

B = 32


def _go_right(x, t, end=None):
    """The routing rule, in NumPy: ``t`` in [0, B-1) right iff bin > t;
    ``t == B`` always left; ``t < 0`` threshold -t-1 and bin 0 goes right;
    a bundled split also wants the bin at or under its member's end."""
    x = x.astype(np.int64)
    dr = t < 0
    right = (x > np.where(dr, -t - 1, t)) | (dr & (x == 0))
    if end is not None:
        right &= x <= end
    return right


def _walk(binned, feat, thresh, depth, end_bin=None):
    """Leaf of every row: a loop over levels, one element a row."""
    node = np.zeros(len(binned), np.int64)
    rows = np.arange(len(binned))
    for level in range(depth):
        heap = 2 ** level - 1 + node
        f, t = feat[heap], thresh[heap]
        end = (end_bin[np.clip(t, 0, B - 1), f] if end_bin is not None
               else None)
        node = 2 * node + _go_right(binned[rows, f], t, end)
    return node


def _thresholds(rng, shape, kind):
    t = rng.integers(0, B - 1, shape)
    u = rng.random(shape)
    if kind in ("default-direction", "mixed"):
        t = np.where(u < 0.3, -(t + 1), t)
    if kind in ("no-split", "mixed"):
        t = np.where(u > 0.7, B, t)
    return t.astype(np.int32)


def _end_table(rng, d):
    """A (B, d) member-end table: B - 1 (the plain rule) in half the
    columns, an end a few bins over the threshold in the others."""
    ts = np.arange(B)[:, None]
    end = np.minimum(ts + rng.integers(0, 6, (B, d)), B - 1)
    return np.where(rng.random(d)[None, :] < 0.5, B - 1, end).astype(np.int32)


def _fresh_jit(fn, **static):
    """``fn``'s Python body under a jit of its own, so that a constant the
    test has just set is read by a new trace."""
    return jax.jit(functools.partial(getattr(fn, "__wrapped__", fn),
                                     **static))


# id: (rows, columns, slots, threshold kind, bundled, dtype, block bytes,
#      compacted layout, by the select?)
LEVELS = {
    "plain-int8": (700, 12, 8, "plain", False, np.int8, None, False, True),
    "plain-int32": (700, 12, 8, "plain", False, np.int32, None, False, True),
    "default-direction": (700, 12, 16, "default-direction", False, np.int8,
                          None, False, True),
    "no-split-sentinel": (700, 12, 16, "no-split", False, np.int8, None,
                          False, True),
    "bundled-interval": (700, 12, 16, "mixed", True, np.int8, None, False,
                         True),
    "one-slot": (700, 12, 1, "mixed", False, np.int8, None, False, True),
    "several-slot-blocks": (700, 12, 64, "mixed", False, np.int8, 8 * 700,
                            False, True),
    "a-slot-a-step": (300, 12, 32, "mixed", True, np.int32, 1, False, True),
    "compacted-level": (50, 12, 64, "mixed", False, np.int8, None, True,
                        True),
    "one-row-four-slots": (1, 12, 4, "mixed", False, np.int8, None, False,
                           True),
    "one-row-a-row-at-a-time": (1, 12, 8, "mixed", True, np.int8, None,
                                False, False),
    "2048-slots-a-row-at-a-time": (3000, 12, 2048, "mixed", True, np.int32,
                                   None, False, False),
    "1024-slots": (3000, 12, 1024, "mixed", False, np.int8, None, False,
                   True),
}


@pytest.mark.parametrize("case", list(LEVELS))
def test_route_level_answers_as_the_row_s_own_slot(case, monkeypatch):
    n, d, m, kind, bundled, dtype, block, compact, selects = LEVELS[case]
    assert gk._by_select(m, n) == selects
    rng = np.random.default_rng(len(case) + n)
    binned = rng.integers(0, B, (n, d)).astype(dtype)
    fid = rng.integers(0, d, m).astype(np.int32)
    thresh = _thresholds(rng, m, kind)
    if compact:
        # rows < slots: every row a slot of its own, the rest empty
        slot = rng.permutation(m)[:n].astype(np.int32)
    else:
        slot = rng.integers(0, m, n).astype(np.int32)
    end_l = None
    if bundled:
        end_l = _end_table(rng, d)[np.clip(thresh, 0, B - 1), fid]
    if block is not None:
        monkeypatch.setattr(gk, "ROUTE_BLOCK_BYTES", block)
        assert block // (n * np.dtype(dtype).itemsize) < m
    got = _fresh_jit(gk.route_level)(
        jnp.asarray(binned.T), jnp.asarray(slot), jnp.asarray(fid),
        jnp.asarray(thresh),
        None if end_l is None else jnp.asarray(end_l))
    want = _go_right(binned[np.arange(n), fid[slot]], thresh[slot],
                     None if end_l is None else end_l[slot])
    assert got.dtype == bool and got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert 0 < want.sum() < n or n == 1


def test_the_select_s_bound_is_the_probe_s():
    """1,024 slots a level and four a row (one v5e, PR 31: a level breaks
    even at some 1,800 slots; a 1-row call gathers an element a slot)."""
    assert gk._by_select(1024, 250_000) and not gk._by_select(2048, 250_000)
    assert gk._by_select(1024, 256) and not gk._by_select(1024, 255)
    assert gk._by_select(4, 1) and not gk._by_select(8, 1)


# id: (rows, columns, depth, trees, K, threshold kind, dtype, trees a chunk
#      or None, block bytes or None)
ENSEMBLES = {
    "plain-k1": (900, 20, 5, 4, 1, "plain", np.int8, None, None),
    "mixed-k3": (900, 20, 5, 4, 3, "mixed", np.int8, None, None),
    "int32-matrix": (900, 20, 4, 3, 1, "mixed", np.int32, None, None),
    "default-direction-k1": (900, 20, 6, 2, 1, "default-direction", np.int8,
                             None, None),
    "several-chunks": (900, 20, 4, 7, 1, "mixed", np.int8, 3, None),
    "a-tree-a-chunk-k3": (400, 20, 4, 5, 3, "mixed", np.int8, 1, 4 * 400),
    "one-row": (1, 20, 6, 9, 1, "mixed", np.int8, None, None),
    "one-row-k3-chunks": (1, 20, 5, 9, 3, "mixed", np.int8, 4, None),
    "deeper-than-rows": (40, 8, 8, 3, 1, "mixed", np.int8, None, None),
    "twenty-trees-k3": (300, 20, 4, 20, 3, "mixed", np.int8, None, None),
    "nineteen-trees-chunks-of-two": (300, 20, 4, 19, 1, "mixed", np.int8, 2,
                                     None),
    "depth-12-past-the-bound": (700, 20, 12, 2, 1, "mixed", np.int8, None,
                                None),
}


@pytest.mark.parametrize("case", list(ENSEMBLES))
def test_predictors_walk_like_numpy(case, monkeypatch):
    n, d, depth, T, k, kind, dtype, chunk, block = ENSEMBLES[case]
    rng = np.random.default_rng(len(case) + d)
    binned = rng.integers(0, B, (n, d)).astype(dtype)
    feat = rng.integers(0, d, (T, 2 ** depth - 1)).astype(np.int32)
    thresh = _thresholds(rng, (T, 2 ** depth - 1), kind)
    leaf = rng.standard_normal((T, 2 ** depth, k)).astype(np.float32)
    if block is not None:
        monkeypatch.setattr(gk, "ROUTE_BLOCK_BYTES", block)
    if chunk is not None:
        per_tree = (min(gk.ROUTE_BLOCK_BYTES,
                        2 ** (depth - 1) * n * np.dtype(dtype).itemsize)
                    + n * (4 + 4 * k))
        monkeypatch.setattr(gk, "ENSEMBLE_CHUNK_BYTES", chunk * per_tree)
    bj = jnp.asarray(binned)

    nodes = [_walk(binned, feat[t], thresh[t], depth) for t in range(T)]
    one_tree = _fresh_jit(gk.predict_tree, max_depth=depth)
    for t in range(T):
        got = np.asarray(one_tree(bj, jnp.asarray(feat[t]),
                                  jnp.asarray(thresh[t]),
                                  jnp.asarray(leaf[t])))
        assert got.shape == (n, k)
        np.testing.assert_array_equal(got, leaf[t][nodes[t]])

    # the sum over trees in float32, in the program's stated order: tree i
    # into lane i mod 8, then the eight lanes by halves
    lanes = np.zeros((8, n, k), np.float32)
    for t in range(T):
        lanes[t % 8] = lanes[t % 8] + leaf[t][nodes[t]]
    want = (((lanes[0] + lanes[4]) + (lanes[2] + lanes[6]))
            + ((lanes[1] + lanes[5]) + (lanes[3] + lanes[7])))
    got = _fresh_jit(gk.predict_ensemble, max_depth=depth)(
        bj, jnp.asarray(feat), jnp.asarray(thresh), jnp.asarray(leaf))
    assert got.shape == (n, k)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [np.int8, np.int32])
def test_a_bundled_tree_walks_under_its_members_ends(dtype):
    """``_predict_tree_T`` with a member-end table (the in-launch margin
    update of EFB growth and of the sharded chains)."""
    n, d, depth = 800, 10, 5
    rng = np.random.default_rng(31)
    binned = rng.integers(0, B, (n, d)).astype(dtype)
    feat = rng.integers(0, d, 2 ** depth - 1).astype(np.int32)
    thresh = _thresholds(rng, 2 ** depth - 1, "mixed")
    leaf = rng.standard_normal((2 ** depth, 1)).astype(np.float32)
    end_bin = _end_table(rng, d)
    got = jax.jit(functools.partial(gk._predict_tree_T, max_depth=depth))(
        jnp.asarray(binned.T), jnp.asarray(feat), jnp.asarray(thresh),
        jnp.asarray(leaf), bundle_end=jnp.asarray(end_bin))
    node = _walk(binned, feat, thresh, depth, end_bin)
    assert (node != _walk(binned, feat, thresh, depth)).any()
    np.testing.assert_array_equal(np.asarray(got), leaf[node].T)


# -- growth: the tree's own leaves say where it routed its rows ---------------

# id: (rows, columns, depth, subset size or None, K, dtype)
GROWN = {
    "full-width-k1": (600, 10, 4, None, 1, np.int8),
    "subset-k1": (600, 10, 4, 4, 1, np.int8),
    "subset-k3-int32": (600, 10, 4, 4, 3, np.int32),
    "compacted-levels": (50, 6, 8, None, 1, np.int8),
    "compacted-subset": (50, 6, 8, 3, 1, np.int8),
}


@pytest.mark.parametrize("case", list(GROWN))
def test_growth_routes_its_rows_where_the_walker_does(case):
    """``_grow_tree_traced`` emits leaf MEANS of the rows it routed into
    each leaf: the walker, over the emitted splits, must find the same rows
    there (full width, the feature subset through ``feat_idx``, levels with
    more nodes than rows, which are compacted)."""
    n, d, depth, msub, k, dtype = GROWN[case]
    rng = np.random.default_rng(len(case))
    binned = rng.integers(0, B, (n, d)).astype(dtype)
    # targets on the 1/64 grid with signal in two columns of the subset
    feat_idx = None if msub is None else np.sort(
        rng.choice(d, msub, replace=False)).astype(np.int32)
    cols = np.arange(d) if feat_idx is None else feat_idx
    sig = binned[:, cols[0]] / 8.0 - binned[:, cols[-1]] / 16.0
    Y = np.stack([np.round((np.tanh(sig + c) + 0.2 * rng.normal(size=n))
                           * 64) / 64 for c in range(k)], 1)
    w = rng.integers(1, 3, n).astype(np.float32)
    G = (w[:, None] * Y).astype(np.float32)
    H = np.broadcast_to(w[:, None], G.shape)
    assert (2 ** (depth - 1) > n) == case.startswith("compacted")

    def grow(b, g, h, c, fi):
        return gk._grow_tree_traced(
            b, g, h, c, jnp.ones(d, bool), jnp.int32(depth), max_depth=depth,
            n_bins=B, lam=jnp.float32(1e-3),
            min_child_weight=jnp.float32(0.0),
            min_info_gain=jnp.float32(0.0), min_instances=jnp.float32(1.0),
            newton_leaf=jnp.bool_(False), learning_rate=jnp.float32(1.0),
            bag_mode="bagged", feat_idx=fi)

    feat, thresh, leaf, _ = jax.jit(grow)(
        jnp.asarray(binned), jnp.asarray(G), jnp.asarray(H), jnp.asarray(w),
        None if feat_idx is None else jnp.asarray(feat_idx))
    feat, thresh, leaf = (np.asarray(a) for a in (feat, thresh, leaf))
    assert (thresh < B).sum() >= 3, "a tree that hardly splits"
    assert set(feat[thresh < B]) <= set(cols)

    node = _walk(binned, feat, thresh, depth)
    Cs = np.bincount(node, w, 2 ** depth)
    for c in range(k):
        Gs = np.bincount(node, G[:, c].astype(np.float64), 2 ** depth)
        np.testing.assert_allclose(
            leaf[:, c], Gs / np.maximum(Cs, 1e-12), rtol=0, atol=2e-6)
    assert (leaf[Cs == 0] == 0).all()


def _logistic_grad(F, y):
    """Gradient and hessian of a chain with unit weights, as the launch
    computes them (float32)."""
    P = jax.nn.sigmoid(jnp.asarray(F))
    return (np.asarray(P - y), np.asarray(jnp.maximum(P * (1 - P), 1e-6)))


@pytest.mark.parametrize("goss", [False, True], ids=["all-rows", "goss"])
def test_a_chain_launch_grows_the_reference_s_trees(goss):
    """One ``_gbt_chain_rounds_jit`` launch, two chains x three rounds: each
    round's tree is the plain reference's tree on the rows and gradients
    the chain had then (the margins rebuilt by the NumPy walker from the
    rounds before, so a wrong margin update shows in the next tree), and
    the margins handed back are the walker's, bit for bit."""
    n, d, depth, S, R, lam, lr = 3000, 16, 4, 2, 3, 1.0, 0.3
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X @ rng.normal(size=d) > 0).astype(np.float32)
    binned = hist_gbt.bin_matrix(X, hist_gbt.quantile_edges(X, B))
    plan = (n // 5, n // 5) if goss else None       # amplification 4: exact
    mcw = np.asarray([1.0, 3.0], np.float32)        # the chains differ
    vec = functools.partial(jnp.full, (S,), dtype=jnp.float32)
    Fm_end, fs, ts, lfs, _ = gk._gbt_chain_rounds_jit(
        jnp.asarray(binned.astype(np.int8)), jnp.asarray(y),
        jnp.ones((S, n), jnp.float32), jnp.zeros((S, n), jnp.float32),
        jnp.zeros(1, jnp.int32), jnp.full((S,), depth, jnp.int32),
        vec(lam), jnp.asarray(mcw), vec(0.0), vec(0.0), vec(lr), vec(0.0),
        R, depth, B, "binary", False, False, skip_counts=True, goss=plan,
        goss_seed=jnp.int32(9), chain_ids=jnp.arange(S, dtype=jnp.int32),
        round_offset=jnp.int32(0))
    fs, ts, lfs = (np.asarray(a) for a in (fs, ts, lfs))
    for c in range(S):
        F = np.zeros(n, np.float32)
        for r in range(R):
            g, h = _logistic_grad(F, y)
            idx, mult = np.arange(n), np.ones(n)
            if goss:
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(9), r), c)
                idx, mult = gk._goss_select(jnp.abs(jnp.asarray(g)), key,
                                            *plan)
                idx, mult = np.asarray(idx), np.asarray(mult, np.float64)
            want_f, want_t, node = hist_gbt.grow_tree(
                binned[idx], (g[idx] * mult)[:, None],
                (h[idx] * mult)[:, None], mult, depth, B, lam,
                min_child_weight=float(mcw[c]))
            assert (want_t < B).sum() >= 3, "a tree that hardly splits"
            np.testing.assert_array_equal(fs[r, c], want_f)
            np.testing.assert_array_equal(ts[r, c], want_t)
            Gs = np.bincount(node, g[idx] * mult, 2 ** depth)
            Hs = np.bincount(node, h[idx] * mult, 2 ** depth)
            np.testing.assert_allclose(lfs[r, c, :, 0], -lr * Gs / (Hs + lam),
                                       rtol=0, atol=1e-5)
            # the update: the round's own tree over ALL rows
            F = F + lfs[r, c, :, 0][_walk(binned, fs[r, c], ts[r, c], depth)]
        np.testing.assert_array_equal(np.asarray(Fm_end)[c], F)


# -- no gather of one element a row is left -----------------------------------

def _gathers(jaxpr):
    """``(slice sizes, elements of the result)`` of every ``gather`` of a
    jaxpr, the jaxprs inside its equations (jit, scan, while, vmap'd calls)
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((tuple(eqn.params["slice_sizes"]),
                        int(np.prod(eqn.outvars[0].aval.shape))))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_gathers(inner))
    return out


def _element_gathers(jaxpr, rows):
    """Gathers whose slices are single elements and whose result has at
    least ``rows`` of them: what routing a row at a time looks like."""
    return [g for g in _gathers(jaxpr)
            if all(s == 1 for s in g[0]) and g[1] >= rows]


def _programs(n, d, depth):
    A = jax.ShapeDtypeStruct
    nodes = 2 ** depth - 1
    S, plan = 2, (n // 5, n // 5)
    vec = A((S,), jnp.float32)
    return {
        "predict_tree": lambda: gk.predict_tree.trace(
            A((n, d), jnp.int8), A((nodes,), jnp.int32),
            A((nodes,), jnp.int32), A((nodes + 1, 1), jnp.float32), depth),
        "predict_ensemble": lambda: gk.predict_ensemble.trace(
            A((n, d), jnp.int8), A((6, nodes), jnp.int32),
            A((6, nodes), jnp.int32), A((6, nodes + 1, 3), jnp.float32),
            depth),
        "chain-launch-goss": lambda: gk._gbt_chain_rounds_jit.trace(
            A((n, d), jnp.int8), A((n,), jnp.float32),
            A((S, n), jnp.float32), A((S, n), jnp.float32),
            A((1,), jnp.int32), A((S,), jnp.int32), vec, vec, vec, vec, vec,
            vec, 2, depth, B, "binary", False, False, skip_counts=True,
            goss=plan, goss_seed=A((), jnp.int32),
            chain_ids=A((S,), jnp.int32), round_offset=A((), jnp.int32)),
        "chain-launch-all-rows": lambda: gk._gbt_chain_rounds_jit.trace(
            A((n, d), jnp.int8), A((n,), jnp.float32),
            A((S, n), jnp.float32), A((S, n), jnp.float32),
            A((1,), jnp.int32), A((S,), jnp.int32), vec, vec, vec, vec, vec,
            vec, 2, depth, B, "binary", False, False, skip_counts=True),
    }


@pytest.mark.parametrize("program", ["predict_tree", "predict_ensemble",
                                     "chain-launch-goss",
                                     "chain-launch-all-rows"])
def test_no_program_gathers_one_element_a_row(program):
    """At a rehearsal shape (40,000 x 60, depth 6, rows past ``ROW_BLOCK``
    so that growth takes its blocked form).  Growth's ``binned[idx]`` and
    the routing's ``binned_T[fid]`` move WHOLE rows and pass; GOSS's own
    ``g[idx]`` reads a fifth of the rows twice a chain and stays under
    ``rows``."""
    n, d, depth = 40_000, 60, 6
    jaxpr = _programs(n, d, depth)[program]().jaxpr.jaxpr
    gathers = _gathers(jaxpr)
    whole_rows = [g for g in gathers if g[0][-1] in (n, d, 2 * (n // 5))]
    assert whole_rows, gathers
    assert _element_gathers(jaxpr, n) == []


def test_the_walker_of_this_file_finds_the_form_that_went():
    """The same walk over a row-at-a-time router (three one-element gathers
    a level, the form the four sites had) does find them."""
    n, d, depth = 5000, 20, 4

    def a_row_at_a_time(binned, feat, thresh, leaf):
        node = jnp.zeros(n, jnp.int32)
        for level in range(depth):
            heap = 2 ** level - 1 + node
            x = jnp.take_along_axis(binned, feat[heap][:, None], 1)[:, 0]
            node = 2 * node + gk._route_right(x, thresh[heap]).astype(
                jnp.int32)
        return leaf[node]

    A = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(a_row_at_a_time)(
        A((n, d), jnp.int8), A((15,), jnp.int32), A((15,), jnp.int32),
        A((16, 1), jnp.float32)).jaxpr
    assert len(_element_gathers(jaxpr, n)) == 3 * depth + 1
