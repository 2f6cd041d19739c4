"""``RFGridGroup``'s depth and gate sharing against plain semantics.

The group grows ONE base forest a min_instances value at the grid's deepest
depth and lowest min_info_gain and reads every shallower and every
higher-gated candidate off its level values and gate ratios
(``gbdt_kernels.prune_rf_grid``).  ``tests/test_grid_groups.py`` and
``tests/test_rf_gate_sharing.py`` tie that to the program's own direct
growth; here it is tied to ``perfbench/reference/rf_grid.py``, a float64
NumPy forest that grows every candidate directly at ITS OWN depth and gate
from the same binned matrix, bags and feature subsets: split features and
thresholds must be EQUAL, leaves equal to f32 rounding, the CV metric rows
those of ``reference/oracle.py`` on the reference's scores, and the winner's
refit the reference's forest on the full weights.

2,000 x 16, 4 trees, depths {1, 2, 3} x min_info_gain {0.001, 0.01, 0.1} x
min_instances_per_node {10, 100} (the cell's grid with the depths cut), 3
folds, integer row weights and f32 histograms (the CPU's): every histogram
sum is exact in both, so the trees cannot differ by rounding.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.generators.planted_linear import generate  # noqa: E402
from perfbench.reference import oracle, rf_grid  # noqa: E402

ROWS, COLS, TREES, FOLDS, BINS = 2000, 16, 4, 3, 32
GRID = {"max_depth": [1, 2, 3], "min_info_gain": [0.001, 0.01, 0.1],
        "min_instances_per_node": [10, 100]}
POINTS = rf_grid.grid_points(GRID)
BASE_DEPTH = max(GRID["max_depth"])


@pytest.fixture(scope="module")
def grown():
    """The group run once, under a tracer, with every part it scored
    recorded: ``trees[c][f]`` = the (feat, thresh, leaf) of candidate ``c``
    on fold ``f`` as ``run`` handed them to the scorer."""
    from transmogrifai_tpu.models import OpRandomForestClassifier
    from transmogrifai_tpu.models.gbdt_kernels import rf_bags_and_features
    from transmogrifai_tpu.models.trees import (_feature_subset_size,
                                                _prep_tree_inputs_weighted,
                                                clear_sweep_caches)
    from transmogrifai_tpu.obs import trace as obs_trace
    from transmogrifai_tpu.selector import grid, grid_groups
    from transmogrifai_tpu.utils import profiling

    frame, _ = generate(ROWS, COLS, 7)
    A = frame.to_numpy(np.float32)
    X, y = np.ascontiguousarray(A[:, 1:]), A[:, 0]
    rng = np.random.default_rng(3)
    # integer row weights (a balancer's up-weighting, kept exact) and three
    # folds that partition them
    base_w = np.where((y > 0) & (rng.random(ROWS) < 0.3), 2.0, 1.0)
    fold = rng.integers(0, FOLDS, ROWS)
    ctxs = [((base_w * (fold != f)).astype(np.float32),
             (base_w * (fold == f)).astype(np.float32))
            for f in range(FOLDS)]
    points = grid(**GRID)
    assert points == POINTS
    proto = OpRandomForestClassifier(num_trees=TREES)
    group = grid_groups.RFGridGroup(proto, points, "AuPR")

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
        with obs_trace.tracing(capture_hlo=False) as tracer:
            metrics = np.asarray(group.run(X, y, ctxs))
            refits = {row: group.refit_model(row) for row in (1, 17)}
            spans = [s.name for s in tracer.snapshot()]
    finally:
        grid_groups._score_pairs_jit = score
    counters = profiling.COUNTERS.to_json()

    # candidate-pair cp = c * FOLDS + f; the full-depth part comes first,
    # then one part a shallower depth, each fold-major (a fold's pairs are
    # scored together on that fold's validation rows)
    trees = [[None] * FOLDS for _ in POINTS]
    assert [p[0] for p in parts] == [BASE_DEPTH, 1, 2]
    for depth, feats, threshs, leaves in parts:
        cps = [c * FOLDS + f for f in range(FOLDS)
               for c, p in enumerate(POINTS) if p["max_depth"] == depth]
        assert len(cps) == len(feats)
        for i, cp in enumerate(cps):
            trees[cp // FOLDS][cp % FOLDS] = (feats[i], threshs[i],
                                              leaves[i])

    msub = _feature_subset_size("auto", COLS, True)
    bags, subsets = rf_bags_and_features(int(proto.seed), TREES, ROWS, COLS,
                                         msub, 1.0)
    _, binned = _prep_tree_inputs_weighted(X, BINS, row_weight=base_w)
    return {"y": y.astype(np.int64), "ctxs": ctxs, "base_w": base_w,
            "binned": np.asarray(binned), "bags": bags, "subsets": subsets,
            "trees": trees, "metrics": metrics, "refits": refits,
            "counters": counters, "spans": spans, "msub": msub}


def _reference(grown, point, weight):
    return rf_grid.grow_forest(
        grown["binned"], grown["y"], weight, grown["bags"],
        grown["subsets"], point["max_depth"], point["min_info_gain"],
        point["min_instances_per_node"], BINS)


def _assert_same_forest(got, want):
    feat, thresh, leaf = (np.asarray(a) for a in got)
    assert np.array_equal(feat, want[0])
    assert np.array_equal(thresh, want[1])
    np.testing.assert_allclose(leaf, want[2], atol=1e-6)


@pytest.mark.parametrize("c", range(len(POINTS)),
                         ids=lambda c: "d{max_depth}-ig{min_info_gain}-"
                         "n{min_instances_per_node}".format(**POINTS[c]))
def test_candidate_s_trees_are_the_reference_s_at_its_own_depth(grown, c):
    """Truncated and pruned candidates as ``run`` scores them (read off
    their base) and the bases alike; and the CV metric row is the
    oracle's AuPR of the reference's own scores."""
    point = POINTS[c]
    for f, (w_train, w_eval) in enumerate(grown["ctxs"]):
        want = _reference(grown, point, w_train)
        assert want[0].shape == (TREES, 2 ** point["max_depth"] - 1)
        _assert_same_forest(grown["trees"][c][f], want)
        # integer eval weights = repeated rows, exactly
        times = w_eval.astype(np.int64)
        p = rf_grid.predict(grown["binned"], *want).astype(np.float32)
        aupr = oracle.aupr(np.repeat(grown["y"], times), np.repeat(p, times))
        assert grown["metrics"][c, f] == pytest.approx(aupr, abs=1e-5)


def test_the_grid_is_not_degenerate(grown):
    """The gates and depths bite: the candidates' trees differ, some split
    at every level and the 0.1 gate stops some at the root."""
    threshs = {c: np.concatenate([t[1].ravel() for t in grown["trees"][c]])
               for c in range(len(POINTS))}
    split_share = {c: float((v < BINS).mean()) for c, v in threshs.items()}
    assert max(split_share.values()) == 1.0
    assert min(split_share.values()) < 0.5
    by_gate = {}
    for c, p in enumerate(POINTS):
        if p["max_depth"] == BASE_DEPTH:
            by_gate[(p["min_info_gain"], p["min_instances_per_node"])] = (
                threshs[c].tobytes())
    assert len(set(by_gate.values())) >= 4
    assert np.isfinite(grown["metrics"]).all()
    assert grown["metrics"].shape == (len(POINTS), FOLDS)


@pytest.mark.parametrize("row", [17, 1], ids=["full-depth", "truncated"])
def test_refit_model_is_the_reference_s_full_weight_forest(grown, row):
    point = POINTS[row]
    assert (point["max_depth"] == BASE_DEPTH) == (row == 17)
    model = grown["refits"][row]
    want = _reference(grown, point, grown["base_w"])
    _assert_same_forest((model.feat, model.thresh, model.leaf), want)


def test_rf_grid_counters_say_what_was_asked_for_and_what_was_grown(grown):
    got = grown["counters"]["rfGrid"]
    chunk = got.pop("chunk")
    launches = got.pop("launches")
    sweep = 2 * FOLDS * TREES
    assert got == {"candidates": 18, "bases": 2, "pairs": 2 * FOLDS + 2,
                   "truncated": 12, "gateShared": 12,
                   "treesGrown": sweep + 2 * TREES,
                   "msub": grown["msub"], "levels": BASE_DEPTH,
                   # the longest fold's validation rows, rounded up
                   "scoredRows": 1024,
                   # its training rows (1,368) round up to the table's
                   "grownRows": ROWS}
    # the chunker's own count: the sweep's launches and one a refit
    assert 1 <= chunk <= sweep
    assert launches == -(-sweep // chunk) + 2
    assert grown["counters"]["launchTags"]["rf_grid_chunk"] == launches


def test_rf_grid_spans_name_each_phase(grown):
    rf = [s for s in grown["spans"] if s.startswith("rf.grid.")]
    assert rf == ["rf.grid.grow", f"rf.grid.score:d{BASE_DEPTH}",
                  "rf.grid.score:d1", "rf.grid.score:d2", "rf.grid.metrics",
                  "rf.grid.refit", "rf.grid.refit"]


def test_rf_grid_counts_add_up_shapes_keep_the_largest_and_both_reset():
    from transmogrifai_tpu.utils import profiling

    profiling.reset_counters()
    profiling.count_rf_grid(treesGrown=3, chunk=5, scoredRows=2048)
    profiling.count_rf_grid(treesGrown=4, chunk=2, scoredRows=1024)
    assert profiling.COUNTERS.to_json()["rfGrid"] == {
        "treesGrown": 7, "chunk": 5, "scoredRows": 2048}
    assert profiling.reset_counters().to_json()["rfGrid"] == {}


# -- the reference by itself --------------------------------------------------

def test_reference_tree_by_hand():
    """Six rows, one column, three bins: the split is where the classes
    part, the gates stop it, and an unsplit node sends every row left."""
    binned = np.array([[0], [0], [1], [1], [2], [2]])
    y = np.array([0, 0, 0, 1, 1, 1])
    w = np.ones(6)
    feat, thresh, leaf = rf_grid.grow_tree(binned, y, w, [0], 1, 0.0, 1.0, 3)
    # bin <= 0 | bin > 0: gain 4/3 ... bin <= 1 | bin > 1: the same; ties
    # go to the lowest threshold
    assert (feat.tolist(), thresh.tolist()) == ([0], [0])
    np.testing.assert_allclose(leaf, [[1.0, 0.0], [0.25, 0.75]])
    # three rows a child are asked for: only no threshold gives that
    _, thresh, leaf = rf_grid.grow_tree(binned, y, w, [0], 1, 0.0, 3.0, 3)
    assert thresh.tolist() == [3]
    np.testing.assert_allclose(leaf, [[0.5, 0.5], [0.0, 0.0]])
    # a gain gate above the split's gain a row
    _, thresh, _ = rf_grid.grow_tree(binned, y, w, [0], 1, 0.5, 1.0, 3)
    assert thresh.tolist() == [3]
    # zero-weight rows count nowhere; depth 2 keeps growing the right child
    w2 = np.array([1, 1, 1, 1, 1, 0.0])
    feat, thresh, leaf = rf_grid.grow_tree(binned, y, w2, [0], 2, 0.0, 1.0,
                                           3)
    assert thresh.tolist() == [0, 3, 1]
    np.testing.assert_allclose(leaf, [[1, 0], [0, 0], [0.5, 0.5], [0, 1]])


def test_reference_predict_is_the_walker_s(grown):
    from perfbench.reference import tree_walker

    point = POINTS[17]
    forest = _reference(grown, point, grown["base_w"])
    # the walker bins raw values by edges; give it the bins as values and
    # half-integer edges
    edges = np.tile(np.arange(BINS - 1) + 0.5, (COLS, 1))
    rows = grown["binned"][:300]
    want = tree_walker.probability_1(rows.astype(np.float32), edges,
                                     *forest, "rf_cls")
    np.testing.assert_allclose(rf_grid.predict(rows, *forest), want,
                               atol=1e-12)


def test_reference_folds_are_stratified_and_partition_the_rows():
    y = (np.arange(100) % 4 == 0).astype(np.int64)
    folds = rf_grid.stratified_folds(y, 3, np.random.default_rng(0))
    assert len(folds) == 3
    np.testing.assert_array_equal(sum(e for _, e in folds), np.ones(100))
    for train, ev in folds:
        np.testing.assert_array_equal(train + ev, np.ones(100))
        assert 8 <= y[ev > 0].sum() <= 9
