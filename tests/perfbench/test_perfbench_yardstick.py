"""The yardstick's own parts: the trace reduction on a trace recorded on
the chip, and the plain references against the program at tiny shapes.

The recorded trace is ``perfbench/testdata/tiny_xgb_v5e.xplane.pb.xz``: one
warm train of ``dense500-xgb`` at 4,000 x 32 under ``--trace 1`` on one v5e (my chip run, PR 22).  The numbers pinned below are that run's.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import breakdown, trace_reduce  # noqa: E402
from perfbench.generators.planted_linear import generate  # noqa: E402
from perfbench.reference import hist_gbt, oracle, tree_walker  # noqa: E402

TRACE = os.path.join(ROOT, "perfbench", "testdata",
                     "tiny_xgb_v5e.xplane.pb.xz")


# -- interval arithmetic ----------------------------------------------------

def test_merge_clip_total_gaps():
    busy = trace_reduce.merge([(5, 7), (0, 2), (1, 3), (6, 9), (20, 21)])
    assert busy == [(0, 3), (5, 9), (20, 21)]
    assert trace_reduce.total(busy) == 8
    assert trace_reduce.clip(busy, 2, 8) == [(2, 3), (5, 8)]
    assert trace_reduce.gaps(busy, 0, 30) == [(9, 20), (21, 30), (3, 5)]


def test_self_time_takes_children_out_of_a_while_op():
    # a while op spanning two body ops and 2 ns of its own
    events = [(0, 10, "while"), (1, 4, "dot"), (5, 10, "fusion"),
              (12, 13, "dot")]
    assert trace_reduce.self_times(events) == {
        "while": 2.0, "dot": 4.0, "fusion": 5.0}


def test_op_and_module_names():
    hlo = ("%fusion.17 = s32[32000]{0:T(1024)S(1)} fusion(s32[128000]{0} "
           "%all-reduce.3, s32[32768]{0} %pad.6), kind=kCustom, calls=%f")
    assert trace_reduce.op_name(hlo) == "%fusion.17 fusion"
    # an operand named like a collective does not make the op one
    assert not trace_reduce.COLLECTIVE.search(trace_reduce.op_name(hlo))
    coll = "%all-reduce.9 = f32[6,32,512]{2,1,0} all-reduce(f32[6,32,512] %x)"
    assert trace_reduce.COLLECTIVE.search(trace_reduce.op_name(coll))
    assert trace_reduce.module_name("jit_shard_fn(123456)") == "jit_shard_fn"


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(TRACE)


def test_recorded_trace_window_is_the_annotation(reduced):
    assert reduced["platform"] == "tpu" and reduced["annotation_found"]
    assert reduced["window_ns"] == [47521125.0, 272952034.0]
    # the host clock round the same train read 0.224897 s (the run's log):
    # the annotation is the train on the trace's clock, to half a millisecond
    assert reduced["window_s"] == pytest.approx(0.225430909, abs=1e-9)
    assert abs(reduced["window_s"] - 0.224897) < 1e-3


def test_recorded_trace_busy_union_and_idle_share(reduced):
    (name,) = reduced["devices"]
    assert name == "/device:TPU:0"
    dev = reduced["devices"][name]
    assert dev["events"] == {"modules": 189, "ops": 13088}
    assert reduced["busy_s"] == pytest.approx(0.162801105, abs=1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.2778, abs=1e-4)
    # busy is a union: disjoint, sorted, inside the window
    lo, hi = reduced["window_ns"]
    prev = lo
    for s, e in dev["busy_intervals"]:
        assert prev <= s < e <= hi
        prev = e
    # op self times add up to the modules' time (nothing counted twice)
    assert sum(dev["op_self_s"].values()) == pytest.approx(
        sum(dev["module_s"].values()), rel=1e-3)
    assert dev["collective_s"] == 0.0  # one chip


def test_recorded_trace_time_by_module(reduced):
    top = dict(reduced["top_modules"])
    assert top["jit__gbt_chain_rounds_jit"] == pytest.approx(0.108598973)
    assert top["jit_predict_ensemble"] == pytest.approx(0.052790112)
    from perfbench.metrics import tree_device_s

    sources = {"trace": reduced}
    assert tree_device_s.read(sources) == pytest.approx(0.161393847)
    # and a reduction marked as a CPU rehearsal is never a device metric
    assert tree_device_s.read({"trace": dict(reduced, platform="cpu")}) is None


def test_breakdown_names_gaps_by_the_innermost_open_span(reduced):
    lo, hi = reduced["window_ns"]
    # spans on a perf_counter clock whose instant 100.0 is the annotation's
    # start: an outer span over the whole train, an inner one over its
    # first 20 ms (where the trace's two longest gaps are)
    with_spans = dict(reduced, annotation_perf_s=100.0, spans=[
        {"name": "workflow.train", "t0": 100.0, "dur_s": (hi - lo) / 1e9},
        {"name": "sweep.unit[3]", "t0": 100.0, "dur_s": 0.020}])
    named = breakdown.name_gaps(with_spans)
    assert set(named) <= {"workflow.train", "sweep.unit",
                          "gaps_under_100us"}
    first_two = sum(e - s for s, e in reduced["idle_gaps_ns"][:2]) / 1e9
    assert named["sweep.unit"] == pytest.approx(first_two, rel=1e-6)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(named.values()) == pytest.approx(idle, rel=1e-6)
    built = breakdown.build(with_spans)
    assert len(built["device_ops"]) == 10
    assert built["device_ops"][0][0].startswith("jit_predict_ensemble/")


# -- readers and the compile meter --------------------------------------------

def _xgb_sources(reduced, device_kind):
    from perfbench import spec

    loaded = spec.load_cell("dense500-xgb")
    return {"trace": reduced, "device_kind": device_kind,
            "winner": ["OpXGBoostClassifier", {"min_child_weight": 1.0}],
            "cell": {"rows": 4000, "cols": 32, "chips": 1,
                     "config": loaded["config"],
                     "traffic": loaded["traffic"]}}, loaded["per_layer"]


def test_a_reader_that_raises_is_a_problem_and_not_a_silent_gap(reduced):
    import importlib.util

    file = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    perfbench_run = importlib.util.module_from_spec(file)
    file.loader.exec_module(perfbench_run)

    sources, per_layer = _xgb_sources(reduced, "TPU v5 lite")
    problems = []
    got = perfbench_run.read_metrics(per_layer, sources, problems)
    assert not problems
    # 32 trees x 10 levels x 1,600 rows x 40 bytes over 0.1614 s of 819 GB/s
    assert got["tree_hist_roofline"]["value"] == pytest.approx(
        100 * 32 * 10 * 1600 * 40 / 0.161393847 / 819e9)
    assert "collective_s" not in got  # nothing to read: left out, no problem
    # a device the peaks table lacks is an error, never a default
    sources, _ = _xgb_sources(reduced, "TPU v9")
    got = perfbench_run.read_metrics(per_layer, sources, problems)
    assert "tree_hist_roofline" not in got and "tree_device_s" in got
    (problem,) = problems
    assert "tree_hist_roofline" in problem and "TPU v9" in problem


@pytest.mark.parametrize("recorded,width", [({}, 32),
                                            ({"selector_cols": 95}, 95)])
def test_the_roofline_reads_the_width_the_selector_was_given(
        reduced, recorded, width):
    """A typed table's vector (pivots and null flags in, SanityChecker's
    drops out) is not its raw column count; a mode that records no width
    leaves the raw count."""
    from perfbench.metrics import tree_hist_roofline

    sources, _ = _xgb_sources(reduced, "TPU v5 lite")
    sources.update(recorded)
    assert tree_hist_roofline.read(sources) == pytest.approx(
        100 * 32 * 10 * 1600 * (width + 8) / 0.161393847 / 819e9)


METER_CHILD = """
import sys
import jax, jax.numpy as jnp
sys.path.insert(0, sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from perfbench.compile_meter import CompileMeter
meter, x = CompileMeter(), jnp.ones((8, 8))
for leg in ("compiled", "loaded"):
    mark = meter.mark()
    jax.jit(lambda a: (a @ a.T).sum() * 3)(x).block_until_ready()
    got = meter.since(mark)
    print(leg, got["programs"], got["cache_hits"], got["cache_misses"])
    jax.clear_caches()
"""


def test_compile_meter_counts_a_cache_load_as_one_program_not_two(tmp_path):
    """JAX's backend-compile event wraps the cache lookup, so a program
    loaded from the persistent cache raises it too: ``programs`` is every
    program built, ``cache_hits`` the loaded ones among them."""
    import subprocess

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _child import child_env

    out = subprocess.run(
        [sys.executable, "-c", METER_CHILD, ROOT], capture_output=True,
        text=True, timeout=300, env=child_env(tmp_path / "jax_cache"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split("\n")[:2] == ["compiled 1 0 1", "loaded 1 1 0"]


# -- the plain references against the program -------------------------------

def _xy(rows, cols, seed, hold=0):
    df, beta = generate(rows + hold, cols, seed)
    A = df.to_numpy(np.float32)
    return A[:rows, 1:], A[:rows, 0], A[rows:, 1:], A[rows:, 0], beta


def test_hist_gbt_grows_the_program_s_trees_at_a_tiny_shape():
    """2,000 x 16, depth 3, no GOSS (depth < 8), f32 histograms: exact
    agreement is expected, so split features and thresholds must be EQUAL
    and leaves agree to f32 rounding (1e-5: leaves are ~0.1, f32 sums over
    2,000 rows)."""
    from transmogrifai_tpu.models import OpGBTClassifier

    X, y, *_ = _xy(2000, 16, 5)
    model = OpGBTClassifier(max_iter=3, max_depth=3,
                            hist_precision="f32").fit_raw(X, y)
    edges, base, trees = hist_gbt.fit_gbt(X, y, depth=3, rounds=3, eta=0.1,
                                          lam=1.0, min_child_weight=1.0)
    assert np.array_equal(np.asarray(model.edges), edges)
    assert base == pytest.approx(float(model.base_score), abs=1e-6)
    for t, (feat, thresh, leaf) in enumerate(trees):
        assert np.array_equal(np.asarray(model.feat)[t], feat), t
        assert np.array_equal(np.asarray(model.thresh)[t], thresh), t
        np.testing.assert_allclose(np.asarray(model.leaf)[t, :, 0], leaf,
                                   atol=1e-5)
    want = model.predict_batch(X).probability[:, 1]
    np.testing.assert_allclose(
        hist_gbt.predict_gbt(X, edges, base, trees, 3), want, atol=1e-5)


@pytest.mark.parametrize("kind", ["gbt", "rf"])
def test_tree_walker_agrees_with_the_program_s_scorer(kind):
    """1e-5 as in the cells: the program sums f32 leaves on the device, the
    walker float64 on the host; a wrong route changes a probability by
    1e-2 and more."""
    from transmogrifai_tpu.models import (OpGBTClassifier,
                                          OpRandomForestClassifier)

    X, y, *_ = _xy(2000, 16, 6)
    est = (OpGBTClassifier(max_iter=4, max_depth=4) if kind == "gbt"
           else OpRandomForestClassifier(num_trees=4, max_depth=5))
    model = est.fit_raw(X, y)
    want = model.predict_batch(X[:400]).probability[:, 1]
    got = tree_walker.probability_1(X[:400], model.edges, model.feat,
                                    model.thresh, model.leaf, model.mode,
                                    float(model.base_score))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_hist_rf_reference_learns_the_planted_signal():
    X, y, Xh, yh, beta = _xy(4000, 16, 8, hold=2000)
    edges, trees = hist_gbt.fit_rf(X, y, depth=6, n_trees=8, seed=1)
    got = oracle.aupr(yh, hist_gbt.predict_rf(Xh, edges, trees, 6))
    best = oracle.oracle_aupr(Xh, yh, beta)
    assert yh.mean() + 0.1 < got <= best + 0.01


def test_oracle_bounds_a_logistic_fit_and_the_fit_nearly_reaches_it():
    """5,000 x 16: a logistic regression on a planted-linear model comes
    within 0.01 of the planted weights' own AuPR (sampling noise of 5,000
    training rows; measured gap 0.0005) and cannot beat it by more than
    the hold-out's noise.  The two AuPR implementations (the program's
    evaluator and the oracle's float64 step sum) agree to 1e-3 on the same
    scores."""
    from transmogrifai_tpu.models import OpLogisticRegression

    X, y, Xh, yh, beta = _xy(5000, 16, 7, hold=5000)
    model = OpLogisticRegression(reg_param=0.01).fit_raw(X, y)
    p = model.predict_batch(Xh).probability[:, 1]
    got, best = oracle.aupr(yh, p), oracle.oracle_aupr(Xh, yh, beta)
    assert best - 0.01 <= got <= best + 0.005
    assert oracle.aupr(np.array([1, 0, 1, 0.]),
                       np.array([.9, .8, .7, .1])) == pytest.approx(
        0.5 * 1.0 + 0.5 * (2 / 3))
