"""Spans inside the vectorizer, the boosted grid group and a sequential tree
fit, and the counters of the first launch, the fresh host bytes and the
hashed bytes (ISSUE 36).

A tiny CPU selector train (an XGB and an RF point, 2 folds, behind a
SanityChecker) through ``OpWorkflow.train()``, as
``tests/test_obs_sweep_spans.py`` builds it; times printed by it mean
nothing, only the names, the tree and the counts are pinned.
"""
import sys
import threading
import time

import numpy as np
import pytest

from transmogrifai_tpu import (FeatureBuilder, OpWorkflow, models, obs,
                               transmogrify)
from transmogrifai_tpu.obs import trace as obs_trace
from transmogrifai_tpu.ops import vectorizers
from transmogrifai_tpu.preparators import SanityChecker
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        grid)
from transmogrifai_tpu.testkit import planted_linear_frame
from transmogrifai_tpu.utils import profiling

ROWS, COLS = 20_000, 16
#: the vector holds a value and a null-indicator column a predictor
WIDTH = 2 * COLS
#: columns of the vector a group buffer of ``GROUP_BYTES`` holds at ROWS
GROUP = 5
GROUP_BYTES = GROUP * ROWS * 4
GROUPS = -(-WIDTH // GROUP)

NEW_SPANS = ["vectorize.fill", "vectorize.flush", "gbt.grid.prepare",
             "gbt.grid.rounds", "gbt.grid.score", "gbt.grid.metrics",
             "tree.fit.prepare", "tree.fit.grow", "tree.fit.fetch"]
GBT_PHASES = ["gbt.grid.prepare", "gbt.grid.rounds", "gbt.grid.score",
              "gbt.grid.metrics"]
FIT_PHASES = ["tree.fit.prepare", "tree.fit.grow", "tree.fit.fetch"]


def _family(name: str) -> str:
    return name.split("[")[0]


def _workflow():
    # 20,000 x 16 f32 is 1.28 MB: over the 1 MB under which ``_content_hash``
    # re-hashes on every probe, so the per-object full hash is on the path
    df = planted_linear_frame(ROWS, COLS, 3)
    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns
             if c != "label"]
    vector = transmogrify(preds)
    checked = SanityChecker(max_correlation=0.99).set_input(
        label, vector).get_output()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=1, models_and_parameters=[
            # (the forest is a stump so that the boosted point wins: the
            # winner's refit must be a sequential ``fit_raw``)
            (models.OpXGBoostClassifier(num_round=2, eta=0.5),
             grid(max_depth=[3])),
            (models.OpRandomForestClassifier(num_trees=1),
             grid(max_depth=[1]))])
    prediction = selector.set_input(label, checked).get_output()
    return (OpWorkflow().set_result_features(prediction).set_input_data(df),
            df, vector, checked)


@pytest.fixture(scope="module")
def trained():
    """The warm workflow and one traced train of it with every array booked
    as fresh and the vectorizer's buffer cut to GROUP columns."""
    wf, df, vector, checked = _workflow()
    wf.train()           # builds every program the later trains use
    mp = pytest.MonkeyPatch()
    mp.setattr(profiling, "FRESH_MIN_BYTES", 0)
    mp.setattr(vectorizers, "_GROUP_BUF_BYTES", GROUP_BYTES)
    try:
        profiling.reset_counters()
        t0 = time.perf_counter()
        with obs.tracing(capture_hlo=False) as tracer:
            model = wf.train()
        wall = time.perf_counter() - t0
        counters = profiling.COUNTERS.to_json()
    finally:
        mp.undo()
    return {"wf": wf, "spans": tracer.snapshot(), "counters": counters,
            "wall": wall, "df": df, "vector": vector,
            "checked": model.train_data[checked.name].values}


def _children(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.t0)


def _only(spans, name):
    (found,) = [s for s in spans if s.name == name]
    return found


# -- the spans -----------------------------------------------------------------

def test_every_new_span_is_reached(trained):
    assert set(NEW_SPANS) <= {_family(s.name) for s in trained["spans"]}


def test_fill_and_flush_alternate_under_the_vectorizer_one_pair_a_group(
        trained):
    stage = _only(trained["spans"], "stage:RealVectorizer")
    kids = _children(trained["spans"], stage)
    want = [f"vectorize.{part}[{g}]" for g in range(GROUPS)
            for part in ("fill", "flush")]
    assert [s.name for s in kids] == want
    assert {s.thread for s in kids} == {stage.thread}
    # they tile the transform: each starts where the one before it ended
    for a, b in zip(kids, kids[1:]):
        assert b.t0 >= a.t0 + a.dur_s
    flushed = [s.attrs["cols"] for s in kids if "flush" in s.name]
    assert flushed == [GROUP] * (GROUPS - 1) + [WIDTH - GROUP * (GROUPS - 1)]


def test_the_vector_is_bit_equal_with_and_without_a_tracer(
        trained, monkeypatch):
    monkeypatch.setattr(vectorizers, "_GROUP_BUF_BYTES", GROUP_BYTES)
    vector = trained["vector"]
    wf = OpWorkflow().set_result_features(vector).set_input_data(
        trained["df"])
    plain = wf.train().train_data[vector.name].values
    with obs.tracing(capture_hlo=False) as tracer:
        traced = wf.train().train_data[vector.name].values
    assert sum(s.name.startswith("vectorize.flush")
               for s in tracer.snapshot()) == GROUPS
    assert traced.shape == (ROWS, WIDTH) and traced.dtype == np.float32
    assert traced.tobytes() == plain.tobytes()
    # and the buffer's cut changes nothing either
    monkeypatch.undo()
    assert wf.train().train_data[vector.name].values.tobytes() == (
        plain.tobytes())


def test_gbt_grid_phases_tile_their_group_in_order(trained):
    spans = trained["spans"]
    group = _only(spans, "sweep.group:OpXGBoostClassifier")
    kids = _children(spans, group)
    assert [s.name for s in kids] == GBT_PHASES
    assert kids[0].t0 - group.t0 < 0.01
    for a, b in zip(kids, kids[1:]):
        assert 0 <= b.t0 - (a.t0 + a.dur_s) < 0.01
    by_id = {s.span_id: s for s in spans}
    under = {name: {_family(s.name) for s in spans
                    if s.parent_id in by_id
                    and by_id[s.parent_id].name == name}
             for name in GBT_PHASES}
    assert {"tree.prep.hash", "tree.prep.sketch",
            "tree.prep.bin"} <= under["gbt.grid.prepare"]
    assert under["gbt.grid.rounds"] == {"launch:gbt_chain_rounds"}
    assert under["gbt.grid.score"] == {"launch:gbt_chain_score"}
    assert not any(n.startswith("launch:")
                   for n in under["gbt.grid.prepare"] - {"launch:device_bin"})


def test_tree_fit_phases_hang_under_the_refit_in_order(trained):
    spans = trained["spans"]
    refit = _only(spans, "selector.refit")
    kids = _children(spans, refit)
    assert [s.name for s in kids] == FIT_PHASES
    grow = kids[1]
    assert [s.name for s in _children(spans, grow)] == ["launch:gbt_rounds"]
    assert not [s for s in _children(spans, kids[0])
                if s.name.startswith("launch:gbt")]


def _fit_rf(X, y):
    return models.OpRandomForestClassifier(num_trees=2, max_depth=3)


def _fit_dt(X, y):
    return models.OpDecisionTreeClassifier(max_depth=3)


def _fit_gbt_rounds(X, y):
    # a row subsample draws on the host every round: the sequential loop,
    # not the scan-chunked launches
    return models.OpGBTClassifier(max_iter=2, max_depth=3,
                                  subsample_rate=0.8)


def _fit_xgb_chunks(X, y):
    return models.OpXGBoostClassifier(num_round=2, max_depth=3)


@pytest.mark.parametrize("make", [_fit_rf, _fit_dt, _fit_gbt_rounds,
                                  _fit_xgb_chunks])
def test_every_tree_estimator_s_fit_raw_has_the_three_phases(make):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    est = make(X, y)
    est.fit_raw(X, y)
    with obs.tracing(capture_hlo=False) as tracer:
        model = est.fit_raw(X, y)
    spans = sorted(tracer.snapshot(), key=lambda s: s.t0)
    phases = [s for s in spans if s.name.startswith("tree.fit.")]
    assert [s.name for s in phases] == FIT_PHASES
    assert {s.parent_id for s in phases} == {None}
    # every growth launch lies in ``grow``
    grow = phases[1]
    for s in spans:
        if s.name.startswith("launch:") and s.name != "launch:device_bin":
            assert s.parent_id == grow.span_id, s.name
    assert model.predict_batch(X).prediction.shape == (400,)


def test_phases_close_the_open_span_on_a_return_and_on_an_exception():
    def early(ph):
        ph.to("b")
        return 1

    with obs.tracing(capture_hlo=False) as tracer:
        with obs_trace.phases("a", cat="t") as ph:
            early(ph)
        with pytest.raises(ValueError):
            with obs_trace.phases("c", cat="t") as ph:
                ph.to("d", n=2)
                raise ValueError("x")
        assert obs_trace.current_span() is None
    got = [(s.name, s.cat, s.dur_s is not None) for s in tracer.snapshot()]
    assert got == [("a", "t", True), ("b", "t", True), ("c", "t", True),
                   ("d", "t", True)]
    assert tracer.snapshot()[-1].attrs["n"] == 2


def test_no_span_is_made_with_no_tracer_armed(trained, monkeypatch):
    made = []

    class Counted(obs_trace.Span):
        def __init__(self, *args, **kwargs):
            made.append(args[0])
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(obs_trace, "Span", Counted)
    assert obs_trace.current_tracer() is None
    trained["wf"].train()
    with obs_trace.phases("x") as ph:
        ph.to("y")
        assert ph._open is None
    assert made == []
    with obs.tracing(capture_hlo=False):
        with obs_trace.phases("x"):
            pass
    assert made == ["x"]       # the probe does count when one is armed


# -- the counters ----------------------------------------------------------------

def test_first_launch_secs_has_the_launch_tags_keys_in_dispatch_order(
        trained):
    c = trained["counters"]
    first = c["firstLaunchSecs"]
    assert list(first) == list(c["launchTags"])
    assert {"device_bin", "gbt_chain_rounds", "gbt_chain_score",
            "rf_grid_chunk", "gbt_rounds"} <= set(first)
    values = list(first.values())
    assert values == sorted(values)
    assert 0 < values[0] and values[-1] < trained["wall"]
    # a tag's first launch is where its first ``launch:`` span opens
    train = _only(trained["spans"], "workflow.train")
    for tag, secs in first.items():
        opened = min(s.t0 for s in trained["spans"]
                     if s.name == f"launch:{tag}")
        assert abs((opened - train.t0) - secs) < 0.01, tag


def test_the_origin_is_the_train_s_entry_not_the_reset(trained):
    profiling.reset_counters()
    time.sleep(0.3)
    t0 = time.perf_counter()
    trained["wf"].train()
    wall = time.perf_counter() - t0
    first = profiling.COUNTERS.to_json()["firstLaunchSecs"]
    assert first and max(first.values()) < wall
    # without a train the origin is the moment the counters were made
    made = profiling.reset_counters()
    time.sleep(0.05)
    profiling.count_launch("probe")
    assert 0.05 <= made.first_launch_s["probe"] < 5.0
    profiling.count_launch("probe")
    assert len(made.first_launch_s) == 1 and made.launch_tags["probe"] == 2


def test_host_fresh_books_the_vector_and_the_filtered_matrix_exactly(
        trained):
    fresh = trained["counters"]["hostFresh"]
    assert fresh["vectorize.out"] == ROWS * WIDTH * 4
    assert fresh["vectorize.buf"] == GROUP * ROWS * 4
    kept = trained["checked"].shape[1]
    assert 0 < kept < WIDTH            # the constant null indicators go
    assert fresh["sanity.filter"] == ROWS * kept * 4
    assert {"tree.hash.copy", "tree.sketch.buf", "tree.bundle.host",
            "selector.predict"} <= set(fresh)
    # a contiguous float32 matrix is handed on as it is
    assert not {"tree.contiguous", "tree.f32", "selector.matrix"} & set(fresh)


def test_a_fetch_is_booked_where_it_makes_a_new_host_array(monkeypatch):
    from transmogrifai_tpu.models.trees import _host_copy

    class Device:
        """What ``_host_copy`` reads of a ``jax.Array``: the host copy its
        first fetch keeps."""
        _npy_value = None

        def __array__(self, dtype=None, copy=None):
            if self._npy_value is None:
                self._npy_value = np.zeros(8, np.float32)
            return self._npy_value

    monkeypatch.setattr(profiling, "FRESH_MIN_BYTES", 0)
    counters = profiling.reset_counters()
    x = Device()
    first = _host_copy(x, "a")
    assert _host_copy(x, "b") is first          # the kept copy: not booked
    assert _host_copy(first, "c") is first      # a host array: no fetch
    assert counters.host_fresh == {"a": 32}
    profiling.reset_counters()


def test_below_the_threshold_nothing_is_booked(trained):
    assert profiling.FRESH_MIN_BYTES == 32 << 20
    profiling.reset_counters()
    trained["wf"].train()
    assert profiling.COUNTERS.to_json()["hostFresh"] == {}
    profiling.count_fresh("site", (32 << 20) - 1)
    profiling.count_fresh("site", 32 << 20)
    profiling.count_fresh("site", 33 << 20)
    assert profiling.COUNTERS.to_json()["hostFresh"] == {"site": 65 << 20}


def test_hash_bytes_are_the_hash_spans_bytes_and_hold_the_matrix(trained):
    c = trained["counters"]
    hashed = [s for s in trained["spans"] if s.name == "tree.prep.hash"]
    assert c["hashes"] == len(hashed) >= 1
    assert c["hashBytes"] == sum(s.attrs["bytes"] for s in hashed)
    assert c["hashBytes"] >= trained["checked"].nbytes


def test_reset_zeroes_the_new_counters(trained):
    profiling.count_hash(10)
    profiling.count_launch("t")
    fresh = profiling.reset_counters().to_json()
    assert (fresh["firstLaunchSecs"], fresh["hostFresh"],
            fresh["hashBytes"], fresh["hashes"]) == ({}, {}, 0, 0)


def test_new_counters_are_exact_under_a_thread_hammer(monkeypatch):
    monkeypatch.setattr(profiling, "FRESH_MIN_BYTES", 0)
    counters = profiling.reset_counters()
    threads, each = 16, 2_000
    start = threading.Barrier(threads)

    def hammer(k):
        start.wait(timeout=30)
        for i in range(each):
            profiling.count_fresh(f"site{i % 3}", 7)
            profiling.count_hash(5)
            profiling.count_launch(f"tag{i % 5}")

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=hammer, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(before)
    total = threads * each
    assert sum(counters.host_fresh.values()) == 7 * total
    assert sorted(counters.host_fresh) == ["site0", "site1", "site2"]
    assert (counters.hash_bytes, counters.hashes) == (5 * total, total)
    assert counters.launches == total
    # one write a TAG, not a launch: five tags, each stamped once
    assert sorted(counters.first_launch_s) == [f"tag{i}" for i in range(5)]
    assert all(v >= 0 for v in counters.first_launch_s.values())
    profiling.reset_counters()


def test_a_train_makes_at_most_forty_counter_calls_beyond_the_parent_s(
        trained, monkeypatch):
    """The calls this PR added to a train, counted where they take the
    counters' lock, with every array booked whatever its size (so no fewer
    than a train at the cells' size makes)."""
    calls = []

    class CountingLock:
        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            calls.append(sys._getframe(1).f_code.co_name)
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    monkeypatch.setattr(profiling, "FRESH_MIN_BYTES", 0)
    monkeypatch.setattr(profiling, "_COUNTERS_LOCK",
                        CountingLock(profiling._COUNTERS_LOCK))
    profiling.reset_counters()
    trained["wf"].train()
    new = [c for c in calls
           if c in ("count_fresh", "count_hash", "mark_run_start")]
    assert "count_launch" in calls and calls.count("mark_run_start") == 1
    assert 5 <= len(new) <= 40, new
