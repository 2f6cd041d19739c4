"""Spans inside the sweep, tree preparation and the winner's refit, the
``jit.*`` spans of programs built under a tracer, the ``memoTags``
counters, and the named scopes inside the tree programs (ISSUE 23).

A tiny CPU selector train (an XGB and an RF point, 2 folds) through
``OpWorkflow.train()``; times printed by it mean nothing, only the names,
the tree and the counts are pinned.
"""
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import (FeatureBuilder, OpWorkflow, models, obs,
                               transmogrify)
from transmogrifai_tpu.models import gbdt_kernels as gk
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.obs import trace as obs_trace
from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                        grid)
from transmogrifai_tpu.selector.model_selector import ModelSelector
from transmogrifai_tpu.testkit import planted_linear_frame
from transmogrifai_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every span name of ISSUE 23's tables that this train's path reaches on
#: one CPU device (no mesh, no CSR, a contiguous matrix, no prefetch thread
#: at this size)
REACHED = [
    "selector.prepare", "selector.validate", "selector.refit",
    "selector.predict", "selector.metrics",
    "sweep.group:OpXGBoostClassifier",
    "sweep.group:OpRandomForestClassifier",
    "tree.prep.hash", "tree.prep.sketch", "tree.prep.bin",
    "tree.prep.upload", "tree.prep.bundle", "launch:device_bin",
    "launch:gbt_chain_rounds", "launch:gbt_chain_score",
    "launch:rf_grid_chunk", "launch:gbt_rounds"]


def _workflow():
    # 20,000 x 16 f32 is 1.28 MB: over the 1 MB under which ``_content_hash``
    # re-hashes on every probe, so the per-object full hash (and its span)
    # is on the path
    df = planted_linear_frame(20_000, 16, 3)
    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns
             if c != "label"]
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=1, models_and_parameters=[
            (models.OpXGBoostClassifier(num_round=2), grid(max_depth=[3])),
            (models.OpRandomForestClassifier(num_trees=2),
             grid(max_depth=[3]))])
    prediction = selector.set_input(label, transmogrify(preds)).get_output()
    return OpWorkflow().set_result_features(prediction).set_input_data(df)


def _traced_train(wf):
    profiling.reset_counters()
    with obs.tracing(capture_hlo=False) as tracer:
        wf.train()
    return tracer, profiling.COUNTERS.to_json()


@pytest.fixture(scope="module")
def wf():
    wf = _workflow()
    wf.train()           # builds every program the later trains use
    return wf


@pytest.fixture(scope="module")
def traced(wf):
    """Two traced trains of the warm workflow, one after the other."""
    first, counters = _traced_train(wf)
    second, counters_again = _traced_train(wf)
    return {"spans": first.snapshot(), "again": second.snapshot(),
            "counters": counters, "counters_again": counters_again,
            "dropped": first.dropped}


def _time_span_listeners() -> int:
    return len(jax._src.monitoring.get_event_time_span_listeners())


def _ancestors(span, by_id):
    while span.parent_id in by_id:
        span = by_id[span.parent_id]
        yield span


# -- the span tree ---------------------------------------------------------------

def test_traced_train_records_every_span_its_path_reaches(traced):
    names = {s.name for s in traced["spans"]}
    assert set(REACHED) <= names
    assert traced["dropped"] == 0 and len(traced["spans"]) < 500
    # the family span stands inside the indexed group span, which stays
    by_id = {s.span_id: s for s in traced["spans"]}
    for s in traced["spans"]:
        if s.name.startswith("sweep.group:"):
            assert by_id[s.parent_id].name.startswith("sweep.group[")
    assert [s.name for s in traced["spans"]
            if s.name.startswith("sweep.group[")] == [
        "sweep.group[0:1]", "sweep.group[1:2]"]


def test_new_spans_hang_under_the_selector_s_stage(traced):
    by_id = {s.span_id: s for s in traced["spans"]}
    mine = [s for s in traced["spans"] if s.name.startswith(
        ("tree.prep.", "selector.", "launch:"))]
    assert len(mine) >= len(REACHED) - 2
    for s in mine:
        assert "stage:ModelSelector" in [a.name for a in
                                         _ancestors(s, by_id)], s.name
    # prep and launches of the sweep stand in their family's group span
    for s in mine:
        up = [a.name for a in _ancestors(s, by_id)]
        if "selector.validate" in up and not s.name.startswith("selector."):
            assert any(a.startswith("sweep.group:") for a in up), s.name


def test_children_of_the_selector_s_stage_cover_it(traced):
    (stage,) = [s for s in traced["spans"]
                if s.name == "stage:ModelSelector"]
    kids = sorted((s.t0, s.t0 + s.dur_s) for s in traced["spans"]
                  if s.parent_id == stage.span_id)
    assert {s.name for s in traced["spans"]
            if s.parent_id == stage.span_id} == {
        "selector.prepare", "selector.validate", "selector.refit",
        "selector.predict", "selector.metrics"}
    covered, at = 0.0, stage.t0
    for lo, hi in kids:
        covered += max(0.0, hi - max(lo, at))
        at = max(at, hi)
    assert covered >= 0.95 * stage.dur_s


def test_two_trains_give_the_same_span_names_in_the_same_order(traced):
    def names(spans):
        # (programs a train builds depend on JAX's caches, not on the train)
        return [s.name for s in sorted(spans, key=lambda s: s.span_id)
                if s.cat != "compile"]

    assert names(traced["spans"]) == names(traced["again"])


def test_prefetch_thread_s_spans_hang_under_its_own_span(wf, monkeypatch):
    monkeypatch.setattr(ModelSelector, "_PREFETCH_MIN_ELEMS", 1)
    tracer, _ = _traced_train(wf)
    spans = tracer.snapshot()
    by_id = {s.span_id: s for s in spans}
    (prefetch,) = [s for s in spans if s.name == "tree.prep.prefetch"]
    assert prefetch.thread == "tree-prep-prefetch"
    assert by_id[prefetch.parent_id].name == "stage:ModelSelector"
    on_thread = [s for s in spans if s.thread == "tree-prep-prefetch"
                 and s is not prefetch]
    # (which of the two threads builds what is a race; the other one waits
    # or finds it)
    assert on_thread
    for s in on_thread:
        assert s.name.startswith(("tree.prep.", "launch:device_bin"))
        assert prefetch in list(_ancestors(s, by_id)), s.name


def test_contiguity_copy_has_a_span_and_the_memoised_probe_has_none():
    strided = np.ones((64, 8), np.float32)[:, ::2]
    with obs.tracing(capture_hlo=False) as tracer:
        first = trees._as_f32(strided)
        assert trees._as_f32(strided) is first     # the memoised copy
        trees._as_f32(np.ones((4, 4), np.float32))  # contiguous: itself
    assert [s.name for s in tracer.snapshot()] == ["tree.prep.contiguous"]


def test_a_checker_that_drops_columns_hands_on_a_matrix_nobody_copies(
        wf, monkeypatch):
    """The fitted SanityChecker writes its kept columns row-major (ISSUE
    33), so the selector's ``_as_f32`` finds nothing to undo: no
    ``tree.prep.contiguous`` span, and what ``selector.prepare`` works on
    IS the checker's output.  (``wf`` only so that the tree programs of
    this shape are built already.)"""
    from transmogrifai_tpu.preparators import SanityChecker

    df = planted_linear_frame(20_000, 16, 3)
    label = FeatureBuilder.RealNN("label").as_response()
    vector = transmogrify([FeatureBuilder.Real(c).as_predictor()
                           for c in df.columns if c != "label"])
    checked = SanityChecker().set_input(label, vector).get_output()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=1, models_and_parameters=[
            (models.OpXGBoostClassifier(num_round=2), grid(max_depth=[3]))])
    prediction = selector.set_input(label, checked).get_output()
    seen = []
    as_f32 = trees._as_f32

    def recording(X):
        seen.append((X, as_f32(X)))
        return seen[-1][1]

    monkeypatch.setattr(trees, "_as_f32", recording)
    with obs.tracing(capture_hlo=False) as tracer:
        model = (OpWorkflow().set_result_features(prediction)
                 .set_input_data(df).train())
    (checker,) = [s for s in model.stages
                  if type(s).__name__ == "SanityCheckerModel"]
    # 16 values interleaved with 16 constant null indicators: half dropped
    assert checker.keep_indices == list(range(0, 32, 2))
    given, prepared = seen[0]
    assert prepared is given
    assert given.shape == (20_000, 16) and given.flags.c_contiguous
    assert all(out is inp for inp, out in seen)
    names = [s.name for s in tracer.snapshot()]
    assert "selector.prepare" in names and "tree.prep.hash" in names
    assert "tree.prep.contiguous" not in names


def test_a_probe_that_waits_for_a_build_in_flight_is_a_span_and_a_count():
    profiling.reset_counters()
    started, release = threading.Event(), threading.Event()
    key = ("edges", "test-wait", (1, 1), 8)

    def slow_build():
        started.set()
        assert release.wait(30)
        return "built"

    got = []
    with obs.tracing(capture_hlo=False) as tracer:
        owner = threading.Thread(
            target=lambda: got.append(trees._memo(
                key, slow_build, span="tree.prep.sketch")))
        owner.start()
        assert started.wait(30)
        waiter = threading.Thread(
            target=lambda: got.append(trees._memo(key, slow_build)))
        waiter.start()
        deadline = time.monotonic() + 30    # until the waiter is waiting
        while not profiling.COUNTERS.memo_tags.get("edges", {}).get("waits"):
            assert time.monotonic() < deadline and waiter.is_alive()
            time.sleep(0.001)
        release.set()
        owner.join(30)
        waiter.join(30)
        assert not owner.is_alive() and not waiter.is_alive()
    trees.clear_sweep_caches()
    assert got == ["built", "built"]
    assert sorted(s.name for s in tracer.snapshot()) == [
        "tree.prep.sketch", "tree.prep.wait"]
    assert profiling.COUNTERS.to_json()["memoTags"] == {
        "edges": {"hits": 0, "builds": 1, "waits": 1}}


# -- programs built under a tracer -----------------------------------------------

def test_a_program_built_inside_a_trace_is_three_spans_inside_the_open_one():
    sys.path.insert(0, ROOT)
    from perfbench.compile_meter import CompileMeter

    def traced_program(a):
        return (a * 3 + 1).sum()

    def later_program(a):
        return (a * 5 - 2).sum()

    before = _time_span_listeners()
    meter = CompileMeter()
    try:
        x = jnp.ones(7)        # (its fill program is built out here)
        mark = meter.mark()
        with obs.tracing(capture_hlo=False) as tracer:
            assert _time_span_listeners() == before + 1
            with obs.span("outer") as outer:
                jax.jit(traced_program)(x).block_until_ready()
        built = meter.since(mark)
        # stop_trace took its one listener out again, and only that one
        assert _time_span_listeners() == before
        jax.jit(later_program)(x).block_until_ready()
        assert meter.since(mark)["programs"] == built["programs"] + 1
    finally:
        jax.monitoring.unregister_event_duration_listener(meter._on_duration)
        jax.monitoring.unregister_event_listener(meter._on_event)
    spans = [s for s in tracer.snapshot() if s.cat == "compile"]
    names = [s.name for s in spans]
    assert {"jit.trace:traced_program", "jit.lower:jit(traced_program)",
            "jit.compile:jit(traced_program)"} <= set(names)
    assert not [n for n in names if "later_program" in n]
    # the benchmark's meter counts the same programs beside the listener
    assert built["programs"] == sum(n.startswith("jit.compile:")
                                    for n in names)
    assert sum(s.dur_s for s in spans) == pytest.approx(built["compile_s"],
                                                        rel=1e-6)
    for s in spans:
        assert s.parent_id == outer.span_id
        # time.time() put on the perf_counter clock: inside the span that
        # was open round the call, to a millisecond
        assert outer.t0 - 1e-3 <= s.t0
        assert s.t0 + s.dur_s <= outer.t0 + outer.dur_s + 1e-3


# -- memoTags --------------------------------------------------------------------

def test_memo_tags_read_builds_then_hits_for_the_same_matrix(traced):
    tags = traced["counters"]["memoTags"]
    # the XGB group builds the binned matrix, the RF group, the refit and
    # the winner's scoring find it
    for kind in ("edges", "bins"):
        assert tags[kind]["builds"] >= 1 and tags[kind]["hits"] >= 1, kind
    assert all(set(t) == {"hits", "builds", "waits"} for t in tags.values())
    # a train clears the sweep's memos when it ends, so the next one builds
    # the same things again
    assert traced["counters_again"]["memoTags"] == tags


def test_reset_counters_resets_memo_tags():
    profiling.count_memo("bins", "builds")
    assert profiling.COUNTERS.to_json()["memoTags"]["bins"]["builds"] >= 1
    assert profiling.reset_counters().to_json()["memoTags"] == {}


# -- with no tracer --------------------------------------------------------------

@pytest.fixture(scope="module")
def untraced_calls(wf):
    """Every ``begin_span`` call of an untraced train: (name, result)."""
    calls = []
    real = obs_trace.begin_span

    def recording(name, *args, **kwargs):
        out = real(name, *args, **kwargs)
        calls.append((name, out))
        return out

    patch = pytest.MonkeyPatch()
    patch.setattr(obs_trace, "begin_span", recording)
    patch.setattr(profiling, "begin_span", recording)
    try:
        assert obs_trace.current_tracer() is None
        listeners = _time_span_listeners()
        profiling.reset_counters()
        wf.train()
        assert _time_span_listeners() == listeners
    finally:
        patch.undo()
    return calls


@pytest.mark.parametrize("site", REACHED)
def test_with_no_tracer_every_new_site_is_one_none_check(site,
                                                         untraced_calls):
    got = [out for name, out in untraced_calls if name == site]
    assert got, f"the untraced train did not pass {site}"
    assert all(out is None for out in got)


def test_with_no_tracer_a_train_records_nothing_but_the_counts(
        untraced_calls):
    assert all(out is None for _, out in untraced_calls)
    assert obs_trace.current_span() is None
    assert profiling.COUNTERS.to_json()["memoTags"]["bins"]["builds"] >= 1


# -- names on the device side ----------------------------------------------------

@pytest.fixture(scope="module")
def lowered_text():
    n, d, bins, depth = 256, 8, 8, 3
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, bins, (n, d)), jnp.int8)
    one = jnp.ones((2,), jnp.float32)
    grow = gk._grow_chunk.lower(
        binned, jnp.ones((2, n, 1), jnp.float32),
        jnp.ones((2, n, 1), jnp.float32), jnp.ones((2, n), jnp.float32),
        jnp.ones((2, d), bool), jnp.full((2,), depth, jnp.int32), depth, bins,
        jnp.float32(1), jnp.float32(1), jnp.float32(0), jnp.float32(1),
        jnp.bool_(True), jnp.float32(0.1), goss=(32, 32),
        goss_key=jax.random.PRNGKey(0))
    chain = gk._gbt_chain_rounds_jit.lower(
        binned, jnp.zeros(n, jnp.float32), jnp.ones((2, n), jnp.float32),
        jnp.zeros((2, n), jnp.float32), jnp.arange(16, dtype=jnp.int32),
        jnp.full((2,), depth, jnp.int32), one, one, 0 * one, one, 0.1 * one,
        0 * one, 2, depth, bins, "binary", False, True, goss=(32, 32),
        goss_seed=jnp.int32(7), chain_ids=jnp.arange(2, dtype=jnp.int32),
        round_offset=jnp.int32(0))
    score = gk.predict_ensemble.lower(
        binned, jnp.zeros((2, 7), jnp.int32), jnp.zeros((2, 7), jnp.int32),
        jnp.zeros((2, 8, 1), jnp.float32), depth)
    return {"_grow_chunk": grow.as_text(debug_info=True),
            "_gbt_chain_rounds_jit": chain.as_text(debug_info=True),
            "predict_ensemble": score.as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("_grow_chunk", "tree.hist"), ("_grow_chunk", "tree.split"),
    ("_grow_chunk", "tree.route"), ("_grow_chunk", "tree.leaf"),
    ("_grow_chunk", "goss.select"),
    ("_gbt_chain_rounds_jit", "gbt.grad"),
    ("_gbt_chain_rounds_jit", "gbt.update"),
    ("_gbt_chain_rounds_jit", "gbt.es_metric"),
    ("_gbt_chain_rounds_jit", "goss.select"),
    ("_gbt_chain_rounds_jit", "tree.hist"),
    ("_gbt_chain_rounds_jit", "tree.predict"),
    ("_gbt_chain_rounds_jit", "metric.grid"),
    ("predict_ensemble", "tree.predict")])
def test_named_scope_reaches_the_lowered_program(program, scope,
                                                 lowered_text):
    # (under a vmap the name stack prints the scope as ``vmap(<scope>)``)
    assert scope in lowered_text[program]


def test_tf_op_counter_walks_the_recorded_trace_s_wire_format():
    """``scripts/tf_op_scopes.py`` on the trace recorded on the chip before
    the scopes existed (PR 22): every op event, the share that carries a
    ``tf_op``, and no scope name yet."""
    import importlib.util

    file = importlib.util.spec_from_file_location(
        "tf_op_scopes", os.path.join(ROOT, "scripts", "tf_op_scopes.py"))
    tf_op_scopes = importlib.util.module_from_spec(file)
    file.loader.exec_module(tf_op_scopes)
    got = tf_op_scopes.count(os.path.join(
        ROOT, "perfbench", "testdata", "tiny_xgb_v5e.xplane.pb.xz"))
    assert (got["op_events"], got["with_tf_op"]) == (13088, 10763)
    assert set(got["scopes"]) == set(tf_op_scopes.SCOPES)
    assert all(s["events"] == 0 for s in got["scopes"].values())
