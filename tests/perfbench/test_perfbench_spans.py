"""The readers of the program's own spans (``perfbench/metrics/_spans.py``
and the twelve metrics that read ``tree.prep.*``, ``selector.*``,
``sweep.group:<Estimator>``, ``jit.*`` and ``COUNTERS.memoTags``).

No JAX beyond the trace file's reader, no subprocess: interval arithmetic on
hand-made intervals, then every reader on synthetic ``sources`` built from
the trace recorded on the chip (``perfbench/testdata``, PR 22) plus
hand-made spans on a ``perf_counter`` clock whose instant 100.0 is the
start of the ``perfbench.train`` annotation.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import breakdown, spec, trace_reduce  # noqa: E402
from perfbench.metrics import _spans  # noqa: E402

TRACE = os.path.join(ROOT, "perfbench", "testdata",
                     "tiny_xgb_v5e.xplane.pb.xz")
BENCH = spec.load_benchmark()
NEW = ["tree_prep_s", "prep_hash_s", "prep_sketch_s", "prep_bin_s",
       "prep_place_s", "prep_builds", "xgb_group_s", "rf_group_s",
       "refit_s", "winner_eval_s", "window_compile_s", "host_unnamed_s"]


def _span(name, t0, t1):
    return {"name": name, "t0": t0, "dur_s": t1 - t0}


# -- interval arithmetic ------------------------------------------------------

@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(2, 3), (5, 7)]),
    ([(0, 2), (4, 6)], [(1, 5)], [(1, 2), (4, 5)]),
    ([(0, 1)], [(1, 2)], []),                  # touching is no overlap
    ([], [(0, 1)], []),
    ([(0, 4), (6, 9)], [(3, 7), (8, 12)], [(3, 4), (6, 7), (8, 9)])])
def test_intersect_of_sorted_disjoint_intervals(a, b, want):
    assert _spans.intersect(a, b) == want
    assert _spans.intersect(b, a) == want


def _hand_made(spans):
    """One chip busy in [0, 1] and [4, 5] s of a 5 s train whose annotation
    started at perf_counter 10.0: ONE idle gap, 11.0-14.0 on the spans'
    clock."""
    return {"trace": {
        "platform": "tpu", "window_ns": [0.0, 5e9], "window_s": 5.0,
        "annotation_perf_s": 10.0, "spans": spans,
        "devices": {"/device:TPU:0": {
            "busy_intervals": [(0.0, 1e9), (4e9, 5e9)]}}}}


THREE = [_span("tree.prep.sketch", 10.5, 12.0),
         _span("tree.prep.bin", 12.0, 13.0),
         _span("selector.refit", 13.0, 14.5)]


@pytest.mark.parametrize("pattern,under", [
    (r"tree\.prep\.sketch", 1.0), (r"tree\.prep\.bin", 1.0),
    (r"selector\.refit", 1.0), (r"tree\.prep\..*", 2.0),
    (r"no\.such\.span", 0.0)])
def test_a_gap_that_straddles_three_spans_gives_each_its_overlap(
        pattern, under):
    idle, named = _spans.idle_seconds_under(_hand_made(THREE), [pattern])
    assert idle == pytest.approx(3.0)
    assert named == pytest.approx(under)


def test_the_overlaps_add_up_to_the_gap_where_the_midpoint_rule_books_one():
    sources = _hand_made(THREE)
    parts = [_spans.idle_seconds_under(sources, [p])[1] for p in (
        r"tree\.prep\.sketch", r"tree\.prep\.bin", r"selector\.refit")]
    assert sum(parts) == pytest.approx(3.0)
    # breakdown.idle_gaps (the accepted rule) gives the whole gap to the span
    # open at its midpoint, 12.5: the case the exact intersection repairs
    assert breakdown.name_gaps(sources["trace"]) == {
        "tree.prep.bin": pytest.approx(3.0)}


def test_spans_of_two_threads_that_overlap_count_once_in_a_union():
    spans = [_span("tree.prep.sketch", 11.0, 13.0),       # prefetch thread
             _span("tree.prep.wait", 12.0, 13.5),         # main thread
             _span("tree.prep.prefetch", 10.0, 14.0)]     # only wraps them
    sources = _hand_made(spans)
    assert _spans.union_seconds(sources, _spans.PREP) == pytest.approx(2.5)
    assert _spans.sum_seconds(sources, _spans.PREP) == pytest.approx(3.5)


def test_indices_are_stripped_and_the_whole_name_must_match():
    spans = [_span("sweep.group[0:2]", 11.0, 12.0),
             _span("sweep.group:OpXGBoostClassifier", 11.0, 12.0),
             _span("sweep.unit[3]", 12.0, 13.0)]
    red = _hand_made(spans)["trace"]
    assert _spans.matching(red, r"sweep\.group") == [(11.0, 12.0)]
    assert _spans.matching(red, r"sweep\.unit") == [(12.0, 13.0)]
    assert _spans.matching(red, r"sweep\.group:OpXGBoost.*") == [(11.0, 12.0)]
    assert _spans.matching(red, r"sweep") == []


# -- every reader on the recorded trace plus hand-made spans -------------------

@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(TRACE)


#: (name, start, end) in ms after the annotation's start; the recorded
#: train lasts 225.4 ms
SPANS_MS = [
    ("workflow.train", 0, 225.4),
    ("stage:RealVectorizer", 0, 4),
    ("stage:ModelSelector", 4, 225),
    ("selector.prepare", 4, 5),
    ("tree.prep.contiguous", 4.2, 4.8),
    ("selector.validate", 5, 180),
    ("sweep.run", 5.5, 180),
    ("sweep.group[0:1]", 6, 100),
    ("sweep.group:OpXGBoostClassifier", 6, 100),
    ("tree.prep.hash", 6, 8),
    ("tree.prep.prefetch", 6, 30),
    ("tree.prep.sketch", 10, 14),
    ("tree.prep.sketch", 12, 16),        # another thread, overlapping
    ("tree.prep.wait", 11, 16),
    ("tree.prep.bin", 16, 18),
    ("tree.prep.upload", 18, 19),
    ("tree.prep.bundle", 19, 21),
    ("tree.prep.upload", 21, 21.5),
    ("tree.prep.csr", 21.5, 22),
    ("launch:gbt_chain_rounds", 22, 30),
    ("jit.trace:shard_fn", 22.5, 24.5),
    ("jit.trace:inner", 23, 24),         # nested: the meter adds it again
    ("jit.lower:jit(shard_fn)", 24.5, 25.5),
    ("jit.compile:jit(shard_fn)", 25.5, 29.5),
    ("sweep.group[1:2]", 100, 150),
    ("sweep.group:OpRandomForestClassifier", 100, 150),
    ("launch:rf_grid_chunk", 101, 103),
    ("sweep.drain", 150, 180),
    ("selector.refit", 180, 180.1),      # the group declined ...
    ("selector.refit", 180.1, 200),      # ... so the winner is fitted anew
    ("selector.predict", 200, 210),
    ("selector.metrics", 210, 224),
]
MEMO_TAGS = {"edges_mesh": {"hits": 1, "builds": 2, "waits": 0},
             "bins": {"hits": 5, "builds": 2, "waits": 1},
             "gbt_grid_W": {"hits": 0, "builds": 1, "waits": 0}}
#: what each reader must give for SPANS_MS, in seconds (``host_unnamed_s``
#: depends on the recorded chip's busy intervals and has its own test)
WANT = {"tree_prep_s": (0.6 + 2 + 6 + 2 + 1 + 2 + 0.5 + 0.5) / 1e3,
        "prep_hash_s": (0.6 + 2) / 1e3,
        "prep_sketch_s": 6 / 1e3,
        "prep_bin_s": 2 / 1e3,
        "prep_place_s": (1 + 2 + 0.5 + 0.5) / 1e3,
        "prep_builds": 5,
        "xgb_group_s": 94 / 1e3,
        "rf_group_s": 50 / 1e3,
        "refit_s": 20 / 1e3,
        "winner_eval_s": 24 / 1e3,
        "window_compile_s": (2 + 1 + 1 + 4) / 1e3}


def _sources(reduced, platform="tpu", spans=SPANS_MS, memo=MEMO_TAGS):
    counters = {"drainSecs": 0.0}
    if memo is not None:
        counters["memoTags"] = memo
    return {"counters": counters, "trace": dict(
        reduced, platform=platform, annotation_perf_s=100.0,
        spans=[_span(n, 100.0 + a / 1e3, 100.0 + b / 1e3)
               for n, a, b in spans])}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_the_recorded_trace_with_hand_made_spans(name, reduced):
    reader = spec.load_module("metrics", name)
    got = reader.read(_sources(reduced))
    assert isinstance(got, (int, float))
    if name in WANT:
        assert got == pytest.approx(WANT[name], abs=1e-9)
    # a reduction marked as a CPU rehearsal is never read: the pinned set of
    # metrics a rehearsal prints stays as it is
    assert reader.read(_sources(reduced, platform="cpu")) is None
    assert reader.read({}) is None


#: the spans a program WITHOUT this PR's hooks records in the same train
OLD_SPANS = [s for s in SPANS_MS if s[0].startswith((
    "workflow.", "stage:", "sweep.run", "sweep.group[", "sweep.drain"))]


@pytest.mark.parametrize("name", [n for n in NEW if n != "host_unnamed_s"])
def test_reader_returns_nothing_where_the_program_has_no_such_span(
        name, reduced):
    """The driver lays these readers over the parent's checkout too: there
    they find no ``tree.prep.*`` / ``selector.*`` / ``jit.*`` span and no
    ``memoTags``, return ``None`` and do not raise."""
    reader = spec.load_module("metrics", name)
    assert reader.read(_sources(reduced, spans=OLD_SPANS, memo=None)) is None
    assert reader.read(_sources(reduced, spans=[], memo=None)) is None


def test_unnamed_plus_named_idle_seconds_are_the_chip_s_idle_seconds(reduced):
    from perfbench.metrics import host_unnamed_s

    sources = _sources(reduced)
    idle, named = _spans.idle_seconds_under(sources, host_unnamed_s.NAMED)
    dev = reduced["devices"]["/device:TPU:0"]
    assert idle == pytest.approx(reduced["window_s"] - dev["busy_s"],
                                 abs=1e-6)
    unnamed = host_unnamed_s.read(sources)
    assert unnamed + named == pytest.approx(idle, abs=1e-6)
    assert 0.0 < unnamed < idle
    # the parts add up: idle under each named pattern's own union, taken
    # one after the other without what an earlier one already covers
    left, parts = _spans.idle_intervals(sources["trace"]), []
    for pattern in host_unnamed_s.NAMED:
        cover = trace_reduce.merge(_spans.matching(sources["trace"],
                                                   pattern))
        parts.append(trace_reduce.total(_spans.intersect(left, cover)))
        left = _spans.intersect(
            left, sorted(trace_reduce.gaps(cover, 0.0, 1e9)))
    assert sum(parts) == pytest.approx(named, abs=1e-6)
    assert trace_reduce.total(left) == pytest.approx(unnamed, abs=1e-6)
    # on the parent the same reader reads what no old span names either
    old = host_unnamed_s.read(_sources(reduced, spans=OLD_SPANS, memo=None))
    assert unnamed < old <= idle


def test_broad_spans_name_nothing(reduced):
    """Idle time directly under ``stage:ModelSelector``, ``selector.validate``,
    ``sweep.run``, ``sweep.group`` or the family span is unnamed: only a span
    that says what the host was doing takes idle time off the number."""
    from perfbench.metrics import host_unnamed_s

    broad = [s for s in SPANS_MS if s[0] in (
        "workflow.train", "stage:ModelSelector", "selector.validate",
        "sweep.run", "sweep.group[0:1]", "sweep.group[1:2]",
        "sweep.group:OpXGBoostClassifier",
        "sweep.group:OpRandomForestClassifier", "tree.prep.prefetch")]
    sources = _sources(reduced, spans=broad)
    idle, named = _spans.idle_seconds_under(sources, host_unnamed_s.NAMED)
    assert named == 0.0
    assert host_unnamed_s.read(sources) == pytest.approx(idle)


# -- BENCHMARK.json ------------------------------------------------------------

#: the per-layer metrics the benchmark had before these twelve, in its order
BEFORE = ["vectorize_s", "sanity_s", "selector_s", "drain_s",
          "mesh_tree_device_s", "collective_s", "window_programs",
          "tree_device_s", "tree_hist_roofline", "peak_hbm_gib", "compile_s",
          "programs", "peak_host_gib"]


def test_the_twelve_entries_stand_at_the_end_and_move_the_mesh_cell_s_wall():
    """The twelve were appended after the thirteen that stood, and later
    entries are appended after them: their place is held, not the table's
    tail, so a later cell or metric leaves this green."""
    mine = BENCH["per_layer"][len(BEFORE):len(BEFORE) + len(NEW)]
    assert [m["name"] for m in mine] == NEW
    layers = {"compile": ["window_compile_s"],
              "sweep": ["xgb_group_s", "rf_group_s", "refit_s",
                        "winner_eval_s", "host_unnamed_s"]}
    for m in mine:
        assert (m["moves"], m["better"], m["workloads"][:1]) == (
            "train_s", "lower", ["mesh4-trees"])
        want = ("program_counter" if m["name"] == "prep_builds"
                else "program_span")
        assert m["source"] == want
        layer = next((k for k, v in layers.items() if m["name"] in v),
                     "tree input prep")
        assert m["layer"] == layer, m["name"]
    # and nothing the accepted benchmark had was touched
    assert [m["name"] for m in BENCH["per_layer"][:len(BEFORE)]] == BEFORE
