"""The eight per-layer metrics of ISSUE 36 (``first_tree_launch_s``,
``vectorize_fill_s``, ``vectorize_flush_s``, ``host_fresh_gib``,
``prep_hashed_gib``, ``xgb_prepare_s``, ``fit_prepare_s``, ``fit_fetch_s``):
each reader on hand-made ``sources``, their entries in ``BENCHMARK.json``,
that no accepted reader's pattern takes a new span's name, and one traced
CPU rehearsal of the mesh cell at a tiny shape on four virtual devices.  No
time printed by the rehearsal means anything.
"""
import ast
import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import ROOT, TINY, child_env  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402
from perfbench.metrics import _spans, host_unnamed_s  # noqa: E402

BENCH = spec.load_benchmark()
CELL = "mesh4-trees"
#: name -> (unit, source, layer), in the order of ISSUE 36's table
NEW = {
    "first_tree_launch_s": ("s", "program_counter", "device"),
    "vectorize_fill_s": ("s", "program_span", "reader and vectorizers"),
    "vectorize_flush_s": ("s", "program_span", "reader and vectorizers"),
    "host_fresh_gib": ("GiB", "program_counter", "host process"),
    "prep_hashed_gib": ("GiB", "program_counter", "tree input prep"),
    "xgb_prepare_s": ("s", "program_span", "sweep"),
    "fit_prepare_s": ("s", "program_span", "sweep"),
    "fit_fetch_s": ("s", "program_span", "sweep"),
}
#: the span families this PR added to the program
NEW_SPANS = ["vectorize.fill", "vectorize.flush", "gbt.grid.prepare",
             "gbt.grid.rounds", "gbt.grid.score", "gbt.grid.metrics",
             "tree.fit.prepare", "tree.fit.grow", "tree.fit.fetch"]

#: (name, start, end) in seconds on the spans' clock
SPANS = [
    ("workflow.train", 100.0, 113.0),
    ("stage:RealVectorizer", 100.0, 102.2),
    ("vectorize.fill[0]", 100.0, 100.1),
    ("vectorize.flush[0]", 100.1, 100.3),
    ("vectorize.fill[1]", 100.3, 100.45),
    ("vectorize.flush[1]", 100.45, 100.7),
    ("sweep.group:OpXGBoostClassifier", 103.0, 109.0),
    ("gbt.grid.prepare", 103.0, 105.0),
    ("tree.prep.hash", 103.1, 103.6),
    ("gbt.grid.rounds", 105.0, 106.0),
    ("launch:gbt_chain_rounds_sharded", 105.0, 105.9),
    ("gbt.grid.score", 106.0, 108.5),
    ("gbt.grid.metrics", 108.5, 109.0),
    ("selector.refit", 110.0, 112.9),
    ("tree.fit.prepare", 110.0, 110.4),
    ("tree.fit.grow", 110.4, 112.6),
    ("tree.fit.fetch", 112.6, 112.9),
    # a second fit on another thread, overlapping the first: a union
    ("tree.fit.prepare", 110.2, 110.5),
]
COUNTERS = {
    "launchTags": {"device_bin": 8, "gbt_chain_rounds_sharded": 1,
                   "gbt_chain_score": 3, "rf_grid_chunk_sharded": 1},
    "firstLaunchSecs": {"device_bin": 4.41, "gbt_chain_rounds_sharded": 4.87,
                        "gbt_chain_score": 8.2, "rf_grid_chunk_sharded": 9.6},
    "hostFresh": {"vectorize.out": 1_000_000_000, "vectorize.buf": 134 << 20,
                  "sanity.filter": 500_000_000},
    "hashBytes": 3 << 29, "hashes": 3,
}
WANT = {"first_tree_launch_s": 4.87,
        "vectorize_fill_s": 0.1 + 0.15,
        "vectorize_flush_s": 0.2 + 0.25,
        "host_fresh_gib": (1_500_000_000 + (134 << 20)) / 2**30,
        "prep_hashed_gib": 1.5,
        "xgb_prepare_s": 2.0,
        "fit_prepare_s": 0.5,
        "fit_fetch_s": 0.3}


def _sources(platform="tpu", spans=SPANS, counters=COUNTERS):
    """What ``train_loop`` hands the readers, as far as they read it: the
    first chip busy from 104.9 to 109.5 and from 110.5 to 112.8 of a train
    that lasts from 100 to 113."""
    trace = {"platform": platform, "annotation_perf_s": 100.0,
             "window_ns": (0, 13_000_000_000),
             "devices": {"/device:TPU:0": {"busy_intervals": [
                 (4_900_000_000, 9_500_000_000),
                 (10_500_000_000, 12_800_000_000)]}},
             "spans": [{"name": n, "t0": a, "dur_s": b - a}
                       for n, a, b in spans]}
    return {"counters": dict(counters), "trace": trace}


# -- the readers -----------------------------------------------------------------

@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_the_value_on_a_tpu_trace(name):
    reader = spec.load_module("metrics", name)
    assert reader.read(_sources()) == pytest.approx(WANT[name], abs=1e-9)


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_nothing_without_a_tpu_trace(name):
    """Never a CPU number under these names: a rehearsal's line leaves them
    out, as it leaves ``prep_builds`` out."""
    reader = spec.load_module("metrics", name)
    assert reader.read(_sources(platform="cpu")) is None
    assert reader.read({"counters": dict(COUNTERS), "trace": None}) is None
    assert reader.read({"counters": dict(COUNTERS)}) is None
    assert reader.read({}) is None


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_nothing_on_the_parent_s_program(name):
    """The parent records no such span and counts no such counter: the
    reader returns nothing and does not raise, and the line leaves the
    metric out."""
    old_spans = [s for s in SPANS
                 if not s[0].startswith(tuple(NEW_SPANS))]
    old_counters = {"launchTags": COUNTERS["launchTags"],
                    "memoTags": {}, "drainSecs": 0.0}
    reader = spec.load_module("metrics", name)
    assert reader.read(_sources(spans=old_spans,
                                counters=old_counters)) is None


def test_first_tree_launch_leaves_the_binning_launches_out():
    reader = spec.load_module("metrics", "first_tree_launch_s")
    only_bins = dict(COUNTERS, firstLaunchSecs={"device_bin": 4.41})
    assert reader.read(_sources(counters=only_bins)) is None
    early = dict(COUNTERS, firstLaunchSecs={"device_bin": 0.2,
                                            "rf_grid_chunk": 3.0,
                                            "gbt_rounds": 7.5})
    assert reader.read(_sources(counters=early)) == 3.0


def test_reader_constants_are_the_entries():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (unit, _, layer) in NEW.items():
        reader = spec.load_module("metrics", name)
        assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
            unit, layer, "train_s")
        assert (entries[name]["unit"], entries[name]["layer"]) == (
            unit, layer)


# -- BENCHMARK.json ------------------------------------------------------------

def test_the_eight_entries_stand_last_in_the_table_s_order():
    """The eight were appended after the thirty that stood; later entries
    are appended after them, so their place is held, not the tail."""
    mine = BENCH["per_layer"][30:30 + len(NEW)]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        unit, source, layer = NEW[m["name"]]
        assert dict(m, workloads=m["workloads"][:1]) == {
            "name": m["name"], "unit": unit, "better": "lower",
            "source": source, "layer": layer, "moves": "train_s",
            "workloads": [CELL]}
    # the thirty that stood are the thirty that stand
    assert len(BENCH["per_layer"]) >= 30 + len(NEW)
    assert BENCH["per_layer"][29]["name"] == "rf_hist_roofline"
    assert BENCH["per_layer"][0]["name"] == "vectorize_s"


# -- no accepted reader takes a new span for one of its own ---------------------

def _accepted_patterns():
    """Every pattern a reader the benchmark had hands to ``_spans``
    (``perfbench/metrics/*.py`` but the eight new files: the string
    constants in the arguments of its ``_spans.<function>(...)`` calls),
    ``_spans``'s own two and ``host_unnamed_s``'s list."""
    found = {_spans.PREP, _spans.COMPILE, *host_unnamed_s.NAMED}
    folder = os.path.join(ROOT, "perfbench", "metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".py") or fname[:-3] in NEW:
            continue
        tree = ast.parse(open(os.path.join(folder, fname)).read())
        for call in ast.walk(tree):
            if (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)
                    and isinstance(call.func.value, ast.Name)
                    and call.func.value.id == "_spans"):
                for arg in call.args[1:]:
                    found.update(c.value for c in ast.walk(arg)
                                 if isinstance(c, ast.Constant)
                                 and isinstance(c.value, str))
    return sorted(found)


def test_no_new_span_name_matches_an_accepted_reader_s_pattern():
    patterns = _accepted_patterns()
    assert {r"launch:.*", r"sweep\.group:Op(XGBoost|GBT).*",
            r"selector\.refit", _spans.PREP} <= set(patterns)
    names = NEW_SPANS + ["vectorize.fill[3]", "vectorize.flush[7]"]
    for pattern in patterns:
        rx = re.compile(pattern)
        for name in names:
            assert not rx.fullmatch(_spans._INDEX.sub("", name)), (
                pattern, name)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"][:30]
                                  if m["source"] == "program_span"
                                  and m["name"] not in ("vectorize_s",
                                                        "sanity_s",
                                                        "selector_s")])
def test_accepted_span_readers_read_the_same_with_the_new_spans_there(name):
    """``host_unnamed_s`` among them: its ``NAMED`` list is the benchmark's,
    so the new spans name nothing for it yet."""
    reader = spec.load_module("metrics", name)
    old_spans = [s for s in SPANS
                 if not s[0].startswith(tuple(NEW_SPANS))]
    assert reader.read(_sources()) == reader.read(_sources(spans=old_spans))


# -- the rehearsal ----------------------------------------------------------------

def test_traced_cpu_rehearsal_of_the_mesh_cell_records_every_new_span_and_counter(  # noqa: E501
        tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("perfbench_host_metrics_cache")
    dump = tmp_path_factory.mktemp("perfbench_host_metrics") / "sources.json"
    out = subprocess.run(
        [sys.executable, os.path.join("tests", "perfbench",
                                      "_host_rehearsal.py"), str(dump),
         "--workload", CELL, "--seed", "3", "--seconds", "2", "--trace", "1",
         "--allow-cpu", *TINY],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=child_env(cache_dir, devices=4))
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    # never a CPU number under the new names
    assert not set(NEW) & set(last["metrics"])
    got = json.loads(dump.read_text())
    families = {_spans._INDEX.sub("", n) for n in got["spans"]}
    assert set(NEW_SPANS) <= families
    c = got["counters"]
    assert list(c["firstLaunchSecs"]) == list(c["launchTags"])
    assert {"device_bin", "gbt_chain_rounds_sharded", "gbt_chain_score",
            "rf_grid_chunk_sharded"} <= set(c["firstLaunchSecs"])
    rows, cols = int(TINY[1]), int(TINY[3])
    assert c["hostFresh"]["vectorize.out"] == rows * 2 * cols * 4
    assert {"vectorize.buf", "sanity.filter", "tree.pad"} <= set(
        c["hostFresh"])
    # (a matrix of this shape is under the 1 MB from which
    # ``_content_hash`` keeps a full hash an object: none is counted)
    assert (c["hashes"], c["hashBytes"]) == (0, 0)
