"""``rf_scored_rows`` (PR 37): the per-layer metric that says on how many
rows the random-forest grid scored a candidate pair
(``COUNTERS.rfGrid.scoredRows``): its reader on hand-made ``sources``, its
entry in ``BENCHMARK.json`` after everything that stood, and one traced CPU
rehearsal of ``dense500-rf-grid18`` at a tiny shape, where a pair must be
scored on a fold's validation rows and not on the table's.  No time printed
by the rehearsal means anything.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import ROOT, TINY, run_cell  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402

NAME = "rf_scored_rows"
CELL = "dense500-rf-grid18"
BENCH = spec.load_benchmark()
#: the per-layer metrics the accepted benchmark had (PR 36), in its order
ACCEPTED = [
    "vectorize_s", "sanity_s", "selector_s", "drain_s", "mesh_tree_device_s",
    "collective_s", "window_programs", "tree_device_s", "tree_hist_roofline",
    "peak_hbm_gib", "compile_s", "programs", "peak_host_gib", "tree_prep_s",
    "prep_hash_s", "prep_sketch_s", "prep_bin_s", "prep_place_s",
    "prep_builds", "xgb_group_s", "rf_group_s", "refit_s", "winner_eval_s",
    "window_compile_s", "host_unnamed_s", "rf_grow_device_s",
    "rf_score_device_s", "rf_trees_grown", "rf_launches", "rf_hist_roofline",
    "first_tree_launch_s", "vectorize_fill_s", "vectorize_flush_s",
    "host_fresh_gib", "prep_hashed_gib", "xgb_prepare_s", "fit_prepare_s",
    "fit_fetch_s"]
RF_GRID = {"candidates": 18, "bases": 2, "pairs": 7, "truncated": 12,
           "gateShared": 12, "treesGrown": 84, "launches": 6, "chunk": 15,
           "msub": 22, "levels": 12, "scoredRows": 83968}


def _sources(grid, platform="tpu"):
    counters = {"drainSecs": 0.0}
    if grid is not None:
        counters["rfGrid"] = grid
    return {"counters": counters, "device_kind": "TPU v5 lite",
            "cell": {"rows": 250_000, "cols": 500},
            "trace": {"platform": platform, "devices": {}}}


def _read(sources):
    return spec.load_module("metrics", NAME).read(sources)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_reader_gives_the_counter_on_any_platform(platform):
    assert _read(_sources(RF_GRID, platform)) == 83968


@pytest.mark.parametrize("grid", [
    None, {}, {k: v for k, v in RF_GRID.items() if k != "scoredRows"}],
    ids=["no-forest-grid", "empty", "the-parent-s-program"])
def test_reader_gives_nothing_where_the_program_has_no_such_counter(grid):
    """The driver lays this file over the parent's checkout: its program
    counts the grid's trees and launches and no scored rows."""
    assert _read(_sources(grid)) is None
    assert _read({}) is None


def test_reader_constants_are_the_entry():
    reader = spec.load_module("metrics", NAME)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    # a later forest cell may append its name to the list
    assert dict(entry, workloads=entry["workloads"][:1]) == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": "program_counter", "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": [CELL]}
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
        "count", "sweep", "train_device_s")


def test_the_entry_stands_after_every_accepted_one_in_their_order():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED)] == NAME
    # the cell reports it, and no other accepted cell does
    for cell in ("dense500-xgb", "mesh4-trees", CELL):
        reported = {m["name"] for m in spec.load_cell(cell)["per_layer"]}
        assert (NAME in reported) == (cell == CELL)


def test_traced_cpu_rehearsal_scores_a_pair_on_its_fold_s_rows(
        tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("perfbench_rf_scored_rows_cache")
    out, last = run_cell(CELL, "--allow-cpu", *TINY, trace="1",
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["metrics"][NAME]["unit"] == "count"
    scored = last["metrics"][NAME]["value"]
    rows = int(TINY[1])
    folds = spec.load_cell(CELL)["config"]["validator"]["num_folds"]
    # at most the weighted rows (never more than the table's) over the
    # folds, rounded up to the compaction's multiple: not the table
    assert 0 < scored <= -(-rows // folds) + 1024
    assert scored % 1024 == 0 and scored < rows
    # the compaction brings no program into the window
    assert last["compared"]["window_programs"] == [0, 0]
