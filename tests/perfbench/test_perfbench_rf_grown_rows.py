"""``rf_grown_rows``: the per-layer metric that says on how many
rows the random-forest grid grew a fold's base forests
(``COUNTERS.rfGrid.grownRows``): its reader on hand-made ``sources``, its
entry in ``BENCHMARK.json`` after everything that stood, and one traced CPU
rehearsal of ``dense500-rf-grid18`` at a tiny shape, where a fold's forests
must grow on the fold's training rows and not on the table's.  No time
printed by the rehearsal means anything.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import ROOT, TINY, run_cell  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402

NAME = "rf_grown_rows"
CELL = "dense500-rf-grid18"
BENCH = spec.load_benchmark()
#: the per-layer metric this one stands after, the last of the accepted
#: benchmark's
BEFORE = "rf_scored_rows"
RF_GRID = {"candidates": 18, "bases": 2, "pairs": 7, "truncated": 12,
           "gateShared": 12, "treesGrown": 84, "launches": 7, "chunk": 12,
           "msub": 22, "levels": 12, "scoredRows": 75776,
           "grownRows": 150528}


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
    assert _read(_sources(RF_GRID, platform)) == 150528


@pytest.mark.parametrize("grid", [
    None, {}, {k: v for k, v in RF_GRID.items() if k != "grownRows"}],
    ids=["no-forest-grid", "empty", "the-parent-s-program"])
def test_reader_gives_nothing_where_the_program_has_no_such_counter(grid):
    """Laid over a checkout whose program counts the grid's trees,
    launches and scored rows, and no grown rows."""
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
        "count", "tree kernels, the forest grid", "train_device_s")


def test_the_entry_stands_right_after_the_accepted_last_one():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) == names.index(BEFORE) + 1
    # the cell reports it, and no other accepted cell does
    for cell in ("dense500-xgb", "mesh4-trees", CELL):
        reported = {m["name"] for m in spec.load_cell(cell)["per_layer"]}
        assert (NAME in reported) == (cell == CELL)


def test_traced_cpu_rehearsal_grows_a_fold_on_its_own_rows(
        tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("perfbench_rf_grown_rows_cache")
    out, last = run_cell(CELL, "--allow-cpu", *TINY, trace="1",
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["metrics"][NAME]["unit"] == "count"
    grown = last["metrics"][NAME]["value"]
    rows = int(TINY[1])
    folds = spec.load_cell(CELL)["config"]["validator"]["num_folds"]
    # at most a fold's share of the table's rows, rounded up to the
    # compaction's multiple: not the table
    assert 0 < grown <= -(-rows * (folds - 1) // folds) + 1024
    assert grown % 1024 == 0 and grown < rows
    # the fold matrices bring no program into the window
    assert last["compared"]["window_programs"] == [0, 0]
