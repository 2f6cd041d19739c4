"""The cell ``dense500-rf-grid18`` (PR 34): its files against what the issue
names, its five readers on the recorded trace and on hand-made ``sources``,
and one traced CPU rehearsal at a tiny shape.

What a CPU can pin: the counters' readings (trees grown = bases x folds x
trees + the refit's forest, not grid points x folds x trees), that the
device-trace readers stay silent off the chip and on a program without the
counters (the driver lays these files over the parent's checkout), and the
bytes the roofline counts.  No time printed by the rehearsal means anything.
"""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import ROOT, TINY, run_cell  # noqa: E402

sys.path.insert(0, ROOT)
from perfbench import spec, trace_reduce  # noqa: E402

CELL = "dense500-rf-grid18"
TRACE = os.path.join(ROOT, "perfbench", "testdata",
                     "tiny_xgb_v5e.xplane.pb.xz")
BENCH = spec.load_benchmark()
NEW = ["rf_grow_device_s", "rf_score_device_s", "rf_trees_grown",
       "rf_launches", "rf_hist_roofline"]
#: the per-layer metrics the accepted benchmark had, in its order
ACCEPTED = [
    "vectorize_s", "sanity_s", "selector_s", "drain_s", "mesh_tree_device_s",
    "collective_s", "window_programs", "tree_device_s", "tree_hist_roofline",
    "peak_hbm_gib", "compile_s", "programs", "peak_host_gib", "tree_prep_s",
    "prep_hash_s", "prep_sketch_s", "prep_bin_s", "prep_place_s",
    "prep_builds", "xgb_group_s", "rf_group_s", "refit_s", "winner_eval_s",
    "window_compile_s", "host_unnamed_s"]


# -- the files ----------------------------------------------------------------

def test_the_cell_is_the_default_forest_grid_uncut_in_width_and_depth():
    loaded = spec.load_cell(CELL)
    cfg, mix = loaded["config"], loaded["traffic"]
    assert loaded["cell"]["chips"] == 1 and cfg["mesh"] is None
    assert (cfg["schema"]["predictors"]["count"], cfg["rows"],
            cfg["holdout_rows"], cfg["max_bins"],
            cfg["validator"]["num_folds"]) == (500, 250_000, 50_000, 32, 3)
    (rf,) = mix["models_and_parameters"]
    assert rf["estimator"] == "OpRandomForestClassifier"
    # upstream DefaultSelectorParams.scala:36-75, every point
    assert rf["grid"] == {"max_depth": [3, 6, 12],
                          "min_info_gain": [0.001, 0.01, 0.1],
                          "min_instances_per_node": [10, 100]}
    assert math.prod(len(v) for v in rf["grid"].values()) == 18
    assert rf["args"] == {"num_trees": cfg["rf_num_trees"]}
    assert cfg["reduced"]["rf_num_trees"]["source"] == 50
    assert (mix["mode"], mix["entry"]) == ("train_loop", "selector_refit")
    # tree_hist_roofline counts trees from the mix (points x folds), which
    # is wrong for a shared grid: the cell brings no group for it
    assert "roofline" not in mix
    # the metrics it was accepted with; later entries may list it too
    assert {"train_device_s", "holdout_aupr", "setup_s"} <= {
        m["name"] for m in loaded["end_to_end"]}
    assert set(NEW) | {"tree_device_s", "peak_hbm_gib", "compile_s",
                       "programs", "peak_host_gib"} <= {
        m["name"] for m in loaded["per_layer"]}


def test_one_band_holds_all_eighteen_candidates_and_says_why():
    band = spec.load_cell(CELL)["traffic"]["checks"]["quality_band"]
    lo, hi = band["cv_aupr"]["OpRandomForestClassifier"]
    ref = band["reference"]["OpRandomForestClassifier"]
    assert "perfbench/reference/rf_grid.py" in ref["command"]
    # the reference's weakest (a gate-0.1 forest that never splits scores
    # the positives' share) and strongest candidate lie inside, with room
    assert lo < ref["cv_aupr_lowest"] < ref["cv_aupr_highest"] < hi
    assert ref["cv_aupr_lowest"] == pytest.approx(ref["positives"], abs=0.02)
    h_lo, h_hi = band["holdout_aupr"]
    assert h_lo < ref["holdout_aupr"] < h_hi
    assert len(ref["why"]) > 100


def test_new_entries_stand_at_the_end_and_touch_nothing_that_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + len(NEW)] == NEW
    # lists and tables are held by the places the cell took in them: a
    # later cell appends its name and its entries after these
    for m in BENCH["per_layer"][len(ACCEPTED):len(ACCEPTED) + len(NEW)]:
        assert (m["moves"], m["workloads"][:1]) == ("train_device_s", [CELL])
        assert m["layer"] == ("sweep" if m["source"] == "program_counter"
                              else "tree kernels")
    assert BENCH["workloads"][2]["name"] == CELL
    assert BENCH["configs"][2]["name"] == "dense500-binary-rfgrid"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in ("train_device_s", "tree_device_s", "peak_hbm_gib"):
            assert m["workloads"][:2] == ["dense500-xgb", CELL]


# -- the readers ----------------------------------------------------------------

RF_GRID = {"candidates": 18, "bases": 6, "pairs": 19, "truncated": 12,
           "treesGrown": 152, "launches": 11, "chunk": 15, "msub": 22,
           "levels": 12}
MODULES = {"jit__grow_chunk_rf_grid": 10.5, "jit__score_ensemble_jit": 3.0,
           "jit__aupr_dev": 0.8, "jit_predict_ensemble": 0.007,
           "jit__bin_block_into": 0.005, "jit_reshape": 0.0005}


def _sources(platform="tpu", grid=RF_GRID, modules=MODULES):
    counters = {"drainSecs": 0.0}
    if grid is not None:
        counters["rfGrid"] = grid
    return {"counters": counters, "device_kind": "TPU v5 lite",
            "cell": {"rows": 250_000, "cols": 500},
            "trace": {"platform": platform, "devices": {
                "/device:TPU:0": {"module_s": modules}}}}


def _read(name, sources):
    return spec.load_module("metrics", name).read(sources)


def test_readers_on_hand_made_sources():
    src = _sources()
    assert _read("rf_grow_device_s", src) == 10.5
    assert _read("rf_score_device_s", src) == pytest.approx(3.807)
    assert _read("rf_trees_grown", src) == 152 == 6 * 3 * 8 + 8
    assert _read("rf_launches", src) == 11
    # 152 trees x 12 levels x 250,000 rows x (22 + 8) B over 10.5 s over
    # 819 GB/s: the forest's histograms are nowhere near bound by bytes
    moved = 152 * 12 * 250_000 * 30
    assert _read("rf_hist_roofline", src) == pytest.approx(
        100.0 * moved / 10.5 / 819e9)
    assert 0.0 < _read("rf_hist_roofline", src) < 1.0
    # growth and scoring are what the tree modules ran, bar the binning
    tree = _read("tree_device_s", src)
    assert tree == pytest.approx(10.5 + 3.0 + 0.007)


def test_the_roofline_counts_what_was_grown_not_the_grid_s_points():
    from perfbench.metrics import rf_hist_roofline, tree_hist_roofline

    grown = rf_hist_roofline.histogram_bytes(152, 12, 250_000, 22)
    assert grown == 152 * 12 * 250_000 * 30
    mix = dict(spec.load_cell(CELL)["traffic"], roofline={"groups": [{
        "estimator": "OpRandomForestClassifier", "trees_arg": "num_trees",
        "levels": 12, "rows_share": 1.0}]})
    trees = mix["models_and_parameters"][0]["args"]["num_trees"]
    by_points = tree_hist_roofline.histogram_bytes(
        250_000, 500, mix, 3, "OpRandomForestClassifier")
    # 55 forests of 500 columns where 19 of 22 columns are grown
    assert by_points == 55 * trees * 12 * 250_000 * 508
    assert by_points / (grown * trees / 8) > 45


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_off_the_chip_and_on_a_program_without_counters(
        name):
    device = name.endswith(("_device_s", "_roofline"))
    counted = name in ("rf_trees_grown", "rf_launches", "rf_hist_roofline")
    assert _read(name, {}) is None
    # a CPU rehearsal: counters are read, device time never
    assert (_read(name, _sources(platform="cpu")) is None) == device
    # the parent's program: no rfGrid counters
    assert (_read(name, _sources(grid=None)) is None) == counted
    assert (_read(name, _sources(grid={})) is None) == counted
    # a train that ran no forest grid
    other = {"jit__gbt_chain_rounds_jit": 2.6}
    got = _read(name, _sources(grid=None, modules=other))
    assert got is None


def test_readers_on_the_recorded_xgb_trace():
    """The trace recorded on the chip holds an XGB train: no forest growth
    module, and the scoring modules the pattern names."""
    reduced = trace_reduce.reduce_trace(TRACE)
    src = {"trace": reduced, "counters": {}, "device_kind": "TPU v5 lite",
           "cell": {"rows": 3000, "cols": 32}}
    assert _read("rf_grow_device_s", src) is None
    assert _read("rf_hist_roofline", src) is None
    modules = reduced["devices"][sorted(reduced["devices"])[0]]["module_s"]
    want = sum(v for k, v in modules.items()
               if "predict_ensemble" in k or "aupr_dev" in k
               or "score_ensemble" in k)
    assert want > 0
    assert _read("rf_score_device_s", src) == pytest.approx(want)


# -- the rehearsal ----------------------------------------------------------------

def test_traced_cpu_rehearsal_counts_the_trees_of_the_shared_grid(
        tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("perfbench_rf_grid_cache")
    out, last = run_cell(CELL, "--allow-cpu", *TINY, trace="1",
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    # 18 candidates x 3 folds + the refit
    assert last["attempted"] == 18 * 3 + 1
    trees = spec.load_cell(CELL)["config"]["rf_num_trees"]
    assert {"rf_trees_grown", "rf_launches", "compile_s", "programs",
            "peak_host_gib"} <= set(last["metrics"])
    # never a CPU number under a device metric's name
    device = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "device_trace"}
    assert not device & set(last["metrics"])
    # the base forests the program grew (one a min_instances_per_node value
    # since gate sharing), as its counters say in the traced train's line
    (traced,) = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("[perfbench] traced wall_s=")]
    bases = json.loads(traced.split(" rf_grid=")[1])["bases"]
    folds = spec.load_cell(CELL)["config"]["validator"]["num_folds"]
    assert last["metrics"]["rf_trees_grown"] == {
        "value": float(bases * folds * trees + trees), "unit": "count"}
    # the sweep's chunked launches and one for the refit; the log carries
    # the same count under the launch tag
    launches = int(last["metrics"]["rf_launches"]["value"])
    assert launches >= 2
    assert f'"rf_grid_chunk": {launches}' in out.stdout
    assert last["compared"]["window_programs"] == [0, 0]
    assert any(name.startswith("rf.grid.") or "ModelSelector" in name
               or "sweep" in name for name, _ in
               last["breakdown"]["idle_gaps"])
