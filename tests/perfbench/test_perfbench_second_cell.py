"""A second cell of a kind enters on top of the first.

In a temporary copy of the benchmark a stand-in for the first regression
cell is entered exactly as ``perfbench/README.md`` ("Adding things") tells
the PR that brings one: its own configuration, the probe's generator copied
byte for byte to ``perfbench/generators/planted_regression.py``, its own
traffic ``rf-reg-grid18``, ``holdout_rmse`` appended with its name as the
list, its name on the lists of the forest metrics.  The typed probe enters
on top, and the benchmark's own tests that run no cell pass on the copy:
those of ``test_perfbench_extend.py``, whose regression test enters the
probe beside the stand-in and holds every pin with two regression cells on
``holdout_rmse``'s list, and the one of ``test_perfbench_run.py`` that
holds what its tiny run asks of each cell to the cell's label kind.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import ROOT, TINY, child_env, tiny_run  # noqa: E402
from _probes import (HOLDOUT_RMSE, LATER,  # noqa: E402
                     REGRESSION_CELL, REGRESSION_METRICS, TYPED_CELL,
                     append_holdout_rmse, copy_of_the_benchmark, enter,
                     enter_regression_probe, enter_typed_probe, hashes,
                     later, list_cell)

STAND_IN = "stand-in-regression"
STAND_IN_CELL = "stand-in-regression-rf"
#: the files a first regression cell brings, by the written recipe
STAND_IN_FILES = ("perfbench/generators/planted_regression.py",
                  f"perfbench/configs/{STAND_IN}.json",
                  "perfbench/traffic/rf-reg-grid18.json")
#: the tests of ``test_perfbench_extend.py`` that run no cell (the last
#: runs every pin of ``_probes.PINS`` on the copy with the probe entered)
EXTEND_WITHOUT_A_RUN = (
    "test_the_typed_generator_plants_one_model_and_owns_its_oracle",
    "test_the_regression_generator_plants_one_model_and_owns_its_oracle",
    "test_a_regression_configuration_is_new_files_and_entries")
#: the test of ``test_perfbench_run.py`` that runs no cell
RUN_WITHOUT_A_RUN = "test_a_tiny_run_asks_what_the_cell_s_configuration_gives"


def enter_first_regression_cell(root):
    """The stand-in, as the recipe enters a first regression cell."""
    config = json.loads(later("regression-probe.json"))
    config.update(name=STAND_IN, source="a stand-in for the first "
                  "regression deployment, entered by the written recipe")
    config["generator"]["name"] = "planted_regression"

    def edit(bench):
        bench["configs"].append({
            "name": STAND_IN, "source": config["source"],
            "file": STAND_IN_FILES[1], "reduced": sorted(config["reduced"]),
            "why": "a first regression cell"})
        bench["workloads"].append({
            "name": STAND_IN_CELL, "config": STAND_IN,
            "traffic": "rf-reg-grid18", "chips": 1,
            "why": "a first regression cell"})
        append_holdout_rmse(bench, STAND_IN_CELL)
        list_cell(bench, STAND_IN_CELL, *REGRESSION_METRICS)

    return enter(root, edit, dict(zip(STAND_IN_FILES, (
        later("planted_regression_probe.py"), json.dumps(config, indent=1),
        later("rf-reg-grid18-probe.json")))))


def test_a_second_regression_cell_enters_on_top_of_a_first(tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    before = hashes(root)
    enter_first_regression_cell(root)
    enter_typed_probe(root)
    test_dir = os.path.join("tests", "perfbench")
    rules = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist",
         *(os.path.join(test_dir, "test_perfbench_extend.py") + "::" + t
           for t in EXTEND_WITHOUT_A_RUN),
         os.path.join(test_dir, "test_perfbench_run.py") + "::"
         + RUN_WITHOUT_A_RUN],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=child_env(tmp_path / "jax_cache"))
    assert rules.returncode == 0, rules.stdout[-3000:] + rules.stderr[-2000:]
    for held in (*EXTEND_WITHOUT_A_RUN,
                 f"{RUN_WITHOUT_A_RUN}[{STAND_IN_CELL}]",
                 f"{RUN_WITHOUT_A_RUN}[{TYPED_CELL}]"):
        assert f"{held} PASSED" in rules.stdout, held
    assert " failed" not in rules.stdout and " error" not in rules.stdout
    # the tiny run asks the stand-in what the regression probe's run shows
    # (``test_perfbench_extend.py``), and the typed probe what its own does
    assert tiny_run(STAND_IN_CELL, root=str(root)) == {
        "flags": TINY, "holdout": "holdout_rmse",
        "oracle_key": "rmse_over_oracle", "oracle_from": "oracle_predict"}
    assert tiny_run(TYPED_CELL, root=str(root)) == {
        "flags": TINY[:2], "holdout": "holdout_aupr",
        "oracle_key": "aupr_over_oracle", "oracle_from": "oracle_score"}

    # the regression probe on top of the stand-in, in this copy too
    enter_regression_probe(root)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    (rmse,) = [m for m in bench["end_to_end"] if m["name"] == "holdout_rmse"]
    assert rmse["workloads"] == [STAND_IN_CELL, REGRESSION_CELL]
    after = hashes(root)
    assert [k for k in before if after.get(k) != before[k]] == [
        "BENCHMARK.json"]
    assert sorted(set(after) - set(before)) == sorted(STAND_IN_FILES + (
        "perfbench/generators/planted_regression_probe.py",
        "perfbench/configs/regression-probe.json",
        "perfbench/traffic/rf-reg-grid18-probe.json",
        "perfbench/generators/typed_planted_probe.py",
        "perfbench/configs/typed-probe.json",
        "perfbench/traffic/xgb-typed-probe.json"))


@pytest.mark.parametrize("found,listed", [
    (None, ["second"]), ({}, ["first", "second"]), ({"bound": 0.05}, None)],
    ids=["absent", "as-entered", "another-bound"])
def test_holdout_rmse_is_appended_once_and_then_listed(found, listed):
    """The first regression cell appends the entry; a later one appends its
    name, and only to the entry as the first one entered it."""
    bench = {"end_to_end": [] if found is None else [
        dict(HOLDOUT_RMSE, workloads=["first"], **found)]}
    if listed is None:
        with pytest.raises(AssertionError):
            append_holdout_rmse(bench, "second")
        return
    append_holdout_rmse(bench, "second")
    assert bench["end_to_end"] == [dict(HOLDOUT_RMSE, workloads=listed)]


def test_a_probe_s_files_carry_probe_and_the_benchmark_s_never_do():
    """So a real cell of a kind can never take a probe's file, nor a probe
    a real cell's."""
    assert all("probe" in f for f in os.listdir(LATER)
               if f != "__pycache__")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    names += [w["traffic"] for w in bench["workloads"]]
    for _, _, files in os.walk(os.path.join(ROOT, "perfbench")):
        names += [f for f in files if not f.endswith(".pyc")]
    assert not [n for n in names if "probe" in n]
