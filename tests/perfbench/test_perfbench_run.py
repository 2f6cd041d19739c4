"""perfbench/run.py end to end on the CPU at a tiny shape.

What tier-1 can pin without the chip: every cell runs through the public
entry points and ends in a last line with exactly the contract's keys, a
CPU is refused unless the rehearsal flag is given (and then says so), the
mesh cell runs on four virtual devices, and the command fails without a
result away from the program.  Times printed by these runs are CPU times
and mean nothing.
"""
import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import RESULT_KEYS, ROOT, TINY, run_cell, tiny_run  # noqa: E402

sys.path.insert(0, ROOT)
from perfbench import checks, spec  # noqa: E402

BENCH = spec.load_benchmark()
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("perfbench_jax_cache")


def _check_last_line(last, cell: str, traced: bool, devices: int = 1):
    assert last is not None, "the last line of stdout is no JSON object"
    want = (RESULT_KEYS | {"rehearsal", "compared"}
            | ({"breakdown"} if traced else set()))
    assert set(last) == want
    assert last["rehearsal"] is True
    # each number compared beside its limit, as the line's last key
    assert list(last)[-1] == "compared"
    assert {tiny_run(cell)["oracle_key"], "tree_scorer_diff",
            "window_programs"} <= set(last["compared"])
    for value, limit in last["compared"].values():
        assert value is not None and limit is not None
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    device = last["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert device["platform"] == "cpu" and device["count"] == devices
    loaded = spec.load_cell(cell)
    group = loaded["per_layer"] if traced else loaded["end_to_end"]
    units = {m["name"]: m["unit"] for m in group}
    assert last["metrics"], "no metric was reported"
    for name, m in last["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == units[name]
        assert isinstance(m["value"], float)
    return units


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_cell_runs_tiny_on_cpu_and_prints_the_contract_s_line(cell, cache_dir):
    tiny = tiny_run(cell)
    out, last = run_cell(cell, "--allow-cpu", *tiny["flags"],
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    units = _check_last_line(last, cell, traced=False)
    # every end-to-end metric of the cell, but for the one taken from the
    # device trace: never a CPU number under a device metric's name
    device = {m["name"] for m in spec.load_cell(cell)["end_to_end"]
              if m["source"] == "device_trace"}
    assert device == {"train_device_s"}
    assert set(last["metrics"]) == set(units) - device
    assert last["metrics"][tiny["holdout"]]["value"] > 0
    assert last["metrics"]["setup_s"]["value"] > 0
    # such a cell traces every train of its window, untraced run or not
    assert "[perfbench] trace file=" in out.stdout
    assert "[perfbench] samples train_s=[" in out.stdout
    assert "x64=false" in out.stdout
    assert f'cache_dir="{cache_dir}"' in out.stdout
    assert "[perfbench] window trains=" in out.stdout
    # the generator's own oracle; planted_linear has none: the float32
    # matrix by beta
    assert f'oracle_from="{tiny["oracle_from"]}"' in out.stdout


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_a_tiny_run_asks_what_the_cell_s_configuration_gives(cell):
    """The test above asks of each cell what its label kind and generator
    give, and no cell is named there; this runs no cell, so a copy that
    holds a new one runs it too."""
    tiny = tiny_run(cell)
    loaded = spec.load_cell(cell)
    config = loaded["config"]
    holdouts = {k.holdout for k in checks.LABEL_KINDS.values()}
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert reported & holdouts == {tiny["holdout"]}
    generator = spec.load_module("generators", config["generator"]["name"])
    if tiny["oracle_from"] == "X @ beta":
        assert config["problem"] == "binary"
    else:
        assert callable(getattr(generator, tiny["oracle_from"]))
    # a schema that lists its columns is drawn at its own width
    assert ("--cols" in tiny["flags"]) == (
        "columns" not in config["schema"]["predictors"])


def test_traced_run_prints_per_layer_metrics_and_a_breakdown(cache_dir):
    out, last = run_cell("dense500-xgb", "--allow-cpu", *TINY, trace="1",
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    _check_last_line(last, "dense500-xgb", traced=True)
    # host-side metrics are read on any platform; device-trace metrics are
    # left out of a CPU rehearsal (never a CPU number under a device name)
    assert {"compile_s", "programs", "peak_host_gib"} == set(last["metrics"])
    assert last["metrics"]["peak_host_gib"]["value"] > 0.1
    assert last["device"]["busy_s"] > 0
    assert last["device"]["window_s"] >= last["device"]["busy_s"] * 0.0
    bd = last["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert any("ModelSelector" in name or "sweep" in name
               for name, _ in bd["idle_gaps"])


def test_mesh_cell_runs_on_four_virtual_devices(cache_dir):
    out, last = run_cell("mesh4-trees", "--allow-cpu", *TINY, devices=4,
                         cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    units = _check_last_line(last, "mesh4-trees", traced=False, devices=4)
    # the wall-clock is this cell's: the four-chip machine holds it steady
    assert set(last["metrics"]) == set(units) == {
        "train_s", "holdout_aupr", "setup_s"}
    assert last["metrics"]["train_s"]["value"] > 0
    assert "[perfbench] trace file=" not in out.stdout
    assert "gbt_chain_rounds_sharded" in out.stdout
    assert "rf_grid_chunk_sharded" in out.stdout
    # the mesh path builds programs anew in every warm train; the run is
    # correct only because the mesh configuration allows that many
    (window,) = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("[perfbench] train0 ")]
    built = json.loads(window.split("compile=")[1].split(" problems=")[0])
    cap = spec.load_cell("mesh4-trees")["config"]["window_programs_max"]
    assert 0 < built["programs"] <= cap["value"]


def test_traced_mesh_run_prints_the_host_stage_metrics(cache_dir):
    """The stage walls and counters move the wall-clock ``train_s``, which
    only the mesh cell reports, so only its traced run prints them."""
    out, last = run_cell("mesh4-trees", "--allow-cpu", *TINY, devices=4,
                         trace="1", cache_dir=cache_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    _check_last_line(last, "mesh4-trees", traced=True, devices=4)
    assert set(last["metrics"]) == {
        "vectorize_s", "sanity_s", "selector_s", "drain_s",
        "window_programs", "compile_s", "programs", "peak_host_gib"}
    cap = spec.load_cell("mesh4-trees")["config"]["window_programs_max"]
    assert 0 < last["metrics"]["window_programs"]["value"] <= cap["value"]
    assert last["metrics"]["selector_s"]["value"] > 0


def test_mesh_cell_refuses_fewer_devices_than_its_chips(cache_dir):
    out, last = run_cell("mesh4-trees", "--allow-cpu", *TINY, devices=1,
                         cache_dir=cache_dir)
    assert out.returncode != 0 and last is None
    assert "needs 4 chips" in out.stderr


def test_refuses_a_cpu_and_prints_no_result(cache_dir):
    out, last = run_cell("dense500-xgb", cache_dir=cache_dir)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert last is None and '"correct"' not in out.stdout


def test_unknown_workload_prints_no_result(cache_dir):
    out, last = run_cell("no-such-cell", "--allow-cpu", cache_dir=cache_dir)
    assert out.returncode != 0 and last is None


def test_fails_without_a_result_away_from_the_program(tmp_path, cache_dir):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: no program to measure, so no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out, last = run_cell("dense500-xgb", "--allow-cpu", *TINY,
                         root=str(tmp_path), cache_dir=cache_dir)
    assert out.returncode != 0 and last is None
    assert '"correct"' not in out.stdout
