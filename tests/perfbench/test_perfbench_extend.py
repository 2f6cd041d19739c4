"""A later PR adds a cell, a configuration, a traffic mix, a per-layer
metric, a generator and a mode as NEW files plus BENCHMARK.json entries,
and edits no file that is there.  Shown here in a temporary copy of the
benchmark: one of each is added, the new cell runs, and every file the
copy started with is byte-for-byte what it was.  The probes of ``later/``
enter under names of their own (``_probes.py``), so each of them enters a
tree that already holds a real cell of its kind as well as one that holds
none: ``test_perfbench_second_cell.py`` runs the tests here that run no cell
on a copy that holds a first regression cell.
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _child import (RESULT_KEYS, ROOT, TINY, child_env,  # noqa: E402
                    run_cell)
from _probes import (LATER, PINS, REGRESSION_CELL,  # noqa: E402
                     TYPED_CELL, copy_of_the_benchmark, enter,
                     enter_probe, enter_regression_probe, enter_typed_probe,
                     hashes, later, list_cell)

NEW_MODE = '''"""Mode ``matmul_loop``: a stand-in for a later PR's mode (say an
open-loop server): it never trains, it runs one jitted product per step."""
import time


def run(ctx):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(ctx.df.drop(columns=["label"]).to_numpy())
    step = jax.jit(lambda a: (a.T @ a).sum())
    step(x).block_until_ready()
    ctx.setup_done()
    t0, walls = time.perf_counter(), []
    while time.perf_counter() - t0 < ctx.args.seconds:
        t = time.perf_counter()
        step(x).block_until_ready()
        walls.append(time.perf_counter() - t)
    return {"correct": True, "problems": [], "attempted": len(walls),
            "failed": 0,
            "end_to_end": {"train_s": sorted(walls)[len(walls) // 2],
                           "holdout_aupr": 1.0},
            "sources": {"steps": len(walls), "trace": None}}
'''

NEW_GENERATOR = '''"""Generator ``uniform_frame``: uniform columns, a coin for a label."""
import numpy as np


def generate(rows, cols, seed, low=0.0):
    import pandas as pd

    rng = np.random.default_rng(seed)
    df = pd.DataFrame(rng.uniform(low, 1.0, (rows, cols)).astype("float32"),
                      columns=[f"f{j}" for j in range(cols)])
    df.insert(0, "label", (rng.random(rows) < 0.5).astype("float32"))
    return df, np.zeros(cols, np.float32)
'''

NEW_METRIC = '''"""Steps the window completed (a later PR's counter)."""
LAYER = "front end"
UNIT = "count"
MOVES = "train_s"


def read(sources):
    return sources.get("steps")
'''


@pytest.mark.parametrize("cell,traffic", [
    # the ids are the cells' and mixes' names before they took ``-probe``
    pytest.param("dense500-rf-probe", "rf-pairs-probe",
                 id="dense500-rf-rf-pairs"),
    pytest.param("dense500-linear-probe", "lr-grid-full-train-probe",
                 id="dense500-linear-lr-grid-full-train")])
def test_a_later_cell_is_a_data_file_and_an_entry(cell, traffic, tmp_path):
    """The cells ``dense500-rf`` and ``dense500-linear`` wait under
    PERF.md's Open questions; their mixes are kept in ``later/``.  The PR
    that enters one adds the data file (for the LR cell its reader too) and
    entries, and the cell's path (RF grid chunks; vectorizer and
    SanityChecker fit with the LR grid, ``full_train``) runs end to end.
    Entered here as probes, so that a real cell of either name, or either
    mix copied under ``perfbench/``, leaves this test as it is."""
    linear = cell == "dense500-linear-probe"
    files = {f"perfbench/traffic/{traffic}.json": later(traffic + ".json")}
    if linear:
        files["perfbench/metrics/linear_device_s_probe.py"] = later(
            "linear_device_s_probe.py")

    def edit(bench):
        bench["workloads"].append({
            "name": cell, "config": "dense500-binary", "traffic": traffic,
            "chips": 1, "why": "entered by a later PR"})
        # a metric that exists only in some cells lists them: the new cell
        # puts its name on the lists of the metrics it reports, its label
        # kind's quality metric among them
        list_cell(bench, cell, "train_s", "holdout_aupr", "selector_s",
                  "drain_s")
        if linear:
            bench["per_layer"].append({
                "name": "linear_device_s_probe", "unit": "s",
                "better": "lower", "source": "device_trace",
                "layer": "linear solver", "moves": "train_s",
                "workloads": [cell]})

    before = enter_probe(copy_of_the_benchmark(tmp_path), edit, files)
    out, last = run_cell(cell, "--allow-cpu", *TINY, root=str(tmp_path),
                         cache_dir=tmp_path / "jax_cache")
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"train_s", "holdout_aupr", "setup_s"}
    if not linear:
        assert "rf_grid_chunk" in out.stdout
    else:
        assert '"RealVectorizer:fit"' in out.stdout
        assert '"SanityChecker:fit"' in out.stdout
        sys.path.insert(0, ROOT)
        from perfbench import spec

        names = [m["name"] for m in spec.load_cell(
            cell, root=str(tmp_path))["per_layer"]]
        assert "linear_device_s_probe" in names
    after = hashes(tmp_path)
    assert [k for k in before if after.get(k) != before[k]] == [
        "BENCHMARK.json"]


def test_the_typed_generator_plants_one_model_and_owns_its_oracle():
    import importlib.util

    import numpy as np

    file = importlib.util.spec_from_file_location(
        "typed_planted_probe", os.path.join(LATER, "typed_planted_probe.py"))
    typed = importlib.util.module_from_spec(file)
    file.loader.exec_module(typed)
    sys.path.insert(0, ROOT)
    from perfbench.reference import oracle

    a, planted_a = typed.generate(20_000, 15, 3)
    b, planted_b = typed.generate(20_000, 15, 3)
    c, planted_c = typed.generate(500, 15, 2147483900)
    assert a.equals(b) and not a.head(500).equals(c)
    assert planted_a["intercept"] == planted_c["intercept"]   # one model
    assert all((x == y).all() for x, y in zip(planted_a["cats"],
                                              planted_c["cats"]))
    assert list(a.columns) == ["label"] + [f"r{j}" for j in range(6)] + [
        f"i{j}" for j in range(4)] + [f"c{j}" for j in range(5)]
    assert 0.04 <= a["label"].mean() <= 0.06
    # numerics missing in blocks, never-null counts, pick-lists with nulls
    block = a[["r0", "r1", "r2"]].isna()
    assert 0.27 <= block["r0"].mean() <= 0.33
    assert (block["r0"] == block["r1"]).all() and (block["r1"]
                                                   == block["r2"]).all()
    assert a["i3"].dtype.kind == "i" and not a["i3"].isna().any()
    assert [a[f"c{j}"].nunique() <= card for j, card in
            enumerate((3, 8, 40, 300, 5000))] == [True] * 5
    assert a["c4"].nunique() > 300 and 0.03 <= a["c4"].isna().mean() <= 0.07
    # X @ beta has no matrix to take: the frame holds strings and NaN
    with pytest.raises(ValueError):
        a.drop(columns=["label"]).to_numpy(np.float32)
    z = typed.oracle_score(a, planted_a)
    assert z.dtype == np.float64 and z.shape == (20_000,)
    assert np.isfinite(z).all()
    # the planted logit ranks far above chance (AuPR = the positives' share)
    assert oracle.aupr(a["label"].to_numpy(), z) > 5 * a["label"].mean()
    with pytest.raises(ValueError):
        typed.generate(100, 16, 3)


def test_a_configuration_with_another_schema_is_new_files_and_entries(
        tmp_path):
    """``typed-probe``: nullable ``Real``, ``Integral`` and ``PickList``
    columns, its own rows, columns and hold-out, a planted model that is not
    linear in the raw columns (``later/typed_planted_probe.py`` and its
    ``oracle_score``).  It enters the copy as three new files and entries;
    every rule of the contract's test file then holds on the copy, and the
    cell rehearses ``correct`` on the CPU."""
    cell = TYPED_CELL
    before = enter_typed_probe(copy_of_the_benchmark(tmp_path))
    assert any(k.startswith("tests/perfbench/") for k in before)

    # (a) the contract's rules, as its own test file states them, on the copy
    cache = tmp_path / "jax_cache"
    rules = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", os.path.join("tests", "perfbench",
                                        "test_perfbench_contract.py")],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=child_env(cache))
    assert rules.returncode == 0, rules.stdout[-3000:] + rules.stderr[-2000:]
    for held in ("test_config_entry[typed-probe]",
                 f"test_cell_entry_and_files[{cell}]",
                 "test_the_cuts_a_configuration_states_are_the_mix_s_own_"
                 f"values[{cell}]",
                 "test_config_entry[dense500-binary]", "test_cells_and_chips",
                 "test_every_file_of_the_benchmark_serves_a_cell"):
        assert f"{held} PASSED" in rules.stdout, held
    assert " failed" not in rules.stdout and " error" not in rules.stdout

    # (b) the cell, rehearsed: typed columns in, the generator's own oracle
    out, last = run_cell(cell, "--allow-cpu", "--rows", "3000", trace="1",
                         root=str(tmp_path), cache_dir=cache)
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] == 4
    assert set(last["metrics"]) == {"compile_s", "programs", "peak_host_gib"}
    assert 'oracle_from="oracle_score"' in out.stdout
    assert last["compared"]["aupr_over_oracle"][0] < 0.01
    assert last["compared"]["tree_scorer_diff"][0] <= 1e-5
    # the typed columns went through their own fitted vectorizers
    for stage in ("RealVectorizer", "IntegralVectorizer", "OneHotVectorizer"):
        assert f'"{stage}:substitute"' in out.stdout
    (traced,) = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("[perfbench] traced wall_s=")]
    memo = json.loads(traced.split(" memo=")[1].split(" selector_cols=")[0])
    # the boosted group made its bundling plan (a plan that declines counts
    # too; the bundled width itself has no counter yet: PERF.md, section 7)
    assert memo["efb"]["builds"] >= 1
    # the roofline's width is the selector's input vector (pivots and null
    # flags in), not the 15 raw columns
    width = int(traced.split(" selector_cols=")[1].split(" ")[0])
    assert 60 <= width <= 15 * 22

    after = hashes(tmp_path)
    assert [k for k in before if after.get(k) != before[k]] == [
        "BENCHMARK.json"]


def test_the_regression_generator_plants_one_model_and_owns_its_oracle():
    import importlib.util

    import numpy as np

    file = importlib.util.spec_from_file_location(
        "planted_regression_probe",
        os.path.join(LATER, "planted_regression_probe.py"))
    gen = importlib.util.module_from_spec(file)
    file.loader.exec_module(gen)
    sys.path.insert(0, ROOT)
    from perfbench.reference import oracle

    a, planted_a = gen.generate(20_000, 30, 3)
    b, _ = gen.generate(20_000, 30, 3)
    c, planted_c = gen.generate(20_000, 30, 2147483900, mean=1998.0)
    assert a.equals(b) and not a.head(500).equals(c.head(500))
    assert (planted_a["beta"] == planted_c["beta"]).all()      # one model
    assert (planted_a["beta"] != 0).sum() == 15
    assert list(a.columns) == ["label"] + [f"f{j}" for j in range(30)]
    assert a["label"].dtype == np.float32
    # the oracle is the planted mean: its RMSE is the noise's sd
    for frame, planted, mean in ((a, planted_a, 0.0), (c, planted_c, 1998.0)):
        best = oracle.rmse(frame["label"].to_numpy(),
                           gen.oracle_predict(frame, planted))
        assert best == pytest.approx(2.0, rel=0.02)
        assert frame["label"].mean() == pytest.approx(mean, abs=0.1)
        assert frame["label"].std() > 1.5 * best


def test_a_regression_configuration_is_new_files_and_entries(tmp_path):
    """The regression probe enters the copy as new files and appended
    entries and names only, on a tree with no regression cell (it appends
    ``holdout_rmse``) or on one that holds a regression cell already (it
    appends its name to the entry's list); every rule of the contract's test
    file and every pin of the benchmark's own tests then holds on the copy,
    ``holdout_rmse`` listing each regression cell."""
    before = enter_regression_probe(copy_of_the_benchmark(tmp_path))
    rules = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-k", "not rehearsal",
         *(os.path.join("tests", "perfbench", f) for f in PINS)],
        capture_output=True, text=True, timeout=600, cwd=tmp_path,
        env=child_env(tmp_path / "jax_cache"))
    assert rules.returncode == 0, rules.stdout[-3000:] + rules.stderr[-2000:]
    for held in ("test_config_entry[regression-probe]",
                 f"test_cell_entry_and_files[{REGRESSION_CELL}]",
                 "test_the_cuts_a_configuration_states_are_the_mix_s_own_"
                 f"values[{REGRESSION_CELL}]",
                 "test_a_cell_reports_the_quality_metric_of_its_label_kind",
                 "test_holdout_rmse_waits_for_its_first_cell_with_its_bound",
                 "test_every_file_of_the_benchmark_serves_a_cell",
                 "test_the_twelve_entries_stand_at_the_end_and_move_the_"
                 "mesh_cell_s_wall",
                 "test_the_eight_entries_stand_last_in_the_table_s_order",
                 "test_new_entries_stand_at_the_end_and_touch_nothing_that_"
                 "was_there",
                 "test_the_entry_stands_after_every_accepted_one_in_their_"
                 "order"):
        assert f"{held} PASSED" in rules.stdout, held
    assert " failed" not in rules.stdout and " error" not in rules.stdout
    after = hashes(tmp_path)
    assert [k for k in before if after.get(k) != before[k]] == [
        "BENCHMARK.json"]


def test_the_regression_probe_runs_correct_through_the_regression_selector(
        tmp_path):
    """The probe's cell, at its own shape: ``RegressionModelSelector`` with
    ``DataSplitter`` over upstream's 18-point forest-regressor grid, held to
    the planted mean, the NumPy walker and its quality bands, and reporting
    ``holdout_rmse`` in place of ``holdout_aupr``.  The probe enters beside
    any regression cell the tree holds: its files and names are its own."""
    enter_regression_probe(copy_of_the_benchmark(tmp_path))
    out, last = run_cell(REGRESSION_CELL, "--allow-cpu", root=str(tmp_path),
                         cache_dir=tmp_path / "jax_cache")
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 18 * 3 + 1
    assert set(last["metrics"]) == {"holdout_rmse", "setup_s"}
    assert last["metrics"]["holdout_rmse"]["unit"] == "RMSE"
    compared = last["compared"]
    assert {"rmse_over_oracle", "tree_scorer_diff", "holdout_rmse",
            "cv_rmse.OpRandomForestRegressor",
            "cv_spread.OpRandomForestRegressor"} <= set(compared)
    assert not [k for k in compared if "aupr" in k]
    ratio, floor = compared["rmse_over_oracle"]
    assert ratio >= floor
    # the grid's depths and gates grew forests that score apart
    spread, least = compared["cv_spread.OpRandomForestRegressor"]
    assert spread >= least > 0
    assert compared["tree_scorer_diff"][0] <= compared["tree_scorer_diff"][1]
    assert 'oracle_from="oracle_predict"' in out.stdout
    assert "rows=12000 cols=30" in out.stdout


COLD_MODE = '''"""Mode ``train_loop_cold``: ``train_loop`` with JAX's in-memory
caches dropped where set-up ends, so that the first train of the window has
to build its programs again (from the persistent cache or the compiler): a
stand-in for a program change that re-jits in every train."""
from perfbench.modes import train_loop


def run(ctx):
    warm_setup_done = ctx.setup_done

    def setup_done():
        import jax

        jax.clear_caches()
        warm_setup_done()

    ctx.setup_done = setup_done
    return train_loop.run(ctx)
'''


def test_one_chip_cell_on_the_mesh_cell_s_mix_fails_on_a_program_in_the_window(
        tmp_path):
    """``mesh4-trees`` may build programs inside its window (its
    configuration says why and how many).  The allowance is the mesh
    configuration's: a one-chip cell on the very same mix is held to zero."""
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "tree-groups.json")) as f:
        mix = json.load(f)
    mix["mode"] = "train_loop_cold"

    def edit(bench):
        bench["workloads"].append({
            "name": "dense500-trees-cold", "config": "dense500-binary",
            "traffic": "tree-groups-cold", "chips": 1,
            "why": "the mesh cell's mix on one chip, re-jitting"})

    enter(copy_of_the_benchmark(tmp_path), edit, {
        "perfbench/modes/train_loop_cold.py": COLD_MODE,
        "perfbench/traffic/tree-groups-cold.json": json.dumps(mix)})
    out, last = run_cell("dense500-trees-cold", "--allow-cpu", *TINY,
                         root=str(tmp_path), cache_dir=tmp_path / "jax_cache")
    assert out.returncode == 0, out.stderr[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    assert "programs compiled or loaded inside the window, 0 allowed" in (
        out.stdout)


def test_new_files_and_entries_are_enough(tmp_path):
    copy_of_the_benchmark(tmp_path)
    before = hashes(tmp_path)

    pb = tmp_path / "perfbench"
    (pb / "modes" / "matmul_loop.py").write_text(NEW_MODE)
    (pb / "generators" / "uniform_frame.py").write_text(NEW_GENERATOR)
    (pb / "metrics" / "steps_done.py").write_text(NEW_METRIC)
    with open(pb / "configs" / "dense500-binary.json") as f:
        cfg = json.load(f)
    cfg.update(name="uniform64", source="a later PR's own source",
               rows=2000, holdout_rows=100,
               generator={"name": "uniform_frame", "params": {"low": 0.5}})
    cfg["schema"]["predictors"]["count"] = 64
    (pb / "configs" / "uniform64.json").write_text(json.dumps(cfg))
    (pb / "traffic" / "matmul-steady.json").write_text(json.dumps(
        {"mode": "matmul_loop", "why": "a new mix is a data file"}))
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "uniform64", "source": cfg["source"],
        "file": "perfbench/configs/uniform64.json", "reduced": [],
        "why": "shows a configuration is a file"})
    bench["workloads"].append({
        "name": "uniform64-matmul", "config": "uniform64",
        "traffic": "matmul-steady", "chips": 1,
        "why": "shows a cell is an entry"})
    bench["per_layer"].append({
        "name": "steps_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "front end",
        "moves": "train_s", "workloads": ["uniform64-matmul"]})
    list_cell(bench, "uniform64-matmul", "train_s", "holdout_aupr")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cache = tmp_path / "jax_cache"
    out, last = run_cell("uniform64-matmul", "--allow-cpu", seconds="1",
                         root=str(tmp_path), cache_dir=cache)
    assert out.returncode == 0, out.stderr[-3000:]
    assert RESULT_KEYS <= set(last) and last["correct"] is True
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_s", "holdout_aupr", "setup_s"}
    assert "rows=2000 cols=64" in out.stdout

    after = hashes(tmp_path)
    changed = [k for k in before if after.get(k) != before[k]]
    assert changed == ["BENCHMARK.json"]  # entries added, no file edited
    added = sorted(set(after) - set(before))
    assert added == ["perfbench/configs/uniform64.json",
                     "perfbench/generators/uniform_frame.py",
                     "perfbench/metrics/steps_done.py",
                     "perfbench/modes/matmul_loop.py",
                     "perfbench/traffic/matmul-steady.json"]

    # and the new per-layer metric is found by its file name
    sys.path.insert(0, ROOT)
    from perfbench import spec

    loaded = spec.load_cell("uniform64-matmul", root=str(tmp_path))
    names = [m["name"] for m in loaded["per_layer"]]
    assert "steps_done" in names and "collective_s" not in names
    reader = spec.load_module("metrics", "steps_done", root=str(tmp_path))
    assert reader.read({"steps": 7}) == 7
