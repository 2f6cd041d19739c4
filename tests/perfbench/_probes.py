"""Entering cells into a temporary copy of the benchmark, as a later PR
enters them into the repo: new files and appended ``BENCHMARK.json``
entries, never an edit of a file that is there.

The probes kept in ``later/`` enter under names that carry ``probe`` (their
configurations, cells, traffic, generators and readers), and a real cell's
files never do, so a probe enters a tree that already holds a real cell of
its kind: the first regression cell brings ``planted_regression.py`` and
``rf-reg-grid18.json``, the probe ``planted_regression_probe.py`` and
``rf-reg-grid18-probe.json``.
"""
import hashlib
import json
import os
import shutil

from _child import ROOT

LATER = os.path.join(ROOT, "tests", "perfbench", "later")

#: the regression probe: its cell, and the metrics the cell appends its name
#: to (a forest cell on one chip, as ``dense500-rf-grid18``)
REGRESSION_CELL = "regression-probe-rf"
REGRESSION_METRICS = (
    "train_device_s", "tree_device_s", "peak_hbm_gib",
    "rf_grow_device_s", "rf_score_device_s", "rf_trees_grown", "rf_launches",
    "rf_hist_roofline", "rf_scored_rows")
#: the end-to-end entry the first regression cell appends, with its own
#: name on the list (the contract admits no empty one); its bound is
#: PERF.md section 2's
HOLDOUT_RMSE = {"name": "holdout_rmse", "unit": "RMSE", "better": "lower",
                "bound": 0.02, "source": "host_clock"}
#: the typed probe's cell
TYPED_CELL = "typed-probe-xgb"
#: the benchmark's own test files that hold BENCHMARK.json's entries
PINS = ("test_perfbench_contract.py", "test_perfbench_spans.py",
        "test_perfbench_host_metrics.py", "test_perfbench_rf_grid.py",
        "test_perfbench_rf_scored_rows.py")


def benchmark_paths(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)["paths"]


def hashes(root):
    """sha256 of BENCHMARK.json and of every file under the benchmark's
    ``paths``, by path under ``root``: what a run writes beside them (its
    outputs, a compile cache) is not the benchmark's."""
    paths = [os.path.join(root, "BENCHMARK.json")]
    for top in benchmark_paths(root):
        for base, dirs, files in os.walk(os.path.join(root, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            paths += [os.path.join(base, f) for f in files]
    out = {}
    for path in paths:
        with open(path, "rb") as fh:
            out[os.path.relpath(path, root)] = hashlib.sha256(
                fh.read()).hexdigest()
    return out


def copy_of_the_benchmark(tmp_path):
    """BENCHMARK.json and the directories of its ``paths`` in
    ``tmp_path``, the program beside them as in a checkout; returns
    ``tmp_path``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in benchmark_paths(ROOT):
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "transmogrifai_tpu"),
               tmp_path / "transmogrifai_tpu")
    return tmp_path


def enter(root, bench_edit, files: dict):
    """Add ``files`` (``{path under root: text}``) to the copy at ``root``
    and the entries ``bench_edit`` makes to its BENCHMARK.json; returns the
    hashes from before."""
    before = hashes(root)
    for rel, text in files.items():
        assert rel not in before, f"{rel} would edit a file that is there"
        (root / rel).write_text(text)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench_edit(bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return before


def enter_probe(root, bench_edit, files: dict):
    """``enter``, for a probe of ``later/``: each file it adds carries
    ``probe`` in its name, so none can be a real cell's."""
    for rel in files:
        assert "probe" in os.path.basename(rel), rel
    return enter(root, bench_edit, files)


def list_cell(bench, cell, *metrics):
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(cell)


def append_holdout_rmse(bench, cell):
    """The first regression cell appends ``holdout_rmse`` with its own name
    as the list; a later one finds the entry as the first left it and
    appends its name."""
    found = [m for m in bench["end_to_end"] if m["name"] == "holdout_rmse"]
    if not found:
        bench["end_to_end"].append(dict(HOLDOUT_RMSE, workloads=[cell]))
        return
    (entry,) = found
    assert {k: entry[k] for k in HOLDOUT_RMSE} == HOLDOUT_RMSE, entry
    entry["workloads"].append(cell)


def later(name):
    with open(os.path.join(LATER, name)) as f:
        return f.read()


def enter_regression_probe(root):
    """The probe as a later PR enters a regression cell: three new files,
    a configuration and a cell, ``holdout_rmse`` appended or its list
    extended, and the cell's name appended to the lists of the other
    metrics it reports."""
    config = json.loads(later("regression-probe.json"))

    def edit(bench):
        bench["configs"].append({
            "name": "regression-probe", "source": config["source"],
            "file": "perfbench/configs/regression-probe.json",
            "reduced": sorted(config["reduced"]),
            "why": "entered by a later PR: a regression label"})
        bench["workloads"].append({
            "name": REGRESSION_CELL, "config": "regression-probe",
            "traffic": "rf-reg-grid18-probe", "chips": 1,
            "why": "entered by a later PR"})
        append_holdout_rmse(bench, REGRESSION_CELL)
        list_cell(bench, REGRESSION_CELL, *REGRESSION_METRICS)

    return enter_probe(root, edit, {
        "perfbench/generators/planted_regression_probe.py": later(
            "planted_regression_probe.py"),
        "perfbench/configs/regression-probe.json": later(
            "regression-probe.json"),
        "perfbench/traffic/rf-reg-grid18-probe.json": later(
            "rf-reg-grid18-probe.json")})


def enter_typed_probe(root):
    """``typed-probe``: another schema, size and planted model, entered as
    three new files and entries."""
    config = json.loads(later("typed-probe.json"))

    def edit(bench):
        bench["configs"].append({
            "name": "typed-probe", "source": config["source"],
            "file": "perfbench/configs/typed-probe.json",
            "reduced": sorted(config["reduced"]),
            "why": "entered by a later PR: another schema, size and model"})
        bench["workloads"].append({
            "name": TYPED_CELL, "config": "typed-probe",
            "traffic": "xgb-typed-probe", "chips": 1,
            "why": "entered by a later PR"})
        list_cell(bench, TYPED_CELL, "train_device_s", "holdout_aupr",
                  "tree_device_s", "tree_hist_roofline", "peak_hbm_gib")

    return enter_probe(root, edit, {
        "perfbench/generators/typed_planted_probe.py": later(
            "typed_planted_probe.py"),
        "perfbench/configs/typed-probe.json": later("typed-probe.json"),
        "perfbench/traffic/xgb-typed-probe.json": later(
            "xgb-typed-probe.json")})

