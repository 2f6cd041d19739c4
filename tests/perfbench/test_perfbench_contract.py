"""BENCHMARK.json and the data files of perfbench/ against the contract.

No JAX, no subprocess: every file a cell names loads, every name and unit
is made of the allowed characters, each per-layer metric's reader agrees
with its BENCHMARK.json entry, and the generator copy is pinned.
"""
import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import spec  # noqa: E402

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
#: what a `reduced` key may never name (the contract's widths)
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "experts_per")
#: the accepted cells and their chips, by name: neither can vanish.  Later
#: cells enter beside them and are held by the rules below, not by a list.
ACCEPTED = {"dense500-xgb": 1, "mesh4-trees": 4}
#: the accepted configurations' sizes, by name (columns, rows, hold-out
#: rows): a later configuration is held by the rules of `test_config_entry`
PINNED_SIZES = {"dense500-binary": (500, 250_000, 20_000),
                "dense500-binary-mesh4": (500, 250_000, 20_000)}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench", "tests/perfbench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    # the full check of 24 cells fits the driver's budget at this length
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names)), names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert spec.NAME.match(entry["name"])
    assert 1 <= len(entry["source"]) <= 200 and "\n" not in entry["source"]
    assert 1 <= len(entry["why"]) <= 200
    assert entry["file"].startswith("perfbench/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert spec.NAME.match(key)
        assert not key.endswith(("_dim", "_rank"))
        assert not any(w in key for w in WIDTH_WORDS), key
        # every cut is a key of the file and says what the source has
        assert key in cfg and key in cfg["reduced"], key
        assert {"source", "here", "why"} <= set(cfg["reduced"][key])
    # the file states what its source publishes; never cut: the published
    # width, bins, folds.  Rows are cut only as `reduced` says, from the
    # published count; the hold-out is the benchmark's own and says so
    published = cfg["published"]
    assert {"columns", "rows", "max_bins", "num_folds"} <= set(published)
    assert cfg["schema"]["predictors"]["count"] == published["columns"]
    assert cfg["max_bins"] == published["max_bins"]
    assert cfg["validator"]["num_folds"] == published["num_folds"]
    if "rows" in entry["reduced"]:
        assert cfg["reduced"]["rows"]["source"] == published["rows"]
        assert cfg["rows"] < published["rows"]
    else:
        assert cfg["rows"] == published["rows"]
    assert cfg["holdout_rows"] > 0 and "holdout_rows" in cfg["assumed"]
    if entry["name"] in PINNED_SIZES:
        assert (cfg["schema"]["predictors"]["count"], cfg["rows"],
                cfg["holdout_rows"]) == PINNED_SIZES[entry["name"]]
        assert (published["columns"], published["rows"],
                published["max_bins"], published["num_folds"]) == (
                    500, 1_000_000, 32, 3)
    assert entry["name"] in {w["config"] for w in BENCH["workloads"]}


def test_the_accepted_configurations_are_there():
    assert set(PINNED_SIZES) <= {c["name"] for c in BENCH["configs"]}


def test_config_files_are_not_shared():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(sources) == len(set(sources))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert spec.NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    loaded = spec.load_cell(cell["name"])
    assert loaded["config"]["chips"] == cell["chips"]
    traffic = loaded["traffic"]
    assert {"mode", "entry", "models_and_parameters", "checks",
            "why"} <= set(traffic)
    assert os.path.isfile(os.path.join(
        ROOT, "perfbench", "modes", traffic["mode"] + ".py"))
    assert os.path.isfile(os.path.join(
        ROOT, "perfbench", "generators",
        loaded["config"]["generator"]["name"] + ".py"))
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    e2e = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded["per_layer"]


def test_cells_and_chips():
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}
    assert set(chips.values()) <= {1, 4}
    assert ACCEPTED.items() <= chips.items()
    four = sum(c == 4 for c in chips.values())
    assert four <= max(1, len(chips) // 4)


#: the mixes of the two cells that wait under PERF.md's Open questions, kept
#: here under probe names and entered in a temporary copy
#: (test_perfbench_extend.py)
LATER = os.path.join(ROOT, "tests", "perfbench", "later")


def _traffic(name, folder=os.path.join(ROOT, "perfbench", "traffic")):
    with open(os.path.join(folder, name + ".json")) as f:
        return json.load(f)


def test_never_cut_depths_are_in_the_traffic_files():
    from transmogrifai_tpu.models import OpXGBoostClassifier

    # XGB runs at the estimator's defaults, which must stay upstream's
    xgb = OpXGBoostClassifier(num_round=8)
    assert (xgb.max_depth, xgb.max_bins) == (10, 32)
    for mix in (_traffic("tree-groups"), _traffic("rf-pairs-probe", LATER)):
        (rf,) = [m for m in mix["models_and_parameters"]
                 if m["estimator"] == "OpRandomForestClassifier"]
        assert rf["grid"]["max_depth"] == [12]
    lr = _traffic("lr-grid-full-train-probe", LATER)
    assert lr["models_and_parameters"][0]["grid"] == {
        "reg_param": [0.001, 0.01, 0.1, 0.2],
        "elastic_net_param": [0.1, 0.5]}
    assert lr["checks"]["oracle_gap_max"] == 0.01


def test_every_file_of_the_benchmark_serves_a_cell():
    """No mix, configuration, mode, generator or reader that no cell of
    BENCHMARK.json names (``_``-prefixed helpers apart)."""
    def stems(kind):
        return {os.path.splitext(f)[0]
                for f in os.listdir(os.path.join(ROOT, "perfbench", kind))
                if not f.startswith("_")}

    loaded = [spec.load_cell(c) for c in CELLS]
    assert stems("traffic") == {w["traffic"] for w in BENCH["workloads"]}
    assert stems("configs") == {c["name"] for c in BENCH["configs"]}
    assert stems("modes") == {c["traffic"]["mode"] for c in loaded}
    assert stems("generators") == {c["config"]["generator"]["name"]
                                   for c in loaded}
    assert stems("metrics") == {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("cell", CELLS)
def test_the_cuts_a_configuration_states_are_the_mix_s_own_values(cell):
    """``reduced`` names keys of the configuration's file; the selector is
    built from the traffic file.  One source of truth: they agree."""
    loaded = spec.load_cell(cell)
    cfg, traffic = loaded["config"], loaded["traffic"]
    for key in loaded["config_entry"]["reduced"]:
        assert cfg[key] == cfg["reduced"][key]["here"], key
    models = traffic["models_and_parameters"]
    assert len(models) == cfg["sweep_groups"]
    seen = set()
    for m in models:
        if m["estimator"] == "OpXGBoostClassifier":
            assert m["args"]["num_round"] == cfg["xgb_num_round"]
            assert m["grid"]["min_child_weight"] == cfg[
                "xgb_min_child_weight"]
            seen |= {"xgb_num_round", "xgb_min_child_weight"}
        elif m["estimator"] in ("OpRandomForestClassifier",
                                "OpRandomForestRegressor"):
            assert m["args"]["num_trees"] == cfg["rf_num_trees"]
            seen |= {"rf_num_trees"}
    # and the file states no cut of an estimator its cells do not run
    stated = {k for k in cfg if k.startswith(("xgb_", "rf_"))}
    assert stated == seen
    assert (cfg.get("mesh") is not None) == (cfg["chips"] == 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BENCH["end_to_end"]
    allowed = ({"name", "unit", "better", "bound", "source"} if e2e else
               {"name", "unit", "better", "source", "layer", "moves"})
    assert set(metric) - {"workloads"} == allowed
    assert spec.NAME.match(metric["name"])
    assert spec.UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in spec.SOURCES
    # a list, where given, names cells: never an empty one
    assert metric.get("workloads", CELLS)
    for w in metric.get("workloads", []):
        assert w in CELLS
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200
        reader = spec.load_module("metrics", metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        # a reader that finds nothing to read returns nothing
        assert reader.read({}) is None
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_a_per_layer_metric_is_reported_only_where_the_metric_it_moves_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert _cells_of(m) <= _cells_of(e2e[m["moves"]]), m["name"]
    for cell in CELLS:  # and every cell has a layer metric for each
        loaded = spec.load_cell(cell)
        moved = {m["moves"] for m in loaded["per_layer"]}
        assert moved == ({m["name"] for m in loaded["end_to_end"]}
                         - {"holdout_aupr", "holdout_rmse"}), cell


def test_wall_clock_on_the_whole_host_and_device_seconds_on_one_chip():
    """The driver's check read a one-chip ``train_s`` spread of 9.3 % in one
    set and 0.8 % in the next (the host's cores are shared): no bound holds
    both.  The wall-clock is bounded where a cell holds the whole host, the
    chip's own seconds where it does not (PERF.md, sections 2 and 6)."""
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert {chips[c] for c in _cells_of(e2e["train_s"])} == {4}
    assert {chips[c] for c in _cells_of(e2e["train_device_s"])} == {1}
    assert e2e["train_s"]["source"] == "host_clock"
    assert e2e["train_device_s"]["source"] == "device_trace"
    assert "workloads" not in e2e["setup_s"]
    # the quality metrics list the cells of their label kind: the accepted
    # binary cells first, later cells appended
    assert e2e["holdout_aupr"]["workloads"][:3] == [
        "dense500-xgb", "mesh4-trees", "dense500-rf-grid18"]


def test_a_cell_reports_the_quality_metric_of_its_label_kind():
    """A binary cell is held to ``holdout_aupr``, a regression cell to
    ``holdout_rmse``, and none to both."""
    from perfbench import checks

    for cell in CELLS:
        loaded = spec.load_cell(cell)
        e2e = {m["name"] for m in loaded["end_to_end"]}
        assert e2e & {"holdout_aupr", "holdout_rmse"} == {
            checks.label_kind(loaded["config"]).holdout}, cell


def test_holdout_rmse_waits_for_its_first_cell_with_its_bound():
    """The contract admits no empty ``workloads`` list, so ``holdout_rmse``
    enters ``end_to_end`` with the first regression cell, which appends the
    entry with its own name on the list.  Its bound, PERF.md section 2: five
    times the widest spread of the planted mean's own hold-out RMSE over two
    sets of 6 seeds at 51,630 rows (0.46 %), rounded to 0.02."""
    found = [m for m in BENCH["end_to_end"] if m["name"] == "holdout_rmse"]
    regression = [c for c in CELLS
                  if spec.load_cell(c)["config"]["problem"] == "regression"]
    if not regression:
        assert found == []
        return
    (rmse,) = found
    assert (rmse["unit"], rmse["better"], rmse["source"], rmse["bound"]) == (
        "RMSE", "lower", "host_clock", 0.02)
    assert sorted(rmse["workloads"]) == sorted(regression)


def test_setup_s_is_an_end_to_end_metric_with_the_contract_s_bound():
    (setup,) = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup["bound"] <= 0.1 and setup["better"] == "lower"


def test_files_under_paths_have_plain_names():
    import re

    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_traffic_files_are_data():
    for f in os.listdir(os.path.join(ROOT, "perfbench", "traffic")):
        assert f.endswith((".json", ".jsonl", ".toml", ".txt", ".csv")), f


# -- a typed schema, as data --------------------------------------------------

_FRAME = ["r0", "r1", "amount", "c0"]
_FAMILIES = [{"type": "Real", "prefix": "r", "count": 2},
             {"type": "Integral", "names": ["amount"]},
             {"type": "PickList", "prefix": "c", "count": 1}]


@pytest.mark.parametrize("predictors,want", [
    # the accepted configurations' form: every column is one type
    ({"type": "Real", "count": 4}, dict.fromkeys(_FRAME, "Real")),
    ({"count": 4, "columns": _FAMILIES},
     {"r0": "Real", "r1": "Real", "amount": "Integral", "c0": "PickList"}),
    # families that do not sum to `count`, leave a frame column out, name
    # one the frame lacks, or name one twice
    ({"count": 5, "columns": _FAMILIES}, "count says 5"),
    ({"count": 3, "columns": _FAMILIES[:2]}, "['c0'] have no family"),
    ({"count": 5, "columns": _FAMILIES + [{"type": "Text", "names": ["t"]}]},
     "['t'] are not in the frame"),
    ({"count": 4, "columns": _FAMILIES + [{"type": "Real", "names": ["r1"]}]},
     "twice")], ids=["one-type", "families", "count", "uncovered", "absent",
                     "twice"])
def test_schema_predictors_is_one_type_or_families_that_cover_the_frame(
        predictors, want):
    from perfbench.modes import train_loop

    if isinstance(want, dict):
        assert train_loop.predictor_types(_FRAME, predictors) == want
        return
    with pytest.raises(train_loop.CellFailure) as failure:
        train_loop.predictor_types(_FRAME, predictors)
    assert want in str(failure.value)


def test_train_loop_is_binary_only_and_says_what_another_label_needs():
    """Binary and regression labels have their selectors and quality
    metrics; a multi-class label has neither yet, and is told so."""
    from perfbench.modes import train_loop

    with pytest.raises(train_loop.CellFailure) as failure:
        train_loop.selector_workflow(None, None, None,
                                     {"problem": "multiclass"}, {}, 1)
    assert "quality metric beside holdout_aupr and holdout_rmse" in str(
        failure.value)


@pytest.mark.parametrize("problem,splitter", [
    ("regression", "DataBalancer"), ("binary", "DataSplitter")])
def test_a_selector_takes_the_splitter_upstream_gives_its_label_kind(
        problem, splitter):
    from perfbench.modes import train_loop

    config = {"problem": problem, "validator": {"splitter": splitter}}
    with pytest.raises(train_loop.CellFailure) as failure:
        train_loop.selector_workflow(None, None, None, config, {}, 1)
    assert f"the configuration says {splitter!r}" in str(failure.value)


# -- the generator copy -----------------------------------------------------

def _frame_hash(df) -> str:
    return hashlib.sha256(df.to_numpy().tobytes()).hexdigest()


def test_generator_copy_is_pinned_and_equal_to_the_test_kit_today():
    from perfbench.generators.planted_linear import generate
    from transmogrifai_tpu.testkit import planted_linear_frame

    df, beta = generate(2000, 32, 11)
    # pinned by a hash, so later drift in the program's test kit does not
    # move the benchmark's inputs
    assert _frame_hash(df) == ("c788fc8b67846d63c776919cd61e7e138dbfd534f0a9"
                               "93db61994f78c50ce123")
    assert df.equals(planted_linear_frame(2000, 32, 11))
    assert list(df.columns[:2]) == ["label", "f0"]
    assert (beta != 0).sum() == 3 and beta.dtype.name == "float32"


def test_generator_is_deterministic_and_weights_seed_fixes_the_model():
    from perfbench.generators.planted_linear import generate

    a, beta_a = generate(500, 40, 3, weights_seed=11)
    b, beta_b = generate(500, 40, 3, weights_seed=11)
    c, beta_c = generate(500, 40, 4, weights_seed=11)
    assert a.equals(b) and (beta_a == beta_b).all()
    assert not a.equals(c)            # another seed, other rows ...
    assert (beta_a == beta_c).all()   # ... of the same planted model


def test_unknown_device_kind_is_an_error():
    from perfbench import peaks

    assert peaks.chip_peaks("TPU v5 lite")["hbm_gbs"] == 819.0
    with pytest.raises(KeyError):
        peaks.chip_peaks("cpu")


def test_histogram_bytes_of_the_roofline_metric():
    from perfbench.metrics.tree_hist_roofline import histogram_bytes

    xgb = spec.load_cell("dense500-xgb")["traffic"]
    # (1 grid point x 3 folds + the refit) x 8 rounds, 10 levels, 40 % of
    # 250,000 rows, 500 + 8 bytes: counted from the mix, not written in it
    assert histogram_bytes(250_000, 500, xgb, 3, "OpXGBoostClassifier") == (
        32 * 10 * 100_000 * 508)
    # a forest that does not win is not refitted: 4 trees x 3 folds
    rf = _traffic("rf-pairs-probe", LATER)
    assert histogram_bytes(1000, 500, rf, 3, "OpLogisticRegression") == (
        12 * 12 * 1000 * 508)
    assert histogram_bytes(1000, 500, rf, 3, "OpRandomForestClassifier") == (
        16 * 12 * 1000 * 508)


def test_cv_bands_hold_the_candidates_of_every_train():
    """``train_loop`` applies this to the warm-up and to every train of the
    window; a rehearsal shape has no band."""
    from types import SimpleNamespace

    from perfbench import checks

    loaded = spec.load_cell("mesh4-trees")
    ctx = SimpleNamespace(traffic=loaded["traffic"], config=loaded["config"],
                          rehearsal_shape=False)
    xgb = {"model": "OpXGBoostClassifier", "params": {}, "cv": 0.8655}
    rf = {"model": "OpRandomForestClassifier", "params": {}, "cv": 0.40}
    assert checks.candidate_band_problems(ctx, [xgb]) == []
    (problem,) = checks.candidate_band_problems(ctx, [xgb, rf])
    assert "OpRandomForestClassifier" in problem and "0.4000" in problem
    ctx.rehearsal_shape = True
    assert checks.candidate_band_problems(ctx, [xgb, rf]) == []


def test_the_last_line_gives_each_cv_band_beside_what_the_trains_read():
    from types import SimpleNamespace

    from perfbench import checks

    loaded = spec.load_cell("mesh4-trees")
    mix = loaded["traffic"]
    ctx = SimpleNamespace(traffic=mix, config=loaded["config"],
                          rehearsal_shape=False)
    trains = [{"candidates": [
        {"model": "OpXGBoostClassifier", "cv": cv},
        {"model": "OpRandomForestClassifier", "cv": cv - 0.3}]}
        for cv in (0.861, 0.858, 0.866)]
    got = checks.compared_cv(ctx, trains)
    band = mix["checks"]["quality_band"]["cv_aupr"]
    assert got["cv_aupr.OpXGBoostClassifier"] == [
        [0.858, 0.866], band["OpXGBoostClassifier"]]
    assert set(got) == {f"cv_aupr.{m}" for m in band}
    ctx.rehearsal_shape = True          # no band under a rehearsal shape
    assert checks.compared_cv(ctx, trains) == {}


# -- programs built inside the window ----------------------------------------

def _rec(n):
    return {"leg": "train0", "new_programs": n, "compile": {"programs": n}}


def test_only_a_mesh_configuration_may_build_programs_in_the_window():
    from perfbench.modes import train_loop

    one = spec.load_cell("dense500-xgb")["config"]
    mesh = spec.load_cell("mesh4-trees")["config"]
    assert train_loop.window_programs_max(one) == 0
    cap = train_loop.window_programs_max(mesh)
    assert cap == mesh["window_programs_max"]["value"] == 42
    # a one-chip configuration cannot take the allowance, whatever it says
    with pytest.raises(train_loop.CellFailure):
        train_loop.window_programs_max(
            dict(one, window_programs_max={"value": 99, "why": "none"}))
    # the traffic files carry no allowance: a mix is shared between cells
    for cell in CELLS:
        assert not [k for k in spec.load_cell(cell)["traffic"]
                    if k.startswith("window_programs")]


@pytest.mark.parametrize("built,allowed,fails", [
    (0, 0, False), (1, 0, True), (39, 42, False), (42, 42, False),
    (43, 42, True)])
def test_a_train_that_builds_more_programs_than_allowed_is_a_problem(
        built, allowed, fails):
    from perfbench.modes import train_loop

    problem = train_loop.window_program_problem(_rec(built), allowed)
    assert (problem is not None) == fails
    if fails:
        assert f"{built} programs" in problem and "train0" in problem


# -- device seconds as an end-to-end metric -----------------------------------

def _traced(busy, platform="tpu"):
    return {"trace": {"busy_s": busy, "platform": platform}}


@pytest.mark.parametrize("trains,want", [
    ([_traced(18.29)], [18.29]),
    ([_traced(18.29), _traced(18.31)], [18.29, 18.31]),
    ([_traced(1.4, "cpu")], []),        # no CPU number under a device name
    ([_traced(18.29), {"trace": None}], []),  # no median over half a window
    ([{}], [])])
def test_device_seconds_need_a_tpu_trace_of_every_train(trains, want):
    from perfbench.modes import train_loop

    assert train_loop.device_seconds(trains) == want
