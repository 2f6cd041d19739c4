"""Mode ``train_loop``: repeat a warm ``OpWorkflow.train()`` for the window.

Set-up builds the cell's workflow through the public entry points exactly
as ``chip_smoke.py`` does (FeatureBuilder -> transmogrify -> SanityChecker
-> BinaryClassificationModelSelector.with_cross_validation, or
RegressionModelSelector's for a regression label -> OpWorkflow,
``.with_mesh`` on four chips), trains it ONCE to compile or load every
program the cell uses, scores the hold-out and runs the checks of
``checks.py``.  The window then repeats ``wf.train(profile=True)`` on the
same workflow object; a new train starts only while ``elapsed +
last_train_wall <= seconds``, so a run never overshoots its window by
design.  Every train's wall is a sample; ``train_s`` is their median.

``entry`` of the traffic file:

``full_train``      the raw frame goes through vectorizer fit, SanityChecker
                    fit and the sweep, every train.
``selector_refit``  the feature stages are fitted once in set-up (a
                    feature-only workflow) and handed over with
                    ``with_model_stages`` (upstream ``withModelStages``), so
                    every train of the window transforms, sweeps and refits
                    the winner but fits no feature stage.

With ``--trace 1`` the window is one train under ``jax.profiler`` and the
``obs`` tracer, wrapped in ``TraceAnnotation("perfbench.train")``.  A cell
whose end-to-end metrics include one taken from the device trace
(``train_device_s``: the seconds in which the chips ran an operation during
one warm train, averaged over the chips) runs every train of its window
that way with ``--trace 0`` too, and gives the median over them.
"""
from __future__ import annotations

import glob
import math
import os
import shutil
import statistics
import tempfile
import time
import warnings

ANNOTATION = "perfbench.train"


class CellFailure(RuntimeError):
    """The cell cannot run as its files describe it."""


# ---------------------------------------------------------------------------
# the workflow, from the configuration and the traffic file
# ---------------------------------------------------------------------------

def predictor_types(columns: list, predictors: dict) -> dict:
    """``{frame column: FeatureBuilder type name}`` from
    ``schema.predictors``: either ``{"type": T, "count": N}`` (every column
    is a ``T``) or ``{"count": N, "columns": [family, ...]}``, a family being
    ``{"type": T, "names": [...]}`` or ``{"type": T, "prefix": P, "count":
    K}`` (``P0 .. P<K-1>``).  The families must name the frame's columns
    exactly, each once, and ``count`` of them."""
    if "columns" not in predictors:
        return dict.fromkeys(columns, predictors["type"])
    types, names = {}, []
    for family in predictors["columns"]:
        own = family.get("names") or [
            f"{family['prefix']}{j}" for j in range(family["count"])]
        names += own
        types.update(dict.fromkeys(own, family["type"]))
    if len(types) != len(names):
        twice = sorted(n for n in types if names.count(n) > 1)
        raise CellFailure(f"schema.predictors names {twice} twice")
    if len(types) != predictors["count"]:
        raise CellFailure(f"schema.predictors' families name {len(types)} "
                          f"columns, its count says {predictors['count']}")
    if set(types) != set(columns):
        raise CellFailure(
            f"schema.predictors does not cover the frame's columns: "
            f"{sorted(set(columns) - set(types))} have no family, "
            f"{sorted(set(types) - set(columns))} are not in the frame")
    return types


def feature_graph(df, config: dict):
    from transmogrifai_tpu import FeatureBuilder, transmogrify
    from transmogrifai_tpu.preparators import SanityChecker

    schema = config["schema"]
    label = getattr(FeatureBuilder, schema["label"]["type"])(
        schema["label"]["name"]).as_response()
    columns = [c for c in df.columns if c != schema["label"]["name"]]
    types = predictor_types(columns, schema["predictors"])
    preds = [getattr(FeatureBuilder, types[c])(c).as_predictor()
             for c in columns]
    checker = SanityChecker(**config["feature_graph"]["sanity_checker"])
    checked = checker.set_input(label, transmogrify(preds)).get_output()
    return label, checked


def build_models(traffic: dict) -> list:
    """``models_and_parameters`` of the selector from the traffic file's
    data: estimator class name, constructor arguments, grid axes."""
    from transmogrifai_tpu import models
    from transmogrifai_tpu.selector import grid

    out = []
    for m in traffic["models_and_parameters"]:
        out.append((getattr(models, m["estimator"])(**m.get("args", {})),
                    grid(**m["grid"])))
    return out


def selector_workflow(df, label, checked, config: dict, traffic: dict,
                      chips: int):
    from perfbench import checks
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu import selector as selectors

    if config["problem"] not in checks.LABEL_KINDS:
        raise CellFailure(
            f"train_loop builds binary and regression selectors, the "
            f"configuration says {config['problem']!r}: a multi-class cell "
            f"needs a quality metric beside holdout_aupr and holdout_rmse "
            f"(and its selector) first, which is a benchmark issue of its "
            f"own")
    # the file must name the splitter its label kind's selector takes
    kind = checks.label_kind(config)
    val = config["validator"]
    if val["splitter"] != kind.splitter:
        raise CellFailure(f"{kind.selector} splits with {kind.splitter}, the "
                          f"configuration says {val['splitter']!r}")
    models = build_models(traffic)
    selector = getattr(selectors, kind.selector).with_cross_validation(
        num_folds=val["num_folds"], seed=val["selector_seed"],
        models_and_parameters=models,
        parallel=chips if chips > 1 else None)
    prediction = selector.set_input(label, checked).get_output()
    wf = OpWorkflow().set_result_features(prediction).set_input_data(df)
    n_candidates = sum(len(points) for _, points in models)
    if chips > 1:
        from transmogrifai_tpu.parallel.mesh import make_sweep_mesh

        mesh = make_sweep_mesh(n_candidates, n_devices=chips)
        want = dict(zip(config["mesh"]["axes"], config["mesh"]["shape"]))
        if dict(mesh.shape) != want:
            raise CellFailure(f"the sweep mesh came out {dict(mesh.shape)}, "
                              f"the configuration says {want}")
        wf.with_mesh(mesh)
    return wf, selector, n_candidates


# ---------------------------------------------------------------------------
# one train, with everything the metrics read
# ---------------------------------------------------------------------------

def one_train(ctx, wf, selector, n_candidates: int, leg: str):
    """One ``wf.train(profile=True)``: ``(record, model)``.  The record
    holds what the metrics and the checks read; the model (which keeps the
    transformed training data on the host) is the caller's to drop."""
    from transmogrifai_tpu.utils import profiling

    profiling.reset_counters()
    mark = ctx.meter.mark()
    t0 = time.perf_counter()
    model = wf.train(profile=True)
    wall = time.perf_counter() - t0
    counters = profiling.COUNTERS.to_json()
    stages = model.train_profile.to_json()["stages"]
    summ = selector.metadata["model_selector_summary"]
    rows = summ["validationResults"]
    folds = ctx.config["validator"]["num_folds"]
    bad = [r for r in rows if r.get("error")
           or not math.isfinite(r["metricValue"])
           or not all(math.isfinite(v) for v in r["foldValues"])]
    elastic = profiling.elastic_snapshot()
    compiled = ctx.meter.since(mark)
    # the width of the vector the selector was given (its second input, the
    # SanityChecker's output): what the tree kernels' histograms span
    vector = model.train_data[selector.input_features[1].name].values
    rec = {
        "leg": leg, "wall_s": wall, "selector_cols": int(vector.shape[1]),
        "winner": [summ["bestModelType"], summ["bestModelParams"]],
        "candidates": [{"model": r["modelType"], "params": r["params"],
                        "cv": r["metricValue"], "folds": r["foldValues"],
                        "error": r.get("error")} for r in rows],
        "attempted": len(rows) * folds + 1,
        "failed": len(bad) * folds,
        "elastic": elastic, "counters": counters, "stages": stages,
        "compile": compiled,
        # built in this train: compiled or loaded from the persistent cache
        "new_programs": compiled["programs"],
        "problems": [],
    }
    if len(rows) != n_candidates:
        rec["problems"].append(f"{len(rows)} validation results for "
                               f"{n_candidates} candidates")
    if bad:
        rec["problems"].append(f"{len(bad)} candidates failed or have a "
                               f"non-finite CV metric")
    if any(elastic.values()):
        rec["failed"] = rec["attempted"]
        rec["problems"].append(f"elastic counters not zero: {elastic}")
    if not (counters["launches"] or counters["drains"]):
        # (the LR grid solve counts no launch of its own; it drains)
        rec["problems"].append("no device launch or drain was counted: "
                               "the sweep did not run")
    stage_s: dict = {}
    for s in stages:
        key = f"{s['op']}:{s['kind']}"
        stage_s[key] = stage_s.get(key, 0.0) + s["wallSecs"]
    ctx.say(leg, wall_s=round(wall, 3), winner=rec["winner"],
            candidates=[[c["model"], c["params"], round(c["cv"], 5)]
                        for c in rec["candidates"]],
            stages_s={k: round(v, 3) for k, v in stage_s.items()},
            drain_s=counters["drainSecs"], fetch_s=counters["fetchSecs"],
            upload_mb=round(counters["uploadBytes"] / 2**20, 1),
            launches=counters["launchTags"], memo=counters["memoTags"],
            selector_cols=rec["selector_cols"], compile=compiled,
            problems=rec["problems"], rf_grid=counters.get("rfGrid"))
    return rec, model


# ---------------------------------------------------------------------------
# the traced train
# ---------------------------------------------------------------------------

def traced_train(ctx, wf, selector, n_candidates: int,
                 leg: str = "traced") -> dict:
    import jax

    from transmogrifai_tpu.obs import trace as obs_trace

    from perfbench import trace_reduce

    # a directory of this process's own: two runs of one cell side by side
    # (the tests' parallel workers) would delete each other's trace
    trace_dir = tempfile.mkdtemp(prefix="trace-", dir=ctx.out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from obs, not the VM
    tracer = obs_trace.start_trace("perfbench", capture_hlo=False)
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        t_perf = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANNOTATION):
            rec, _ = one_train(ctx, wf, selector, n_candidates, leg)
    finally:
        jax.profiler.stop_trace()
        obs_trace.stop_trace()
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise CellFailure(f"the profiler left no .xplane.pb under "
                          f"{trace_dir}")
    ctx.say("trace", file=os.path.relpath(files[-1], ctx.out_dir),
            mb=round(os.path.getsize(files[-1]) / 2**20, 1))
    t0 = time.perf_counter()
    reduced = trace_reduce.reduce_trace(files[-1], ANNOTATION)
    reduced["reduce_s"] = time.perf_counter() - t0
    ctx.say("reduced", train=leg, platform=reduced["platform"],
            busy_s=reduced["busy_s"], window_s=reduced["window_s"],
            annotation_found=reduced["annotation_found"],
            reduce_s=round(reduced["reduce_s"], 3))
    spans = [{"name": s.name, "t0": s.t0, "dur_s": s.dur_s or 0.0}
             for s in tracer.snapshot()]
    reduced["annotation_perf_s"] = t_perf
    reduced["spans"] = spans
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec["trace"] = reduced
    return rec


def device_seconds(trains: list) -> list:
    """``busy_s`` of every train of the window (the seconds in which an
    operation ran on the chips inside the train's annotation, averaged over
    the chips), or ``[]`` unless every train was traced on a TPU: never a
    CPU number under a device metric's name, and no median over a part of
    the window."""
    busy = [rec["trace"]["busy_s"] for rec in trains
            if rec.get("trace") and rec["trace"]["platform"] == "tpu"]
    return busy if len(busy) == len(trains) else []


# ---------------------------------------------------------------------------
# programs built inside the window
# ---------------------------------------------------------------------------

def window_programs_max(config: dict) -> int:
    """How many programs one train of the window may build: 0, unless the
    configuration runs on a mesh and its file gives ``window_programs_max``
    (a number and the reason why the PROGRAM builds programs anew in every
    train of that path, which no warm-up can prevent).  The allowance
    belongs to the mesh path, so a one-chip configuration cannot take it,
    whatever traffic it shares with a mesh cell."""
    entry = config.get("window_programs_max")
    if entry is None:
        return 0
    if not config.get("mesh"):
        raise CellFailure("window_programs_max is for a configuration on a "
                          "mesh; this one has none, so its warm-up warms "
                          "every program")
    return int(entry["value"])


def window_program_problem(rec: dict, allowed: int):
    """The problem line for a train that built more programs than
    ``allowed``, or ``None``."""
    if rec["new_programs"] <= allowed:
        return None
    return (f"{rec['leg']}: {rec['new_programs']} programs compiled or "
            f"loaded inside the window, {allowed} allowed "
            f"({rec['compile']})")


# ---------------------------------------------------------------------------
# the mode
# ---------------------------------------------------------------------------

def run(ctx) -> dict:
    from perfbench import checks

    config, traffic = ctx.config, ctx.traffic
    chips = ctx.cell["chips"]
    # a failing grid group / metric fetch falls back with a RuntimeWarning
    # from selector/validators.py: here that is a failure, not a slow pass
    warnings.filterwarnings(
        "error", category=RuntimeWarning,
        module=r"transmogrifai_tpu\.selector\.validators")

    allowed = window_programs_max(config)
    label, checked = feature_graph(ctx.df, config)
    wf, selector, n_candidates = selector_workflow(
        ctx.df, label, checked, config, traffic, chips)
    entry = traffic["entry"]
    if entry == "selector_refit":
        from transmogrifai_tpu import OpWorkflow

        t0 = time.perf_counter()
        feature_model = (OpWorkflow().set_result_features(checked)
                         .set_input_data(ctx.df).train())
        wf.with_model_stages(feature_model)
        ctx.say("features", fit_s=round(time.perf_counter() - t0, 3),
                stages=[type(s).__name__ for s in feature_model.stages])
        ctx.split["feature_fit_s"] = time.perf_counter() - t0
    elif entry != "full_train":
        raise CellFailure(f"unknown entry {entry!r} in the traffic file")

    t0 = time.perf_counter()
    warm, model = one_train(ctx, wf, selector, n_candidates, "warmup")
    ctx.split["warmup_train_s"] = time.perf_counter() - t0
    if entry == "selector_refit":
        fitted_again = [s["op"] for s in warm["stages"]
                        if s["kind"] == "fit" and s["op"] != "ModelSelector"]
        if fitted_again:
            raise CellFailure(f"selector_refit fitted {fitted_again} again")
    t0 = time.perf_counter()
    verdict = checks.check_model(ctx, model, warm["candidates"], checked)
    del model
    ctx.split["checks_s"] = time.perf_counter() - t0
    setup_compile = ctx.meter.since((0.0, 0, 0, 0))

    ctx.setup_done()

    # device seconds as an end-to-end metric come from the trace alone
    device_e2e = any(m["source"] == "device_trace" for m in ctx.end_to_end)
    trains = []
    if ctx.args.trace:
        trains.append(traced_train(ctx, wf, selector, n_candidates))
    else:
        seconds, t_start, last = ctx.args.seconds, time.perf_counter(), 0.0
        while (not trains
               or time.perf_counter() - t_start + last <= seconds):
            leg = f"train{len(trains)}"
            if device_e2e:
                rec = traced_train(ctx, wf, selector, n_candidates, leg)
            else:
                rec, _ = one_train(ctx, wf, selector, n_candidates, leg)
            last = rec["wall_s"]
            trains.append(rec)
        ctx.say("window", trains=len(trains),
                elapsed_s=round(time.perf_counter() - t_start, 3))

    problems = list(warm["problems"]) + verdict["problems"]
    for rec in trains:
        problems += [f"{rec['leg']}: {p}" for p in rec["problems"]]
        if rec["winner"] != warm["winner"]:
            problems.append(f"{rec['leg']} picked {rec['winner']}, the "
                            f"warm-up picked {warm['winner']}")
        # the hold-out checks ran on the warm-up's model; every train of
        # the window is held to the same CV bands
        problems += [f"{rec['leg']}: {p}" for p in
                     checks.candidate_band_problems(ctx, rec["candidates"])]
        built = window_program_problem(rec, allowed)
        if built:
            problems.append(built)
        if rec.get("trace") and not rec["trace"]["annotation_found"]:
            problems.append(f"{rec['leg']}: the trace holds no "
                            f"{ANNOTATION!r} annotation, so its device "
                            f"seconds are not those of the train alone")
    walls = [rec["wall_s"] for rec in trains]
    holdout = checks.label_kind(config).holdout
    end_to_end = {"train_s": statistics.median(walls),
                  holdout: verdict[holdout]}
    busy = device_seconds(trains)
    if busy:
        end_to_end["train_device_s"] = statistics.median(busy)
    last = trains[-1]
    compared = dict(verdict["compared"],
                    **checks.compared_cv(ctx, [warm] + trains))
    compared["window_programs"] = [
        max(rec["new_programs"] for rec in trains), allowed]
    return {
        "correct": not problems, "problems": problems, "compared": compared,
        "attempted": sum(rec["attempted"] for rec in trains),
        "failed": sum(rec["failed"] for rec in trains),
        "end_to_end": end_to_end,
        "samples": {"train_s": walls, "train_device_s": busy},
        "sources": {"stages": last["stages"], "counters": last["counters"],
                    "compile": setup_compile, "trace": last.get("trace"),
                    "traced_wall_s": last["wall_s"],
                    "window_programs": last["new_programs"],
                    "selector_cols": last["selector_cols"],
                    "winner": last["winner"], "checks": verdict},
    }
