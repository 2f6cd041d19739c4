#!/usr/bin/env python3
"""chip_smoke.py — train -> save -> serve once on the accelerator.

The standing proof that the main path starts on the chip: one process, data
generated from a seed, public entry points only (FeatureBuilder ->
transmogrify -> SanityChecker -> BinaryClassificationModelSelector ->
OpWorkflow.train() -> model.save() -> ModelServer.from_path()), at the full
width of BASELINE config 4 (500 Real columns, 32 bins, 3-fold CV).  Depth is
cut by trees, rounds and candidates — never by width or tree depth.

Legs, each of which raises on failure (no leg records an error and goes on):

  sweep  LR x2 (one Gram grid solve), RF depth 12 x 8 trees, XGB depth 10 x
         8 rounds x 2 min_child_weight (6 chains -> GOSS over the dense
         shared-one-hot histogram); cold train() then warm train()
  gbt    single-model workflow, OpGBTClassifier depth 5 x 8 rounds: one
         chain, depth < 8 (no GOSS), all rows -> the one-hot dot histogram
         of every tree program; its lowered program holds no custom call
  serve  LR-winner model saved and served with device programs + AOT store
         (1/8/64-row requests, once over HTTP); a second program set on the
         same store must load every bucket; then the sweep's own winner on
         the default path against model.score

Usage:
  python chip_smoke.py              one chip, full size
  python chip_smoke.py --devices 4  the same sweep also on a ("data","grid")
                                    mesh of four chips, parity vs one chip
  JAX_PLATFORMS=cpu python chip_smoke.py --rows 4000 --cols 32
                                    tiny CPU run: --rows/--cols relax ONLY
                                    the platform assertion (and the AuPR
                                    band, which is a property of the
                                    500-column configuration)

The seconds printed here are set-up evidence, not a benchmark.  The last
line of stdout is {"ok": true, "device": {...}} with the device as JAX
reports it; any failure exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import sys
import threading
import time
import urllib.request
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: BASELINE config 4's published width
FULL_COLS = 500
#: rows of the default run.  1,000,000 fits the 1200 s contract on one v5e
#: (864 s cold, every leg passing — my chip run, PR 21, ``--rows
#: 1000000``) but peaks at 38.0 GiB of host RSS on a 40 GiB machine, so
#: the default is the next size down.  Never below 250,000:
#: _BF16_UPLOAD_ELEMS selects the code path scale runs take.
FULL_ROWS = 500_000
#: rows generated past the training rows and scored as the holdout
HOLD_ROWS = 20_000
FOLDS = 3
#: holdout AuPR band of the sweep's winner at 500 columns.  Source: this
#: script under JAX_PLATFORMS=cpu at --rows 100000 --cols 500 (PR 21):
#: winner LR, holdout AuPR 0.9856; the lower edge leaves 0.02 for the
#: chip's bf16 matrix upload and other row counts (chip runs, PR 21:
#: 0.9809 at 250k, 0.9831 at 1M).  (Orientation: BENCH_r04 had 0.9827
#: train AuPR on this generator at 100k x 500.)
AUPR_BAND = (0.965, 1.0)
#: candidate-metric tolerance of the four-chip sweep against the one-chip
#: sweep — the atol examples/bench_multichip.py uses for the same parity
MESH_ATOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(leg: str, **fields) -> None:
    print(f"[smoke:{leg}] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in fields.items()),
        flush=True)


# ---------------------------------------------------------------------------
# instrumentation: compile seconds, lowered-program text, live shardings
# ---------------------------------------------------------------------------

class CompileMeter:
    """Sums JAX's own trace/lower/backend-compile durations and the
    persistent-cache hit/miss events, so compile seconds print apart from
    the train wall without a second process.

    ``programs`` and ``cache_hits`` are NOT disjoint: JAX raises its
    backend-compile event round the cache lookup and the compile alike, so
    a program loaded from the persistent cache counts as a program too
    (with the load's duration).  ``programs`` is every program built,
    ``cache_hits`` those of them that were loaded and not compiled,
    ``cache_misses`` those compiled and written to the cache: never add
    ``cache_hits`` to ``programs`` (``tests/perfbench`` pins the event on
    the benchmark's copy of this class)."""

    _DUR = ("/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in self._DUR:
            self.secs += secs
            if event == self._DUR[2]:
                self.backend_compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.secs, self.backend_compiles, self.hits, self.misses)

    def since(self, mark) -> dict:
        return {"compile_s": round(self.secs - mark[0], 1),
                "programs": self.backend_compiles - mark[1],
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}


def lowered_programs(ir_dir: str, seen: set, name_part: str) -> list:
    """Texts of the programs JAX lowered since the last call whose jit name
    contains ``name_part`` (``jax_dump_ir_to`` writes one file per
    lowering).  ``seen`` accumulates the file names already read."""
    out = []
    for fn in sorted(os.listdir(ir_dir)) if os.path.isdir(ir_dir) else []:
        if fn in seen:
            continue
        seen.add(fn)
        if name_part in fn:
            with open(os.path.join(ir_dir, fn)) as f:
                out.append(f.read())
    return out


@contextlib.contextmanager
def watch_shardings(n_rows: int, found: dict):
    """While the body runs, sample ``jax.live_arrays()`` and record the
    widest ``sharding.device_set`` seen for the binned matrix (int8 — int32
    at tiny sizes — with ~n_rows rows) and the fold-weight matrix (f32,
    (folds|chains, ~n_rows)).  train() drops these buffers when it
    returns, so they can only be observed while it runs."""
    import jax

    stop = threading.Event()

    def near(v):
        return n_rows <= v < n_rows + 64

    def loop():
        while not stop.wait(0.5):
            for a in jax.live_arrays():
                if a.ndim != 2:
                    continue
                width = len(a.sharding.device_set)
                if a.dtype.name in ("int8", "int32") and near(a.shape[0]):
                    found["binned"] = max(found.get("binned", 0), width)
                elif (a.dtype.name == "float32" and a.shape[0] <= 64
                      and near(a.shape[1])):
                    found["fold_weights"] = max(
                        found.get("fold_weights", 0), width)

    t = threading.Thread(target=loop, name="smoke-shardings", daemon=True)
    t.start()
    try:
        yield
    finally:
        stop.set()
        t.join(timeout=10)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# workflows
# ---------------------------------------------------------------------------

def feature_graph(df):
    from transmogrifai_tpu import FeatureBuilder, transmogrify
    from transmogrifai_tpu.preparators import SanityChecker

    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns[1:]]
    checked = SanityChecker(max_correlation=0.99).set_input(
        label, transmogrify(preds)).get_output()
    return label, checked


def sweep_models():
    from transmogrifai_tpu.models import (OpLogisticRegression,
                                          OpRandomForestClassifier,
                                          OpXGBoostClassifier)
    from transmogrifai_tpu.selector import grid

    return [
        (OpLogisticRegression(), grid(reg_param=[0.01, 0.1])),
        (OpRandomForestClassifier(num_trees=8), grid(max_depth=[12])),
        (OpXGBoostClassifier(num_round=8),
         grid(min_child_weight=[1.0, 10.0])),
    ]


def queue_width() -> int:
    return sum(len(points) for _, points in sweep_models())


def selector_workflow(df, label, checked, models, parallel=None):
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.selector import BinaryClassificationModelSelector

    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=FOLDS, models_and_parameters=models, parallel=parallel)
    prediction = selector.set_input(label, checked).get_output()
    wf = OpWorkflow().set_result_features(prediction).set_input_data(df)
    return wf, selector


def holdout_aupr(model, hold_df) -> float:
    from transmogrifai_tpu.evaluators import Evaluators

    _, metrics = model.score_and_evaluate(
        Evaluators.BinaryClassification.auPR(), data=hold_df)
    return float(metrics["AuPR"])


def check_aupr(leg: str, aupr: float, banded: bool) -> None:
    import math

    check(math.isfinite(aupr), f"{leg}: holdout AuPR is not finite")
    # off the 500-column configuration the planted signal differs (at 32
    # columns this seed's three informative weights are weak): finite only
    lo, hi = AUPR_BAND if banded else (0.0, 1.0)
    check(lo <= aupr <= hi,
          f"{leg}: holdout AuPR {aupr:.4f} outside [{lo}, {hi}]")


def check_selector(leg: str, selector) -> dict:
    """Zero candidate errors, a finite CV metric for every candidate, every
    elastic counter zero.  Returns the selector summary."""
    import math

    from transmogrifai_tpu.utils import profiling

    summ = selector.metadata["model_selector_summary"]
    rows = summ["validationResults"]
    check(rows, f"{leg}: no validation results")
    for r in rows:
        check(not r.get("error"),
              f"{leg}: candidate {r['modelType']} {r['params']} failed: "
              f"{r.get('error')}")
        check(math.isfinite(r["metricValue"])
              and all(math.isfinite(v) for v in r["foldValues"]),
              f"{leg}: candidate {r['modelType']} {r['params']} has a "
              f"non-finite CV metric {r['metricValue']} {r['foldValues']}")
    elastic = profiling.elastic_snapshot()
    check(not any(elastic.values()),
          f"{leg}: elastic counters not zero: {elastic}")
    return summ


def candidate_metrics(summ: dict) -> dict:
    return {f"{r['modelType']}{json.dumps(r['params'], sort_keys=True)}":
            r["metricValue"] for r in summ["validationResults"]}


def peak_rss_mb() -> float:
    import resource

    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024)


def train_timed(leg: str, wf, meter: CompileMeter):
    """train() with the wall, the compile seconds inside it, the wall per
    stage kind, and the drain/fetch split of the transfer counters."""
    from transmogrifai_tpu.utils import profiling

    profiling.reset_counters()
    mark = meter.mark()
    t0 = time.perf_counter()
    model = wf.train(profile=True)
    wall = time.perf_counter() - t0
    c = profiling.COUNTERS.to_json()
    stages: dict = {}
    for s in model.train_profile.to_json()["stages"]:
        key = f"{s['op']}:{s['kind']}"
        stages[key] = round(stages.get(key, 0.0) + s["wallSecs"], 1)
    say(leg, wall_s=round(wall, 1), **meter.since(mark), stages_s=stages,
        drain_s=c["drainSecs"], drains=c["drains"], fetch_s=c["fetchSecs"],
        fetches=c["fetches"], upload_s=c["uploadSecs"],
        upload_mb=round(c["uploadBytes"] / 2**20, 1),
        launches=c["launchTags"], peak_rss_mb=peak_rss_mb())
    return model, wall


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------

def leg_sweep(ctx, parallel=None, warm=True):
    """The cut config-4 sweep through OpWorkflow.train(); on a mesh when
    ``parallel`` names a device count."""
    import jax

    leg = "sweep" if parallel is None else f"sweep{parallel}"
    df, label, checked = ctx["df"], ctx["label"], ctx["checked"]
    wf, selector = selector_workflow(df, label, checked, sweep_models(),
                                     parallel=parallel)
    found = {}
    if parallel is not None:
        from transmogrifai_tpu.parallel.mesh import make_sweep_mesh

        wf.with_mesh(make_sweep_mesh(queue_width(), n_devices=parallel))
        watcher = watch_shardings(len(df), found)
    else:
        watcher = contextlib.nullcontext()
    with watcher:
        model, cold = train_timed(f"{leg}.cold", wf, ctx["meter"])
    summ = check_selector(leg, selector)
    if parallel is None:
        from transmogrifai_tpu.models import OpXGBoostClassifier
        from transmogrifai_tpu.models.gbdt_kernels import goss_plan

        progs = lowered_programs(ctx["ir_dir"], ctx["ir_seen"],
                                 "gbt_chain_rounds")
        goss = any("top_k" in p for p in progs)
        say(f"{leg}.xgb", programs=len(progs), goss=goss,
            hist=("one-hot dot per chain over a GOSS row gather" if goss
                  else "one-hot dot shared across chains"))
        check(not any("tpu_custom_call" in p for p in progs),
              f"{leg}: a tree program holds a tpu_custom_call")
        if goss_plan(len(df), OpXGBoostClassifier().max_depth) is not None:
            check(goss, f"{leg}: the XGB group's program holds no top_k — "
                        f"GOSS did not run over {len(df)} rows")
    else:
        # the mesh programs (RF grid chunk, GBT chain rounds) are shard_map
        # bodies: dense histograms over row shards, psum'd; no GOSS
        progs = lowered_programs(ctx["ir_dir"], ctx["ir_seen"], "shard_fn")
        parts = sorted({int(m) for p in progs for m in re.findall(
            r"mhlo\.num_partitions = (\d+)", p)})
        say(f"{leg}.trees", programs=len(progs), num_partitions=parts,
            goss=any("top_k" in p for p in progs),
            hist="dense, rows sharded over the data axis, psum")
        check(parts == [parallel],
              f"{leg}: tree programs partitioned over {parts}, expected "
              f"[{parallel}]")
    aupr = holdout_aupr(model, ctx["hold"])
    check_aupr(leg, aupr, ctx["banded"])
    say(leg, winner=summ["bestModelType"], params=summ["bestModelParams"],
        holdout_aupr=round(aupr, 4),
        selector_holdout_aupr=summ["holdoutMetrics"].get("AuPR"),
        candidates=candidate_metrics(summ))
    if warm:
        _, warm_s = train_timed(f"{leg}.warm", wf, ctx["meter"])
        summ_w = check_selector(f"{leg}.warm", selector)
        check(summ_w["bestModelType"] == summ["bestModelType"],
              f"{leg}: warm train picked {summ_w['bestModelType']}, cold "
              f"picked {summ['bestModelType']}")
        say(leg, cold_s=round(cold, 1), warm_s=round(warm_s, 1))
    if parallel is not None:
        say(leg, shardings=found)
        check(found.get("binned") == parallel,
              f"{leg}: binned matrix sharded over {found.get('binned')} "
              f"devices, expected {parallel}")
        check(found.get("fold_weights") == parallel,
              f"{leg}: fold weights sharded over "
              f"{found.get('fold_weights')} devices, expected {parallel}")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:parallel]]
        say(leg, peak_bytes_in_use=peaks)
        if not (ctx["relaxed"] and peaks[0] is None):  # CPU reports none
            check(all(peaks),
                  f"{leg}: a device reports no memory in use (everything "
                  f"on device 0?): {peaks}")
    return model, summ


def leg_mesh_parity(ctx, summ1: dict, summ4: dict) -> None:
    m1, m4 = candidate_metrics(summ1), candidate_metrics(summ4)
    check(m1.keys() == m4.keys(),
          f"mesh parity: candidates differ {sorted(m1)} vs {sorted(m4)}")
    worst = max(abs(m1[k] - m4[k]) for k in m1)
    say("mesh.parity", winner_1chip=summ1["bestModelType"],
        winner_mesh=summ4["bestModelType"], worst_abs_diff=round(worst, 5),
        atol=MESH_ATOL)
    check(summ1["bestModelType"] == summ4["bestModelType"]
          and summ1["bestModelParams"] == summ4["bestModelParams"],
          "mesh parity: the mesh sweep picked a different winner")
    check(worst <= MESH_ATOL,
          f"mesh parity: candidate metrics differ by {worst:.4f} > "
          f"{MESH_ATOL}")


def leg_gbt(ctx, fitted) -> None:
    """Single-chain GBT through OpWorkflow.train(): all rows (no GOSS at
    depth 5), the same one-hot dot histogram as every tree program."""
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.models import OpGBTClassifier

    df, label, checked = ctx["df"], ctx["label"], ctx["checked"]
    pred = OpGBTClassifier(max_iter=8).set_input(label, checked).get_output()
    wf = (OpWorkflow().set_result_features(pred).set_input_data(df)
          .with_model_stages(fitted))
    model, _ = train_timed("gbt", wf, ctx["meter"])
    progs = lowered_programs(ctx["ir_dir"], ctx["ir_seen"],
                             "gbt_chain_rounds")
    goss = any("top_k" in p for p in progs)
    custom = any("tpu_custom_call" in p for p in progs)
    check(progs, "gbt: no gbt_chain_rounds program was lowered")
    check(not goss, "gbt: GOSS ran at depth 5")
    check(not custom, "gbt: a tree program holds a tpu_custom_call")
    aupr = holdout_aupr(model, ctx["hold"])
    check_aupr("gbt", aupr, banded=False)
    say("gbt", programs=len(progs), goss=goss, custom_call=custom,
        holdout_aupr=round(aupr, 4))


def _rows_of(frame, n: int):
    cols = [c for c in frame.columns if c != "label"]
    return [{c: float(v) for c, v in zip(cols, rec)}
            for rec in frame[cols].head(n).itertuples(index=False)]


def _prob1(model, frame):
    """P(class 1) of ``model.score`` on ``frame``."""
    import numpy as np

    from transmogrifai_tpu.types.feature_types import Prediction

    scored = model.score(data=frame)
    for name in scored.names():
        col = scored[name]
        if issubclass(col.ftype, Prediction):
            return np.asarray(col.values.probability)[:, 1]
    raise SmokeFailure("model.score returned no Prediction column")


def _served_prob1(results):
    import numpy as np

    out = []
    for r in results:
        check(isinstance(r, dict), f"serve: request shed: {r}")
        (pred,) = r.values()
        out.append(pred["probability_1"])
    return np.asarray(out)


def check_serving(leg: str, server, rows, want,
                  want_programs: bool) -> dict:
    """Requests of 1, 8 and 64 rows through ``server.score`` agree with
    ``model.score`` to 1e-6, and nothing was answered by a fallback."""
    import numpy as np

    for n in (1, 8, 64):
        got = _served_prob1(server.score(rows[:n], wait_s=300.0))
        check(got.shape == (n,) and np.isfinite(got).all(),
              f"{leg}: bad answer to a {n}-row request")
        np.testing.assert_allclose(got, want[:n], atol=1e-6, rtol=0)
    snap = server.snapshot()
    check(snap["hostFallbacks"] == 0 and snap["deviceErrors"] == 0,
          f"{leg}: hostFallbacks={snap['hostFallbacks']} "
          f"deviceErrors={snap['deviceErrors']} "
          f"({snap['lastFallbackReason']})")
    check(snap["breakerState"] == "closed" and snap["shed"] == 0,
          f"{leg}: breaker {snap['breakerState']}, shed {snap['shed']}")
    if want_programs:
        check(snap.get("aotPrograms"),
              f"{leg}: no device programs installed (None program set)")
    return snap


def leg_serve_lr(ctx, fitted) -> None:
    """LR-only selector -> save -> ModelServer with device programs and an
    AOT store; then a second program set must LOAD every bucket."""
    import numpy as np

    from transmogrifai_tpu.models import OpLogisticRegression
    from transmogrifai_tpu.selector import grid
    from transmogrifai_tpu.serving import (AOTStore, ModelServer,
                                           ScoringProgramSet)
    from transmogrifai_tpu.serving.aot import find_predictor
    from transmogrifai_tpu.serving.http import make_http_server
    from transmogrifai_tpu.utils import compile_cache

    wf, selector = selector_workflow(
        ctx["df"], ctx["label"], ctx["checked"],
        [(OpLogisticRegression(), grid(reg_param=[0.01, 0.1]))])
    wf.with_model_stages(fitted)
    model, _ = train_timed("serve.lr.train", wf, ctx["meter"])
    check_selector("serve.lr", selector)
    path = os.path.join(OUT_DIR, "model_lr")
    aot_dir = os.path.join(OUT_DIR, "aot")
    model.save(path)
    hold = ctx["hold"]
    rows = _rows_of(hold, 64)
    want = _prob1(model, hold.head(64))

    compile_cache.reset_cache_stats()
    server = ModelServer.from_path(path, name="lr", device_programs=True,
                                   aot_store=aot_dir, warmup_row=rows[0])
    t0 = time.perf_counter()
    server.start()
    warm_s = time.perf_counter() - t0
    httpd = None
    try:
        httpd = make_http_server(server, host="127.0.0.1", port=0)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        req = urllib.request.Request(
            f"http://127.0.0.1:{httpd.server_address[1]}/score",
            data=json.dumps({"rows": rows[:8]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as resp:
            check(resp.status == 200, f"serve.lr: HTTP {resp.status}")
            body = json.loads(resp.read())
        np.testing.assert_allclose(_served_prob1(body["scores"]), want[:8],
                                   atol=1e-6, rtol=0)
        snap = check_serving("serve.lr", server, rows, want,
                             want_programs=True)
        modes = snap["aotPrograms"]
        say("serve.lr", warmup_s=round(warm_s, 1), programs=modes,
            requests=snap["requests"], rows=snap["rows"],
            host_fallbacks=snap["hostFallbacks"],
            device_errors=snap["deviceErrors"],
            breaker=snap["breakerState"], path="device programs")
        check(sorted(int(b) for b in modes) == [1, 2, 4, 8, 16, 32, 64],
              f"serve.lr: programs cover buckets {sorted(modes)}")
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        server.stop()

    # a fresh program set on the same store: every bucket is a serialized
    # executable deserialized and run, byte-identical to a JIT-compiled one
    predictor = find_predictor(server.registry.get("lr").model)
    compile_cache.reset_cache_stats()
    ps_aot = ScoringProgramSet(predictor, store=AOTStore(aot_dir),
                               cache_key_prefix="smoke.aot")
    ps_jit = ScoringProgramSet(predictor, store=None,
                               cache_key_prefix="smoke.jit")
    rng = np.random.default_rng(5)
    buckets = [1, 2, 4, 8, 16, 32, 64]
    for b in buckets:
        mode = ps_aot.ensure_bucket(b)
        check(mode == "aot", f"serve.aot: bucket {b} came back {mode!r}")
        ps_jit.ensure_bucket(b)
        X = rng.normal(size=(b, ps_aot.n_features)).astype(np.float32)
        a, j = ps_aot.predict(X), ps_jit.predict(X)
        for name in ("prediction", "raw_prediction", "probability"):
            av, jv = np.asarray(getattr(a, name)), np.asarray(
                getattr(j, name))
            check(av.tobytes() == jv.tobytes(),
                  f"serve.aot: bucket {b} {name} differs between the "
                  f"deserialized and the JIT executable")
    totals = compile_cache.cache_stats()["totals"]
    say("serve.aot", buckets=len(buckets), aot_loads=totals["aotLoads"],
        aot_misses=totals["aotMisses"], byte_identical=True)
    check(totals["aotLoads"] == len(buckets) and totals["aotMisses"] == 0,
          f"serve.aot: aotLoads={totals['aotLoads']} "
          f"aotMisses={totals['aotMisses']} over {len(buckets)} buckets")


def leg_serve_winner(ctx, model, summ) -> None:
    """The sweep's own winner, whatever family, on the default path."""
    import numpy as np

    from transmogrifai_tpu import native
    from transmogrifai_tpu.serving import ModelServer

    path = os.path.join(OUT_DIR, "model_winner")
    model.save(path)
    hold = ctx["hold"]
    rows = _rows_of(hold, 64)
    want = _prob1(model, hold.head(64))
    server = ModelServer.from_path(path, name="winner", warmup_row=rows[0])
    server.start()
    try:
        snap = check_serving("serve.winner", server, rows, want,
                             want_programs=False)
    finally:
        server.stop()
    family = summ["bestModelType"]
    trees = family != "OpLogisticRegression"
    say("serve.winner", family=family, requests=snap["requests"],
        host_fallbacks=snap["hostFallbacks"],
        path=("executor -> predict_batch -> "
              + (("native C++ tree scorer" if native.AVAILABLE
                  else "NumPy tree scorer") if trees
                 else "jitted XLA logistic program")))


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (relaxes the platform and "
                         "AuPR-band assertions)")
    ap.add_argument("--cols", type=int, default=None,
                    help="Real columns (relaxes the same assertions)")
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4: run the sweep on a four-chip mesh as well")
    args = ap.parse_args()
    relaxed = args.rows is not None or args.cols is not None
    rows = args.rows if args.rows is not None else FULL_ROWS
    cols = args.cols if args.cols is not None else FULL_COLS
    t_start = time.perf_counter()
    # a smoke's stage walls are not cost-model training data: keep them
    # out of the committed benchmarks/cost_history.json
    os.environ.setdefault("TMOG_COST_HISTORY", "")

    # generated artefacts come from committed files only: say whether the
    # native scorer is built from source in this run
    from transmogrifai_tpu import native
    had_so = os.path.exists(os.path.join(
        os.path.dirname(native.__file__), "libtmognative.so"))

    from transmogrifai_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    cache_before = cache_entries(cache_dir)

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("device", **device, jax=jax.__version__,
        x64=bool(jax.config.jax_enable_x64))
    if not relaxed:
        check(device["platform"] == "tpu",
              f"no accelerator: jax.devices()[0].platform is "
              f"{device['platform']!r}, expected 'tpu'")
    check(len(devices) >= args.devices,
          f"--devices {args.devices} needs {args.devices} devices, JAX "
          f"reports {len(devices)}")

    from transmogrifai_tpu.obs.bench_meta import bench_meta

    say("meta", **bench_meta())
    say("cache", dir=cache_dir,
        from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        entries_before=cache_before)
    native_ok = bool(native.AVAILABLE)
    say("native", available=native_ok,
        built_from_source_this_run=native_ok and not had_so)

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    ir_dir = os.path.join(OUT_DIR, "ir")
    os.makedirs(ir_dir)
    jax.config.update("jax_dump_ir_to", ir_dir)
    # a failing grid group / metric fetch falls back with a RuntimeWarning
    # from selector/validators.py: here that is a failure
    warnings.filterwarnings(
        "error", category=RuntimeWarning,
        module=r"transmogrifai_tpu\.selector\.validators")

    from transmogrifai_tpu.testkit import planted_linear_frame
    from transmogrifai_tpu.tuning.planner import advise_mesh
    from transmogrifai_tpu.utils import profiling

    t0 = time.perf_counter()
    frame = planted_linear_frame(rows + HOLD_ROWS, cols)
    df = frame.iloc[:rows].reset_index(drop=True)
    hold = frame.iloc[rows:].reset_index(drop=True)
    del frame
    say("data", rows=rows, cols=cols, hold_rows=len(hold), folds=FOLDS,
        gen_s=round(time.perf_counter() - t0, 1))
    label, checked = feature_graph(df)
    ctx = {"df": df, "hold": hold, "label": label, "checked": checked,
           "meter": CompileMeter(), "ir_dir": ir_dir, "ir_seen": set(),
           "relaxed": relaxed,
           "banded": cols == FULL_COLS and rows >= 100_000}
    say("advise_mesh", not_asserted=advise_mesh(
        rows, cols, queue_width=queue_width(),
        devices_available=len(devices)).to_json())

    # the four-chip run pays for four chips: one cold train per sweep there
    model, summ = leg_sweep(ctx, warm=args.devices == 1)
    if args.devices > 1:
        _, summ4 = leg_sweep(ctx, parallel=args.devices, warm=False)
        leg_mesh_parity(ctx, summ, summ4)
    leg_gbt(ctx, model)
    leg_serve_lr(ctx, model)
    leg_serve_winner(ctx, model, summ)

    stats = devices[0].memory_stats() or {}
    say("done", wall_s=round(time.perf_counter() - t_start, 1),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        peak_rss_mb=peak_rss_mb(),
        cache_entries_after=cache_entries(cache_dir),
        elastic=profiling.elastic_snapshot(), claim=None)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
