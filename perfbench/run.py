#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: set-up (environment, device, data from ``--seed``, the
mode's own set-up with ONE warm-up), then the measured window, then one
JSON object as the last line of stdout: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
``compared`` (each number the checks compared, beside its limit; the same
on the last lines of stderr).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Everything else worth keeping goes
on earlier lines prefixed ``[perfbench]`` and into
``chiprun_out/perfbench/<cell>/``.

Without an accelerator, with fewer chips than the cell asks for, or away
from the program (``transmogrifai_tpu`` beside ``perfbench/``), it exits
non-zero and prints no result.  A failure after that still ends in one
parseable last line with ``correct: false``.

Rehearsal flags, never given by the driver: ``--allow-cpu`` (the result
then says ``platform: cpu`` and is no device measurement), ``--rows`` /
``--cols`` (a tiny shape; the checks tied to the configuration's own shape
are skipped).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

EXIT_NO_PROGRAM = 3
EXIT_NO_DEVICE = 4


class Run:
    """What a mode gets: the cell's files, the data, the meters, and the
    two calls that mark time (``say``, ``setup_done``)."""

    def __init__(self, args, spec: dict):
        self.args = args
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        #: the end-to-end metrics this cell reports (BENCHMARK.json entries)
        self.end_to_end = spec["end_to_end"]
        self.rehearsal_shape = (args.rows is not None
                                or args.cols is not None)
        self.out_dir = os.path.join(ROOT, "chiprun_out", "perfbench",
                                    self.cell["name"])
        os.makedirs(self.out_dir, exist_ok=True)
        self.log = open(os.path.join(
            self.out_dir, f"seed{args.seed}_trace{args.trace}.log"), "w")
        #: seconds of set-up by part, for PERF.md's split
        self.split: dict = {}
        self.setup_s = None
        self.meter = None
        #: the generator module, and what its ``generate`` returned: the
        #: frame split in two, and second the planted model (weights, or
        #: whatever the generator's own ``oracle_score`` reads)
        self.generator = None
        self.df = self.hold = self.planted = None

    def say(self, leg: str, **fields) -> None:
        line = f"[perfbench] {leg} " + " ".join(
            f"{k}={json.dumps(v, default=str)}" for k, v in fields.items())
        print(line, flush=True)
        self.log.write(line + "\n")
        self.log.flush()

    def setup_done(self) -> None:
        """Called by the mode where set-up ends and the window starts."""
        self.setup_s = time.perf_counter() - T_START
        self.say("setup", setup_s=round(self.setup_s, 3),
                 split={k: round(v, 3) for k, v in self.split.items()})


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--cols", type=int, default=None)
    return ap.parse_args(argv)


def refuse(code: int, why: str) -> int:
    print(f"perfbench: {why}", file=sys.stderr, flush=True)
    return code


def device_report(devices, chips: int) -> dict:
    from perfbench import peaks

    used = [peaks.memory_peak(d.memory_stats() or {})
            for d in devices[:chips]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(used)}


def read_metrics(names, sources: dict, problems: list) -> dict:
    """Each per-layer metric through its own reader,
    ``metrics/<name>.py``; a reader that finds nothing returns ``None`` and
    the metric is left out of the line.  A reader that raises (an unknown
    ``device_kind``, say) keeps the other metrics but is a problem: the run
    is not ``correct``."""
    from perfbench import spec

    out = {}
    for m in names:
        reader = spec.load_module("metrics", m["name"])
        try:
            value = reader.read(sources)
        except Exception as e:
            problems.append(f"metric {m['name']}: its reader raised {e!r}")
            continue
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    # -- 1. environment ----------------------------------------------------
    os.environ["TMOG_COST_HISTORY"] = ""  # no appends to the committed file
    os.environ["JAX_ENABLE_X64"] = "0"    # the chip path is 32-bit
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from perfbench import spec as spec_mod

    try:
        spec = spec_mod.load_cell(args.workload)
        mode = spec_mod.load_module("modes", spec["traffic"]["mode"])
        generator = spec_mod.load_module(
            "generators", spec["config"]["generator"]["name"])
    except (spec_mod.SpecError, OSError, KeyError, ValueError) as e:
        return refuse(2, f"cannot load cell {args.workload!r}: {e!r}")
    try:
        from transmogrifai_tpu.utils.compile_cache import (
            enable_persistent_cache)
    except ImportError as e:
        return refuse(EXIT_NO_PROGRAM,
                      f"the program is not beside perfbench/: {e!r}")
    # JAX_COMPILATION_CACHE_DIR if set, else the fixed <checkout>/.jax_cache
    cache_dir = enable_persistent_cache()

    # -- 2. device: fail, never fall back ----------------------------------
    import jax

    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" and not args.allow_cpu:
        return refuse(EXIT_NO_DEVICE,
                      f"no accelerator: jax.devices()[0].platform is "
                      f"{devices[0].platform!r}, expected 'tpu'")
    if len(devices) < chips:
        return refuse(EXIT_NO_DEVICE,
                      f"cell {args.workload!r} needs {chips} chips, JAX "
                      f"reports {len(devices)}")

    from perfbench import breakdown
    from perfbench.compile_meter import CompileMeter

    ctx = Run(args, spec)
    ctx.meter = CompileMeter()
    ctx.split["import_runtime_s"] = time.perf_counter() - T_START
    ctx.say("device", platform=devices[0].platform,
            kind=devices[0].device_kind, count=len(devices),
            jax=jax.__version__, x64=bool(jax.config.jax_enable_x64),
            cache_dir=cache_dir,
            cache_entries=len(os.listdir(cache_dir)))
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": device_report(devices, chips)}
    code = 1
    try:
        # -- 3. data from --seed -------------------------------------------
        cfg = spec["config"]
        rows = args.rows if args.rows is not None else cfg["rows"]
        cols = (args.cols if args.cols is not None
                else cfg["schema"]["predictors"]["count"])
        hold_rows = cfg["holdout_rows"]
        t0 = time.perf_counter()
        ctx.generator = generator
        frame, ctx.planted = generator.generate(
            rows + hold_rows, cols, args.seed, **cfg["generator"]["params"])
        ctx.df = frame.iloc[:rows].reset_index(drop=True)
        ctx.hold = frame.iloc[rows:].reset_index(drop=True)
        del frame
        ctx.split["data_s"] = time.perf_counter() - t0
        ctx.say("data", rows=rows, cols=cols, hold_rows=hold_rows,
                seed=args.seed, gen_s=round(ctx.split["data_s"], 3),
                rehearsal_shape=ctx.rehearsal_shape)

        # -- 4-7 and the window: the mode ----------------------------------
        out = mode.run(ctx)

        sources = out["sources"]
        sources["cell"] = {"rows": rows, "cols": cols, "chips": chips,
                           "config": cfg, "traffic": spec["traffic"]}
        sources["device_kind"] = devices[0].device_kind
        sources["memory"] = [d.memory_stats() or {} for d in devices[:chips]]
        result["device"] = device_report(devices, chips)
        ctx.say("memory", stats=sources["memory"])
        result.update(correct=bool(out["correct"]),
                      attempted=int(out["attempted"]),
                      failed=int(out["failed"]))
        sources["peak_rss_kib"] = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss
        if args.trace:
            reduced = sources.get("trace")
            if reduced is None:
                raise RuntimeError(f"mode {spec['traffic']['mode']!r} "
                                   f"returned no trace for --trace 1")
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["metrics"] = read_metrics(spec["per_layer"], sources,
                                             out["problems"])
            result["breakdown"] = breakdown.build(reduced)
            if reduced["platform"] != devices[0].platform:
                out["problems"].append(
                    f"the trace was reduced as {reduced['platform']!r} on "
                    f"a {devices[0].platform!r} device")
            ctx.say("traced", train_wall_s=sources["traced_wall_s"],
                    window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                    reduce_s=round(reduced["reduce_s"], 3),
                    top_modules=reduced["top_modules"],
                    collectives={d: sorted(v["collective_op_s"].items(),
                                           key=lambda kv: -kv[1])[:6]
                                 for d, v in reduced["devices"].items()},
                    device_busy_s={d: v["busy_s"] for d, v in
                                   reduced["devices"].items()},
                    planes=reduced["planes"])
        else:
            values = dict(out["end_to_end"])
            values["setup_s"] = ctx.setup_s
            result["metrics"] = {
                m["name"]: {"value": float(values[m["name"]]),
                            "unit": m["unit"]}
                for m in spec["end_to_end"] if m["name"] in values}
            # a CPU rehearsal has no device time, so it leaves a
            # device_trace metric out; on the chip every metric is owed
            owed = [m["name"] for m in spec["end_to_end"]
                    if m["name"] not in values
                    and (devices[0].platform == "tpu"
                         or m["source"] != "device_trace")]
            if owed:
                out["problems"].append(f"end-to-end metrics of this cell "
                                       f"that the mode did not give: {owed}")
            ctx.say("samples", **out.get("samples", {}))
            ctx.say("host", peak_rss_gib=round(
                sources["peak_rss_kib"] / 2**20, 3))
        if out["problems"]:
            result["correct"] = False
            ctx.say("problems", problems=out["problems"])
        if ctx.rehearsal_shape or devices[0].platform != "tpu":
            result["rehearsal"] = True
        # each number the checks compared, beside its limit: the last lines
        # of stderr, and the last key of the result's line
        result["compared"] = out.get("compared", {})
        for name, (value, limit) in result["compared"].items():
            print(f"perfbench: compared {name} = {value!r}, limit {limit!r}",
                  file=sys.stderr, flush=True)
        code = 0
    except Exception:
        traceback.print_exc()
        ctx.say("error", error=traceback.format_exc(limit=3))
    finally:
        ctx.log.close()
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
