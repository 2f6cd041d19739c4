#!/usr/bin/env python3
"""Do the ``jax.named_scope`` names of the tree programs reach the device trace?

  python scripts/tf_op_scopes.py <file.xplane.pb[.xz]>   count in a trace
  python scripts/tf_op_scopes.py --train [--rows N --cols D --out DIR]
                                  one selector train under jax.profiler on
                                  the device JAX finds, then count in it

On a TPU every event of a device plane's "XLA Ops" line points at an
``XEventMetadata`` whose stats carry ``tf_op``: the JAX scope path of the op
(``jit(_gbt_chain_rounds_jit)/while/body/tree.hist/dot_general``).
``jax.profiler.ProfileData`` does not expose those stats, so this reads the
``XSpace`` protobuf's wire format itself (no TensorFlow import beside JAX on
the chip).  It prints one JSON object: op events, how many have a ``tf_op``,
and for each scope name the events and the distinct ``tf_op`` values that
contain it.  What ``perfbench/trace_reduce.py`` would need to report device
seconds by scope is the same walk plus the events' durations (PERF.md §7).

An executable loaded from a compile cache that another build of the program
wrote carries THAT build's metadata: take the trace with a fresh
``JAX_COMPILATION_CACHE_DIR``.
"""
from __future__ import annotations

import argparse
import glob
import json
import lzma
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCOPES = ["tree.hist", "tree.split", "tree.route", "tree.leaf",
          "goss.select", "gbt.grad", "gbt.update", "gbt.es_metric",
          "tree.predict", "metric.grid"]


# -- protobuf wire format, as far as XSpace needs it ---------------------------

def _varint(buf: bytes, at: int):
    out = shift = 0
    while True:
        b = buf[at]
        at += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, at
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of one message: varints as int,
    length-delimited fields as bytes, fixed ones skipped."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            val, at = buf[at:at + size], at + size
        elif wire == 1:
            val, at = None, at + 8
        elif wire == 5:
            val, at = None, at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield num, wire, val


def _map_entry(buf: bytes):
    key = val = None
    for num, _, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _stat(buf: bytes):
    """XStat: ``(metadata_id, str_value or None, ref_value or None)``."""
    meta = text = ref = None
    for num, wire, v in fields(buf):
        if num == 1:
            meta = v
        elif num == 5 and wire == 2:
            text = v.decode("utf-8", "replace")
        elif num == 7 and wire == 0:
            ref = v
    return meta, text, ref


def plane_tf_ops(plane: bytes):
    """``(plane name, [tf_op or None for each event of "XLA Ops"])``."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for num, wire, v in fields(plane):
        if num == 2:
            name = v.decode()
        elif num == 3:
            lines.append(v)
        elif num == 4:              # map<int64, XEventMetadata>
            key, val = _map_entry(v)
            event_meta[key] = val
        elif num == 5:              # map<int64, XStatMetadata>
            key, val = _map_entry(v)
            stat_names[key] = next((f.decode() for n, _, f in fields(val)
                                    if n == 2), "")
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    by_meta = {}
    for key, val in event_meta.items():
        for num, wire, v in fields(val):
            if num == 5 and wire == 2:          # XEventMetadata.stats
                meta, text, ref = _stat(v)
                if meta in tf_op_ids:
                    by_meta[key] = (text if text is not None
                                    else stat_names.get(ref))
    out = []
    for line in lines:
        parts = list(fields(line))
        if not any(n == 2 and v == b"XLA Ops" for n, _, v in parts):
            continue
        for num, wire, v in parts:
            if num == 4 and wire == 2:          # XLine.events
                meta = next((f for n, _, f in fields(v) if n == 1), None)
                out.append(by_meta.get(meta))
    return name, out


def count(path: str) -> dict:
    opener = lzma.open if path.endswith(".xz") else open
    with opener(path, "rb") as f:
        space = f.read()
    ops, with_tf_op = 0, []
    for num, wire, v in fields(space):
        if num == 1 and wire == 2:
            name, found = plane_tf_ops(v)
            if name.startswith("/device:TPU:"):
                ops += len(found)
                with_tf_op += [t for t in found if t]
    scopes = {}
    for scope in SCOPES:
        hits = [t for t in with_tf_op if scope in t]
        scopes[scope] = {"events": len(hits), "distinct": len(set(hits)),
                         "example": hits[0] if hits else None}
    return {"file": os.path.relpath(path), "op_events": ops,
            "with_tf_op": len(with_tf_op),
            "distinct_tf_op": len(set(with_tf_op)), "scopes": scopes}


# -- one train under the profiler ---------------------------------------------

def train_traced(rows: int, cols: int, out_dir: str) -> str:
    """An XGB (depth 10, GOSS on a deep grid) and an RF point in one
    selector, 2 folds, trained twice (the second one traced)."""
    os.environ.setdefault("TMOG_COST_HISTORY", "")  # no appends to the
    sys.path.insert(0, ROOT)                        # committed file
    import jax

    from transmogrifai_tpu import (FeatureBuilder, OpWorkflow, models,
                                   transmogrify)
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, grid)
    from transmogrifai_tpu.testkit import planted_linear_frame
    from transmogrifai_tpu.utils.compile_cache import enable_persistent_cache

    print(f"[tf_op_scopes] device={jax.devices()[0].device_kind} "
          f"cache_dir={enable_persistent_cache()}", flush=True)
    df = planted_linear_frame(rows, cols, 5)
    label = FeatureBuilder.RealNN("label").as_response()
    preds = [FeatureBuilder.Real(c).as_predictor() for c in df.columns
             if c != "label"]
    selector = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=1, models_and_parameters=[
            (models.OpXGBoostClassifier(num_round=4), grid(max_depth=[10])),
            (models.OpRandomForestClassifier(num_trees=2),
             grid(max_depth=[6]))])
    prediction = selector.set_input(label, transmogrify(preds)).get_output()
    wf = OpWorkflow().set_result_features(prediction).set_input_data(df)
    wf.train()
    jax.profiler.start_trace(out_dir)
    try:
        wf.train()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"the profiler left no .xplane.pb under {out_dir}")
    return files[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--train", action="store_true")
    ap.add_argument("--rows", type=int, default=120_000)
    ap.add_argument("--cols", type=int, default=64)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "tf_op_scopes"))
    args = ap.parse_args(argv)
    if args.train == bool(args.trace):
        ap.error("give a trace file, or --train")
    path = (train_traced(args.rows, args.cols, args.out) if args.train
            else args.trace)
    print(json.dumps(count(path)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
