"""The ``breakdown`` of a traced run: where the device's time went, and what
the host was doing while the device was idle.

``device_ops``  the ten ops of the first device with most SELF time, under
                the names the trace gives them.
``idle_gaps``   the idle time of the first device inside the traced train,
                summed by the innermost host span open at each gap's
                midpoint, ten names with most idle time.  The program's
                ``obs`` spans are on the host's ``perf_counter`` clock; the
                ``perfbench.train`` annotation is on both clocks and gives
                the offset.  Gaps under 100 us are summed under one name.
"""
from __future__ import annotations

import re

import numpy as np

from perfbench import trace_reduce

_INDEX = re.compile(r"\[[\d:,]+\]")
SHORT_GAP_NS = 100_000.0


def name_gaps(reduced: dict) -> dict:
    """Idle seconds of the first device by innermost open span."""
    lo, hi = reduced["window_ns"]
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    gaps = trace_reduce.gaps(first["busy_intervals"], lo, hi)
    spans = [s for s in reduced.get("spans", []) if s["dur_s"] > 0]
    out: dict = {}
    if not gaps:
        return out
    g = np.asarray(gaps, np.float64)
    length = g[:, 1] - g[:, 0]
    short = length < SHORT_GAP_NS
    if short.any():
        out["gaps_under_100us"] = float(length[short].sum()) / 1e9
    g, length = g[~short], length[~short]
    if not len(g):
        return out
    if not spans:
        out["no_span_recorded"] = float(length.sum()) / 1e9
        return out
    # the perf_counter clock of a trace-clock instant
    mid = (g.mean(axis=1) - lo) / 1e9 + reduced["annotation_perf_s"]
    s0 = np.asarray([s["t0"] for s in spans])
    s1 = s0 + np.asarray([s["dur_s"] for s in spans])
    open_ = (s0[None, :] <= mid[:, None]) & (mid[:, None] <= s1[None, :])
    dur = np.where(open_, (s1 - s0)[None, :], np.inf)
    inner = dur.argmin(axis=1)
    for i, j in enumerate(inner):
        name = (_INDEX.sub("", spans[j]["name"]) if open_[i, j]
                else "outside_any_span")
        out[name] = out.get(name, 0.0) + float(length[i]) / 1e9
    return out


def build(reduced: dict, top: int = 10) -> dict:
    named = sorted(name_gaps(reduced).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, float(v)] for k, v in
                           reduced["top_ops"][:top]],
            "idle_gaps": [[k, float(v)] for k, v in named]}
