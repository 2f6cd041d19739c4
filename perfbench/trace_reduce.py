"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  What
comes out, all on the trace's own clock (nanoseconds):

- the window: the host event named by ``annotation`` (a
  ``jax.profiler.TraceAnnotation`` the harness wraps round the traced
  train).  Its start is known on the host's ``perf_counter`` clock too, so
  it is the offset that puts the program's own spans on the trace's clock;
- per device: the merged busy intervals inside the window (union of the
  events on the "XLA Modules" line, or of the op events where a trace has
  no such line), device time by XLA module name, self time by
  ``<module>/<op>``, and the time during which a collective op runs or is
  in flight ("XLA Ops" and "Async XLA Ops" lines);
- the idle gaps of the first device, longest first.

A device plane is one named ``/device:TPU:<n>``.  A trace taken on the CPU
backend has none: there the events that carry an ``hlo_module`` stat (the
CPU client's op events, on host threads) stand in as one device, so that
the whole path can be rehearsed without the chip.  Such a reduction is
marked ``"platform": "cpu"`` and never reported as a device metric.
"""
from __future__ import annotations

import lzma
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
#: HLO names of the ops that move data between chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all")

Interval = Tuple[float, float]


def module_name(event_name: str) -> str:
    """``jit_gbt_chain_rounds(1234)`` -> ``jit_gbt_chain_rounds``."""
    return _MODULE_SUFFIX.sub("", event_name.strip())


_HLO = re.compile(r"^(%[^\s=]+) = .*?\s([a-z][a-z\-]*)\(")


def op_name(event_name: str) -> str:
    """A device op's own name and opcode.  The TPU trace names an op by its
    whole HLO instruction (``%fusion.17 = s32[32000]{...} fusion(s32[...]
    %get-tuple-element.266, ...), kind=kCustom, ...``): keep ``%fusion.17
    fusion``, so that operands never count as the op (a fusion that reads
    ``%all-reduce.3`` is no collective)."""
    m = _HLO.match(event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name.strip()[:80]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: List[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time by name of possibly nested events ``(start, end, name)``
    of ONE line: an event's duration less what its children cover (a
    ``while`` op spans the ops of its body)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, name, self]

    def close():
        end, name, own = stack.pop()
        out[name] = out.get(name, 0.0) + max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given merged busy intervals,
    longest first."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def _open(path: str):
    """ProfileData of ``path``; an ``.xz`` is unpacked to a temporary file
    first (the recorded test trace is committed compressed)."""
    from jax.profiler import ProfileData

    if not path.endswith(".xz"):
        return ProfileData.from_file(path)
    with tempfile.TemporaryDirectory() as tmp:
        plain = os.path.join(tmp, "trace.xplane.pb")
        with lzma.open(path, "rb") as src, open(plain, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return ProfileData.from_file(plain)


def _events(line) -> List[Tuple[float, float, str, dict]]:
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name,
             e.stats) for e in line.events]


def _stat(stats, key: str):
    for k, v in stats:
        if k == key:
            return v
    return None


def _device_tables(planes) -> Tuple[str, Dict[str, dict]]:
    """``(platform, {device: {"modules": [...], "ops": [...]}})`` with
    event lists ``(start, end, name)``."""
    devices: Dict[str, dict] = {}
    for p in planes:
        if not _DEVICE_PLANE.match(p.name):
            continue
        tab = devices.setdefault(p.name, {"modules": [], "ops": [],
                                          "async": []})
        for line in p.lines:
            if line.name == "XLA Modules":
                tab["modules"] += [(s, e, module_name(n))
                                   for s, e, n, _ in _events(line)]
            elif line.name == "XLA Ops":
                tab["ops"] += [(s, e, op_name(n))
                               for s, e, n, _ in _events(line)]
            elif line.name == "Async XLA Ops":  # start..done of async ops
                tab["async"] += [(s, e, op_name(n))
                                 for s, e, n, _ in _events(line)]
    if devices:
        return "tpu", devices
    # CPU rehearsal: op events carry their module in a stat
    tab = {"modules": [], "ops": [], "async": []}
    for p in planes:
        for line in p.lines:
            for s, e, n, stats in _events(line):
                mod = _stat(stats, "hlo_module")
                if mod is not None and e > s:
                    tab["ops"].append((s, e, n))
                    tab["modules"].append((s, e, module_name(str(mod))))
    return "cpu", ({"/host:CPU": tab} if tab["ops"] else {})


def _with_module(ops, mods):
    """Ops renamed ``<module>/<op>``, the module being the one running at
    the op's start (op names repeat from module to module)."""
    import bisect

    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for s, e, n in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and mods[i][1] >= s and mods[i][2] != n:
            n = f"{mods[i][2]}/{n}"
        out.append((s, e, n))
    return out


def find_annotation(planes, annotation: str) -> Optional[Interval]:
    """The first host event named ``annotation``: ``(start, end)``."""
    for p in planes:
        if _DEVICE_PLANE.match(p.name):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == annotation:
                    return (float(e.start_ns),
                            float(e.start_ns + e.duration_ns))
    return None


def reduce_trace(path: str, annotation: str = "perfbench.train",
                 top: int = 10) -> dict:
    """See the module docstring.  Raises ``ValueError`` for a trace with no
    device events at all."""
    planes = list(_open(path).planes)
    platform, tables = _device_tables(planes)
    if not tables:
        raise ValueError(f"{path}: no device events in the trace")
    window = found = find_annotation(planes, annotation)
    if window is None:
        lo = min(ev[0] for t in tables.values() for ev in t["ops"] or
                 t["modules"])
        hi = max(ev[1] for t in tables.values() for ev in t["ops"] or
                 t["modules"])
        window = (lo, hi)
    lo, hi = window
    out = {"platform": platform, "annotation_found": found is not None,
           "window_ns": [lo, hi], "window_s": (hi - lo) / 1e9,
           "planes": [[p.name, [[ln.name, sum(1 for _ in ln.events)]
                                for ln in p.lines][:12]] for p in planes],
           "devices": {}}
    for name in sorted(tables):
        tab = tables[name]
        mods = [ev for ev in tab["modules"] if ev[1] > lo and ev[0] < hi]
        ops = [ev for ev in tab["ops"] if ev[1] > lo and ev[0] < hi]
        busy = clip(merge([(s, e) for s, e, _ in (mods or ops)]), lo, hi)
        by_module: Dict[str, float] = {}
        for s, e, n in mods:
            by_module[n] = by_module.get(n, 0.0) + (min(e, hi) - max(s, lo))
        by_op = self_times(_with_module(ops, mods))
        coll_ops = [ev for ev in ops + tab["async"]
                    if COLLECTIVE.search(ev[2]) and ev[1] > lo and ev[0] < hi]
        coll = clip(merge([(s, e) for s, e, _ in coll_ops]), lo, hi)
        coll_by_op: Dict[str, float] = {}
        for s, e, n in coll_ops:
            coll_by_op[n] = coll_by_op.get(n, 0.0) + (e - s) / 1e9
        out["devices"][name] = {
            "busy_s": total(busy) / 1e9,
            "busy_intervals": busy,
            "module_s": {k: v / 1e9 for k, v in by_module.items()},
            "op_self_s": {k: v / 1e9 for k, v in by_op.items()},
            "collective_s": total(coll) / 1e9,
            "collective_op_s": coll_by_op,
            "events": {"modules": len(mods), "ops": len(ops)},
        }
    first = out["devices"][sorted(out["devices"])[0]]
    out["busy_s"] = (sum(d["busy_s"] for d in out["devices"].values())
                     / len(out["devices"]))
    out["idle_gaps_ns"] = gaps(first["busy_intervals"], lo, hi)[:top]
    tops = sorted(first["op_self_s"].items(), key=lambda kv: -kv[1])[:top]
    out["top_ops"] = [[k, v] for k, v in tops]
    tops = sorted(first["module_s"].items(), key=lambda kv: -kv[1])[:top]
    out["top_modules"] = [[k, v] for k, v in tops]
    return out


def module_seconds(reduced: dict, pattern: str, device: int = 0) -> float:
    """Device seconds, on the ``device``-th device of the reduction, of the
    XLA modules whose name matches the regular expression ``pattern``."""
    dev = reduced["devices"][sorted(reduced["devices"])[device]]
    rx = re.compile(pattern)
    return float(sum(v for k, v in dev["module_s"].items() if rx.search(k)))
