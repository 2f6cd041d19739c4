"""Shared by the readers of the program's own spans (``obs`` spans of the
traced train, handed over as ``name``, ``t0``, ``dur_s`` on the host's
``perf_counter`` clock).

A reader names its spans by a regular expression that must match the whole
name once the indices in ``[...]`` are stripped (``sweep.group[0:2]`` is
``sweep.group``).  Seconds "of" spans are the seconds of the UNION of their
intervals, so nested spans and spans of two threads that overlap count
once.  Idle seconds are those of the first chip (the gaps between its
``busy_intervals`` inside the train's window), put on the spans' clock by
the ``perfbench.train`` annotation as ``breakdown.py`` does, and
intersected with a union EXACTLY: a gap that straddles three spans gives
each its overlap, where ``breakdown.idle_gaps`` books the whole gap to the
span open at its midpoint.

Everything returns ``None`` where there is nothing to read: no trace, a
trace that is no TPU's (never a CPU number under a device metric's name),
or a program that records no such span (the parent of the PR that added
it).
"""
from __future__ import annotations

import re

from perfbench import trace_reduce

_INDEX = re.compile(r"\[[\d:,]+\]")

#: the spans inside tree preparation; ``tree.prep.prefetch`` only wraps
#: them on the prefetch thread
PREP = r"tree\.prep\.(?!prefetch$).*"
COMPILE = r"jit\.(trace|lower|compile):.*"


def tpu_trace(sources: dict):
    """The reduction of a train traced on a TPU, or ``None``."""
    reduced = sources.get("trace")
    if not reduced or reduced.get("platform") != "tpu":
        return None
    return reduced


def traced(sources: dict):
    """The reduction of a train traced on a TPU, if it holds spans."""
    reduced = tpu_trace(sources)
    return reduced if reduced and reduced.get("spans") else None


def matching(reduced: dict, pattern: str) -> list:
    """``(start, end)`` of every span whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [(s["t0"], s["t0"] + s["dur_s"]) for s in reduced["spans"]
            if rx.fullmatch(_INDEX.sub("", s["name"]))]


def union_seconds(sources: dict, pattern: str):
    """Seconds of the union of the matching spans of the traced train."""
    reduced = traced(sources)
    found = matching(reduced, pattern) if reduced else []
    return trace_reduce.total(trace_reduce.merge(found)) if found else None


def sum_seconds(sources: dict, pattern: str):
    """Seconds of the matching spans added up (nested ones twice, as the
    compile meter adds its durations)."""
    reduced = traced(sources)
    found = matching(reduced, pattern) if reduced else []
    return float(sum(e - s for s, e in found)) if found else None


def intersect(a: list, b: list) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_intervals(reduced: dict) -> list:
    """The first chip's idle intervals inside the train, sorted, in seconds
    on the spans' clock."""
    lo, hi = reduced["window_ns"]
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    at = reduced["annotation_perf_s"]
    return sorted(((s - lo) / 1e9 + at, (e - lo) / 1e9 + at) for s, e in
                  trace_reduce.gaps(first["busy_intervals"], lo, hi))


def idle_seconds_under(sources: dict, patterns: list):
    """``(idle, named)``: the first chip's idle seconds in the train, and
    those of them that lie under a span matching any of ``patterns``."""
    reduced = traced(sources)
    if not reduced:
        return None
    idle = idle_intervals(reduced)
    named = trace_reduce.merge(
        [iv for p in patterns for iv in matching(reduced, p)])
    return (trace_reduce.total(idle),
            trace_reduce.total(intersect(idle, named)))
