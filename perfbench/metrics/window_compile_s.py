"""Seconds of trace + lower + backend compile (or cache load) inside the
traced train: the ``jit.trace:*``, ``jit.lower:*`` and ``jit.compile:*`` spans
the tracer records from JAX's monitoring events, added up as the compile
meter adds its durations.  The seconds behind ``window_programs``; no span
in a warm train that builds no program.
"""
from perfbench.metrics import _spans

LAYER = "compile"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.sum_seconds(sources, _spans.COMPILE)
