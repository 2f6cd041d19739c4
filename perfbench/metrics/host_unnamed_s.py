"""Seconds of the traced train in which the first chip is idle and the host
is under none of the spans that name a piece of work: what the measurement
still cannot see, as a number.  Idle time is intersected exactly with the
union of the named spans (``_spans.py``), not booked by a gap's midpoint.
Broad spans that only hold others (``workflow.train``, ``plan.layer``,
``stage:ModelSelector``, ``selector.validate``, ``sweep.run``,
``sweep.group``, ``sweep.unit``) name nothing: idle time directly under them
counts here.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"

#: a span of one of these names says what the host was doing
NAMED = [_spans.PREP, r"selector\.(prepare|refit|predict|metrics)",
         r"launch:.*", _spans.COMPILE, r"sweep\.drain",
         r"sweep\.checkpoint\.flush", r"stage:(?!ModelSelector$).*"]


def read(sources: dict):
    found = _spans.idle_seconds_under(sources, NAMED)
    if found is None:
        return None
    idle, named = found
    return idle - named
