"""Host seconds of the traced train inside the boosted-tree grid group's
``run`` (``sweep.group:OpXGBoost*`` and ``sweep.group:OpGBT*``): its input
preparation and its dispatches.  The device runs on after the span ends;
the wait for it is ``drain_s``.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(
        sources, r"sweep\.group:Op(XGBoost|GBT).*")
