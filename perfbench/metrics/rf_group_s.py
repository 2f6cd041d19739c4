"""Host seconds of the traced train inside the random-forest grid group's
``run`` (``sweep.group:OpRandomForest*``): its input preparation and its
dispatches.  The device runs on after the span ends.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(
        sources, r"sweep\.group:OpRandomForest.*")
