"""Host seconds of the traced train scoring the refitted winner on the whole
matrix and computing its train and hold-out metrics and the summary:
``selector.predict`` + ``selector.metrics``.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(
        sources, r"selector\.(predict|metrics)")
