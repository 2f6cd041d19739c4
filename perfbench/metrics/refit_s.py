"""Host seconds of the traced train refitting the winner on the full training
split: ``selector.refit`` (the group's ``refit_model``, else a sequential
``fit_raw`` of the winner, on a mesh with the programs it builds anew).
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"selector\.refit")
