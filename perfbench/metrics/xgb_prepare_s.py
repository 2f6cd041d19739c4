"""Host seconds of the traced train inside the boosted-tree grid group
before its first chain launch (``gbt.grid.prepare``): static checks, the
memo probes or builds (hash, sketch, bins, bundle, placement lie under it
as ``tree.prep.*``), the fold weights and the uploads.  The part of
``xgb_group_s`` in which the chips have nothing of this group to do.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"gbt\.grid\.prepare")
