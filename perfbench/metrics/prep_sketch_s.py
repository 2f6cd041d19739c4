"""Host seconds of the traced train in the quantile sketches:
``tree.prep.sketch``, the builds of the ``edges``, ``edges_sp`` and
``edges_mesh`` memos (``quantile_bins``, ``quantile_bins_sparse_aware``,
``quantile_bins_sharded``).
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"tree\.prep\.sketch")
