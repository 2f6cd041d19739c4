"""Host seconds of the traced train inside tree input preparation: the union
of the ``tree.prep.*`` spans of models/trees.py (contiguity copy, content
hash, quantile sketch, binning, uploads, EFB bundling, CSR build and the
wait for a build in flight on the prefetch thread), without
``tree.prep.prefetch``, which only wraps them.  Every train redoes this
work: ``clear_sweep_caches()`` drops the memos when a train ends.
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, _spans.PREP)
