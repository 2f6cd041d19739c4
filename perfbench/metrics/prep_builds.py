"""Builds of the sweep memo in the traced train: the sum of ``builds`` over
``COUNTERS.memoTags`` (``models/trees._memo`` counts every probe by memo
kind as a hit, a build or a wait).  Work done as a count, where the work
happens: sketches, binned matrices, bundles and placements a train makes
anew.  A change that keeps memos across trains turns builds into hits.
Reported with the span metrics it stands beside: on a TPU only.
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "count"
MOVES = "train_s"


def read(sources: dict):
    tags = (sources.get("counters") or {}).get("memoTags")
    if not tags or _spans.tpu_trace(sources) is None:
        return None
    return sum(t["builds"] for t in tags.values())
