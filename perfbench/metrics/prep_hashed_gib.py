"""GiB given to the full-content hash of big arrays in the traced train:
``COUNTERS.hashBytes`` (``models/trees._content_hash``, counted beside its
``tree.prep.hash`` span).  ``prep_hash_s`` is the UNION of those spans, so
it cannot show a matrix hashed twice at once on two threads; the bytes can.
Reported with the span metrics it stands beside: on a TPU only.
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "GiB"
MOVES = "train_s"


def read(sources: dict):
    hashed = (sources.get("counters") or {}).get("hashBytes")
    if hashed is None or _spans.tpu_trace(sources) is None:
        return None
    return hashed / 2**30
