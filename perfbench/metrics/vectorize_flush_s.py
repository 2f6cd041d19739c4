"""Host seconds of the traced train inside ``RealVectorizerModel``'s group
flushes (``vectorize.flush[g]``): the transposed write of a full group
buffer into the result, which is where the fresh result is first touched.
"""
from perfbench.metrics import _spans

LAYER = "reader and vectorizers"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"vectorize\.flush")
