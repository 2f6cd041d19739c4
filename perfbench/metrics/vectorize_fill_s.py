"""Host seconds of the traced train inside ``RealVectorizerModel``'s column
work (``vectorize.fill[g]``, one a column group): the conversion, the fill
of the missing, the mask's inverse, the copy into the transposed group
buffer and the metadata.  With ``vectorize_flush_s`` it makes up
``vectorize_s``; which of the two holds the seconds says whether the
columns' temporaries or the result's first touch costs them.
"""
from perfbench.metrics import _spans

LAYER = "reader and vectorizers"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"vectorize\.fill")
