"""Host seconds of the traced train spent making the memo keys of matrix-sized
arrays: ``tree.prep.hash`` (the full-bytes crc32 + adler32 of an array over
64 MB, once per object) and ``tree.prep.contiguous`` (``_as_f32``'s copy of
a strided matrix).
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(
        sources, r"tree\.prep\.(hash|contiguous)")
