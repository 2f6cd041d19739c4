"""Host seconds of the traced train in binning: ``tree.prep.bin``, the build
of the ``bins`` memo (``_host_bins``, or the launch of the device binning)
without the upload that follows it.
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"tree\.prep\.bin")
