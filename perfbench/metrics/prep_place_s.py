"""Host seconds of the traced train placing prepared inputs on the chips:
``tree.prep.upload`` (``_upload_timed``, and the ``device_put`` of
``_dev_memo_sharded`` on a mesh), ``tree.prep.bundle`` (host copy of the
binned matrix and the EFB packer) and ``tree.prep.csr``.
"""
from perfbench.metrics import _spans

LAYER = "tree input prep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(
        sources, r"tree\.prep\.(upload|bundle|csr)")
