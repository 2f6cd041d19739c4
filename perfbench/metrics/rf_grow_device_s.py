"""Device seconds, on the first chip, of the random-forest grid's growth
program in the traced train: the XLA module ``jit__grow_chunk_rf_grid``
(``models/gbdt_kernels._grow_chunk_rf_grid``, one launch a chunk of trees,
the sweep's base pairs and the winner's refit alike).  Bags, feature
subsets, histograms, split search, routing and leaf snapshots are all
inside it; scoring and the metric grid are ``rf_score_device_s``.
"""
from perfbench import trace_reduce
from perfbench.metrics import _spans

PATTERN = r"grow_chunk_rf_grid"

LAYER = "tree kernels"
UNIT = "s"
MOVES = "train_device_s"


def read(sources: dict):
    reduced = _spans.tpu_trace(sources)  # a CPU rehearsal has no device time
    return reduced and (trace_reduce.module_seconds(reduced, PATTERN) or None)
