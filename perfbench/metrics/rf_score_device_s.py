"""Device seconds, on the first chip, of what the random-forest grid does
with its forests in the traced train: the candidate-pair scoring of
``selector/grid_groups._score_pairs_jit`` (``jit__score_ensemble_jit``, one
launch a fold a scoring part), the winner's ``predict_ensemble`` /
``predict_tree`` and the metric grid (``jit__aupr_dev`` /
``jit__auroc_dev`` under ``binary_metric_grid``).  In a cell whose selector
holds another tree family too, that family's scoring modules carry the same
names and are counted here as well.

A regression label's metric grid is left out: ``regression_metric_grid``
is no jitted program, and its ``vmap`` of ``_regression_metric_dev`` runs
eagerly, one module a primitive, each named by its primitive alone
(``jit_subtract``, ``jit_sqrt``, ...), which any eager op of the train may
carry.  Nor can the host's ``rf.grid.metrics`` span place them: it ends
when they are enqueued, behind the scoring still on the chip.  They are a
few elementwise passes over the (folds, candidates, rows) scores.  A
metric grid made one jitted program would carry ``metric_grid`` in its
name and be counted.
"""
from perfbench import trace_reduce
from perfbench.metrics import _spans

PATTERN = (r"score_pairs|score_ensemble|predict_ensemble|predict_tree"
           r"|predict_round|aupr_dev|auroc_dev|metric_grid")

LAYER = "tree kernels"
UNIT = "s"
MOVES = "train_device_s"


def read(sources: dict):
    reduced = _spans.tpu_trace(sources)  # a CPU rehearsal has no device time
    return reduced and (trace_reduce.module_seconds(reduced, PATTERN) or None)
