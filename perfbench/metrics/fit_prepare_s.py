"""Host seconds of the traced train inside a sequential tree fit before its
first growth launch (``tree.fit.prepare``, in ``fit_raw`` of the tree
estimators): the memo probes or builds, weights, padding and uploads.  In
the cells the fit is the winner's refit, so this lies inside ``refit_s``.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"tree\.fit\.prepare")
