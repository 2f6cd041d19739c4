"""Host seconds of the traced train in which a sequential tree fit turns its
grown arrays into the model (``tree.fit.fetch``, in ``fit_raw`` of the tree
estimators): where the arrays are fetched, the wait for the device and the
transfer.  In the cells the fit is the winner's refit, so this lies inside
``refit_s``.
"""
from perfbench.metrics import _spans

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return _spans.union_seconds(sources, r"tree\.fit\.fetch")
