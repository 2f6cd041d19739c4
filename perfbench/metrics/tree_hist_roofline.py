"""The histogram passes' share of their HBM roofline, in the traced train.

Algorithmic bytes over device time over the chip's peak bandwidth.  Bytes
bound this kernel: a level's histogram reads each used row's int8 bins
(``cols`` bytes) and its gradient and hessian (8 bytes) once, and the
one-hot matmul form does under 1 FLOP per byte moved at 32 bins.  Bytes =
trees x levels x rows used x (cols + 8), summed over the traffic file's
``roofline`` groups, one per estimator class.  A group gives what only it
knows: the levels of a tree, the share of the training rows a level reads
(GOSS keeps 0.2 + 0.2 of them; a forest reads all) and the constructor
argument that holds the trees of one fit.  The trees are counted here from
the mix itself, so they cannot drift from it: (grid points x folds, + 1 for
the refit if this estimator won) x trees of one fit.  ``cols`` is the width
of the vector the selector was given (``selector_cols``, which the mode
records from the fitted train: vectorizers, pivots and null flags in,
SanityChecker's drops out), not the raw column count and not the width after
the program's own feature bundling: the bytes are those of the work, whatever
implements it.  A mode that records none leaves the raw count.  The time is
``tree_device_s``: ALL tree device time, so this is a lower bound on the
histogram kernel's own share.  (ISSUE 22 called this metric
``hist_hbm_share``; the contract names a roofline share
``<kernel>_roofline``.)
"""
import math

from perfbench import peaks
from perfbench.metrics import tree_device_s

LAYER = "tree kernels"
UNIT = "%"
MOVES = "train_device_s"


def trees_grown(model: dict, trees_arg: str, folds: int, won: bool) -> int:
    """Trees one train grows for one entry of ``models_and_parameters``."""
    points = math.prod(len(axis) for axis in model["grid"].values())
    return (points * folds + won) * model["args"][trees_arg]


def histogram_bytes(rows: int, cols: int, traffic: dict, folds: int,
                    winner: str) -> float:
    """Bytes the histogram passes of one train must move."""
    total = 0.0
    for group in traffic["roofline"]["groups"]:
        for model in traffic["models_and_parameters"]:
            if model["estimator"] != group["estimator"]:
                continue
            trees = trees_grown(model, group["trees_arg"], folds,
                                winner == group["estimator"])
            total += (trees * group["levels"] * rows * group["rows_share"]
                      * (cols + 8))
    return total


def read(sources: dict):
    seconds = tree_device_s.read(sources)
    cell = sources.get("cell") or {}
    traffic = cell.get("traffic", {})
    if not seconds or "roofline" not in traffic:
        return None
    moved = histogram_bytes(
        cell["rows"], sources.get("selector_cols", cell["cols"]), traffic,
        cell["config"]["validator"]["num_folds"], sources["winner"][0])
    peak = peaks.chip_peaks(sources["device_kind"])["hbm_gbs"] * 1e9
    return 100.0 * moved / seconds / peak
