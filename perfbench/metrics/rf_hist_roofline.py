"""The forest histograms' share of their HBM roofline, in the traced train.

Algorithmic bytes over the growth program's device time over the chip's peak
bandwidth.  A level's histogram must read, for every row, the int8 bins of
the tree's ``msub`` subset columns and the row's two float32 channels (8
bytes): ``_grow_tree_traced`` builds K - 1 class channels and the bag weight
for a one-hot target (two for a binary label) and K value channels and the
bag weight for a K-channel one (two for a regression label's one-channel
target).  bytes = trees grown x levels x rows x (msub + 8).  Every factor
but the rows is read from the program's counters (``COUNTERS.rfGrid``:
``treesGrown``, ``levels`` = the heap depth the launches were compiled for,
``msub``), so the count is of what was GROWN: with depth-truncation
sharing a grid's points x folds would count forests that no launch grows
(``tree_hist_roofline`` counts from the mix, which is right only where
nothing is shared).  The time is ``rf_grow_device_s``: the whole growth
program (bags, split search, routing and leaves too), so this is a lower
bound on the histogram's own share.  It reads far under 1 %: these histograms are bound by the slot
one-hot and the MXU, not by bytes, and the number says by how much.
"""
from perfbench import peaks
from perfbench.metrics import rf_grow_device_s

LAYER = "tree kernels"
UNIT = "%"
MOVES = "train_device_s"


def histogram_bytes(trees: int, levels: int, rows: int, msub: int) -> float:
    """Bytes the histogram passes of the grown trees must move."""
    return float(trees) * levels * rows * (msub + 8)


def read(sources: dict):
    seconds = rf_grow_device_s.read(sources)
    grid = (sources.get("counters") or {}).get("rfGrid") or {}
    if not seconds or not {"treesGrown", "levels", "msub"} <= set(grid):
        return None
    moved = histogram_bytes(grid["treesGrown"], grid["levels"],
                            sources["cell"]["rows"], grid["msub"])
    peak = peaks.chip_peaks(sources["device_kind"])["hbm_gbs"] * 1e9
    return 100.0 * moved / seconds / peak
