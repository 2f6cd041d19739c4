"""Trees the random-forest grid grew in the traced train:
``COUNTERS.rfGrid.treesGrown``, counted where the launches are made
(``gbdt_kernels.grow_rf_grid``): base pairs x trees of a forest for the
sweep, one forest more for the winner's refit.  With depth and gate
sharing this is NOT grid points x folds x trees: the default grid's 18
candidates x 3 folds are 2 bases (one a ``min_instances_per_node`` value) x
3 folds.  Work done as a count, read on any platform.
"""
LAYER = "sweep"
UNIT = "count"
MOVES = "train_device_s"


def read(sources: dict):
    grid = (sources.get("counters") or {}).get("rfGrid") or {}
    return grid.get("treesGrown")
