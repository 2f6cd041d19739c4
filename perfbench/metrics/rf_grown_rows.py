"""Rows a base forest of the random-forest grid was grown on in the traced
train: ``COUNTERS.rfGrid.grownRows``, counted in
``selector/grid_groups.RFGridGroup.run`` before the growth is launched.
That is the length of a fold's compacted training rows (the
longest fold's weighted rows, rounded up to 1,024), about two thirds of the
weighted rows under 3-fold CV; it reads the table's rows where the group
grew on every row (no zero weight to leave out, or the mesh's sharded
rows).  A program without the counter (every fold grew on the table's
rows) reads nothing.  Read on any platform.
"""
LAYER = "tree kernels, the forest grid"
UNIT = "count"
MOVES = "train_device_s"


def read(sources: dict):
    grid = (sources.get("counters") or {}).get("rfGrid") or {}
    return grid.get("grownRows")
