"""Rows a candidate pair of the random-forest grid was scored on in the
traced train: ``COUNTERS.rfGrid.scoredRows``, counted where the scoring
parts are launched (``selector/grid_groups.RFGridGroup.run``).  Since PR 37
that is the length of a fold's compacted validation rows (the longest
fold's weighted rows, rounded up to 1,024), about a third of the weighted
rows under 3-fold CV; it reads the table's rows where the group scored
every row (no zero weight to leave out).  A program without the counter
(every pair walked all rows) reads nothing.  Read on any platform.
"""
LAYER = "sweep"
UNIT = "count"
MOVES = "train_device_s"


def read(sources: dict):
    grid = (sources.get("counters") or {}).get("rfGrid") or {}
    return grid.get("scoredRows")
