"""Launches of the random-forest grid's growth program in the traced train:
``COUNTERS.rfGrid.launches``, the chunker's count (``forest_chunk_size``
trees a launch over the sweep's flat tree stream, and the refit's own
launches).  Read on any platform.
"""
LAYER = "sweep"
UNIT = "count"
MOVES = "train_device_s"


def read(sources: dict):
    grid = (sources.get("counters") or {}).get("rfGrid") or {}
    return grid.get("launches")
