"""Seconds the host waited on queued device work in the traced train:
COUNTERS.drainSecs of utils/profiling (exact on tpu: block_until_ready does
not return early there, PR 21).
"""
LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    counters = sources.get("counters")
    return None if not counters else counters["drainSecs"]
