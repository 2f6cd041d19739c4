"""Host wall of the ModelSelector stage of the traced train (sweep, winner
refit, metrics), from train_profile.
"""
from perfbench.metrics._stages import stage_seconds

LAYER = "sweep"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return stage_seconds(sources, "ModelSelector")
