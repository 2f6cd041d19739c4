"""Host wall of the SanityChecker stage of the traced train, from
train_profile.
"""
from perfbench.metrics._stages import stage_seconds

LAYER = "SanityChecker"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return stage_seconds(sources, "SanityChecker")
