"""Host wall of the RealVectorizer stages of the traced train (fit and
transform in a full train, transform alone under selector_refit), from
train_profile.  Column extraction by the reader has no stage of its own and
is not in it.
"""
from perfbench.metrics._stages import stage_seconds

LAYER = "reader and vectorizers"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    return stage_seconds(sources, "RealVectorizer")
