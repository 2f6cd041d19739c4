"""Seconds from the start of ``OpWorkflow.train()`` to the first tree launch
of the traced train: the least of ``COUNTERS.firstLaunchSecs`` over the
launch tags other than ``device_bin`` (the binning launches feed the trees,
they are none).  The program stamps the origin where ``workflow.train``
opens and each tag's first ``count_launch``, so this is the length of the
host stretch in front of the chips' first tree work, stated by the program
itself and not by the label a gap's midpoint falls under.  Reported with
the span metrics it stands beside: on a TPU only.
"""
from perfbench.metrics import _spans

LAYER = "device"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    first = (sources.get("counters") or {}).get("firstLaunchSecs")
    if not first or _spans.tpu_trace(sources) is None:
        return None
    trees = [s for tag, s in first.items() if tag != "device_bin"]
    return min(trees) if trees else None
