"""Peak device memory of the fullest chip the cell uses after the traced
train: ``peaks.memory_peak`` of each chip's ``memory_stats()``, a lower
bound (the larger of live buffers' and program temporaries' peaks)."""
from perfbench import peaks

LAYER = "device"
UNIT = "GiB"
MOVES = "train_device_s"


def read(sources: dict):
    used = [peaks.memory_peak(m) for m in sources.get("memory") or []]
    return max(used) / 2**30 if any(used) else None
