"""GiB of host arrays of 32 MiB or more that the traced train made anew: the
sum of ``COUNTERS.hostFresh`` (``utils.profiling.count_fresh``, counted by
site where the program makes a result, a buffer of the call or a fetch from
the device).  The allocator maps each of them fresh from the system, so its
first writer pays a page fault a page (about 1.0 s a GB on the chip's host,
PERF.md) and the host's minor faults, which that machine does not count,
get a number.  Reported with the span metrics it stands beside: on a TPU
only.
"""
from perfbench.metrics import _spans

LAYER = "host process"
UNIT = "GiB"
MOVES = "train_s"


def read(sources: dict):
    fresh = (sources.get("counters") or {}).get("hostFresh")
    if fresh is None or _spans.tpu_trace(sources) is None:
        return None
    return sum(fresh.values()) / 2**30
