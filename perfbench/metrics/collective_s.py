"""Seconds during which the first chip runs or has in flight an all-reduce,
all-gather, collective-permute, reduce-scatter or all-to-all in the traced
train (union of those intervals on the trace's XLA Ops and Async XLA Ops
lines).  The part of it no compute hides needs spans inside the program
(PERF.md, section 7).
"""
LAYER = "mesh"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    reduced = sources.get("trace")
    if not reduced or reduced["platform"] != "tpu":
        return None
    first = reduced["devices"][sorted(reduced["devices"])[0]]
    return first["collective_s"] or None
