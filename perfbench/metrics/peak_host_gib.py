"""Peak resident set of the benchmark's process (``ru_maxrss``) when the
window has ended: data, feature fit, the warm-up train and the window's
trains, in the one process that holds the chips.  The peak is reached in
set-up, which fits the feature stages and runs one whole train.  Host RAM
is the first limit the published shape meets (38.0 of 40 GiB at 1M rows,
PR 21).  No end-to-end metric: the driver's check read a spread of 4.4 % on
one chip, too wide for any bound it admits (PERF.md, section 2).
"""
LAYER = "host process"
UNIT = "GiB"
MOVES = "setup_s"


def read(sources: dict):
    kib = sources.get("peak_rss_kib")
    return kib / 2**20 if kib else None
