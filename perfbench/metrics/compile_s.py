"""Seconds of trace + lower + backend compile over set-up, from JAX monitoring
events (perfbench/compile_meter.py).  For a program found in the persistent
cache the backend-compile event lasts as long as the load.
"""
LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(sources: dict):
    meter = sources.get("compile")
    return None if not meter else meter["compile_s"]
