"""Programs set-up had to build, compiled or loaded from the persistent
compile cache (JAX's backend-compile event fires for both), from JAX
monitoring events.  Never 0 in a run that trains.
"""
LAYER = "compile"
UNIT = "count"
MOVES = "setup_s"


def read(sources: dict):
    meter = sources.get("compile")
    return None if not meter else meter["programs"]
