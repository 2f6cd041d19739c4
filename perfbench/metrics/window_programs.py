"""Programs the traced train itself built (compiled or loaded from the
persistent cache), from JAX monitoring events.  0 wherever the warm-up can
warm every program; above 0 only where the program builds its jitted
functions anew in every train, which today is the mesh path (the
configuration's file then says why and how many, ``window_programs_max``)."""
LAYER = "compile"
UNIT = "count"
MOVES = "train_s"


def read(sources: dict):
    return sources.get("window_programs")
