"""Device seconds, on the first chip, of the logistic-regression grid's XLA
modules in the traced train: jit_fit_logreg_grid (one Gram grid solve; my
chip run, PR 22).  The metric grid (jit__aupr_dev) is the evaluators', not
the solver's.  For the LR mix beside it, which is no cell of BENCHMARK.json
yet: the PR that enters the cell copies both under perfbench/.
"""
from perfbench import trace_reduce

#: jit names of models/linear.py
PATTERN = r"fit_logreg|logreg"

LAYER = "linear solver"
UNIT = "s"
MOVES = "train_s"


def read(sources: dict):
    reduced = sources.get("trace")
    if not reduced or reduced["platform"] != "tpu":
        return None  # a CPU rehearsal has no device time
    return trace_reduce.module_seconds(reduced, PATTERN) or None
