"""Device seconds, on the first chip, of the XLA modules that bin, grow, route
and score trees in the traced train.  Module names as the trace gives them
(my chip runs, PR 22): jit__gbt_chain_rounds_jit, jit__grow_chunk_rf_grid,
jit_predict_ensemble, jit__score_ensemble_jit, jit_apply_bins on one chip;
jit_shard_fn (the shard_map bodies of parallel/sharded.py) and
jit__grow_chunk on the mesh.  Under selector_refit no other shard_fn runs
(SanityChecker's sharded statistics are fitted in set-up).
"""
from perfbench import trace_reduce

#: jit names of models/gbdt_kernels.py, models/trees.py, parallel/sharded.py
PATTERN = (r"gbt_chain|gbt_round|grow_chunk|grow_forest|grow_tree|grow_rf"
           r"|predict_ensemble|predict_tree|predict_round|score_ensemble"
           r"|apply_bins|shard_fn|goss")

LAYER = "tree kernels"
UNIT = "s"
MOVES = "train_device_s"


def read(sources: dict):
    reduced = sources.get("trace")
    if not reduced or reduced["platform"] != "tpu":
        return None  # a CPU rehearsal has no device time
    return trace_reduce.module_seconds(reduced, PATTERN) or None
