"""Device milliseconds a traced step in the operations whose ``op_name``
holds the program's ``optimizer_update`` scope (``ShardedTrainer``'s
per-parameter update, its casts and layout pins). A fusion counts under
the one ``op_name`` XLA gives it: where the update is fused into its
weight-gradient matmul the fusion is named by the matmul and counts
elsewhere. Averaged over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "compiled step", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.scope_ms_per_step(trace, ("optimizer_update",))
