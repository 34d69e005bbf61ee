"""Device milliseconds a traced step in the operations jax traced as a
recomputed layer's forward (``rematted_computation`` in the ``op_name``:
``jax.checkpoint``, which the decoders' ``remat=True`` puts round each
layer). Overlaps the layer metrics: a recomputed norm counts in
``norm_ms.train`` too. Averaged over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "compiled step", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.scope_ms_per_step(trace, op_scopes.RECOMPUTE)
