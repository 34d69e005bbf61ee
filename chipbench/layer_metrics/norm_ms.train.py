"""Device milliseconds a traced step in the operations whose ``op_name``
holds ``layer_norm`` or ``rms_norm`` (``ops.nn``'s two norms): forward,
recomputation and backward. A norm's statistic that XLA fuses into a
matmul's epilogue is named by the matmul and counts elsewhere. Averaged
over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "compiled step", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.scope_ms_per_step(trace, op_scopes.NORMS)
