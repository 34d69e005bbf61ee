"""Device milliseconds a traced step in the operations whose ``op_name``
holds the scope ``mamba_mixer`` (``models/granite_hybrid.py``: the whole
Mamba-2 mixer, its in- and out-projections, the convolution, the scan and
the gated norm), forward, recomputation and backward. A weight-gradient
matmul fused with its AdamW update counts here when XLA names the fusion by
the matmul. Averaged over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "compiled step", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.scope_ms_per_step(trace, ("mamba_mixer",))
