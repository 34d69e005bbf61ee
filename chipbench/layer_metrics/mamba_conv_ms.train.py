"""Device milliseconds a traced step in the operations whose ``op_name``
holds the scope ``mamba_conv`` (``models/granite_hybrid.py``: the Mamba-2
mixer's causal convolution with bias and SiLU, ``ops.nn.causal_conv1d``),
forward, recomputed forward and backward. It reads the scope and not a
kernel's name, so XLA's fusions of the plain form and the kernel pair
(``causal_conv_fwd``, ``causal_conv_bwd``) are the same work read the same
way. Averaged over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.scope_ms_per_step(trace, ("mamba_conv",))
