"""Device time a traced step spends in the routed experts' grouped-matmul
kernels (``moe_gmm`` forward and rows' gradient, ``moe_tgmm`` weights'
gradient)."""
from chipbench import afmoe_spans, program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, afmoe_spans.MOE_GMM)
