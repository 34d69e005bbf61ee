"""Device time a traced step spends in the flash backward kernel for dQ
(``flash_bwd_dq``, one call a layer)."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, "flash_bwd_dq")
