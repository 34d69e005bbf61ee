"""Device time a traced step spends in the windowed flash kernels
(``flash_fwd_win``, ``flash_bwd_dkv_win``, ``flash_bwd_dq_win``: the sliding
layers' attention, forward, recomputed forward and backward)."""
from chipbench import afmoe_spans, program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, afmoe_spans.FLASH_WINDOW)
