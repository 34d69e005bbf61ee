"""Device time a traced step spends in the flash kernels of the full
(causal, unwindowed) layers: ``flash_fwd``, ``flash_bwd_dkv``,
``flash_bwd_dq`` and not their ``_win`` namesakes."""
from chipbench import afmoe_spans, program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, afmoe_spans.FLASH_FULL)
