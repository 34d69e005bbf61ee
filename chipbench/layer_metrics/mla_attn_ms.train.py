"""Device time a traced step spends in the latent-attention flash kernels:
``flash_fwd_mla``, ``flash_bwd_dkv_mla``, ``flash_bwd_dq_mla`` (the calls
with the shared second pair of operands) and no other flash kernel."""
from chipbench import mla_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return mla_spans.kernel_ms(trace)
