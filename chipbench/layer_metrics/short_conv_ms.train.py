"""Device time a traced step spends in the gated short convolution's kernel
pair (``short_conv_fwd``, ``short_conv_bwd``: ``ops/pallas/short_conv.py``),
every convolution layer's forward, recomputed forward and backward
together."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"

KERNELS = r"short_conv_(fwd|bwd)"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, KERNELS)
