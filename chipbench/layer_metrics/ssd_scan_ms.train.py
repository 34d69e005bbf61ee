"""Device time a traced step spends in the chunked state-space scan's
kernels (``ssd_fwd``, ``ssd_bwd``: ``ops/pallas/ssd.py``; every kernel the
scan runs is named ``ssd_...``), every Mamba layer's forward, recomputed
forward and backward together."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "kernels", "ms", "train_tokens_per_s_per_chip"

KERNELS = r"ssd_"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, KERNELS)
