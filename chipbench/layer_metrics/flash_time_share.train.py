"""Share of device busy time spent in the flash attention kernels: the
step's only custom calls (forward, dkv, dq per layer)."""
from chipbench import tracered

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    if not trace or not trace.busy_s:
        return None
    return 100.0 * trace.seconds_matching(tracered.CUSTOM_CALL) / trace.busy_s
