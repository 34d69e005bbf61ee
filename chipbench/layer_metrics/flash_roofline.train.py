"""Least time the chip could take for one step's attention over the time
its kernels took: operations and bytes from ``chipbench/flops.py``, peaks
from ``chipbench/peaks.py``. At these shapes the compute roof sets it."""
from chipbench import flops, peaks, tracered

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    kernel_s = trace.seconds_matching(tracered.CUSTOM_CALL) if steps else 0.0
    if not kernel_s:
        return None
    ops, nbytes = flops.attention_step_flops_bytes(**samples["attention"])
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (kernel_s / steps)
