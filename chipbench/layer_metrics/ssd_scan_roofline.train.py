"""Least time the chip could take for one step's state-space scans (one
forward and one backward a Mamba layer: the chunked algorithm's matmuls and
every operand read or written once, ``chipbench/flops_granite_hybrid.py``;
the larger of the compute and the memory time) over the time the ``ssd_``
kernels took in the traced steps. The kernels' time holds the recomputed
forward of a rematerialised layer and the chunk states the forward hands
the backward; the count holds neither, so the share cannot pass 100%."""
from chipbench import flops, flops_granite_hybrid, peaks, program_spans

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"

KERNELS = r"ssd_"


def compute(samples, trace):
    kernel_ms = program_spans.kernel_ms_per_step(trace, KERNELS)
    shapes = (samples.get("attention") or {}).get("ssd")
    if not kernel_ms or not shapes:
        return None
    ops, nbytes = flops_granite_hybrid.ssd_step_flops_bytes(**shapes)
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (kernel_ms * 1e-3)
