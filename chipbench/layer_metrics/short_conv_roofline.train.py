"""Least time the chip could take for one step's gated short convolutions
(one forward and one backward a convolution layer, every tensor read or
written once: 11 values a (token, channel), ``chipbench/flops_lfm2_moe.py``;
the memory roof sets it) over the time ``short_conv_fwd`` + ``short_conv_bwd``
took in the traced steps. The kernels' time holds the recomputed forward of
a rematerialised layer and the halo rows each tile fetches beside its own;
the count holds neither, so the share cannot pass 100%."""
from chipbench import flops, flops_lfm2_moe, peaks, program_spans

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"

KERNELS = r"short_conv_(fwd|bwd)"


def compute(samples, trace):
    kernel_ms = program_spans.kernel_ms_per_step(trace, KERNELS)
    shapes = (samples.get("attention") or {}).get("short_conv")
    if not kernel_ms or not shapes:
        return None
    ops, nbytes = flops_lfm2_moe.short_conv_step_flops_bytes(**shapes)
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (kernel_ms * 1e-3)
