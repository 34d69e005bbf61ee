"""Least time the chip could take for one step's attention (pairs counted as
the causal mask and the window allow, 3.5 x forward, grouped K/V bytes:
``chipbench/flops_afmoe.py``) over the time its flash kernels took, windowed
and full together. The kernels' time holds the recomputed forward of a
rematerialised layer; the count does not."""
from chipbench import afmoe_spans, flops, flops_afmoe, peaks, program_spans

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    kernel_ms = [program_spans.kernel_ms_per_step(trace, rx)
                 for rx in (afmoe_spans.FLASH_WINDOW, afmoe_spans.FLASH_FULL)]
    inputs = samples.get("attention") or {}
    if not any(kernel_ms) or "windows" not in inputs:
        return None
    ops, nbytes = flops_afmoe.attention_step_flops_bytes(
        **{k: inputs[k] for k in ("batch", "seq_len", "heads", "kv_heads", "head_dim", "windows")})
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (sum(ms or 0.0 for ms in kernel_ms) * 1e-3)
