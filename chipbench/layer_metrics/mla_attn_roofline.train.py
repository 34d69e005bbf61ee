"""Least time the chip could take for one step's latent attention (pairs
counted as the causal mask allows, the 64-wide shared key's bytes once for
all heads: ``chipbench/flops_deepseek_v3.py``) over the time its ``_mla``
kernels took. Recomputation never counts, and with the kernel's result kept
(``remat``) there is none in these kernels."""
from chipbench import flops, flops_deepseek_v3, mla_spans, peaks

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"

_SHAPES = ("batch", "seq_len", "heads", "nope_dim", "rope_dim", "v_dim", "layers")


def compute(samples, trace):
    kernel_ms = mla_spans.kernel_ms(trace)
    inputs = samples.get("attention") or {}
    if not kernel_ms or "rope_dim" not in inputs:
        return None
    ops, nbytes = flops_deepseek_v3.mla_attention_step_flops_bytes(
        **{k: inputs[k] for k in _SHAPES})
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (kernel_ms * 1e-3)
