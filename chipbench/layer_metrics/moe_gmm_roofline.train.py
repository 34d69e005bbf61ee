"""Least time the chip could take for one step's grouped matmuls, for the
rows really present in the traced steps (each MoE layer's ``expert_rows``
buffer as those steps wrote it, their mean; operations and bytes from
``chipbench/flops_afmoe.py``), over the time ``moe_gmm`` + ``moe_tgmm`` took
in the same steps. The kernels' time holds the recomputed forward of a
rematerialised layer; the count does not."""
from chipbench import afmoe_spans, flops, flops_afmoe, peaks, program_spans

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    kernel_ms = program_spans.kernel_ms_per_step(trace, afmoe_spans.MOE_GMM)
    moe = (samples.get("attention") or {}).get("moe")
    if not kernel_ms or not moe:
        return None
    rows = afmoe_spans.traced_rows(trace)
    if rows is None:
        return None
    ops, nbytes = flops_afmoe.grouped_matmul_step_flops_bytes(
        rows.sum(-1).mean(0).tolist(), moe["groups"], moe["hidden"], moe["ffn"])
    least_s, _roof = flops.roofline_seconds(ops, nbytes, peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (kernel_ms * 1e-3)
