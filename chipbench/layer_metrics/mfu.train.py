"""Model FLOP/s utilization over the traced window: operations the forward
and backward passes need per token (``chipbench/flops.py``, from shapes;
recomputation never counts) times tokens per second per chip, over the
chip's published bf16 peak (``chipbench/peaks.py``)."""
from chipbench import peaks

LAYER, UNIT, MOVES = "compiled step", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    if not steps or not trace.window_s:
        return None
    tokens_per_s = steps * samples["tokens_per_step"] / samples["chips"] / trace.window_s
    peak = peaks.peak(samples["device_kind"])["bf16_flops_per_s"]
    return 100.0 * samples["flops_per_token"] * tokens_per_s / peak
