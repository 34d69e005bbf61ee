"""Device busy time (union of the device's operation intervals) per traced
step."""
LAYER, UNIT, MOVES = "compiled step", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    if not steps or not trace.busy_s:
        return None
    return trace.busy_s / steps * 1e3
