"""1 - device busy / traced window."""
LAYER, UNIT, MOVES = "device", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    idle = trace.idle_share if trace else None
    return None if idle is None else 100.0 * idle
