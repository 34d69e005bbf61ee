"""Device milliseconds a traced step in the collectives' own operations
(``collective_ms.train``) while no other operation runs on that chip:
what the collectives cost where compute does not hide them. At most
``collective_ms.train``."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "collectives", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    got = op_scopes.collective_seconds(trace) if steps else None
    return got[1] / steps * 1e3 if got else None
