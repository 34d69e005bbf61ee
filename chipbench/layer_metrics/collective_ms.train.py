"""Device milliseconds a traced step in the collectives' own operations:
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``collective-permute``,
``all-to-all`` (a ``-start`` and a ``-done`` each for its own event), or a
fusion that calls one, as the compiled step's text says (``op_scopes``).
The union on each chip, averaged over the chips."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "collectives", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    got = op_scopes.collective_seconds(trace) if steps else None
    return got[0] / steps * 1e3 if got else None
