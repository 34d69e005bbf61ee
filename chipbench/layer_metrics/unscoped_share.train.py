"""Per cent of the device's busy time in operations whose ``op_name``
holds none of the program's scopes (``op_scopes.SCOPES``), or that the
compiled step's text does not name: what the scope metrics cannot see."""
from chipbench import op_scopes

LAYER, UNIT, MOVES = "compiled step", "%", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return op_scopes.unscoped_share(trace)
