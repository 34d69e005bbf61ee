"""Host clock round the first call into the system: trace, compile or cache
read, and the first run."""
LAYER, UNIT, MOVES = "entry / harness", "s", "setup_s"


def compute(samples, trace):
    return samples.get("first_call_s")
