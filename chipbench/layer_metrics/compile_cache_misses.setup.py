"""Compiles during set-up that the persistent cache could not answer: cache
requests minus hits. 0 on every run of a cell after its first."""
LAYER, UNIT, MOVES = "entry / harness", "count", "setup_s"


def compute(samples, trace):
    c = samples.get("setup_cache")
    return None if c is None else float(c["compile_requests"] - c["cache_hits"])
