"""Seconds jax reports for getting the step's executable, from the
program's ``compile_log`` at the site ``trainer.step``: the backend
compile's own time (warm: hashing the key, loading the executable) plus
the persistent cache's read."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "entry / harness", "s", "setup_s"


def compute(samples, trace):
    phases = program_spans.compile_phases("trainer.step")
    return phases["backend_compile_s"] + phases["cache_retrieval_s"] if phases else None
