"""Seconds jax reports for tracing the step to a jaxpr and lowering it to
a module, from the program's ``compile_log`` at the site ``trainer.step``.
Paid on every run, warm cache or not."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "entry / harness", "s", "setup_s"


def compute(samples, trace):
    phases = program_spans.compile_phases("trainer.step")
    return phases["trace_s"] + phases["lower_s"] if phases else None
