"""Median of the program's ``step.dispatch`` spans in the trace: the call
of the jitted step, which returns once the step is enqueued."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "step driver (host)", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.host_span_ms_p50(trace, "step.dispatch")
