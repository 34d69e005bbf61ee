"""Device time a traced step spends in the row kernels round the routed
experts' grouped matmuls (``moe_rows_pack``, ``moe_rows_gather``,
``moe_rows_combine``, ``moe_rows_dot``, ``moe_rows_gate``,
``moe_rows_gate_bwd``): what moving rows into the dropless buffer and out of
it costs, which follows the rows in use and not the buffer."""
from chipbench import program_spans

LAYER, UNIT, MOVES = "router", "ms", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    return program_spans.kernel_ms_per_step(trace, r"moe_rows")
