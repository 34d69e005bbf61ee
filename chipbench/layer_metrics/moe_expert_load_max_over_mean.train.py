"""Rows of the busiest expert held over the mean of the experts held, in
the traced steps as they counted them (each MoE layer's ``expert_rows``
buffer): the mean over those steps, the worst of the MoE layers. 1 is an
even router; the grouped matmul's tiles follow it."""
from chipbench import afmoe_spans

LAYER, UNIT, MOVES = "router", "ratio", "train_tokens_per_s_per_chip"


def compute(samples, trace):
    rows = afmoe_spans.traced_rows(trace)
    if rows is None or not rows.sum(-1).all():
        return None
    return float((rows.max(-1) / rows.mean(-1)).mean(0).max())
