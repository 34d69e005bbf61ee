"""How often a traced step runs a flash forward kernel (``flash_fwd``,
``flash_fwd_win``, under any ``jvp_`` / ``checkpoint_`` prefix; never a
``flash_bwd_*``): once a layer where a recomputed layer keeps the kernel's
result, twice where the backward pass rebuilds it. Events of the first
device, over the traced steps."""
import re

from chipbench import tracered

LAYER, UNIT, MOVES = "compiled step", "count", "train_tokens_per_s_per_chip"

FORWARD = re.compile("flash_fwd" + r"\S*" + tracered.CUSTOM_CALL)


def compute(samples, trace):
    steps = trace.span_count("bench.step") if trace else 0
    if not steps or not trace.device:
        return None
    calls = sum(1 for name, _s, _e in next(iter(trace.device.values())) if FORWARD.search(name))
    return calls / steps if calls else None
