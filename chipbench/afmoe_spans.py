"""What the readers of the AFMoE family's kernels and counters share.

Kernel names as ``pl.pallas_call(name=...)`` gives them, inside the HLO
instruction's name (``checkpoint_flash_fwd_win.7 custom-call tpu_custom_call
(...)``): a windowed flash call carries ``_win``, a full one does not. The
routing counters are the program's own: each MoE layer's ``expert_rows``
buffer, which the compiled step writes with the rows each held expert got,
kept step by step by the family (``families/afmoe.py`` ``STEP_ROWS``) and
read here after the window. A program without the kernel or the buffer gives
``None``, never an error.
"""
import numpy as np

#: kernel names for ``program_spans.kernel_ms_per_step`` (regular expressions)
FLASH_WINDOW = r"flash_(fwd|bwd_dkv|bwd_dq)_win"
FLASH_FULL = r"flash_(fwd|bwd_dkv|bwd_dq)(?!_win)"
MOE_GMM = r"moe_t?gmm"


def traced_rows(trace):
    """``(traced steps, MoE layers, experts held)``: the rows each held
    expert got in each step the trace covers, as those steps counted them.
    The ``train_steps`` kind traces the window's steps from ``WARMUP_STEPS``
    on, after as many warm-up steps: one ``bench.step`` span each."""
    from chipbench.families import afmoe
    from chipbench.traffic.train_steps import WARMUP_STEPS
    steps = trace.span_count("bench.step") if trace else 0
    first = 2 * WARMUP_STEPS
    if not steps or len(afmoe.STEP_ROWS) < first + steps:
        return None
    return np.stack([np.asarray(r) for r in afmoe.STEP_ROWS[first:first + steps]])
