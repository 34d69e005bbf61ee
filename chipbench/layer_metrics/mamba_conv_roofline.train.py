"""Least time the chip could take for one step's causal convolutions over
the time the scope ``mamba_conv`` took in the traced steps
(``mamba_conv_ms.train``).

The least time is one forward and one backward a Mamba layer, every tensor
read or written once: ``x`` in and ``y`` out forward, ``x`` and ``dy`` in
and ``dx`` out backward, 5 values a (token, channel) at 2 bytes, over ``C =
heads * head_dim + 2 * groups * state`` channels (the convolution runs over
the scan's ``x | B | C``), with the layers, batch and length of the ``ssd``
shapes the family hands the readers. The taps and the bias are a few
kilobytes and not counted, and neither are the convolution's multiply-adds,
which run on the vector unit: the HBM roof sets the time. The scope's time
holds the recomputed forward of a rematerialised layer and the count does
not, so the share cannot pass about 71%."""
from chipbench import flops, op_scopes, peaks

LAYER, UNIT, MOVES = "kernels", "%", "train_tokens_per_s_per_chip"

VALUES = 5          # x, y forward; x, dy, dx backward
BYTES = 2           # bf16


def step_bytes(batch: int, seq_len: int, heads: int, head_dim: int, groups: int, state: int,
               layers: int, **_) -> float:
    """Bytes one step's convolutions must move at least."""
    channels = heads * head_dim + 2 * groups * state
    return float(layers) * batch * seq_len * channels * VALUES * BYTES


def compute(samples, trace):
    scope_ms = op_scopes.scope_ms_per_step(trace, ("mamba_conv",))
    shapes = (samples.get("attention") or {}).get("ssd")
    if not scope_ms or not shapes:
        return None
    least_s, _roof = flops.roofline_seconds(0.0, step_bytes(**shapes),
                                            peaks.peak(samples["device_kind"]))
    return 100.0 * least_s / (scope_ms * 1e-3)

