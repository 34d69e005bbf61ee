"""Operations and bytes of the ``lfm2_moe`` family (LFM2-8B-A1B), from shapes.

The yardstick of ``mfu.train`` and ``short_conv_roofline.train`` in that
family's cells, kept beside ``flops.py`` and ``flops_afmoe.py`` and under
their rules: nothing reads the program, a multiply-add is two operations,
and recomputed operations never count. The routed experts' grouped matmuls
are counted by ``flops_afmoe.grouped_matmul_step_flops_bytes``: the kernels
and their rows are the same.
"""
from chipbench.flops_afmoe import mean_keys_per_query


def layer_counts(cfg: dict) -> dict:
    """Layers by kind: ``conv`` and ``attn`` mixers, ``dense`` and ``moe`` FFNs."""
    kinds, n_dense = cfg["layer_types"], cfg["num_dense_layers"]
    return {"conv": sum(k == "conv" for k in kinds),
            "attn": sum(k == "full_attention" for k in kinds),
            "dense": n_dense, "moe": len(kinds) - n_dense}


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward operations per token, term by term.

    ``conv_proj``: a convolution mixer's two matmuls, ``C x 3C`` in and ``C x
    C`` out (the convolution itself and its two gates are ``2K + 2``
    operations a channel on the vector unit, no matmul: not counted, as
    norms and rotary are not). ``attn_proj``: q and o ``C x H D`` each, k
    and v ``C x Hkv D``. ``attn_pairs``: QK^T and PV, ``4 H D`` a (query,
    key) pair, pairs as the causal mask allows. ``dense_ffn``: three ``C x
    I`` matmuls in the leading dense layers. ``router``: ``C x E`` over all
    the experts. ``routed``: three ``C x F`` matmuls for each of a token's
    experts held here, in expectation ``top_k * held / E`` of them; there is
    no shared expert. ``head``: ``C x V`` over the vocabulary held (the
    tied matrix read the other way). The embedding is a gather.
    """
    C, H, Hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = C // H
    F, E, k = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_experts_per_tok"]
    held = cfg.get("experts_held", E)
    n = layer_counts(cfg)
    return {
        "conv_proj": n["conv"] * 2 * C * 4 * C,
        "attn_proj": n["attn"] * 2 * C * (2 * H * D + 2 * Hkv * D),
        "attn_pairs": n["attn"] * 4 * H * D * mean_keys_per_query(seq_len),
        "dense_ffn": n["dense"] * 6 * C * cfg["intermediate_size"],
        "router": n["moe"] * 2 * C * E,
        "routed": n["moe"] * 6 * C * F * k * held / E,
        "head": 2 * C * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward: the backward of a matmul is two matmuls of the
    same size, so three times the forward count."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def short_conv_step_flops_bytes(batch: int, seq_len: int, channels: int, taps: int,
                                layers: int, bytes_per_el: int = 2) -> tuple:
    """``(operations, bytes)`` the gated short convolutions of one training
    step need at least, over ``layers`` convolution layers: one forward and
    one backward, every tensor read or written once. Forward: ``bcx`` (three
    values a channel) read, ``y`` written, 4 a (token, channel); ``s``, the
    taps and the gate are ``2K + 2`` operations. Backward: ``bcx`` and ``dy``
    read, ``d bcx`` written, 7; ``s`` and the convolution rebuilt, the taps
    walked back and ``d w`` summed, ``6K + 5``. The weights' ``C K`` values
    are nothing beside them. A recomputed layer's second forward is in the
    kernels' time and not in this count."""
    cells = float(layers) * batch * seq_len * channels
    return cells * (8 * taps + 7), cells * 11 * bytes_per_el
