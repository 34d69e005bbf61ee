"""Operations and bytes of the ``deepseek_v3`` family (Kanana-2-30B-A3B),
from shapes.

The yardstick of ``mfu.train`` and ``mla_attn_roofline.train`` in that
family's cells, kept beside ``flops.py`` and ``flops_afmoe.py`` and under
their rules: nothing reads the program, a multiply-add is two operations,
and recomputed operations never count. The routed experts' grouped matmuls
are counted by ``flops_afmoe.grouped_matmul_step_flops_bytes``: the kernels
and their rows are the same.
"""
from chipbench.flops_afmoe import mean_keys_per_query


def moe_layers(cfg: dict) -> int:
    """MoE layers: from ``first_k_dense_replace`` on, every ``moe_layer_freq``-th."""
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i >= cfg["first_k_dense_replace"] and i % cfg.get("moe_layer_freq", 1) == 0)


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward operations per token, term by term.

    ``attn_proj``: the query ``C x H (dn + dr)``, the down-projection ``C x
    (r + dr)``, the up-projection ``r x H (dn + dv)`` and the output ``H dv x
    C``. ``attn_pairs``: the two score products and PV, ``2 H (dn + dr + dv)``
    a (query, key) pair, pairs counted as the causal mask allows (every
    layer is a full one). ``dense_ffn``: three ``C x I`` matmuls in the
    layers that are not MoE layers. ``router``: ``C x E`` over all the
    experts. ``shared``: three ``C x F`` matmuls a shared expert. ``routed``:
    the same for each of a token's experts held here, in expectation ``top_k
    * held / E`` of them. ``head``: ``C x V`` over the vocabulary held. The
    embedding is a gather; norms, rotary and the softmax are not matmuls;
    none counts.
    """
    C, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F, E, k = cfg["moe_intermediate_size"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    held = cfg.get("experts_held", E)
    n, n_moe = cfg["num_hidden_layers"], moe_layers(cfg)
    return {
        "attn_proj": n * 2 * (C * H * (dn + dr) + C * (r + dr) + r * H * (dn + dv) + H * dv * C),
        "attn_pairs": n * 2 * H * (dn + dr + dv) * mean_keys_per_query(seq_len),
        "dense_ffn": (n - n_moe) * 6 * C * cfg["intermediate_size"],
        "router": n_moe * 2 * C * E,
        "shared": n_moe * 6 * C * F * cfg["n_shared_experts"],
        "routed": n_moe * 6 * C * F * k * held / E,
        "head": 2 * C * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward: the backward of a matmul is two matmuls of the
    same size, so three times the forward count."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def mla_attention_step_flops_bytes(batch: int, seq_len: int, heads: int, nope_dim: int,
                                   rope_dim: int, v_dim: int, layers: int,
                                   bytes_per_el: int = 2) -> tuple:
    """``(operations, bytes)`` the latent attention of one training step
    needs at least, over ``layers`` full causal layers. A (query, key, head)
    pair costs ``2 (dn + dr + dv)`` forward (the two score products and PV)
    and, backward, the scores rebuilt, dq and dk (three products ``dn + dr``
    wide) and dp and dv (two ``dv`` wide): ``2 (3 (dn + dr) + 2 dv)``. Pairs
    as the causal mask allows. Bytes: q and dq (``H (dn + dr)``), o, do,
    k_n, v, dk_n and dv (``H`` times their width) and k_r, dk_r (``dr``,
    once for all heads) cross HBM once each a layer."""
    tokens = batch * seq_len
    qk = nope_dim + rope_dim
    pair = 2.0 * (qk + v_dim) + 2.0 * (3 * qk + 2 * v_dim)
    ops = layers * pair * heads * mean_keys_per_query(seq_len) * tokens
    per_token = heads * (2 * qk + 2 * v_dim + 2 * nope_dim + 2 * v_dim) + 2 * rope_dim
    return ops, float(layers * per_token * tokens * bytes_per_el)
