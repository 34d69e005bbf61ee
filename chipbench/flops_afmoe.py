"""Operations and bytes of the AFMoE family (Trinity-Mini), from shapes.

The yardstick of ``mfu.train``, ``attn_roofline.train`` and
``moe_gmm_roofline.train`` in that family's cells, kept beside ``flops.py``
and under its rules: nothing reads the program, a multiply-add is two
operations, and recomputed operations (a layer rebuilt in the backward pass,
the flash backward's second look at QK^T beyond what the algorithm needs)
never count.
"""


def mean_keys_per_query(seq_len: int, window=None) -> float:
    """Keys a query sees under the causal mask, averaged over a row of
    ``seq_len`` full positions: query ``i`` sees ``min(i + 1, window)``."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward operations per token, term by term.

    ``attn_proj``: q, gate and output ``C x H D`` each, k and v ``C x Hkv D``.
    ``attn_pairs``: QK^T and PV, ``4 H D`` a (query, key) pair, pairs counted
    as the causal mask and the window allow. ``dense_ffn``: three ``C x I``
    matmuls in the leading dense layers. ``router``: ``C x E`` over all the
    experts. ``shared``: three ``C x F`` matmuls a shared expert. ``routed``:
    the same for each of a token's experts held here, in expectation
    ``top_k * held / E`` of them. ``head``: ``C x V`` over the vocabulary
    held. The embedding is a gather; norms, rotary, gates and the softmax
    are not matmuls; none counts.
    """
    C, D = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    F, E, k = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_experts_per_tok"]
    held = cfg.get("experts_held", E)
    kinds = cfg["layer_types"]
    n, n_dense = len(kinds), cfg["num_dense_layers"]
    pairs = sum(mean_keys_per_query(
        seq_len, cfg["sliding_window"] if kind == "sliding_attention" else None)
        for kind in kinds)
    return {
        "attn_proj": n * 2 * C * (3 * H * D + 2 * Hkv * D),
        "attn_pairs": 4 * H * D * pairs,
        "dense_ffn": n_dense * 6 * C * cfg["intermediate_size"],
        "router": (n - n_dense) * 2 * C * E,
        "shared": (n - n_dense) * 6 * C * F * cfg["num_shared_experts"],
        "routed": (n - n_dense) * 6 * C * F * k * held / E,
        "head": 2 * C * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward: the backward of a matmul is two matmuls of the
    same size, so three times the forward count."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def attention_step_flops_bytes(batch: int, seq_len: int, heads: int, kv_heads: int,
                               head_dim: int, windows: list, bytes_per_el: int = 2) -> tuple:
    """``(operations, bytes)`` the attention of one training step needs at
    least, over the layers of ``windows`` (None: full causal): forward
    ``4 D`` a pair and head, pairs as the mask allows; a flash backward
    rebuilds the probabilities, five matmuls to the forward's two, so 3.5
    times the forward in all (as ``flops.attention_step_flops_bytes``
    reckons); q, o, do, dq (``heads``) and k, v, dk, dv (``kv_heads``)
    cross HBM once each."""
    tokens = batch * seq_len
    pairs = sum(mean_keys_per_query(seq_len, w) for w in windows)
    ops = 3.5 * 4.0 * heads * head_dim * pairs * tokens
    nbytes = len(windows) * 4.0 * (heads + kv_heads) * head_dim * tokens * bytes_per_el
    return ops, nbytes


def grouped_matmul_step_flops_bytes(rows: list, groups: int, hidden: int, ffn: int,
                                    bytes_per_el: int = 2) -> tuple:
    """``(operations, bytes)`` of the routed experts' grouped matmuls in one
    training step, for the rows really present: ``rows[l]`` assignments held
    in MoE layer ``l``, ``groups`` experts of ``hidden x ffn`` each.

    A row costs ``6 C F`` forward (gate and up ``C x 2F``, down ``F x C``)
    and twice that backward (the rows' gradient and the weights'). Bytes:
    each of the six kernel calls a layer (two forward, two for the rows'
    gradient, two for the weights') reads its two operands and writes its
    result once; the weights and their gradients are ``3 groups C F``."""
    ops = nbytes = 0.0
    weights = 3.0 * groups * hidden * ffn
    for r in rows:
        ops += 18.0 * r * hidden * ffn
        # rows' side: x, h (2F), act, y forward; dy, dact, dh, dx and the
        # operands of the two weight-gradient calls backward
        acts = r * (hidden + 2 * ffn) + r * (ffn + hidden)          # forward calls
        acts += r * (hidden + ffn) + r * (2 * ffn + hidden)         # rows' gradient
        acts += r * (hidden + ffn) + r * (2 * ffn + hidden)         # weights' gradient
        nbytes += (acts + 3.0 * weights) * bytes_per_el
    return ops, nbytes
