"""Operations and bytes the algorithm needs, counted from shapes.

The yardstick of ``mfu.train`` and ``flash_roofline.train``. Nothing here
reads the program: every count follows from the configuration file's sizes
and the cell's batch shape. A multiply-add is two operations. Recomputed
operations (remat, the flash backward's second look at QK^T beyond what the
algorithm itself needs) never count towards MFU.
"""


def bert_forward_flops_per_token(cfg: dict, seq_len: int, masked: int) -> dict:
    """Forward operations per token of BERT pretraining, term by term.

    ``layers``: per layer the four d x d projections (q, k, v, output) and
    the two d x ffn matmuls, ``2 * (4 d^2 + 2 d ffn)``, plus attention's two
    L x L matmuls, ``4 L d`` per token. ``mlm``: transform ``d^2`` and the
    tied decoder ``d V``, on the ``masked`` of ``seq_len`` positions only.
    ``heads``: pooler ``d^2`` and NSP ``2 d`` once per sequence. The
    embedding lookups are gathers, not matmuls, and count nothing.
    """
    d, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    n, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    return {
        "layers": n * (2 * (4 * d * d + 2 * d * ffn) + 4 * seq_len * d),
        "mlm": 2 * (d * d + d * vocab) * masked / seq_len,
        "heads": 2 * (d * d + 2 * d) / seq_len,
    }


def bert_train_flops_per_token(cfg: dict, seq_len: int, masked: int) -> float:
    """Forward + backward: the backward of a matmul is two matmuls of the
    same size, so three times the forward count."""
    return 3.0 * sum(bert_forward_flops_per_token(cfg, seq_len, masked).values())


def attention_step_flops_bytes(batch: int, heads: int, seq_len: int,
                               head_dim: int, layers: int,
                               bytes_per_el: int = 2) -> tuple:
    """``(operations, bytes)`` the attention of one training step needs at
    least, over all layers: forward ``4 B H L^2 D`` (QK^T and PV); a flash
    backward has to rebuild the probabilities, five matmuls to the
    forward's two, so 2.5 times that; q, k, v, o, do, dq, dk, dv cross HBM
    once each."""
    fwd = 4.0 * batch * heads * seq_len * seq_len * head_dim
    ops = layers * fwd * (1.0 + 2.5)
    nbytes = layers * 8.0 * batch * heads * seq_len * head_dim * bytes_per_el
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take and which roof sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
