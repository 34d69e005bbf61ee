"""Operations and bytes of the ``granite_hybrid`` family (Granite-4.0-H), from
shapes.

The yardstick of ``mfu.train`` and ``ssd_scan_roofline.train`` in that
family's cells, kept beside ``flops.py`` and ``flops_lfm2_moe.py`` and under
their rules: nothing reads the program, a multiply-add is two operations,
and recomputed operations never count.
"""
from chipbench.flops_afmoe import mean_keys_per_query


def layer_counts(cfg: dict) -> dict:
    """Layers by mixer: ``mamba`` and ``attn``."""
    kinds = cfg["layer_types"]
    return {"mamba": sum(k == "mamba" for k in kinds),
            "attn": sum(k == "attention" for k in kinds)}


def scan_shapes(cfg: dict) -> dict:
    """The scan's sizes under the names the counts below take."""
    return dict(heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
                groups=cfg["mamba_n_groups"], state=cfg["mamba_d_state"],
                chunk=cfg["mamba_chunk_size"])


def scan_flops_per_token(heads: int, head_dim: int, groups: int, state: int,
                         chunk: int) -> tuple:
    """``(forward, backward)`` operations a token of the chunked scan at
    chunk ``Q`` performs, matmuls only, the causal half of each diagonal
    block (``Q (Q + 1) / 2`` pairs a chunk). Forward: ``C B^T`` once a group
    (``2 N`` a pair), ``M (dt x)`` a head (``2 P`` a pair), and a head's two
    passes over the state, ``C T`` and the state's update (``2 N P`` a
    token each). Backward, without rebuilding anything the forward made:
    ``dY Xd^T`` and ``M^T dY`` a head (``2 P`` a pair each), ``dG B`` and
    ``dG^T C`` a group, and four passes over the state a head (``dC``,
    ``dT``, ``B dT``, ``dB``). The decay's ``exp`` and sums run on the vector
    unit and are not counted, as norms are not."""
    H, P, G, N, Q = heads, head_dim, groups, state, chunk
    pairs = (Q + 1) / 2.0                    # a token's share of a chunk's causal pairs
    fwd = G * 2 * N * pairs + H * 2 * P * pairs + H * 4 * N * P
    bwd = H * 4 * P * pairs + G * 4 * N * pairs + H * 8 * N * P
    return fwd, bwd


def scan_bytes_per_token(heads: int, head_dim: int, groups: int, state: int,
                         bytes_per_el: int = 2) -> tuple:
    """``(forward, backward)`` bytes a token of the scan must move, every
    operand read or written once: forward ``x``, ``dt`` (fp32), ``B``, ``C``
    in and ``y`` out; backward ``x``, ``dt``, ``B``, ``C``, ``dy`` in and
    ``dx``, ``d dt``, ``dB``, ``dC`` out. The chunk states the forward
    hands the backward are the algorithm's own and not counted."""
    HP, GN = heads * head_dim, groups * state
    fwd = 2 * HP * bytes_per_el + 4 * heads + 2 * GN * bytes_per_el
    bwd = 3 * HP * bytes_per_el + 2 * 4 * heads + 4 * GN * bytes_per_el
    return fwd, bwd


def ssd_step_flops_bytes(batch: int, seq_len: int, heads: int, head_dim: int, groups: int,
                         state: int, chunk: int, layers: int) -> tuple:
    """``(operations, bytes)`` the scans of one training step need at least:
    one forward and one backward a Mamba layer. A recomputed layer's second
    forward is in the kernels' time and not in this count."""
    tokens = float(layers) * batch * seq_len
    ops = sum(scan_flops_per_token(heads, head_dim, groups, state, chunk))
    nbytes = sum(scan_bytes_per_token(heads, head_dim, groups, state))
    return tokens * ops, tokens * nbytes


def forward_flops_per_token(cfg: dict, seq_len: int) -> dict:
    """Forward operations per token, term by term.

    ``mamba_proj``: a Mamba mixer's in-projection ``C x (2 d_inner + 2 G N
    + heads)`` and out-projection ``d_inner x C``. ``scan``: the chunked
    scan's forward (:func:`scan_flops_per_token`); the convolution (``2
    d_conv`` a channel), the gates and norms run on the vector unit and are
    not counted. ``attn_proj``: q and o ``C x H D`` each, k and v ``C x Hkv
    D``. ``attn_pairs``: QK^T and PV, ``4 H D`` a (query, key) pair, pairs
    as the causal mask allows. ``mlp``: three ``C x I`` matmuls in every
    layer. ``head``: ``C x V`` over the vocabulary held (the tied matrix
    read the other way). The embedding is a gather.
    """
    C, H, Hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = C // H
    s = scan_shapes(cfg)
    inner = s["heads"] * s["head_dim"]
    n = layer_counts(cfg)
    return {
        "mamba_proj": n["mamba"] * 2 * C * (3 * inner + 2 * s["groups"] * s["state"]
                                            + s["heads"]),
        "scan": n["mamba"] * scan_flops_per_token(**s)[0],
        "attn_proj": n["attn"] * 2 * C * (2 * H * D + 2 * Hkv * D),
        "attn_pairs": n["attn"] * 4 * H * D * mean_keys_per_query(seq_len),
        "mlp": len(cfg["layer_types"]) * 6 * C * cfg["shared_intermediate_size"],
        "head": 2 * C * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward: the backward of a matmul is two matmuls of the
    same size, so three times the forward count (the scan's own backward
    count is two of its forwards as well)."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())
