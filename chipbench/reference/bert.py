"""BERT, plainly: forward pass and pretraining loss in float32 ``jax.numpy``.

Follows Devlin et al. 2018 (post-LN encoder, learned positions, GELU, tanh
pooler, MLM transform + decoder tied to the word embedding, NSP). No
kernels, no gluon, no batching tricks. ``params`` is the system's own
parameters as a dict of arrays keyed by name without the block prefix
(``word_embed_weight``, ``encoder_layer0_attn_qkv_weight``, ...); dense
weights are ``(out, in)``.

Departures from the paper, all the system's own layout: q, k and v come
from one ``(3d, d)`` projection split in thirds; GELU is the exact erf
form; padding is an additive key mask of -1e9 built from ``valid_length``;
no dropout (the comparison runs with dropout off).
"""
import jax
import jax.numpy as jnp


def _ln(x, gamma, beta, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


def _dense(x, p, name):
    return x @ p[name + "_weight"].T + p[name + "_bias"]


def forward(params, cfg, ids, token_types, valid_length, masked_positions=None):
    """``(seq, pooled, nsp, mlm)``; ``nsp``/``mlm`` are None where the
    parameters have no such head or no positions are given."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        heads, eps = cfg["num_attention_heads"], cfg["layer_norm_eps"]
        B, L = ids.shape
        x = (p["word_embed_weight"][ids] + p["token_type_embed_weight"][token_types]
             + p["position_weight"][:L][None])
        x = _ln(x, p["embed_ln_gamma"], p["embed_ln_beta"], eps)
        keep = jnp.arange(L)[None, :] < jnp.asarray(valid_length)[:, None]
        bias = jnp.where(keep, 0.0, -1e9)[:, None, None, :]
        d = x.shape[-1] // heads
        for i in range(cfg["num_hidden_layers"]):
            pre = f"encoder_layer{i}_"
            q, k, v = (t.reshape(B, L, heads, d).transpose(0, 2, 1, 3) for t in
                       jnp.split(_dense(x, p, pre + "attn_qkv"), 3, axis=-1))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(float(d)) + bias
            o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            o = o.transpose(0, 2, 1, 3).reshape(B, L, heads * d)
            x = _ln(x + _dense(o, p, pre + "attn_proj"),
                    p[pre + "ln1_gamma"], p[pre + "ln1_beta"], eps)
            h = jax.nn.gelu(_dense(x, p, pre + "ffn_ffn1"), approximate=False)
            x = _ln(x + _dense(h, p, pre + "ffn_ffn2"),
                    p[pre + "ln2_gamma"], p[pre + "ln2_beta"], eps)
        pooled = jnp.tanh(_dense(x[:, 0], p, "pooler"))
        nsp = _dense(pooled, p, "nsp") if "nsp_weight" in p else None
        mlm = None
        if masked_positions is not None and "decoder_bias" in p:
            h = x[jnp.arange(B)[:, None], masked_positions]
            h = jax.nn.gelu(_dense(h, p, "decoder_transform"), approximate=False)
            h = _ln(h, p["decoder_ln_gamma"], p["decoder_ln_beta"], eps)
            mlm = h @ p["word_embed_weight"].T + p["decoder_bias"]
        return x, pooled, nsp, mlm


def pretrain_loss(nsp, mlm, mlm_labels, mlm_weights, nsp_labels):
    """Weighted mean MLM cross-entropy plus mean NSP cross-entropy."""
    def nll(logits, labels):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), -1)[..., 0]
    w = jnp.asarray(mlm_weights, jnp.float32)
    return ((nll(mlm, mlm_labels) * w).sum() / (w.sum() + 1e-8)
            + nll(nsp, nsp_labels).mean())
