"""DeepSeek-V3-style decoder (``model_type`` ``deepseek_v3``, e.g.
Kanana-2-30B-A3B): latent attention (MLA) and a fine-grained mixture of
experts with shared experts, as one chip of an expert-parallel deployment
runs it.

Forward contract, the chip's share (``experts_held`` from ``expert_first``,
the slice ``vocab_size``), ``remat``, ``routing()`` and ``expert_rows()`` are
:class:`~.afmoe.DecoderLM`'s, and the loss is :func:`~.afmoe.afmoe_lm_loss`:
the two families share the shell, the norms, the gated FFN and the routed
half (``AfmoeMoE`` over ``parallel/moe_dropless.py``). What is this
family's own (``h`` the residual stream, every norm an RMSNorm, no bias):

- ``h = E[ids]`` (no embedding scale); final norm, untied head.
- Layer, pre-norm: ``a = h + Attn(N1 h)``, ``h' = a + FFN(N2 a)``.
- Attention (``H`` heads, ``dn`` = ``qk_nope_head_dim``, ``dr`` =
  ``qk_rope_head_dim``, ``dv`` = ``v_head_dim``, ``r`` = ``kv_lora_rank``)::

      q = Wq x                           -> (H, dn + dr): q_n | q_r a head
      [c | k_r] = Wkva x                 -> r | dr: k_r ONE vector a token
      [k_n | v] = Wkvb RMSNorm_r(c)      -> (H, dn + dv) a head
      q_r, k_r = rotary(., positions) on the pairs (2i, 2i + 1)
      s_h[i, j] = (q_n[h, i] . k_n[h, j] + q_r[h, i] . k_r[j]) / sqrt(dn + dr)
      Attn = Wo concat_h(softmax_j(s_h) v_h),  j <= i, keys masked by length

  The score's second term reads one key for all heads: it goes to
  ``dot_product_attention(shared=(q_r, k_r))``, whose flash kernels fetch
  that key through the block index, so no ``(H, dn + dr)`` key and no ``H``
  copies of ``k_r`` are built. ``q_lora_rank`` must be null (the query is
  projected directly, without a norm) and ``rope_scaling`` null.
- FFN: ``W2 (silu(W1 x) * W3 x)`` in the first ``first_k_dense_replace``
  layers; after them (every ``moe_layer_freq``-th) sigmoid scores over all
  ``n_routed_experts`` in fp32, the ``num_experts_per_tok`` largest of
  ``score + e_score_correction_bias``, weights renormalised
  (``norm_topk_prob``) and scaled by ``routed_scaling_factor``, plus
  ``n_shared_experts`` shared experts as one gated FFN of their summed
  width. With ``n_group`` = ``topk_group`` = 1 the group-limited selection
  (``noaux_tc``) is this plain top-k; other values are refused. The bias is
  a buffer outside the gradient, held at zero.
"""
from __future__ import annotations

import jax

from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from ..ops.attention import dot_product_attention
from ..ops.pallas.moe_gmm import TILE_ROWS
from .afmoe import AfmoeMoE, DecoderLM, GatedFFN, RMSNorm, _dense, rotary

__all__ = ["DeepseekV3Model", "DeepseekV3DecoderLayer", "MLAttention",
           "get_deepseek_v3"]


class MLAttention(HybridBlock):
    """Causal multi-head latent attention (module docstring): keys and
    values are an up-projection of one normed ``rank``-wide latent a token,
    and the rotary part of the key is one ``rope_dim``-wide vector that all
    heads read."""

    def __init__(self, units: int, num_heads: int, nope_dim: int, rope_dim: int,
                 v_dim: int, rank: int, rope_theta: float = 10000.0,
                 epsilon: float = 1e-6, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._heads, self._theta = num_heads, rope_theta
        self._nope, self._rope, self._v, self._rank = nope_dim, rope_dim, v_dim, rank
        with self.name_scope():
            self.q = _dense(num_heads * (nope_dim + rope_dim), units, dtype, "q_")
            self.kv_a = _dense(rank + rope_dim, units, dtype, "kv_a_")
            self.kv_norm = RMSNorm(rank, epsilon, prefix="kv_norm_")
            self.kv_b = _dense(num_heads * (nope_dim + v_dim), rank, dtype, "kv_b_")
            self.o = _dense(units, num_heads * v_dim, dtype, "o_")

    def hybrid_forward(self, F, x, positions, key_mask):
        B, L = x.shape[0], x.shape[1]
        H, dn, dr, dv = self._heads, self._nope, self._rope, self._v
        with jax.named_scope("mla_q"):
            q = self.q(x)._data.reshape(B, L, H, dn + dr)
            q_n, q_r = q[..., :dn], q[..., dn:]
        with jax.named_scope("mla_latent"):
            latent = self.kv_a(x)._data
            k_r = latent[..., self._rank:].reshape(B, L, 1, dr)
            c = self.kv_norm(NDArray(latent[..., :self._rank], ctx=x.context))
        with jax.named_scope("mla_kv_up"):
            kv = self.kv_b(c)._data.reshape(B, L, H, dn + dv)
            k_n, v = kv[..., :dn], kv[..., dn:]
        with jax.named_scope("mla_attention"):
            q_r, k_r = (rotary(t, positions._data, self._theta, interleaved=True)
                        for t in (q_r, k_r))
            q_n, k_n, v, q_r, k_r = (t.transpose(0, 2, 1, 3)
                                     for t in (q_n, k_n, v, q_r, k_r))
            out = dot_product_attention(
                q_n, k_n, v, mask=key_mask._data[:, None, None, :], causal=True,
                scale=(dn + dr) ** -0.5, shared=(q_r, k_r))
            out = out.transpose(0, 2, 1, 3).reshape(B, L, H * dv)
        return self.o(NDArray(out, ctx=x.context))


class DeepseekV3DecoderLayer(HybridBlock):
    """Pre-norm layer: ``a = h + Attn(N1 h)``, ``h' = a + FFN(N2 a)``.
    Returns ``(h', rows)``: ``rows`` what a MoE FFN counted, ``None`` from a
    dense one."""

    def __init__(self, units: int, attention: MLAttention, ffn: HybridBlock,
                 epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn, self.ffn = attention, ffn
            self.register_child(attention, "attn")
            self.register_child(ffn, "ffn")
            self.norm1 = RMSNorm(units, epsilon, prefix="norm1_")
            self.norm2 = RMSNorm(units, epsilon, prefix="norm2_")

    def hybrid_forward(self, F, x, positions, key_mask):
        x = x + self.attn(self.norm1(x), positions, key_mask)
        y = self.ffn(self.norm2(x))
        y, rows = y if isinstance(y, tuple) else (y, None)
        return x + y, rows


class DeepseekV3Model(DecoderLM):
    """The decoder (module docstring); ``cfg`` as :func:`get_deepseek_v3`
    lists it."""

    def decoder_layers(self, cfg, dtype):
        for key in ("q_lora_rank", "rope_scaling"):
            if cfg.get(key) is not None:
                raise ValueError(f"deepseek_v3: {key}={cfg[key]!r} is not supported (null only)")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("deepseek_v3: group-limited routing needs n_group = topk_group = 1")
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        held = (cfg.get("expert_first", 0),
                cfg.get("experts_held", cfg["n_routed_experts"]))
        for i in range(cfg["num_hidden_layers"]):
            pre = f"layer{i}_"
            attention = MLAttention(
                units, cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
                rope_theta=cfg["rope_theta"], epsilon=eps, dtype=dtype,
                prefix=pre + "attn_")
            if i >= cfg["first_k_dense_replace"] and i % cfg.get("moe_layer_freq", 1) == 0:
                ffn = AfmoeMoE(
                    units, cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                    cfg["num_experts_per_tok"], held,
                    num_shared=cfg["n_shared_experts"],
                    route_norm=cfg["norm_topk_prob"],
                    route_scale=cfg["routed_scaling_factor"],
                    tile_rows=cfg.get("moe_tile_rows", TILE_ROWS), dtype=dtype,
                    prefix=pre + "moe_")
            else:
                ffn = GatedFFN(units, cfg["intermediate_size"], dtype,
                               prefix=pre + "ffn_")
            yield DeepseekV3DecoderLayer(units, attention, ffn, eps, prefix=pre)


def get_deepseek_v3(cfg: dict, dtype="float32", remat: bool = False,
                    **kwargs) -> DeepseekV3Model:
    """Model-zoo constructor from a configuration under the source's keys
    (``config.json`` of ``model_type`` ``deepseek_v3``): ``hidden_size``,
    ``num_hidden_layers``, ``num_attention_heads``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``kv_lora_rank``, ``q_lora_rank``
    (null), ``rope_theta``, ``rope_scaling`` (null), ``rms_norm_eps``,
    ``intermediate_size``, ``first_k_dense_replace``, ``moe_layer_freq``,
    ``moe_intermediate_size``, ``n_routed_experts``, ``num_experts_per_tok``,
    ``n_shared_experts``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``n_group``, ``topk_group`` (1), ``vocab_size``; and the chip's share:
    ``experts_held`` (default all), ``expert_first`` (default 0),
    ``moe_tile_rows``."""
    return DeepseekV3Model(cfg, dtype=dtype, remat=remat, **kwargs)
