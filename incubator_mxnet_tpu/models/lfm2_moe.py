"""LFM2-MoE-style decoder (``model_type`` ``lfm2_moe``, e.g. LFM2-8B-A1B):
layers whose token mixer is a gated short convolution beside layers of
grouped-query attention, and a mixture of experts without a shared expert,
as one chip of an expert-parallel deployment runs it.

Forward contract, the chip's share (``experts_held`` from ``expert_first``,
the slice ``vocab_size``), ``remat``, ``routing()`` and ``expert_rows()`` are
:class:`~.afmoe.DecoderLM`'s, the loss is :func:`~.afmoe.afmoe_lm_loss`, the
pre-norm layer is :class:`~.deepseek_v3.DeepseekV3DecoderLayer` (its
``attn`` is whichever mixer the layer has) and the routed half is
``AfmoeMoE`` over ``parallel/moe_dropless.py``. What is this family's own
(``h`` the residual stream, every norm an RMSNorm with ``norm_eps``, no
bias anywhere):

- ``h = E[ids]``; ``logits = E^T N(h)``: the embedding is tied (the family's
  ``embedding_norm`` is the shell's ``norm``).
- Layer: ``a = h + Mix(N1 h)``, ``h' = a + FFN(N2 a)``, ``Mix`` by
  ``layer_types[i]``.
- ``conv`` (``K = conv_L_cache`` taps)::

      [Bg | Cg | x] = Win u                     -> 3 x C, split in that order
      s = Bg * x
      c[t] = sum_k w[:, k] * s[t - (K - 1) + k]   (depthwise, causal: s zero before the row)
      Mix = Wout (Cg * c)

  No attention, no positions, no key mask: the convolution crosses document
  boundaries inside a row, and what a padded position holds reaches at most
  ``K - 1`` positions that are themselves past the row's valid length. The
  middle three lines are one op, ``ops.nn.short_conv_gate`` (on a TPU the
  kernel pair of ``ops/pallas/short_conv.py``).
- ``full_attention``: ``H`` query heads over ``Hkv`` key/value heads of
  ``hidden / H``; q and k RMS-normed per head with a learnt scale
  (``q_layernorm``, ``k_layernorm``), then rotary (halves rotated, the whole
  head) on both; causal softmax at ``head_dim ** -0.5``, keys masked by
  length.
- FFN: ``W2 (silu(W1 x) * W3 x)`` at ``intermediate_size`` in the first
  ``num_dense_layers`` layers; after them sigmoid scores over all
  ``num_experts`` in fp32, the ``num_experts_per_tok`` largest of ``score +
  expert_bias`` (``use_expert_bias``; a buffer outside the gradient, held
  at zero), weights the chosen scores over their sum + 1e-6
  (``norm_topk_prob``) times ``routed_scaling_factor``, each expert a gated
  FFN at ``moe_intermediate_size``, **no shared expert**.

Under ``remat=True`` a recomputed convolution layer holds nothing but its
input: the policy of ``ops.attention.checkpoint_layer`` keeps the flash
kernel's result by name, and a layer without that kernel has no such name.
"""
from __future__ import annotations

import jax

from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from ..ops.attention import dot_product_attention
from ..ops.nn import short_conv_gate
from ..ops.pallas.moe_gmm import TILE_ROWS
from .afmoe import AfmoeMoE, DecoderLM, GatedFFN, RMSNorm, _dense, normed_heads
from .deepseek_v3 import DeepseekV3DecoderLayer

__all__ = ["Lfm2MoeModel", "Lfm2ShortConv", "Lfm2Attention", "get_lfm2_moe"]


class Lfm2ShortConv(HybridBlock):
    """The gated short convolution as a token mixer (module docstring);
    called as an attention block is, and reads neither positions nor mask."""

    def __init__(self, units: int, taps: int, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(3 * units, units, dtype, "in_proj_")
            self.conv_weight = self.params.get("weight", shape=(units, taps), dtype=dtype)
            self.out_proj = _dense(units, units, dtype, "out_proj_")

    def hybrid_forward(self, F, x, positions, key_mask, conv_weight):
        with jax.named_scope("lfm2_mixer_conv"):
            y = short_conv_gate(self.in_proj(x)._data, conv_weight._data)
            return self.out_proj(NDArray(y, ctx=x.context))


class Lfm2Attention(HybridBlock):
    """Causal attention over grouped K/V heads, q and k normed per head and
    rotated by position (module docstring)."""

    def __init__(self, units: int, num_heads: int, num_kv_heads: int,
                 rope_theta: float = 1000000.0, epsilon: float = 1e-5,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, units // num_heads
        self._theta, self._epsilon = rope_theta, epsilon
        D = self._dim
        with self.name_scope():
            self.q = _dense(num_heads * D, units, dtype, "q_")
            self.k = _dense(num_kv_heads * D, units, dtype, "k_")
            self.v = _dense(num_kv_heads * D, units, dtype, "v_")
            self.o = _dense(units, num_heads * D, dtype, "o_")
            self.q_norm = RMSNorm(D, epsilon, prefix="q_norm_")
            self.k_norm = RMSNorm(D, epsilon, prefix="k_norm_")

    def hybrid_forward(self, F, x, positions, key_mask):
        B, L = x.shape[0], x.shape[1]
        H, Hkv, D = self._heads, self._kv_heads, self._dim
        with jax.named_scope("lfm2_mixer_attn"):
            q, k = normed_heads(self, x, positions._data)
            v = self.v(x)._data.reshape(B, L, Hkv, D).transpose(0, 2, 1, 3)
            out = dot_product_attention(
                q, k, v, mask=key_mask._data[:, None, None, :], causal=True,
                scale=D ** -0.5)
            out = out.transpose(0, 2, 1, 3).reshape(B, L, H * D)
            return self.o(NDArray(out, ctx=x.context))


class Lfm2MoeModel(DecoderLM):
    """The decoder (module docstring); ``cfg`` as :func:`get_lfm2_moe`
    lists it."""

    def __init__(self, cfg: dict, dtype="float32", remat: bool = False, **kwargs):
        if cfg.get("conv_bias"):
            raise ValueError("lfm2_moe: conv_bias=True is not supported (no bias anywhere)")
        if cfg["hidden_size"] % cfg["num_attention_heads"]:
            raise ValueError("lfm2_moe: hidden_size is not a whole number of heads")
        super().__init__(cfg, dtype, remat, tie_embeddings=True,
                         epsilon=cfg["norm_eps"], **kwargs)

    def decoder_layers(self, cfg, dtype):
        units, eps = cfg["hidden_size"], cfg["norm_eps"]
        held = (cfg.get("expert_first", 0),
                cfg.get("experts_held", cfg["num_experts"]))
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"
            if kind == "conv":
                mixer = Lfm2ShortConv(units, cfg["conv_L_cache"], dtype, prefix=pre + "conv_")
            elif kind == "full_attention":
                mixer = Lfm2Attention(
                    units, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    rope_theta=cfg["rope_theta"], epsilon=eps, dtype=dtype,
                    prefix=pre + "attn_")
            else:
                raise ValueError(f"lfm2_moe: layer_types[{i}]={kind!r} is neither "
                                 "'conv' nor 'full_attention'")
            if i < cfg["num_dense_layers"]:
                ffn = GatedFFN(units, cfg["intermediate_size"], dtype, prefix=pre + "ffn_")
            else:
                ffn = AfmoeMoE(
                    units, cfg["moe_intermediate_size"], cfg["num_experts"],
                    cfg["num_experts_per_tok"], held, num_shared=0,
                    route_norm=cfg["norm_topk_prob"],
                    route_scale=cfg["routed_scaling_factor"],
                    tile_rows=cfg.get("moe_tile_rows", TILE_ROWS), dtype=dtype,
                    route_norm_eps=1e-6, prefix=pre + "moe_")
            yield DeepseekV3DecoderLayer(units, mixer, ffn, eps, prefix=pre)


def get_lfm2_moe(cfg: dict, dtype="float32", remat: bool = False,
                 **kwargs) -> Lfm2MoeModel:
    """Model-zoo constructor from a configuration under the source's keys
    (``config.json`` of ``model_type`` ``lfm2_moe``): ``hidden_size``,
    ``num_attention_heads``, ``num_key_value_heads``, ``layer_types``
    (``"conv"`` / ``"full_attention"``, one a layer), ``conv_L_cache``,
    ``conv_bias`` (false), ``rope_theta``, ``norm_eps``,
    ``intermediate_size``, ``num_dense_layers``, ``moe_intermediate_size``,
    ``num_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
    ``routed_scaling_factor``, ``vocab_size``; and the chip's share:
    ``experts_held`` (default all), ``expert_first`` (default 0),
    ``moe_tile_rows``."""
    return Lfm2MoeModel(cfg, dtype=dtype, remat=remat, **kwargs)
