"""Granite-4.0-H-style hybrid decoder (``model_type`` ``granitemoehybrid``
without experts, e.g. Granite-4.0-H-Micro): Mamba-2 state-space layers
beside grouped-query attention layers that carry no positions, each with a
dense gated MLP.

Forward contract, ``remat`` and the loss are :class:`~.afmoe.DecoderLM`'s
and :func:`~.afmoe.afmoe_lm_loss`'s; ``vocab_size`` may be the slice held.
The equations (``h`` the residual stream, ``N`` an RMSNorm with a learnt
scale and ``rms_norm_eps``, no bias but the convolution's)::

    h = embedding_multiplier * E[ids];   logits = E^T N(h_last) / logits_scaling   (tied)
    a = h + r * Mix(N1 h);   h' = a + r * W2 (silu(W1 N2 a) * W3 N2 a)   (r = residual_multiplier)

``Mix`` by ``layer_types[i]``:

- ``mamba`` (``d_inner = mamba_expand * hidden`` = ``mamba_n_heads`` heads of
  ``mamba_d_head``, state ``mamba_d_state``, ``mamba_n_groups`` groups)::

      [z | xBC | dt] = W_in u                 widths d_inner | d_inner + 2 G N | heads
      xBC = silu(conv1d_causal(xBC) + b)      depthwise, mamba_d_conv taps
      [x | B | C] = xBC
      dt = softplus(dt + dt_bias);  A = -exp(A_log)
      y = ssd_scan(x, dt, A, B, C, D)         the recurrence of ops.ssm
      Mix = W_out (g * y silu(z) / rms(y silu(z)))     the statistic over d_inner

  It reads no positions and no key mask: the state crosses document
  boundaries inside a row, and a padded position (after a row's valid
  length) reaches only positions that are padded too. No ``dt`` clamp
  (``time_step_limit`` unset).
- ``attention``: ``H`` query heads over ``Hkv`` key/value heads of ``hidden /
  H``, no rotary and no q/k norm (``position_embedding_type`` ``nope``),
  causal softmax at ``attention_multiplier`` (not ``head_dim ** -0.5``),
  keys masked by length.

The initialisers are the Mamba-2 family's, so that a state carries across
chunks: ``A_log = log U[1, 16]``, ``dt_bias = softplus^-1(exp U[log 1e-3, log
1e-1])``, ``D = 1``, the convolution's taps and bias ``U[+-1/sqrt(taps)]``
(PyTorch's ``Conv1d``); every other weight the caller's initializer.

Under ``remat=True`` a recomputed Mamba layer holds nothing but its input
(``ops.attention.checkpoint_layer`` keeps only the flash kernel's result,
which such a layer has not); the scan's own backward rebuilds its chunk
states from the recomputed forward.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import random as _rng
from ..gluon.block import HybridBlock
from ..initializer import Initializer
from ..ndarray import NDArray
from ..ops.attention import dot_product_attention
from ..ops.nn import causal_conv1d, rms_norm_gated
from ..ops.ssm import ssd_scan
from .afmoe import DecoderLM, GatedFFN, RMSNorm, _dense

__all__ = ["GraniteHybridModel", "GraniteMamba", "GraniteAttention", "GraniteHybridLayer",
           "get_granite_hybrid"]


class _Draw(Initializer):
    """A parameter drawn by ``fn(key, shape)`` from the context's stream,
    whatever its name."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def __call__(self, name, arr):
        arr._set_data(self._fn(_rng.next_key(arr.context), arr.shape).astype(arr._data.dtype))


def _uniform(lo, hi):
    return lambda key, shape: jax.random.uniform(key, shape, jnp.float32, lo, hi)


def _a_log(key, shape):
    return jnp.log(_uniform(1.0, 16.0)(key, shape))


def _dt_bias(key, shape):
    dt = jnp.exp(_uniform(math.log(1e-3), math.log(1e-1))(key, shape))
    return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1


class GraniteMamba(HybridBlock):
    """The Mamba-2 mixer (module docstring); called as an attention block
    is, and reads neither positions nor mask."""

    def __init__(self, units: int, cfg: dict, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
        G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
        inner = cfg["mamba_expand"] * units
        if H * P != inner:
            raise ValueError(f"granite_hybrid: mamba_n_heads x mamba_d_head = {H * P} is not "
                             f"mamba_expand x hidden_size = {inner}")
        self._shape = (H, P, G, N)
        self._chunk, self._eps = cfg["mamba_chunk_size"], cfg["rms_norm_eps"]
        conv_dim = inner + 2 * G * N
        bound = 1.0 / math.sqrt(K)
        with self.name_scope():
            self.in_proj = _dense(inner + conv_dim + H, units, dtype, "in_proj_")
            self.conv_weight = self.params.get("conv_weight", shape=(conv_dim, K), dtype=dtype,
                                               init=_Draw(_uniform(-bound, bound)))
            self.conv_bias = self.params.get("conv_bias", shape=(conv_dim,), dtype=dtype,
                                             init=_Draw(_uniform(-bound, bound)))
            self.A_log = self.params.get("A_log", shape=(H,), init=_Draw(_a_log))
            self.dt_bias = self.params.get("dt_bias", shape=(H,), init=_Draw(_dt_bias))
            self.D = self.params.get("D", shape=(H,), init="ones")
            self.norm_gamma = self.params.get("norm_gamma", shape=(inner,), init="ones")
            self.out_proj = _dense(units, inner, dtype, "out_proj_")

    def hybrid_forward(self, F, x, positions, key_mask, conv_weight, conv_bias, A_log,
                       dt_bias, D, norm_gamma):
        Bt, L = x.shape[0], x.shape[1]
        H, P, G, N = self._shape
        inner = H * P
        with jax.named_scope("mamba_mixer"):
            zxbcdt = self.in_proj(x)._data
            z, dt = zxbcdt[..., :inner], zxbcdt[..., 2 * inner + 2 * G * N:]
            with jax.named_scope("mamba_conv"):     # reads xBC where the projection wrote it
                xs, b, c = causal_conv1d(zxbcdt, conv_weight._data, conv_bias._data,
                                         split=(inner, inner + G * N), start=inner)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias._data.astype(jnp.float32))
            A = -jnp.exp(A_log._data.astype(jnp.float32))
            y = ssd_scan(xs.reshape(Bt, L, H, P), dt, A, b.reshape(Bt, L, G, N),
                         c.reshape(Bt, L, G, N), D._data.astype(jnp.float32), self._chunk)
            with jax.named_scope("mamba_gated_norm"):
                y = rms_norm_gated(y.reshape(Bt, L, inner), z, norm_gamma._data, eps=self._eps)
            return self.out_proj(NDArray(y, ctx=x.context))


class GraniteAttention(HybridBlock):
    """Causal attention over grouped K/V heads with no positions at all
    (module docstring)."""

    def __init__(self, units: int, num_heads: int, num_kv_heads: int, scale: float,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, units // num_heads
        self._scale = scale
        D = self._dim
        with self.name_scope():
            self.q = _dense(num_heads * D, units, dtype, "q_")
            self.k = _dense(num_kv_heads * D, units, dtype, "k_")
            self.v = _dense(num_kv_heads * D, units, dtype, "v_")
            self.o = _dense(units, num_heads * D, dtype, "o_")

    def hybrid_forward(self, F, x, positions, key_mask):
        B, L, D = x.shape[0], x.shape[1], self._dim

        def heads(proj, n):
            return proj(x)._data.reshape(B, L, n, D).transpose(0, 2, 1, 3)
        out = dot_product_attention(
            heads(self.q, self._heads), heads(self.k, self._kv_heads),
            heads(self.v, self._kv_heads), mask=key_mask._data[:, None, None, :],
            causal=True, scale=self._scale)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, self._heads * D)
        return self.o(NDArray(out, ctx=x.context))


class GraniteHybridLayer(HybridBlock):
    """Pre-norm layer with scaled residual branches: ``a = h + r Mix(N1
    h)``, ``h' = a + r FFN(N2 a)``. Returns ``(h', None)``: no layer here
    counts expert rows."""

    def __init__(self, units: int, mixer: HybridBlock, ffn: HybridBlock, epsilon: float,
                 residual: float, **kwargs):
        super().__init__(**kwargs)
        self._residual = residual
        with self.name_scope():
            self.mixer, self.ffn = mixer, ffn
            self.register_child(mixer, "mixer")
            self.register_child(ffn, "ffn")
            self.norm1 = RMSNorm(units, epsilon, prefix="norm1_")
            self.norm2 = RMSNorm(units, epsilon, prefix="norm2_")

    def hybrid_forward(self, F, x, positions, key_mask):
        x = x + self.mixer(self.norm1(x), positions, key_mask) * self._residual
        return x + self.ffn(self.norm2(x)) * self._residual, None


class GraniteHybridModel(DecoderLM):
    """The decoder (module docstring); ``cfg`` as :func:`get_granite_hybrid`
    lists it."""

    def __init__(self, cfg: dict, dtype="float32", remat: bool = False, **kwargs):
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise ValueError("granite_hybrid: only position_embedding_type 'nope' is supported")
        if cfg.get("num_local_experts", 0):
            raise ValueError("granite_hybrid: num_local_experts > 0 (routed experts) is not "
                             "supported")
        if not cfg.get("tie_word_embeddings", True):
            raise ValueError("granite_hybrid: only tied embeddings are supported")
        if cfg["hidden_size"] % cfg["num_attention_heads"]:
            raise ValueError("granite_hybrid: hidden_size is not a whole number of heads")
        self._logits_scaling = cfg["logits_scaling"]
        super().__init__(cfg, dtype, remat, embed_scale=cfg["embedding_multiplier"],
                         tie_embeddings=True, **kwargs)

    def decoder_layers(self, cfg, dtype):
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"
            if kind == "mamba":
                mixer = GraniteMamba(units, cfg, dtype, prefix=pre + "mamba_")
            elif kind == "attention":
                mixer = GraniteAttention(units, cfg["num_attention_heads"],
                                         cfg["num_key_value_heads"],
                                         cfg["attention_multiplier"], dtype, prefix=pre + "attn_")
            else:
                raise ValueError(f"granite_hybrid: layer_types[{i}]={kind!r} is neither "
                                 "'mamba' nor 'attention'")
            ffn = GatedFFN(units, cfg["shared_intermediate_size"], dtype, prefix=pre + "ffn_")
            yield GraniteHybridLayer(units, mixer, ffn, eps, cfg["residual_multiplier"],
                                     prefix=pre)

    def head(self, x, weight=None):
        """Logits of the final normed hidden state over ``logits_scaling``."""
        out = super().head(x, weight)
        return NDArray(out._data / jnp.asarray(self._logits_scaling, out._data.dtype),
                       ctx=out.context)


def get_granite_hybrid(cfg: dict, dtype="float32", remat: bool = False,
                       **kwargs) -> GraniteHybridModel:
    """Model-zoo constructor from a configuration under the source's keys
    (``config.json`` of ``model_type`` ``granitemoehybrid``): ``hidden_size``,
    ``layer_types`` (``"mamba"`` / ``"attention"``, one a layer),
    ``num_attention_heads``, ``num_key_value_heads``, ``attention_multiplier``,
    ``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
    ``rms_norm_eps``, ``shared_intermediate_size``, ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
    ``mamba_expand``, ``mamba_chunk_size``, ``tie_word_embeddings``,
    ``vocab_size``; ``num_local_experts`` must be 0,
    ``position_embedding_type`` ``"nope"`` and the embeddings tied."""
    return GraniteHybridModel(cfg, dtype=dtype, remat=remat, **kwargs)
