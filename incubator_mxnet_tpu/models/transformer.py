"""Transformer building blocks shared by BERT and the NMT Transformer.

Reference parity: GluonNLP's ``BERTEncoder``/``TransformerEncoderCell``
(gluonnlp/model/bert.py, transformer.py), whose hot path is the contrib
interleaved-MHA ops (``src/operator/contrib/transformer.cc`` — SURVEY §2.4).

TPU-native design: one fused QKV projection (a single MXU matmul over the
batch·seq rows) followed by the Pallas flash kernels on TPU. The reference's
(B·H, L, L) score tensor never exists in HBM. ``MultiHeadAttention`` hands
its projections as they are to
:func:`~incubator_mxnet_tpu.ops.attention.projected_attention`, which
picks the layout from what the call shows. On a TPU (head size 32, 64 or
128, whole 128-lane blocks of heads, no ``tp`` or ``sp`` mesh) the kernels
read q, k and v as lane blocks of the ``(B, L, 3C)`` projection and write
the ``(B, L, C)`` the output projection reads, with no head-major copy
either way. Elsewhere (the CPU, other shapes) the op splits the heads out,
``(B, H, L, D)``, for :func:`~incubator_mxnet_tpu.ops.attention.
dot_product_attention` and transposes the result back.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderCell",
           "StackedTransformerEncoder"]


class MultiHeadAttention(HybridBlock):
    """Multi-head attention with fused QKV projection.

    ``__call__(query, kv, mask)`` — pass ``kv=None`` (or ``query``) for
    self-attention (one fused qkv matmul); a different ``kv`` gives
    cross-attention (q proj + fused kv proj, the encdec layout of the
    reference's ``interleaved_matmul_encdec_*`` ops).

    ``mask`` is broadcastable to (B, H, Lq, Lk), 1 = attend; ``None`` = full.
    """

    def __init__(self, units: int, num_heads: int, dropout: float = 0.0,
                 causal: bool = False, use_bias: bool = True, dtype="float32",
                 cross_attention: bool = False, weight_initializer=None,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        self._cross = cross_attention
        with self.name_scope():
            # Only the projections this cell actually uses exist — dead
            # parameters would get optimizer state and distort MFU accounting.
            if cross_attention:
                self.q_proj = nn.Dense(units, flatten=False, use_bias=use_bias,
                                       in_units=units, dtype=dtype,
                                       prefix="query_",
                                       weight_initializer=weight_initializer)
                self.kv_proj = nn.Dense(2 * units, flatten=False,
                                        use_bias=use_bias, in_units=units,
                                        dtype=dtype, prefix="kv_",
                                        weight_initializer=weight_initializer)
            else:
                self.qkv = nn.Dense(3 * units, flatten=False, use_bias=use_bias,
                                    in_units=units, dtype=dtype, prefix="qkv_",
                                    weight_initializer=weight_initializer)
            self.proj = nn.Dense(units, flatten=False, use_bias=use_bias,
                                 in_units=units, dtype=dtype, prefix="proj_",
                                 weight_initializer=weight_initializer)
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, query, kv=None, mask=None):
        if not self._cross:
            if kv is not None and kv is not query:
                raise ValueError(
                    "this MultiHeadAttention was built for self-attention; "
                    "pass cross_attention=True to attend over a memory")
            operands = (self.qkv(query),)
        else:
            operands = (self.q_proj(query), self.kv_proj(query if kv is None else kv))
        if mask is not None:
            operands += (mask,)
        # (B, Lq, C), whichever layout the kernels read
        out = F.projected_attention(*operands, heads=self._num_heads, cross=self._cross,
                                    causal=self._causal)
        out = self.proj(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class PositionwiseFFN(HybridBlock):
    """The transformer MLP: dense(hidden) -> act -> dense(units) -> dropout."""

    def __init__(self, units: int, hidden_size: int, dropout: float = 0.0,
                 activation: str = "gelu", dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ffn1 = nn.Dense(hidden_size, flatten=False, in_units=units,
                                 activation=activation, dtype=dtype,
                                 prefix="ffn1_",
                                 weight_initializer=weight_initializer)
            self.ffn2 = nn.Dense(units, flatten=False, in_units=hidden_size,
                                 dtype=dtype, prefix="ffn2_",
                                 weight_initializer=weight_initializer)
            self.dropout = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        out = self.ffn2(self.ffn1(x))
        if self.dropout is not None:
            out = self.dropout(out)
        return out


class TransformerEncoderCell(HybridBlock):
    """Post-LN transformer encoder layer (BERT layout):
    ``x = LN(x + MHA(x)); x = LN(x + FFN(x))``."""

    def __init__(self, units: int, hidden_size: int, num_heads: int,
                 dropout: float = 0.0, layer_norm_eps: float = 1e-12,
                 activation: str = "gelu", dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = MultiHeadAttention(
                units, num_heads, dropout=dropout, dtype=dtype,
                prefix="attn_", weight_initializer=weight_initializer)
            self.ln1 = nn.LayerNorm(epsilon=layer_norm_eps, prefix="ln1_")
            self.ffn = PositionwiseFFN(
                units, hidden_size, dropout=dropout, activation=activation,
                dtype=dtype, prefix="ffn_",
                weight_initializer=weight_initializer)
            self.ln2 = nn.LayerNorm(epsilon=layer_norm_eps, prefix="ln2_")

    @staticmethod
    def _add_norm(ln, x, y):
        # the residual sum enters the norm unrounded: added in float32 (XLA
        # fuses the casts and the add into the norm's own fusion, no HBM
        # round trip), the result back in the stream's dtype. A bf16 sum
        # rounded before the statistics costs a quarter more error over 12
        # layers (PERF.md, PR 26). A float32 stream is returned as it is.
        s = x.astype("float32", copy=False) + y.astype("float32", copy=False)
        return ln(s).astype(x.dtype, copy=False)

    def hybrid_forward(self, F, x, mask=None):
        x = self._add_norm(self.ln1, x, self.attention(x, None, mask))
        x = self._add_norm(self.ln2, x, self.ffn(x))
        return x


class StackedTransformerEncoder(HybridBlock):
    """Scan-over-layers transformer encoder: every parameter carries a
    leading ``(num_layers,)`` axis, the forward is a ``lax.scan`` over that
    axis — the production-JAX formulation of a deep stack (one compiled
    layer body regardless of depth).

    This layout is what makes PIPELINE parallelism a pure sharding choice:
    with an active mesh whose ``pp`` axis divides ``num_layers``, the layer
    stack becomes ``pp`` stages of ``num_layers/pp`` layers and the forward
    runs the microbatched GPipe schedule (``parallel/pipeline.py``), the
    stage stacks sharded over ``pp``. Without pp it is an ordinary scan.
    Reference counterpart: none — SURVEY §2.5 parity-plus extension.
    """

    def __init__(self, num_layers: int, units: int, hidden_size: int,
                 num_heads: int, layer_norm_eps: float = 1e-12,
                 n_micro: int = 4, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._N = num_layers
        self._units = units
        self._hidden = hidden_size
        self._heads = num_heads
        self._eps = layer_norm_eps
        self._n_micro = n_micro
        N, C, H = num_layers, units, hidden_size
        with self.name_scope():
            get = self.params.get
            self.qkv_w = get("qkv_weight", shape=(N, 3 * C, C), init="xavier",
                             dtype=dtype)
            self.qkv_b = get("qkv_bias", shape=(N, 3 * C), init="zeros",
                             dtype=dtype)
            self.proj_w = get("proj_weight", shape=(N, C, C), init="xavier",
                              dtype=dtype)
            self.proj_b = get("proj_bias", shape=(N, C), init="zeros",
                              dtype=dtype)
            self.ffn1_w = get("ffn1_weight", shape=(N, H, C), init="xavier",
                              dtype=dtype)
            self.ffn1_b = get("ffn1_bias", shape=(N, H), init="zeros",
                              dtype=dtype)
            self.ffn2_w = get("ffn2_weight", shape=(N, C, H), init="xavier",
                              dtype=dtype)
            self.ffn2_b = get("ffn2_bias", shape=(N, C), init="zeros",
                              dtype=dtype)
            self.ln1_g = get("ln1_gamma", shape=(N, C), init="ones",
                             dtype=dtype)
            self.ln1_b = get("ln1_beta", shape=(N, C), init="zeros",
                             dtype=dtype)
            self.ln2_g = get("ln2_gamma", shape=(N, C), init="ones",
                             dtype=dtype)
            self.ln2_b = get("ln2_beta", shape=(N, C), init="zeros",
                             dtype=dtype)

    # -- one layer on one (mb, L, C) block ---------------------------------
    def _layer(self, p, x):
        C, Hd = self._units, self._heads
        D = C // Hd
        B, L, _ = x.shape

        def ln(v, g, b):
            mu = v.mean(-1, keepdims=True)
            var = ((v - mu) ** 2).mean(-1, keepdims=True)
            return (v - mu) * jax.lax.rsqrt(var + self._eps) * g + b

        qkv = jnp.einsum("blc,oc->blo", x, p["qkv_w"]) + p["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, L, Hd, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, L, Hd, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, L, Hd, D).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
        o = o.transpose(0, 2, 1, 3).reshape(B, L, C)
        o = jnp.einsum("blc,oc->blo", o, p["proj_w"]) + p["proj_b"]
        x = ln(x + o, p["ln1_g"], p["ln1_b"])
        h = jax.nn.gelu(jnp.einsum("blc,hc->blh", x, p["ffn1_w"])
                        + p["ffn1_b"], approximate=False)
        f = jnp.einsum("blh,ch->blc", h, p["ffn2_w"]) + p["ffn2_b"]
        return ln(x + f, p["ln2_g"], p["ln2_b"])

    def _params_tree(self, kw):
        names = ["qkv_w", "qkv_b", "proj_w", "proj_b", "ffn1_w", "ffn1_b",
                 "ffn2_w", "ffn2_b", "ln1_g", "ln1_b", "ln2_g", "ln2_b"]
        from ..ndarray import NDArray
        return {n: (kw[n]._data if isinstance(kw[n], NDArray) else kw[n])
                for n in names}

    def hybrid_forward(self, F, x, **kw):
        from ..ndarray import NDArray
        from ..parallel.mesh import current_active_mesh
        xv = x._data if isinstance(x, NDArray) else x
        tree = self._params_tree(kw)
        mesh = current_active_mesh()
        pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        use_pp = (pp > 1 and self._N % pp == 0
                  and isinstance(xv, jax.core.Tracer)
                  and xv.shape[0] % self._n_micro == 0)
        if use_pp:
            from functools import partial
            from jax.sharding import PartitionSpec as P
            from ..parallel.collectives import shard_map
            from ..parallel.pipeline import pipeline_apply
            per_stage = self._N // pp
            M = self._n_micro
            B = xv.shape[0]
            stage = {n: v.reshape((pp, per_stage) + v.shape[1:])
                     for n, v in tree.items()}

            def stage_fn(p, mb):
                def body(h, i):
                    pl = jax.tree.map(lambda v: v[i], p)
                    return self._layer(pl, h), None
                out, _ = jax.lax.scan(body, mb, jnp.arange(per_stage))
                return out

            xm = xv.reshape((M, B // M) + xv.shape[1:])
            dp = mesh.shape.get("dp", 1)
            use_dp = dp > 1 and (B // M) % dp == 0
            xspec = P(None, "dp" if use_dp else None)
            pspec = {n: P("pp") for n in stage}
            fn = shard_map(partial(pipeline_apply, stage_fn=stage_fn,
                                   axis="pp"),
                           mesh=mesh, in_specs=(pspec, xspec),
                           out_specs=xspec)
            out = fn(stage, xm)
            out = out.reshape(xv.shape)
        else:
            def body(h, i):
                pl = jax.tree.map(lambda v: v[i], tree)
                return self._layer(pl, h), None
            out, _ = jax.lax.scan(body, xv, jnp.arange(self._N))
        return NDArray(out, ctx=x.context) if isinstance(x, NDArray) else out
