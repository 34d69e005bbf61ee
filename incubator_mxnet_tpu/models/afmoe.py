"""AFMoE: a decoder-only mixture-of-experts language model (Arcee's
``afmoe`` family, e.g. Trinity-Mini), as one chip of an expert-parallel
deployment runs it.

Forward contract::

    logits, valid = model(ids, positions, valid_length)

with ``ids``/``positions (B, L)`` int32, ``valid_length (B,)``; ``logits
(B, L, vocab)`` and ``valid (B, L)`` (1 where a position counts), which is
what :func:`afmoe_lm_loss` takes with the next-token ``labels``.

The block, every norm an RMSNorm, no biases (``h`` the residual stream):

- ``h = E[ids] * sqrt(hidden)`` (``mup_enabled``); final norm, untied head.
- Layer: ``a = h + N2(Attn(N1(h)))``, ``h' = a + N4(FFN(N3(a)))``.
- Attention: grouped K/V heads (query head ``h`` reads K/V head
  ``h // group``); q and k RMS-normed per head with a learnt scale; rotary
  embedding on q and k **in sliding layers only**; causal, and in a sliding
  layer only the ``sliding_window`` most recent keys; the result gated by
  ``sigmoid(Wg x)`` before the output projection.
- FFN: ``W2 (silu(W1 x) * W3 x)``, dense in the leading ``num_dense_layers``.
  After them a mixture of experts: sigmoid scores over all ``num_experts``
  in fp32, the ``num_experts_per_tok`` largest of ``score + expert_bias``,
  weights renormalised and scaled by ``route_scale``, plus shared experts
  that every token visits. ``expert_bias`` is a buffer outside the gradient,
  held at zero: the update that moves it during training is not implemented.
  ``expert_rows`` is a buffer the forward writes: the rows each expert held
  got, so a compiled step carries its own count out.

**The chip's share.** The layer is told which experts it holds
(``experts_held`` from ``expert_first``, contiguous), routes over all of
them, and adds nothing for the absent ones (``parallel/moe_dropless.py``);
``vocab_size`` is the slice of the vocabulary held. Nothing stands in for
the other chips. With ``experts_held == num_experts`` it is the whole model.

The whole training step (embedding, layers on the flash and grouped-matmul
kernels, head, loss, gradients, AdamW) compiles to one executable through
``parallel.ShardedTrainer``; ``remat=True`` recomputes each layer in the
backward pass, as ``BERTEncoder`` does, and holds only what the attention
kernel returned (``ops.attention.checkpoint_layer``): its output and
log-sum-exp are 68 MB a layer at 8,192 tokens, and rebuilding them is a
second run of the step's most expensive kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock, _is_tracing
from ..gluon import nn
from ..ndarray import NDArray
from ..ops.attention import checkpoint_layer, dot_product_attention
from ..ops.nn import qk_norm_rope, rotary
from ..ops.pallas.moe_gmm import TILE_ROWS
from ..parallel import moe_dropless

__all__ = ["AfmoeModel", "DecoderLM", "AfmoeDecoderLayer", "AfmoeAttention", "AfmoeMoE",
           "GatedFFN", "RMSNorm", "get_afmoe", "afmoe_lm_loss", "rotary"]


class RMSNorm(HybridBlock):
    """``x / rms(x) * gamma``, statistics in fp32 (``ops.nn.rms_norm``)."""

    def __init__(self, in_channels: int, epsilon: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._epsilon)


def _dense(units, in_units, dtype, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    dtype=dtype, prefix=prefix)


class GatedFFN(HybridBlock):
    """``W2 (silu(W1 x) * W3 x)``: the dense FFN and the shared expert."""

    def __init__(self, units: int, hidden: int, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate = _dense(hidden, units, dtype, "gate_")
            self.up = _dense(hidden, units, dtype, "up_")
            self.down = _dense(units, hidden, dtype, "down_")

    def hybrid_forward(self, F, x):
        g, u = self.gate(x)._data, self.up(x)._data
        act = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32))
        return self.down(NDArray(act.astype(g.dtype), ctx=x.context))


def normed_heads(attn, x, positions):
    """``q`` and ``k`` of an attention block as its kernel takes them, ``(B,
    heads, L, D)``: the block's projections of ``x``, RMS-normed per head
    with ``q_norm`` / ``k_norm``'s scale and rotated by ``positions`` (not
    with ``None``): ``ops.nn.qk_norm_rope``, one fused pass on the chip."""
    return (qk_norm_rope(proj(x)._data, norm.gamma.data()._data, positions, attn._theta,
                         attn._epsilon, heads)
            for proj, norm, heads in ((attn.q, attn.q_norm, attn._heads),
                                      (attn.k, attn.k_norm, attn._kv_heads)))


class AfmoeAttention(HybridBlock):
    """Gated causal attention over grouped K/V heads; ``window`` makes it a
    sliding layer (rotary positions, the ``window`` most recent keys)."""

    def __init__(self, units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, window=None, rope_theta: float = 10000.0,
                 epsilon: float = 1e-5, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, head_dim
        self._window, self._theta, self._epsilon = window, rope_theta, epsilon
        with self.name_scope():
            self.q = _dense(num_heads * head_dim, units, dtype, "q_")
            self.k = _dense(num_kv_heads * head_dim, units, dtype, "k_")
            self.v = _dense(num_kv_heads * head_dim, units, dtype, "v_")
            self.gate = _dense(num_heads * head_dim, units, dtype, "gate_")
            self.o = _dense(units, num_heads * head_dim, dtype, "o_")
            self.q_norm = RMSNorm(head_dim, epsilon, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, epsilon, prefix="k_norm_")

    def hybrid_forward(self, F, x, positions, key_mask):
        B, L = x.shape[0], x.shape[1]
        H, Hkv, D = self._heads, self._kv_heads, self._dim
        # rotary positions in sliding layers only
        q, k = normed_heads(self, x, None if self._window is None else positions._data)
        v = self.v(x)._data.reshape(B, L, Hkv, D).transpose(0, 2, 1, 3)
        gate = self.gate(x)._data
        with jax.named_scope("afmoe_attention"):
            out = dot_product_attention(
                q, k, v, mask=key_mask._data[:, None, None, :], causal=True,
                window=self._window, scale=D ** -0.5)
            out = out.transpose(0, 2, 1, 3).reshape(B, L, H * D)
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)
        return self.o(NDArray(out, ctx=x.context))


class AfmoeMoE(HybridBlock):
    """Shared experts for every token (``num_shared`` of them as one gated
    FFN; none with 0) plus the part of the routed result that the experts
    held here give: ``held = (first, count)`` of ``num_experts``. No
    assignment is dropped. Returns ``(out, rows)``, ``rows (count,)`` the
    rows each expert held got in this call."""

    def __init__(self, units: int, hidden: int, num_experts: int, top_k: int,
                 held, num_shared: int = 1, route_norm: bool = True,
                 route_scale: float = 1.0, tile_rows: int = TILE_ROWS,
                 dtype="float32", route_norm_eps: float = 1e-20, **kwargs):
        super().__init__(**kwargs)
        first, count = held
        if not 0 <= first <= first + count <= num_experts or count < 1:
            raise ValueError(f"experts held {held} are not a range of "
                             f"{num_experts} experts")
        self._held, self._top_k, self._tile_rows = (first, count), top_k, tile_rows
        self._route_norm, self._route_scale = route_norm, route_scale
        self._route_norm_eps = route_norm_eps
        #: set to a list by ``AfmoeModel.routing``: each eager call appends
        #: what its own routing function chose
        self.routes = None
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), dtype=dtype)
            # selection bias of aux-loss-free balancing: a buffer outside the
            # gradient, which no step moves
            self.expert_bias = self.params.get(
                "expert_bias", shape=(num_experts,), init="zeros")
            # rows of each expert held in the last forward, written (by
            # AfmoeModel.hidden, outside a recomputed layer's trace) as
            # BatchNorm writes its running statistics: an output of the
            # compiled step, no call on the host
            self.expert_rows = self.params.get(
                "expert_rows", shape=(count,), init="zeros", grad_req="null")
            self.shared = GatedFFN(units, hidden * num_shared, dtype,
                                   prefix="shared_") if num_shared else None
            self.experts_w13 = self.params.get(
                "experts_w13", shape=(count, 2 * hidden, units), dtype=dtype)
            self.experts_w2 = self.params.get(
                "experts_w2", shape=(count, units, hidden), dtype=dtype)

    def hybrid_forward(self, F, x, router_weight, expert_bias, expert_rows,
                       experts_w13, experts_w2):
        B, L, C = x.shape
        tokens = x._data.reshape(B * L, C)
        with jax.named_scope("moe_router"):
            idx, weight = moe_dropless.sigmoid_topk(
                tokens, router_weight._data, expert_bias._data, self._top_k,
                self._route_norm, self._route_scale, self._route_norm_eps)
            plan = moe_dropless.plan_rows(idx, self._held, self._tile_rows)
        if self.routes is not None and not isinstance(idx, jax.core.Tracer):
            self.routes.append(dict(
                idx=idx, **moe_dropless.placement(idx, self._held, self._tile_rows)))
        routed = moe_dropless.routed_experts(
            tokens, idx, weight.astype(jnp.float32), experts_w13._data,
            experts_w2._data, self._held, self._tile_rows, plan=plan)
        shared = None if self.shared is None else self.shared(x)
        routed = NDArray(routed.reshape(B, L, C), ctx=x.context)
        return (routed if shared is None else shared + routed,
                NDArray(plan.counts.astype(jnp.float32), ctx=x.context))


class AfmoeDecoderLayer(HybridBlock):
    """Sandwich-norm layer: ``a = h + N2(Attn(N1 h))``, ``h' = a + N4(FFN(N3 a))``.
    Returns ``(h', rows)``: ``rows`` what a MoE FFN counted, ``None`` from a
    dense one."""

    def __init__(self, units: int, attention: AfmoeAttention, ffn: HybridBlock,
                 epsilon: float = 1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn, self.ffn = attention, ffn
            self.register_child(attention, "attn")
            self.register_child(ffn, "ffn")
            self.norm1 = RMSNorm(units, epsilon, prefix="norm1_")
            self.norm2 = RMSNorm(units, epsilon, prefix="norm2_")
            self.norm3 = RMSNorm(units, epsilon, prefix="norm3_")
            self.norm4 = RMSNorm(units, epsilon, prefix="norm4_")

    def hybrid_forward(self, F, x, positions, key_mask):
        x = x + self.norm2(self.attn(self.norm1(x), positions, key_mask))
        y = self.ffn(self.norm3(x))
        y, rows = y if isinstance(y, tuple) else (y, None)
        return x + self.norm4(y), rows


class DecoderLM(HybridBlock):
    """What the decoder-only language models here share: embedding, a list
    of decoder layers, final norm and an LM head over the vocabulary held
    (its own matrix, or with ``tie_embeddings`` the embedding's read the
    other way), with the forward contract of the module docstring. A family gives
    its layers (:meth:`decoder_layers`): each is called as ``layer(x,
    positions, key_mask)``, returns ``(x', rows)`` (``rows`` what a MoE FFN
    counted, else ``None``) and keeps its FFN as ``layer.ffn``. ``cfg``
    carries the source's own keys."""

    def __init__(self, cfg: dict, dtype="float32", remat: bool = False,
                 embed_scale: float = 1.0, tie_embeddings: bool = False,
                 epsilon: float = None, **kwargs):
        super().__init__(**kwargs)
        units = cfg["hidden_size"]
        eps = cfg["rms_norm_eps"] if epsilon is None else epsilon   # the final norm's
        self._units, self._remat, self._embed_scale = units, remat, embed_scale
        with self.name_scope():
            self.embed = nn.Embedding(cfg["vocab_size"], units, dtype=dtype,
                                      prefix="embed_")
            self.layers = []
            for i, layer in enumerate(self.decoder_layers(cfg, dtype)):
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm = RMSNorm(units, eps, prefix="norm_")
            if tie_embeddings:
                # one parameter under two names: registered here, it reaches
                # hybrid_forward as the compiled step's own argument
                self.lm_head, self.head_weight = None, self.embed.weight
            else:
                self.lm_head = _dense(cfg["vocab_size"], units, dtype, "lm_head_")

    def decoder_layers(self, cfg: dict, dtype):
        """The family's layers in order, layer ``i`` under the prefix
        ``layer{i}_`` (called inside the model's name scope)."""
        raise NotImplementedError

    def hidden(self, ids, positions, valid_length):
        """``(hidden, valid)``: the final normed hidden state ``(B, L, C)``
        the head reads, and the valid-position mask ``(B, L)``."""
        L, ctx = ids.shape[1], ids.context
        valid = (jnp.arange(L, dtype=jnp.float32)[None, :]
                 < valid_length._data.astype(jnp.float32)[:, None])
        key_mask = NDArray(valid, ctx=ctx)
        x = self.embed(ids)
        x = NDArray(x._data * jnp.asarray(self._embed_scale, x._data.dtype), ctx=ctx)
        # checkpoint only under a real jit trace (see BERTEncoder): a layer's
        # activations are then rebuilt in the backward pass, not held, except
        # the flash kernel's output and log-sum-exp (checkpoint_layer)
        remat = (self._remat and _is_tracing()
                 and isinstance(x._data, jax.core.Tracer))
        for layer in self.layers:
            def body(xv, layer=layer):
                out, rows = layer(NDArray(xv, ctx=ctx), positions, key_mask)
                return out._data, None if rows is None else rows._data
            xv, rows = (checkpoint_layer(body) if remat else body)(x._data)
            x = NDArray(xv, ctx=ctx)
            if rows is not None:
                layer.ffn.expert_rows._deposit_aux(rows, ctx)
        return self.norm(x), NDArray(valid.astype(jnp.float32), ctx=ctx)

    def head(self, x, weight=None):
        """Logits ``(B, L, vocab)`` of the final normed hidden state ``x``;
        ``weight``: the tied matrix where the caller holds it already."""
        with jax.named_scope("lm_head"):
            if self.lm_head is not None:
                return self.lm_head(x)
            weight = self.head_weight.data(x.context) if weight is None else weight
            return NDArray(jnp.matmul(x._data, weight._data.T), ctx=x.context)

    def hybrid_forward(self, F, ids, positions, valid_length, head_weight=None):
        x, valid = self.hidden(ids, positions, valid_length)
        return self.head(x, head_weight), valid

    def routing(self, ids, positions, valid_length, publish: bool = True) -> list:
        """One eager forward that records, for each MoE layer in order, what
        the layer's own routing function chose: ``idx (T, k)``, and from the
        row plan ``counts`` (rows of each expert held), ``assignments_held``,
        ``rows_placed`` and ``rows_live_share`` (the share of the worst-case
        buffer in use, which is how much of it the routed half's passes
        visit). With ``publish`` they go out as ``mxtpu_moe_*``
        gauges (``telemetry.metrics``); the compiled step itself calls
        nothing on the host, and leaves its own count in each layer's
        ``expert_rows`` buffer (:meth:`expert_rows`)."""
        from ..telemetry import metrics
        moes = [l.ffn for l in self.layers if isinstance(l.ffn, AfmoeMoE)]
        for m in moes:
            m.routes = []
        try:
            self.hidden(ids, positions, valid_length)        # the head routes nothing
            out = [m.routes[-1] for m in moes]
        finally:
            for m in moes:
                m.routes = None
        for i, r in enumerate(out if publish else ()):
            held, placed = int(r["assignments_held"]), int(r["rows_placed"])
            counts = jax.device_get(r["counts"])
            for name, value in (("assignments_held", held),
                                ("assignments_dropped", held - placed),
                                ("expert_rows_max", float(counts.max())),
                                ("expert_rows_mean", float(counts.mean())),
                                ("rows_live_share", float(r["rows_live_share"]))):
                metrics.gauge("mxtpu_moe_" + name, "Routing of one MoE layer, "
                              "last routing() call", layer=str(i)).set(value)
        return out

    def expert_rows(self, ctx=None) -> list:
        """For each MoE layer in order, the rows each expert held got in the
        last forward or compiled step: device arrays ``(experts_held,)``,
        not read here, so a training loop can keep them without a sync."""
        return [l.ffn.expert_rows.data(ctx)._data for l in self.layers
                if isinstance(l.ffn, AfmoeMoE)]


class AfmoeModel(DecoderLM):
    """The AFMoE decoder (module docstring); ``cfg`` as :func:`get_afmoe`
    lists it."""

    def __init__(self, cfg: dict, dtype="float32", remat: bool = False,
                 **kwargs):
        super().__init__(
            cfg, dtype, remat,
            embed_scale=cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled") else 1.0,
            **kwargs)

    def decoder_layers(self, cfg, dtype):
        units, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        held = (cfg.get("expert_first", 0),
                cfg.get("experts_held", cfg["num_experts"]))
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"
            attention = AfmoeAttention(
                units, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"],
                window=cfg["sliding_window"] if kind == "sliding_attention" else None,
                rope_theta=cfg["rope_theta"], epsilon=eps, dtype=dtype,
                prefix=pre + "attn_")
            if i < cfg["num_dense_layers"]:
                ffn = GatedFFN(units, cfg["intermediate_size"], dtype,
                               prefix=pre + "ffn_")
            else:
                ffn = AfmoeMoE(
                    units, cfg["moe_intermediate_size"], cfg["num_experts"],
                    cfg["num_experts_per_tok"], held,
                    num_shared=cfg["num_shared_experts"],
                    route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
                    tile_rows=cfg.get("moe_tile_rows", TILE_ROWS), dtype=dtype,
                    prefix=pre + "moe_")
            yield AfmoeDecoderLayer(units, attention, ffn, eps, prefix=pre)


def get_afmoe(cfg: dict, dtype="float32", remat: bool = False,
              **kwargs) -> AfmoeModel:
    """Model-zoo constructor from a configuration under the source's keys
    (``config.json`` of ``model_type`` ``afmoe``): ``hidden_size``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
    ``num_experts_per_tok``, ``num_shared_experts``, ``num_dense_layers``,
    ``layer_types``, ``sliding_window``, ``rope_theta``, ``rms_norm_eps``,
    ``route_norm``, ``route_scale``, ``mup_enabled``, ``vocab_size``; and
    the chip's share: ``experts_held`` (default all), ``expert_first``
    (default 0), ``moe_tile_rows``."""
    return AfmoeModel(cfg, dtype=dtype, remat=remat, **kwargs)


def afmoe_lm_loss(outputs, labels):
    """Mean next-token cross-entropy over the valid positions, in fp32 over
    the vocabulary held. ``outputs`` = the model's ``(logits, valid)``;
    ``labels (B, L)`` the next token of each position."""
    logits, valid = (o._data for o in outputs)
    lab = labels._data.astype(jnp.int32)
    z = logits.astype(jnp.float32)
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, lab[..., None], axis=-1)[..., 0])
    loss = (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
    return NDArray(loss, ctx=outputs[0].context)
