"""LFM2-MoE (``model_type`` ``lfm2_moe``, e.g. LFM2-8B-A1B), plainly: forward
pass, next-token loss and gradients in float32 ``jax.numpy`` at the highest
matmul precision; gradients through ``jax.grad``.

Written from the equations below, which are the family's public modeling
code and ``config.json`` keys. No kernels, no gluon, no sort, no dispatch:
the causal mask is dense, the convolution is ``K`` shifted copies, the
experts are a loop and a token's expert weights a dense ``(T, E)`` matrix.
``params`` is the system's own parameters keyed by name without the block
prefix (``embed_weight``, ``layer2_conv_in_proj_weight``,
``layer2_conv_weight``, ``layer1_attn_q_weight``, ``layer1_moe_experts_w13``,
...); dense weights are ``(out, in)``; arrays of any float dtype are upcast
where they are used. It imports nothing from the program; the float32
primitives (matmul, RMSNorm, rotary by halves, the gated FFN) are
``reference/afmoe.py``'s.

The equations (``h`` the residual stream, every norm an RMSNorm with a learnt
scale and ``norm_eps``, no bias; ``C`` = ``hidden_size``, ``K`` =
``conv_L_cache``, ``H`` / ``Hkv`` heads of ``D = C / H``):

    h  = E[ids];   logits = E^T N(h_last)           (tied embedding)
    a  = h + Mix(N1 h);  h' = a + FFN(N2 a)         (pre-norm; Mix by layer_types[i])
    conv: [Bg | Cg | x] = Win u;  s = Bg * x
          c[t] = sum_k w[:, k] * s[t - (K - 1) + k]     (s zero before the row)
          Mix = Wout (Cg * c)
    full_attention: q, k, v = Wq u, Wk u, Wv u;  q, k RMS-normed per head,
          then rotated by position (halves of the head, theta rope_theta);
          causal softmax(q k^T / sqrt(D)) v over K/V head  head // (H / Hkv),
          keys masked by length;  Mix = Wo .
    FFN:  W2 (silu(W1 x) * W3 x)   dense in the first num_dense_layers, then
    MoE:  s = sigmoid(Wr x); the num_experts_per_tok largest of s + bias;
          w = s[top] / (sum + 1e-6) * routed_scaling_factor;
          sum over the chosen experts held of w_e Expert_e(x)   (no shared expert)

Departures from the published model, each the system's own share or layout
and made here exactly as there:

- the chip's share: only the experts ``[expert_first, expert_first +
  experts_held)`` exist in ``params`` and only they add to the result; the
  router still scores all ``num_experts``; the vocabulary is the slice
  ``vocab_size`` of the configuration;
- an expert's gate and up projections are stacked in one ``(2F, C)`` matrix
  (``experts_w13``), gate first; the convolution's weight is ``(C, K)``
  where the source stores ``(C, 1, K)``;
- the selection bias is held at zero (its update is a training recipe
  outside the gradient); padding is a key mask from ``valid_length`` (the
  convolution reads no mask: a padded position reaches only positions that
  are padded too);
- **so that it fits at the timed sizes** (16,384 tokens in float32 beside the
  system's state): attention runs one (row, head) at a time, the experts one
  at a time, the head and the loss over blocks of tokens, and each of those
  and each layer is rebuilt in the backward pass (``jax.checkpoint``) where
  a gradient is asked for. The mathematics is unchanged.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.afmoe import _f32, _gated, _mm, _rms, _rotary


def short_conv(p, pre, cfg, u, operands=None):
    """The gated short convolution of ``u (B, L, C)``."""
    K = cfg["conv_L_cache"]
    bg, cg, x = jnp.split(_mm(u, p[pre + "in_proj_weight"], operands), 3, axis=-1)
    s, w = bg * x, _f32(p[pre + "weight"])

    def back(d):       # s[t - d], zero before the row
        return s if d == 0 else jnp.concatenate([jnp.zeros_like(s[:, :d]), s[:, :-d]], 1)
    c = sum(w[:, k] * back(K - 1 - k) for k in range(K))
    return _mm(cg * c, p[pre + "out_proj_weight"], operands)


def attention(p, pre, cfg, u, positions, keep, operands=None):
    B, L, C = u.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps = C // H, cfg["norm_eps"]
    q = _rms(_mm(u, p[pre + "q_weight"], operands).reshape(B, L, H, D),
             p[pre + "q_norm_gamma"], eps)
    k = _rms(_mm(u, p[pre + "k_weight"], operands).reshape(B, L, Hkv, D),
             p[pre + "k_norm_gamma"], eps)
    v = _mm(u, p[pre + "v_weight"], operands).reshape(B, L, Hkv, D)
    q, k = (_rotary(t, positions, cfg["rope_theta"]) for t in (q, k))
    kv_head = jnp.arange(H) // (H // Hkv)
    see = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh, keep_b = args                     # (L, D) x3, (L,)
        s = jnp.where(see & keep_b[None, :], _mm(qh, kh, operands) * D ** -0.5, -1e30)
        return _mm(jax.nn.softmax(s, -1), vh.T, operands)

    o = jax.lax.map(one_head, (
        q.transpose(0, 2, 1, 3).reshape(B * H, L, D),
        k.transpose(0, 2, 1, 3)[:, kv_head].reshape(B * H, L, D),
        v.transpose(0, 2, 1, 3)[:, kv_head].reshape(B * H, L, D),
        jnp.repeat(keep, H, axis=0)))
    o = o.reshape(B, H, L, D).transpose(0, 2, 1, 3).reshape(B, L, H * D)
    return _mm(o, p[pre + "o_weight"], operands)


def route(p, pre, cfg, x):
    """``(idx (T, k), weight (T, k), gap (T,))`` over all experts; ``gap`` is
    the margin in selection score between the last expert taken and the
    first left out (a near-tie is where a rounding may swap them)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _f32(p[pre + "router_weight"]).T)
    top, idx = jax.lax.top_k(s + _f32(p[pre + "expert_bias"]), k + 1)
    w = jnp.take_along_axis(s, idx[:, :k], 1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx[:, :k], w * cfg["routed_scaling_factor"], top[:, k - 1] - top[:, k]


def moe(p, pre, cfg, x, held, operands=None):
    """``(out (T, C), (idx, gap))``: what the experts ``held = (first,
    count)`` add, and nothing else; ``experts_w13``/``experts_w2`` hold
    those ``count`` experts."""
    first, count = held
    idx, w, gap = route(p, pre, cfg, x)
    dense_w = (w[:, :, None] * (idx[:, :, None] == jnp.arange(cfg["num_experts"]))).sum(1)

    @jax.checkpoint
    def expert(x, w13, w2, weight):
        gate, up = jnp.split(_f32(w13), 2, axis=0)
        return weight[:, None] * _gated(x, gate, up, w2, operands)

    out = jnp.zeros_like(x)
    for e in range(count):
        out = out + expert(x, p[pre + "experts_w13"][e], p[pre + "experts_w2"][e],
                           dense_w[:, first + e])
    return out, (idx, gap)


def forward(params, cfg, ids, positions, valid_length, operands=None):
    """``{"hidden", "valid", "routes"}``: the final normed hidden state ``(B,
    L, C)`` the head reads, the valid-position mask, and for each MoE layer
    ``(idx (T, k), gap (T,))``. ``operands`` names a dtype to round every
    matmul operand to first (the lower-precision control the cell's limits
    have to fail); the router's scores stay float32 either way, as the
    configuration states them."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, cfg["norm_eps"]
        B, L = ids.shape
        C = cfg["hidden_size"]
        held = (cfg.get("expert_first", 0), cfg.get("experts_held", cfg["num_experts"]))
        keep = jnp.arange(L)[None, :] < jnp.asarray(valid_length)[:, None]
        h = _f32(p["embed_weight"])[ids]
        routes = []
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"

            @jax.checkpoint
            def layer(p, h, pre=pre, kind=kind, dense=i < cfg["num_dense_layers"]):
                u = _rms(h, p[pre + "norm1_gamma"], eps)
                if kind == "conv":
                    h = h + short_conv(p, pre + "conv_", cfg, u, operands)
                else:
                    h = h + attention(p, pre + "attn_", cfg, u, positions, keep, operands)
                x = _rms(h, p[pre + "norm2_gamma"], eps)
                if dense:
                    return h + _gated(x, p[pre + "ffn_gate_weight"], p[pre + "ffn_up_weight"],
                                      p[pre + "ffn_down_weight"], operands), None
                f, r = moe(p, pre + "moe_", cfg, x.reshape(B * L, C), held, operands)
                return h + f.reshape(B, L, C), r

            h, r = layer({k: v for k, v in p.items() if k.startswith(pre)}, h)
            if r is not None:
                routes.append(r)
        return {"hidden": _rms(h, p["norm_gamma"], eps),
                "valid": keep.astype(jnp.float32), "routes": routes}


def logits(params, hidden, operands=None):
    """``hidden (..., C)`` through the tied head: ``hidden E^T``."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden, params["embed_weight"], operands)


def lm_loss(params, hidden, valid, labels, operands=None, block: int = 2048):
    """Mean next-token cross-entropy over the valid positions, the head and
    the log-softmax over ``block`` tokens at a time."""
    C = hidden.shape[-1]
    T = hidden.size // C
    block = next(b for b in range(min(block, T), 0, -1) if T % b == 0)

    @jax.checkpoint
    def some(args):
        h, keep, lab = args
        logp = jax.nn.log_softmax(logits(params, h, operands), -1)
        return -(jnp.take_along_axis(logp, lab[:, None], -1)[:, 0] * keep).sum()

    nll = jax.lax.map(some, (hidden.reshape(T // block, block, C),
                             _f32(valid).reshape(T // block, block),
                             jnp.asarray(labels, jnp.int32).reshape(T // block, block)))
    return nll.sum() / jnp.maximum(_f32(valid).sum(), 1.0)


def loss_and_grads(params, cfg, ids, positions, valid_length, labels, wrt, operands=None):
    """``(loss, out, grads)``: the loss of one batch, :func:`forward`'s
    ``out`` and the loss's gradient with respect to the parameters named in
    ``wrt``, a dict by name."""
    def loss_of(some):
        p = {**params, **some}
        out = forward(p, cfg, ids, positions, valid_length, operands)
        return lm_loss(p, out["hidden"], out["valid"], labels, operands), out

    (loss, out), grads = jax.value_and_grad(loss_of, has_aux=True)(
        {name: _f32(params[name]) for name in wrt})
    return loss, out, grads
