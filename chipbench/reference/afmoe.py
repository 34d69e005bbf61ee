"""AFMoE (Arcee's ``afmoe`` family, e.g. Trinity-Mini), plainly: forward pass
and next-token loss in float32 ``jax.numpy``; gradients through ``jax.grad``.

Follows the family's public modeling code and ``config.json`` keys. No
kernels, no gluon, no sort, no dispatch: causal and window are dense masks,
the experts are a loop, and a token's expert weights are a dense ``(T, E)``
matrix. ``params`` is the system's own parameters keyed by name without the
block prefix (``embed_weight``, ``layer3_attn_q_weight``,
``layer3_moe_experts_w13``, ...); dense weights are ``(out, in)``; arrays of
any float dtype are upcast where they are used, layer by layer, so the
float32 copy of a large bf16 net never exists at once.

The equations (``h`` the residual stream, every norm an RMSNorm):

    h  = E[ids] * sqrt(hidden)                        (mup_enabled)
    a  = h + N2(Attn(N1 h));  h' = a + N4(FFN(N3 a))  (four norms a layer)
    Attn: q, k RMS-normed per head; rotary on q, k in sliding layers only;
          causal softmax(q k^T / sqrt(D)) v over K/V head  head // group,
          a sliding layer seeing the `sliding_window` most recent keys;
          out = Wo (attn * sigmoid(Wg x))
    FFN:  W2 (silu(W1 x) * W3 x)   dense in the first num_dense_layers, then
    MoE:  s = sigmoid(Wr x); the num_experts_per_tok largest of s + bias;
          w = s[top] / (sum + 1e-20) * route_scale;
          Shared(x) + sum over the chosen experts held of w_e Expert_e(x)

Departures from the published model, each the system's own share or layout
and made here exactly as there:

- the chip's share: only the experts ``[expert_first, expert_first +
  experts_held)`` exist in ``params`` and only they add to the result; the
  router still scores all ``num_experts``; the vocabulary is the slice
  ``vocab_size`` of the configuration;
- an expert's gate and up projections are stacked in one ``(2F, C)`` matrix
  (``experts_w13``), gate first;
- the selection bias is held at zero (its update is a training recipe
  outside the gradient); padding is a key mask from ``valid_length``;
- attention is computed one (row, head) at a time so that an ``L x L``
  score matrix at L=8,192 fits; the mathematics is unchanged.
"""
import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(gamma)


def _rotary(x, positions, theta):
    """``x (B, L, H, D)``: rotate the pairs (first half, second half)."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = _f32(positions)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w, operands=None):
    """``x @ w.T`` for a weight ``(out, in)``. ``operands`` names a dtype to
    round both operands to first: the lower-precision control that the
    cell's tolerances have to fail (None, the reference proper, rounds
    nothing)."""
    x, w = _f32(x), _f32(w)
    if operands is not None:
        x, w = (_f32(t.astype(operands)) for t in (x, w))
    return x @ w.T


def _gated(x, gate, up, down, operands=None):
    return _mm(jax.nn.silu(_mm(x, gate, operands)) * _mm(x, up, operands), down, operands)


def attention(p, pre, cfg, x, positions, keep, window, operands=None):
    B, L, _ = x.shape
    H, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _rms(_mm(x, p[pre + "q_weight"], operands).reshape(B, L, H, D),
             p[pre + "q_norm_gamma"], eps)
    k = _rms(_mm(x, p[pre + "k_weight"], operands).reshape(B, L, Hkv, D),
             p[pre + "k_norm_gamma"], eps)
    v = _mm(x, p[pre + "v_weight"], operands).reshape(B, L, Hkv, D)
    if window is not None:
        q, k = (_rotary(t, positions, cfg["rope_theta"]) for t in (q, k))
    kv_head = jnp.arange(H) // (H // Hkv)
    rows, cols = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    see = cols <= rows
    if window is not None:
        see = see & (cols > rows - window)

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
    gate = jax.nn.sigmoid(_mm(x, p[pre + "gate_weight"], operands))
    return _mm(o * gate, p[pre + "o_weight"], operands)


def route(p, pre, cfg, x):
    """``(idx (T, k), weight (T, k), gap (T,))`` over all experts; ``gap`` is
    the margin in selection score between the last expert taken and the
    first left out (a near-tie is where a rounding may swap them)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _f32(p[pre + "router_weight"]).T)
    top, idx = jax.lax.top_k(s + _f32(p[pre + "expert_bias"]), k + 1)
    w = jnp.take_along_axis(s, idx[:, :k], 1)
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx[:, :k], w * cfg["route_scale"], top[:, k - 1] - top[:, k]


def moe(p, pre, cfg, x, held, operands=None):
    """``(out (T, C), (idx, gap))``: shared experts plus what the experts
    ``held = (first, count)`` add; ``experts_w13``/``experts_w2`` hold those
    ``count`` experts."""
    first, count = held
    idx, w, gap = route(p, pre, cfg, x)
    dense_w = (w[:, :, None] * (idx[:, :, None] == jnp.arange(cfg["num_experts"]))).sum(1)
    out = _gated(x, p[pre + "shared_gate_weight"], p[pre + "shared_up_weight"],
                 p[pre + "shared_down_weight"], operands)
    for e in range(count):
        gate, up = jnp.split(_f32(p[pre + "experts_w13"][e]), 2, axis=0)
        out = out + dense_w[:, first + e, None] * _gated(
            x, gate, up, p[pre + "experts_w2"][e], operands)
    return out, (idx, gap)


def forward(params, cfg, ids, positions, valid_length, operands=None):
    """``{"hidden", "logits", "valid", "routes"}``: final normed hidden state
    ``(B, L, C)``, logits over the vocabulary held, the valid-position mask,
    and for each MoE layer ``(idx (T, k), gap (T,))``. ``operands``: see
    :func:`_mm`; the router's scores stay float32 either way, as the
    configuration states them."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, cfg["rms_norm_eps"]
        B, L = ids.shape
        C = cfg["hidden_size"]
        held = (cfg.get("expert_first", 0), cfg.get("experts_held", cfg["num_experts"]))
        keep = jnp.arange(L)[None, :] < jnp.asarray(valid_length)[:, None]
        h = _f32(p["embed_weight"])[ids] * (C ** 0.5 if cfg.get("mup_enabled") else 1.0)
        routes = []
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"
            window = cfg["sliding_window"] if kind == "sliding_attention" else None
            a = attention(p, pre + "attn_", cfg, _rms(h, p[pre + "norm1_gamma"], eps),
                          positions, keep, window, operands)
            h = h + _rms(a, p[pre + "norm2_gamma"], eps)
            x = _rms(h, p[pre + "norm3_gamma"], eps)
            if i < cfg["num_dense_layers"]:
                f = _gated(x, p[pre + "ffn_gate_weight"], p[pre + "ffn_up_weight"],
                           p[pre + "ffn_down_weight"], operands)
            else:
                f, r = moe(p, pre + "moe_", cfg, x.reshape(B * L, C), held, operands)
                f = f.reshape(B, L, C)
                routes.append(r)
            h = h + _rms(f, p[pre + "norm4_gamma"], eps)
        hidden = _rms(h, p["norm_gamma"], eps)
        return {"hidden": hidden, "logits": _mm(hidden, p["lm_head_weight"], operands),
                "valid": keep.astype(jnp.float32), "routes": routes}


def lm_loss(logits, valid, labels):
    """Mean next-token cross-entropy over the valid positions."""
    logp = jax.nn.log_softmax(_f32(logits), -1)
    nll = -jnp.take_along_axis(logp, jnp.asarray(labels, jnp.int32)[..., None], -1)[..., 0]
    return (nll * valid).sum() / jnp.maximum(valid.sum(), 1.0)
