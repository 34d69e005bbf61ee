"""DeepSeek-V3-style decoder (``model_type`` ``deepseek_v3``, e.g.
Kanana-2-30B-A3B), plainly: forward pass and next-token loss in float32
``jax.numpy``; gradients through ``jax.grad``.

Follows the family's public modeling code and ``config.json`` keys. No
kernels, no gluon, no sort, no dispatch: the causal mask is dense, the
experts are a loop, a token's expert weights are a dense ``(T, E)`` matrix,
and every head's key is written out (``k_n`` of the head beside the one
``k_r`` of the token). ``params`` is the system's own parameters keyed by
name without the block prefix (``embed_weight``, ``layer3_attn_kv_a_weight``,
``layer3_moe_experts_w13``, ...); dense weights are ``(out, in)``; arrays of
any float dtype are upcast where they are used, layer by layer. It imports
nothing from the program; the float32 primitives, the gated FFN, the router
and the loss are ``reference/afmoe.py``'s, given this family's keys.

The equations (``h`` the residual stream, every norm an RMSNorm with a
learnt scale, no bias; ``H`` heads, ``dn`` / ``dr`` / ``dv`` =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``, ``r`` =
``kv_lora_rank``):

    h  = E[ids]                                  (no embedding scale)
    a  = h + Attn(N1 h);  h' = a + FFN(N2 a)     (pre-norm, two norms a layer)
    Attn: q = Wq x -> (H, dn + dr) = q_n | q_r a head   (q_lora_rank null)
          [c | k_r] = Wkva x -> r | dr           (k_r one vector a token)
          [k_n | v] = Wkvb RMSNorm_r(c) -> (H, dn + dv) a head
          q_r, k_r rotated by position on the pairs (2i, 2i + 1)
          s_h[i, j] = (q_n[h, i] . k_n[h, j] + q_r[h, i] . k_r[j]) / sqrt(dn + dr)
          causal softmax over the valid keys; Attn = Wo concat_h(p_h v_h)
    FFN:  W2 (silu(W1 x) * W3 x)   dense in the first first_k_dense_replace, then
    MoE:  s = sigmoid(Wr x); the num_experts_per_tok largest of s + bias;
          w = s[top] / (sum + 1e-20) * routed_scaling_factor;
          Shared(x) + sum over the chosen experts held of w_e Expert_e(x)

Departures from the published model, each the system's own share or layout
and made here exactly as there:

- the chip's share: only the experts ``[expert_first, expert_first +
  experts_held)`` exist in ``params`` and only they add to the result; the
  router still scores all ``n_routed_experts``; the vocabulary is the slice
  ``vocab_size`` of the configuration;
- an expert's gate and up projections are stacked in one ``(2F, C)`` matrix
  (``experts_w13``), gate first; the ``n_shared_experts`` shared experts are
  one gated FFN of their summed width, as the source stores them;
- ``n_group`` = ``topk_group`` = 1, so the group-limited selection is the
  plain top-k; the selection bias is held at zero (its update is a training
  recipe outside the gradient); padding is a key mask from ``valid_length``;
- rotated pairs stay at ``(2i, 2i + 1)`` where the source's code moves them
  to ``(i, i + dr/2)``: the same permutation of q_r's and k_r's components,
  so every score is the source's;
- attention is computed one (row, head) at a time so that an ``L x L``
  score matrix at L=8,192 fits; the mathematics is unchanged.
"""
import jax
import jax.numpy as jnp

from chipbench.reference.afmoe import _f32, _gated, _mm, _rms, lm_loss, moe  # noqa: F401


def _rotary_pairs(x, positions, theta):
    """``x (B, L, H, D)``: rotate each pair ``(2i, 2i + 1)`` by ``position *
    theta ** (-2i / D)``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = _f32(positions)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(*x.shape[:-1], D // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def attention(p, pre, cfg, x, positions, keep, operands=None):
    B, L, _ = x.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = _mm(x, p[pre + "q_weight"], operands).reshape(B, L, H, dn + dr)
    latent = _mm(x, p[pre + "kv_a_weight"], operands)
    c = _rms(latent[..., :r], p[pre + "kv_norm_gamma"], cfg["rms_norm_eps"])
    kv = _mm(c, p[pre + "kv_b_weight"], operands).reshape(B, L, H, dn + dv)
    q_r = _rotary_pairs(q[..., dn:], positions, cfg["rope_theta"])
    k_r = _rotary_pairs(latent[..., None, r:], positions, cfg["rope_theta"])
    # every head's whole query and key, written out: (B, L, H, dn + dr)
    query = jnp.concatenate([q[..., :dn], q_r], -1)
    key = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (B, L, H, dr))], -1)
    see = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]

    def one_head(args):
        qh, kh, vh, keep_b = args                     # (L, dn + dr) x2, (L, dv), (L,)
        s = jnp.where(see & keep_b[None, :],
                      _mm(qh, kh, operands) * (dn + dr) ** -0.5, -1e30)
        return _mm(jax.nn.softmax(s, -1), vh.T, operands)

    heads = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, L, -1)  # noqa: E731
    o = jax.lax.map(one_head, (heads(query), heads(key), heads(kv[..., dn:]),
                               jnp.repeat(keep, H, axis=0)))
    o = o.reshape(B, H, L, dv).transpose(0, 2, 1, 3).reshape(B, L, H * dv)
    return _mm(o, p[pre + "o_weight"], operands)


def forward(params, cfg, ids, positions, valid_length, operands=None):
    """``{"hidden", "logits", "valid", "routes"}``: final normed hidden state
    ``(B, L, C)``, logits over the vocabulary held, the valid-position mask,
    and for each MoE layer ``(idx (T, k), gap (T,))``. ``operands`` names a
    dtype to round every matmul operand to first (the lower-precision
    control the cell's limits have to fail); the router's scores stay
    float32 either way, as the configuration states them."""
    with jax.default_matmul_precision("highest"):
        p, eps = params, cfg["rms_norm_eps"]
        B, L = ids.shape
        C = cfg["hidden_size"]
        held = (cfg.get("expert_first", 0), cfg.get("experts_held", cfg["n_routed_experts"]))
        router = dict(num_experts=cfg["n_routed_experts"],
                      num_experts_per_tok=cfg["num_experts_per_tok"],
                      route_norm=cfg["norm_topk_prob"],
                      route_scale=cfg["routed_scaling_factor"])
        keep = jnp.arange(L)[None, :] < jnp.asarray(valid_length)[:, None]
        h = _f32(p["embed_weight"])[ids]
        routes = []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"layer{i}_"
            h = h + attention(p, pre + "attn_", cfg, _rms(h, p[pre + "norm1_gamma"], eps),
                              positions, keep, operands)
            x = _rms(h, p[pre + "norm2_gamma"], eps)
            if i >= cfg["first_k_dense_replace"] and i % cfg.get("moe_layer_freq", 1) == 0:
                f, route = moe(p, pre + "moe_", router, x.reshape(B * L, C), held, operands)
                f = f.reshape(B, L, C)
                routes.append(route)
            else:
                f = _gated(x, p[pre + "ffn_gate_weight"], p[pre + "ffn_up_weight"],
                           p[pre + "ffn_down_weight"], operands)
            h = h + f
        hidden = _rms(h, p["norm_gamma"], eps)
        return {"hidden": hidden, "logits": _mm(hidden, p["lm_head_weight"], operands),
                "valid": keep.astype(jnp.float32), "routes": routes}
