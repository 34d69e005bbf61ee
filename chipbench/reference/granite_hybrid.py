"""Granite-4.0-H (``model_type`` ``granitemoehybrid`` without experts, e.g.
Granite-4.0-H-Micro), plainly: forward pass, next-token loss and gradients
in float32 ``jax.numpy`` at the highest matmul precision; gradients through
``jax.grad``; AdamW's first step from them in float64 numpy.

Written from the equations below, which are the family's public modeling
code and ``config.json`` keys. No kernels, no gluon, no chunks: the Mamba-2
mixer is **the recurrence itself, one position after another** (``lax.scan``
over positions), which shares no decomposition with the chunked algorithm
the program runs; attention is a dense causal mask. ``params`` is the
system's own parameters keyed by name without the block prefix
(``embed_weight``, ``layer0_mamba_in_proj_weight``, ``layer0_mamba_A_log``,
``layer5_attn_q_weight``, ``layer0_ffn_gate_weight``, ...); dense weights
are ``(out, in)``; arrays of any float dtype are upcast where they are used.
It imports nothing from the program; the float32 primitives (matmul,
RMSNorm, the gated MLP) are ``reference/afmoe.py``'s.

The equations (``h`` the residual stream, ``N`` an RMSNorm with a learnt
scale and ``rms_norm_eps``, ``r`` = ``residual_multiplier``)::

    h  = embedding_multiplier * E[ids];   logits = E^T N(h_last) / logits_scaling   (tied)
    a  = h + r * Mix(N1 h);  h' = a + r * W2 (silu(W1 x) * W3 x),  x = N2 a
    mamba: [z | xBC | dt] = W_in u;  xBC = silu(conv(xBC) + b)   (depthwise, causal, d_conv taps)
           [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t   (a head; B, C a group's)
           g = y * silu(z);  Mix = W_out (gamma * g / rms(g))    (the statistic over all heads)
    attention: q, k, v = Wq u, Wk u, Wv u (no positions, no norm);
           causal softmax(q k^T * attention_multiplier) v over K/V head head // (H / Hkv),
           keys masked by length;  Mix = Wo .

Departures from the published model, each the system's own share or layout
and made here exactly as there:

- the vocabulary is the slice ``vocab_size`` of the configuration;
- the convolution's weight is ``(C, K)`` where the source stores ``(C, 1,
  K)``; the gated MLP's two input projections are two matrices (``gate``,
  ``up``) where the source stores one ``(2 I, C)``;
- padding is a key mask from ``valid_length``; the Mamba mixer reads no mask
  (a padded position reaches only positions that are padded too), and its
  state crosses document boundaries inside a row (no reset);
- **so that it fits at the timed sizes** (two rows of 8,192 tokens in
  float32 beside the system's state): the recurrence is checkpointed in
  blocks of 256 positions and, inside a block, of 16, so that a layer holds
  32 boundary states a row and one block is rebuilt at a time; attention
  runs one (row, head) at a time, the head and the loss over blocks of
  tokens, and each of those and each layer is rebuilt in the backward pass
  (``jax.checkpoint``) where a gradient is asked for. The mathematics is
  unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.afmoe import _f32, _gated, _mm, _rms

BLOCK, SUB = 256, 16


def _round(v, operands):
    return v if operands is None else _f32(v.astype(operands))


def recurrence(x, dt, A, Bm, Cm, D, operands=None):
    """``y (B, L, H, P)`` of ``x (B, L, H, P)``, ``dt (B, L, H)``, ``A``,
    ``D (H,)``, ``Bm``/``Cm (B, L, G, N)``: the state updated one position
    at a time, from zero. ``operands`` rounds ``x``, ``B`` and ``C`` first,
    as the lower-precision control rounds every matmul operand."""
    Bt, L, H, P = x.shape
    x, Bm, Cm = (_round(_f32(v), operands) for v in (x, Bm, Cm))
    Bh, Ch = (jnp.repeat(v, H // v.shape[2], axis=2) for v in (Bm, Cm))
    dt, A = _f32(dt), _f32(A)

    def step(S, inp):                                   # S (B, H, P, N)
        xt, dtt, bt, ct = inp
        S = (jnp.exp(dtt * A)[..., None, None] * S
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, ct)

    pad = -L % BLOCK
    seq = tuple(jnp.moveaxis(jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)), 1, 0)
                for v in (x, dt, Bh, Ch))                # dt = 0 leaves the state as it was
    def positions(S, seq):
        return jax.lax.scan(step, S, seq)
    _, y = _in_blocks(_in_blocks(positions, SUB), BLOCK)(
        jnp.zeros((Bt, H, P, Bm.shape[-1])), seq)
    return jnp.moveaxis(y, 0, 1)[:, :L] + _f32(D)[:, None] * x


def _in_blocks(run, size):
    """``run(S, seq) -> (S, ys)`` over a sequence (leading axis) taken in
    blocks of ``size`` steps, each block rebuilt from its first state in
    the backward pass."""
    block = jax.checkpoint(run)

    def blocks(S, seq):
        S, y = jax.lax.scan(block, S, jax.tree.map(
            lambda v: v.reshape(v.shape[0] // size, size, *v.shape[1:]), seq))
        return S, y.reshape(-1, *y.shape[2:])
    return blocks


def mamba(p, pre, cfg, u, operands=None):
    """The Mamba-2 mixer of ``u (B, L, C)``."""
    B, L, _ = u.shape
    H, P, G, N, K = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
                     cfg["mamba_d_state"], cfg["mamba_d_conv"])
    inner = H * P
    z, xbc, dt = jnp.split(_mm(u, p[pre + "in_proj_weight"], operands),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    w = _f32(p[pre + "conv_weight"])
    s = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))       # zero before the row
    xbc = jax.nn.silu(sum(w[:, k] * s[:, k:k + L] for k in range(K))
                      + _f32(p[pre + "conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + _f32(p[pre + "dt_bias"]))
    y = recurrence(x.reshape(B, L, H, P), dt, -jnp.exp(_f32(p[pre + "A_log"])),
                   b.reshape(B, L, G, N), c.reshape(B, L, G, N), p[pre + "D"], operands)
    g = y.reshape(B, L, inner) * jax.nn.silu(z)
    return _mm(_rms(g, p[pre + "norm_gamma"], cfg["rms_norm_eps"]),
               p[pre + "out_proj_weight"], operands)


def attention(p, pre, cfg, u, keep, operands=None):
    B, L, C = u.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = C // H
    q = _mm(u, p[pre + "q_weight"], operands).reshape(B, L, H, D)
    k = _mm(u, p[pre + "k_weight"], operands).reshape(B, L, Hkv, D)
    v = _mm(u, p[pre + "v_weight"], operands).reshape(B, L, Hkv, D)
    kv_head = jnp.arange(H) // (H // Hkv)
    see = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh, keep_b = args                     # (L, D) x3, (L,)
        s = jnp.where(see & keep_b[None, :],
                      _mm(qh, kh, operands) * cfg["attention_multiplier"], -1e30)
        return _mm(jax.nn.softmax(s, -1), vh.T, operands)

    o = jax.lax.map(one_head, (
        q.transpose(0, 2, 1, 3).reshape(B * H, L, D),
        k.transpose(0, 2, 1, 3)[:, kv_head].reshape(B * H, L, D),
        v.transpose(0, 2, 1, 3)[:, kv_head].reshape(B * H, L, D),
        jnp.repeat(keep, H, axis=0)))
    o = o.reshape(B, H, L, D).transpose(0, 2, 1, 3).reshape(B, L, H * D)
    return _mm(o, p[pre + "o_weight"], operands)


def forward(params, cfg, ids, positions, valid_length, operands=None):
    """``{"hidden", "valid"}``: the final normed hidden state ``(B, L, C)``
    the head reads and the valid-position mask. ``operands`` names a dtype
    to round every matmul operand to first (the lower-precision control the
    cell's limits have to fail). ``positions`` is read by nothing: no layer
    has positions."""
    del positions
    with jax.default_matmul_precision("highest"):
        p, eps, r = params, cfg["rms_norm_eps"], cfg["residual_multiplier"]
        L = ids.shape[1]
        keep = jnp.arange(L)[None, :] < jnp.asarray(valid_length)[:, None]
        h = _f32(p["embed_weight"])[ids] * cfg["embedding_multiplier"]
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"layer{i}_"

            @jax.checkpoint
            def layer(p, h, pre=pre, kind=kind):
                u = _rms(h, p[pre + "norm1_gamma"], eps)
                if kind == "mamba":
                    h = h + r * mamba(p, pre + "mamba_", cfg, u, operands)
                else:
                    h = h + r * attention(p, pre + "attn_", cfg, u, keep, operands)
                x = _rms(h, p[pre + "norm2_gamma"], eps)
                return h + r * _gated(x, p[pre + "ffn_gate_weight"], p[pre + "ffn_up_weight"],
                                      p[pre + "ffn_down_weight"], operands)

            h = layer({k: v for k, v in p.items() if k.startswith(pre)}, h)
        return {"hidden": _rms(h, p["norm_gamma"], eps), "valid": keep.astype(jnp.float32)}


def logits(params, cfg, hidden, operands=None):
    """``hidden (..., C)`` through the tied head: ``hidden E^T / logits_scaling``."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden, params["embed_weight"], operands) / cfg["logits_scaling"]


def lm_loss(params, cfg, hidden, valid, labels, operands=None, block: int = 2048):
    """Mean next-token cross-entropy over the valid positions, the head and
    the log-softmax over ``block`` tokens at a time."""
    C = hidden.shape[-1]
    T = hidden.size // C
    block = next(b for b in range(min(block, T), 0, -1) if T % b == 0)

    @jax.checkpoint
    def some(args):
        h, keep, lab = args
        logp = jax.nn.log_softmax(logits(params, cfg, h, operands), -1)
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
        return lm_loss(p, cfg, out["hidden"], out["valid"], labels, operands), out

    (loss, out), grads = jax.value_and_grad(loss_of, has_aux=True)(
        {name: _f32(params[name]) for name in wrt})
    return loss, out, grads


def adamw_first_step(w, g, opt: dict) -> np.ndarray:
    """The change AdamW's first step makes to a weight ``w`` whose gradient
    is ``g``, in float64: both moments from zero and bias-corrected at
    ``t = 1``, the decay decoupled (Loshchilov and Hutter 2019); ``opt`` is
    the configuration's optimizer block (``learning_rate``, ``beta1``,
    ``beta2``, ``epsilon``, ``wd``). At ``t = 1`` the corrected moments are
    ``g`` and ``g^2``, so an element moves by the learning rate times the
    sign of its gradient wherever ``|g|`` is well above ``epsilon``."""
    w, g = np.asarray(w, "float64"), np.asarray(g, "float64")
    b1, b2 = opt["beta1"], opt["beta2"]
    m_hat = (1 - b1) * g / (1 - b1)
    v_hat = (1 - b2) * g * g / (1 - b2)
    return -opt["learning_rate"] * (m_hat / (np.sqrt(v_hat) + opt["epsilon"]) + opt["wd"] * w)
