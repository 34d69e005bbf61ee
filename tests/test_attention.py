"""Fused attention ops: interleaved contrib parity + flash kernel vs XLA
(reference test model: tests/python/unittest/test_operator.py attention
cases + check_consistency, SURVEY §4)."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops.attention import (
    dot_product_attention, interleaved_matmul_selfatt_qk,
    interleaved_matmul_selfatt_valatt, interleaved_matmul_encdec_qk,
    interleaved_matmul_encdec_valatt)
from incubator_mxnet_tpu.ops.pallas.flash_attention import flash_attention


def _dense_ref(q, k, v, mask=None, causal=False):
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    if mask is not None:
        s = jnp.where(mask.astype(bool), s, -1e30)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((lq, lk), bool), lk - lq), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def test_dot_product_attention_xla_matches_dense():
    rng = onp.random.RandomState(0)
    B, H, L, D = 2, 3, 17, 8          # odd L: must work on the XLA path
    q, k, v = (jnp.asarray(rng.randn(B, H, L, D), jnp.float32) for _ in range(3))
    vl = rng.randint(3, L, (B,))
    mask = jnp.asarray((onp.arange(L)[None, :] < vl[:, None]
                        ).astype("float32")[:, None, None, :])
    for causal in (False, True):
        out = dot_product_attention(q, k, v, mask, causal=causal, impl="xla")
        ref = _dense_ref(q, k, v, mask, causal)
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_flash_kernel_matches_xla(causal, masked):
    """Pallas kernel (interpret mode on CPU) == XLA path, fwd + grads."""
    rng = onp.random.RandomState(1)
    B, H, L, D = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, L, D), jnp.float32) for _ in range(3))
    mask = None
    if masked:
        vl = rng.randint(64, L, (B,))
        mask = jnp.asarray((onp.arange(L)[None, :] < vl[:, None]
                            ).astype("float32")[:, None, None, :])
    out = flash_attention(q, k, v, mask=mask, causal=causal)
    ref = dot_product_attention(q, k, v, mask, causal=causal, impl="xla") \
        if masked else dot_product_attention(q, k, v, causal=causal, impl="xla")
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref), atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.mean(flash_attention(q, k, v, mask=mask, causal=causal) ** 2)

    def loss_xla(q, k, v):
        if masked:
            return jnp.mean(dot_product_attention(q, k, v, mask, causal=causal,
                                                  impl="xla") ** 2)
        return jnp.mean(dot_product_attention(q, k, v, causal=causal,
                                              impl="xla") ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        onp.testing.assert_allclose(onp.asarray(b), onp.asarray(a),
                                    atol=1e-6, rtol=1e-3)


def test_flash_cross_length_causal_matches_xla():
    """Bottom-right-aligned causal masking when Lq != Lk (decode shapes)."""
    rng = onp.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 256, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 256, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref), atol=2e-5)


def test_flash_rejects_non_divisible_lengths():
    # Sublane-aligned lengths <= 1024 fit one block (unaligned ones are
    # env-gated); beyond 1024 a length with no 512/256 divisor has no tiling
    # — reject so the caller routes to the XLA path.
    rng = onp.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 1, 1500, 32), jnp.float32)
               for _ in range(3))
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


def test_flash_odd_mid_length_single_block(monkeypatch):
    # 300 % 8 != 0: sublane-unaligned single blocks are env-gated until
    # validated on hardware; the default routes such shapes to XLA.
    from incubator_mxnet_tpu.ops.pallas.flash_attention import (
        _auto_block, flash_supported)
    rng = onp.random.RandomState(8)
    q, k, v = (jnp.asarray(rng.randn(1, 1, 300, 32), jnp.float32)
               for _ in range(3))
    # backend-independent: the alignment gate itself must reject 300
    assert 300 % _auto_block(300) != 0
    assert _auto_block(296) == 296          # 296 % 8 == 0: single block ok
    assert not flash_supported(q, k, v)
    out = dot_product_attention(q, k, v)          # auto: falls back to XLA
    ref = dot_product_attention(q, k, v, impl="xla")
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                atol=2e-5, rtol=2e-5)
    monkeypatch.setenv("MXTPU_FLASH_UNALIGNED", "1")
    out = flash_attention(q, k, v)                # opt-in single block
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                atol=2e-5, rtol=2e-5)


def test_flash_odd_short_length_now_supported():
    rng = onp.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 200, 32), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v)
    ref = dot_product_attention(q, k, v, impl="xla")
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref), atol=2e-5)


def test_interleaved_selfatt_ops_match_dense():
    """Reference-layout contract: (L, B, H*3*D) interleaved qkv, scores
    (B*H, L, L) with q pre-scaled (src/operator/contrib/transformer.cc)."""
    rng = onp.random.RandomState(2)
    L, B, H, D = 12, 3, 4, 8
    qkv = jnp.asarray(rng.randn(L, B, H * 3 * D), jnp.float32)
    scores = interleaved_matmul_selfatt_qk(qkv, heads=H)
    assert scores.shape == (B * H, L, L)
    att = jax.nn.softmax(scores, -1)
    out = interleaved_matmul_selfatt_valatt(qkv, att, heads=H)
    assert out.shape == (L, B, H * D)

    x = onp.asarray(qkv).reshape(L, B, H, 3, D)
    q = jnp.asarray(x[:, :, :, 0].transpose(1, 2, 0, 3))
    k = jnp.asarray(x[:, :, :, 1].transpose(1, 2, 0, 3))
    v = jnp.asarray(x[:, :, :, 2].transpose(1, 2, 0, 3))
    ref = _dense_ref(q, k, v)
    ref_out = onp.asarray(ref).transpose(2, 0, 1, 3).reshape(L, B, H * D)
    onp.testing.assert_allclose(onp.asarray(out), ref_out, atol=2e-5)


def test_interleaved_encdec_ops_match_dense():
    rng = onp.random.RandomState(3)
    Lq, Lk, B, H, D = 7, 11, 2, 2, 8
    qs = jnp.asarray(rng.randn(Lq, B, H * D), jnp.float32)
    kv = jnp.asarray(rng.randn(Lk, B, H * 2 * D), jnp.float32)
    scores = interleaved_matmul_encdec_qk(qs, kv, heads=H)
    assert scores.shape == (B * H, Lq, Lk)
    att = jax.nn.softmax(scores, -1)
    out = interleaved_matmul_encdec_valatt(kv, att, heads=H)
    assert out.shape == (Lq, B, H * D)

    q = jnp.asarray(onp.asarray(qs).reshape(Lq, B, H, D).transpose(1, 2, 0, 3))
    x = onp.asarray(kv).reshape(Lk, B, H, 2, D)
    k = jnp.asarray(x[:, :, :, 0].transpose(1, 2, 0, 3))
    v = jnp.asarray(x[:, :, :, 1].transpose(1, 2, 0, 3))
    ref = _dense_ref(q, k, v)
    ref_out = onp.asarray(ref).transpose(2, 0, 1, 3).reshape(Lq, B, H * D)
    onp.testing.assert_allclose(onp.asarray(out), ref_out, atol=2e-5)


def test_nd_contrib_aliases_exposed():
    """The reference op names are callable from mx.nd (mx.nd.contrib parity)."""
    rng = onp.random.RandomState(4)
    qkv = mx.nd.array(rng.randn(6, 2, 2 * 3 * 4).astype("float32"))
    s = mx.nd._contrib_interleaved_matmul_selfatt_qk(qkv, heads=2)
    assert s.shape == (4, 6, 6)
    out = mx.nd.dot_product_attention(
        mx.nd.array(rng.randn(1, 2, 8, 4).astype("float32")),
        mx.nd.array(rng.randn(1, 2, 8, 4).astype("float32")),
        mx.nd.array(rng.randn(1, 2, 8, 4).astype("float32")))
    assert out.shape == (1, 2, 8, 4)


def _banded_ref(q, k, v, window, mask=None):
    """Dense reference for causal sliding-window attention."""
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    lq, lk = s.shape[-2], s.shape[-1]
    band = jnp.logical_and(
        jnp.tril(jnp.ones((lq, lk), bool), lk - lq),
        jnp.triu(jnp.ones((lq, lk), bool), lk - lq - window + 1))
    if mask is not None:
        band = jnp.logical_and(band, mask.astype(bool))
    s = jnp.where(band, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", [16, 96, 300])
def test_flash_sliding_window_matches_banded_dense(window, monkeypatch):
    # 64-row tiles over L=256 so the band spans several tiles and whole
    # tiles die on both sides of it (the O(L*W) skip path)
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    rng = onp.random.RandomState(1)
    B, H, L, D = 2, 2, 256, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
               for _ in range(3))
    out = flash_attention(q, k, v, causal=True, window=window)
    ref = _banded_ref(q, k, v, window)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                atol=2e-4)

    # gradients through the banded kernel == gradients through dense
    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, window=window)
                * 0.1).sum()

    def f_ref(q, k, v):
        return (_banded_ref(q, k, v, window) * 0.1).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    atol=3e-4)


def test_flash_sliding_window_with_key_padding(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    rng = onp.random.RandomState(2)
    B, H, L, D = 2, 2, 128, 16
    q, k, v = (jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
               for _ in range(3))
    vl = onp.array([90, 128])
    key_mask = jnp.asarray((onp.arange(L)[None, :] < vl[:, None]
                            ).astype("float32"))
    out = flash_attention(q, k, v, mask=key_mask, causal=True, window=50)
    full = onp.broadcast_to(onp.asarray(key_mask)[:, None, None, :],
                            (B, H, L, L))
    ref = _banded_ref(q, k, v, 50, mask=jnp.asarray(full))
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                atol=2e-4)


def test_window_validation_and_xla_parity():
    rng = onp.random.RandomState(3)
    B, H, L, D = 1, 2, 64, 8
    q, k, v = (jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
               for _ in range(3))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, window=8)
    out = dot_product_attention(q, k, v, causal=True, window=12, impl="xla")
    ref = _banded_ref(q, k, v, 12)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                atol=2e-5)


def test_window_rejects_zero_and_ring():
    rng = onp.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(1, 1, 16, 8), jnp.float32)
               for _ in range(3))
    with pytest.raises(ValueError, match="positive"):
        dot_product_attention(q, k, v, causal=True, window=0, impl="xla")
    with pytest.raises(ValueError, match="ring"):
        dot_product_attention(q, k, v, causal=True, window=8, impl="ring")


@pytest.mark.parametrize("axes,masked", [(dict(dp=2, tp=2), True),
                                         (dict(dp=4, tp=2), False),
                                         (dict(dp=3, tp=1), True)],
                         ids=["dp2_tp2_masked", "dp4_tp2", "dp3_indivisible"])
def test_flash_under_a_mesh_runs_per_shard(axes, masked):
    """GSPMD cannot partition a Mosaic kernel, so inside a step compiled
    over a multi-device mesh the flash call is wrapped in a full-manual
    shard_map (batch over dp, heads over tp where they divide; replicated
    where they do not): same values and grads as the XLA path, and the
    shard_map really is in the graph."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    n = axes["dp"] * axes["tp"]
    mesh = parallel.make_mesh(devices=jax.devices()[:n], **axes)
    rng = onp.random.RandomState(0)
    B, H, L, D = 4, 4, 64, 16
    q, k, v, do = (jnp.asarray(rng.randn(B, H, L, D).astype("float32"))
                   for _ in range(4))
    mask = None
    if masked:
        lens = onp.array([64, 40, 17, 33])
        mask = jnp.asarray(onp.arange(L)[None, :] < lens[:, None]
                           ).reshape(B, 1, 1, L)

    def run(impl):
        def f(q, k, v, do):
            o, vjp = jax.vjp(lambda q, k, v: dot_product_attention(
                q, k, v, mask, impl=impl), q, k, v)
            return (o,) + vjp(do)
        with active_mesh(mesh):
            jaxpr = str(jax.make_jaxpr(f)(q, k, v, do))
            return jax.jit(f)(q, k, v, do), jaxpr

    got, jaxpr = run("flash")
    want, ref_jaxpr = run("xla")
    assert "shard_map" in jaxpr and "shard_map" not in ref_jaxpr
    for g, w in zip(got, want):
        onp.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
