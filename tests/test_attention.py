"""Fused attention ops: interleaved contrib parity + flash kernel vs XLA
(reference test model: tests/python/unittest/test_operator.py attention
cases + check_consistency, SURVEY §4)."""
import collections
import contextlib

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


# ---------------------------------------------------------------------------
# the causal kernels' tile schedule (flash_attention._tile_schedule)
# ---------------------------------------------------------------------------

def _tile_truth(Lq, Lk, bq, bk, window):
    """``{(i, j): every pair visible?}`` for the tiles with a visible pair,
    by brute force over the kernel's own in-tile mask."""
    from incubator_mxnet_tpu.ops.pallas.flash_attention import _band
    rows, cols = onp.arange(Lq)[:, None], onp.arange(Lk)[None, :]
    seen = onp.asarray(_band(rows, cols, Lk - Lq, window))
    tiles = seen.reshape(Lq // bq, bq, Lk // bk, bk).transpose(0, 2, 1, 3)
    return {(i, j): bool(tiles[i, j].all()) for i in range(Lq // bq)
            for j in range(Lk // bk) if tiles[i, j].any()}


# (Lq, Lk, bq, bk): square, Lq < Lk (decode shapes; with a window, key
# blocks older than every query's window), Lq > Lk (rows with no key), and
# tiles that are not square
_SCHEDULE_SHAPES = [(256, 256, 64, 64), (128, 256, 64, 64), (256, 128, 64, 64),
                    (256, 256, 64, 32), (256, 256, 32, 64)]


@pytest.mark.parametrize("heads", [None, 1, 3], ids=["stream_k", "dkv", "dkv_3_heads"])
@pytest.mark.parametrize("window", [None, 16, 64, 100, 300],
                         ids=["full", "w_lt_tile", "w_eq_tile", "w_not_multiple", "w_gt_L"])
@pytest.mark.parametrize("shape", _SCHEDULE_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_tile_schedule_lists_the_live_tiles_once(shape, window, heads):
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    Lq, Lk, bq, bk = shape
    truth = _tile_truth(Lq, Lk, bq, bk, window)
    sched = fa._tile_schedule(Lq, Lk, bq, bk, window, heads)
    assert len(sched.tables) == (3 if heads is None else 4)
    assert all(t.dtype == onp.int32 and t.shape == sched.tables[0].shape for t in sched.tables)
    fixed, streamed, kind = (t.tolist() for t in sched.tables[:3])
    head = sched.tables[3].tolist() if heads else [0] * len(kind)
    live = [(f, s, h) for f, s, k, h in zip(fixed, streamed, kind, head)
            if k & (fa._WHOLE | fa._CUT)]
    # every tile with a visible pair once a head, none without one, in the
    # rectangular grid's order: fixed block, head, streamed block
    tile = (lambda f, s: (f, s)) if heads is None else (lambda f, s: (s, f))
    assert live == sorted(set(live), key=lambda x: (x[0], x[2], x[1]))
    assert {tile(f, s) for f, s, _ in live} == set(truth)
    assert len(live) == len(truth) * (heads or 1)
    # cut exactly where some pair is hidden; never both kinds
    for f, s, k in zip(fixed, streamed, kind):
        if k & (fa._WHOLE | fa._CUT):
            assert bool(k & fa._WHOLE) == truth[tile(f, s)]
            assert bool(k & fa._CUT) != truth[tile(f, s)]
    # a fixed block (and head) with no live tile has the one step that
    # writes its zeros; first / last one each a (fixed block, head), at its
    # ends; open / close one each a fixed block
    n_fixed = (Lq // bq) if heads is None else (Lk // bk)
    segments = {}
    for t, (f, h) in enumerate(zip(fixed, head)):
        segments.setdefault((f, h), []).append(t)
    assert sorted(segments) == [(f, h) for f in range(n_fixed) for h in range(heads or 1)]
    for (f, h), steps in segments.items():
        assert steps == list(range(steps[0], steps[-1] + 1))
        kinds = [kind[t] for t in steps]
        if not any(k & (fa._WHOLE | fa._CUT) for k in kinds):
            assert len(steps) == 1
        else:
            assert all(k & (fa._WHOLE | fa._CUT) for k in kinds)
        assert [bool(k & fa._FIRST) for k in kinds] == [True] + [False] * (len(steps) - 1)
        assert [bool(k & fa._LAST) for k in kinds] == [False] * (len(steps) - 1) + [True]
        assert [bool(k & fa._OPEN) for k in kinds] == [h == 0] + [False] * (len(steps) - 1)
        assert [bool(k & fa._CLOSE) for k in kinds] == \
            [False] * (len(steps) - 1) + [h == (heads or 1) - 1]
    assert sched.live_share == len(truth) / ((Lq // bq) * (Lk // bk))
    assert sched.cut_share == sum(not w for w in truth.values()) / len(truth)


@pytest.mark.parametrize("window,steps,cut", [(None, 136, 16), (2048, 70, 28)],
                         ids=["full", "window2048"])
def test_tile_schedule_and_its_gauges_at_the_cells_shapes(window, steps, cut):
    """L = 8,192 at 512-row tiles: 136 steps a head where the rectangle has
    256 (70 with Trinity's window), and the gauges say so for each kernel a
    causal call traces; a non-causal call sets nothing."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    from incubator_mxnet_tpu.telemetry import metrics
    sched = fa._tile_schedule(8192, 8192, 512, 512, window)
    assert len(sched.tables[0]) == steps
    assert len(fa._tile_schedule(8192, 8192, 512, 512, window, 8).tables[0]) == 8 * steps
    names = [fa._kernel_name(k, window) for k in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")]
    gauges = [metrics.gauge(f"mxtpu_flash_tiles_{what}_share", kernel=name)
              for name in names for what in ("live", "cut")]
    for g in gauges:
        g.set(-1.0)
    q = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, 8192, 128), jnp.bfloat16)
    if window is None:
        jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(q, k, v).sum().astype(
            jnp.float32), (0, 1, 2)), q, kv, kv)
        assert [g.value for g in gauges] == [-1.0] * 6
    jax.eval_shape(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window).sum().astype(jnp.float32), (0, 1, 2)), q, kv, kv)
    assert [g.value for g in gauges] == [steps / 256, cut / steps] * 3
    assert round(steps / 256, 2) == (0.53 if window is None else 0.27)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, by kernel name."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.update(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_only_a_causal_call_walks_a_schedule(causal, monkeypatch):
    """A non-causal call has no dead tile and no band: its three kernels
    keep the rectangular grid and take no scalar-prefetch operand. A causal
    call's streamed dimension has one step a live tile."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    B, H, Hkv, L, D = 2, 4, 2, 256, 16
    q = jnp.zeros((B, H, L, D), jnp.float32)
    kv = jnp.zeros((B, Hkv, L, D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=causal).sum(), (0, 1, 2)))(q, kv, kv)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    grids = {name: (eqn.params["grid_mapping"].grid,
                    eqn.params["grid_mapping"].num_index_operands)
             for name, eqn in calls.items()}
    if not causal:
        assert grids == {"flash_fwd": ((B * H, 4, 4), 0), "flash_bwd_dq": ((B * H, 4, 4), 0),
                         "flash_bwd_dkv": ((B * Hkv, 4, 2 * 4), 0)}
    else:
        live = len(_tile_truth(L, L, 64, 64, None))        # 10 of 16
        assert grids == {"flash_fwd": ((B * H, live), 3), "flash_bwd_dq": ((B * H, live), 3),
                         "flash_bwd_dkv": ((B * Hkv, 2 * live), 4)}


def _reference(q, k, v, mask=None, window=None, shared=None):
    """Dense causal attention (bottom-right aligned) over grouped K/V heads,
    with an optional window, key mask and shared second score term; a row
    that sees no key gives zeros, as the kernel does."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    k, v = (jnp.repeat(x, H // x.shape[1], axis=1) for x in (k, v))
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], -1)
        k = jnp.concatenate([k, jnp.broadcast_to(shared[1], (B, H, Lk, shared[1].shape[-1]))], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    seen = jnp.tril(jnp.ones((Lq, Lk), bool), Lk - Lq)
    if window is not None:
        seen = seen & jnp.triu(jnp.ones((Lq, Lk), bool), Lk - Lq - window + 1)
    seen = jnp.broadcast_to(seen, s.shape)
    if mask is not None:
        seen = seen & mask[:, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.where(seen.any(-1, keepdims=True), p, 0.0), v)


# (Hkv, Lq, Lk, window, key padding, width of the shared pair) at H = 4 and
# 64-row tiles: four tiles a side, so every kind of tile is there
@pytest.mark.parametrize("Hkv,Lq,Lk,window,padded,Ds", [
    pytest.param(4, 256, 256, None, False, None, id="causal"),
    pytest.param(4, 256, 256, 100, False, None, id="window"),
    pytest.param(4, 256, 256, 64, True, None, id="window_padded"),
    pytest.param(4, 256, 256, None, True, None, id="padded"),
    pytest.param(2, 256, 256, None, False, None, id="grouped"),
    pytest.param(1, 256, 256, 70, True, None, id="grouped_window_padded"),
    pytest.param(4, 256, 256, None, False, 8, id="shared"),
    pytest.param(4, 256, 256, 100, True, 8, id="shared_window_padded"),
    pytest.param(2, 128, 256, None, False, None, id="grouped_lq_lt_lk"),
    pytest.param(4, 128, 256, 40, False, None, id="window_lq_lt_lk"),
    pytest.param(2, 256, 128, None, True, None, id="grouped_padded_lq_gt_lk"),
    pytest.param(4, 256, 128, 70, False, 8, id="shared_window_lq_gt_lk"),
])
def test_scheduled_kernels_match_dense(Hkv, Lq, Lk, window, padded, Ds, monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    rng = onp.random.RandomState(11)
    B, H, D = 2, 4, 16

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = draw(B, H, Lq, D), draw(B, Hkv, Lk, D), draw(B, Hkv, Lk, D)
    shared = None if Ds is None else (draw(B, H, Lq, Ds), draw(B, 1, Lk, Ds))
    mask = None
    if padded:
        mask = jnp.asarray(onp.arange(Lk)[None, :] < onp.array([Lk - 37, Lk])[:, None])

    def flash(q, k, v, shared):
        return flash_attention(q, k, v, mask=mask, causal=True, window=window, shared=shared)

    def dense(q, k, v, shared):
        return _reference(q, k, v, mask, window, shared)
    out, vjp = jax.vjp(flash, q, k, v, shared)
    ref, ref_vjp = jax.vjp(dense, q, k, v, shared)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref), atol=2e-4)
    do = draw(B, H, Lq, D) * 0.1
    for got, want in zip(jax.tree_util.tree_leaves(vjp(do)),
                         jax.tree_util.tree_leaves(ref_vjp(do))):
        assert got.shape == want.shape
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want), atol=3e-4)


# ---------------------------------------------------------------------------
# the lane layout: q, k and v read as 128-lane blocks of the projections'
# own arrays (flash_attention.flash_attention_lanes, ops.projected_attention)
# ---------------------------------------------------------------------------

def _split_heads(x, n, H):
    """``(B, L, n·H·D)`` -> ``n`` head-major ``(B, H, L, D)``: what
    ``MultiHeadAttention`` traces where the lane layout is not taken."""
    B, L, _ = x.shape
    return [p.reshape(B, L, H, -1).transpose(0, 2, 1, 3) for p in jnp.split(x, n, -1)]


def _head_major_from_projections(attn, H):
    def run(x_q, x_kv):
        if x_kv is None:
            q, k, v = _split_heads(x_q, 3, H)
        else:
            (q,), (k, v) = _split_heads(x_q, 1, H), _split_heads(x_kv, 2, H)
        o = attn(q, k, v)
        B, _, Lq, D = o.shape
        return o.transpose(0, 2, 1, 3).reshape(B, Lq, H * D)
    return run


# D, self- or cross-attention (Lk), key mask, causal; 64-row tiles, so a
# call has several of each kind
@pytest.mark.parametrize("D,Lk,masked,causal", [
    pytest.param(64, None, False, False, id="D64_self"),
    pytest.param(64, None, True, False, id="D64_self_masked"),
    pytest.param(64, None, True, True, id="D64_self_masked_causal"),
    pytest.param(128, None, True, False, id="D128_self_masked"),
    pytest.param(128, None, False, True, id="D128_self_causal"),
    pytest.param(64, 192, True, False, id="D64_cross_masked"),
    pytest.param(64, 128, False, True, id="D64_cross_causal"),
    pytest.param(128, 192, False, False, id="D128_cross"),
    pytest.param(32, 128, True, True, id="D32_cross_masked_causal"),
])
def test_lane_layout_matches_head_major_and_xla(D, Lk, masked, causal, monkeypatch):
    """Output and the gradients of the projections, against today's
    head-major kernel on the transposes (to float32 rounding: the same
    kernel bodies, a head's other lanes zeroed) and against the XLA path."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    rng = onp.random.RandomState(D + (Lk or 0))
    B, L, H = 2, 128, 2 * 128 // D
    C = H * D
    if Lk is None:
        x_q, x_kv, Lk = jnp.asarray(rng.randn(B, L, 3 * C), jnp.float32), None, L
    else:
        x_q = jnp.asarray(rng.randn(B, L, C), jnp.float32)
        x_kv = jnp.asarray(rng.randn(B, Lk, 2 * C), jnp.float32)
    mask = None
    if masked:
        mask = jnp.asarray(onp.arange(Lk)[None, :] < onp.array([Lk - 37, Lk])[:, None]
                           ).reshape(B, 1, 1, Lk)
    do = jnp.asarray(rng.randn(B, L, C), jnp.float32)
    assert fa._lane_layout(x_q, x_kv, H, mask) is not None

    def lanes(x_q, x_kv):
        return fa.flash_attention_lanes(x_q, x_kv, H, mask=mask, causal=causal)
    got, vjp = jax.vjp(lanes, x_q, x_kv)
    got_g = vjp(do)
    for attn, tol in ((lambda q, k, v: flash_attention(q, k, v, mask=mask, causal=causal), 1e-6),
                      (lambda q, k, v: dot_product_attention(q, k, v, mask, causal=causal,
                                                             impl="xla"), 2e-5)):
        want, want_vjp = jax.vjp(_head_major_from_projections(attn, H), x_q, x_kv)
        onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want), atol=tol)
        for a, b in zip(jax.tree_util.tree_leaves(got_g),
                        jax.tree_util.tree_leaves(want_vjp(do))):
            assert a.shape == b.shape
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b), atol=10 * tol)


def test_lane_layout_kernels_are_the_three_named_kernels(monkeypatch):
    """Self-attention traces ``flash_fwd``, ``flash_bwd_dkv`` and
    ``flash_bwd_dq`` over grid rows of (batch row, lane block): two heads a
    block at D = 64. The dq kernel writes into the buffer the dkv kernel
    began (one aliased operand), so the projection's gradient needs no
    concatenation; the only ``transpose`` left is delta's, of the small
    float32 ``(B, L, H)``."""
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    B, L, H, D = 2, 128, 4, 64
    x = jnp.zeros((B, L, 3 * H * D), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: fa.flash_attention_lanes(x, None, H).sum()))(x)
    calls = _pallas_calls(jaxpr.jaxpr)
    assert sorted(calls) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    grids = {name: eqn.params["grid_mapping"].grid for name, eqn in calls.items()}
    assert grids == {"flash_fwd": (B * 2, 2, 2), "flash_bwd_dkv": (B * 2, 2, 2),
                     "flash_bwd_dq": (B * 2, 2, 2)}
    assert list(calls["flash_bwd_dq"].params["input_output_aliases"]) == [(6, 0)]
    names = collections.Counter(e.primitive.name for e in _walk(jaxpr.jaxpr))
    assert names["concatenate"] == 0 and names["split"] == 0
    transposes = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "transpose"]
    assert [e.outvars[0].aval.shape for e in transposes] == [(B, H, L)]


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name != "pallas_call":
                yield from _walk(sub)


def _on_a_chip(monkeypatch):
    """Steer the kernels' one platform decision to the chip's, for a test
    that only asks which path a call takes (nothing runs)."""
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret_for", lambda x: False)


@pytest.mark.parametrize("H,D,axes", [
    pytest.param(3, 64, None, id="odd_heads_at_D64"),
    pytest.param(4, 96, None, id="D96"),
    pytest.param(4, 64, dict(sp=2), id="sp2_ring"),
    pytest.param(4, 64, dict(dp=2, tp=2), id="tp2_heads_split"),
])
def test_lane_layout_falls_back_where_it_cannot_be_taken(H, D, axes, monkeypatch):
    """Odd heads at D = 64 (C no whole lane blocks), D = 96 (not a divisor
    of 128), and a mesh that shards the sequence (ring attention) or the
    heads: ``projected_attention`` splits the heads out for
    ``dot_product_attention``, the gauge reads 0, and the same call on a
    one-device mesh at D = 64 with even heads takes the lane layout."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.ops import attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    from incubator_mxnet_tpu.telemetry import metrics
    _on_a_chip(monkeypatch)
    decide = attention._lanes_taken
    seen = []
    monkeypatch.setattr(attention, "_lanes_taken",
                        lambda *a: seen.append(decide(*a)) or seen[-1])
    x = jnp.zeros((4, 256, 3 * H * D), jnp.bfloat16)
    mask = jnp.ones((4, 1, 1, 256), bool)
    mesh = None if axes is None else parallel.make_mesh(
        devices=jax.devices()[:2 * len(axes)], **axes)

    def attend(x, mask):
        return attention.projected_attention(x, mask, heads=x.shape[-1] // 3 // D)
    with active_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        out = jax.eval_shape(attend, x, mask)
    assert seen == [False] and out.shape == (4, 256, H * D)
    assert metrics.gauge("mxtpu_flash_lane_layout", kernel=f"flash_h{H}_d{D}").value == 0
    y = jnp.zeros((4, 256, 3 * 4 * 64), jnp.bfloat16)
    assert jax.eval_shape(lambda y, m: attention.projected_attention(y, m, heads=4),
                          y, mask).shape == (4, 256, 256)
    assert seen == [False, True]
    assert metrics.gauge("mxtpu_flash_lane_layout", kernel="flash_h4_d64").value == 1


# mesh size, self- or cross-attention: the lane kernels under a dp mesh, each
# shard its rows (bert_base_pretrain.dp4 is the dp = 4 case)
@pytest.mark.parametrize("dp", [2, 4], ids=["dp2", "dp4"])
@pytest.mark.parametrize("cross", [False, True], ids=["self", "cross"])
def test_projected_attention_under_a_dp_mesh_runs_per_shard(dp, cross, monkeypatch):
    """Inside a step compiled over a ``dp`` mesh the lane kernels run under
    a full-manual shard_map, each shard its batch rows and their key mask:
    output and the projections' gradients as the head-major kernels give
    them under the same mesh (interpret mode here), and as the XLA path."""
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.ops import attention
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    mesh = parallel.make_mesh(devices=jax.devices()[:dp], dp=dp)
    rng = onp.random.RandomState(dp + 2 * cross)
    B, L, H, D = 2 * dp, 128, 4, 64
    C, Lk = H * D, 192 if cross else L
    xs = ((jnp.asarray(rng.randn(B, L, C), jnp.float32),
           jnp.asarray(rng.randn(B, Lk, 2 * C), jnp.float32)) if cross
          else (jnp.asarray(rng.randn(B, L, 3 * C), jnp.float32),))
    mask = jnp.asarray(onp.arange(Lk)[None, :] < rng.randint(1, Lk + 1, B)[:, None]
                       ).reshape(B, 1, 1, Lk)
    do = jnp.asarray(rng.randn(B, L, C), jnp.float32)

    def run(lanes, impl):
        monkeypatch.setenv("MXTPU_ATTN_IMPL", impl)
        taken = []
        monkeypatch.setattr(attention, "_lanes_taken", lambda q, kv, h, m: taken.append(
            lanes and fa._lane_layout(q, kv, h, m) is not None) or taken[-1])

        def f(xs, do):
            o, vjp = jax.vjp(lambda xs: attention.projected_attention(
                *xs, mask, heads=H, cross=cross), xs)
            return (o,) + vjp(do)[0]
        with active_mesh(mesh):
            jaxpr = str(jax.make_jaxpr(f)(xs, do))
            out = jax.jit(f)(xs, do)
        assert taken and all(t == lanes for t in taken)
        return out, jaxpr

    got, jaxpr = run(True, "auto")
    assert "shard_map" in jaxpr and jaxpr.count("pallas_call") >= 3
    for (want, ref_jaxpr), tol in ((run(False, "flash"), 1e-6), (run(False, "xla"), 2e-5)):
        assert ("shard_map" in ref_jaxpr) == (tol == 1e-6)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b), atol=10 * tol)


@pytest.mark.parametrize("H,D", [(2, 64), (3, 64), (2, 96)],
                         ids=["lane_layout", "fallback_odd_heads", "fallback_D96"])
@pytest.mark.parametrize("cross", [False, True], ids=["self", "cross"])
def test_multihead_attention_is_the_same_on_both_paths(H, D, cross, monkeypatch):
    """``MultiHeadAttention``'s output and parameter gradients where its
    projections go to the kernels as they are (interpret mode here) and
    where its heads are split out for ``dot_product_attention`` (the XLA
    path on this CPU). A shape without a lane layout takes the second path
    whatever the platform, and agrees with itself."""
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.models.transformer import MultiHeadAttention
    from incubator_mxnet_tpu.ops import attention
    from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")
    rng = onp.random.RandomState(H * D + cross)
    B, L, Lk, C = 2, 128, 192 if cross else 128, H * D
    net = MultiHeadAttention(C, H, cross_attention=cross)
    net.initialize()
    x = nd.array(rng.randn(B, L, C).astype("float32"))
    kv = nd.array(rng.randn(B, Lk, C).astype("float32")) if cross else None
    mask = nd.array((onp.arange(Lk)[None, :] < onp.array([Lk - 37, Lk])[:, None]
                     ).astype("float32").reshape(B, 1, 1, Lk))

    def run():
        with autograd.record():
            out = net(x, kv, mask)
            loss = (out * out).sum()
        loss.backward()
        return out.asnumpy(), {n: p.grad().asnumpy() for n, p in net.collect_params().items()}
    want, want_g = run()
    taken = []

    def interpret_lanes(q, kv, heads, m):
        taken.append(fa._lane_layout(q, kv, heads, m) is not None)
        return taken[-1]
    monkeypatch.setattr(attention, "_lanes_taken", interpret_lanes)
    got, got_g = run()
    assert taken == [D == 64 and H % 2 == 0]
    onp.testing.assert_allclose(got, want, atol=2e-5)
    assert sorted(got_g) == sorted(want_g)
    for n in want_g:
        onp.testing.assert_allclose(got_g[n], want_g[n], rtol=1e-4,
                                    atol=1e-4 * max(1.0, float(onp.abs(want_g[n]).max())))
