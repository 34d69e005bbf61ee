"""The main path's kernels, compiled for a v5e that is described and not
attached (the TPU compiler is part of the installation; nothing runs).

Interpret mode, which every other kernel test uses on the CPU, cannot see
what Mosaic refuses: a slice off the tiling, too much VMEM, a kernel that
cannot be partitioned under ``shard_map``. These compiles can, at the real
widths, in about a second each. A compile that passes says nothing about
results or times: those come from ``chip_smoke.py`` on the chip.

The topology is described inside the module-scoped fixture only — never at
import or collection — because describing it loads libtpu, which one
process at a time may hold: every pytest worker imports this file, only
the worker that runs it may load the library. For the same reason the
compiles run in the test's own process and all live in this one file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from incubator_mxnet_tpu import parallel
from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
from incubator_mxnet_tpu.ops import nn as ops_nn
from incubator_mxnet_tpu.ops import ssm
from incubator_mxnet_tpu.ops.pallas import (causal_conv, moe_gmm, moe_rows, qk_prologue,
                                             short_conv, ssd)
from incubator_mxnet_tpu.parallel import collectives, moe_dropless, ring


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def for_the_chip(monkeypatch):
    """Steer the kernel's one platform decision, in the test and not by an
    option: a tracer asks the process default backend, which is the CPU
    here, and would choose interpret mode (and ``flash_supported`` the XLA
    path). Also keep these compiles out of the persistent cache: an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(fa, "_interpret_for", lambda x: False)
    monkeypatch.setattr(moe_gmm, "_interpret_for", lambda x: False)
    monkeypatch.setattr(moe_rows, "_interpret_for", lambda x: False)
    monkeypatch.setattr(short_conv, "_interpret_for", lambda x: False)
    monkeypatch.setattr(qk_prologue, "_interpret_for", lambda x: False)
    monkeypatch.setattr(ssd, "_interpret_for", lambda x: False)
    monkeypatch.setattr(causal_conv, "_interpret_for", lambda x: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _fwd_bwd(attn):
    def f(*args):
        *ins, do = args
        o, vjp = jax.vjp(attn, *ins)
        return (o,) + vjp(do)
    return jax.jit(f)


# (B, H, L, D), dtype, masked, causal, window — the shapes of ISSUE 21:
# BERT-base/-large heads at the bench and serve lengths, one whole-block
# length (384), f32, the long causal and sliding-window paths, D=128
_FLASH_CASES = [
    pytest.param((8, 12, 512, 64), "bfloat16", True, False, None,
                 id="bert_base_L512_masked"),
    pytest.param((8, 12, 512, 64), "bfloat16", False, False, None,
                 id="bert_base_L512"),
    pytest.param((8, 16, 512, 64), "bfloat16", True, False, None,
                 id="bert_large_L512_masked"),
    pytest.param((8, 12, 128, 64), "bfloat16", True, False, None,
                 id="bert_base_L128_masked"),
    pytest.param((8, 12, 384, 64), "bfloat16", True, False, None,
                 id="bert_base_L384_masked"),
    pytest.param((8, 12, 512, 64), "float32", False, False, None,
                 id="f32_L512"),
    pytest.param((2, 12, 2048, 64), "bfloat16", False, True, None,
                 id="causal_L2048"),
    pytest.param((1, 12, 4096, 64), "bfloat16", False, True, 1024,
                 id="causal_window1024_L4096"),
    pytest.param((4, 8, 512, 128), "bfloat16", False, False, None,
                 id="head_dim128_L512"),
]


@pytest.mark.parametrize("shape,dtype,masked,causal,window", _FLASH_CASES)
def test_flash_fwd_bwd_compiles_for_v5e(topo, for_the_chip, shape, dtype,
                                        masked, causal, window):
    one_chip = SingleDeviceSharding(topo.devices[0])
    B, H, L, D = shape
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    ins = [x, x, x]
    if masked:
        ins.append(jax.ShapeDtypeStruct((B, L), jnp.bool_, sharding=one_chip))

    def attn(q, k, v, mask=None):
        return fa.flash_attention(q, k, v, mask=mask, causal=causal,
                                  window=window)

    text = _fwd_bwd(attn).lower(*ins, x).compile().as_text()
    # forward, dk/dv and dq: three Mosaic kernels, none interpreted
    assert text.count("tpu_custom_call") >= 3
    # each under its own name in its instruction's, which is what a trace
    # of the chip shows and the benchmark's per-kernel readers match
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert re.search(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                         text, re.M), kernel


# (B, H, Hkv, L, D), window, width of the shared pair: Trinity-Mini's heads,
# 32 query heads over 4 K/V heads at D=128, key-masked and causal, a sliding
# layer and a full one; and latent attention's at Kanana-2's widths and the
# cell's length: 32 heads of 128 with a second, 64-wide score term whose one
# key head all of them read
@pytest.mark.parametrize("shape,window,shared", [
    pytest.param((1, 32, 4, 4096, 128), 2048, None, id="gqa8_window2048_L4096"),
    pytest.param((1, 32, 4, 2048, 128), None, None, id="gqa8_causal_L2048"),
    pytest.param((1, 32, 32, 8192, 128), None, 64, id="mla_causal_L8192"),
    pytest.param((2, 32, 32, 1024, 128), None, 64, id="mla_causal_B2_L1024"),
])
def test_flash_grouped_heads_compile_for_v5e(topo, for_the_chip, shape, window, shared):
    one_chip = SingleDeviceSharding(topo.devices[0])
    B, H, Hkv, L, D = shape
    sds = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)  # noqa: E731
    q, kv = sds(B, H, L, D), sds(B, Hkv, L, D)
    mask = jax.ShapeDtypeStruct((B, L), jnp.bool_, sharding=one_chip)
    pair = () if shared is None else (sds(B, H, L, shared), sds(B, 1, L, shared))

    def attn(q, k, v, *pair, mask):
        return fa.flash_attention(q, k, v, mask=mask, causal=True, window=window,
                                  shared=pair or None)

    text = jax.jit(lambda mask, do, *ins: (lambda o, vjp: (o,) + vjp(do))(
        *jax.vjp(functools.partial(attn, mask=mask), *ins))).lower(
            mask, q, q, kv, kv, *pair).compile().as_text()
    # a windowed call names its kernels apart, and so does one with the
    # shared pair; dk and dv come out at the K/V heads' count (summed over
    # each group inside the kernel)
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        name = fa._kernel_name(kernel, window, shared)
        assert re.search(rf'^\s*%\S*{name}[_.\d]* = .*custom_call_target="tpu_custom_call"',
                         text, re.M), name
    assert re.search(rf'flash_bwd_dkv\S* = \(bf16\[{B * Hkv},{L},{D}\]', text), "dk, dv per K/V head"
    if shared:
        # dk_s leaves the kernel summed over the heads: one key head a row,
        # and neither a (D + Ds)-wide key nor H copies of the shared one
        # anywhere in the program
        assert re.search(rf'flash_bwd_dkv_mla\S* = \(bf16\[{B * H},{L},{D}\]\S*, '
                         rf'bf16\[{B * H},{L},{D}\]\S*, bf16\[{B},{L},{shared}\]', text)
        assert not re.search(rf'\[(?:{B},{H}|{B * H}),{L},(?:{D + shared}|{shared})\]\S* '
                             r'(?:broadcast|concatenate)\(', text)


# (K, N) of an expert's weight: Trinity-Mini's gate and up stacked (2 x 1,024)
# and Kanana-2's, whose widths 1,536 = 2 x 768 and 768 no 1,024-column block
# divides: the kernel takes the whole width as one block
@pytest.mark.parametrize("K,N", [(2048, 2048), (2048, 1536), (768, 2048)],
                         ids=["w13_2048", "w13_1536", "w2_from_768"])
def test_grouped_matmul_compiles_for_v5e(topo, for_the_chip, K, N):
    """The routed experts' kernels: 16 experts of ``N x K`` over a buffer of
    24 tiles, forward, rows' gradient and weights' gradient, each under its
    name."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    tm = moe_gmm.TILE_ROWS
    R, G = 24 * tm, 16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def f(lhs, rhs, tile_group, n_tiles, dout):
        out, vjp = jax.vjp(lambda a, w: moe_gmm.grouped_matmul(
            a, w, tile_group, n_tiles, impl="pallas"), lhs, rhs)
        return (out,) + vjp(dout)

    text = jax.jit(f).lower(
        sds((R, K), jnp.bfloat16), sds((G, N, K), jnp.bfloat16), sds((R // tm,), jnp.int32),
        sds((1,), jnp.int32), sds((R, N), jnp.bfloat16)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for kernel in ("moe_gmm", "moe_tgmm"):
        assert re.search(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                         text, re.M), kernel


# LFM2-8B-A1B's convolution mixer at the cell's size (4 rows of 8,192 tokens,
# 2,048 channels, 3 taps), and a row that is no whole number of tiles
@pytest.mark.parametrize("shape,dtype", [((4, 8192, 2048), "bfloat16"), ((1, 1000, 256), "float32")],
                         ids=["lfm2_b4_8k_bf16", "ragged_rows_f32"])
def test_short_conv_gate_compiles_for_v5e(topo, for_the_chip, shape, dtype):
    """The fused op through its own backward rule: one ``short_conv_fwd``
    and one ``short_conv_bwd`` kernel, and no convolution left to XLA."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    (B, L, C), dt = shape, jnp.dtype(dtype)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def f(bcx, w, dy):
        out, vjp = jax.vjp(ops_nn.short_conv_gate, bcx, w)
        return (out,) + vjp(dy)

    text = jax.jit(f).lower(sds((B, L, 3 * C)), sds((C, 3)), sds((B, L, C))).compile().as_text()
    for kernel in ("short_conv_fwd", "short_conv_bwd"):
        assert len(re.findall(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                              text, re.M)) == 1, kernel
    assert " convolution(" not in text


# Granite-4.0-H-Micro's scan at the cell's size: one row of 8,192 tokens, 64
# heads of 64, a state of 128, one group, chunks of 256
def test_ssd_scan_compiles_for_v5e(topo, for_the_chip):
    """The op through its own backward rule: one ``ssd_fwd`` and one
    ``ssd_bwd`` kernel, and no chunk-by-chunk decay array of XLA's."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    Bt, L, H, D, N = 1, 8192, 64, 64, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def f(x, dt, A, B, C, skip, dy):
        out, vjp = jax.vjp(lambda *a: ssm.ssd_scan(*a, 256), x, dt, A, B, C, skip)
        return (out,) + vjp(dy)

    bf16, f32 = jnp.bfloat16, jnp.float32
    text = jax.jit(f).lower(
        sds((Bt, L, H, D), bf16), sds((Bt, L, H), f32), sds((H,), f32), sds((Bt, L, 1, N), bf16),
        sds((Bt, L, 1, N), bf16), sds((H,), f32), sds((Bt, L, H, D), bf16)).compile().as_text()
    for kernel in ("ssd_fwd", "ssd_bwd"):
        assert len(re.findall(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                              text, re.M)) == 1, kernel
    assert "256,256" not in text


# Granite-4.0-H-Micro's causal convolution at the cell's size: one row of 8,192
# tokens, 4,352 channels (x | B | C), 4 taps, bf16; alone, and read where it
# lies in the in-projection's 8,512-wide output, as the mixer calls it
@pytest.mark.parametrize("width,start", [(4352, 0), (8512, 4096)],
                         ids=["alone", "in_projection_window"])
def test_causal_conv1d_compiles_for_v5e(topo, for_the_chip, width, start):
    """The op through its own backward rule, its output in the scan's three
    parts: one ``causal_conv_fwd`` and one ``causal_conv_bwd`` kernel, and
    neither a pad nor a shifted slice of the activations left to XLA (nor
    a copy of the window the kernels read in place)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    L, C, split = 8192, 4352, (4096, 4224)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)  # noqa: E731

    def f(x, w, b, dys):       # x and dx as (L, width): a row-major layout, as a matmul's
        parts, vjp = jax.vjp(lambda *a: ops_nn.causal_conv1d(*a, split=split, start=start),
                             x.reshape(1, L, width), w, b)
        dx, dw, db = vjp(dys)
        return parts + (dx.reshape(L, width), dw, db)

    dys = tuple(sds((1, L, n)) for n in (4096, 128, 128))
    text = jax.jit(f).lower(sds((L, width)), sds((C, 4)), sds((C,)), dys).compile().as_text()
    for kernel in ("causal_conv_fwd", "causal_conv_bwd"):
        assert len(re.findall(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                              text, re.M)) == 1, kernel
    assert not re.findall(rf'= \S+\[1,{L}\d*,{C}\]\S* (?:pad|slice|fusion|copy)\(', text)
    assert not re.findall(rf'= \S+\[1,{L + 3},', text)       # XLA's padded row


# Trinity-Mini's q and k at the cell's size (one row of 8,192 tokens, 32 and 4
# heads of 128), a sliding layer's (rotary) and the full layer's (none)
@pytest.mark.parametrize("heads", [32, 4], ids=["q_h32", "k_h4"])
@pytest.mark.parametrize("rope", [True, False], ids=["rotary", "no_rotary"])
def test_qk_norm_rope_compiles_for_v5e(topo, for_the_chip, heads, rope):
    """The fused op through its own backward rule: one ``qk_prologue_fwd``
    and one ``qk_prologue_bwd`` kernel, and between the projection's bf16
    result and the head-major bf16 result no array of their size in fp32."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    B, L, D = 1, 8192, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731

    def f(x, gamma, positions, dy):
        out, vjp = jax.vjp(lambda x, gamma: ops_nn.qk_norm_rope(
            x, gamma, positions if rope else None, 10000.0, 1e-5, heads), x, gamma)
        return (out,) + vjp(dy)

    text = jax.jit(f).lower(
        sds((B, L, heads * D), jnp.bfloat16), sds((D,), jnp.float32), sds((B, L), jnp.int32),
        sds((B, heads, L, D), jnp.bfloat16)).compile().as_text()
    for kernel in ("qk_prologue_fwd", "qk_prologue_bwd"):
        assert len(re.findall(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                              text, re.M)) == 1, kernel
    assert f"f32[{B},{L},{heads},{D}]" not in text and f"f32[{B},{heads},{L},{D}]" not in text


# dtype, experts a token, expert width: Trinity-Mini's (top 8, 1,024) and
# Kanana-2's (top 6, 768 = 6 x 128 lanes, where the gate kernel slices)
@pytest.mark.parametrize("dtype,k,F", [("bfloat16", 8, 1024), ("float32", 8, 1024),
                                       ("bfloat16", 6, 768)],
                         ids=["bfloat16", "float32", "bfloat16_top6_width768"])
def test_routed_half_compiles_for_v5e_on_the_row_kernels(topo, for_the_chip, dtype, k, F):
    """The routed half as a TPU runs it, forward and backward at the
    published widths (1,024 tokens, 16 experts held: a buffer of up to 48
    tiles): the six row kernels and the grouped matmuls each under its
    name, Mosaic taking the slabs' row DMAs, the strided loads and the index
    blocks in SMEM; and no XLA gather of 2,048-wide rows left beside them."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    T, C, G = 1024, 2048, 16
    dt = jnp.dtype(dtype)
    sds = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=one_chip)  # noqa: E731

    def f(x, idx, weight, w13, w2, dout):
        out, vjp = jax.vjp(lambda x, weight, w13, w2: moe_dropless.routed_experts(
            x, idx, weight, w13, w2, (0, G)), x, weight, w13, w2)
        return (out,) + vjp(dout)

    text = jax.jit(f).lower(
        sds((T, C), dt), sds((T, k), jnp.int32), sds((T, k), jnp.float32),
        sds((G, 2 * F, C), dt), sds((G, C, F), dt), sds((T, C), dt)).compile().as_text()
    for kernel in ("moe_rows_pack", "moe_rows_gather", "moe_rows_combine", "moe_rows_dot",
                   "moe_rows_gate", "moe_rows_gate_bwd", "moe_gmm", "moe_tgmm"):
        assert re.search(rf'^\s*%\S*{kernel}[.\d]* = .*custom_call_target="tpu_custom_call"',
                         text, re.M), kernel
    assert not re.search(rf"\[\d+,{C}\]\S* gather\(", text)


# mesh axes, (B, H, L, D) global, masked, causal
_RING_CASES = [
    pytest.param(dict(dp=2, sp=2), (8, 12, 1024, 64), True, False,
                 id="dp2_sp2_masked_L1024"),
    pytest.param(dict(sp=4), (2, 12, 4096, 64), False, True,
                 id="sp4_causal_L4096"),
]


@pytest.mark.parametrize("axes,shape,masked,causal", _RING_CASES)
def test_ring_attention_compiles_for_v5e_2x2(topo, for_the_chip, axes, shape,
                                             masked, causal):
    mesh = parallel.make_mesh(devices=list(topo.devices), **axes)
    assert mesh.devices.size == 4
    B, H, L, D = shape
    bspec = "dp" if mesh.shape["dp"] > 1 else None
    spec, mspec = P(bspec, None, "sp", None), P(bspec, "sp")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    ins, in_specs = [x, x, x], (spec, spec, spec)
    if masked:
        ins.append(jax.ShapeDtypeStruct(
            (B, L), jnp.int32, sharding=NamedSharding(mesh, mspec)))
        in_specs += (mspec,)
    # the hop's local block must take the Pallas branch, not the einsum
    hop = jax.ShapeDtypeStruct((B // mesh.shape["dp"], H,
                                L // mesh.shape["sp"], D), jnp.bfloat16)
    assert ring._hop_flash_ok(hop, hop)

    attn = collectives.shard_map(
        functools.partial(ring.ring_attention, axis="sp", causal=causal),
        mesh=mesh, in_specs=in_specs, out_specs=spec)
    text = _fwd_bwd(attn).lower(*ins, x).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text     # K/V really go round the ring


# mesh axes, (B, H, L, D) global: a dp·tp mesh, and the four-chip benchmark
# cell's (bert_base_pretrain.dp4: 32 rows a chip)
@pytest.mark.parametrize("axes,shape", [
    pytest.param(dict(dp=2, tp=2), (8, 12, 512, 64), id="dp2_tp2"),
    pytest.param(dict(dp=4), (128, 12, 512, 64), id="dp4_bert_base_B128"),
])
def test_flash_inside_a_mesh_step_compiles_for_v5e_2x2(topo, for_the_chip, axes, shape):
    """What a BERT layer's attention is inside a step compiled over a
    mesh. GSPMD cannot partition a Mosaic kernel (lowering raises
    "Mosaic kernels cannot be automatically partitioned"), so
    ``dot_product_attention`` must hand it over per shard."""
    from incubator_mxnet_tpu.ops.attention import dot_product_attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    mesh = parallel.make_mesh(devices=list(topo.devices), **axes)
    spec = P("dp", "tp" if "tp" in axes else None, None, None)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    mask = jax.ShapeDtypeStruct((shape[0], 1, 1, shape[2]), jnp.bool_,
                                sharding=NamedSharding(mesh, P("dp")))
    with active_mesh(mesh):
        compiled = _fwd_bwd(dot_product_attention).lower(
            x, x, x, mask, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


# mesh axes, (B, L, H, D) global, whether the lane kernels take the call:
# the four-chip benchmark cell's attention (32 rows a chip) and a dp·tp mesh,
# which splits heads and so the head-major kernels take it
@pytest.mark.parametrize("axes,shape,lanes", [
    pytest.param(dict(dp=4), (128, 512, 12, 64), True, id="dp4_bert_base_B128"),
    pytest.param(dict(dp=2, tp=2), (8, 512, 12, 64), False, id="dp2_tp2"),
])
def test_projected_attention_inside_a_mesh_step_compiles_for_v5e_2x2(topo, for_the_chip, axes,
                                                                     shape, lanes):
    """``projected_attention`` over the fused projection inside a step
    compiled over a mesh: per shard, the three named kernels forward and
    backward; with the lane layout no head-major array on a chip."""
    from incubator_mxnet_tpu.ops.attention import projected_attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    mesh = parallel.make_mesh(devices=list(topo.devices), **axes)
    B, L, H, D = shape
    x = jax.ShapeDtypeStruct((B, L, 3 * H * D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp", None, None)))
    mask = jax.ShapeDtypeStruct((B, 1, 1, L), jnp.bool_, sharding=NamedSharding(mesh, P("dp")))
    do = jax.ShapeDtypeStruct((B, L, H * D), jnp.bfloat16,
                              sharding=NamedSharding(mesh, P("dp", None, None)))
    with active_mesh(mesh):
        text = _fwd_bwd(lambda x, m: projected_attention(x, m, heads=H)).lower(
            x, mask, do).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert re.search(rf'^\s*%\S*{kernel}\S* = .*custom_call_target="tpu_custom_call"',
                         text, re.M), kernel
    b = B // axes["dp"]
    head_major = re.search(rf'bf16\[(?:{b},{H // axes.get("tp", 1)},{L},{D}|{b},{L},{H},{D})\]', text)
    assert (head_major is None) == lanes


def test_v5e_reports_a_kind_the_peak_table_knows(topo):
    """What the chip calls itself must resolve to the v5e row, whole-kind:
    'TPU v5 lite' contains 'TPU v5', the v5p's kind."""
    from incubator_mxnet_tpu import util
    kind = topo.devices[0].device_kind
    assert kind == "TPU v5 lite"
    assert util.device_peaks(kind) == (197.0, 819.0, 200.0)
    assert util.device_peaks("TPU v5") == util.device_peaks("TPU v5p") \
        != util.device_peaks(kind)
    assert onp.isclose(util.device_peaks(kind)[2] * 8, 1600.0)  # Gbit/s


# (B, L, H, D), self- or cross-attention, masked, causal: BERT-base's and
# -large's cells at L = 512 (8 rows), phase 1's at L = 128, a cross-attention
# call at D = 128 and a causal one
@pytest.mark.parametrize("shape,cross,masked,causal", [
    pytest.param((8, 512, 12, 64), False, True, False, id="bert_base_L512_masked"),
    pytest.param((8, 512, 16, 64), False, True, False, id="bert_large_L512_masked"),
    pytest.param((32, 128, 12, 64), False, True, False, id="bert_base_L128_masked"),
    pytest.param((4, 512, 8, 128), True, False, False, id="cross_head_dim128"),
    pytest.param((2, 1024, 12, 64), False, False, True, id="causal_L1024"),
])
def test_flash_lane_layout_compiles_for_v5e(topo, for_the_chip, shape, cross, masked, causal):
    """The kernels over the projections' own arrays: the three named
    kernels, the gradient of the fused projection leaving the dq kernel as
    one ``(B, L, 3C)`` array (the dkv kernel's buffer, aliased), and no
    head-major bf16 array or concatenation anywhere in the program."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    B, L, H, D = shape
    C = H * D
    sds = lambda *dims, dt=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)  # noqa: E731
    x_q = sds(B, L, C if cross else 3 * C)
    x_kv = sds(B, L, 2 * C) if cross else None
    mask = sds(B, L, dt=jnp.bool_) if masked else None

    def f(x_q, x_kv, mask, do):
        o, vjp = jax.vjp(lambda a, b: fa.flash_attention_lanes(a, b, H, mask=mask, causal=causal),
                         x_q, x_kv)
        return (o,) + vjp(do)

    text = jax.jit(f).lower(x_q, x_kv, mask, sds(B, L, C)).compile().as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert len(re.findall(rf'^\s*%\S*{kernel}[_.\d]* = .*custom_call_target="tpu_custom_call"',
                              text, re.M)) == 1, kernel
    assert re.search(rf'flash_bwd_dq[_.\d]* = bf16\[{B},{L},{C if cross else 3 * C}\]', text)
    assert not re.search(rf'bf16\[(?:{B},{H},{L},{D}|{B},{L},{H},{D}|{B * H},{L},{D})\]', text)
    assert " concatenate(" not in text


def _bert_step_text(topo, monkeypatch, lanes, chips=1):
    """BERT's whole training step at the rehearsal size (2 layers, 2 heads
    of 64, 4 rows of 128 a chip), compiled for one described v5e or, with
    ``chips=4``, for the described 2x2 under the mesh of
    ``bert_base_pretrain.dp4`` (dp = 4, zero1 by the trainer's default),
    with the lane layout taken or not; the trainer built on the CPU."""
    from incubator_mxnet_tpu import models
    from incubator_mxnet_tpu.ops import attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    B, L, P, V = 4 * chips, 128, 19, 1000
    rng = onp.random.RandomState(0)
    batch = (rng.randint(0, V, (B, L)).astype("int32"), rng.randint(0, 2, (B, L)).astype("int32"),
             onp.full((B,), L, "float32"), onp.sort(rng.rand(B, L).argsort(1)[:, :P], 1).astype("int32"),
             rng.randint(0, V, (B, P)).astype("float32"), onp.ones((B, P), "float32"),
             rng.randint(0, 2, (B,)).astype("float32"))
    net = models.get_bert("bert_2_128_2", vocab_size=V, max_length=L, dropout=0.1, dtype="bfloat16")
    net.initialize()
    axes = {"dp": chips} if chips > 1 else {}
    tr = parallel.ShardedTrainer(net, models.bert_pretrain_loss, "adamw",
                                 {"learning_rate": 1e-4, "multi_precision": True},
                                 mesh=parallel.make_mesh(devices=jax.devices()[:chips], **axes),
                                 rules=models.bert_sharding_rules(), n_labels=3)
    with monkeypatch.context() as m:
        m.setattr(fa, "_interpret_for", lambda x: True)   # the eager warm-up runs here
        tr.prepare(*batch)
    if not lanes:
        monkeypatch.setattr(attention, "_lanes_taken", lambda *a: False)
    monkeypatch.setattr(jax.lax, "with_sharding_constraint", lambda x, s: x)
    if chips == 1:
        mesh, one_chip = tr.mesh, SingleDeviceSharding(topo.devices[0])
        place = lambda x: one_chip  # noqa: E731
    else:
        mesh = parallel.make_mesh(devices=list(topo.devices)[:chips], **axes)
        place = lambda x: NamedSharding(  # noqa: E731
            mesh, x.sharding.spec if isinstance(x.sharding, NamedSharding) else P())
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place(x))
                        if hasattr(x, "shape") else x, tr.step_trace_args(*batch))
    with active_mesh(mesh):
        return jax.jit(tr._step_fn.__wrapped__).lower(*args).compile().as_text()


def test_bert_step_holds_no_head_major_copy_on_v5e(topo, for_the_chip, monkeypatch):
    """With the lane layout the compiled step moves no q, k, v, o or their
    gradients into or out of head-major order: no ``copy``, ``transpose``
    or ``concatenate`` whose result is ``bf16[B, H, L, D]`` (or its
    transpose) or the fused projection's ``(B, L, 3C)`` gradient; the
    program that splits the heads out holds several a layer (ten at this
    size, eight a layer at BERT-base's cell, 96 in all)."""
    B, H, L, D, layers = 4, 2, 128, 64, 2
    moved = re.compile(rf'= bf16\[(?:{B},{H},{L},{D}|{B},{L},{H},{D}|{B},{L},{3 * H * D})\]'
                       r'\S* (?:copy|transpose|concatenate)\(')
    kernels = re.compile(r'^\s*%\S*flash_\S* = .*custom_call_target="tpu_custom_call"', re.M)
    text = _bert_step_text(topo, monkeypatch, lanes=True)
    assert len(kernels.findall(text)) == 3 * layers
    assert moved.findall(text) == []
    split = _bert_step_text(topo, monkeypatch, lanes=False)
    assert len(kernels.findall(split)) == 3 * layers
    assert len(moved.findall(split)) >= 4 * layers


def test_bert_dp4_step_holds_no_head_major_copy_on_v5e_2x2(topo, for_the_chip, monkeypatch):
    """``bert_base_pretrain.dp4``'s step at the rehearsal size, compiled for
    the described 2x2 v5e: the lane kernels run per shard of the batch
    under the step's ``dp`` mesh (each chip its 4 rows), beside the
    gradients' reduce-scatter and the weights' all-gather, and no q, k, v,
    o or gradient is moved into or out of head-major order on a chip."""
    B, H, L, D, layers = 4, 2, 128, 64, 2
    text = _bert_step_text(topo, monkeypatch, lanes=True, chips=4)
    kernels = re.findall(r'^\s*%\S*(flash_[a-z_]+?)[_.\d]* = .*custom_call_target="tpu_custom_call"',
                         text, re.M)
    assert sorted(kernels) == sorted(["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"] * layers)
    assert re.search(rf'flash_bwd_dq[_.\d]* = bf16\[{B},{L},{3 * H * D}\]', text)
    assert not re.search(rf'= bf16\[(?:{B},{H},{L},{D}|{B},{L},{H},{D}|{B},{L},{3 * H * D})\]'
                         r'\S* (?:copy|transpose|concatenate)\(', text)
    assert re.search(r"all-gather|reduce-scatter|all-reduce", text)


@pytest.mark.parametrize("cell", ["trinity_mini_train.packed8k", "kanana2_30b_a3b_train.packed8k",
                                  "lfm2_8b_a1b_train.packed8k_b4"])
def test_decoder_steps_never_ask_for_the_lane_layout(cell, monkeypatch):
    """The three decoder blocks build their own head-major q, k and v and
    call ``dot_product_attention``: their whole training step, lowered at
    the benchmark's rehearsal size (CPU), never consults the lane layout's
    gate, so their programs are the head-major kernels' as before."""
    import importlib
    import json
    from incubator_mxnet_tpu.ops import attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)
    bench = load("BENCHMARK.json")
    workload = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = load(next(c["file"] for c in bench["configs"] if c["name"] == workload["config"]))
    cfg = {**cfg, **cfg["rehearse"]}
    traffic = load("chipbench", "workloads", cell + ".json")["traffic"]
    traffic = {**traffic, **traffic["rehearse"]}
    asked = []
    monkeypatch.setattr(attention, "_lanes_taken", lambda *a: asked.append(a) or False)
    family = importlib.import_module("chipbench.families." + cfg["family"])
    system = family.build_train(cfg, jax.devices()[:1], 2147483001)
    batch = family.train_batches(cfg, traffic, 2147483001, 1, traffic["batch"])[0]
    tr = system.trainer
    with system.ctx:
        tr.prepare(*batch)
        with active_mesh(tr.mesh):
            text = tr._step_fn.lower(*tr.step_trace_args(*batch)).as_text()
    assert "dot_general" in text          # the whole step was traced and lowered
    assert asked == []
