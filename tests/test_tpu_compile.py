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
from incubator_mxnet_tpu.parallel import collectives, ring


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


def test_flash_inside_a_dp2_tp2_step_compiles_for_v5e_2x2(topo, for_the_chip):
    """What a BERT layer's attention is inside a step compiled over a
    dp·tp mesh. GSPMD cannot partition a Mosaic kernel (lowering raises
    "Mosaic kernels cannot be automatically partitioned"), so
    ``dot_product_attention`` must hand it over per shard."""
    from incubator_mxnet_tpu.ops.attention import dot_product_attention
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    mesh = parallel.make_mesh(devices=list(topo.devices), dp=2, tp=2)
    spec = P("dp", "tp", None, None)
    x = jax.ShapeDtypeStruct((8, 12, 512, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    mask = jax.ShapeDtypeStruct((8, 1, 1, 512), jnp.bool_,
                                sharding=NamedSharding(mesh, P("dp")))
    with active_mesh(mesh):
        compiled = _fwd_bwd(dot_product_attention).lower(
            x, x, x, mask, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


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
