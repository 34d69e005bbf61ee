#!/usr/bin/env python3
"""Time what lies between a q or k projection and the flash kernels on the
chip, the fused kernel pair against XLA's lowering of the plain form.

    python benchmark/qk_prologue_probe.py [--shape 1 8192 128] [--heads 32 4] [--out chiprun_out/qk_prologue_probe.json]

At Trinity-Mini's shapes (one row of 8,192 tokens, 32 query and 4 key heads
of 128, so ``x`` is ``(1, 8192, 4096)`` and ``(1, 8192, 512)`` bf16), with
rotary (a sliding layer) and without (the full layer):
``ops/pallas/qk_prologue.py`` forward and backward at several tile heights
and widths of the loop over heads, and ``ops.nn.qk_norm_rope_plain`` with ``jax.vjp`` of
it, each compiled by XLA; the kernels' values and gradients are held to the
plain form's. Milliseconds a call (host clock round one jitted function that
makes 20 calls on 20 operands, the best of three: a call by itself takes less
than the host needs to send it, 0.2 ms) and the share of the HBM roof the
bytes the op must move would take (forward ``4 T H D`` bytes in bf16,
backward ``6 T H D``, and the two fp32 tables' ``8 T D`` with rotary; the
tables are built outside the timed function, once a layer in a model).
Prints one JSON object; needs a TPU (the numbers of a CPU run would be the
interpreter's).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import peaks
from incubator_mxnet_tpu.ops import nn
from incubator_mxnet_tpu.ops.pallas import qk_prologue

THETA, EPS, CALLS = 10000.0, 1e-5, 20


def timed(op, *operands) -> float:
    """Milliseconds a call of ``op``, made on each of ``CALLS`` operands
    (``operands``: lists of them) inside one jitted function."""
    fn = jax.jit(lambda *lists: [op(*args) for args in zip(*lists)])
    jax.block_until_ready(fn(*operands))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        best = min(best, time.perf_counter() - t0)
    return best / len(operands[0]) * 1e3


def f32(a):
    return np.asarray(a, "float32")


def probe(B, L, H, D, rope, tiles, heads_unrolled, roof):
    keys = jax.random.split(jax.random.PRNGKey(H + rope), 2 * CALLS + 1)
    xs = [jax.random.normal(k, (B, L, H * D), jnp.bfloat16) for k in keys[:CALLS]]
    dys = [jax.random.normal(k, (B, H, L, D), jnp.bfloat16) for k in keys[CALLS:-1]]
    gamma = 1.0 + 0.1 * jax.random.normal(keys[-1], (D,), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L)) if rope else None
    table = jax.jit(lambda: nn.rotary_table(positions, THETA, D))() if rope else None
    table_bytes = 8.0 * B * L * D if rope else 0.0
    fwd_bytes, bwd_bytes = 4.0 * B * L * H * D + table_bytes, 6.0 * B * L * H * D + table_bytes

    def share(nbytes, ms):
        return 100.0 * nbytes / roof / (ms * 1e-3)

    def plain(x):
        return nn.qk_norm_rope_plain(x, gamma, positions, THETA, EPS, H)

    def plain_bwd(x, dy):
        return jax.vjp(lambda x, gamma: nn.qk_norm_rope_plain(
            x, gamma, positions, THETA, EPS, H), x, gamma)[1](dy)
    want = f32(jax.jit(plain)(xs[0]))
    want_x, want_g = map(f32, jax.jit(plain_bwd)(xs[0], dys[0]))
    out = {"heads": H, "rotary": rope, "shape": [B, L, H * D],
           "xla": {"fwd_ms": timed(plain, xs), "bwd_ms": timed(plain_bwd, xs, dys)}, "kernels": {}}
    out["xla"]["fwd_roofline"] = share(fwd_bytes, out["xla"]["fwd_ms"])
    out["xla"]["bwd_roofline"] = share(bwd_bytes, out["xla"]["bwd_ms"])
    for n in heads_unrolled:
        qk_prologue.HEADS_UNROLLED = n        # read when a kernel is traced
        jax.clear_caches()
        for tile in tiles:
            def fwd(x, tile=tile):
                return qk_prologue.forward(x, gamma, table, EPS, H, tile)

            def bwd(x, dy, tile=tile):
                return qk_prologue.backward(x, gamma, table, dy, EPS, H, tile)
            got, (got_x, got_g) = f32(jax.jit(fwd)(xs[0])), map(f32, jax.jit(bwd)(xs[0], dys[0]))
            row = {"fwd_ms": timed(fwd, xs), "bwd_ms": timed(bwd, xs, dys),
                   # bf16 out: one rounding of the result; d gamma sums T x H rows in fp32 both ways
                   "fwd_max_err": float(np.abs(got - want).max()),
                   "fwd_differing_share": float((got != want).mean()),
                   "dx_max_err": float(np.abs(got_x - want_x).max()),
                   "dx_differing_share": float((got_x != want_x).mean()),
                   "dgamma_rel_err": float(np.abs(got_g - want_g).max() / np.abs(want_g).max())}
            row["fwd_roofline"] = share(fwd_bytes, row["fwd_ms"])
            row["bwd_roofline"] = share(bwd_bytes, row["bwd_ms"])
            out["kernels"][f"tile{tile}_heads{n}"] = row
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=3, default=(1, 8192, 128), metavar=("B", "L", "D"))
    ap.add_argument("--heads", type=int, nargs="+", default=(32, 4))
    ap.add_argument("--tiles", type=int, nargs="+", default=(256, 512, 1024))
    ap.add_argument("--heads-unrolled", type=int, nargs="+", default=(qk_prologue.HEADS_UNROLLED,),
                    help="heads a step of the kernels' loop over heads takes")
    ap.add_argument("--out", default="chiprun_out/qk_prologue_probe.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe needs a TPU and found {device.platform!r}")
    B, L, D = args.shape
    roof = peaks.peak(device.device_kind)["hbm_bytes_per_s"]
    out = {"device": device.device_kind, "dtype": "bfloat16",
           "calls": [probe(B, L, H, D, rope, args.tiles, args.heads_unrolled, roof)
                     for H in args.heads for rope in (True, False)]}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
