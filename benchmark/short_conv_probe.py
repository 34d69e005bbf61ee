#!/usr/bin/env python3
"""Time the gated short convolution on the chip, the kernel pair against
XLA's lowering of the plain form.

    python benchmark/short_conv_probe.py [--shape 4 8192 2048] [--taps 3] [--out chiprun_out/short_conv_probe.json]

At LFM2-8B-A1B's shapes (4 rows of 8,192 tokens, 2,048 channels, so ``bcx``
is ``(4, 8192, 6144)`` bf16): ``ops/pallas/short_conv.py`` forward and
backward at several tile heights, and ``ops.nn.short_conv_gate_plain`` with
``jax.grad`` of it, each compiled by XLA; the kernels' values and
gradients are held to the plain form's. Milliseconds a call (host clock
round back-to-back calls, the last awaited) and the share of the HBM roof
the bytes the op must move would take (forward ``8 T C`` bytes in bf16,
backward ``14 T C``). Prints one JSON object; needs a TPU (the numbers of a
CPU run would be the interpreter's).
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
from incubator_mxnet_tpu.ops.pallas import short_conv


def timed(fn, *args, calls: int = 20) -> float:
    """Milliseconds a call of the jitted ``fn``."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=3, default=(4, 8192, 2048), metavar=("B", "L", "C"))
    ap.add_argument("--taps", type=int, default=3)
    ap.add_argument("--tiles", type=int, nargs="+", default=(256, 512, 1024))
    ap.add_argument("--out", default="chiprun_out/short_conv_probe.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe needs a TPU and found {device.platform!r}")
    (B, L, C), K = args.shape, args.taps
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcx = jax.random.normal(keys[0], (B, L, 3 * C), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (C, K), jnp.float32) * 0.5).astype(jnp.bfloat16)
    dy = jax.random.normal(keys[2], (B, L, C), jnp.bfloat16)
    fwd_bytes, bwd_bytes = 8.0 * B * L * C, 14.0 * B * L * C
    roof = peaks.peak(device.device_kind)["hbm_bytes_per_s"]

    def share(nbytes, ms):
        return 100.0 * nbytes / roof / (ms * 1e-3)

    plain_fwd = jax.jit(nn.short_conv_gate_plain)
    plain_bwd = jax.jit(lambda bcx, w, dy: jax.vjp(nn.short_conv_gate_plain, bcx, w)[1](dy))
    want = np.asarray(plain_fwd(bcx, w), "float32")
    want_b, want_w = (np.asarray(g, "float32") for g in plain_bwd(bcx, w, dy))
    out = {"device": device.device_kind, "shape": [B, L, 3 * C], "taps": K, "dtype": "bfloat16",
           "xla": {"fwd_ms": timed(plain_fwd, bcx, w), "bwd_ms": timed(plain_bwd, bcx, w, dy)},
           "kernels": {}}
    out["xla"]["fwd_roofline"] = share(fwd_bytes, out["xla"]["fwd_ms"])
    out["xla"]["bwd_roofline"] = share(bwd_bytes, out["xla"]["bwd_ms"])
    for tile in args.tiles:
        fwd = jax.jit(lambda bcx, w, tile=tile: short_conv.forward(bcx, w, tile))
        bwd = jax.jit(lambda bcx, w, dy, tile=tile: short_conv.backward(bcx, w, dy, tile))
        got, (got_b, got_w) = fwd(bcx, w), bwd(bcx, w, dy)
        row = {"fwd_ms": timed(fwd, bcx, w), "bwd_ms": timed(bwd, bcx, w, dy),
               # bf16 out: one rounding of the result; d w sums 32,768 rows in fp32 both ways
               "fwd_max_err": float(np.abs(np.asarray(got, "float32") - want).max()),
               "dbcx_max_err": float(np.abs(np.asarray(got_b, "float32") - want_b).max()),
               "dw_rel_err": float(np.abs(np.asarray(got_w, "float32") - want_w).max()
                                   / np.abs(want_w).max())}
        row["fwd_roofline"] = share(fwd_bytes, row["fwd_ms"])
        row["bwd_roofline"] = share(bwd_bytes, row["bwd_ms"])
        out["kernels"][str(tile)] = row
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
