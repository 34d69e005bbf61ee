#!/usr/bin/env python3
"""Time a Mamba-2 mixer's causal convolution with bias and SiLU on the chip,
the kernel pair against XLA's lowering of the plain form.

    python benchmark/causal_conv_probe.py [--shape 1 8192 4352] [--taps 4] [--out chiprun_out/causal_conv_probe.json]

At Granite-4.0-H-Micro's shapes (one row of 8,192 tokens, 4,352 channels:
the scan's ``x | B | C``, 4,096 + 128 + 128, bf16): ``ops/pallas/causal_conv.py``
forward and backward at several tile heights, the output in the scan's three
parts and the input read where it lies in the in-projection's 8,512-wide
output, as the mixer calls them; and ``ops.nn.causal_conv1d_plain`` with
``jax.vjp`` of it, compiled by XLA. The kernels' values and gradients are
held to the plain form's. Milliseconds a call (host clock round back-to-back
calls, the last awaited) and the share of the HBM roof the bytes the op must
move would take (forward ``x`` in and ``y`` out, ``4 T C`` bytes in bf16;
backward ``x``, ``dy`` in and ``dx`` out, ``6 T C``). Prints one JSON
object; needs a TPU (the numbers of a CPU run would be the interpreter's).
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
from incubator_mxnet_tpu.ops.pallas import causal_conv


def timed(fn, *args, calls: int = 20) -> float:
    """Milliseconds a call of the jitted ``fn``."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def _max_err(got, want) -> float:
    return max(float(np.abs(np.asarray(g, "float32") - np.asarray(w, "float32")).max())
               for g, w in zip(got, want))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=3, default=(1, 8192, 4352), metavar=("B", "L", "C"))
    ap.add_argument("--taps", type=int, default=4)
    ap.add_argument("--split", type=int, nargs="*", default=(4096, 4224))
    ap.add_argument("--start", type=int, default=4096, help="the window's first channel")
    ap.add_argument("--width", type=int, default=8512, help="the projection's whole width")
    ap.add_argument("--tiles", type=int, nargs="+", default=(256, 512, 1024))
    ap.add_argument("--out", default="chiprun_out/causal_conv_probe.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe needs a TPU and found {device.platform!r}")
    (B, L, C), K, split, start = args.shape, args.taps, tuple(args.split), args.start
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (B, L, args.width), jnp.bfloat16)
    bound = K ** -0.5
    w = jax.random.uniform(keys[1], (C, K), jnp.float32, -bound, bound).astype(jnp.bfloat16)
    b = jax.random.uniform(keys[2], (C,), jnp.float32, -bound, bound).astype(jnp.bfloat16)
    dys = tuple(jax.random.normal(k, (B, L, n), jnp.bfloat16) for k, n in zip(
        jax.random.split(keys[3], 3), causal_conv.widths_of(C, split)))
    fwd_bytes, bwd_bytes = 4.0 * B * L * C, 6.0 * B * L * C
    roof = peaks.peak(device.device_kind)["hbm_bytes_per_s"]

    def share(nbytes, ms):
        return 100.0 * nbytes / roof / (ms * 1e-3)

    plain_fwd = jax.jit(lambda x, w, b: nn.causal_conv1d_plain(x, w, b, split, start))
    plain_bwd = jax.jit(lambda x, w, b, dys: jax.vjp(
        lambda *a: nn.causal_conv1d_plain(*a, split, start), x, w, b)[1](dys))
    want = plain_fwd(x, w, b)
    want_x, want_w, want_b = plain_bwd(x, w, b, dys)
    want_x = want_x[..., start:start + C]
    out = {"device": device.device_kind, "shape": [B, L, C], "taps": K, "split": list(split),
           "start": start, "width": args.width, "dtype": "bfloat16",
           "xla": {"fwd_ms": timed(plain_fwd, x, w, b), "bwd_ms": timed(plain_bwd, x, w, b, dys)},
           "kernels": {}}
    out["xla"]["fwd_roofline"] = share(fwd_bytes, out["xla"]["fwd_ms"])
    out["xla"]["bwd_roofline"] = share(bwd_bytes, out["xla"]["bwd_ms"])
    for tile in args.tiles:
        fwd = jax.jit(lambda x, w, b, tile=tile: causal_conv.forward(x, w, b, split, start, tile))
        bwd = jax.jit(lambda x, w, b, dys, tile=tile: causal_conv.backward(
            x, w, b, dys, start, tile))
        got, (got_x, got_w, got_b) = fwd(x, w, b), bwd(x, w, b, dys)
        row = {"fwd_ms": timed(fwd, x, w, b), "bwd_ms": timed(bwd, x, w, b, dys),
               # bf16 out: one rounding of each result; d w and d b sum 8,192 rows in fp32
               "y_max_err": _max_err(got, want), "dx_max_err": _max_err([got_x], [want_x]),
               "dw_rel_err": _max_err([got_w], [want_w]) / float(np.abs(np.asarray(
                   want_w, "float32")).max()),
               "db_rel_err": _max_err([got_b], [want_b]) / float(np.abs(np.asarray(
                   want_b, "float32")).max())}
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
