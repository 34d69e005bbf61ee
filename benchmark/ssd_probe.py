#!/usr/bin/env python3
"""Time the chunked state-space scan on the chip, the kernel pair against
XLA's lowering of the plain chunked form.

    python benchmark/ssd_probe.py [--shape 1 8192 64 64] [--state 128] [--chunk 256] [--out FILE]

At Granite-4.0-H-Micro's shapes (one row of 8,192 tokens, 64 heads of 64, a
state of 128, one group, chunks of 256): ``ops.ssm.ssd_fused`` (the kernels
``ssd_fwd`` / ``ssd_bwd`` behind the op's own backward rule) against
``ops.ssm.ssd_scan_plain`` and ``jax.vjp`` of it, each compiled by XLA, on
inputs drawn as the Mamba-2 initialisers draw ``A`` and ``dt`` (a state that
lives across chunks); the kernels' values and gradients are held to the
plain form's. Milliseconds a call (host clock round back-to-back calls, the
last awaited), forward alone and forward with backward, and the share of
the roofline the scan's counts (``chipbench/flops_granite_hybrid.py``)
would take. Prints one JSON object; needs a TPU (the numbers of a CPU run
would be the interpreter's).
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

from chipbench import flops, flops_granite_hybrid, peaks
from incubator_mxnet_tpu.ops import ssm


def timed(fn, *args, calls: int = 10) -> float:
    """Milliseconds a call of the jitted ``fn``."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def inputs(B, L, H, P, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (B, L, H, P), jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(k[1], (B, L, H), jnp.float32, np.log(1e-3), np.log(1e-1)))
    A = -jax.random.uniform(k[2], (H,), jnp.float32, 1.0, 16.0)
    Bm = (jax.random.normal(k[3], (B, L, 1, N), jnp.float32) * N ** -0.5).astype(jnp.bfloat16)
    Cm = (jax.random.normal(k[4], (B, L, 1, N), jnp.float32) * N ** -0.5).astype(jnp.bfloat16)
    D = jax.random.normal(k[5], (H,), jnp.float32)
    dy = jax.random.normal(k[6], (B, L, H, P), jnp.bfloat16)
    return (x, dt, A, Bm, Cm, D), dy


def _rel(got, want) -> float:
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return float(np.sqrt(((got - want) ** 2).sum() / max((want ** 2).sum(), 1e-300)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", type=int, nargs=4, default=(1, 8192, 64, 64),
                    metavar=("B", "L", "H", "P"))
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--out", help="also write the JSON object there")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe needs a TPU and found {device.platform!r}")
    (B, L, H, P), N, Q = args.shape, args.state, args.chunk
    ins, dy = inputs(B, L, H, P, N)
    peak = peaks.peak(device.device_kind)
    f_ops, b_ops = flops_granite_hybrid.scan_flops_per_token(H, P, 1, N, Q)
    f_bytes, b_bytes = flops_granite_hybrid.scan_bytes_per_token(H, P, 1, N)

    def share(ops, nbytes, ms):
        return 100.0 * flops.roofline_seconds(B * L * ops, B * L * nbytes, peak)[0] / (ms * 1e-3)

    def both(scan):
        def f(*a):
            y, vjp = jax.vjp(lambda *a: scan(*a, Q), *a)
            return (y,) + vjp(dy)
        return jax.jit(lambda *a: scan(*a, Q)), jax.jit(f)

    out = {"device": device.device_kind, "shape": [B, L, H, P], "state": N, "chunk": Q}

    def measure(scan):
        fwd, fwd_bwd = both(scan)
        got = jax.device_get(fwd_bwd(*ins))
        fwd_ms, all_ms = timed(fwd, *ins), timed(fwd_bwd, *ins)
        return got, {"fwd_ms": fwd_ms, "fwd_bwd_ms": all_ms,
                     "fwd_roofline": share(f_ops, f_bytes, fwd_ms),
                     "fwd_bwd_roofline": share(f_ops + b_ops, f_bytes + b_bytes, all_ms)}

    want, out["xla"] = measure(ssm.ssd_scan_plain)
    got, row = measure(ssm.ssd_fused)
    # the plain form computes in fp32 from the bf16 inputs; the kernels round
    # their matmul operands to bf16: rms error over rms, each output
    row["rel_err_vs_xla"] = {
        n: _rel(g, w) for n, g, w in zip(("y", "dx", "ddt", "dA", "dB", "dC", "dD"), got, want)}
    row["xla_over_kernels"] = {k: out["xla"][k] / row[k] for k in ("fwd_ms", "fwd_bwd_ms")}
    out["kernels"] = row
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
