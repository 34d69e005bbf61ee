#!/usr/bin/env python3
"""Time BERT's self-attention between the fused qkv projection and the output
projection on the chip, in the two layouts the flash kernels take.

    python benchmark/flash_layout_probe.py [--shapes base large phase1] [--out chiprun_out/flash_layout_probe.json]

From the projection's ``(B, L, 3C)`` bf16 result with a key mask to the
``(B, L, C)`` the output projection reads, at BERT-base's cell (32 rows of
512, 12 heads of 64), BERT-large's (16 rows of 512, 16 heads of 64) and
phase 1's (128 rows of 128, 12 heads of 64):

- ``head_major``: split, reshape and transpose q, k and v to ``(B, H, L, D)``,
  ``flash_attention``, transpose back (what ``MultiHeadAttention`` traces
  where the lane layout is not taken);
- ``lanes``: ``flash_attention_lanes``, the kernels reading and writing
  128-lane blocks of the projections' own arrays.

Milliseconds a call forward, and forward plus backward (``jax.vjp`` of the
call and its pullback on a cotangent of the output; host clock round one
jitted function that makes 10 calls on 10 operands, the best of three), and
how far the two layouts' values and gradients lie apart. Prints one JSON
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

from incubator_mxnet_tpu.ops.pallas import flash_attention as fa

CALLS = 10
#: (B, L, H, D) of each cell
SHAPES = {"base": (32, 512, 12, 64), "large": (16, 512, 16, 64), "phase1": (128, 128, 12, 64)}


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


def probe(B, L, H, D):
    C = H * D
    keys = jax.random.split(jax.random.PRNGKey(B * L), 2 * CALLS)
    xs = [jax.random.normal(k, (B, L, 3 * C), jnp.bfloat16) for k in keys[:CALLS]]
    dos = [jax.random.normal(k, (B, L, C), jnp.bfloat16) for k in keys[CALLS:]]
    # every row but the first padded by a quarter, as a cell's check batch pads one
    lengths = np.where(np.arange(B) == 0, L, L * 3 // 4)
    mask = jnp.asarray(np.arange(L)[None, :] < lengths[:, None])

    def head_major(x):
        q, k, v = (p.reshape(B, L, H, D).transpose(0, 2, 1, 3) for p in jnp.split(x, 3, -1))
        o = fa.flash_attention(q, k, v, mask=mask)
        return o.transpose(0, 2, 1, 3).reshape(B, L, C)

    def lanes(x):
        return fa.flash_attention_lanes(x, None, H, mask=mask)

    out = {"shape": [B, L, H, D]}
    values = {}
    for name, f in (("head_major", head_major), ("lanes", lanes)):
        def fwd_bwd(x, do, f=f):
            o, vjp = jax.vjp(f, x)
            return o, vjp(do)[0]
        out[name] = {"fwd_ms": timed(f, xs), "fwd_bwd_ms": timed(fwd_bwd, xs, dos)}
        values[name] = [np.asarray(a, "float32") for a in jax.jit(fwd_bwd)(xs[0], dos[0])]
    for what, a, b in zip(("o", "d_qkv"), values["head_major"], values["lanes"]):
        out[f"{what}_max_err"] = float(np.abs(a - b).max())
        out[f"{what}_differing_share"] = float((a != b).mean())
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--out", default="chiprun_out/flash_layout_probe.json")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the probe needs a TPU and found {device.platform!r}")
    out = {"device": device.device_kind, "dtype": "bfloat16", "calls_per_jit": CALLS,
           "cells": {name: probe(*SHAPES[name]) for name in args.shapes}}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
