#!/usr/bin/env python
"""int8-on-MXU evidence probe.

Measures a quantized Dense layer vs its bf16 original on the live device
and inspects the compiled HLO for signs that the int8 dot actually lowered
to integer MXU ops (vs dequantizing early to a float dot).

Whole-forward timing only — a per-op microbench is dominated by dispatch,
so we amortize over a large batch and many iterations and sync once. The
platform is whatever JAX starts on (``JAX_PLATFORMS`` pins it, as for any
JAX program); the record names it.

Prints ONE JSON line with keys: int8_ms, bf16_ms, speedup,
hlo_has_int8_dot, hlo_convert_before_dot, backend.

Reference counterpart: src/operator/quantization/ op suite + the perf FAQ's
quantization section (SURVEY §2.4); here the evidence target is the MXU's
int8 path via XLA.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as onp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python benchmark/int8_probe.py` direct run
    sys.path.insert(0, REPO)


def main() -> None:
    import jax
    import jax.numpy as jnp

    B, IN, OUT = (int(os.environ.get(k, d)) for k, d in
                  (("MXTPU_INT8_BATCH", "4096"), ("MXTPU_INT8_IN", "4096"),
                   ("MXTPU_INT8_OUT", "4096")))
    iters = int(os.environ.get("MXTPU_INT8_ITERS", "30"))

    rng = onp.random.RandomState(0)
    w8 = rng.randint(-127, 128, (OUT, IN)).astype(onp.int8)
    x8 = rng.randint(-127, 128, (B, IN)).astype(onp.int8)
    xbf = jnp.asarray(rng.randn(B, IN), jnp.bfloat16)
    wbf = jnp.asarray(rng.randn(OUT, IN), jnp.bfloat16)
    sx, sw = 0.017, 0.021  # activation/weight scales (values irrelevant)

    @jax.jit
    def int8_dense(x, w):
        # the quantized-Dense inner contraction: int8 x int8 -> int32
        # accumulate on the MXU, one scale multiply after
        acc = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * (sx * sw)

    @jax.jit
    def bf16_dense(x, w):
        return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    xi, wi = jnp.asarray(x8), jnp.asarray(w8)
    hlo = int8_dense.lower(xi, wi).compile().as_text()
    # Post-optimization HLO: an integer MXU dot shows up as a dot/fusion
    # producing s32 (or convolution with s8 operands); a float line with no
    # s32 producer anywhere means the compiler dequantized early.
    import re
    int_dots = re.findall(r"s32\[[^\]]*\][^\n]*(?:dot|fusion|custom-call)",
                          hlo)
    has_int8_dot = bool(int_dots) and "s8[" in hlo
    early_convert = not has_int8_dot

    def _time(fn, *args):
        fn(*args).block_until_ready()
        onp.asarray(fn(*args))          # a host copy is always a sync
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        onp.asarray(out)
        return (time.perf_counter() - t0) / iters * 1e3

    int8_ms = _time(int8_dense, xi, wi)
    bf16_ms = _time(bf16_dense, xbf, wbf)

    # The synthetic dense above proves the MXU path exists; the bucket
    # census below proves the SERVED graphs actually take it. Trace the
    # quantized-zoo twin (models.quantized_smoke — the same entry
    # mxlint --hlo --quantized lints and serve_bench --int8 runs) and
    # report the per-bucket int8 census from the MX71x pass's own
    # boundary accounting, so the probe's evidence and the lint's
    # verdict can never disagree.
    family = os.environ.get("MXTPU_INT8_FAMILY", "lenet")
    from incubator_mxnet_tpu import analysis, models
    qsm = models.quantized_smoke(family)
    traced = analysis.hlo.trace_entry(
        qsm["compiled"], max_graphs=max(8, qsm["table"].num_buckets()))
    buckets = []
    for g in traced.graphs:
        st = analysis.hlo.quant_graph_stats(g)
        buckets.append({
            "site": g.site,
            "signature": [list(s) for s in (g.signature or [])],
            "quantized": st.quantized,
            "int8_matmuls": len(st.int_matmuls),
            "quantize_boundaries": len(st.q_converts),
            "dequantize_boundaries": len(st.dq_converts),
            "saved_bytes": st.saved_bytes,
            "churn_bytes": st.churn_bytes,
        })

    print(json.dumps({
        "metric": "int8_dense_vs_bf16",
        "int8_ms": round(int8_ms, 4), "bf16_ms": round(bf16_ms, 4),
        "speedup": round(bf16_ms / int8_ms, 3),
        "hlo_has_int8_dot": bool(has_int8_dot),
        "hlo_convert_before_dot": bool(early_convert),
        "shape": [B, IN, OUT],
        "quantized_zoo": {
            "family": family,
            "buckets": buckets,
            "all_buckets_quantized": bool(buckets) and all(
                b["quantized"] for b in buckets),
        },
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
