"""opperf — per-operator micro-benchmark suite.

Reference parity: ``benchmark/opperf/`` (opperf.py + nd_operations/*) — run
every registered operator (or a chosen subset) on representative shapes,
timing forward and forward+backward, and emit a machine-readable report.
Per-operator timings on the host or device it runs on; whole-step speed is
``chipbench/run.py``'s to measure.

TPU-native design: each measurement jits the op once (fwd, and
``jax.value_and_grad`` over a sum-reduction for bwd), warms the executable,
then times ``--iters`` synchronized runs. Dispatch overhead is excluded the
XLA way (block_until_ready around the loop) rather than with CUDA events.

Usage::

    python -m benchmark.opperf                       # curated default set
    python -m benchmark.opperf --ops dot,softmax     # subset
    python -m benchmark.opperf --all                 # every op with a config
    python -m benchmark.opperf --json out.json

Each row: {"op", "case", "fwd_ms", "bwd_ms", "gflops" (when known)}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as onp


def _rng():
    return onp.random.RandomState(0)


# ---------------------------------------------------------------------------
# op configs: name -> list of (case_label, kwargs_builder, flops or None).
# The builder returns (args, kwargs) of NUMPY arrays / python scalars.
# ---------------------------------------------------------------------------

def _elementwise(shape=(1024, 1024)):
    return lambda: (( _rng().randn(*shape).astype("float32"),), {}), \
        float(onp.prod(shape))


def _binary(shape=(1024, 1024)):
    r = _rng()
    return lambda: ((r.randn(*shape).astype("float32"),
                     r.randn(*shape).astype("float32")), {}), \
        float(onp.prod(shape))


def op_configs() -> Dict[str, List[Tuple[str, Callable, Optional[float]]]]:
    r = _rng()
    cfg: Dict[str, List] = {}

    def add(name, case, builder, flops=None):
        cfg.setdefault(name, []).append((case, builder, flops))

    # --- matmul family (the MXU ops) ---
    for m, k, n in ((512, 512, 512), (2048, 2048, 2048)):
        add("dot", f"{m}x{k}x{n}",
            lambda m=m, k=k, n=n: ((r.randn(m, k).astype("float32"),
                                    r.randn(k, n).astype("float32")), {}),
            2.0 * m * k * n)
    add("batch_dot", "32x128x128x128",
        lambda: ((r.randn(32, 128, 128).astype("float32"),
                  r.randn(32, 128, 128).astype("float32")), {}),
        2.0 * 32 * 128 ** 3)
    add("FullyConnected", "B256_C1024_H1024",
        lambda: ((r.randn(256, 1024).astype("float32"),
                  r.randn(1024, 1024).astype("float32"),
                  r.randn(1024).astype("float32")),
                 {"num_hidden": 1024}),
        2.0 * 256 * 1024 * 1024)

    # --- conv / pool ---
    add("Convolution", "B32_C64_HW56_K3",
        lambda: ((r.randn(32, 64, 56, 56).astype("float32"),
                  r.randn(64, 64, 3, 3).astype("float32"),
                  r.randn(64).astype("float32")),
                 {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}),
        2.0 * 32 * 64 * 56 * 56 * 64 * 9)
    add("Pooling", "B32_C64_HW56_max2",
        lambda: ((r.randn(32, 64, 56, 56).astype("float32"),),
                 {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"}))

    # --- norm / activation / softmax ---
    add("LayerNorm", "B64_L512_C1024",
        lambda: ((r.randn(64, 512, 1024).astype("float32"),
                  onp.ones(1024, "float32"), onp.zeros(1024, "float32")), {}))
    add("BatchNorm", "B64_C256_HW28",
        lambda: ((r.randn(64, 256, 28, 28).astype("float32"),
                  onp.ones(256, "float32"), onp.zeros(256, "float32"),
                  onp.zeros(256, "float32"), onp.ones(256, "float32")), {}))
    add("softmax", "B64_L512_V32k",
        lambda: ((r.randn(64, 512, 32768).astype("float32"),), {}))
    add("Activation", "relu_1Melem",
        lambda: ((r.randn(1024, 1024).astype("float32"),),
                 {"act_type": "relu"}))

    # --- elementwise / binary / reduce ---
    b, f = _binary()
    add("broadcast_add", "1024x1024", b, f)
    b, f = _binary()
    add("broadcast_mul", "1024x1024", b, f)
    e, f = _elementwise()
    add("exp", "1024x1024", e, f)
    e, f = _elementwise()
    add("sqrt", "1024x1024", e, f)
    add("sum", "1024x1024_axis1",
        lambda: ((r.randn(1024, 1024).astype("float32"),), {"axis": 1}))
    add("transpose", "1024x1024",
        lambda: ((r.randn(1024, 1024).astype("float32"),), {}))

    # --- attention (the north-star hot op) ---
    add("dot_product_attention", "B8_H12_L512_D64",
        lambda: ((r.randn(8, 12, 512, 64).astype("float32"),
                  r.randn(8, 12, 512, 64).astype("float32"),
                  r.randn(8, 12, 512, 64).astype("float32")), {}),
        4.0 * 8 * 12 * 512 * 512 * 64)
    add("dot_product_attention", "B4_H8_L2048_D64_causal_win256",
        lambda: ((r.randn(4, 8, 2048, 64).astype("float32"),
                  r.randn(4, 8, 2048, 64).astype("float32"),
                  r.randn(4, 8, 2048, 64).astype("float32")),
                 {"causal": True, "window": 256}),
        # useful FLOPs ~ 4*B*H*L*W*D inside the band
        4.0 * 4 * 8 * 2048 * 256 * 64)

    # --- patch extraction ---
    add("im2col", "B32_C64_HW56_K3",
        lambda: ((r.randn(32, 64, 56, 56).astype("float32"),),
                 {"kernel": (3, 3), "stride": (1, 1)}))

    # --- indexing ---
    add("take", "emb30k_1024x512",
        lambda: ((r.randn(30522, 256).astype("float32"),
                  r.randint(0, 30522, (1024,)).astype("int32")), {}))
    add("Embedding", "V30k_C256_B256xL64",
        lambda: ((r.randint(0, 30522, (256, 64)).astype("int32"),
                  r.randn(30522, 256).astype("float32")),
                 {"input_dim": 30522, "output_dim": 256}))

    # --- int8 path ---
    add("quantized_fully_connected", "B256_C1024_H1024_int8",
        lambda: ((r.randint(-127, 127, (256, 1024)).astype("int8"),
                  r.randint(-127, 127, (1024, 1024)).astype("int8"),
                  None,
                  onp.float32(-1), onp.float32(1),
                  onp.float32(-1), onp.float32(1)),
                 {"num_hidden": 1024, "no_bias": True}),
        2.0 * 256 * 1024 * 1024)
    return cfg


DEFAULT_SET = ["dot", "FullyConnected", "Convolution", "LayerNorm",
               "softmax", "dot_product_attention", "broadcast_add", "take"]


def bench_one(opname: str, case: str, builder: Callable,
              flops: Optional[float], iters: int = 10,
              with_bwd: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.registry import OPS

    fn = OPS[opname].fn
    args, kwargs = builder()
    dev_args = [None if a is None else jnp.asarray(a) for a in args]

    def fwd(*xs):
        out = fn(*xs, **kwargs)
        return out

    jfwd = jax.jit(fwd)

    def _sync(o):
        for leaf in jax.tree.leaves(o):
            leaf.block_until_ready()

    _sync(jfwd(*dev_args))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfwd(*dev_args)
    _sync(out)
    fwd_ms = (time.perf_counter() - t0) / iters * 1e3

    row = {"op": opname, "case": case, "fwd_ms": round(fwd_ms, 4)}
    if flops:
        row["gflops"] = round(flops / (fwd_ms / 1e3) / 1e9, 1)

    if with_bwd:
        diff_idx = [i for i, a in enumerate(dev_args)
                    if a is not None
                    and jnp.issubdtype(a.dtype, jnp.floating)]
        if diff_idx:
            def loss(*xs):
                out = fn(*xs, **kwargs)
                leaves = [l for l in jax.tree.leaves(out)
                          if jnp.issubdtype(l.dtype, jnp.floating)]
                return sum(jnp.sum(l.astype(jnp.float32)) for l in leaves)

            try:
                jbwd = jax.jit(jax.grad(loss, argnums=tuple(diff_idx)))
                _sync(jbwd(*dev_args))
                t0 = time.perf_counter()
                for _ in range(iters):
                    g = jbwd(*dev_args)
                _sync(g)
                row["bwd_ms"] = round(
                    (time.perf_counter() - t0) / iters * 1e3, 4)
            except Exception:
                row["bwd_ms"] = None   # non-differentiable op
    return row


def run(ops: Optional[List[str]] = None, iters: int = 10,
        with_bwd: bool = True) -> List[Dict]:
    """Bench the named ops (default: the curated set). Every finished row
    is published as one ``opperf.result`` telemetry event, so a run with
    ``MXTPU_TELEMETRY_JSONL`` set leaves a stream
    ``tools/telemetry_check.py`` validates exactly like the serve bench's
    — machine consumers read the JSONL, not scraped stdout."""
    from incubator_mxnet_tpu import telemetry

    cfg = op_configs()
    names = ops if ops else DEFAULT_SET
    rows = []
    for name in names:
        if name not in cfg:
            rows.append({"op": name, "error": "no benchmark config"})
            telemetry.emit("opperf.result", severity="warning",
                           **rows[-1])
            continue
        for case, builder, flops in cfg[name]:
            try:
                rows.append(bench_one(name, case, builder, flops,
                                      iters=iters, with_bwd=with_bwd))
                telemetry.emit("opperf.result", **rows[-1])
            except Exception as e:  # pragma: no cover - per-op diagnostics
                rows.append({"op": name, "case": case,
                             "error": f"{type(e).__name__}: {e}"})
                telemetry.emit("opperf.result", severity="error",
                               **rows[-1])
    return rows


def run_performance_test(fn_name: str, inputs: dict, iters: int = 10) -> Dict:
    """Programmatic single-op entry (reference: opperf
    run_performance_test): ``inputs`` maps arg names to numpy arrays /
    values, applied positionally after sorting by key order given."""
    args = tuple(inputs.values())
    return bench_one(fn_name, "custom", lambda: (args, {}), None,
                     iters=iters)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ops", help="comma-separated op names")
    ap.add_argument("--all", action="store_true",
                    help="every op with a config")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--no-bwd", action="store_true")
    ap.add_argument("--json", help="write the report to this file")
    args = ap.parse_args(argv)
    names = None
    if args.all:
        names = sorted(op_configs())
    elif args.ops:
        names = [s.strip() for s in args.ops.split(",") if s.strip()]
    rows = run(names, iters=args.iters, with_bwd=not args.no_bwd)
    import jax
    from incubator_mxnet_tpu import telemetry
    report = {"backend": jax.default_backend(),
              "device": str(jax.devices()[0].device_kind),
              "rows": rows}
    # the summary rides the telemetry stream too (per-row events were
    # emitted by run()); stdout keeps the one strict-JSON report line
    telemetry.emit("opperf.report", backend=report["backend"],
                   device=report["device"], rows=len(rows),
                   errors=sum(1 for r in rows if "error" in r))
    text = telemetry.dumps_strict(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
