#!/usr/bin/env python3
"""Time the routed experts' grouped matmul on the chip, three ways.

    python benchmark/moe_gmm_probe.py [--tokens 16384] [--out chiprun_out/moe_gmm_probe.json]

At Trinity-Mini's shapes on one chip of eight (16 experts of 2,048 x 1,024
held, top 8 of 128, rows from a seeded uniform router): the repo's Pallas
kernels (``ops/pallas/moe_gmm.py``) at two tile heights, ``jax.lax.ragged_dot``
and jax's ``megablox`` kernel (tiles 512 x 1,024 x 1,024), the last two also
on the rows unpadded, each forward (gate-and-up, then down) and
forward + backward, on the same sorted rows. With ``--routed`` instead the
whole routed half of a layer (``moe_dropless.routed_experts``: sort, gather,
the grouped matmuls, combine), forward + backward, in the buffer it is
compiled for (the worst case, every assignment held) and in a quarter of it,
by the row kernels a TPU takes (``rows``) and by the XLA passes over the
whole buffer that they replaced (``xla``): what the buffer's size costs round
the grouped matmuls, before and after. Then each pass alone both ways, at a
drawn router's rows (an eighth of the buffer live) and with every assignment
held (all of it live), and the half once more with every dead tile set to
NaN as each kernel hands its output over, which must change no bit of the
result. Prints one JSON object; needs a TPU (the numbers of a CPU run would
be the interpreter's).
"""
import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.pallas import moe_gmm, moe_rows
from incubator_mxnet_tpu.parallel import moe_dropless

C, F, G, E, K = 2048, 1024, 16, 128, 8

#: every function that hands over a buffer with tiles out of use
DEAD_TILE_WRITERS = ((moe_rows, "pack"), (moe_rows, "gather"), (moe_rows, "_by_tile"),
                     (moe_gmm, "gmm"), (moe_gmm, "tgmm"))


def poisoned(fn, tiles: int, calls: list):
    """``fn`` with every row of its output past the tiles in use set to NaN
    (all bits set, in a slab); ``tiles`` is what the buffer has, and
    ``n_tiles`` is found among the arguments: the one ``(1,)`` int32. An
    output over every row, or one a group (``tgmm``), has nothing dead.
    Each output poisoned leaves ``fn``'s name in ``calls``."""
    def wrapped(*args, **kwargs):
        out = fn(*args, **kwargs)
        n_tiles = [a for a in list(args) + list(kwargs.values())
                   if getattr(a, "shape", None) == (1,) and a.dtype == jnp.int32]
        if not n_tiles or out.ndim != 2 or out.shape[0] % tiles:
            return out
        calls.append(fn.__name__)
        dead = jnp.arange(out.shape[0])[:, None] >= n_tiles[0][0] * (out.shape[0] // tiles)
        bad = jnp.uint32(0xFFFFFFFF) if out.dtype == jnp.uint32 else jnp.nan
        return jnp.where(dead, bad, out).astype(out.dtype)
    return wrapped


@contextlib.contextmanager
def dead_tiles_poisoned(tiles: int):
    """Every dead tile NaN as it is handed over, for the routed halves traced
    inside; gives the list of the writers whose output was poisoned."""
    calls = []
    kept = [(m, n, getattr(m, n)) for m, n in DEAD_TILE_WRITERS]
    for m, n, fn in kept:
        setattr(m, n, poisoned(fn, tiles, calls))
    moe_dropless._routed_half.clear_cache()      # traced with the writers as they were
    try:
        yield calls
    finally:
        for m, n, fn in kept:
            setattr(m, n, fn)
        moe_dropless._routed_half.clear_cache()


def timed(fn, *args, reps=10):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def routed_half(result, idx, w13, w2, keys):
    """``routed_experts`` forward + backward at the layer's own tile height,
    in its own buffer and in a quarter of it (which holds a fresh router's
    rows with room to spare, and would lose assignments under a skew), by the
    XLA passes and by the row kernels; each pass alone; the poison check."""
    T, tm = idx.shape[0], moe_gmm.TILE_ROWS
    x = jax.random.normal(keys[3], (T, C)).astype(jnp.bfloat16)
    weight = jax.random.uniform(keys[4], (T, K), jnp.float32)
    whole = moe_dropless.buffer_rows(T, K, G, tm)
    worst_case, on_the_chip = moe_dropless.buffer_rows, moe_dropless._row_kernels
    result["rows_present"] = int(moe_dropless.plan_rows(idx, (0, G), tm).counts.sum())

    def loss(x, weight, w13, w2):
        return moe_dropless.routed_experts(x, idx, weight, w13, w2, (0, G)
                                           ).astype(jnp.float32).sum()

    grads = {}
    for passes in ("xla", "rows"):
        moe_dropless._row_kernels = lambda x, passes=passes: passes == "rows"
        for name, rows in (("worst_case", whole), ("quarter", whole // 4)):
            moe_dropless.buffer_rows = lambda *a, rows=rows: rows
            both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            result[f"rows_buffer_{name}"] = rows
            result["ms"][f"routed_half_{name}_{passes}_fwd_bwd"] = timed(both, x, weight, w13, w2)
            if name == "worst_case":
                grads[passes] = both(x, weight, w13, w2)
    moe_dropless.buffer_rows, moe_dropless._row_kernels = worst_case, on_the_chip
    # the row kernels against the XLA passes, on the chip: the largest
    # difference of each gradient over its largest value
    result["rows_against_xla_rel_err"] = {
        name: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
                    / jnp.abs(b.astype(jnp.float32)).max())
        for name, a, b in zip(("dx", "dweight", "dw13", "dw2"), grads["rows"], grads["xla"])}

    # every dead tile NaN as it is handed over: not a bit of the result may move
    both = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(x, weight, w13, w2)  # noqa: E731
    clean = jax.tree.leaves(both())
    with dead_tiles_poisoned(whole // tm) as calls:
        dirty = jax.tree.leaves(both())
    result["poisoned_dead_tiles"] = {
        "loss": float(dirty[0]), "loss_unpoisoned": float(clean[0]),
        "outputs_poisoned": sorted(set(calls)),
        "all_finite": all(bool(jnp.isfinite(a.astype(jnp.float32)).all()) for a in dirty),
        "bit_equal": all(bool((a == b).all()) for a, b in zip(dirty, clean))}

    # each pass alone, forward and forward + backward, both ways
    held_all = jax.lax.top_k(jax.random.uniform(keys[0], (T, G)), K)[1].astype(jnp.int32)
    for routing, ids in (("drawn", idx), ("all_held", held_all)):
        plan = moe_dropless.plan_rows(ids, (0, G), tm)
        tile_group, n_tiles, row_assign, tile_valid = moe_dropless._rows_of(plan, whole, tm)
        mv = moe_dropless._moves(plan, tile_group, n_tiles, row_assign, tile_valid, tm)
        row_token = jnp.where(row_assign < 2**30, row_assign // K, 2**30)
        live = (row_assign < 2**30)[:, None]
        result[f"rows_live_share_{routing}"] = float(plan.rows_padded) / whole
        y = jnp.where(live, jax.random.normal(keys[1], (whole, C)), 0).astype(jnp.bfloat16)
        h = jnp.where(live, jax.random.normal(keys[2], (whole, 2 * F)), 0).astype(jnp.bfloat16)

        def gate_xla(h):
            gate, up = jnp.split(h, 2, axis=-1)
            return jnp.where(live, jax.nn.silu(gate.astype(jnp.float32))
                             * up.astype(jnp.float32), 0).astype(h.dtype)

        for name, fn, args in (
                ("dispatch_xla", lambda x: moe_dropless._dispatch(x, row_token, plan.dest), (x,)),
                ("dispatch_rows", lambda x: moe_dropless._dispatch_rows(x, mv, tm), (x,)),
                ("combine_xla", lambda y, w: moe_dropless._combine(
                    y, w, row_token, row_assign, plan.dest), (y, weight)),
                ("combine_rows", lambda y, w: moe_dropless._combine_rows(y, w, mv, tm), (y, weight)),
                ("gate_xla", gate_xla, (h,)),
                ("gate_rows", lambda h: moe_rows.gate(h, n_tiles, tm), (h,))):
            fwd = jax.jit(fn)
            both = jax.jit(jax.grad(lambda *a, fn=fn: fn(*a).astype(jnp.float32).sum(),
                                    argnums=tuple(range(len(args)))))
            result["ms"][f"{name}_{routing}_fwd"] = timed(fwd, *args)
            result["ms"][f"{name}_{routing}_fwd_bwd"] = timed(both, *args)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--routed", action="store_true",
                    help="time the routed half at two buffer sizes and its passes alone, "
                         "by XLA and by the row kernels, not the grouped matmuls")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform!r}")
    T = args.tokens
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    idx = jax.lax.top_k(jax.random.uniform(keys[0], (T, E)), K)[1].astype(jnp.int32)
    w13 = (jax.random.normal(keys[1], (G, 2 * F, C)) * 0.02).astype(jnp.bfloat16)
    w2 = (jax.random.normal(keys[2], (G, C, F)) * 0.02).astype(jnp.bfloat16)
    result = {"device": dev.device_kind, "tokens": T, "ms": {}}
    if args.routed:
        routed_half(result, idx, w13, w2, keys)
    for tm in () if args.routed else (512, 256):
        plan = moe_dropless.plan_rows(idx, (0, G), tm)
        rows = -(-int(plan.rows_padded) // 2048) * 2048     # the rows in use, not the worst case
        tile_group, n_tiles, row_assign, _ = moe_dropless._rows_of(plan, rows, tm)
        result[f"rows_present_tm{tm}"] = int((row_assign < 2**30).sum())
        result[f"rows_padded_tm{tm}"] = int(plan.rows_padded)
        result[f"rows_buffer_tm{tm}"] = rows
        xs = (jax.random.normal(keys[3], (rows, C))).astype(jnp.bfloat16)
        sizes = tm * jnp.sum(jnp.logical_and(
            tile_group[:, None] == jnp.arange(G)[None, :],
            (jnp.arange(tile_group.shape[0]) < n_tiles[0])[:, None]), axis=0, dtype=jnp.int32)

        def ffn(mm):
            def f(xs, w13, w2):
                h = mm(xs, w13)
                gate, up = jnp.split(h, 2, axis=-1)
                act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(xs.dtype)
                return mm(act, w2)
            return f

        def pallas(a, w):
            return moe_gmm.grouped_matmul(a, w, tile_group, n_tiles, tm, impl="pallas")

        def ragged(a, w):
            return jax.lax.ragged_dot(a, w.transpose(0, 2, 1), sizes)

        def megablox(a, w, sizes=sizes):
            from jax.experimental.pallas.ops.tpu.megablox import gmm as mb
            return mb(a, w, sizes, a.dtype, (512, 1024, 1024), None, None, True)

        # ragged_dot and megablox take any group sizes: also on the rows as
        # they come, sorted and unpadded (the buffer a whole number of tiles)
        tight = -(-int(plan.counts.sum()) // 512) * 512
        xt = xs[:tight]

        def ragged_tight(a, w):
            return jax.lax.ragged_dot(a, w.transpose(0, 2, 1), plan.counts)

        def megablox_tight(a, w):
            return megablox(a, w, plan.counts)

        for name, mm in (("pallas", pallas), ("ragged_dot", ragged), ("megablox", megablox),
                         ("ragged_dot_unpadded", ragged_tight),
                         ("megablox_unpadded", megablox_tight)):
            if tm != 512 and name != "pallas":
                continue
            f = ffn(mm)
            xs_ = xt if name.endswith("_unpadded") else xs
            fwd = jax.jit(f)
            both = jax.jit(lambda xs, w13, w2, f=f: jax.grad(
                lambda *a: f(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(xs, w13, w2))
            try:
                result["ms"][f"{name}_tm{tm}_fwd"] = timed(fwd, xs_, w13, w2)
                result["ms"][f"{name}_tm{tm}_fwd_bwd"] = timed(both, xs_, w13, w2)
            except Exception as e:  # noqa: BLE001 - a candidate that cannot run is a finding
                result["ms"][f"{name}_tm{tm}_error"] = f"{type(e).__name__}: {str(e)[:300]}"
    if not args.routed:
        result["fwd_tflop"] = 6.0 * result["rows_present_tm512"] * C * F / 1e12
    print(json.dumps(result))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(result, fh, indent=1)


if __name__ == "__main__":
    main()
