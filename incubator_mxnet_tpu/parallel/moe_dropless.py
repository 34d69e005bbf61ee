"""Dropless top-k routing over the experts one chip holds.

The second routed FFN of the repo (``moe.py`` is the first: top-1, softmax,
a fixed capacity, tokens dropped, every expert present). This one is what an
expert-parallel deployment runs on each chip: the router scores **all**
``num_experts`` experts, a token takes its ``top_k`` best, and the chip
computes the part of the result that the experts it **holds**
(``held = (first, count)``, a contiguous range) give. What the absent
experts would add is left out; no code stands in for the other chips or
their exchange.

No assignment is dropped, whatever the skew. Rows are laid out for
``ops/pallas/moe_gmm.py`` (sorted by expert, each expert padded to whole
tiles) in one buffer sized for the worst case: every one of the ``tokens *
top_k`` assignments landing here. The kernels do work for the rows present;
the gathers and elementwise passes round them cost what the buffer costs
(PERF.md section 6, PR 27, has both), and the step stays one executable
with one path through it.

Everything is a pure function of arrays; ``models/afmoe.py`` wraps it.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..ops.pallas import moe_gmm

__all__ = ["router_scores", "sigmoid_topk", "plan_rows", "RowPlan",
           "routed_experts", "buffer_rows", "placement"]

_FAR = 2**30          # an index out of every buffer: gathers fill, scatters drop


def router_scores(x, router_weight):
    """Sigmoid scores ``(T, E)`` over every expert, in fp32."""
    return jax.nn.sigmoid(jnp.einsum(
        "tc,ec->te", x.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


def sigmoid_topk(x, router_weight, select_bias, top_k: int,
                 route_norm: bool = True, route_scale: float = 1.0):
    """``(idx (T, k) int32, weight (T, k) float32)``: sigmoid scores in fp32
    over every expert, the ``top_k`` largest of ``score + select_bias`` (the
    bias steers selection only and carries no gradient), weights the chosen
    scores themselves, renormalised to sum to one and scaled."""
    scores = router_scores(x, router_weight)
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), top_k)
    weight = jnp.take_along_axis(scores, idx, axis=1)
    if route_norm:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weight * route_scale


class RowPlan(NamedTuple):
    """Where each assignment's row lies in the sorted buffer, and back."""
    dest: jax.Array         # (T, k): row of each assignment; _FAR where its expert is absent
    order: jax.Array        # (T*k,): assignments sorted by expert held (absent last)
    counts: jax.Array       # (G,): rows of each expert held
    starts: jax.Array       # (G,): first row of each expert, padded layout
    sorted_starts: jax.Array  # (G,): first sorted position of each expert
    rows_padded: jax.Array  # (): rows in use, padding included


def plan_rows(idx, held: Sequence[int], tile_rows: int) -> RowPlan:
    """Sort the ``(T, k)`` assignments by expert held and lay each expert's
    rows on whole tiles (an expert with no row keeps one empty tile, so the
    weight-gradient kernel writes its zeros)."""
    first, G = held
    T, k = idx.shape
    local = idx - first
    mine = jnp.logical_and(local >= 0, local < G)
    key = jnp.where(mine, local, G).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)          # assignment -> sorted position
    counts = (key[:, None] == jnp.arange(G)[None, :]).sum(0).astype(jnp.int32)
    sorted_starts = jnp.cumsum(counts) - counts
    sizes = jnp.maximum((counts + tile_rows - 1) // tile_rows, 1) * tile_rows
    starts = jnp.cumsum(sizes) - sizes
    g = jnp.minimum(key, G - 1)
    dest = jnp.where(key < G, rank - sorted_starts[g] + starts[g], _FAR)
    return RowPlan(dest.reshape(T, k).astype(jnp.int32), order, counts,
                   starts.astype(jnp.int32), sorted_starts.astype(jnp.int32),
                   sizes.sum().astype(jnp.int32))


def _rows_of(plan: RowPlan, rows: int, tile_rows: int):
    """For a buffer of ``rows``: each tile's expert, the tiles in use, and
    for each row the assignment it carries (``_FAR``: padding)."""
    G = plan.counts.shape[0]
    ends = plan.starts + jnp.maximum(
        (plan.counts + tile_rows - 1) // tile_rows, 1) * tile_rows
    tile_first = jnp.arange(rows // tile_rows, dtype=jnp.int32) * tile_rows
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_first, side="right"), G - 1).astype(jnp.int32)
    n_tiles = (plan.rows_padded // tile_rows).reshape(1)
    g = jnp.repeat(tile_group, tile_rows)
    off = jnp.arange(rows, dtype=jnp.int32) - plan.starts[g]
    valid = jnp.logical_and(off < plan.counts[g],
                            jnp.arange(rows) < plan.rows_padded)
    pos = jnp.clip(plan.sorted_starts[g] + off, 0, plan.order.shape[0] - 1)
    row_assign = jnp.where(valid, plan.order[pos], _FAR)
    return tile_group, n_tiles, row_assign


# The two moves between token order and sorted rows are each other's
# transpose, and both directions are gathers here (the row of an assignment,
# the assignment of a row): XLA's own transpose of a gather is a scatter-add,
# which a TPU runs an index at a time.

@jax.custom_vjp
def _dispatch(x, row_token, dest):
    return jnp.take(x, row_token, axis=0, mode="fill", fill_value=0)


def _dispatch_fwd(x, row_token, dest):
    return _dispatch(x, row_token, dest), (row_token, dest)


def _dispatch_bwd(res, d_rows):
    row_token, dest = res
    T, k = dest.shape
    dx = jnp.take(d_rows, dest.reshape(-1), axis=0, mode="fill", fill_value=0)
    return dx.reshape(T, k, -1).sum(1).astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, weight, row_token, row_weight_at, dest):
    T, k = dest.shape
    rows = jnp.take(y, dest.reshape(-1), axis=0, mode="fill", fill_value=0)
    return (rows.reshape(T, k, -1).astype(jnp.float32)
            * weight[..., None]).sum(1).astype(y.dtype)


def _combine_fwd(y, weight, row_token, row_weight_at, dest):
    return (_combine(y, weight, row_token, row_weight_at, dest),
            (y, weight, row_token, row_weight_at, dest))


def _combine_bwd(res, d_out):
    y, weight, row_token, row_weight_at, dest = res
    d_at_row = jnp.take(d_out, row_token, axis=0, mode="fill", fill_value=0)
    row_weight = jnp.take(weight.reshape(-1), row_weight_at, mode="fill", fill_value=0)
    valid = (row_weight_at < _FAR)[:, None]
    dy = jnp.where(valid, d_at_row.astype(jnp.float32) * row_weight[:, None], 0)
    dw_row = jnp.where(valid, y.astype(jnp.float32) * d_at_row.astype(jnp.float32),
                       0).sum(-1)
    dw = jnp.take(dw_row, dest.reshape(-1), mode="fill", fill_value=0)
    return dy.astype(y.dtype), dw.reshape(weight.shape), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def buffer_rows(tokens: int, top_k: int, groups: int, tile_rows: int) -> int:
    """Rows of the buffer the routed half is compiled for, the worst case:
    every assignment landing on the ``groups`` experts held, however they
    fall (whole tiles, every expert's last tile nearly empty)."""
    return (-(-tokens * top_k // tile_rows) + groups) * tile_rows


def placement(idx, held: Sequence[int], tile_rows: int) -> dict:
    """What :func:`routed_experts` does with ``idx``, counted (eagerly, on
    concrete arrays): ``counts`` (rows of each expert held),
    ``assignments_held``, and ``rows_placed``, the rows of the buffer that
    carry an assignment. Dropless means the last two agree."""
    T, k = idx.shape
    plan = plan_rows(idx, held, tile_rows)
    rows = buffer_rows(T, k, held[1], tile_rows)
    return {"counts": plan.counts, "assignments_held": (plan.dest < _FAR).sum(),
            "rows_placed": (_rows_of(plan, rows, tile_rows)[2] < _FAR).sum()}


def routed_experts(x, idx, weight, w13, w2, held: Sequence[int],
                   tile_rows: int = moe_gmm.TILE_ROWS, plan: RowPlan = None):
    """``(T, C)``: for each token the weighted sum of its experts held here:
    gather, gate-and-up grouped matmul, SiLU gate, down grouped matmul,
    weighted sum back per token.

    ``x (T, C)``; ``idx``/``weight (T, k)`` from :func:`sigmoid_topk`;
    ``w13 (G, 2F, C)`` gate and up projections stacked, ``w2 (G, C, F)``,
    both ``(out, in)``; ``held = (first, G)``; ``plan`` the
    :func:`plan_rows` of ``idx`` where the caller has made it already."""
    T, k = idx.shape
    if plan is None:
        plan = plan_rows(idx, tuple(held), tile_rows)
    rows = buffer_rows(T, k, held[1], tile_rows)
    tile_group, n_tiles, row_assign = _rows_of(plan, rows, tile_rows)
    row_token = jnp.where(row_assign < _FAR, row_assign // k, _FAR)
    valid = (row_assign < _FAR)[:, None]
    with jax.named_scope("moe_dispatch"):
        xs = _dispatch(x, row_token, plan.dest)
    with jax.named_scope("moe_experts"):
        h = moe_gmm.grouped_matmul(xs, w13, tile_group, n_tiles, tile_rows)
        gate, up = jnp.split(h, 2, axis=-1)
        # rows past those in use are whatever the buffer held: masked here,
        # where it fuses with the gate
        act = jnp.where(valid, jax.nn.silu(gate.astype(jnp.float32))
                        * up.astype(jnp.float32), 0).astype(x.dtype)
        y = moe_gmm.grouped_matmul(act, w2, tile_group, n_tiles, tile_rows)
        y = jnp.where(valid, y, 0)
    with jax.named_scope("moe_combine"):
        return _combine(y, weight, row_token, row_assign, plan.dest)
