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
top_k`` assignments landing here. The step stays one executable with one
path through it, and on a TPU every pass over that buffer costs what the
rows present cost: a pass in row order visits the ``n_tiles`` tiles in use,
as the grouped matmuls do, and a pass in token order fetches a row only for
an assignment whose expert is held (``ops/pallas/moe_rows.py``). Rows past
``rows_padded`` hold whatever was there and are read by nothing; the padding
rows of a tile in use are zero from the dispatch on, and stay zero through
the products and the gate. Off the chip the same moves are XLA gathers over
the whole buffer and the products ``ragged_dot``, chosen by platform as
``grouped_matmul`` chooses; the tests hold the kernels to them.

Everything is a pure function of arrays; ``models/afmoe.py`` wraps it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..ops.pallas import moe_gmm, moe_rows

__all__ = ["router_scores", "sigmoid_topk", "plan_rows", "RowPlan",
           "routed_experts", "buffer_rows", "placement"]

_FAR = 2**30          # an index out of every buffer: gathers fill, scatters drop


def router_scores(x, router_weight):
    """Sigmoid scores ``(T, E)`` over every expert, in fp32."""
    return jax.nn.sigmoid(jnp.einsum(
        "tc,ec->te", x.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


def sigmoid_topk(x, router_weight, select_bias, top_k: int,
                 route_norm: bool = True, route_scale: float = 1.0,
                 norm_eps: float = 1e-20):
    """``(idx (T, k) int32, weight (T, k) float32)``: sigmoid scores in fp32
    over every expert, the ``top_k`` largest of ``score + select_bias`` (the
    bias steers selection only and carries no gradient), weights the chosen
    scores themselves, renormalised to sum to one (over their sum +
    ``norm_eps``, each family's own) and scaled."""
    scores = router_scores(x, router_weight)
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)), top_k)
    # the chosen scores by selection, not by a gather: a TPU gathers scalars
    # an index at a time, and the gather's transpose is a scatter-add
    chosen = idx[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]
    weight = jnp.where(chosen, scores[:, None, :], 0).sum(-1)
    if route_norm:
        weight = weight / (weight.sum(-1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), weight * route_scale


class RowPlan(NamedTuple):
    """Where each assignment's row lies in the sorted buffer, and back."""
    dest: jax.Array         # (T, k): row of each assignment; _FAR where its expert is absent
    order: jax.Array        # (T*k,): assignments sorted by expert held (absent last)
    counts: jax.Array       # (G,): rows of each expert held
    starts: jax.Array       # (G,): first row of each expert, padded layout
    sorted_starts: jax.Array  # (G,): first sorted position of each expert
    rows_padded: jax.Array  # (): rows in use, padding included


@functools.partial(jax.jit, static_argnums=(1, 2))
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


def _spread(values, plan: RowPlan, row_group, tile_rows: int):
    """``values (T*k,)`` in sorted order, laid out as the buffer's rows are
    (``row_group (R,)`` the expert of each): expert ``g``'s run, from
    ``sorted_starts[g]``, lands from ``starts[g]``. The padded layout is the
    sorted one with gaps let in, so each expert's rows are one slice moved
    back by the padding before it: a slice an expert, where a gather of
    ``R`` scalars costs a TPU ten times that. Rows that carry nothing get
    whatever lies there."""
    G, rows = plan.counts.shape[0], row_group.shape[0]
    room = G * tile_rows                  # the most padding before any expert
    wide = jnp.concatenate([jnp.zeros((room,), values.dtype), values,
                            jnp.zeros((max(rows - values.shape[0], 0),), values.dtype)])
    out = jnp.zeros((rows,), values.dtype)
    for g in range(G):
        moved = jax.lax.dynamic_slice(
            wide, (room - (plan.starts[g] - plan.sorted_starts[g]),), (rows,))
        out = jnp.where(row_group == g, moved, out)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _rows_of(plan: RowPlan, rows: int, tile_rows: int):
    """For a buffer of ``rows``: each tile's expert, the tiles in use, for
    each row the assignment it carries (``_FAR``: padding), and the rows of
    each tile that carry one (its leading rows; none past the tiles in use)."""
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
    row_assign = jnp.where(valid, _spread(plan.order, plan, g, tile_rows), _FAR)
    tile_valid = jnp.where(
        tile_first < plan.rows_padded,
        jnp.clip(plan.counts[tile_group] - (tile_first - plan.starts[tile_group]),
                 0, tile_rows), 0).astype(jnp.int32)
    return tile_group, n_tiles, row_assign, tile_valid


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


# The same two moves on a TPU, by the row kernels: what is fetched a row at a
# time is packed into a slab first (``moe_rows``), the tiles in use only.

class _Moves(NamedTuple):
    """The indices the row kernels move rows by."""
    n_tiles: jax.Array      # (1,): tiles in use
    tile_valid: jax.Array   # (tiles,): leading rows of each tile that carry an assignment
    row_token: jax.Array    # (R,): the token of each such row
    row_assign: jax.Array   # (R,): its assignment (_FAR: padding)
    dest: jax.Array         # (T, k): a token's rows, the assignments held in its leading slots
    held: jax.Array         # (T,): how many
    slot: jax.Array         # (T, k, k) fp32: 1 where assignment j is held and takes slot s
    plan: RowPlan
    row_group: jax.Array    # (R,): the expert of each row


def _moves(plan: RowPlan, tile_group, n_tiles, row_assign, tile_valid, tile_rows) -> _Moves:
    k = plan.dest.shape[1]
    held = plan.dest < _FAR
    slot = jnp.logical_and(held[:, :, None], (jnp.cumsum(held, axis=1) - 1)[:, :, None]
                           == jnp.arange(k)[None, None, :])
    dest = jnp.where(slot, plan.dest[:, :, None], 0).sum(1).astype(jnp.int32)
    return _Moves(n_tiles, tile_valid, row_assign // k, row_assign, dest,
                  held.sum(1).astype(jnp.int32), slot.astype(jnp.float32), plan,
                  jnp.repeat(tile_group, tile_rows))


def _like(a):
    return a.shape[1], a.dtype


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch_rows(x, mv: _Moves, tile_rows: int):
    return moe_rows.gather(moe_rows.pack(x, None, tile_rows), _like(x), mv.row_token,
                           mv.tile_valid, mv.n_tiles, tile_rows)


def _dispatch_rows_fwd(x, mv, tile_rows):
    return _dispatch_rows(x, mv, tile_rows), mv


def _dispatch_rows_bwd(tile_rows, mv, d_rows):
    slab = moe_rows.pack(d_rows, mv.n_tiles, tile_rows)
    return moe_rows.combine(slab, _like(d_rows), mv.dest, mv.held,
                            jnp.ones(mv.dest.shape, jnp.float32)), None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_rows(y, weight, mv: _Moves, tile_rows: int):
    return _combine_rows_fwd(y, weight, mv, tile_rows)[0]


def _combine_rows_fwd(y, weight, mv, tile_rows):
    slab = moe_rows.pack(y, mv.n_tiles, tile_rows)
    out = moe_rows.combine(slab, _like(y), mv.dest, mv.held,
                           jnp.einsum("tjs,tj->ts", mv.slot, weight))
    return out, (slab, weight, mv)


def _combine_rows_bwd(tile_rows, res, d_out):
    slab, weight, mv = res
    like = _like(d_out)
    # each row's weight: the weights sorted as the rows are (a sort by row is
    # a tenth of a gather by assignment), then spread as the rows are
    by_row = jax.lax.sort((mv.plan.dest.reshape(-1), weight.reshape(-1)), num_keys=1)[1]
    row_weight = jnp.where(mv.row_assign < _FAR,
                           _spread(by_row, mv.plan, mv.row_group, tile_rows), 0)
    d_slab = moe_rows.pack(d_out, None, tile_rows)
    dy = moe_rows.gather(d_slab, like, mv.row_token, mv.tile_valid, mv.n_tiles, tile_rows,
                         scale=row_weight[:, None])
    dw = jnp.einsum("tjs,ts->tj", mv.slot,
                    moe_rows.dot(slab, like, mv.dest, mv.held, d_slab))
    return dy, dw, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _row_kernels(x) -> bool:
    """The row kernels and the Pallas grouped matmuls on a TPU, XLA's gathers
    and ``ragged_dot`` elsewhere: by platform, as ``grouped_matmul`` chooses."""
    return not moe_gmm._interpret_for(x)


def buffer_rows(tokens: int, top_k: int, groups: int, tile_rows: int) -> int:
    """Rows of the buffer the routed half is compiled for, the worst case:
    every assignment landing on the ``groups`` experts held, however they
    fall (whole tiles, every expert's last tile nearly empty)."""
    return (-(-tokens * top_k // tile_rows) + groups) * tile_rows


def placement(idx, held: Sequence[int], tile_rows: int) -> dict:
    """What :func:`routed_experts` does with ``idx``, counted (eagerly, on
    concrete arrays): ``counts`` (rows of each expert held),
    ``assignments_held``, ``rows_placed``, the rows of the buffer that carry
    an assignment (dropless means these two agree), and ``rows_live_share``,
    the share of the buffer in use (``rows_padded`` over its rows), which is
    the share of it the passes in row order visit."""
    T, k = idx.shape
    plan = plan_rows(idx, tuple(held), tile_rows)
    rows = buffer_rows(T, k, held[1], tile_rows)
    return {"counts": plan.counts, "assignments_held": (plan.dest < _FAR).sum(),
            "rows_placed": (_rows_of(plan, rows, tile_rows)[2] < _FAR).sum(),
            "rows_live_share": plan.rows_padded / rows}


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
    held = tuple(held)
    return _routed_half(x, idx, weight, w13, w2, plan, held, tile_rows,
                        buffer_rows(T, k, held[1], tile_rows), _row_kernels(x))


# One jitted function, so that a model's routed layers (the same shapes, each
# traced for the forward pass, its recomputation and the backward pass) are
# traced and lowered once between them, and an eager call is one dispatch.

@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _routed_half(x, idx, weight, w13, w2, plan, held, tile_rows, rows, row_kernels):
    k = idx.shape[1]
    if plan is None:
        plan = plan_rows(idx, held, tile_rows)
    tile_group, n_tiles, row_assign, tile_valid = _rows_of(plan, rows, tile_rows)
    if row_kernels:
        mv = _moves(plan, tile_group, n_tiles, row_assign, tile_valid, tile_rows)
        with jax.named_scope("moe_dispatch"):
            xs = _dispatch_rows(x, mv, tile_rows)
        with jax.named_scope("moe_experts"):
            h = moe_gmm.grouped_matmul(xs, w13, tile_group, n_tiles, tile_rows, impl="pallas")
            y = moe_gmm.grouped_matmul(moe_rows.gate(h, n_tiles, tile_rows), w2,
                                       tile_group, n_tiles, tile_rows, impl="pallas")
        with jax.named_scope("moe_combine"):
            return _combine_rows(y, weight, mv, tile_rows)
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
