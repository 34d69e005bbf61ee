"""Grouped matmul over the experts a chip holds, as Pallas TPU kernels.

The routed half of a mixture-of-experts FFN multiplies each token's row by
the weights of the expert it was sent to. Rows arrive **sorted by expert**,
and how many each expert got is known only on the device. The layout these
kernels take makes that cheap to index:

- ``lhs (R, K)``: rows sorted by group, every group **padded to whole tiles**
  of ``tile_rows`` rows (padding rows are zero), a group with no row still
  owning one tile. ``R`` is the buffer, sized for the worst case by the
  caller; the rows in use end at ``n_tiles * tile_rows``.
- ``tile_group (R // tile_rows,)`` int32: the group of each tile;
  ``n_tiles (1,)`` int32: tiles in use. Both ride as scalar prefetch, so
  block indices are computed from them before the body runs.

Work is in proportion to the rows present, not to the buffer: a tile past
``n_tiles`` computes nothing, and names the blocks of the last tile in use,
so Pallas (which copies a block only when its index changes) moves nothing
for it either. Output rows past ``n_tiles * tile_rows`` are left as they
come. On a TPU nothing reads them (``parallel/moe_dropless.py``: every pass
round these kernels follows the same ``n_tiles``, ``moe_rows.py``); the XLA
passes that stand in off the chip read the whole buffer and mask them.

``moe_gmm``: ``out[rows of g] = lhs[rows of g] @ rhs[g]``, one MXU matmul a
tile in the input dtype with fp32 accumulation; the weights of a group are
fetched once per column block, however many tiles the group has.
``moe_tgmm``: ``out[g] = a[rows of g]^T @ b[rows of g]``, the weight gradient,
accumulated in VMEM over a group's tiles. :func:`grouped_matmul` ties them
with a custom VJP. ``jax.lax.ragged_dot`` and jax's ``megablox`` compute the
same products from group sizes; PERF.md has what each took at one cell's
shapes on a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_for

__all__ = ["grouped_matmul", "gmm", "tgmm", "TILE_ROWS"]

#: rows a tile holds at full size. An expert's last tile is half empty on
#: average, so at the 512 to 1,024 rows an expert gets in the benchmark's
#: cell 256 wastes half of what 512 does; on a v5e, Trinity-Mini's FFN over
#: 16 experts and 16,354 rows took 2.34 ms forward and 5.86 ms forward and
#: backward at 256 against 2.23 and 6.09 at 512 (PERF.md section 6, PR 27)
TILE_ROWS = 256
_COLS = 1024          # widest block of output columns held in VMEM
#: the blocks below are double-buffered: 2 x (2 + 4 + 1) MB at K=2,048,
#: over Mosaic's 16 MB default and far under a v5e's 128 MiB of VMEM
_VMEM_LIMIT = 64 * 2**20


def _col_block(n: int) -> int:
    return _COLS if n % _COLS == 0 else n


def _gmm_kernel(tg_ref, nt_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del tg_ref                                    # used by the index maps

    @pl.when(pl.program_id(1) < nt_ref[0])
    def _tile():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def gmm(lhs, rhs, tile_group, n_tiles, transpose_rhs: bool = False,
        tile_rows: int = TILE_ROWS):
    """``out (R, N)``: each tile of ``lhs (R, K)`` times its group's matrix,
    ``rhs (G, K, N)`` or, with ``transpose_rhs``, ``rhs (G, N, K)``."""
    R, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tn = tile_rows, _col_block(N)

    def tile(i, nt):              # a tile out of use names the last in use
        return jnp.minimum(i, nt[0] - 1)

    rhs_spec = (pl.BlockSpec((1, tn, K), lambda n, i, tg, nt: (tg[tile(i, nt)], n, 0))
                if transpose_rhs else
                pl.BlockSpec((1, K, tn), lambda n, i, tg, nt: (tg[tile(i, nt)], 0, n)))
    interpret = _interpret_for(lhs)
    kwargs = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, R // tm),      # rows innermost: weights stay put
            in_specs=[pl.BlockSpec((tm, K), lambda n, i, tg, nt: (tile(i, nt), 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda n, i, tg, nt: (tile(i, nt), n))),
        out_shape=jax.ShapeDtypeStruct((R, N), lhs.dtype),
        interpret=interpret, **kwargs,
    )(tile_group, n_tiles, lhs, rhs)


def _tgmm_kernel(tg_ref, nt_ref, a_ref, b_ref, out_ref, acc_ref):
    i, nt = pl.program_id(2), nt_ref[0]
    last_tile = pl.num_programs(2) - 1
    g = tg_ref[i]
    first = jnp.logical_or(i == 0, tg_ref[jnp.maximum(i - 1, 0)] != g)
    last = jnp.logical_or(i == nt - 1, tg_ref[jnp.minimum(i + 1, last_tile)] != g)
    active = i < nt

    @pl.when(jnp.logical_and(active, first))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _tile():
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(active, last))
    def _finish():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def tgmm(a, b, tile_group, n_tiles, num_groups: int,
         tile_rows: int = TILE_ROWS, out_dtype=None):
    """``out (G, P, Q)``: for each group, ``a[rows]^T @ b[rows]`` over its
    rows of ``a (R, P)`` and ``b (R, Q)``. Every group owns at least one
    tile, so every block of ``out`` is written."""
    R, P = a.shape
    Q = b.shape[1]
    tm, tp, tq = tile_rows, _col_block(P), _col_block(Q)

    def tile(i, nt):
        return jnp.minimum(i, nt[0] - 1)

    interpret = _interpret_for(a)
    kwargs = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        _tgmm_kernel,
        name="moe_tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(P // tp, Q // tq, R // tm),
            in_specs=[pl.BlockSpec((tm, tp), lambda p, q, i, tg, nt: (tile(i, nt), p)),
                      pl.BlockSpec((tm, tq), lambda p, q, i, tg, nt: (tile(i, nt), q))],
            out_specs=pl.BlockSpec((1, tp, tq),
                                   lambda p, q, i, tg, nt: (tg[tile(i, nt)], p, q)),
            scratch_shapes=[pltpu.VMEM((tp, tq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_groups, P, Q), out_dtype or a.dtype),
        interpret=interpret, **kwargs,
    )(tile_group, n_tiles, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_pallas(lhs, rhs, tile_group, n_tiles, tile_rows):
    return gmm(lhs, rhs, tile_group, n_tiles, True, tile_rows)


def _grouped_fwd(lhs, rhs, tile_group, n_tiles, tile_rows):
    return (gmm(lhs, rhs, tile_group, n_tiles, True, tile_rows),
            (lhs, rhs, tile_group, n_tiles))


def _grouped_bwd(tile_rows, res, dout):
    lhs, rhs, tile_group, n_tiles = res
    dlhs = gmm(dout, rhs, tile_group, n_tiles, False, tile_rows)
    drhs = tgmm(dout, lhs, tile_group, n_tiles, rhs.shape[0], tile_rows,
                out_dtype=rhs.dtype)
    return dlhs, drhs, None, None


_grouped_pallas.defvjp(_grouped_fwd, _grouped_bwd)


def _grouped_ragged(lhs, rhs, tile_group, n_tiles, tile_rows):
    """The same product by ``jax.lax.ragged_dot`` (XLA's own lowering): the
    groups' padded sizes are counted back from the tiles; rows past them
    come out zero."""
    in_use = jnp.arange(tile_group.shape[0]) < n_tiles[0]
    sizes = tile_rows * jnp.sum(
        jnp.logical_and(tile_group[:, None] == jnp.arange(rhs.shape[0])[None, :],
                        in_use[:, None]), axis=0, dtype=jnp.int32)
    return jax.lax.ragged_dot(lhs, rhs.transpose(0, 2, 1), sizes)


def grouped_matmul(lhs, rhs, tile_group, n_tiles, tile_rows: int = TILE_ROWS,
                   impl: str = "auto"):
    """``lhs (R, K)`` times ``rhs (G, N, K)`` by group, weights laid out
    ``(out, in)`` as ``nn.Dense`` has them: ``out (R, N)``. Differentiable in
    ``lhs`` and ``rhs``; see the module docstring for the row layout.

    ``impl``: "auto" takes the Pallas kernels on a TPU and
    ``jax.lax.ragged_dot`` elsewhere (as ``dot_product_attention`` takes the
    XLA path off the chip); "pallas" / "ragged" force one."""
    if impl == "auto":
        impl = "ragged" if _interpret_for(lhs) else "pallas"
    if impl == "ragged":
        return _grouped_ragged(lhs, rhs, tile_group, n_tiles, tile_rows)
    return _grouped_pallas(lhs, rhs, tile_group, n_tiles, tile_rows)
