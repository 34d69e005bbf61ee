"""Collective verbs over mesh axes.

Reference counterpart: the NCCL verb surface in ``src/kvstore/kvstore_nccl.h``
(ncclAllReduce/ncclBcast) and the device-to-device reduce in
``src/kvstore/comm.h (CommDevice::Reduce/Broadcast)``. Here each verb is the
XLA collective primitive, usable inside ``shard_map``/``pjit`` regions where
the named axis is bound; XLA lowers them onto ICI rings/trees automatically
(the hand-written PCIe tree in comm_tree.h has no equivalent to maintain).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec
from jax import shard_map as _raw_shard_map


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off: the
    per-shard bodies here (ring attention, MoE dispatch, the pipeline
    schedule) return values replicated by construction."""
    return _raw_shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)


P = PartitionSpec

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "broadcast",
           "ppermute", "all_to_all", "axis_index", "axis_size", "psum_scatter"]


def all_reduce(x, axis: Union[str, Sequence[str]], op: str = "sum"):
    """In-shard_map all-reduce (``ncclAllReduce`` parity)."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op {op!r}")


def all_gather(x, axis: Union[str, Sequence[str]], *, tiled: bool = True,
               gather_axis: int = 0):
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: Union[str, Sequence[str]], *, scatter_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


psum_scatter = reduce_scatter


def broadcast(x, axis: str, root: int = 0):
    """Every shard takes the root shard's value (``ncclBcast`` parity)."""
    full = lax.all_gather(x, axis, axis=0, tiled=False)
    return full[root]


def ppermute(x, axis: str, perm: Sequence[tuple]):
    return lax.ppermute(x, axis, perm=perm)


def all_to_all(x, axis: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.psum(1, axis)


# ----------------------------------------------------------------------
# Host-level convenience: run one collective over sharded arrays outside any
# traced region (the kvstore eager path uses these).
# ----------------------------------------------------------------------
def _reduce_fn(mesh: Mesh, axis: str, op: str, spec: PartitionSpec):
    key = (mesh, axis, op, spec)
    fn = _REDUCE_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map(lambda v: all_reduce(v, axis, op), mesh=mesh,
                               in_specs=(spec,), out_specs=spec))
        _REDUCE_CACHE[key] = fn
    return fn


_REDUCE_CACHE: dict = {}


def run_all_reduce(mesh: Mesh, x: jax.Array, axis: str = "dp", op: str = "sum",
                   spec: Optional[PartitionSpec] = None) -> jax.Array:
    """Eager all-reduce of a sharded array over ``axis``; other mesh axes
    pass through. ``spec`` is the array's PartitionSpec if known. Compiled
    executables are cached per (mesh, axis, op, spec) — the analog of the
    reference kvstore reusing its comm buffers across pushes."""
    spec = spec if spec is not None else P()
    return _reduce_fn(mesh, axis, op, spec)(x)
