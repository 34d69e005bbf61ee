"""Named device meshes.

Reference counterpart: the *topology* side of the kvstore backends — the GPU
tree in ``src/kvstore/comm_tree.h (CommDeviceTree)`` and ps-lite's
scheduler/server/worker role map (``3rdparty/ps-lite/src/postoffice.cc``).
On TPU the topology is a first-class compiler input: a
:class:`jax.sharding.Mesh` whose named axes carry the parallelism meaning.

Axis convention (all optional, size-1 axes are free):

======  =======================================
``dp``  data parallelism (batch dim)
``tp``  tensor/model parallelism (hidden dims)
``pp``  pipeline parallelism (layer stages)
``sp``  sequence/context parallelism (ring attention)
``ep``  expert parallelism (MoE expert dim)
======  =======================================

Collectives ride ICI when the mesh is built from
``mesh_utils.create_device_mesh`` (which lays contiguous axes onto the torus)
and DCN across slices — the "collectives ride ICI, not DCN" rule is encoded
by putting ``dp`` outermost (slowest/DCN-most) and ``tp``/``sp`` innermost.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as onp
from jax.sharding import Mesh

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_EP = "ep"

#: canonical outer→inner ordering: dp over DCN/outer ICI, tp/sp innermost
#: (highest-bandwidth ICI neighbours), matching the scaling-book recipe.
CANONICAL_ORDER = (AXIS_DP, AXIS_PP, AXIS_EP, AXIS_SP, AXIS_TP)

_DEFAULT: List[Optional[Mesh]] = [None]


@dataclass
class MeshConfig:
    """Declarative mesh spec. Unset axes default to 1; one axis may be -1
    meaning "all remaining devices" (like a reshape wildcard)."""

    dp: int = -1
    tp: int = 1
    pp: int = 1
    sp: int = 1
    ep: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {AXIS_DP: self.dp, AXIS_TP: self.tp, AXIS_PP: self.pp,
                 AXIS_SP: self.sp, AXIS_EP: self.ep}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"only one axis may be -1, got {wild}")
        known = 1
        for k, v in sizes.items():
            if v != -1:
                if v <= 0:
                    raise ValueError(f"axis {k} must be positive or -1, got {v}")
                known *= v
        if wild:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}")
            sizes[wild[0]] = n_devices // known
        else:
            if known != n_devices:
                raise ValueError(
                    f"mesh axes product {known} != device count {n_devices}")
        return sizes


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None, **axes) -> Mesh:
    """Build a named Mesh. ``make_mesh(dp=2, tp=4)`` or with a MeshConfig.

    Axes are laid out in :data:`CANONICAL_ORDER`; on TPU devices the
    device order comes from ``mesh_utils.create_device_mesh`` so inner axes
    land on ICI neighbours (it raises where it cannot lay the mesh out);
    other platforms take the devices in list order.
    """
    if config is None:
        config = MeshConfig(**{**dict(dp=-1), **axes}) if axes else MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in CANONICAL_ORDER)
    if devices[0].platform == "tpu":
        # the torus-aware layout; a failure is an error, never a silent
        # reshape that puts tp/sp neighbours on distant chips
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        # virtual CPU devices have no topology: list order is the layout
        dev_array = onp.array(devices).reshape(shape)
    return Mesh(dev_array, CANONICAL_ORDER)


def local_mesh(**axes) -> Mesh:
    """Mesh over this process's addressable devices only."""
    return make_mesh(devices=jax.local_devices(), **axes)


def default_mesh() -> Mesh:
    """The process-wide mesh (lazily a pure-DP mesh over all devices)."""
    if _DEFAULT[0] is None:
        _DEFAULT[0] = make_mesh()
    return _DEFAULT[0]


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    _DEFAULT[0] = mesh


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


# ---------------------------------------------------------------------------
# Active compute mesh: bound while a ShardedTrainer step (or any mesh-aware
# computation) is being TRACED, so ops can emit mesh-native collectives —
# e.g. dot_product_attention lowering to ring attention over ``sp``.
# ---------------------------------------------------------------------------
import threading as _threading

_ACTIVE = _threading.local()


class active_mesh:
    """Context manager binding the mesh visible to mesh-aware ops."""

    def __init__(self, mesh: Optional[Mesh]):
        self._mesh = mesh

    def __enter__(self):
        stack = getattr(_ACTIVE, "stack", None)
        if stack is None:
            stack = _ACTIVE.stack = []
        stack.append(self._mesh)
        return self._mesh

    def __exit__(self, *exc):
        _ACTIVE.stack.pop()


def current_active_mesh() -> Optional[Mesh]:
    stack = getattr(_ACTIVE, "stack", None)
    return stack[-1] if stack else None
