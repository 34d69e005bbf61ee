"""Device Context model.

TPU-native counterpart of ``include/mxnet/base.h (mxnet::Context)`` and
``python/mxnet/context.py``. The north star (BASELINE.json) asks for TPU as a
first-class Context: ``mx.tpu()``. Under JAX, a Context maps onto a concrete
``jax.Device``; NDArray storage lives in PjRt device buffers addressed by it.

Differences from the reference, by design:
- ``gpu`` is accepted as an alias of the accelerator context so that reference
  scripts run unchanged on TPU machines (``mx.gpu(0)`` → accelerator 0).
- ``cpu_pinned``/``cpu_shared`` map to plain host CPU; PjRt manages pinned
  staging internally and DataLoader sharing uses OS shm at the io layer.
"""
from __future__ import annotations

import threading
from typing import List

import jax

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "cpu_shared",
    "current_context",
    "num_gpus",
    "num_tpus",
    "gpu_memory_info",
    "tpu_memory_info",
    "memory_stats",
]


def _devices_for(dev_type: str) -> List[jax.Device]:
    """Concrete jax devices backing a context type.

    device_id is WORKER-LOCAL (reference: each dmlc worker numbers its own
    GPUs from 0) — in a multi-controller run only this process's devices are
    addressable, so contexts index ``jax.local_devices()``.
    """
    all_devices = jax.local_devices()
    if dev_type in ("cpu", "cpu_pinned", "cpu_shared"):
        local_cpu = [d for d in all_devices if d.platform == "cpu"]
        if local_cpu:
            return local_cpu
        try:
            # default backend is an accelerator: this process's CPU devices
            # live on the cpu backend (still worker-local).
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            # CPU platform absent (rare) — fall back to default devices.
            return all_devices
    # accelerator types: tpu (and gpu as an alias)
    accel = [d for d in all_devices if d.platform not in ("cpu",)]
    if accel:
        return accel
    # No accelerator present: transparently fall back to CPU so that
    # device-parametrized test suites (SURVEY §4.1) run everywhere.
    return _devices_for("cpu") if _has_cpu() else all_devices


def _has_cpu() -> bool:
    try:
        jax.devices("cpu")
        return True
    except RuntimeError:
        return False


class Context:
    """A device context ``(device_type, device_id)``.

    Reference parity: ``mxnet::Context`` devtype ids (kCPU=1, kGPU=2,
    kCPUPinned=3, kCPUShared=5) plus the new first-class kTPU=6.
    """

    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}

    _default = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_id = device_type.device_id
            device_type = device_type.device_type
        if device_type not in self.devtype2id:
            raise MXNetError(f"Unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ----------------------------------------------------------
    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    # -- jax mapping -------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_for(self.device_type)
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: device_id out of range, only {len(devs)} "
                f"device(s) of this type are visible"
            )
        return devs[self.device_id]

    @property
    def is_accelerator(self) -> bool:
        return self.jax_device.platform != "cpu"

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default, "stack"):
            Context._default.stack = []
        Context._default.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()

    def empty_cache(self):
        """Reference parity: ``Context.empty_cache`` — PjRt manages pooling;
        trigger a GC of unreferenced buffers."""
        import gc

        gc.collect()

    def memory_stats(self) -> dict:
        """Memory stats for this context's device: PjRt
        ``device.memory_stats()`` where the backend exposes them
        (``source="pjrt"``), else the ``telemetry.memory`` ledger's view
        — live-array residency on the device, ``MXTPU_HBM_BUDGET`` as
        the limit (``source="ledger"``) — so reference scripts read
        real numbers on every backend instead of hitting the PjRt
        stub."""
        dev = self.jax_device
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            return dict(stats, source="pjrt")
        from .telemetry import memory as _memory
        used = _memory.device_bytes(dev)
        budget = _memory.hbm_budget()
        return {"bytes_in_use": used,
                "bytes_limit": budget if budget else used,
                "source": "ledger"}


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of the accelerator context (reference scripts using ``mx.gpu``
    transparently target TPU here)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(Context._default, "stack", None)
    if stack:
        return stack[-1]
    return cpu(0)


def context_for_device(device) -> Context:
    """Context addressing a concrete jax.Device (e.g. a mesh's first device)."""
    dev_type = "cpu" if device.platform == "cpu" else "tpu"
    peers = _devices_for(dev_type)
    try:
        idx = peers.index(device)
    except ValueError:
        idx = 0
    return Context(dev_type, idx)


def num_gpus() -> int:
    """Number of accelerator devices visible (alias surface)."""
    return num_tpus()


def gpu_memory_info(device_id: int = 0):
    """``(free, total)`` bytes on the accelerator, reference
    ``python/mxnet/context.py (gpu_memory_info)`` / C API
    ``MXGetGPUMemoryInformation64``. On TPU the numbers come from PjRt's
    ``memory_stats`` (HBM); alias name kept so reference scripts run
    unchanged. Backends exposing no PjRt stats (pure-CPU test runs) fall
    back to the ``telemetry.memory`` ledger — live-array residency as
    "used", ``MXTPU_HBM_BUDGET`` as "total" — so the call reports real
    numbers everywhere instead of raising on the PjRt stub."""
    return tpu_memory_info(device_id)


def memory_stats(device_id: int = 0) -> dict:
    """Module-level alias of :meth:`Context.memory_stats` for the
    accelerator context (reference scripts call it off ``mx.context``)."""
    return tpu(device_id).memory_stats()


def tpu_memory_info(device_id: int = 0):
    devs = _devices_for("tpu")
    if not 0 <= device_id < len(devs):
        raise MXNetError(
            f"device_id {device_id} out of range ({len(devs)} devices)")
    stats = None
    try:
        stats = devs[device_id].memory_stats()
    except Exception:
        stats = None
    if stats:
        total = stats.get("bytes_limit", 0)
        used = stats.get("bytes_in_use", 0)
        return (total - used, total)
    # no PjRt stats on this backend: the telemetry.memory ledger is the
    # source of truth — residency measured off jax.live_arrays(), the
    # configured HBM budget as capacity (used = total when unbudgeted,
    # i.e. free reads 0 rather than a made-up number)
    from .telemetry import memory as _memory
    used = _memory.device_bytes(devs[device_id])
    budget = _memory.hbm_budget()
    total = budget if budget else used
    return (max(total - used, 0), total)


def num_tpus() -> int:
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return len(devs)
