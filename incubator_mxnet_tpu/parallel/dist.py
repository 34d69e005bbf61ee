"""Multi-host runtime initialization + the membership-aware helpers.

Reference counterpart: ``tools/launch.py`` + dmlc tracker, which spawned the
ps-lite scheduler/server/worker processes and wired them with ``DMLC_ROLE`` /
``DMLC_PS_ROOT_URI`` / ``DMLC_NUM_WORKER`` env vars (SURVEY §2.5). In the
multi-controller JAX model every host runs the same program;
``jax.distributed.initialize`` plays the scheduler's role (rendezvous at the
coordinator address), after which ``jax.devices()`` spans the whole pod and
every mesh built from it is global. There are no server processes — gradient
exchange is XLA collectives inside the compiled step.

Rebuilt for the elastic control plane (:mod:`.elastic`): initialization now
*banks membership* — after the rendezvous and the first collective-ledger
crosscheck, the heartbeat lease daemon starts (``MXTPU_ELASTIC=1``), so a
host that dies later is a detected loss with a flight bundle, not a pod
wedged inside a collective. Three helpers became load-bearing across the
runtime:

- :func:`is_primary` — THE host-0 election every persistent side effect
  consults (checkpoint manifest commit, shared telemetry paths, artifact
  uploads): collectives must not diverge across hosts, filesystem effects
  must (the MX902 invariant).
- :func:`world` — ``(process_index, process_count)`` without initializing
  a backend, the pair the checkpoint manifest protocol and the data-shard
  view key on.
- :func:`process_namespace` — the per-process token (``"p<idx>"``) that
  namespaces telemetry JSONL files and flight-bundle directories, so every
  host keeps its own forensics with zero shared-file races.

Env-var compatibility: if the dmlc-style vars are present they are mapped
onto the JAX rendezvous so reference launch scripts keep working:

- ``DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT`` → coordinator_address
- ``DMLC_NUM_WORKER``                   → num_processes
- ``DMLC_WORKER_ID``                    → process_id
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax

from . import elastic

_INITIALIZED = [False]


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids=None) -> None:
    """Rendezvous this process into the global runtime, crosscheck the
    collective-schedule ledger, and bank elastic membership. No-op when
    single-process (the common single-host case) or already initialized."""
    if _INITIALIZED[0]:
        return
    if coordinator_address is None:
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
        if uri:
            coordinator_address = f"{uri}:{port}"
    if num_processes is None and "DMLC_NUM_WORKER" in os.environ:
        num_processes = int(os.environ["DMLC_NUM_WORKER"])
    if process_id is None and "DMLC_WORKER_ID" in os.environ:
        process_id = int(os.environ["DMLC_WORKER_ID"])
    if coordinator_address is None and num_processes in (None, 1):
        _INITIALIZED[0] = True  # single-process: nothing to do
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    _INITIALIZED[0] = True
    # first collective-ledger crosscheck the moment the coordination
    # service exists: validates every process reached the same rendezvous
    # (and, on restarts, that restored fingerprint tables agree) before
    # the first real collective can wedge the pod. One env read when the
    # ledger is off.
    from ..telemetry import collective_ledger
    collective_ledger.crosscheck("dist.initialize")
    # membership becomes explicit the moment the pod exists: every
    # process banks a heartbeat lease, and a host that dies from here on
    # is a detected loss (flight bundle + HostLossError), never a silent
    # collective hang. One env read when elastic is off.
    elastic.start()


def finalize() -> None:
    elastic.stop()
    if _INITIALIZED[0]:
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
        _INITIALIZED[0] = False


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def world() -> Tuple[int, int]:
    """``(process_index, process_count)`` from the coordination-service
    state — readable before/without a backend (``(0, 1)`` outside a
    multi-host run), with the dmlc launcher vars as the pre-rendezvous
    fallback so the checkpoint/telemetry layers see a consistent answer
    at import time. The pair the manifest commit protocol, the data
    shard view, and the telemetry namespacing key on."""
    # a private jax module: an import or attribute error here means the
    # installed jax moved it, and must be seen (a silent env fallback
    # would make every host of a pod believe it is host 0 of 1)
    from jax._src.distributed import global_state
    if global_state.client is not None:
        return (int(global_state.process_id or 0),
                int(global_state.num_processes or 1))
    try:
        idx = int(os.environ.get("DMLC_WORKER_ID", "0") or 0)
        n = int(os.environ.get("DMLC_NUM_WORKER", "1") or 1)
    except ValueError:
        return 0, 1
    return idx, max(n, 1)


def is_primary() -> bool:
    """True on the elected writer host (process 0) — THE election every
    persistent side effect (checkpoint saves, telemetry sinks, artifact
    uploads) must consult in a multi-host run (the MX902 invariant:
    collectives must not diverge across hosts, filesystem effects must).

    Reads the coordination-service state directly so it never initializes
    a backend from a telemetry code path; falls back to the dmlc-style
    ``DMLC_WORKER_ID`` before rendezvous so launch scripts see a
    consistent answer at import time. Single-process runs are always
    primary."""
    return world()[0] == 0


def process_namespace() -> str:
    """The per-process namespacing token for persistent telemetry paths:
    ``""`` single-process (every existing single-host path is untouched),
    ``"p<index>"`` in a multi-host run. ``telemetry.flight`` appends it
    to the bundle directory and ``telemetry.export.JsonlSink`` folds it
    into non-primary stream names, so N hosts write N disjoint files —
    per-host forensics with zero shared-file races, and the primary's
    paths stay exactly where a single-host operator expects them."""
    idx, n = world()
    return f"p{idx}" if n > 1 else ""
