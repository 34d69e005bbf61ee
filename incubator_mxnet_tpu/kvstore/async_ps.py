"""Asynchronous parameter server — kvstore type ``dist_async``.

Reference counterpart: ``src/kvstore/kvstore_dist_server.h``
(``DataHandleEx`` async branch over ps-lite): each worker's push is handled
IMMEDIATELY in arrival order — no cross-worker barrier — and pull returns
whatever the server holds right now. Gradient staleness is traded for
throughput; convergence analysis is the user's problem (same contract as
the reference).

TPU-native position: the COMPILED training path stays on XLA collectives
(``dist_sync``) — every XLA collective is a synchronization point by
construction, so async semantics cannot ride one. Exactly like the
reference, whose ps-lite is host-side networking beside the device kernels,
the async store is host-side networking beside the XLA step: a TCP
parameter server thread on rank 0, length-prefixed pickled messages, pushes
handled under a store lock in arrival order. ps-lite's scheduler/van roles
collapse to one listening socket because the worker set is fixed at launch
(DMLC_* env, SURVEY §2.5).

Semantics, mirroring :class:`~incubator_mxnet_tpu.kvstore.KVStore`:

- no server optimizer: ``push`` REPLACES the key's merged value (each push
  is its own merge, as in the sync store); concurrent workers interleave
  latest-wins — the async staleness contract. ``pull`` reads the latest
  push (or the init value). This is what ``gluon.Trainer``'s
  push-grad/pull-merged step consumes.
- with ``set_optimizer`` (shipped pickled, the reference's server-side
  ``DataHandleEx`` update): every push updates the WEIGHTS immediately and
  ``pull`` returns them — update-on-kvstore, per-arrival.

Fault tolerance (``mx.fault`` wiring — the reference client died on the
first socket error):

- the client survives connection loss: every call runs under an
  env-tunable :class:`~incubator_mxnet_tpu.fault.retry.RetryPolicy`
  (``MXNET_KVSTORE_RETRIES`` / ``MXNET_KVSTORE_RETRY_DELAY``) that
  reconnects with exponential backoff and resends; the per-op socket
  timeout comes from ``MXNET_KVSTORE_TIMEOUT`` (default 60s). Exhaustion
  raises :class:`MXNetError` carrying the op + key, never a bare
  ``ConnectionError``.
- resends are safe because pushes are *versioned*: each client stamps a
  monotonically increasing version per push and the server remembers the
  last version applied per (worker, key) — a retry of a push whose first
  copy DID land (the reply was what got lost) is acknowledged without
  re-applying, so server-side optimizer updates are exactly-once.
- the server shuts down gracefully (``stop(checkpoint=...)``) and a new
  one restarts from that checkpoint on the same port
  (``AsyncPSServer(restore=...)``) — weights, merged buffers, optimizer
  state, and the applied-version table all survive.
- chaos hooks (``fault.inject``): ``kv_drop`` severs the client socket
  before a call, ``kv_delay`` stalls it — the seeded harness drives the
  full reconnect path in tests.
"""
from __future__ import annotations

import itertools
import os
import pickle
import socket
import struct
import threading
import time
from typing import Dict, Optional

import numpy as onp

from ..base import MXNetError
from ..fault import inject as _inject
from ..fault.retry import RetryExhausted, RetryPolicy
from ..lockcheck import make_lock
from ..ndarray import NDArray
from ..telemetry import events as _tele
from ..telemetry import metrics as _tmetrics
from ..telemetry import trace as _trace
from . import GradientCompressionMixin, KVStoreBase

__all__ = ["AsyncPSServer", "AsyncKVStore"]

_LEN = struct.Struct("<Q")


def _io_timeout() -> float:
    """Per-socket-op timeout (seconds) — MXNET_KVSTORE_TIMEOUT, default 60.
    Read per connection so tests/jobs can retune without reimporting."""
    try:
        return float(os.environ.get("MXNET_KVSTORE_TIMEOUT", "60"))
    except ValueError as e:
        raise MXNetError(f"bad MXNET_KVSTORE_TIMEOUT: {e}") from e


def _send_msg(sock: socket.socket, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


def _recv_msg(sock: socket.socket):
    (n,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return pickle.loads(_recv_exact(sock, n))


class AsyncPSServer:
    """The rank-0 server: weights, latest-merged buffers, and an optional
    server-side optimizer applied per push in arrival order (DataHandleEx
    async semantics). One handler thread per worker connection; a single
    store lock serializes updates — the ordering guarantee the reference
    gets from ps-lite's per-key server queue."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 restore: Optional[str] = None):
        self._store: Dict = {}     # init values / optimizer-updated weights
        self._merged: Dict = {}    # latest pushed merge per key (no-opt mode)
        self._opt_states: Dict = {}
        self._optimizer = None
        self._lock = make_lock("AsyncPSServer._lock")
        self._push_count = 0
        #: (worker id, key) -> last applied push version: the resend-dedupe
        #: table that makes client retries exactly-once
        self._applied: Dict = {}
        if restore is not None:
            self._restore(restore)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._stop = threading.Event()
        self._conns: set = set()       # live worker connections (for stop)
        self._handlers: list = []      # their threads (stop joins them)
        self._thread = threading.Thread(target=self._serve,
                                        name="mx-kvstore-ps-accept",
                                        daemon=True)
        # attribute the server's parameter table on the device-memory
        # ledger (host-side numpy here, but it is the same weights a
        # device store pins — the "kvstore" site of telemetry.memory)
        from ..telemetry import memory as _tele_memory
        self._mem_unregister = _tele_memory.register_site(
            "kvstore", self._resident_bytes)
        self._thread.start()

    def _resident_bytes(self) -> int:
        with self._lock:
            return sum(int(getattr(v, "nbytes", 0) or 0)
                       for table in (self._store, self._merged)
                       for v in table.values())

    # -- message handling ---------------------------------------------------
    def _serve(self) -> None:
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            with self._lock:
                self._conns.add(conn)
            t = threading.Thread(
                target=self._handle, args=(conn,),
                name=f"mx-kvstore-ps-handler-{conn.fileno()}",
                daemon=True)
            with self._lock:
                self._handlers = [h for h in self._handlers
                                  if h.is_alive()] + [t]
            t.start()
        self._sock.close()

    def _handle(self, conn: socket.socket) -> None:
        try:
            while True:
                msg = _recv_msg(conn)
                stop = False
                try:
                    resp = self._dispatch(msg)
                    stop = msg[0] == "stop"
                except Exception as e:  # reply, keep the connection alive
                    resp = ("err", f"{type(e).__name__}: {e}")
                _send_msg(conn, resp)
                if stop:
                    self._stop.set()
                    return
        except (ConnectionError, EOFError, OSError):
            return
        finally:
            with self._lock:
                self._conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _dispatch(self, msg):
        # a trailing {"_meta": 1, ...} dict is the carried trace context
        # (see _Client.call): pop it, resume the worker's trace, and span
        # the server-side handling — the client→PS hop becomes one
        # stitched edge instead of a correlation cliff, and a slow or
        # deduped resend is attributable to the training step that
        # issued the push
        if isinstance(msg[-1], dict) and msg[-1].get("_meta"):
            meta, msg = msg[-1], msg[:-1]
            key = msg[1] if len(msg) > 1 and not isinstance(
                msg[1], (bytes, bytearray)) else None
            step = meta.get("step")
            with _trace.use(_trace.from_wire(meta.get("trace"))), \
                    _trace.span(f"kvstore.server.{msg[0]}", kind="server",
                                key=key, step=step):
                if step is None:
                    return self._dispatch_inner(msg)
                # the carried step binds server-side events (resend,
                # errors) to the issuing step, same as the span above
                with _tele.step_scope(step):
                    return self._dispatch_inner(msg)
        return self._dispatch_inner(msg)

    def _dispatch_inner(self, msg):
        op = msg[0]
        if op == "init":
            _, key, arr = msg
            with self._lock:
                self._store.setdefault(key, onp.array(arr))
            return ("ok",)
        if op == "push":
            # ("push", key, arr) legacy or ("push", key, arr, wid, version)
            key, arr = msg[1], msg[2]
            wid, ver = (msg[3], msg[4]) if len(msg) >= 5 else (None, None)
            deduped = False
            with self._lock:
                if wid is not None:
                    if self._applied.get((wid, key), 0) >= ver:
                        deduped = True
                    else:
                        self._applied[(wid, key)] = ver
                if not deduped:
                    self._apply(key, onp.asarray(arr))
                    self._push_count += 1
            if deduped:
                # resend of an applied push: ack only — and say so on
                # the timeline (trace-correlated when the push carried
                # context), because an exactly-once dedupe firing is
                # the visible tail of a lost reply or a slow link.
                # Emitted OUTSIDE self._lock: subscriber fan-out can do
                # file I/O (the JSONL sink) and must not serialize every
                # concurrent push/pull behind it
                _tele.emit("kvstore.resend", key=key, worker=wid,
                           version=ver)
            return ("ok",)
        if op == "pull":
            _, key = msg
            with self._lock:
                if self._optimizer is not None:
                    val = self._store.get(key)
                else:
                    val = self._merged.get(key, self._store.get(key))
            if val is None:
                return ("err", f"key {key!r} not initialized")
            return ("ok", val)
        if op == "set_optimizer":
            _, blob = msg
            with self._lock:
                self._optimizer = pickle.loads(blob)
                self._opt_states.clear()
            return ("ok",)
        if op == "stats":
            with self._lock:
                return ("ok", {"pushes": self._push_count,
                               "keys": len(self._store)})
        if op == "stop":
            return ("ok",)
        return ("err", f"unknown op {op!r}")

    def _apply(self, key, grad: onp.ndarray) -> None:
        """Arrival-order push handling (lock held)."""
        if key not in self._store:
            raise MXNetError(f"push before init for key {key!r}")
        if self._optimizer is None:
            self._merged[key] = grad  # per-push merge; latest wins
            return
        w = NDArray(self._store[key])
        g = NDArray(grad)
        idx = key if isinstance(key, int) else abs(hash(key)) % (2 ** 31)
        state = self._opt_states.get(key)
        if state is None:
            state = self._optimizer.create_state(idx, w)
        self._opt_states[key] = self._optimizer.update(idx, w, g, state)
        self._store[key] = w.asnumpy()

    # -- graceful shutdown / restart ----------------------------------------
    def state_dict(self) -> dict:
        """Host-side snapshot of everything a restarted server needs."""
        import jax
        with self._lock:
            return {
                "format": 1,
                "store": {k: onp.asarray(v) for k, v in self._store.items()},
                "merged": {k: onp.asarray(v)
                           for k, v in self._merged.items()},
                "opt_states": {k: jax.tree.map(onp.asarray, st)
                               for k, st in self._opt_states.items()},
                "optimizer": (pickle.dumps(self._optimizer)
                              if self._optimizer is not None else None),
                "push_count": self._push_count,
                "applied": dict(self._applied),
            }

    def save_checkpoint(self, path: str) -> None:
        """Atomically persist :meth:`state_dict` (temp + ``os.replace``)."""
        blob = pickle.dumps(self.state_dict(),
                            protocol=pickle.HIGHEST_PROTOCOL)
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            # intentional single-writer divergence: exactly one process
            # (rank 0) hosts the AsyncPSServer, so this save never races
            # a peer — the election happened at server construction
            with open(tmp, "wb") as f:  # mxlint: disable=MX902
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # mxlint: disable=MX902
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _restore(self, path: str) -> None:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if blob.get("format") != 1:
            raise MXNetError(f"{path}: unknown PS checkpoint format "
                             f"{blob.get('format')!r}")
        self._store = dict(blob["store"])
        self._merged = dict(blob["merged"])
        self._opt_states = dict(blob["opt_states"])
        self._optimizer = (pickle.loads(blob["optimizer"])
                           if blob["optimizer"] is not None else None)
        self._push_count = int(blob["push_count"])
        self._applied = dict(blob["applied"])

    def stop(self, checkpoint: Optional[str] = None) -> None:
        """Graceful shutdown: optionally checkpoint the store first, then
        stop accepting, join the accept loop, close the workers'
        connections and join their handler threads."""
        if checkpoint is not None:
            self.save_checkpoint(checkpoint)
        self._stop.set()
        self._thread.join(timeout=2)
        # Close live worker connections so clients observe the shutdown and
        # fail over (retry/backoff) to a restarted server instead of
        # talking to this one's zombie handler threads.
        with self._lock:
            conns, self._conns = list(self._conns), set()
            handlers, self._handlers = self._handlers, []
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        # A handler still inside a reply (the server-side optimizer runs
        # jax) when the interpreter tears down is a daemon thread killed
        # inside native frames: the process aborts ("FATAL: exception not
        # rethrown", rc -6) after its work was done. Wait for them; each
        # ends at its closed connection.
        me = threading.current_thread()
        for t in handlers:
            if t is not me:
                t.join(timeout=5)


class _Client:
    """Reconnecting PS client. Every call retries under the env retry
    policy; a lost connection is re-established with exponential backoff
    before the resend (safe for every op — pushes are versioned, the rest
    are idempotent reads/replaces)."""

    def __init__(self, host: str, port: int, timeout: float = 30.0,
                 retry: Optional[RetryPolicy] = None):
        self._host, self._port = host, port
        self._retry = retry or RetryPolicy.from_env()
        self._sock: Optional[socket.socket] = None
        self._ver = itertools.count(1)
        # registry handles resolved ONCE — per-op resolution would take
        # the registry lock on every push/pull of every tensor
        self._m = {
            "push": _tmetrics.counter("mxtpu_kvstore_push_total",
                                      "kvstore push calls completed"),
            "pull": _tmetrics.counter("mxtpu_kvstore_pull_total",
                                      "kvstore pull calls completed"),
            "retry": _tmetrics.counter(
                "mxtpu_kvstore_retries_total",
                "kvstore reconnect/resend attempts"),
            "reconnect": _tmetrics.counter(
                "mxtpu_kvstore_reconnects_total",
                "kvstore client reconnections"),
        }
        deadline = time.time() + timeout
        last = None
        while True:
            try:
                self._connect()
                break
            except OSError as e:  # server not up yet: retry (worker launch
                last = e           # order is unordered, like ps-lite's van)
                if time.time() > deadline:
                    raise MXNetError(
                        f"cannot reach async PS at {host}:{port}: {last}")
                time.sleep(0.1)
        self._lock = make_lock("_Client._lock")

    def _connect(self) -> None:
        self.close()
        self._sock = socket.create_connection((self._host, self._port),
                                              timeout=5.0)
        self._sock.settimeout(_io_timeout())

    def call(self, *msg):
        op = msg[0]
        key = msg[1] if len(msg) > 1 and not isinstance(
            msg[1], (bytes, bytearray)) else None
        # the trace context rides the wire as a trailing meta element the
        # server pops off — push/pull only (init/set_optimizer/stats are
        # setup, not steady state). Ids propagate whenever a context or
        # step is active — an UNSAMPLED trace still carries its ids (the
        # documented contract: sampling gates recording, not
        # propagation, so the server's resend/timeline events stay
        # step- and trace-attributed for unsampled traffic) — while the
        # client span that RECORDS the hop only opens when sampled
        ctx = _trace.current()
        sp = None
        if op in ("push", "pull"):
            step = _tele.current_step()
            if ctx is not None and ctx.sampled:
                sp = _trace.start_span(f"kvstore.{op}", kind="client",
                                       key=key)
            wire = _trace.to_wire(sp.ctx if sp is not None else ctx)
            if wire is not None or step is not None:
                msg = msg + ({"_meta": 1, "trace": wire, "step": step},)
        try:
            return self._call_locked(op, key, msg, sp)
        except BaseException as e:
            if sp is not None:
                sp.finish(error=type(e).__name__)
            raise
        finally:
            if sp is not None:
                sp.finish()

    def _call_locked(self, op, key, msg, sp):
        # the client lock deliberately serializes the SOCKET (one
        # request/reply in flight per connection, like ps-lite's van);
        # blocking I/O under it is the design
        with self._lock:  # mxlint: disable=MX803
            if op == "push" and len(msg) >= 5 and msg[4] is None:
                # stamp the version under the SAME lock that serializes
                # sends: assigned any earlier, concurrent pushers could
                # deliver versions out of order and the server's monotone
                # dedupe would drop real updates as resends
                msg = msg[:4] + (next(self._ver),) + msg[5:]
            if _inject.should("kv_drop"):   # chaos: sever before the call
                self.close()
            _inject.maybe_delay("kv_delay")

            def attempt():
                if self._sock is None:
                    self._connect()
                _send_msg(self._sock, msg)
                return _recv_msg(self._sock)

            def on_retry(n, exc):
                # reconnect + resend is the fault path worth a timeline
                # entry: a flapping PS shows up as a retry/reconnect
                # stream correlated with the training step
                _tele.emit("kvstore", severity="warning", op="retry",
                           target_op=op, key=key, attempt=n,
                           error=f"{type(exc).__name__}: {exc}")
                self._m["retry"].inc()
                if sp is not None:   # the span tells the resend story
                    sp.attrs["retries"] = n
                self.close()   # force a fresh connection before resending
                self._connect()
                self._m["reconnect"].inc()

            try:
                resp = attempt()
            except self._retry.retry_on:
                self.close()
                from ..fault.retry import call_with_retry
                try:
                    resp = call_with_retry(
                        attempt, self._retry, on_retry=on_retry,
                        describe=f"async PS {op!r} (key {key!r}) at "
                                 f"{self._host}:{self._port}")
                except RetryExhausted as e:
                    self.close()
                    _tele.emit("kvstore", severity="error", op=op,
                               key=key, error=str(e.last))
                    raise MXNetError(str(e)) from e.last
        if resp[0] != "ok":
            raise MXNetError(
                f"async PS {op!r} (key {key!r}) failed: "
                + (resp[1] if len(resp) > 1 else "unknown server error"))
        if op in ("push", "pull"):
            _tele.emit("kvstore", op=op, key=key)
            self._m[op].inc()
        return resp[1] if len(resp) > 1 else None

    def close(self) -> None:
        if self._sock is None:
            return
        try:
            self._sock.close()
        except OSError:
            pass
        self._sock = None


class AsyncKVStore(GradientCompressionMixin, KVStoreBase):
    """``mx.kv.create('dist_async')`` (reference: kvstore_dist.h async mode).

    Rank 0 hosts :class:`AsyncPSServer`; every rank (including 0) talks to
    it through a socket client. ``push`` is handled at the server the
    moment it arrives — concurrent workers interleave in arrival order, and
    ``pull`` observes the freshest state with NO barrier anywhere. Worker
    topology comes from the dmlc-compatible env (``DMLC_NUM_WORKER`` /
    ``DMLC_WORKER_ID`` / ``DMLC_PS_ROOT_URI``, SURVEY §2.5); single-process
    use spins up a local server — same semantics, one worker.
    """

    #: offset from the rendezvous port so the PS socket never collides with
    #: the jax.distributed coordinator sharing DMLC_PS_ROOT_URI
    PORT_OFFSET = 17

    def __init__(self, optimizer=None):
        self._rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
        self._num = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        self._server: Optional[AsyncPSServer] = None
        self._compression: Dict = {}
        self._residuals: Dict = {}
        if uri and self._num > 1:
            port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9000")) + \
                self.PORT_OFFSET
            if self._rank == 0:
                self._server = AsyncPSServer(host="0.0.0.0", port=port)
            self._client = _Client(uri, port)
        else:
            self._server = AsyncPSServer()
            self._client = _Client("127.0.0.1", self._server.port)
        #: identity stamped on every push (the client adds the monotone
        #: version) so server-side dedupe makes retried pushes exactly-once
        self._wid = f"{self._rank}:{os.getpid()}:{id(self):x}"
        if optimizer is not None:
            self.set_optimizer(optimizer)

    # -- identity -----------------------------------------------------------
    @property
    def type(self) -> str:
        return "dist_async"

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def num_workers(self) -> int:
        return self._num

    # -- core ops -----------------------------------------------------------
    def _keys(self, key):
        return key if isinstance(key, (list, tuple)) else [key]

    def _vals(self, key, value):
        if isinstance(key, (list, tuple)):
            if len(key) != len(value):
                raise MXNetError("key list and value list length mismatch")
            return list(value)
        return [value]

    def _merge(self, k, v) -> onp.ndarray:
        """Device-local replica sum (per-replica compression first, exactly
        as KVStore.push orders it); the cross-WORKER story is the server's
        arrival-order handling — no all-reduce, no barrier."""
        vlist = v if isinstance(v, (list, tuple)) else [v]
        parts = [self._compress(k, i, x._data) for i, x in enumerate(vlist)]
        total = parts[0]
        for x in parts[1:]:
            total = total + x.astype(total.dtype)
        return onp.asarray(total)

    def init(self, key, value):
        for k, v in zip(self._keys(key), self._vals(key, value)):
            if isinstance(v, (list, tuple)):
                v = v[0]
            self._client.call("init", k, v.asnumpy())

    def push(self, key, value, priority: int = 0):
        for k, v in zip(self._keys(key), self._vals(key, value)):
            self._client.call("push", k, self._merge(k, v),
                              self._wid, None)  # client stamps the version

    def pull(self, key, out=None, priority: int = 0,
             ignore_sparse: bool = True):
        results = [NDArray(self._client.call("pull", k))
                   for k in self._keys(key)]
        if out is not None:
            outs = out if isinstance(key, (list, tuple)) else [out]
            for o, r in zip(outs, results):
                for oo in (o if isinstance(o, (list, tuple)) else [o]):
                    oo._set_data(r._data.astype(oo.dtype))
            return out
        return results if isinstance(key, (list, tuple)) else results[0]

    def set_optimizer(self, optimizer) -> None:
        """Ship the optimizer to the server (reference: the pickled
        optimizer sent through ps-lite's control channel for server-side
        DataHandleEx updates). Accepts a name string like the sync store."""
        from .. import optimizer as opt_mod
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer)
        self._client.call("set_optimizer", pickle.dumps(optimizer))

    def stats(self) -> dict:
        return self._client.call("stats")

    def close(self) -> None:
        self._client.close()
        if self._server is not None:
            self._server.stop()
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
