"""``mx.io`` — data iterators.

Reference parity: ``include/mxnet/io.h`` (``IIterator<DataBatch>``) and
``src/io/`` (SURVEY §2.6): ``NDArrayIter``, ``CSVIter``, ``MNISTIter``,
``ImageRecordIter``, ``PrefetchingIter``, ``ResizeIter``, plus the
``DataBatch``/``DataDesc`` records the Module API consumes.

TPU-native design: iterators produce host-side batches (numpy-backed
NDArrays); the device hop happens once per step inside the compiled path
(``ShardedTrainer``/Trainer) — matching the reference's pinned-staging +
priority-copy-thread overlap, which PjRt performs internally. The decode/
augment pipeline of ``ImageRecordIter`` runs in a thread pool
(``ThreadedIter`` parity).
"""
from __future__ import annotations

import os
import struct
import threading
import queue as _queue
from collections import namedtuple
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as onp

from ..base import MXNetError
from ..ndarray import NDArray, array
from .. import recordio as rec_mod

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "CSVIter",
           "LibSVMIter", "MNISTIter", "ImageRecordIter", "PrefetchingIter",
           "PrefetchIter", "ResizeIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape", "dtype", "layout"])):
    def __new__(cls, name, shape, dtype=onp.float32, layout="NCHW"):
        return super().__new__(cls, name, tuple(shape), dtype, layout)

    @staticmethod
    def get_batch_axis(layout: Optional[str]) -> int:
        return 0 if layout is None else layout.find("N")


class DataBatch:
    def __init__(self, data, label=None, pad: int = 0, index=None,
                 provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __repr__(self):
        shapes = [getattr(d, "shape", None) for d in (self.data or [])]
        return f"DataBatch: data shapes {shapes} pad {self.pad}"


class DataIter:
    """Iterator base (reference: io.DataIter)."""

    def __init__(self, batch_size: int = 0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self) -> DataBatch:
        if self.iter_next():
            return DataBatch(self.getdata(), self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self) -> bool:
        raise NotImplementedError

    def getdata(self):
        raise NotImplementedError

    def getlabel(self):
        raise NotImplementedError

    def getindex(self):
        return None

    def getpad(self) -> int:
        return 0


def _as_named_arrays(data, default_name: str):
    """Normalize array|list|dict into an ordered [(name, ndarray)] list."""
    if data is None:
        return []
    if isinstance(data, dict):
        items = list(data.items())
    elif isinstance(data, (list, tuple)):
        items = [(f"{default_name}" if i == 0 else f"{default_name}{i}", d)
                 for i, d in enumerate(data)]
    else:
        items = [(default_name, data)]
    out = []
    for name, d in items:
        if isinstance(d, NDArray):
            d = d.asnumpy()
        out.append((name, onp.asarray(d)))
    return out


class NDArrayIter(DataIter):
    """In-memory iterator (reference: io.NDArrayIter): shuffle,
    pad/discard/roll_over last-batch handling."""

    def __init__(self, data, label=None, batch_size: int = 1,
                 shuffle: bool = False, last_batch_handle: str = "pad",
                 data_name: str = "data", label_name: str = "softmax_label"):
        super().__init__(batch_size)
        self.data = _as_named_arrays(data, data_name)
        self.label = _as_named_arrays(label, label_name)
        self.num_data = self.data[0][1].shape[0] if self.data else 0
        for _, d in self.data + self.label:
            if d.shape[0] != self.num_data:
                raise MXNetError("all data/label arrays must share dim 0")
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.cursor = -batch_size
        self._order = onp.arange(self.num_data)
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(n, (self.batch_size,) + d.shape[1:], d.dtype)
                for n, d in self.data]

    @property
    def provide_label(self):
        return [DataDesc(n, (self.batch_size,) + d.shape[1:], d.dtype)
                for n, d in self.label]

    def reset(self):
        if self.shuffle:
            onp.random.shuffle(self._order)
        if self.last_batch_handle == "roll_over" and \
                0 < self.cursor < self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data)
        else:
            self.cursor = -self.batch_size

    def iter_next(self) -> bool:
        self.cursor += self.batch_size
        if self.last_batch_handle == "discard":
            return self.cursor + self.batch_size <= self.num_data
        return self.cursor < self.num_data

    def _slice(self, arrays):
        out = []
        for _, d in arrays:
            idx = self._order[max(0, self.cursor):self.cursor + self.batch_size]
            chunk = d[idx]
            if chunk.shape[0] < self.batch_size:  # pad by wrapping
                extra = self._order[:self.batch_size - chunk.shape[0]]
                chunk = onp.concatenate([chunk, d[extra]], axis=0)
            out.append(array(chunk))
        return out

    def getdata(self):
        return self._slice(self.data)

    def getlabel(self):
        return self._slice(self.label)

    def getpad(self) -> int:
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class CSVIter(NDArrayIter):
    """CSV-backed iterator (reference: src/io/iter_csv.cc)."""

    def __init__(self, data_csv: str, data_shape: Tuple[int, ...],
                 label_csv: Optional[str] = None, label_shape: Tuple[int, ...] = (1,),
                 batch_size: int = 1, **kwargs):
        data = onp.loadtxt(data_csv, delimiter=",", dtype=onp.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv:
            label = onp.loadtxt(label_csv, delimiter=",", dtype=onp.float32)
            label = label.reshape((-1,) + tuple(label_shape))
        super().__init__(data, label, batch_size=batch_size, **kwargs)


class LibSVMIter(NDArrayIter):
    """LibSVM-format iterator (reference: src/io/iter_libsvm.cc).

    Parses ``label idx:val idx:val ...`` lines. The reference yields CSR
    batches; on TPU sparse storage is a dense facade (SURVEY §7 sparse
    scoping), so features densify to ``(n, *data_shape)`` float32 — the
    iterator surface (provide_data/label, pad/shuffle semantics) matches.
    """

    def __init__(self, data_libsvm: str, data_shape: Tuple[int, ...],
                 label_libsvm: Optional[str] = None,
                 label_shape: Tuple[int, ...] = (1,),
                 batch_size: int = 1, **kwargs):
        feat_dim = int(onp.prod(data_shape))
        labels, rows, cols, vals = [], [], [], []
        with open(data_libsvm) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                labels.append(float(parts[0]))
                for tok in parts[1:]:
                    idx, val = tok.split(":")
                    rows.append(len(labels) - 1)
                    cols.append(int(idx))
                    vals.append(float(val))
        n = len(labels)
        data = onp.zeros((n, feat_dim), dtype=onp.float32)
        if rows:
            if max(cols) >= feat_dim or min(cols) < 0:
                raise MXNetError(
                    f"libsvm feature index out of range [0, {feat_dim}): "
                    f"[{min(cols)}, {max(cols)}]")
            data[rows, cols] = vals
        data = data.reshape((-1,) + tuple(data_shape))
        if label_libsvm:
            lab = []
            with open(label_libsvm) as f:
                for line in f:
                    if line.split():
                        lab.append([float(x) for x in line.split()])
            label = onp.asarray(lab, dtype=onp.float32)
            label = label.reshape((-1,) + tuple(label_shape))
        else:
            label = onp.asarray(labels, dtype=onp.float32)
        super().__init__(data, label, batch_size=batch_size, **kwargs)


class MNISTIter(NDArrayIter):
    """idx-format MNIST reader (reference: src/io/iter_mnist.cc)."""

    def __init__(self, image: str, label: str, batch_size: int = 128,
                 shuffle: bool = False, flat: bool = False, **kwargs):
        imgs = _read_idx_images(image)
        labs = _read_idx_labels(label)
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, 28, 28)
        super().__init__(imgs.astype(onp.float32) / 255.0,
                         labs.astype(onp.float32),
                         batch_size=batch_size, shuffle=shuffle,
                         label_name="softmax_label", **kwargs)


def _read_idx_images(path: str) -> onp.ndarray:
    import gzip
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        magic, n, rows, cols = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            raise MXNetError(f"{path} is not an MNIST image idx file")
        return onp.frombuffer(f.read(), dtype=onp.uint8).reshape(n, rows, cols)


def _read_idx_labels(path: str) -> onp.ndarray:
    import gzip
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rb") as f:
        magic, n = struct.unpack(">II", f.read(8))
        if magic != 2049:
            raise MXNetError(f"{path} is not an MNIST label idx file")
        return onp.frombuffer(f.read(), dtype=onp.uint8)


class ImageRecordIter(DataIter):
    """.rec image pipeline with threaded decode+augment
    (reference: src/io/iter_image_recordio_2.cc ImageRecordIOParser2).

    Supported aug params mirror the common reference set: resize,
    rand_crop, rand_mirror, data_shape, mean_r/g/b, std_r/g/b, shuffle.
    """

    def __init__(self, path_imgrec: str, data_shape: Tuple[int, int, int],
                 batch_size: int, path_imgidx: Optional[str] = None,
                 shuffle: bool = False, rand_crop: bool = False,
                 rand_mirror: bool = False, resize: int = -1,
                 mean_r: float = 0.0, mean_g: float = 0.0, mean_b: float = 0.0,
                 std_r: float = 1.0, std_g: float = 1.0, std_b: float = 1.0,
                 preprocess_threads: int = 4, round_batch: bool = True,
                 seed: int = 0, **kwargs):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape)
        self._rand_crop = rand_crop
        self._rand_mirror = rand_mirror
        self._resize = resize
        self._mean = onp.array([mean_r, mean_g, mean_b], onp.float32)
        self._std = onp.array([std_r, std_g, std_b], onp.float32)
        self._rng = onp.random.RandomState(seed)
        self._shuffle = shuffle
        self._threads = max(1, preprocess_threads)
        # Load the record offsets once; records decode lazily per batch.
        idx = path_imgidx or (path_imgrec[:-4] + ".idx")
        if os.path.isfile(idx):
            self._rec = rec_mod.MXIndexedRecordIO(idx, path_imgrec, "r")
            self._keys = list(self._rec.keys)
        else:
            self._rec = rec_mod.MXRecordIO(path_imgrec, "r")
            self._keys = None
            self._records = []
            while True:
                r = self._rec.read()
                if r is None:
                    break
                self._records.append(r)
        self._order = None
        self._pos = 0
        self.reset()

    def reset(self):
        n = len(self._keys) if self._keys is not None else len(self._records)
        self._order = onp.arange(n)
        if self._shuffle:
            self._rng.shuffle(self._order)
        self._pos = 0

    def _fetch(self, i: int) -> bytes:
        if self._keys is not None:
            return self._rec.read_idx(self._keys[i])
        return self._records[i]

    def _decode_one(self, raw: bytes):
        header, img = rec_mod.unpack_img(raw, iscolor=1)
        import cv2
        if self._resize > 0:
            h, w = img.shape[:2]
            scale = self._resize / min(h, w)
            img = cv2.resize(img, (int(w * scale + 0.5), int(h * scale + 0.5)))
        c, H, W = self.data_shape
        h, w = img.shape[:2]
        if self._rand_crop:
            # per-dimension: random offset where the image is larger, 0 where
            # it is smaller (the resize below fixes undersized dims)
            y = self._rng.randint(0, h - H + 1) if h > H else 0
            x = self._rng.randint(0, w - W + 1) if w > W else 0
        else:
            y, x = max(0, (h - H) // 2), max(0, (w - W) // 2)
        img = img[y:y + H, x:x + W]
        if img.shape[:2] != (H, W):
            img = cv2.resize(img, (W, H))
        img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB).astype(onp.float32)
        if self._rand_mirror and self._rng.rand() < 0.5:
            img = img[:, ::-1]
        img = (img - self._mean) / self._std
        label = header.label if onp.ndim(header.label) else float(header.label)
        return img.transpose(2, 0, 1), onp.float32(label)

    def iter_next(self) -> bool:
        return self._pos + self.batch_size <= len(self._order)

    def next(self) -> DataBatch:
        if not self.iter_next():
            raise StopIteration
        idxs = self._order[self._pos:self._pos + self.batch_size]
        self._pos += self.batch_size
        raws = [self._fetch(int(i)) for i in idxs]
        if self._threads > 1:
            from concurrent.futures import ThreadPoolExecutor
            if not hasattr(self, "_pool"):
                self._pool = ThreadPoolExecutor(self._threads)
            decoded = list(self._pool.map(self._decode_one, raws))
        else:
            decoded = [self._decode_one(r) for r in raws]
        data = onp.stack([d for d, _ in decoded])
        label = onp.stack([l for _, l in decoded])
        return DataBatch([array(data)], [array(label)], pad=0)

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]


class PrefetchingIter(DataIter):
    """Background-thread prefetch wrapper (reference: iter_prefetcher.h —
    the ThreadedIter overlap that hides decode latency behind compute)."""

    def __init__(self, iters, rename_data=None, rename_label=None,
                 prefetch_depth: int = 2):
        if not isinstance(iters, (list, tuple)):
            iters = [iters]
        if len(iters) != 1:
            raise MXNetError("PrefetchingIter here wraps a single iterator")
        self._it = iters[0]
        super().__init__(self._it.batch_size)
        self._depth = prefetch_depth
        self._queue: _queue.Queue = _queue.Queue(maxsize=prefetch_depth)
        self._worker = None
        self._gen = 0
        self._start()

    def _start(self):
        gen = self._gen
        q = self._queue

        def run():
            # A stale generation (reset() bumped self._gen) must stop touching
            # the shared underlying iterator and exit without the sentinel.
            done = False
            try:
                while gen == self._gen:
                    try:
                        b = self._it.next()
                    except StopIteration:
                        done = True
                        break
                    while gen == self._gen:
                        try:
                            q.put(b, timeout=0.05)
                            break
                        except _queue.Full:
                            continue
            finally:
                if done and gen == self._gen:
                    q.put(None)

        self._worker = threading.Thread(target=run, name="mx-io-prefetch",
                                        daemon=True)
        self._worker.start()

    def reset(self):
        self._gen += 1  # signal the old worker to exit
        try:
            while True:
                self._queue.get_nowait()
        except _queue.Empty:
            pass
        if self._worker is not None:
            self._worker.join(timeout=5)
        self._it.reset()
        self._queue = _queue.Queue(maxsize=self._depth)
        self._start()

    def next(self) -> DataBatch:
        b = self._queue.get()
        if b is None:
            raise StopIteration
        return b

    def iter_next(self) -> bool:
        raise MXNetError("PrefetchingIter supports iteration via next() only")

    @property
    def provide_data(self):
        return self._it.provide_data

    @property
    def provide_label(self):
        return self._it.provide_label


class PrefetchIter(DataIter):
    """Async double-buffered DEVICE prefetch over any :class:`DataIter`.

    Where :class:`PrefetchingIter` overlaps host-side decode with
    compute, this wrapper additionally runs a *placement* function on the
    worker thread — typically ``ShardedTrainer.place`` — so the
    host→device hop of batch N+1 (and N+2, with the default ``depth=2``
    double buffer) proceeds while the compiled step is executing batch N.
    Input placement never serializes with the step: the training loop's
    per-step host work drops to one queue pop::

        it = mx.io.PrefetchIter(
            base_iter, place=lambda b: trainer.place(*b.data, *b.label))
        for placed in it:
            trainer.step(*placed)

    ``place`` takes the wrapped iterator's :class:`DataBatch` and may
    return anything (default: the batch unchanged — pure async
    prefetch). Batches arrive strictly in the wrapped iterator's order.
    Every consumer-side queue pop is timed: the blocked portion is
    recorded as an ``io.wait`` profiler span, the ``mxtpu_io_wait_ms``
    histogram + ``mxtpu_io_queue_depth`` gauge, and (when the goodput
    ledger is on) the ``input_wait`` attribution bucket — so "the step
    is starving on input" is a measured, gated fact, testable end to
    end via the seeded ``slow_input`` chaos knob (``fault.inject``
    delays the producer).
    A ``place``/iterator exception is captured on the worker and
    re-raised from :meth:`next` — never swallowed. The worker is one
    named daemon thread (``mx-io-device-prefetch``, lockcheck/MX804
    conventions); :meth:`close` (or ``with`` exit) shuts it down and
    joins it, :meth:`reset` restarts the stream from the wrapped
    iterator's top.

    **Host sharding** (the elastic data plane): :meth:`shard` gives this
    process a disjoint round-robin view of the wrapped stream — global
    batch ``g`` belongs to host ``g % process_count == process_index``;
    the worker *consumes* every batch from the wrapped iterator but
    delivers (and places) only this host's share, so N hosts driving N
    identical iterators partition the epoch with zero overlap and zero
    cross-host coordination. The shard boundary is checkpointable:
    :meth:`shard_state` returns the pod-wide consumed-through cursor
    (every host computes the same value at the same step — SPMD
    lockstep), trainers bank it in checkpoint meta, and
    :meth:`restore_shard` fast-forwards past it under a **new**
    ``(index, count)`` membership — so a 2-host run restored on 1 host
    resumes the stream with no sample replayed and no sample dropped.
    """

    _DONE = object()

    def __init__(self, data_iter, place=None, depth: int = 2):
        if depth < 1:
            raise MXNetError("PrefetchIter depth must be >= 1")
        super().__init__(getattr(data_iter, "batch_size", 0))
        self._it = data_iter
        self._place = place
        self._depth = depth
        self._queue: _queue.Queue = _queue.Queue(maxsize=depth)
        self._worker: Optional[threading.Thread] = None
        self._exc: Optional[BaseException] = None
        self._done = False           # stream ended (worker queues _DONE once)
        self._gen = 0
        self._closed = False
        # host-shard view (identity by default). All five fields are
        # written only while the worker is stopped (shard/restore/reset),
        # so the worker thread reads them race-free.
        self._shard_index = 0
        self._shard_count = 1
        self._shard_base = 0      # global index round-robin starts from
        self._skip_to = 0         # globals below this are already consumed
        self._boundary = 0        # pod-wide consumed-through cursor
        # input-wait instrumentation: every consumer-side queue pop is
        # timed — the blocked portion IS input starvation, the number
        # the goodput ledger's input_wait bucket and the "is the step
        # waiting on data" triage question both need. Registry handles
        # resolve ONCE (the per-call registry lookup takes a lock; this
        # sits on the per-batch hot path).
        from ..telemetry import metrics as _tmetrics
        self._m_wait = _tmetrics.histogram(
            "mxtpu_io_wait_ms",
            "Consumer wait on the PrefetchIter queue per batch (ms)")
        self._m_depth = _tmetrics.gauge(
            "mxtpu_io_queue_depth",
            "Prefetched batches ready at the last queue pop")
        self._start()

    def _start(self):
        gen = self._gen
        q = self._queue

        from ..fault import inject as _inject

        # the shard view, snapshotted at worker start (only mutated while
        # the worker is stopped); g counts batches pulled from the wrapped
        # iterator since its last reset — the GLOBAL batch index
        sh_index, sh_count = self._shard_index, self._shard_count
        sh_base, sh_skip = self._shard_base, self._skip_to

        def run():
            # A stale generation (reset()/close() bumped self._gen) stops
            # touching the shared underlying iterator and exits without
            # queueing its sentinel.
            tail = None
            g = 0
            try:
                while gen == self._gen:
                    try:
                        # chaos: the seeded slow_input knob starves the
                        # consumer HERE, on the producer — the realistic
                        # slow-storage/slow-decode signature the goodput
                        # ledger must attribute as input_wait
                        _inject.maybe_delay("slow_input")
                        b = self._it.next()
                    except StopIteration:
                        tail = PrefetchIter._DONE
                        break
                    except BaseException as e:  # surfaced to the consumer
                        self._exc = e
                        tail = PrefetchIter._DONE
                        break
                    g_cur, g = g, g + 1
                    if g_cur < sh_skip:
                        continue   # restored boundary: already trained on
                    if sh_count > 1 and \
                            (g_cur - sh_base) % sh_count != sh_index:
                        continue   # another host's batch: consume, not ours
                    if self._place is not None:
                        try:
                            # the device hop happens HERE, on the worker —
                            # overlapped with the step consuming the
                            # previous batch
                            b = self._place(b)
                        except BaseException as e:
                            self._exc = e
                            tail = PrefetchIter._DONE
                            break
                    while gen == self._gen:
                        try:
                            q.put((g_cur, b), timeout=0.05)
                            break
                        except _queue.Full:
                            continue
            finally:
                while tail is not None and gen == self._gen:
                    try:
                        q.put(tail, timeout=0.05)
                        break
                    except _queue.Full:
                        continue

        self._worker = threading.Thread(target=run,
                                        name="mx-io-device-prefetch",
                                        daemon=True)
        self._worker.start()

    def _stop_worker(self) -> bool:
        """Signal + join the worker; True when it actually exited."""
        self._gen += 1  # signal the worker to exit
        try:
            while True:
                self._queue.get_nowait()
        except _queue.Empty:
            pass
        if self._worker is not None:
            self._worker.join(timeout=5)
            if self._worker.is_alive():
                return False
            self._worker = None
        return True

    def reset(self):
        if self._closed:
            raise MXNetError("PrefetchIter is closed")
        if not self._stop_worker():
            # the old worker is still blocked inside the wrapped
            # iterator/place call — starting a second one would drive the
            # same (non-thread-safe) iterator from two threads; fail loud
            raise MXNetError(
                "PrefetchIter worker did not stop within 5s (the wrapped "
                "iterator or place() is blocked); cannot reset safely")
        self._exc = None
        self._done = False
        # a new epoch re-shards from global 0: the shard membership
        # (index/count) survives reset, any restored fast-forward does not
        self._shard_base = 0
        self._skip_to = 0
        self._boundary = 0
        self._it.reset()
        self._queue = _queue.Queue(maxsize=self._depth)
        self._start()

    @property
    def depth(self) -> int:
        """Prefetch queue capacity currently in force."""
        return self._depth

    def set_depth(self, depth: int) -> int:
        """Resize the prefetch bound **live** — no worker restart, no
        batch dropped or replayed. The stdlib queue re-reads ``maxsize``
        under its own mutex on every put, so mutating it there (and
        waking blocked producers) makes a grow take effect within one
        producer put; a shrink drains naturally as the consumer pops —
        queued batches are never discarded. This is the flight
        director's ``input_bound`` remediation, and it is allowlisted
        precisely because nothing else moves: stream order, the worker's
        global-batch cursor, and the shard/restore accounting are all
        untouched (a restart would rewind the worker's cursor to 0 and
        drop in-flight batches). ``reset``/``shard``/``restore_shard``
        rebuild their queues at the new depth. Returns the previous
        depth."""
        depth = int(depth)
        if depth < 1:
            raise MXNetError("PrefetchIter depth must be >= 1")
        if self._closed:
            raise MXNetError("PrefetchIter is closed")
        prev, q = self._depth, self._queue
        with q.mutex:
            q.maxsize = depth
            q.not_full.notify_all()
        self._depth = depth
        return prev

    def shard(self, process_index: int, process_count: int) -> "PrefetchIter":
        """Restrict this iterator to host ``process_index``'s round-robin
        share of the stream (global batch ``g`` is ours iff
        ``g % process_count == process_index``). Restarts the stream from
        the wrapped iterator's top so every host's view starts from the
        same global 0 — call it once, right after construction, with
        ``parallel.dist.world()``. Returns ``self`` for chaining. A
        ``(0, 1)`` shard is the identity view."""
        process_index, process_count = int(process_index), int(process_count)
        if process_count < 1 or not 0 <= process_index < process_count:
            raise MXNetError(
                f"invalid shard view ({process_index}, {process_count}): "
                "need 0 <= process_index < process_count")
        if self._closed:
            raise MXNetError("PrefetchIter is closed")
        if not self._stop_worker():
            raise MXNetError(
                "PrefetchIter worker did not stop within 5s; cannot "
                "reshard safely")
        self._shard_index = process_index
        self._shard_count = process_count
        self._shard_base = 0
        self._skip_to = 0
        self._boundary = 0
        self._exc = None
        self._done = False
        self._it.reset()
        self._queue = _queue.Queue(maxsize=self._depth)
        self._start()
        return self

    def shard_state(self) -> Dict[str, int]:
        """The checkpointable shard boundary. ``next_global`` is the
        pod-wide consumed-through cursor: with every host in SPMD
        lockstep (same step count at the save barrier), batches
        ``[0, next_global)`` have each been consumed by exactly one
        host, so a restore under ANY new membership starts there with
        no overlap and no gap. Trainers bank this dict in checkpoint
        meta (``meta["data_state"]``)."""
        return {"next_global": self._boundary,
                "index": self._shard_index,
                "count": self._shard_count,
                "batch_size": int(self.batch_size)}

    def restore_shard(self, state: Dict[str, int],
                      index: Optional[int] = None,
                      count: Optional[int] = None) -> "PrefetchIter":
        """Resume the stream from a banked :meth:`shard_state` under a
        (possibly different) membership — THE elastic-recovery data
        path: the wrapped iterator restarts from its top, the worker
        fast-forwards past the ``next_global`` already-consumed batches,
        and round-robin assignment restarts from that boundary with the
        NEW ``(index, count)`` (defaults: the saved membership). No
        consumed sample is replayed, no unconsumed sample is skipped."""
        state = dict(state or {})
        idx = int(state.get("index", 0)) if index is None else int(index)
        n = int(state.get("count", 1)) if count is None else int(count)
        if n < 1 or not 0 <= idx < n:
            raise MXNetError(
                f"invalid shard view ({idx}, {n}): need 0 <= index < count")
        boundary = max(0, int(state.get("next_global", 0)))
        if self._closed:
            raise MXNetError("PrefetchIter is closed")
        if not self._stop_worker():
            raise MXNetError(
                "PrefetchIter worker did not stop within 5s; cannot "
                "restore shard safely")
        self._shard_index = idx
        self._shard_count = n
        self._shard_base = boundary
        self._skip_to = boundary
        self._boundary = boundary
        self._exc = None
        self._done = False
        self._it.reset()
        self._queue = _queue.Queue(maxsize=self._depth)
        self._start()
        return self

    def close(self):
        """Stop and join the worker thread (idempotent). The wrapped
        iterator is left as-is — mid-stream batches it already produced
        into the dropped queue are consumed, matching any prefetcher's
        read-ahead semantics."""
        if self._closed:
            return
        self._closed = True
        self._stop_worker()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def next(self):
        if self._closed:
            raise MXNetError("PrefetchIter is closed")
        if self._done:
            # the worker queued its sentinel exactly once and exited; any
            # further next() must keep raising (matching plain iterators)
            # instead of blocking forever on an empty, producer-less queue
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        # the blocked pop is the step's input starvation: an io.wait span
        # on the profiler timeline, the mxtpu_io_* metrics, and the
        # goodput ledger's input_wait bucket — all from the ONE timing
        from .. import profiler as _prof
        with _prof.Scope("io.wait") as waited:
            b = self._queue.get()
        wait_ms = waited.dur_ms
        self._m_wait.observe(wait_ms)
        self._m_depth.set(self._queue.qsize())
        from ..telemetry import goodput as _goodput
        if _goodput.enabled():
            _goodput.note("input_wait", wait_ms)
        if b is PrefetchIter._DONE:
            self._done = True
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        g, batch = b
        # consuming our batch of round r means the pod (SPMD lockstep)
        # consumed every global through the end of that round — THE
        # value shard_state() banks
        r = (g - self._shard_base) // self._shard_count
        self._boundary = self._shard_base + (r + 1) * self._shard_count
        return batch

    def iter_next(self) -> bool:
        raise MXNetError("PrefetchIter supports iteration via next() only")

    @property
    def provide_data(self):
        return self._it.provide_data

    @property
    def provide_label(self):
        return self._it.provide_label


class ResizeIter(DataIter):
    """Truncate/extend an iterator to exactly ``size`` batches
    (reference: io.ResizeIter)."""

    def __init__(self, data_iter, size: int, reset_internal: bool = True):
        super().__init__(data_iter.batch_size)
        self._it = data_iter
        self._size = size
        self._reset_internal = reset_internal
        self._cur = 0

    def reset(self):
        self._cur = 0
        if self._reset_internal:
            self._it.reset()

    def next(self) -> DataBatch:
        if self._cur >= self._size:
            raise StopIteration
        self._cur += 1
        try:
            return self._it.next()
        except StopIteration:
            self._it.reset()
            return self._it.next()

    def iter_next(self) -> bool:
        return self._cur < self._size

    @property
    def provide_data(self):
        return self._it.provide_data

    @property
    def provide_label(self):
        return self._it.provide_label
