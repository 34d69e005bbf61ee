"""ctypes bindings for the C++ runtime shim (native/mxtpu_native.cc).

Reference parity: the ctypes half of the C ABI boundary
(``python/mxnet/base.py`` ``_LIB`` loading libmxnet.so — SURVEY §2.7). The
shared library is built on demand from ``native/`` with the system g++; all
callers degrade gracefully to the pure-Python paths when a toolchain is
unavailable (``native.available()``).

Surfaces:
- :class:`NativeRecordReader` / :class:`NativeRecordWriter` / index_build —
  the C++ recordio parser (src/io/ parity).
- :class:`ShmSegment` — named POSIX shared memory
  (CPUSharedStorageManager parity) for DataLoader worker transfer.
- :class:`NativeEngine` — host-side dependency engine (ThreadedEngine
  parity): push(fn, read_vars, write_vars), wait_all.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Callable, List, Optional, Sequence

from .base import MXNetError
from .lockcheck import make_lock

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_NAME = "libmxtpu_native.so"
_SO_PATH = os.path.join(_NATIVE_DIR, _SO_NAME)

_LIB: Optional[ctypes.CDLL] = None
_LOAD_LOCK = make_lock("native._LOAD_LOCK")
_LOAD_FAILED = ""      # why the build failed, once it has

_TASK_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


_SRC_PATH = os.path.join(_NATIVE_DIR, "mxtpu_native.cc")


def _stale() -> bool:
    """No library yet, or one older than its source (a copied tree can
    carry a stale ``.so``: it is ignored by git, the source is not)."""
    try:
        return os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def _build() -> None:
    """``make -C native`` into a private name, then an atomic rename —
    several processes (pytest workers) may build at once and none may
    load a half-written library. Raises MXNetError carrying what failed."""
    tmp_name = f"{_SO_NAME}.tmp-{os.getpid()}"
    tmp_path = os.path.join(_NATIVE_DIR, tmp_name)
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, f"SO={tmp_name}"],
                       check=True, capture_output=True, text=True,
                       timeout=120)
        os.replace(tmp_path, _SO_PATH)
    except FileNotFoundError as e:
        raise MXNetError(
            f"cannot build {_SO_PATH}: no make on PATH ({e})") from e
    except subprocess.TimeoutExpired as e:
        raise MXNetError(f"cannot build {_SO_PATH}: make timed out "
                         f"after {e.timeout:g}s") from e
    except subprocess.CalledProcessError as e:
        raise MXNetError(
            f"cannot build {_SO_PATH}: make exited {e.returncode}\n"
            f"{(e.stderr or e.stdout or '').strip()[-2000:]}") from e
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)


def _lib() -> ctypes.CDLL:
    global _LIB, _LOAD_FAILED
    if _LIB is not None:
        return _LIB
    with _LOAD_LOCK:
        if _LIB is not None:
            return _LIB
        if _LOAD_FAILED:
            raise MXNetError(f"native library unavailable: {_LOAD_FAILED}")
        if _stale():
            try:
                _build()
            except MXNetError as e:
                _LOAD_FAILED = str(e)
                raise
        lib = ctypes.CDLL(_SO_PATH)
        lib.MXTPUGetLastError.restype = ctypes.c_char_p
        lib.MXTPURecordIOWriterCreate.restype = ctypes.c_void_p
        lib.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPURecordIOWriterWrite.restype = ctypes.c_int
        lib.MXTPURecordIOWriterWrite.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.MXTPURecordIOWriterFree.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOReaderCreate.restype = ctypes.c_void_p
        lib.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPURecordIOReaderSeek.restype = ctypes.c_int
        lib.MXTPURecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.MXTPURecordIOReaderNext.restype = ctypes.c_int64
        lib.MXTPURecordIOReaderNext.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int)]
        lib.MXTPURecordIOReaderTell.restype = ctypes.c_uint64
        lib.MXTPURecordIOReaderTell.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOWriterTell.restype = ctypes.c_uint64
        lib.MXTPURecordIOWriterTell.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOIndexBuild.restype = ctypes.c_int64
        lib.MXTPURecordIOIndexBuild.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64]
        lib.MXTPUIm2RecCreate.restype = ctypes.c_void_p
        lib.MXTPUIm2RecCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPUIm2RecWrite.restype = ctypes.c_int
        lib.MXTPUIm2RecWrite.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint32, ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64]
        lib.MXTPUIm2RecClose.restype = ctypes.c_int
        lib.MXTPUIm2RecClose.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.MXTPUShmCreate.restype = ctypes.c_void_p
        lib.MXTPUShmCreate.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.MXTPUShmAttach.restype = ctypes.c_void_p
        lib.MXTPUShmAttach.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.MXTPUShmPtr.restype = ctypes.c_void_p
        lib.MXTPUShmPtr.argtypes = [ctypes.c_void_p]
        lib.MXTPUShmSize.restype = ctypes.c_uint64
        lib.MXTPUShmSize.argtypes = [ctypes.c_void_p]
        lib.MXTPUShmFree.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.MXTPUEngineCreate.restype = ctypes.c_void_p
        lib.MXTPUEngineCreate.argtypes = [ctypes.c_int]
        lib.MXTPUEngineNewVar.restype = ctypes.c_int64
        lib.MXTPUEngineNewVar.argtypes = [ctypes.c_void_p]
        lib.MXTPUEnginePush.argtypes = [
            ctypes.c_void_p, _TASK_FN, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
        lib.MXTPUEngineWaitAll.argtypes = [ctypes.c_void_p]
        lib.MXTPUEngineFree.argtypes = [ctypes.c_void_p]
        lib.MXTPUParamsWriterCreate.restype = ctypes.c_void_p
        lib.MXTPUParamsWriterCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPUParamsWriterAdd.restype = ctypes.c_int
        lib.MXTPUParamsWriterAdd.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_void_p, ctypes.c_uint64]
        lib.MXTPUParamsWriterFinish.restype = ctypes.c_int
        lib.MXTPUParamsWriterFinish.argtypes = [ctypes.c_void_p]
        lib.MXTPUParamsWriterFree.argtypes = [ctypes.c_void_p]
        lib.MXTPUParamsReaderCreate.restype = ctypes.c_void_p
        lib.MXTPUParamsReaderCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPUParamsReaderCount.restype = ctypes.c_int64
        lib.MXTPUParamsReaderCount.argtypes = [ctypes.c_void_p]
        lib.MXTPUParamsReaderGet.restype = ctypes.c_int
        lib.MXTPUParamsReaderGet.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64)]
        lib.MXTPUParamsReaderFree.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB


def available() -> bool:
    try:
        _lib()
        return True
    except MXNetError:
        return False


def last_error() -> str:
    return _lib().MXTPUGetLastError().decode()


# ---------------------------------------------------------------------------
# RecordIO
# ---------------------------------------------------------------------------

class NativeRecordWriter:
    def __init__(self, path: str):
        self._h = _lib().MXTPURecordIOWriterCreate(path.encode())
        if not self._h:
            raise MXNetError(last_error())

    def write(self, buf: bytes) -> int:
        pos = ctypes.c_uint64()
        if _lib().MXTPURecordIOWriterWrite(self._h, buf, len(buf),
                                           ctypes.byref(pos)) != 0:
            raise MXNetError(last_error())
        return pos.value

    def tell(self) -> int:
        return _lib().MXTPURecordIOWriterTell(self._h)

    def close(self):
        if self._h:
            _lib().MXTPURecordIOWriterFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeIm2RecWriter:
    """C++ im2rec packer hot loop (reference: tools/im2rec.cc): per record,
    IRHeader pack + dmlc framing + index entry happen in one native call;
    close() writes the ``.idx`` sidecar. Byte-identical to the Python
    ``recordio.pack`` + ``MXIndexedRecordIO`` path."""

    def __init__(self, rec_path: str, idx_path: str):
        self._idx_path = idx_path
        self._h = _lib().MXTPUIm2RecCreate(rec_path.encode())
        if not self._h:
            raise MXNetError(last_error())

    def write(self, key: int, label, id_: int, payload: bytes,
              id2: int = 0) -> None:
        import numpy as _onp
        multi = isinstance(label, (list, tuple, _onp.ndarray))
        labels = [float(x) for x in _onp.asarray(label).reshape(-1)] \
            if multi else [label]
        arr = (ctypes.c_float * len(labels))(*[float(x) for x in labels])
        if _lib().MXTPUIm2RecWrite(self._h, key, arr, len(labels),
                                   int(multi), id_, id2,
                                   payload, len(payload)) != 0:
            raise MXNetError(last_error())

    def close(self):
        if self._h:
            rc = _lib().MXTPUIm2RecClose(self._h, self._idx_path.encode())
            self._h = None
            if rc != 0:
                raise MXNetError(last_error())

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordReader:
    def __init__(self, path: str):
        self._h = _lib().MXTPURecordIOReaderCreate(path.encode())
        if not self._h:
            raise MXNetError(last_error())

    def seek(self, pos: int) -> None:
        _lib().MXTPURecordIOReaderSeek(self._h, pos)

    def read(self) -> Optional[bytes]:
        out = ctypes.c_char_p()
        eof = ctypes.c_int()
        n = _lib().MXTPURecordIOReaderNext(self._h, ctypes.byref(out),
                                           ctypes.byref(eof))
        if n < 0:
            raise MXNetError(last_error())
        if eof.value:
            return None
        return ctypes.string_at(out, n)

    def tell(self) -> int:
        return _lib().MXTPURecordIOReaderTell(self._h)

    def close(self):
        if self._h:
            _lib().MXTPURecordIOReaderFree(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def index_build(path: str) -> List[int]:
    """Native two-pass index: count records, then fill the exact-size
    offset array (the C function tolerates a NULL buffer for counting)."""
    lib = _lib()
    n = lib.MXTPURecordIOIndexBuild(path.encode(), None, 0)
    if n < 0:
        raise MXNetError(last_error())
    if n == 0:
        return []
    arr = (ctypes.c_uint64 * n)()
    n2 = lib.MXTPURecordIOIndexBuild(path.encode(), arr, n)
    if n2 < 0:
        raise MXNetError(last_error())
    return list(arr[:n2])


# ---------------------------------------------------------------------------
# Shared memory
# ---------------------------------------------------------------------------

class ShmSegment:
    """Named POSIX shared memory, zero-copy viewable as a numpy buffer."""

    def __init__(self, name: str, size: int, create: bool = True):
        lib = _lib()
        fn = lib.MXTPUShmCreate if create else lib.MXTPUShmAttach
        self._h = fn(name.encode(), size)
        if not self._h:
            raise MXNetError(last_error())
        self.name = name
        self.size = size
        self._create = create

    def as_numpy(self, shape, dtype):
        import numpy as onp

        class _ShmArray(onp.ndarray):
            # ndarray subclass so the view can pin the segment: the mapping
            # must outlive every array built on it.
            pass

        ptr = _lib().MXTPUShmPtr(self._h)
        n = int(onp.prod(shape)) * onp.dtype(dtype).itemsize
        if n > self.size:
            raise MXNetError(f"shm segment too small: {n} > {self.size}")
        buf = (ctypes.c_char * n).from_address(ptr)
        arr = onp.frombuffer(buf, dtype=dtype).reshape(shape).view(_ShmArray)
        arr._segment = self
        return arr

    def __exit__(self, *exc):
        self.close()

    def __enter__(self):
        return self

    def close(self, unlink: Optional[bool] = None):
        if self._h:
            _lib().MXTPUShmFree(self._h, 1 if (unlink if unlink is not None
                                               else self._create) else 0)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Dependency engine
# ---------------------------------------------------------------------------

class NativeEngine:
    """Host-side async executor with read/write var dependencies
    (ThreadedEngine semantics: concurrent readers, exclusive ordered
    writers)."""

    def __init__(self, num_workers: int = 0):
        self._h = _lib().MXTPUEngineCreate(num_workers)
        # ctypes callbacks stay referenced until wait_all(): freeing one from
        # inside its own trampoline would unmap the ffi closure the C worker
        # thread is still returning through.
        self._keepalive: list = []
        self._lock = make_lock("NativeEngine._lock")
        # Async exception propagation (reference:
        # ThreadedEngine::OnCompleteStatic capture → rethrow in WaitToRead,
        # SURVEY §5.2): a task's exception is captured on the worker thread
        # and rethrown at the next wait_all() sync point — never swallowed,
        # never crashing the worker.
        self._errors: list = []

    def new_var(self) -> int:
        return _lib().MXTPUEngineNewVar(self._h)

    def push(self, fn: Callable[[], None],
             read_vars: Sequence[int] = (),
             write_vars: Sequence[int] = ()) -> None:
        def trampoline(_ctx, _fn=fn):
            try:
                _fn()
            except BaseException as e:  # noqa: BLE001 — must cross threads
                with self._lock:
                    self._errors.append(e)

        cfn = _TASK_FN(trampoline)
        with self._lock:
            self._keepalive.append(cfn)
        rv = (ctypes.c_int64 * max(1, len(read_vars)))(*read_vars)
        wv = (ctypes.c_int64 * max(1, len(write_vars)))(*write_vars)
        _lib().MXTPUEnginePush(self._h, cfn, None, rv, len(read_vars),
                               wv, len(write_vars))

    def wait_all(self) -> None:
        _lib().MXTPUEngineWaitAll(self._h)
        # all pushed tasks have returned through their closures; safe to free
        with self._lock:
            self._keepalive.clear()
            errors, self._errors = self._errors, []
        if errors:
            raise errors[0]

    def close(self):
        """Drain, free, and rethrow any captured task exception — close() is
        a sync point like wait_all() (the __del__ path swallows, as Python
        finalizers must)."""
        if self._h:
            _lib().MXTPUEngineWaitAll(self._h)
            _lib().MXTPUEngineFree(self._h)
            self._h = None
            with self._lock:
                self._keepalive.clear()
                errors, self._errors = self._errors, []
            if errors:
                raise errors[0]

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# dmlc .params container (NDArray::Save/Load parity)
# ---------------------------------------------------------------------------

def params_save(path: str, arrays, names, dtype_flags) -> None:
    """Write the kMXAPINDArrayListMagic container natively. ``arrays`` are
    C-contiguous numpy arrays, ``dtype_flags`` their mshadow type flags
    (serialization._DTYPE_TO_FLAG)."""
    lib = _lib()
    h = lib.MXTPUParamsWriterCreate(path.encode())
    if not h:
        raise MXNetError(last_error())
    try:
        for i, (a, flag) in enumerate(zip(arrays, dtype_flags)):
            # unnamed list saves carry no names section (names may be empty
            # or shorter than arrays) — NULL name marks "unnamed"
            name = names[i].encode() if i < len(names) else None
            shape = (ctypes.c_int64 * max(1, a.ndim))(*a.shape)
            if lib.MXTPUParamsWriterAdd(
                    h, name, flag, a.ndim, shape,
                    a.ctypes.data_as(ctypes.c_void_p), a.nbytes) != 0:
                raise MXNetError(last_error())
        if lib.MXTPUParamsWriterFinish(h) != 0:
            raise MXNetError(last_error())
    finally:
        lib.MXTPUParamsWriterFree(h)


def params_load(path: str):
    """Read a dmlc .params container natively → (arrays, names, flags).
    Raises MXNetError on any layout the C++ reader doesn't cover (V1/legacy/
    sparse records) — the caller falls back to the Python reader."""
    import numpy as onp
    lib = _lib()
    h = lib.MXTPUParamsReaderCreate(path.encode())
    if not h:
        raise MXNetError(last_error())
    try:
        n = lib.MXTPUParamsReaderCount(h)
        arrays, names, flags = [], [], []
        for i in range(n):
            name = ctypes.c_char_p()
            flag = ctypes.c_int32()
            ndim = ctypes.c_uint32()
            shape_p = ctypes.POINTER(ctypes.c_int64)()
            data_p = ctypes.c_void_p()
            nbytes = ctypes.c_uint64()
            if lib.MXTPUParamsReaderGet(
                    h, i, ctypes.byref(name), ctypes.byref(flag),
                    ctypes.byref(ndim), ctypes.byref(shape_p),
                    ctypes.byref(data_p), ctypes.byref(nbytes)) != 0:
                raise MXNetError(last_error())
            shape = tuple(shape_p[d] for d in range(ndim.value))
            raw = ctypes.string_at(data_p, nbytes.value) if nbytes.value \
                else b""
            arrays.append((shape, raw))
            if name.value is not None:  # NULL ⇒ unnamed list save
                names.append(name.value.decode())
            flags.append(flag.value)
        return arrays, names, flags
    finally:
        lib.MXTPUParamsReaderFree(h)
