"""C++ runtime shim tests (reference model: tests/cpp/ — engine dependency
ordering (threaded_engine_test.cc), storage (storage_test.cc) — run from
Python through the ctypes boundary)."""
import struct
import time

import numpy as onp
import pytest

from incubator_mxnet_tpu import native, recordio

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def test_native_recordio_roundtrip_with_embedded_magic(tmp_path):
    f = str(tmp_path / "n.rec")
    payload = b"abc" + struct.pack("<I", 0xCED7230A) + b"defgh"
    w = native.NativeRecordWriter(f)
    p0 = w.write(b"hello")
    p1 = w.write(payload)
    w.close()
    r = native.NativeRecordReader(f)
    assert r.read() == b"hello"
    assert r.read() == payload
    assert r.read() is None
    r.seek(p1)
    assert r.read() == payload
    r.close()
    offs = native.index_build(f)
    assert offs == [p0, p1]


def test_python_and_native_readers_interop(tmp_path):
    """Same wire format both ways (dmlc recordio)."""
    import os
    f1 = str(tmp_path / "a.rec")
    w = recordio.MXRecordIO(f1, "w")  # native-backed when available
    w.write(b"one")
    w.write(b"two" * 100)
    w.close()
    # force the pure-python reader on the native-written file
    os.environ["MXTPU_NO_NATIVE"] = "1"
    try:
        r = recordio.MXRecordIO(f1, "r")
        assert r._nat is None
        assert r.read() == b"one"
        assert r.read() == b"two" * 100
        r.close()
    finally:
        del os.environ["MXTPU_NO_NATIVE"]


def test_shm_cross_handle_visibility():
    name = f"/mxtpu_t_{int(time.time() * 1e6) % 10**9}"
    seg = native.ShmSegment(name, 4096)
    arr = seg.as_numpy((32,), "float32")
    arr[:] = onp.arange(32)
    other = native.ShmSegment(name, 4096, create=False)
    onp.testing.assert_allclose(other.as_numpy((32,), "float32"),
                                onp.arange(32))
    other.close()
    seg.close()


def test_engine_write_ordering():
    eng = native.NativeEngine(4)
    v = eng.new_var()
    out = []
    for i in range(50):
        eng.push(lambda i=i: out.append(i), write_vars=[v])
    eng.wait_all()
    assert out == list(range(50))
    eng.close()


def test_engine_readers_run_concurrently():
    eng = native.NativeEngine(4)
    v = eng.new_var()
    t0 = time.time()
    for _ in range(4):
        eng.push(lambda: time.sleep(0.15), read_vars=[v])
    eng.wait_all()
    assert time.time() - t0 < 0.45
    eng.close()


def test_engine_writer_waits_for_readers():
    eng = native.NativeEngine(4)
    v = eng.new_var()
    log = []
    for i in range(2):
        eng.push(lambda i=i: (time.sleep(0.1), log.append(("r", i))),
                 read_vars=[v])
    eng.push(lambda: log.append(("w", 0)), write_vars=[v])
    eng.push(lambda: log.append(("r2", 0)), read_vars=[v])
    eng.wait_all()
    assert log[2] == ("w", 0)       # writer after both readers
    assert log[3] == ("r2", 0)      # reader after writer
    eng.close()


def test_engine_independent_vars_parallel():
    eng = native.NativeEngine(4)
    t0 = time.time()
    for _ in range(4):
        eng.push(lambda: time.sleep(0.15), write_vars=[eng.new_var()])
    eng.wait_all()
    assert time.time() - t0 < 0.45
    eng.close()


def test_cpp_unit_suite():
    """Build and run the in-tree C++ test binary (tests/cpp parity:
    threaded_engine_test.cc / storage_test.cc analog, native/test_native.cc)."""
    import os
    import shutil
    import subprocess
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("native toolchain unavailable")
    native_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    out = subprocess.run(["make", "-s", "test"], cwd=native_dir,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "all native tests passed" in out.stdout


def test_engine_async_exception_rethrown_at_sync_point():
    """Reference mechanism (SURVEY §5.2 / tests test_exc_handling.py):
    a task raising on a worker thread must surface at the next wait_all,
    not crash the worker or vanish."""
    eng = native.NativeEngine(2)
    v = eng.new_var()
    ran = []

    def boom():
        raise RuntimeError("kaboom-async")

    eng.push(boom, write_vars=[v])
    eng.push(lambda: ran.append(1), write_vars=[v])  # dependents still run
    with pytest.raises(RuntimeError, match="kaboom-async"):
        eng.wait_all()
    assert ran == [1]
    # the engine stays usable after the failure surfaced
    eng.push(lambda: ran.append(2), write_vars=[v])
    eng.wait_all()
    assert ran == [1, 2]
    eng.close()


def test_stale_library_is_rebuilt_and_a_failed_make_says_why(monkeypatch,
                                                           tmp_path):
    """The library is ignored by git and the source is not, so a copied
    tree can carry a ``.so`` older than ``mxtpu_native.cc``: ``_lib()``
    rebuilds it. And a build that fails reports make's own words."""
    import os
    import shutil
    from incubator_mxnet_tpu.base import MXNetError
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("native toolchain unavailable")
    assert native.available()
    work = tmp_path / "native"
    shutil.copytree(native._NATIVE_DIR, work)
    so, src = work / native._SO_NAME, work / "mxtpu_native.cc"
    for name, value in (("_NATIVE_DIR", str(work)), ("_SO_PATH", str(so)),
                        ("_SRC_PATH", str(src)), ("_LIB", None),
                        ("_LOAD_FAILED", "")):
        monkeypatch.setattr(native, name, value)
    assert not native._stale()
    os.utime(so, (1, 1))                    # older than its source
    assert native._stale()
    native._lib()
    assert not native._stale() and os.path.getmtime(so) > 1
    assert not [f for f in os.listdir(work) if ".tmp-" in f]
    # a source that does not compile: the error carries the compiler's text
    src.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_LIB", None)
    with pytest.raises(MXNetError, match=r"make exited \d+(.|\n)*error"):
        native._lib()
    assert not native.available()           # and stays a clean False
    with pytest.raises(MXNetError, match="make exited"):
        native._lib()                       # remembered, not re-run blind
