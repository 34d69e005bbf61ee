"""Multi-process kvstore tests (reference mechanism: SURVEY §4 mech 4 —
multi-process-on-localhost, tests/nightly/dist_sync_kvstore.py), plus
single-process assertions that the mesh path is ONE compiled collective.
"""
import os
import socket
import subprocess
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import kvstore as kvmod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mesh_push_multidevice_sums_on_device():
    """kvstore('nccl') with replicas on distinct local devices: one compiled
    all-reduce; every replica's pull lands on its own device."""
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")
    kv = mx.kv.create("nccl")
    kv.init("g", mx.nd.zeros((4,), ctx=mx.cpu(0)))
    reps = [mx.nd.full((4,), float(i + 1), ctx=mx.cpu(i)) for i in range(4)]
    kv.push("g", reps)
    outs = [mx.nd.zeros((4,), ctx=mx.cpu(i)) for i in range(4)]
    kv.pull("g", out=outs)
    for o in outs:
        onp.testing.assert_allclose(o.asnumpy(), onp.full((4,), 10.0))
    # distribution stayed per-device (no host bounce to one device)
    assert {next(iter(o._data.devices())).id for o in outs} == {0, 1, 2, 3}


def test_mesh_push_key_batch_multidevice():
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    kv = mx.kv.create("nccl")
    keys = ["a", "b"]
    kv.init(keys, [mx.nd.zeros((2,)), mx.nd.zeros((3,))])
    kv.push(keys, [
        [mx.nd.ones((2,), ctx=mx.cpu(0)), mx.nd.ones((2,), ctx=mx.cpu(1))],
        [mx.nd.full((3,), 2.0, ctx=mx.cpu(0)),
         mx.nd.full((3,), 3.0, ctx=mx.cpu(1))],
    ])
    a, b = kv.pull(keys)
    onp.testing.assert_allclose(a.asnumpy(), onp.full((2,), 2.0))
    onp.testing.assert_allclose(b.asnumpy(), onp.full((3,), 5.0))


def test_allreduce_lowers_to_one_collective():
    """The cached executable behind push IS an all-reduce (HLO-asserted)."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    devs = onp.array(jax.devices()[:2])
    mesh = Mesh(devs, ("kv",))
    sig = (((4,), "float32"),)
    fn = kvmod._allreduce_fn(mesh, sig)
    arg = jax.ShapeDtypeStruct(
        (2, 4), jnp.float32, sharding=NamedSharding(mesh, P("kv")))
    stablehlo = fn.lower(arg).as_text()
    compiled = fn.lower(arg).compile().as_text()
    n = stablehlo.count("all_reduce") + compiled.count("all-reduce")
    assert n >= 1, "no all-reduce in lowered push executable"


def test_colocated_replicas_pre_reduce():
    """Replicas on ONE device sum without any collective machinery."""
    kv = mx.kv.create("nccl")
    kv.init(0, mx.nd.zeros((4,)))
    kv.push(0, [mx.nd.ones((4,)), mx.nd.full((4,), 2.0)])
    onp.testing.assert_allclose(kv.pull(0).asnumpy(), onp.full((4,), 3.0))


def test_update_on_kvstore_multidevice_pull_returns_weight():
    """After a multi-device push under update-on-kvstore, pull must hand back
    the UPDATED WEIGHT — not the per-device gradient sum the collective left
    behind (regression: stale _merged_shards shadowing _store)."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    kv = mx.kv.create("nccl")
    kv.init(0, mx.nd.ones((4,)))
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5))
    kv.push(0, [mx.nd.ones((4,), ctx=mx.cpu(0)),
                mx.nd.ones((4,), ctx=mx.cpu(1))])
    outs = [mx.nd.zeros((4,), ctx=mx.cpu(0)), mx.nd.zeros((4,), ctx=mx.cpu(1))]
    kv.pull(0, out=outs)
    for o in outs:  # w - 0.5 * (1 + 1) = 0
        onp.testing.assert_allclose(o.asnumpy(), onp.zeros((4,)), atol=1e-6)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.parametrize("nproc", [2])
def test_dist_sync_kvstore_multiprocess(nproc):
    """The reference's key distributed-testing mechanism: N real processes on
    localhost rendezvous via jax.distributed; push/pull crosses processes
    through the compiled psum (gloo CPU collectives)."""
    port = _free_port()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
    })
    worker = os.path.join(REPO, "tests", "dist_sync_kvstore_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, f"localhost:{port}", str(nproc), str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("dist kvstore workers timed out:\n" +
                    "\n".join(o or "" for o in outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"DIST_KV_OK rank={i}" in out


def test_launch_py_local_spawns_rendezvoused_workers(tmp_path):
    """tools/launch.py (reference: tools/launch.py + dmlc tracker): the local
    launcher wires DMLC_* env vars that dist.initialize maps onto the JAX
    rendezvous."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    worker = tmp_path / "worker.py"
    worker.write_text(
        "import os\n"
        "from incubator_mxnet_tpu.parallel import dist\n"
        "dist.initialize()\n"
        "assert dist.process_count() == 2, dist.process_count()\n"
        "print('LAUNCH_OK rank=%s' % os.environ['DMLC_WORKER_ID'])\n"
        "dist.finalize()\n")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
    })
    rc = launch.launch_local(2, [sys.executable, str(worker)], env=env)
    assert rc == 0


@pytest.mark.parametrize("nproc", [2])
def test_dist_async_kvstore_multiprocess(nproc):
    """N real processes against ONE async PS (reference mechanism:
    tests/nightly/dist_async_kvstore.py): barrier-free pushes interleave at
    the server; each worker converges to the total by polling (eventual
    consistency — the async contract)."""
    from incubator_mxnet_tpu.kvstore.async_ps import AsyncKVStore
    base_port = _free_port() - AsyncKVStore.PORT_OFFSET
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": REPO,
    })
    worker = os.path.join(REPO, "tests", "dist_async_kvstore_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, "127.0.0.1", str(base_port), str(nproc),
         str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("async kv workers timed out:\n" +
                    "\n".join(o or "" for o in outs))
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"DIST_ASYNC_KV_OK rank={i}" in out
