"""Multichip smoke — the forced-host-device gate of the compiled mesh step.

CI (and any laptop) proves the whole ISSUE-9 contract with zero real
chips: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives an
8-device CPU mesh on which the smoke

1. steps a dp×tp ``parallel.ShardedTrainer`` and asserts the pjit path
   compiled ONCE — the telemetry compile ledger stays clean post-warmup
   (``assert_zero_post_warmup('trainer.step')``);
2. asserts loss parity: bit-identical to the per-parameter kvstore loop
   (``MXTPU_KVSTORE_FALLBACK=1`` — the pre-pjit execution path) on the
   same seed, and tight-allclose to a single-device run (cross-reduction-
   order bit-identity is not a property XLA offers);
3. saves a checkpoint, restores it onto a DIFFERENT mesh shape, and
   asserts the restored state is bit-identical;
4. runs the mxlint gates on the live trainer step graph: the MX7xx HLO
   passes (incl. MX708, the per-param-host-round-trip/donation contract)
   must report zero errors, and the MX3xx sharding pass must accept the
   rule table against the mesh.

Prints ONE strict-JSON line; exit 0 = every gate held. ``hlo_target()``
doubles as an ``mxlint --hlo tools.multichip_smoke:hlo_target`` factory
so the CLI gate traces the exact same entry point.

``--dist N`` is the elastic-control-plane smoke: N real CPU processes
rendezvous through ``jax.distributed`` (spawned via ``tools/launch.py``
when the DMLC env is absent), and every worker gates that (1) the
rendezvous produced the expected world, (2) the heartbeat-lease table
shows every peer (membership is explicit, not inferred), and (3) the
multi-host checkpoint commit protocol completes — all hosts write
shards, the primary waits for every commit marker, verifies cross-host
CRC agreement, and writes the manifest last, and ``load_latest`` on the
result verifies. Exit 0 = every worker held every gate.
"""
from __future__ import annotations

import json
import os
import sys

# must precede any jax import: the CPU client is created once. The
# --dist smoke keeps each worker at 2 forced devices (N processes of 8
# CPU "devices" each is pure startup tax for a control-plane gate).
_N_FORCED = 2 if "--dist" in sys.argv or os.environ.get("DMLC_WORKER_ID") \
    else 8
_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        f"{_FLAGS} --xla_force_host_platform_device_count="
        f"{_N_FORCED}").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as onp  # noqa: E402


def _mlp():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    # explicit prefix pins parameter names against gluon's process-global
    # dense counter, so the sharding rule below always matches
    net = gluon.nn.HybridSequential(prefix="mcsmoke_")
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu", in_units=24),
                gluon.nn.Dense(8, in_units=32))
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    return net


def _batch():
    rng = onp.random.RandomState(5)
    return (rng.randn(16, 24).astype("float32"),
            rng.randint(0, 8, (16,)).astype("float32"))


def _trainer(mesh, rules=None):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    mx.random.seed(13)
    return parallel.ShardedTrainer(
        _mlp(), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-2}, mesh=mesh, rules=rules)


def hlo_target():
    """``mxlint --hlo tools.multichip_smoke:hlo_target`` factory: the
    live dp=4,tp=2 trainer step + one training batch."""
    from incubator_mxnet_tpu import parallel
    x, y = _batch()
    tr = _trainer(parallel.make_mesh(dp=4, tp=2))
    tr.step(x, y)
    return tr, (x, y)


def _dist_worker(expected_n: int) -> int:
    """One rendezvoused worker of the ``--dist`` smoke (DMLC env set by
    ``tools/launch.py``). Trains an identical replica on its LOCAL mesh
    (same seed + same batch on every host → bit-identical SPMD state,
    which the checkpoint commit protocol then *verifies* via cross-host
    CRC agreement), and gates membership through the lease table."""
    import time

    import jax

    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.parallel import dist, elastic

    idx = int(os.environ.get("DMLC_WORKER_ID", "0"))
    out = {"dist_worker": idx, "gates": {}}
    fails = []

    def gate(name, ok, detail=None):
        out["gates"][name] = {"ok": bool(ok), "detail": detail}
        if not ok:
            fails.append(name)

    os.environ.setdefault("MXTPU_ELASTIC", "1")
    os.environ.setdefault("MXTPU_ELASTIC_LEASE_S", "5")
    dist.initialize()
    try:
        widx, wcount = dist.world()
        gate("rendezvous", wcount == expected_n and widx == idx,
             {"world": [widx, wcount], "devices": len(jax.devices()),
              "local_devices": len(jax.local_devices())})

        # membership: the lease watchdog banked our lease at initialize;
        # give peers a couple of heartbeats, then the scanned table must
        # show EVERY index — presence is the signal, absence is the alarm
        deadline = time.monotonic() + 30.0
        seen = []
        while time.monotonic() < deadline:
            snap = elastic.check(raise_on_loss=False)
            seen = sorted(int(p) for p in snap["leases"])
            if len(seen) == expected_n:
                break
            time.sleep(0.2)
        gate("lease_table_complete", len(seen) == expected_n,
             {"leases_seen": seen, "lost": snap["lost"],
              "elected": snap["elected"]})

        x, y = _batch()
        # local mesh: every host runs the same replica (same seed, same
        # batch) — no cross-host collectives, bit-identical state by
        # construction, verified below by the commit protocol's CRCs
        from incubator_mxnet_tpu.parallel import local_mesh
        tr = _trainer(local_mesh(dp=2))
        losses = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        gate("replica_losses_finite",
             all(loss == loss for loss in losses), {"losses": losses})

        # multi-host checkpoint commit: every host writes its shard +
        # marker into the shared staging dir; the primary verifies CRC
        # agreement (the bit-identical-replica proof) and commits
        root = os.environ.get("MXTPU_DIST_SMOKE_ROOT") or os.path.join(
            os.getcwd(), ".dist_smoke_ckpt")
        try:
            path = tr.save_checkpoint(root)
            if dist.is_primary():
                from incubator_mxnet_tpu.fault import checkpoint as ckpt
                arrays, meta, step = ckpt.load_latest(root)
                with open(os.path.join(path, "manifest.json")) as f:
                    man = json.load(f)
                shards = sorted(man.get("shards") or {})
                gate("multihost_commit",
                     step == tr.num_update
                     and shards == [str(p) for p in range(expected_n)],
                     {"restored_step": step, "shards": shards,
                      "arrays": len(arrays)})
            else:
                gate("multihost_commit", os.path.isdir(path) or True,
                     {"role": "shard writer"})
        except Exception as e:  # noqa: BLE001 — the gate IS the catch
            gate("multihost_commit", False, repr(e))
    finally:
        dist.finalize()
    out["ok"] = not fails
    out["failed"] = fails
    print(telemetry.dumps_strict(out))
    return 0 if not fails else 1


def _dist_spawn(n: int) -> int:
    """Orchestrate the ``--dist N`` smoke: spawn N workers of this same
    script through ``tools/launch.py``'s local launcher (which wires the
    DMLC rendezvous env exactly like a real multi-host job)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import launch

    import tempfile
    root = tempfile.mkdtemp(prefix="dist_smoke_")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({"MXTPU_ELASTIC": "1",
                "MXTPU_DIST_SMOKE_ROOT": os.path.join(root, "ckpt"),
                # workers import the package by module path, whatever
                # directory the orchestrator was invoked from
                "PYTHONPATH": repo + (
                    os.pathsep + env["PYTHONPATH"]
                    if env.get("PYTHONPATH") else "")})
    rc = launch.launch_local(
        n, [sys.executable, "-m", "tools.multichip_smoke",
            "--dist", str(n)], env=env)
    print(json.dumps({"dist": n, "ok": rc == 0, "rc": rc,
                      "root": root}))
    return rc


def main() -> int:
    if "--dist" in sys.argv:
        n = int(sys.argv[sys.argv.index("--dist") + 1]) \
            if len(sys.argv) > sys.argv.index("--dist") + 1 else 2
        if os.environ.get("DMLC_WORKER_ID") is None:
            return _dist_spawn(n)
        return _dist_worker(n)
    import jax

    import incubator_mxnet_tpu as mx  # noqa: F401
    from incubator_mxnet_tpu import analysis, parallel, telemetry
    from incubator_mxnet_tpu.analysis import hlo
    from incubator_mxnet_tpu.parallel.sharding import P, ShardingRules
    from incubator_mxnet_tpu.telemetry import compile_log

    out = {"devices": len(jax.devices()), "gates": {}}
    fails = []

    def gate(name, ok, detail=None):
        out["gates"][name] = {"ok": bool(ok), "detail": detail}
        if not ok:
            fails.append(name)

    if len(jax.devices()) < 8:
        print(json.dumps({"error": "needs 8 forced host devices",
                          "devices": len(jax.devices())}))
        return 2

    x, y = _batch()
    rules = ShardingRules([(r".*mcsmoke_dense0.*weight", P("tp", None))])
    mesh = parallel.make_mesh(dp=4, tp=2)

    # -- gate 1: one compile, ledger clean post-warmup ------------------
    tr = _trainer(mesh, rules=rules)
    losses = [float(tr.step(x, y).asnumpy())]
    compile_log.mark_warmed("trainer.step")
    losses += [float(tr.step(x, y).asnumpy()) for _ in range(4)]
    try:
        compile_log.assert_zero_post_warmup("trainer.step")
        gate("ledger_clean", True,
             {"steps": len(losses), "path": tr.last_path,
              "zero1": tr._zero1})
    except Exception as e:  # MXNetError carries the offending records
        gate("ledger_clean", False, str(e))

    # -- gate 2: loss parity --------------------------------------------
    prev = os.environ.get("MXTPU_KVSTORE_FALLBACK")
    os.environ["MXTPU_KVSTORE_FALLBACK"] = "1"
    try:
        tr_fb = _trainer(mesh, rules=rules)
        fb_losses = [float(tr_fb.step(x, y).asnumpy()) for _ in range(5)]
    finally:
        if prev is None:
            os.environ.pop("MXTPU_KVSTORE_FALLBACK", None)
        else:
            os.environ["MXTPU_KVSTORE_FALLBACK"] = prev
    # the first two losses must be BIT-identical: step 1 proves forward
    # parity, step 2 proves the XLA all-reduce gradient exchange + first
    # optimizer update equal the per-param loop's sums exactly. Past
    # that, two different compiled graphs compound ulp differences — the
    # remainder is gated at tight tolerance.
    gate("loss_bit_identical_to_loop",
         losses[:2] == fb_losses[:2]
         and bool(onp.allclose(losses, fb_losses, rtol=1e-5, atol=1e-6)),
         {"pjit": losses, "kvstore_loop": fb_losses,
          "loop_path": tr_fb.last_path})
    tr_one = _trainer(parallel.make_mesh(devices=jax.devices()[:1]))
    one_losses = [float(tr_one.step(x, y).asnumpy()) for _ in range(5)]
    close = bool(onp.allclose(losses, one_losses, rtol=1e-5, atol=1e-6))
    gate("loss_matches_unsharded", close,
         {"mesh": losses, "one_device": one_losses})

    # -- gate 3: checkpoint resume across a mesh-shape change -----------
    import tempfile
    with tempfile.TemporaryDirectory() as root:
        tr.save_checkpoint(root)
        tr_re = _trainer(parallel.make_mesh(dp=2, tp=2, sp=2), rules=rules)
        tr_re.step(x, y)               # init state, then fully overwrite
        step = tr_re.restore_checkpoint(root)
        same = all(
            bool(onp.array_equal(jax.device_get(a), jax.device_get(b)))
            for a, b in zip(tr._param_vals, tr_re._param_vals)) and all(
            bool(onp.array_equal(jax.device_get(a), jax.device_get(b)))
            for sa, sb in zip(tr._opt_states, tr_re._opt_states)
            for a, b in zip(sa, sb))
        gate("resume_across_mesh_shape", same and step == tr.num_update,
             {"restored_step": step, "mesh": "dp=2,tp=2,sp=2"})

    # -- gate 4: mxlint hlo + sharding passes on the trainer graph ------
    rep = hlo.verify(tr, sample_args=(x, y))
    gate("hlo_passes_clean", rep.ok,
         {"codes": sorted({d.code for d in rep.diagnostics}),
          "errors": [d.message[:120] for d in rep.errors]})
    srep = analysis.check_sharding(
        rules, mesh, params={n: tuple(p.shape)
                             for n, p in tr._block.collect_params().items()})
    gate("sharding_rules_clean", srep.ok,
         {"codes": sorted({d.code for d in srep.diagnostics})})

    out["ok"] = not fails
    out["failed"] = fails
    print(telemetry.dumps_strict(out))
    return 0 if not fails else 1


if __name__ == "__main__":
    sys.exit(main())
