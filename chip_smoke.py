#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on one TPU, through the entry points a user calls,
at the full width of BERT-base (``bert_12_768_12``, vocab 30,522, bf16
parameters with fp32 masters, random weights from a seed), in ONE process:

==========  ============================================================
device      ``jax.devices()`` first; anything but a TPU fails at once
kernel      the Pallas flash kernel, forward and grads, compiled, against
            the XLA attention path on the same inputs
train       ``bert_trainer`` below (``parallel.ShardedTrainer``, AdamW)
            at B=8, L=512: twenty steps on one repeated batch, one compile
sync        a few steps timed with ``wait_to_read`` and with ``asnumpy``
serve       ``serve.CompiledModel`` over the ``bert_encoder`` family,
            four buckets, ``warmup()``, requests through ``predict`` and
            through ``DynamicBatcher``
checkpoint  ``save_checkpoint`` -> new trainer -> ``restore_checkpoint``
            -> one more step
==========  ============================================================

Each phase prints one JSON line; a phase that fails raises, and the script
exits non-zero with the traceback. The last line of a run that held is
exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
1}}``.

``--chips 4`` runs the four-chip phase instead, and no other: BERT-base
width cut to two layers on a dp2·tp2 mesh (zero1 on) and on a dp2·sp2 mesh
at L=1024 (ring attention over the Pallas hop), each against the same
seeded batch on a one-device mesh in this process. Its last line has
``"count": 4``.

``--rehearse`` shrinks every size to ``bert_2_128_2`` and lifts the
platform check, so the control flow can be rehearsed on a CPU
(``JAX_PLATFORMS=cpu python chip_smoke.py --rehearse``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``).
A rehearsal proves nothing about the chip: it ends in ``"ok": false`` and
exit code 3, whatever it ran on.

The persistent compilation cache is where ``JAX_COMPILATION_CACHE_DIR``
says, else ``<checkout>/.jax_cache`` (``use_compile_cache`` below); the
``cache`` line and every phase's ``compile_requests`` / ``cache_hits`` say
whether a second run of the same command found the first one's programs.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

import numpy as onp

#: bf16 parity bound, as max|a-b| / max|reference|: bf16 keeps 8 bits of
#: mantissa (one rounding is 2^-8 = 0.4%), and the two attention paths
#: round at different points and sum in different orders
_BF16_TOL = 2e-2
#: loss bound between one program on one device and the same step
#: partitioned over four: bf16 activations, other reduction orders. Ten
#: times what two correct ONE-device programs (flash vs XLA attention)
#: drift apart over four steps at _MESH_LR (1.6e-4, my chip run, PR 21)
_MESH_LOSS_RTOL = 2e-3
#: a tenth of ``bert_trainer``'s default learning rate. At 1e-4 with no
#: warm-up the first AdamW updates overshoot (the loss rises, gradients reverse, and the
#: second update is a near-cancellation of two moments), which amplifies
#: bf16 rounding: the same two one-device programs differ by 2e-5, 7e-4,
#: 1.8e-2 over three steps there. At 1e-5 the loss falls from the first
#: step and rounding stays rounding, so a tight bound means something.
_MESH_LR = 1e-5

_FULL = dict(model="bert_12_768_12", B=8, L=512, steps=20, sync_steps=5,
             kernel_masked=(8, 12, 512, 64),
             kernel_window=((1, 12, 4096, 64), 1024),
             serve_batch=(4, 8), serve_seq=(256, 512),
             serve_lens=(37, 200, 256, 300, 512, 90),
             mesh_layers=2, mesh_L=512, ring_L=1024)
_TINY = dict(model="bert_2_128_2", B=2, L=128, steps=6, sync_steps=2,
             kernel_masked=(2, 2, 128, 64),
             kernel_window=((1, 2, 256, 64), 128),
             serve_batch=(4, 8), serve_seq=(32, 64),
             serve_lens=(5, 20, 32, 40, 64, 11),
             mesh_layers=2, mesh_L=128, ring_L=256)


def _say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class _CompileCounter:
    """Persistent-cache traffic since the last ``take()``: how many
    compiles asked the cache, and how many it answered."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_requests": self.requests, "cache_hits": self.hits}
        self.requests = self.hits = 0
        return out


def _memory(compiled) -> dict:
    """``memory_analysis()`` of a compiled program, in bytes."""
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp", "generated_code")}


def _rel_err(a, ref) -> float:
    a = onp.asarray(a, "float32")
    ref = onp.asarray(ref, "float32")
    return float(onp.max(onp.abs(a - ref)) / max(onp.max(onp.abs(ref)), 1e-6))


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on, before the first
    compile. ``JAX_COMPILATION_CACHE_DIR``, when set, is the only
    location (jax reads it itself; no directory is set in code). Unset,
    the cache is ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what a later process must find again. Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_BERT_VOCAB = 30522


def bert_batch(B: int, L: int, vocab: int = _BERT_VOCAB) -> tuple:
    """THE seeded synthetic pretraining batch: ``(ids, token_types,
    valid_length, masked_positions, mlm_labels, mlm_weights, nsp_labels)``
    with BERT's 15% masking rate."""
    P = max(1, round(0.15 * L))
    rng = onp.random.RandomState(0)
    ids = rng.randint(0, vocab, (B, L)).astype("int32")
    tt = rng.randint(0, 2, (B, L)).astype("int32")
    vl = onp.full((B,), L, "float32")
    pos = rng.randint(0, L, (B, P)).astype("int32")
    mlm_lab = rng.randint(0, vocab, (B, P)).astype("float32")
    mlm_w = onp.ones((B, P), "float32")
    nsp = rng.randint(0, 2, (B,)).astype("float32")
    return (ids, tt, vl, pos, mlm_lab, mlm_w, nsp)


def bert_trainer(model_name: str, L: int, mesh, vocab: int = _BERT_VOCAB,
                 dropout: float = 0.1, learning_rate: float = 1e-4,
                 **overrides):
    """``(net, trainer)`` of THE BERT pretraining job: bf16 parameters
    with fp32 masters under AdamW, Megatron sharding rules, the whole
    step one compiled program on ``mesh``. ``overrides`` reach
    ``models.get_bert`` (``remat=``, a ``num_layers=`` cut)."""
    from incubator_mxnet_tpu import models, parallel

    net = models.get_bert(model_name, vocab_size=vocab, max_length=L,
                          dropout=dropout, dtype="bfloat16", **overrides)
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, models.bert_pretrain_loss, "adamw",
        {"learning_rate": learning_rate, "multi_precision": True}, mesh=mesh,
        rules=models.bert_sharding_rules(), n_labels=3,
        # banked autotune winners (MXTPU_AUTOTUNE_DIR) apply at build —
        # a tuned config is reproducible per key, not a one-off env
        # recipe pasted into a shell
        autotune_key="bert")
    return net, trainer


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_device(rehearse: bool, chips: int):
    """First act: ask JAX what it has. No TPU, no run."""
    import jax
    import jaxlib
    import incubator_mxnet_tpu as mx

    devs = jax.devices()
    on_chip = devs[0].platform == "tpu"
    if not on_chip and not rehearse:
        raise SystemExit(
            f"chip_smoke.py needs a TPU and found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); it does not fall "
            "back (--rehearse shrinks it for a CPU and cannot pass)")
    if len(devs) < chips:
        # MULTICHIP_r01's failure, kept as the message: never shrink the mesh
        raise SystemExit(f"{len(devs)} devices not divisible by fixed axes "
                         f"product {chips}")
    if on_chip:     # the library's accelerator context is this very chip
        assert mx.tpu(0).jax_device == devs[0], (mx.tpu(0).jax_device, devs[0])
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    _say("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, mx_tpu0=str(mx.tpu(0).jax_device))
    return devs, on_chip


def phase_kernel(cfg, on_chip: bool, counter) -> None:
    """Flash forward + grads against the XLA path, on the same device."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.attention import dot_product_attention
    from incubator_mxnet_tpu.ops.pallas.flash_attention import flash_attention

    def case(name, shape, causal, window, masked):
        B, H, L, D = shape
        ks = jax.random.split(jax.random.key(7), 4)
        q, k, v, do = (jax.random.normal(kk, shape, jnp.bfloat16)
                       for kk in ks)
        mask = None
        if masked:   # key-padding mask, every row keeps at least L/2 keys
            lens = onp.linspace(L // 2, L, B).astype("int32")
            mask = jnp.asarray(onp.arange(L)[None, :] < lens[:, None])

        def flash(q, k, v):
            return flash_attention(q, k, v, mask=mask, causal=causal,
                                   window=window)

        def xla(q, k, v):
            m4 = None if mask is None else mask[:, None, None, :]
            return dot_product_attention(q, k, v, m4, causal=causal,
                                         window=window, impl="xla")

        def fwd_bwd(attn):
            def f(q, k, v, do):
                o, vjp = jax.vjp(attn, q, k, v)
                return (o,) + vjp(do)
            return jax.jit(f)

        t0 = time.perf_counter()
        lowered = fwd_bwd(flash).lower(q, k, v, do)
        n_calls = lowered.as_text().count("tpu_custom_call")
        if on_chip:   # compiled by Mosaic, not interpreted: fwd, dkv, dq
            assert n_calls >= 3, f"{name}: {n_calls} tpu_custom_call"
        got = jax.block_until_ready(lowered.compile()(q, k, v, do))
        want = jax.block_until_ready(fwd_bwd(xla)(q, k, v, do))
        errs = {n: _rel_err(g, w)
                for n, g, w in zip(("o", "dq", "dk", "dv"), got, want)}
        for n, g in zip(errs, got):
            assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), (name, n)
        assert max(errs.values()) <= _BF16_TOL, (name, errs)
        _say("kernel", case=name, shape=list(shape), causal=causal,
             window=window, masked=masked, tpu_custom_calls=n_calls,
             rel_err=errs, tol=_BF16_TOL,
             seconds=round(time.perf_counter() - t0, 3), **counter.take())

    case("masked", cfg["kernel_masked"], False, None, True)
    shape, window = cfg["kernel_window"]
    case("causal_window", shape, True, window, False)


def phase_train(cfg, devs, on_chip: bool, counter):
    """BERT pretraining through ``bert_trainer``: finite, falling
    loss, ONE compile, flash in the step, state on the device."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, parallel
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    from incubator_mxnet_tpu.telemetry import compile_log

    mx.random.seed(0)
    t0 = time.perf_counter()
    net, trainer = bert_trainer(
        cfg["model"], cfg["L"], parallel.make_mesh(devices=devs[:1]))
    batch = bert_batch(cfg["B"], cfg["L"])
    t1 = time.perf_counter()
    losses = [float(trainer.step(*batch).asnumpy())]      # init + compile
    first_s = time.perf_counter() - t1
    compile_log.mark_warmed("trainer.step")
    placed = trainer.place(*batch)
    t2 = time.perf_counter()
    for _ in range(cfg["steps"] - 1):
        losses.append(float(trainer.step(*placed).asnumpy()))
    steps_s = time.perf_counter() - t2
    cache = counter.take()

    assert all(onp.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # one compile: the ledger sees new batch signatures, the jit entry's
    # own cache sees every trace (e.g. a step input whose type changed)
    compile_log.assert_zero_post_warmup("trainer.step")
    assert trainer._step_fn._cache_size() == 1, \
        trainer._step_fn._cache_size()
    assert trainer.last_path == "pjit", trainer.last_path

    # the step program itself: is the kernel in it, what does it hold
    t3 = time.perf_counter()
    with active_mesh(trainer.mesh):
        lowered = trainer._step_fn.lower(*trainer.step_trace_args(*placed))
        n_calls = lowered.as_text().count("tpu_custom_call")
        n_layers = models.bert.BERT_CONFIGS[cfg["model"]]["num_layers"]
        if on_chip:   # fwd + dkv + dq per layer
            assert n_calls >= 3 * n_layers, (n_calls, n_layers)
        mem = _memory(lowered.compile())
    donated = sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(
        (trainer._param_vals, trainer._opt_states)))
    analysis_s = time.perf_counter() - t3

    stats = mx.tpu(0).memory_stats()
    if on_chip:
        assert stats["source"] == "pjrt", stats
        dev = devs[0]
        assert all(v.devices() == {dev} for v in trainer._param_vals)
    _say("train", model=cfg["model"], batch=cfg["B"], seq=cfg["L"],
         params=sum(int(onp.prod(p.shape))
                    for p in net.collect_params().values()),
         losses=losses, build_seconds=round(t1 - t0, 3),
         first_step_seconds=round(first_s, 3),
         later_steps_seconds=round(steps_s, 3),
         step_traces=trainer._step_fn._cache_size(), path=trainer.last_path,
         tpu_custom_calls=n_calls, memory_analysis=mem,
         donated_bytes=donated, aliased_bytes=mem["alias"],
         analysis_seconds=round(analysis_s, 3),
         memory_source=stats["source"],
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"), **cache,
         analysis_cache=counter.take())
    return trainer, placed


def phase_sync(cfg, trainer, placed) -> None:
    """Which sync is honest here: does ``wait_to_read`` (block_until_ready)
    wait for the device, as ``asnumpy`` (a host copy) must? Read by eye and
    recorded in the README."""
    n = cfg["sync_steps"]
    times = {}
    for how in ("wait_to_read", "asnumpy", "wait_to_read", "asnumpy"):
        t0 = time.perf_counter()
        for _ in range(n):
            loss = trainer.step(*placed)
        t_enq = time.perf_counter() - t0
        getattr(loss, how)()
        times.setdefault(how, []).append(
            {"enqueue_ms_per_step": round(t_enq / n * 1e3, 3),
             "synced_ms_per_step":
                 round((time.perf_counter() - t0) / n * 1e3, 3)})
    best = {h: min(r["synced_ms_per_step"] for r in rs)
            for h, rs in times.items()}
    _say("sync", steps=n, readings=times,
         wait_to_read_over_asnumpy=round(
             best["wait_to_read"] / best["asnumpy"], 4))


def phase_serve(cfg, on_chip: bool, counter) -> None:
    """``bert_encoder`` at published width behind a small bucket table:
    zero post-warmup compiles, finite outputs, and one request answered
    the same alone and inside a batch (padding is masked)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, nd, serve

    ctx = mx.tpu(0)     # the accelerator context (CPU only in a rehearsal)
    vocab = 30522
    t0 = time.perf_counter()
    mx.random.seed(1)
    net = models.get_bert(cfg["model"], vocab_size=vocab,
                          max_length=cfg["serve_seq"][1], dropout=0.1,
                          dtype="bfloat16", use_decoder=False,
                          use_classifier=False)
    net.initialize(ctx=ctx)
    net.hybridize()
    spec = models.serve_spec("bert_encoder")
    table = serve.BucketTable({"batch": cfg["serve_batch"],
                               "seq": cfg["serve_seq"]})
    assert table.num_buckets() <= 4, table
    B0, L0 = cfg["serve_batch"][0], cfg["serve_seq"][0]
    example = (nd.array(onp.ones((B0, L0), "int32"), ctx=ctx),
               nd.array(onp.zeros((B0, L0), "int32"), ctx=ctx),
               nd.array(onp.full((B0,), L0, "float32"), ctx=ctx))
    model = serve.CompiledModel(net, table, spec["input_axes"],
                                example_args=example,
                                output_axes=spec["output_axes"],
                                pad_values=spec["pad_values"],
                                autotune_key="bert_encoder")
    build_s = time.perf_counter() - t0
    warm = model.warmup()
    assert warm["compiled"] == table.num_buckets(), warm
    cache = counter.take()

    rng = onp.random.RandomState(3)
    reqs = [(rng.randint(0, vocab, (n,)).astype("int32"),
             rng.randint(0, 2, (n,)).astype("int32"),
             onp.float32(n)) for n in cfg["serve_lens"]]

    def check(seq, pooled, n):
        seq, pooled = onp.asarray(seq, "float32"), onp.asarray(pooled, "float32")
        assert seq.shape[0] == n and pooled.ndim == 1, (seq.shape, pooled.shape)
        assert onp.isfinite(seq).all() and onp.isfinite(pooled).all()
        return seq, pooled

    # (1) predict: the first request alone (smallest bucket) ...
    t1 = time.perf_counter()
    ids, tt, vl = reqs[0]
    seq, pooled = model.predict(ids[None], tt[None], onp.asarray([vl]))
    alone = check(seq.asnumpy()[0], pooled.asnumpy()[0], len(ids))
    # ... and every request in one call (largest buckets, mixed lengths)
    stacked = serve.batcher.stack_examples(model, reqs)
    seq, pooled = model.predict(*stacked)
    seq, pooled = seq.asnumpy(), pooled.asnumpy()
    for i, (ids_i, _, _) in enumerate(reqs):
        check(seq[i, :len(ids_i)], pooled[i], len(ids_i))
    errs = {"predict_seq": _rel_err(seq[0, :len(ids)], alone[0]),
            "predict_pooled": _rel_err(pooled[0], alone[1])}
    # (2) the way a client reaches it: single requests, coalesced
    batcher = serve.DynamicBatcher(model, max_delay_ms=50.0).start()
    try:
        futures = [batcher.submit(*r) for r in reqs]
        outs = [f.result(timeout=300) for f in futures]
    finally:
        batcher.stop()
    for (ids_i, _, _), (s, p) in zip(reqs, outs):
        check(s, p, len(ids_i))
    errs["batcher_seq"] = _rel_err(outs[0][0], alone[0])
    errs["batcher_pooled"] = _rel_err(outs[0][1], alone[1])
    assert max(errs.values()) <= _BF16_TOL, errs

    info = model.cache_info()
    assert info["post_warmup_compiles"] == 0, info
    assert info["misses"] == 0, info
    _say("serve", model=cfg["model"], family="bert_encoder",
         buckets=repr(table), build_seconds=round(build_s, 3),
         warmup_seconds=warm["seconds"], warmup_cache=cache,
         requests=len(reqs) * 2 + 1, request_lengths=list(cfg["serve_lens"]),
         batches=batcher.metrics.batches,
         request_seconds=round(time.perf_counter() - t1, 3),
         alone_vs_batched_rel_err=errs, tol=_BF16_TOL,
         cache_info=info, on_chip=on_chip, **counter.take())


def phase_checkpoint(cfg, devs, trainer, placed, counter) -> None:
    """save -> new trainer -> restore -> the same next step."""
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as root:
        t0 = time.perf_counter()
        path = trainer.save_checkpoint(root)
        save_s = time.perf_counter() - t0
        mx.random.seed(99)              # other weights than the saved ones
        _, fresh = bert_trainer(
            cfg["model"], cfg["L"], parallel.make_mesh(devices=devs[:1]))
        fresh.step(*placed)             # builds its state; overwritten next
        t1 = time.perf_counter()
        step = fresh.restore_checkpoint(root)
        restore_s = time.perf_counter() - t1
    assert step == trainer.num_update, (step, trainer.num_update)
    same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree_util.tree_leaves((trainer._param_vals, trainer._opt_states)),
        jax.tree_util.tree_leaves((fresh._param_vals, fresh._opt_states))))
    assert same, "restored state differs from the saved trainer's"
    want = float(trainer.step(*placed).asnumpy())
    got = float(fresh.step(*placed).asnumpy())
    assert onp.isfinite(got) and onp.isclose(got, want, rtol=1e-5), (got, want)
    # the restored step counter and RNG key keep the step's one signature
    assert fresh._step_fn._cache_size() == 1, fresh._step_fn._cache_size()
    _say("checkpoint", dir=path.rsplit("/", 1)[-1], restored_step=step,
         save_seconds=round(save_s, 3), restore_seconds=round(restore_s, 3),
         state_identical=same, next_loss_saved_trainer=want,
         next_loss_restored_trainer=got, **counter.take())


# ---------------------------------------------------------------------------
# the four-chip phase (--chips 4): nothing above runs
# ---------------------------------------------------------------------------

def phase_mesh(cfg, devs, on_chip: bool, counter) -> None:
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, parallel
    from incubator_mxnet_tpu.parallel import ring
    from incubator_mxnet_tpu.parallel.mesh import active_mesh

    n_layers, B = cfg["mesh_layers"], 8
    full_layers = models.bert.BERT_CONFIGS[cfg["model"]]["num_layers"]

    def run(mesh, L):
        mx.random.seed(5)               # the same weights on every mesh
        # dropout off: on the TPU the RNG is XLA's RngBitGenerator, whose
        # bits depend on how the program is partitioned, so two meshes
        # would drop different units and the losses could not be compared
        _, tr = bert_trainer(cfg["model"], L, mesh, dropout=0.0,
                                   learning_rate=_MESH_LR,
                                   num_layers=n_layers)
        batch = bert_batch(B, L)
        losses = [float(tr.step(*batch).asnumpy()) for _ in range(3)]
        assert all(onp.isfinite(losses)), losses
        assert tr.last_path == "pjit" and tr._step_fn._cache_size() == 1
        return tr, batch, losses

    def spread(tr):
        """Every array a rule or zero1 splits is really split, and every
        device of the mesh holds a shard of it."""
        n_split = 0
        mesh_devs = set(tr.mesh.devices.flat)
        for leaf in jax.tree_util.tree_leaves(
                (tr._param_vals, tr._opt_states)):
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == mesh_devs, leaf.sharding
            parts = 1       # how many ways its spec splits it on this mesh
            for entry in leaf.sharding.spec:
                for axis in ((entry,) if isinstance(entry, str)
                             else entry or ()):
                    parts *= tr.mesh.shape[axis]
            if parts == 1:
                continue
            n_split += 1
            assert all(s.data.size * parts == leaf.size for s in shards), \
                ("whole on a device", leaf.sharding, leaf.shape)
        return n_split

    def program(tr, batch):
        with active_mesh(tr.mesh):
            compiled = tr._step_fn.lower(
                *tr.step_trace_args(*batch)).compile()
        text = compiled.as_text()
        prog = {verb: len(re.findall(rf"\b{verb}(?:-start)?\(", text))
                for verb in ("all-reduce", "reduce-scatter", "all-gather",
                             "collective-permute")}
        prog["tpu_custom_call"] = text.count("tpu_custom_call")
        prog["per_device_bytes"] = _memory(compiled)
        return prog

    for name, axes, L in (("dp2_tp2", dict(dp=2, tp=2), cfg["mesh_L"]),
                          ("dp2_sp2", dict(dp=2, sp=2), cfg["ring_L"])):
        t0 = time.perf_counter()
        _, _, one = run(parallel.make_mesh(devices=devs[:1]), L)
        tr, batch, losses = run(
            parallel.make_mesh(devices=devs[:4], **axes), L)
        assert onp.allclose(losses, one, rtol=_MESH_LOSS_RTOL), (losses, one)
        assert tr._zero1, "zero1 defaults on when dp > 1"
        n_split = spread(tr)
        assert n_split > 0
        prog = program(tr, batch)
        # zero1: grads reduce-scatter into the dp-sharded update (XLA may
        # fuse that as all-reduce + slice), new weights all-gather back
        assert prog["reduce-scatter"] + prog["all-reduce"] > 0, prog
        assert prog["all-gather"] > 0, prog
        extra = {}
        if "sp" in axes:
            H = tr._block.encoder.layers[0].attention._num_heads
            D = tr._block._units // H
            hop = jax.ShapeDtypeStruct((B // 2, H, L // 2, D), "bfloat16")
            extra["ring_hop_shape"] = list(hop.shape)
            extra["ring_hop_pallas"] = ring._hop_flash_ok(hop, hop)
            assert extra["ring_hop_pallas"], hop   # not the einsum branch
            assert prog["collective-permute"] > 0, prog
        if on_chip:
            assert prog["tpu_custom_call"] > 0, prog
        _say("mesh", mesh=name, axes=dict(tr.mesh.shape), layers=n_layers,
             cut=f"{full_layers} layers -> {n_layers}, widths unchanged",
             batch=B, seq=L, zero1=tr._zero1, losses=losses,
             one_device_losses=one, rtol=_MESH_LOSS_RTOL,
             learning_rate=_MESH_LR,
             max_rel_diff=float(onp.max(onp.abs(
                 onp.subtract(losses, one) / onp.asarray(one)))),
             split_arrays=n_split, program=prog,
             seconds=round(time.perf_counter() - t0, 3), **extra,
             **counter.take())


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the four-chip mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any platform; always ends ok=false")
    args = ap.parse_args(argv)
    cfg = _TINY if args.rehearse else _FULL
    t_start = time.perf_counter()

    devs, on_chip = phase_device(args.rehearse, args.chips)
    cache_dir = use_compile_cache()
    _say("cache", dir=cache_dir,
         from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         entries_at_start=(len(os.listdir(cache_dir))
                           if os.path.isdir(cache_dir) else 0))
    counter = _CompileCounter()

    if args.chips == 4:
        phase_mesh(cfg, devs, on_chip, counter)
    else:
        phase_kernel(cfg, on_chip, counter)
        trainer, placed = phase_train(cfg, devs, on_chip, counter)
        phase_sync(cfg, trainer, placed)
        phase_checkpoint(cfg, devs, trainer, placed, counter)
        del trainer, placed
        phase_serve(cfg, on_chip, counter)

    _say("done", seconds=round(time.perf_counter() - t_start, 3),
         rehearsal=args.rehearse)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}))
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
