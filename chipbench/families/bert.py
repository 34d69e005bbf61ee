"""The BERT family: builds the system under test from a configuration file.

Calls the program's normal entry points (``models.get_bert``,
``parallel.ShardedTrainer``, ``models.bert_pretrain_loss``), the ones
``bench.bert_trainer`` uses, and imports neither ``bench.py`` nor
``chip_smoke.py``. Everything that judges the system (reference, FLOP
counts) is the benchmark's own.
"""
import jax
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.context import context_for_device
from incubator_mxnet_tpu.parallel.mesh import active_mesh

from chipbench import flops
from chipbench.reference import bert as reference

#: system (bf16 matmuls, fp32 accumulation, flash attention) against the
#: float32 reference on one seeded batch, dropout off. One bf16 rounding is
#: 2^-8 = 0.4%; post-LN renormalises every layer, so the error through 12 or
#: 24 layers stays a few roundings. Measured over 17 seeded runs a cell (my
#: chip runs, PR 23): sequence output at most 3.9e-3 (base) and 7.0e-3
#: (large), loss at most 2.8e-4 and 5.9e-4. The bounds are about three and
#: eight times the worst seen; an int8 matmul path sits near 5e-2 to 8e-2
#: (PR 19's own accuracy gate) and fails the first.
SEQ_TOL = 2e-2     # max|a-b| / max|ref| over the valid positions
LOSS_RTOL = 5e-3   # the loss averages 154 cross-entropies computed in fp32


def train_batches(cfg: dict, traffic: dict, seed: int, n: int, batch: int) -> list:
    """``n`` host batches of packed pretraining data from ``seed``: ids and
    labels uniform over the vocabulary, ``masked`` distinct positions a row,
    every ``valid_length`` the full ``seq_len``."""
    rng = np.random.default_rng(seed)
    L, P, V = traffic["seq_len"], traffic["masked"], cfg["vocab_size"]
    out = []
    for _ in range(n):
        pos = np.sort(np.argsort(rng.random((batch, L)), axis=1)[:, :P], axis=1)
        out.append((rng.integers(0, V, (batch, L)).astype("int32"),
                    rng.integers(0, 2, (batch, L)).astype("int32"),
                    np.full((batch,), L, "float32"),
                    pos.astype("int32"),
                    rng.integers(0, V, (batch, P)).astype("float32"),
                    np.ones((batch, P), "float32"),
                    rng.integers(0, 2, (batch,)).astype("float32")))
    return out


def flops_per_token(cfg: dict, traffic: dict) -> float:
    return flops.bert_train_flops_per_token(cfg, traffic["seq_len"], traffic["masked"])


def attention_roofline_inputs(cfg: dict, traffic: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return dict(batch=traffic["batch"], heads=heads, seq_len=traffic["seq_len"],
                head_dim=cfg["hidden_size"] // heads, layers=cfg["num_hidden_layers"])


class TrainSystem:
    """``net`` + ``ShardedTrainer`` on a one-device mesh, built as
    ``bench.bert_trainer`` builds them, on the chip's own context."""

    def __init__(self, cfg: dict, devices, seed: int):
        self.cfg = cfg
        self.ctx = context_for_device(devices[0])
        mx.random.seed(seed)
        opt = dict(cfg["optimizer"])
        with self.ctx:
            self.net = models.get_bert(
                dict(num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
                     hidden_size=cfg["intermediate_size"],
                     num_heads=cfg["num_attention_heads"]),
                vocab_size=cfg["vocab_size"], max_length=cfg["max_position_embeddings"],
                dropout=cfg["hidden_dropout_prob"], dtype=cfg["dtype"])
            self.net.initialize(ctx=self.ctx)
            self.trainer = parallel.ShardedTrainer(
                self.net, models.bert_pretrain_loss, opt.pop("name"), opt,
                mesh=parallel.make_mesh(devices=list(devices)),
                rules=models.bert_sharding_rules(), n_labels=3)

    def step(self, batch):
        """Enqueue one step on a host batch; returns the loss, not synced."""
        with self.ctx:
            return self.trainer.step(*batch)

    def reference_check(self, batch) -> dict:
        """The net's own forward in predict mode plus the program's loss,
        against the plain reference on the same parameters. Run before the
        first step, while the block's parameters are the trainer's."""
        vl = batch[2]
        with self.ctx:
            args = [mx.nd.array(a, ctx=self.ctx) for a in batch]
            out = self.net(*args[:4])                     # predict mode: no dropout
            loss = float(models.bert_pretrain_loss(out, *args[4:]).asnumpy().mean())
            seq = np.asarray(out[0].asnumpy(), "float32")
        prefix = self.net.prefix
        params = {k[len(prefix):]: p.data(self.ctx)._data
                  for k, p in self.net.collect_params().items()}

        @jax.jit          # the batch is an argument: a constant would change the program with the seed
        def ref(params, ids, tt, vl, pos, lab, w, nsp):
            r_seq, _, r_nsp, r_mlm = reference.forward(params, self.cfg, ids, tt, vl, pos)
            return r_seq, reference.pretrain_loss(r_nsp, r_mlm, lab, w, nsp)

        r_seq, r_loss = jax.device_get(ref(params, *batch))
        keep = np.arange(seq.shape[1])[None, :] < vl[:, None]
        seq_err = float(np.abs(seq - r_seq)[keep].max() / np.abs(r_seq[keep]).max())
        loss_err = abs(loss - float(r_loss)) / abs(float(r_loss))
        return {"loss_system": loss, "loss_reference": float(r_loss),
                "loss_rel_err": loss_err, "seq_rel_err": seq_err,
                "ok": bool(seq_err <= SEQ_TOL and loss_err <= LOSS_RTOL)}

    def program_check(self, batch, on_chip: bool) -> dict:
        """The compiled step itself: traced once, on the pjit path, the flash
        kernel in it (forward, dkv, dq per layer), and the bytes it holds."""
        tr = self.trainer
        with self.ctx, active_mesh(tr.mesh):
            lowered = tr._step_fn.lower(*tr.step_trace_args(*batch))
            kernels = lowered.as_text().count("tpu_custom_call")
            ma = lowered.compile().memory_analysis()
        mem = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp", "generated_code")}
        need = 3 * self.cfg["num_hidden_layers"] if on_chip else 0
        return {"step_traces": tr._step_fn._cache_size(), "path": tr.last_path,
                "tpu_custom_calls": kernels, "memory_analysis": mem,
                # what the device holds while the step runs: state in (aliased to
                # state out), the batch, temporaries and the program itself
                "program_bytes": (mem["argument"] + mem["output"] - mem["alias"]
                                  + mem["temp"] + mem["generated_code"]),
                "ok": bool(tr._step_fn._cache_size() == 1 and tr.last_path == "pjit"
                           and kernels >= need)}


def build_train(cfg: dict, devices, seed: int) -> TrainSystem:
    return TrainSystem(cfg, devices, seed)
