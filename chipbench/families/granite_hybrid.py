"""The ``granite_hybrid`` family (Granite-4.0-H-Micro): builds the system
under test from a configuration file.

Calls the program's normal entry points (``models.get_granite_hybrid``,
``parallel.ShardedTrainer``, ``models.afmoe_lm_loss``) as
``families/lfm2_moe.py`` does for its family, with the AFMoE family's batch
layout ``(ids, positions, valid_length, labels)`` and batches
(``train_batches``). Everything that judges the system (reference,
operation counts, limits) is the benchmark's own and this family's.

**What decides ``correct``.** On one seeded two-row batch at the timed
sequence length (one row padded to 3L/4, so the key mask is exercised), the
float32 reference at ``highest`` on the same bf16-rounded weights, whose
Mamba mixers run the recurrence one position at a time, against: the net's
own forward (final hidden state, **read per 256-position chunk of each row,
the worst chunk deciding**, so that an error carried across chunk
boundaries cannot hide in a row's average; logits on a block of positions),
**the forward-backward half of the trainer's step itself**
(``ShardedTrainer._make_loss_grads``, the function the compiled step is
built from, jitted here without the update): its loss and its gradients of
one tensor of each kind (``grad_tensors``); and **the trainer's first real
step** on the check batch's full row: the change it makes to those tensors
(their fp32 masters) against the reference's AdamW step from the
reference's own gradients of that row. Each limit lies between two readings
on the chip at the published widths (PERF.md section 2 has them): what the
system gives over its seeds, and what the reference itself gives with every
matmul operand rounded to fp8 (e4m3, the next precision below the stated
bf16); the update's also below 1, what a state left unchanged reads.
"""
import re

import jax
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.context import context_for_device
from incubator_mxnet_tpu.parallel.mesh import active_mesh

from chipbench import flops_granite_hybrid as flops_gh
from chipbench.families.afmoe import train_batches  # noqa: F401
from chipbench.reference import granite_hybrid as reference

#: positions a chunk of the hidden-state reading holds: the scan's chunk
CHUNK_READ = 256
#: Readings on one v5e at the published widths (PERF.md section 2):
#: "system" the bf16 program, "fp8" the reference with fp8 operands, "bf16
#: decay" the system with the scan's decay sums rounded to bf16 before its
#: kernels, each against the reference proper. Each limit but the loss's
#: lies between the system's largest reading and the fp8 control's; the
#: bf16 decay fails three of the five.
#:
#: The final normed hidden state over the valid positions of one chunk of
#: one row: root mean square of the difference over that of the reference,
#: the worst of the 56 chunks. Ten layers of bf16 roundings add up in the
#: residual stream and the final norm rescales them; the chunks read alike,
#: and a decay off by a bf16 step is off in every chunk after the first
#: position.
HIDDEN_RMS_TOL = 0.035  # system 0.0172-0.0177, bf16 decay 0.042-0.045, fp8 0.208-0.211
#: logits of the first LOGIT_BLOCK positions of each row through the tied
#: head, root mean square of the difference over that of the reference
LOGIT_BLOCK = 512
LOGIT_RMS_TOL = 0.035   # system 0.0170-0.0175, bf16 decay 0.037-0.038, fp8 0.200-0.201
#: the loss of the step's own forward, a mean of 14,336 cross-entropies in
#: fp32 near ln(vocabulary): precision hardly moves it (system at most
#: 3.0e-6, bf16 decay 7.2e-6, fp8 5.3e-5), so it has the limit of the
#: harness's accepted cells (families/bert.py, families/afmoe.py), which
#: leaves the system's reading over a thousand times of room and which no
#: control reaches: it decides nothing against them
LOSS_RTOL = 5e-3
#: Gradients of the step's own backward pass, a tensor of each kind: the
#: Frobenius norm of the difference over that of the reference's gradient,
#: the largest of the ten. System 0.023-0.036 (`A_log`, `dt_bias` or the query
#: the largest); bf16 decay 0.155-0.162 (`dt_bias` or `A_log`); fp8 0.93 (the
#: embedding) to 1.37 (the query), the rest about 1.0: fp8 cotangents underflow.
GRAD_TOL = 0.07
#: The first update: for each of the ten tensors the change one real step of
#: the trainer makes to it (its fp32 master where the weight is bf16), the
#: Frobenius norm of its difference from the reference's AdamW change
#: (``reference.adamw_first_step`` of the reference's gradient of the same
#: row) over the norm of the latter, the largest of the ten. A state left
#: unchanged reads 1. AdamW's first step moves an element by the learning
#: rate times the sign of its gradient, so the reading is 2 sqrt(s), s the
#: share of elements whose gradient's sign the bf16 step does not share with
#: the reference's: 0.7% in the large tensors (0.15-0.16), and none to two
#: of the 64 of `A_log` or `dt_bias` (0.25 and 0.35 a flip or two). System
#: 0.16-0.32 a seed, bf16 decay 0.55-0.57, fp8 1.46 (its tensors 0.92 to
#: 1.46), unchanged 1; the limit leaves seven flipped signs among 64 (0.66)
#: and lies under every fp8 tensor's reading.
UPDATE_TOL = 0.7


def flops_per_token(cfg: dict, traffic: dict) -> float:
    return flops_gh.train_flops_per_token(cfg, traffic["seq_len"])


def attention_roofline_inputs(cfg: dict, traffic: dict) -> dict:
    """What the family's roofline readers need from shapes: the attention
    layers' heads and windows (``attn_roofline.train``: full causal, no
    window), and under ``ssd`` the Mamba layers' scans
    (``ssd_scan_roofline.train``)."""
    return dict(batch=traffic["batch"], seq_len=traffic["seq_len"],
                heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                windows=[None] * flops_gh.layer_counts(cfg)["attn"],
                ssd=dict(batch=traffic["batch"], seq_len=traffic["seq_len"],
                         layers=flops_gh.layer_counts(cfg)["mamba"],
                         **flops_gh.scan_shapes(cfg)))


def grad_tensors(cfg: dict) -> tuple:
    """One parameter of each kind, by name: of the first Mamba layer (whose
    gradient passes through every layer above it) ``A_log``, ``dt_bias``,
    ``D``, the convolution's taps, the in- and out-projections, the gated
    norm's scale and the MLP's ``W1``; the attention layer's query
    projection; the tied embedding."""
    kinds = cfg["layer_types"]
    m = f"layer{kinds.index('mamba')}_"
    a = f"layer{kinds.index('attention')}_"
    return (m + "mamba_A_log", m + "mamba_dt_bias", m + "mamba_D", m + "mamba_conv_weight",
            m + "mamba_in_proj_weight", m + "mamba_out_proj_weight", m + "mamba_norm_gamma",
            a + "attn_q_weight", m + "ffn_gate_weight", "embed_weight")


class TrainSystem:
    """``net`` + ``ShardedTrainer`` on a one-device mesh, on the chip's own
    context, as the other decoder families build theirs."""

    def __init__(self, cfg: dict, devices, seed: int):
        self.cfg = cfg
        self.ctx = context_for_device(devices[0])
        mx.random.seed(seed)
        opt = dict(cfg["optimizer"])
        with self.ctx:
            self.net = models.get_granite_hybrid(cfg, dtype=cfg["dtype"],
                                                 remat=cfg.get("remat", False))
            # the trainer differentiates the step as a function: the gluon
            # gradient buffers would hold another 1.5 GB for nothing
            self.net.collect_params().setattr("grad_req", "null")
            self.net.initialize(mx.init.Normal(cfg["init_std"]), ctx=self.ctx)
            self.trainer = parallel.ShardedTrainer(
                self.net, models.afmoe_lm_loss, opt.pop("name"), opt,
                mesh=parallel.make_mesh(devices=list(devices)), n_labels=1)

    def step(self, batch):
        """Enqueue one step on a host batch; returns the loss, not synced."""
        with self.ctx:
            loss = self.trainer.step(*batch)
        if self.trainer.num_update == 1:
            # the trainer's copies are the weights now; the chip has no room
            # for the block's own beside the step's temporaries (the checks
            # that read the block ran before this)
            self.trainer.release_block()
        return loss

    def _at(self, names) -> list:
        """Where each of ``names`` sits in the trainer's parameter order."""
        order = sorted(self.net.collect_params())
        return [order.index(self.net.prefix + name) for name in names]

    def step_half(self, batch, names) -> list:
        """``[(loss, {name: gradient}), ...]`` a row of ``batch``: the
        forward-backward half of the trainer's step, the function the
        compiled step is built from, jitted without the update, so the
        gradients asked for are the step's own. It runs a row at a time, at
        the timed step's own shape (one row of ``L``); ``rows_weighted``
        makes the batch's of them. Builds the trainer's state, which the
        first step would, and frees the block's own copy of the weights:
        beside the state and the step's temporaries the chip has no room for
        it (nothing reads the block after this)."""
        tr = self.trainer
        with self.ctx:
            tr.prepare(*_rows(batch)[0])
            tr.release_block()
            at = self._at(names)
            half = tr._make_loss_grads(len(batch) - 1)

            @jax.jit
            def some(*args):
                loss, _norm, grads, _effects, _taps = half(*args)
                return loss, [grads[i] for i in at]

            parts = []
            with active_mesh(tr.mesh):
                for row in _rows(batch):
                    r_loss, r_grads = jax.device_get(
                        some(tr._param_vals, tr._base_key, tr._t_dev, *tr.place(*row)))
                    parts.append((float(r_loss), dict(zip(names, r_grads))))
        return parts

    def step_update(self, row, names) -> dict:
        """``{name: change}``: what one real step of the trainer on ``row``
        (one row, the timed step's shape) does to each tensor of ``names``:
        to its fp32 master where the weight is kept in bf16, else to the
        weight. The trainer's first update: run after ``step_half``, before
        any other step."""
        tr = self.trainer
        at = self._at(names)
        _lr, _wd, masters = tr._per_param_hparams()

        def held():
            return [np.asarray(jax.device_get(
                tr._opt_states[i][0] if masters[i] else tr._param_vals[i]), "float64")
                for i in at]
        before = held()
        with self.ctx:
            tr.step(*row).wait_to_read()
        return {n: a - b for n, a, b in zip(names, held(), before)}

    def forward_readings(self, batch) -> dict:
        """The net's own forward on ``batch``: the final hidden state and the
        logits of its first ``LOGIT_BLOCK`` positions."""
        with self.ctx:
            args = [mx.nd.array(a, ctx=self.ctx, dtype=a.dtype) for a in batch]
            hidden, _valid = self.net.hidden(*args[:3])
            logits = np.asarray(self.net.head(hidden[:, :LOGIT_BLOCK]).asnumpy(), "float32")
            return dict(hidden=np.asarray(hidden.asnumpy(), "float32"), logits=logits)

    def reference_part(self, batch, operands=None) -> dict:
        """The plain reference's readings on ``batch`` on the block's own
        parameters (``operands``: the reference's lower-precision control,
        see ``reference.forward``), a row at a time: the batch's, and under
        ``update`` the AdamW change its gradients of the first row give
        (``reference.adamw_first_step``). Run while the block holds the
        parameters, before the trainer's state exists."""
        names = grad_tensors(self.cfg)
        prefix = self.net.prefix
        params = {k[len(prefix):]: p.data(self.ctx)._data
                  for k, p in self.net.collect_params().items()}

        @jax.jit          # the batch is an argument: a constant would change the program with the seed
        def ref(params, ids, pos, vl, lab):
            r_loss, out, r_grads = reference.loss_and_grads(
                params, self.cfg, ids, pos, vl, lab, names, operands)
            return (out["hidden"], r_loss, r_grads,
                    reference.logits(params, self.cfg, out["hidden"][:, :LOGIT_BLOCK], operands))

        outs = [jax.device_get(ref(params, *row)) for row in _rows(batch)]
        loss, grads = rows_weighted([(float(o[1]), o[2]) for o in outs], batch[2])
        update = {n: reference.adamw_first_step(jax.device_get(params[n]), outs[0][2][n],
                                                self.cfg["optimizer"]) for n in names}
        return dict(hidden=np.concatenate([o[0] for o in outs]),
                    logits=np.concatenate([o[3] for o in outs]), loss=loss, grads=grads,
                    update=update, keep=np.arange(batch[0].shape[1])[None, :] < batch[2][:, None])

    def reference_readings(self, batch) -> dict:
        """The reference's readings on ``batch`` beside the system's under
        ``system`` (module docstring): the reference first, then the net's
        forward, then the step's half, which frees the block, then the
        trainer's first step on the first row. Run before any other step."""
        names = grad_tensors(self.cfg)
        r = self.reference_part(batch)
        system = self.forward_readings(batch)
        system["loss"], system["grads"] = rows_weighted(self.step_half(batch, names), batch[2])
        system["update"] = self.step_update(_rows(batch)[0], names)
        return dict(r, system=system)

    def reference_check(self, batch) -> dict:
        return compare(self.reference_readings(batch))

    def program_check(self, batch, on_chip: bool) -> dict:
        """The compiled step itself: traced once, on the pjit path, with the
        kernels this model needs in it (the attention layer: flash forward,
        dkv and dq; a Mamba layer: ``ssd_fwd`` and ``ssd_bwd``), and the
        bytes it holds. The kernels are counted in the compiled program:
        the layers share one jitted scan each way, which the lowered module
        holds once."""
        tr = self.trainer
        with self.ctx, active_mesh(tr.mesh):
            compiled = tr._step_fn.lower(*tr.step_trace_args(*batch)).compile()
        calls = re.findall(r'^\s*%?(\S+) = .*custom_call_target="tpu_custom_call"',
                           compiled.as_text(), re.M)
        flash = sum("flash_" in name for name in calls)
        ssd_fwd = sum("ssd_fwd" in name for name in calls)
        ssd_bwd = sum("ssd_bwd" in name for name in calls)
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp", "generated_code")}
        n = flops_gh.layer_counts(self.cfg)
        return {"step_traces": tr._step_fn._cache_size(), "path": tr.last_path,
                "tpu_custom_calls": len(calls), "flash_calls": flash,
                "ssd_fwd_calls": ssd_fwd, "ssd_bwd_calls": ssd_bwd, "memory_analysis": mem,
                "program_bytes": (mem["argument"] + mem["output"] - mem["alias"]
                                  + mem["temp"] + mem["generated_code"]),
                "ok": bool(tr._step_fn._cache_size() == 1 and tr.last_path == "pjit"
                           and (not on_chip or (flash >= 3 * n["attn"]
                                                and ssd_fwd >= n["mamba"]
                                                and ssd_bwd >= n["mamba"])))}


def _rows(batch) -> list:
    """``batch`` a row at a time, each a batch of one."""
    return [tuple(a[i:i + 1] for a in batch) for i in range(len(batch[0]))]


def rows_weighted(parts, valid) -> tuple:
    """``(loss, {name: gradient})`` of a batch from its rows' ``parts``
    (``[(loss, {name: gradient}), ...]``, each a mean over the row's valid
    tokens), weighted by the rows' ``valid`` tokens: the batch's mean."""
    shares = np.asarray(valid, "float64") / float(np.sum(valid))
    loss = float(sum(w * l for w, (l, _g) in zip(shares, parts)))
    grads = {n: np.asarray(sum(w * np.asarray(g[n], "float64") for w, (_l, g) in zip(shares, parts)),
                           "float32") for n in parts[0][1]}
    return loss, grads


def _rms_err(got, want) -> float:
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return float(np.sqrt(((got - want) ** 2).sum() / max((want ** 2).sum(), 1e-300)))


def chunk_errors(got, want, keep, size: int = CHUNK_READ) -> np.ndarray:
    """``(rows, chunks)`` rms error of ``got`` against ``want`` ``(B, L, C)``
    over the valid positions of each ``size``-position chunk of each row;
    NaN where a chunk holds none."""
    B, L, _ = want.shape
    out = np.full((B, -(-L // size)), np.nan)
    for b in range(B):
        for j in range(out.shape[1]):
            rows = slice(j * size, (j + 1) * size)
            k = keep[b, rows]
            if k.any():
                out[b, j] = _rms_err(got[b, rows][k], want[b, rows][k])
    return out


def compare(r: dict, got: dict = None) -> dict:
    """``got`` (hidden state, logits block, loss, gradients, first update;
    by default the system's, ``r["system"]``) against the reference's
    readings ``r``, each beside its limit."""
    got = r["system"] if got is None else got
    keep = r["keep"]
    per_chunk = chunk_errors(got["hidden"], r["hidden"], keep)
    hidden_rms = float(np.nanmax(per_chunk))
    block = keep[:, :r["logits"].shape[1]]
    logit_rms = _rms_err(got["logits"][block], r["logits"][block])
    loss_err = abs(got["loss"] - r["loss"]) / abs(r["loss"])
    grad_err = {name: _rms_err(got["grads"][name], want) for name, want in r["grads"].items()}
    update_err = {name: _rms_err(got["update"][name], want) for name, want in r["update"].items()}
    return {"loss_system": got["loss"], "loss_reference": r["loss"],
            "loss_rel_err": loss_err, "hidden_rms_err": hidden_rms,
            "hidden_rms_err_worst_chunk": [int(i) for i in np.unravel_index(
                np.nanargmax(per_chunk), per_chunk.shape)],
            "hidden_rms_err_row": [_rms_err(got["hidden"][b][keep[b]], r["hidden"][b][keep[b]])
                                   for b in range(keep.shape[0])],
            "logits_rms_err": logit_rms, "grad_rms_err": grad_err,
            "update_rms_err": update_err,
            "ok": bool(hidden_rms <= HIDDEN_RMS_TOL and logit_rms <= LOGIT_RMS_TOL
                       and loss_err <= LOSS_RTOL and max(grad_err.values()) <= GRAD_TOL
                       and max(update_err.values()) <= UPDATE_TOL)}


def build_train(cfg: dict, devices, seed: int) -> TrainSystem:
    return TrainSystem(cfg, devices, seed)
