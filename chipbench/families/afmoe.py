"""The AFMoE family (Trinity-Mini): builds the system under test from a
configuration file.

Calls the program's normal entry points (``models.get_afmoe``,
``parallel.ShardedTrainer``, ``models.afmoe_lm_loss``) as
``families/bert.py`` does for BERT. Everything that judges the system
(reference, operation counts) is the benchmark's own.

A batch is ``(ids, positions, valid_length, labels)``: ``ids`` and ``labels``
``(B, L)`` int32 (the labels the next token of every position, from a row of
``L + 1`` tokens), ``positions`` ``(B, L)`` int32 for the rotary embedding,
``valid_length`` ``(B,)``. Element 2 is the rows' valid lengths and element 3
a per-row integer array that stays a valid label under ``% (3L/4)``: the two
conventions of the ``train_steps`` traffic kind.
"""
import jax
import jax.numpy as jnp
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.context import context_for_device
from incubator_mxnet_tpu.parallel.mesh import active_mesh

from chipbench import flops_afmoe
from chipbench.reference import afmoe as reference

#: The system (bf16 parameters, activations and matmul operands, fp32
#: accumulation, flash and grouped-matmul kernels) against the float32
#: reference on the same bf16-rounded weights, one seeded two-row batch (one
#: row padded to 3L/4). Each limit lies between two readings on the chip at
#: the published widths (PERF.md section 2 has them): what the
#: system gives, and what the reference itself gives with every matmul
#: operand rounded to fp8 (e4m3, the next precision below the stated bf16),
#: which has to fail.
#:
#: The final normed hidden state over the valid positions, two ways. Root
#: mean square of the difference over that of the reference: the four norms
#: a layer renormalise what each sub-block adds, so five layers of bf16
#: roundings (2^-8 each) leave a few percent. The largest difference over the
#: largest value follows the few tokens whose 8th and 9th expert swap (such a
#: token moves by a whole expert's output), so it reads several times higher
#: and says less; it is kept because it catches a single broken position.
HIDDEN_RMS_TOL = 0.1    # system 0.030-0.038 over 33 seeds, fp8 0.51
HIDDEN_TOL = 0.4        # system 0.17-0.26, fp8 0.58-0.62
#: the loss, a mean of 14,336 cross-entropies in fp32 near ln(vocabulary):
#: precision hardly moves it (system at most 4.9e-5, fp8 1.9e-5 to 6.3e-4),
#: so it has the limit of the harness's accepted cells (families/bert.py)
LOSS_RTOL = 5e-3
#: Routing, a MoE layer: the share of valid tokens whose set of 8 experts is
#: the reference's (system 0.87-0.95, falling with depth; fp8 0.015-0.04), and
#: the share of (token, layer) pairs that differ where the reference's margin
#: between the 8th and the 9th selection score is TIE_GAP or more. A near-tie
#: is the only disagreement a correct bf16 path makes but for a few tens of
#: pairs in 57,344 (system at most 1.2e-3: by the fourth MoE layer the hidden
#: state's 3% moves a score by up to 0.05); fp8 strays in 9e-2 of them.
ROUTE_AGREE = 0.7
TIE_GAP = 2e-2
STRAY_SHARE = 5e-3


#: The rows each held expert got in each step of the newest system built,
#: ``(MoE layers, experts held)`` a step, as the compiled step itself counted
#: them (the layers' ``expert_rows`` buffers): device arrays, read by the
#: routing readers after the window (``chipbench/afmoe_spans.py``).
STEP_ROWS = []


def train_batches(cfg: dict, traffic: dict, seed: int, n: int, batch: int) -> list:
    """``n`` host batches from ``seed``: rows of ``seq_len + 1`` ids uniform
    over the vocabulary held, every row full, the labels the next token."""
    rng = np.random.default_rng(seed)
    L, V = traffic["seq_len"], cfg["vocab_size"]
    positions = np.tile(np.arange(L, dtype="int32"), (batch, 1))
    out = []
    for _ in range(n):
        rows = rng.integers(0, V, (batch, L + 1)).astype("int32")
        out.append((rows[:, :L].copy(), positions.copy(),
                    np.full((batch,), L, "float32"), rows[:, 1:].copy()))
    return out


def flops_per_token(cfg: dict, traffic: dict) -> float:
    return flops_afmoe.train_flops_per_token(cfg, traffic["seq_len"])


def attention_roofline_inputs(cfg: dict, traffic: dict) -> dict:
    """What the family's roofline readers need from shapes: the attention
    of one step, and under ``moe`` the routed experts' sizes."""
    return dict(
        batch=traffic["batch"], seq_len=traffic["seq_len"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        windows=[cfg["sliding_window"] if kind == "sliding_attention" else None
                 for kind in cfg["layer_types"]],
        moe=dict(groups=cfg["experts_held"], hidden=cfg["hidden_size"],
                 ffn=cfg["moe_intermediate_size"],
                 layers=len(cfg["layer_types"]) - cfg["num_dense_layers"]))


class TrainSystem:
    """``net`` + ``ShardedTrainer`` on a one-device mesh, on the chip's own
    context, as ``families/bert.py`` builds BERT's."""

    def __init__(self, cfg: dict, devices, seed: int):
        self.cfg = cfg
        self.ctx = context_for_device(devices[0])
        mx.random.seed(seed)
        opt = dict(cfg["optimizer"])
        with self.ctx:
            self.net = models.get_afmoe(cfg, dtype=cfg["dtype"],
                                        remat=cfg.get("remat", False))
            # the trainer differentiates the step as a function: the gluon
            # gradient buffers would hold another 1.4 GB for nothing
            self.net.collect_params().setattr("grad_req", "null")
            self.net.initialize(mx.init.Normal(cfg["initializer_range"]), ctx=self.ctx)
            self.trainer = parallel.ShardedTrainer(
                self.net, models.afmoe_lm_loss, opt.pop("name"), opt,
                mesh=parallel.make_mesh(devices=list(devices)), n_labels=1)

    def step(self, batch):
        """Enqueue one step on a host batch; returns the loss, not synced."""
        with self.ctx:
            if self.trainer.num_update == 0:
                # the routing counters (mxtpu_moe_*) from the layers' own
                # routing function on this pool batch, in set-up: the
                # compiled step calls nothing on the host
                self.net.routing(*(mx.nd.array(a, ctx=self.ctx, dtype=a.dtype)
                                   for a in batch[:3]))
            loss = self.trainer.step(*batch)
            # this step's own count, kept as a device array: nothing is read
            # here, and a copy so that release_block() cannot take it back
            STEP_ROWS.append(jnp.stack(self.net.expert_rows(self.ctx)))
        if self.trainer.num_update == 1:
            # the trainer's copies are the weights now; at 705 M parameters
            # the chip has no room for the block's own beside the step's
            # temporaries (the checks that read the block ran before this)
            self.trainer.release_block()
        return loss

    def reference_check(self, batch) -> dict:
        """The net's own forward and the program's loss, and what each MoE
        layer's own routing function chose, against the plain reference on
        the same parameters. Run before the first step, while the block's
        parameters are the trainer's and the trainer's state does not exist."""
        ids, pos, vl, lab = batch
        with self.ctx:
            args = [mx.nd.array(a, ctx=self.ctx, dtype=a.dtype) for a in batch]
            hidden, valid = self.net.hidden(*args[:3])
            logits = self.net.lm_head(hidden)
            loss = float(models.afmoe_lm_loss((logits, valid), args[3]).asnumpy())
            hidden = np.asarray(hidden.asnumpy(), "float32")
            del logits
            routes = self.net.routing(*args[:3], publish=False)
        prefix = self.net.prefix
        params = {k[len(prefix):]: p.data(self.ctx)._data
                  for k, p in self.net.collect_params().items()}

        @jax.jit          # the batch is an argument: a constant would change the program with the seed
        def ref(params, ids, pos, vl, lab):
            out = reference.forward(params, self.cfg, ids, pos, vl)
            return (out["hidden"], reference.lm_loss(out["logits"], out["valid"], lab),
                    out["routes"])

        r_hidden, r_loss, r_routes = jax.device_get(ref(params, ids, pos, vl, lab))
        keep = np.arange(hidden.shape[1])[None, :] < vl[:, None]
        diff = (hidden - r_hidden)[keep]
        hidden_err = float(np.abs(diff).max() / np.abs(r_hidden[keep]).max())
        hidden_rms = float(np.sqrt((diff ** 2).mean() / (r_hidden[keep] ** 2).mean()))
        loss_err = abs(loss - float(r_loss)) / abs(float(r_loss))
        agree, stray, pairs, dropped, load, widest = [], 0, 0, 0, [], 0.0
        for mine, (r_idx, r_gap) in zip(routes, r_routes):
            same = (np.sort(np.asarray(mine["idx"]), 1) == np.sort(r_idx, 1)).all(1)
            same, gap = same[keep.reshape(-1)], r_gap[keep.reshape(-1)]
            agree.append(float(same.mean()))
            stray += int((~same & (gap >= TIE_GAP)).sum())       # differs, and no near-tie
            pairs += same.size
            widest = max(widest, float(gap[~same].max(initial=0.0)))
            dropped += int(mine["assignments_held"]) - int(mine["rows_placed"])
            counts = np.asarray(mine["counts"])
            load.append(float(counts.max() / max(counts.mean(), 1e-9)))
        return {"loss_system": loss, "loss_reference": float(r_loss),
                "loss_rel_err": loss_err, "hidden_rel_err": hidden_err,
                "hidden_rms_err": hidden_rms, "route_agree_share": agree,
                "route_stray_share": stray / max(pairs, 1), "route_widest_gap_differing": widest,
                "assignments_dropped": dropped, "expert_load_max_over_mean": load,
                "ok": bool(hidden_rms <= HIDDEN_RMS_TOL and hidden_err <= HIDDEN_TOL
                           and loss_err <= LOSS_RTOL and min(agree) >= ROUTE_AGREE
                           and stray <= STRAY_SHARE * pairs and dropped == 0)}

    def program_check(self, batch, on_chip: bool) -> dict:
        """The compiled step itself: traced once, on the pjit path, with the
        kernels this model needs in it (a layer: flash forward, dkv and dq;
        a MoE layer besides: the grouped matmuls, forward and both
        gradients), and the bytes it holds."""
        tr = self.trainer
        with self.ctx, active_mesh(tr.mesh):
            lowered = tr._step_fn.lower(*tr.step_trace_args(*batch))
            kernels = lowered.as_text().count("tpu_custom_call")
            ma = lowered.compile().memory_analysis()
        mem = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp", "generated_code")}
        layers = len(self.cfg["layer_types"])
        need = (3 * layers + 6 * (layers - self.cfg["num_dense_layers"])) if on_chip else 0
        return {"step_traces": tr._step_fn._cache_size(), "path": tr.last_path,
                "tpu_custom_calls": kernels, "memory_analysis": mem,
                "program_bytes": (mem["argument"] + mem["output"] - mem["alias"]
                                  + mem["temp"] + mem["generated_code"]),
                "ok": bool(tr._step_fn._cache_size() == 1 and tr.last_path == "pjit"
                           and kernels >= need)}


def build_train(cfg: dict, devices, seed: int) -> TrainSystem:
    STEP_ROWS.clear()
    return TrainSystem(cfg, devices, seed)
