"""The ``deepseek_v3`` family (Kanana-2-30B-A3B): builds the system under
test from a configuration file.

Calls the program's normal entry points (``models.get_deepseek_v3``,
``parallel.ShardedTrainer``, ``models.afmoe_lm_loss``) as ``families/afmoe.py``
does for its family, and on its plan: the batch layout ``(ids, positions,
valid_length, labels)``, the step and what it keeps are that file's
(``TrainSystem.step``, ``STEP_ROWS``: the routed half is the same code, so
``moe_gmm_roofline.train`` and ``moe_expert_load_max_over_mean.train`` read
this family's steps through the same list). Everything that judges the
system (reference, operation counts, limits) is the benchmark's own and this
family's.
"""
import re

import jax
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.context import context_for_device
from incubator_mxnet_tpu.parallel.mesh import active_mesh

from chipbench import flops_deepseek_v3
from chipbench.families import afmoe
from chipbench.families.afmoe import STEP_ROWS, train_batches  # noqa: F401
from chipbench.reference import deepseek_v3 as reference

#: The system (bf16 parameters, activations and matmul operands, fp32
#: accumulation, the latent-attention flash kernels, the grouped matmuls and
#: the row kernels) against the float32 reference on the same bf16-rounded
#: weights, one seeded two-row batch (one row padded to 3L/4). Each limit
#: lies between two readings on the chip at the published widths (PERF.md
#: section 2 has them): what the system gives over its seeds, and what the
#: reference itself gives with every matmul operand rounded to fp8 (e4m3, the
#: next precision below the stated bf16), which has to fail.
#:
#: The final normed hidden state over the valid positions, two ways: root
#: mean square of the difference over that of the reference, and the largest
#: difference over the largest value. The layer is pre-norm: nothing
#: renormalises what a sub-block adds, so six layers of bf16 roundings add
#: up in the residual stream and only the final norm rescales them; it reads
#: what Trinity's sandwich-norm layers read all the same. The largest
#: difference follows the few tokens whose 6th and 7th expert swap (such a
#: token moves by a whole expert's output): it reads five times higher and
#: says less, and is kept because it catches a single broken position.
HIDDEN_RMS_TOL = 0.1    # system 0.031-0.037 over 27 seeds, fp8 1.00-1.02
HIDDEN_TOL = 0.4        # system 0.158-0.233, fp8 1.01-1.18
#: the loss, a mean of 14,336 cross-entropies in fp32 near ln(vocabulary):
#: precision hardly moves it (system at most 6.0e-5, fp8 8.9e-5 to 8.2e-4),
#: so it has the limit of the harness's accepted cells (families/bert.py,
#: families/afmoe.py), a hundred times the reading
LOSS_RTOL = 5e-3
#: Routing, a MoE layer: the share of valid tokens whose set of 6 experts is
#: the reference's (system 0.95 in the first MoE layer falling to 0.895-0.907
#: in the fifth; fp8 0.009-0.010), and the share of (token, layer) pairs that
#: differ where the reference's margin between the 6th and the 7th selection
#: score is TIE_GAP or more. A near-tie is the only disagreement a correct
#: bf16 path makes but for some tens of pairs in 71,680 (system 6.0e-4 to
#: 1.13e-3, the widest such margin 0.06); fp8 strays in 0.141-0.148 of them.
ROUTE_AGREE = 0.7
TIE_GAP = 2e-2
STRAY_SHARE = 5e-3


def flops_per_token(cfg: dict, traffic: dict) -> float:
    return flops_deepseek_v3.train_flops_per_token(cfg, traffic["seq_len"])


def attention_roofline_inputs(cfg: dict, traffic: dict) -> dict:
    """What the family's roofline readers need from shapes: the latent
    attention of one step, and under ``moe`` the routed experts' sizes (the
    keys ``moe_gmm_roofline.train`` reads)."""
    return dict(
        batch=traffic["batch"], seq_len=traffic["seq_len"],
        heads=cfg["num_attention_heads"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        layers=cfg["num_hidden_layers"],
        moe=dict(groups=cfg["experts_held"], hidden=cfg["hidden_size"],
                 ffn=cfg["moe_intermediate_size"], layers=flops_deepseek_v3.moe_layers(cfg)))


class TrainSystem(afmoe.TrainSystem):
    """``net`` + ``ShardedTrainer`` on a one-device mesh, on the chip's own
    context. ``step`` is the AFMoE family's: routing counters from the
    layers' own routing function in set-up, each step's ``expert_rows`` kept
    in ``STEP_ROWS``, the block's copies released after the first step."""

    def __init__(self, cfg: dict, devices, seed: int):
        self.cfg = cfg
        self.ctx = context_for_device(devices[0])
        mx.random.seed(seed)
        opt = dict(cfg["optimizer"])
        with self.ctx:
            self.net = models.get_deepseek_v3(cfg, dtype=cfg["dtype"],
                                              remat=cfg.get("remat", False))
            # the trainer differentiates the step as a function: the gluon
            # gradient buffers would hold another 1.4 GB for nothing
            self.net.collect_params().setattr("grad_req", "null")
            self.net.initialize(mx.init.Normal(cfg["initializer_range"]), ctx=self.ctx)
            self.trainer = parallel.ShardedTrainer(
                self.net, models.afmoe_lm_loss, opt.pop("name"), opt,
                mesh=parallel.make_mesh(devices=list(devices)), n_labels=1)

    def reference_readings(self, batch, operands=None) -> dict:
        """The net's own forward and the program's loss, and what each MoE
        layer's own routing function chose, against the plain reference on
        the same parameters (``operands``: the reference's lower-precision
        control, see ``reference.forward``). Run before the first step,
        while the block's parameters are the trainer's and the trainer's
        state does not exist."""
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
            out = reference.forward(params, self.cfg, ids, pos, vl, operands)
            return (out["hidden"], reference.lm_loss(out["logits"], out["valid"], lab),
                    out["routes"])

        r_hidden, r_loss, r_routes = jax.device_get(ref(params, ids, pos, vl, lab))
        system = dict(hidden=hidden, loss=loss, dropped=sum(
            int(r["assignments_held"]) - int(r["rows_placed"]) for r in routes),
            idx=[np.asarray(r["idx"]) for r in routes],
            load=[float(np.asarray(r["counts"]).max() / max(np.asarray(r["counts"]).mean(), 1e-9))
                  for r in routes])
        return dict(system=system, hidden=r_hidden, loss=float(r_loss),
                    idx=[i for i, _ in r_routes], gap=[g for _, g in r_routes],
                    keep=np.arange(hidden.shape[1])[None, :] < vl[:, None])

    def reference_check(self, batch) -> dict:
        return compare(self.reference_readings(batch))

    def program_check(self, batch, on_chip: bool) -> dict:
        """The compiled step itself: traced once, on the pjit path, with the
        kernels this model needs in it (a layer: flash forward, dkv and dq;
        a MoE layer besides: the grouped matmuls, forward and both
        gradients), and the bytes it holds. The kernels are counted in the
        compiled program: the layers share one jitted routed half, which the
        lowered module holds once."""
        tr = self.trainer
        with self.ctx, active_mesh(tr.mesh):
            compiled = tr._step_fn.lower(*tr.step_trace_args(*batch)).compile()
        calls = re.findall(r'^\s*%?(\S+) = .*custom_call_target="tpu_custom_call"',
                           compiled.as_text(), re.M)
        flash = sum("flash_" in name for name in calls)
        gmm = sum(bool(re.search("moe_t?gmm", name)) for name in calls)
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp", "generated_code")}
        layers, moe = self.cfg["num_hidden_layers"], flops_deepseek_v3.moe_layers(self.cfg)
        return {"step_traces": tr._step_fn._cache_size(), "path": tr.last_path,
                "tpu_custom_calls": len(calls), "flash_calls": flash, "moe_gmm_calls": gmm,
                "memory_analysis": mem,
                "program_bytes": (mem["argument"] + mem["output"] - mem["alias"]
                                  + mem["temp"] + mem["generated_code"]),
                "ok": bool(tr._step_fn._cache_size() == 1 and tr.last_path == "pjit"
                           and (not on_chip or (flash >= 3 * layers and gmm >= 6 * moe)))}


def compare(r: dict, got: dict = None) -> dict:
    """``got`` (hidden state, loss and each MoE layer's experts; by default
    the system's, ``r["system"]``) against the reference's readings ``r``,
    each beside its limit."""
    got = r["system"] if got is None else got
    keep = r["keep"]
    diff = (got["hidden"] - r["hidden"])[keep]
    hidden_err = float(np.abs(diff).max() / np.abs(r["hidden"][keep]).max())
    hidden_rms = float(np.sqrt((diff ** 2).mean() / (r["hidden"][keep] ** 2).mean()))
    loss_err = abs(got["loss"] - r["loss"]) / abs(r["loss"])
    agree, stray, pairs, widest = [], 0, 0, 0.0
    for mine, r_idx, r_gap in zip(got["idx"], r["idx"], r["gap"]):
        same = (np.sort(mine, 1) == np.sort(r_idx, 1)).all(1)
        same, gap = same[keep.reshape(-1)], r_gap[keep.reshape(-1)]
        agree.append(float(same.mean()))
        stray += int((~same & (gap >= TIE_GAP)).sum())       # differs, and no near-tie
        pairs += same.size
        widest = max(widest, float(gap[~same].max(initial=0.0)))
    dropped = got.get("dropped", 0)
    return {"loss_system": got["loss"], "loss_reference": r["loss"],
            "loss_rel_err": loss_err, "hidden_rel_err": hidden_err,
            "hidden_rms_err": hidden_rms, "route_agree_share": agree,
            "route_stray_share": stray / max(pairs, 1), "route_widest_gap_differing": widest,
            "assignments_dropped": dropped, "expert_load_max_over_mean": got.get("load", []),
            "ok": bool(hidden_rms <= HIDDEN_RMS_TOL and hidden_err <= HIDDEN_TOL
                       and loss_err <= LOSS_RTOL and min(agree) >= ROUTE_AGREE
                       and stray <= STRAY_SHARE * pairs and dropped == 0)}


def build_train(cfg: dict, devices, seed: int) -> TrainSystem:
    STEP_ROWS.clear()
    return TrainSystem(cfg, devices, seed)
