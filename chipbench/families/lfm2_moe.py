"""The ``lfm2_moe`` family (LFM2-8B-A1B): builds the system under test from a
configuration file.

Calls the program's normal entry points (``models.get_lfm2_moe``,
``parallel.ShardedTrainer``, ``models.afmoe_lm_loss``) as ``families/afmoe.py``
does for its family, and on its plan: the batch layout ``(ids, positions,
valid_length, labels)``, the step and what it keeps are that file's
(``TrainSystem.step``, ``STEP_ROWS``: the routed half is the same code, so
``moe_gmm_roofline.train`` and ``moe_expert_load_max_over_mean.train`` read
this family's steps through the same list). Everything that judges the
system (reference, operation counts, limits) is the benchmark's own and this
family's.

**What decides ``correct``.** On one seeded two-row batch at the timed
sequence length (one row padded to 3L/4, so the key mask is exercised), the
float32 reference at ``highest`` on the same bf16-rounded weights against:
the net's own forward (final hidden state, logits on a block of positions,
each MoE layer's routing), and **the forward-backward half of the trainer's
step itself** (``ShardedTrainer._make_loss_grads``, the function the
compiled step is built from, jitted here without the update): its loss and
its gradients of one tensor of each kind (``grad_tensors``). Each limit
lies between two readings on the chip at the published widths (PERF.md
section 2 has them): what the system gives over its seeds, and what the
reference itself gives with every matmul operand rounded to fp8 (e4m3, the
next precision below the stated bf16), which fails five of the six.
"""
import re

import jax
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.context import context_for_device
from incubator_mxnet_tpu.parallel.mesh import active_mesh

from chipbench import flops_lfm2_moe
from chipbench.families import afmoe
from chipbench.families.afmoe import STEP_ROWS, train_batches  # noqa: F401
from chipbench.reference import lfm2_moe as reference

#: Readings on the chip at the published widths (my chip runs, PR 34; PERF.md
#: section 2): "system" over 18 seeds, "fp8" the reference with fp8 operands
#: against the reference proper over 3.
#:
#: The final normed hidden state over the valid positions: root mean square
#: of the difference over that of the reference. Pre-norm: five layers of
#: bf16 roundings add up in the residual stream and the final norm rescales
#: them. (The largest difference over the largest value, ``hidden_rel_err``,
#: is printed and decides nothing here: it follows the few tokens whose 4th
#: and 5th expert swap, system 0.15-0.24, and fp8 reads 0.24-0.26 beside it.)
HIDDEN_RMS_TOL = 0.08   # system 0.032-0.033, fp8 0.160-0.161
#: logits of the first LOGIT_BLOCK positions of each row through the tied
#: head, root mean square of the difference over that of the reference
LOGIT_BLOCK = 512
LOGIT_RMS_TOL = 0.08    # system 0.029-0.036, fp8 0.163-0.164
#: the loss of the step's own forward, a mean of 14,336 cross-entropies in
#: fp32 near ln(vocabulary): precision hardly moves it (system at most
#: 5.7e-5, fp8 1.3e-5 to 6.2e-5), so it has the limit of the harness's
#: accepted cells (families/bert.py, families/afmoe.py), a hundred times the
#: reading
LOSS_RTOL = 5e-3
#: Routing, a MoE layer: the share of valid tokens whose set of 4 experts is
#: the reference's (system 0.975 in the first MoE layer falling to 0.952-0.958
#: in the fourth; fp8 0.57-0.58), and the share of (token, layer) pairs that
#: differ where the reference's margin between the 4th and the 5th selection
#: score is TIE_GAP or more. Over 32 outputs with weights of 0.02 the scores
#: lie further apart than the siblings' 128, and a bf16 hidden state moves
#: one by up to 0.09: system 1.4e-3 to 2.0e-3 of 57,344 pairs, fp8 9.6e-2.
ROUTE_AGREE = 0.8
TIE_GAP = 2e-2
STRAY_SHARE = 1e-2
#: Gradients of the step's own backward pass, a tensor of each kind: the
#: Frobenius norm of the difference over that of the reference's gradient,
#: the largest of the nine. A gradient passes through every rounding of the
#: forward and of the backward pass, and the router's and an expert's follow
#: each token whose experts swap. System: projections, norms, embedding
#: 0.033-0.051, the convolution's taps 0.093-0.099, the experts 0.114-0.128,
#: the router 0.150-0.185 (the largest); fp8: 0.38 (the dense FFN) to 1.24 (the
#: query norm), the largest 1.05-1.24.
GRAD_TOL = 0.4


def flops_per_token(cfg: dict, traffic: dict) -> float:
    return flops_lfm2_moe.train_flops_per_token(cfg, traffic["seq_len"])


def attention_roofline_inputs(cfg: dict, traffic: dict) -> dict:
    """What the family's roofline readers need from shapes: under ``moe`` the
    routed experts' sizes (the keys ``moe_gmm_roofline.train`` reads), under
    ``short_conv`` the convolution layers' (``short_conv_roofline.train``)."""
    n = flops_lfm2_moe.layer_counts(cfg)
    return dict(
        batch=traffic["batch"], seq_len=traffic["seq_len"],
        moe=dict(groups=cfg["experts_held"], hidden=cfg["hidden_size"],
                 ffn=cfg["moe_intermediate_size"], layers=n["moe"]),
        short_conv=dict(batch=traffic["batch"], seq_len=traffic["seq_len"],
                        channels=cfg["hidden_size"], taps=cfg["conv_L_cache"],
                        layers=n["conv"]))


def grad_tensors(cfg: dict) -> tuple:
    """One parameter of each kind, by name: the taps and the in-projection
    of the first convolution layer that has experts, the attention layer's
    query projection and query norm, a MoE layer's router and experts, a
    layer norm, the dense FFN's down projection and the tied embedding."""
    kinds, n_dense = cfg["layer_types"], cfg["num_dense_layers"]
    conv = next(i for i, k in enumerate(kinds) if k == "conv" and i >= n_dense)
    attn = next(i for i, k in enumerate(kinds) if k == "full_attention")
    return (f"layer{conv}_conv_weight", f"layer{conv}_conv_in_proj_weight",
            f"layer{attn}_attn_q_weight", f"layer{attn}_attn_q_norm_gamma",
            f"layer{conv}_moe_router_weight", f"layer{conv}_moe_experts_w13",
            f"layer{conv}_norm1_gamma", "layer0_ffn_down_weight", "embed_weight")


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
            self.net = models.get_lfm2_moe(cfg, dtype=cfg["dtype"],
                                           remat=cfg.get("remat", False))
            # the trainer differentiates the step as a function: the gluon
            # gradient buffers would hold another 1 GB for nothing
            self.net.collect_params().setattr("grad_req", "null")
            self.net.initialize(mx.init.Normal(cfg["initializer_range"]), ctx=self.ctx)
            self.trainer = parallel.ShardedTrainer(
                self.net, models.afmoe_lm_loss, opt.pop("name"), opt,
                mesh=parallel.make_mesh(devices=list(devices)), n_labels=1)

    def step_half(self, batch, names) -> tuple:
        """``(loss, {name: gradient})`` of the forward-backward half of the
        trainer's step on ``batch``: the function the compiled step is built
        from, jitted without the update, so the gradients asked for are the
        step's own. Builds the trainer's state, which the first step would."""
        tr = self.trainer
        with self.ctx:
            tr.prepare(*batch)
            order = sorted(self.net.collect_params())
            at = [order.index(self.net.prefix + name) for name in names]
            half = tr._make_loss_grads(len(batch) - 1)

            @jax.jit
            def some(*args):
                loss, _norm, grads, _effects, _taps = half(*args)
                return loss, [grads[i] for i in at]

            with active_mesh(tr.mesh):
                loss, grads = jax.device_get(
                    some(tr._param_vals, tr._base_key, tr._t_dev, *tr.place(*batch)))
        return float(loss), {n: np.asarray(g, "float32") for n, g in zip(names, grads)}

    def reference_readings(self, batch, operands=None) -> dict:
        """The system's readings on ``batch`` (module docstring) beside the
        plain reference's on the same parameters (``operands``: the
        reference's lower-precision control, see ``reference.forward``). Run
        before the first step, while the block's parameters are the
        trainer's."""
        ids, pos, vl, lab = batch
        names = grad_tensors(self.cfg)
        with self.ctx:
            args = [mx.nd.array(a, ctx=self.ctx, dtype=a.dtype) for a in batch]
            hidden, _valid = self.net.hidden(*args[:3])
            logits = np.asarray(self.net.head(hidden[:, :LOGIT_BLOCK]).asnumpy(), "float32")
            hidden = np.asarray(hidden.asnumpy(), "float32")
            routes = self.net.routing(*args[:3], publish=False)
        loss, grads = self.step_half(batch, names)
        prefix = self.net.prefix
        params = {k[len(prefix):]: p.data(self.ctx)._data
                  for k, p in self.net.collect_params().items()}

        @jax.jit          # the batch is an argument: a constant would change the program with the seed
        def ref(params, ids, pos, vl, lab):
            r_loss, out, r_grads = reference.loss_and_grads(
                params, self.cfg, ids, pos, vl, lab, names, operands)
            return (out["hidden"], r_loss, out["routes"], r_grads,
                    reference.logits(params, out["hidden"][:, :LOGIT_BLOCK], operands))

        r_hidden, r_loss, r_routes, r_grads, r_logits = jax.device_get(
            ref(params, ids, pos, vl, lab))
        system = dict(hidden=hidden, logits=logits, loss=loss, grads=grads, dropped=sum(
            int(r["assignments_held"]) - int(r["rows_placed"]) for r in routes),
            idx=[np.asarray(r["idx"]) for r in routes],
            load=[float(np.asarray(r["counts"]).max() / max(np.asarray(r["counts"]).mean(), 1e-9))
                  for r in routes])
        return dict(system=system, hidden=r_hidden, logits=r_logits, loss=float(r_loss),
                    grads={n: np.asarray(g) for n, g in r_grads.items()},
                    idx=[i for i, _ in r_routes], gap=[g for _, g in r_routes],
                    keep=np.arange(hidden.shape[1])[None, :] < vl[:, None])

    def reference_check(self, batch) -> dict:
        return compare(self.reference_readings(batch))

    def program_check(self, batch, on_chip: bool) -> dict:
        """The compiled step itself: traced once, on the pjit path, with the
        kernels this model needs in it (an attention layer: flash forward,
        dkv and dq; a convolution layer: ``short_conv_fwd`` and
        ``short_conv_bwd``; a MoE layer: the grouped matmuls, forward and
        both gradients), and the bytes it holds. The kernels are counted in
        the compiled program: the layers share one jitted routed half and
        one jitted convolution each way, which the lowered module holds once."""
        tr = self.trainer
        with self.ctx, active_mesh(tr.mesh):
            compiled = tr._step_fn.lower(*tr.step_trace_args(*batch)).compile()
        calls = re.findall(r'^\s*%?(\S+) = .*custom_call_target="tpu_custom_call"',
                           compiled.as_text(), re.M)
        flash = sum("flash_" in name for name in calls)
        gmm = sum(bool(re.search("moe_t?gmm", name)) for name in calls)
        conv = sum("short_conv_" in name for name in calls)
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp", "generated_code")}
        n = flops_lfm2_moe.layer_counts(self.cfg)
        return {"step_traces": tr._step_fn._cache_size(), "path": tr.last_path,
                "tpu_custom_calls": len(calls), "flash_calls": flash, "moe_gmm_calls": gmm,
                "short_conv_calls": conv, "memory_analysis": mem,
                "program_bytes": (mem["argument"] + mem["output"] - mem["alias"]
                                  + mem["temp"] + mem["generated_code"]),
                "ok": bool(tr._step_fn._cache_size() == 1 and tr.last_path == "pjit"
                           and (not on_chip or (flash >= 3 * n["attn"] and gmm >= 6 * n["moe"]
                                                and conv >= 2 * n["conv"])))}


def _rms_err(got, want) -> float:
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    return float(np.sqrt(((got - want) ** 2).sum() / max((want ** 2).sum(), 1e-300)))


def compare(r: dict, got: dict = None) -> dict:
    """``got`` (hidden state, logits block, loss, gradients and each MoE
    layer's experts; by default the system's, ``r["system"]``) against the
    reference's readings ``r``, each beside its limit."""
    got = r["system"] if got is None else got
    keep = r["keep"]
    diff = (got["hidden"] - r["hidden"])[keep]
    hidden_err = float(np.abs(diff).max() / np.abs(r["hidden"][keep]).max())
    hidden_rms = _rms_err(got["hidden"][keep], r["hidden"][keep])
    block = keep[:, :r["logits"].shape[1]]
    logit_rms = _rms_err(got["logits"][block], r["logits"][block])
    loss_err = abs(got["loss"] - r["loss"]) / abs(r["loss"])
    grad_err = {name: _rms_err(got["grads"][name], want) for name, want in r["grads"].items()}
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
            "hidden_rms_err": hidden_rms, "logits_rms_err": logit_rms,
            "grad_rms_err": grad_err, "route_agree_share": agree,
            "route_stray_share": stray / max(pairs, 1), "route_widest_gap_differing": widest,
            "assignments_dropped": dropped, "expert_load_max_over_mean": got.get("load", []),
            "ok": bool(hidden_rms <= HIDDEN_RMS_TOL
                       and logit_rms <= LOGIT_RMS_TOL and loss_err <= LOSS_RTOL
                       and max(grad_err.values()) <= GRAD_TOL and min(agree) >= ROUTE_AGREE
                       and stray <= STRAY_SHARE * pairs and dropped == 0)}


def build_train(cfg: dict, devices, seed: int) -> TrainSystem:
    STEP_ROWS.clear()
    return TrainSystem(cfg, devices, seed)
