#!/usr/bin/env python
"""Fusion-aware, device-blind autotuner over the model-family configs.

TVM's argument (arXiv 1802.04799) applied to this runtime: the remaining
MFU lives in *searching* configuration space over the compiled graph,
not hand-picking one env recipe per round. This driver declares a
search space (remat policy × flash block size ×
batch/bucket geometry × embedding-gradient path), evaluates candidates
**in-process with zero XLA compiles** — every candidate is traced
(``ShardedTrainer.prepare`` + ``jax.make_jaxpr`` for train families, the
un-warmed ``CompiledModel`` for serving families) and priced by
``analysis.hlo.cost`` — and persists the winner per
``(family, mesh_shape, chip)`` into the CRC-manifested
:class:`~incubator_mxnet_tpu.autotune.AutotuneCache` that BOTH
``parallel.ShardedTrainer`` and ``serve.CompiledModel`` consult at build
time. The search is a deterministic function of the graph, so the same
space always elects the same winner — bankable and CI-gateable with no
hardware.

Score: a roofline proxy over the cost table plus the compile-ledger
dimensions (docs/architecture.md "Autotuning")::

    steady_s = max(flops/PEAK_FLOPS, hbm_bytes/PEAK_BW)
               + comm_bytes/ICI_BW + LAUNCH_S * fusion_groups
    warmup_s = COMPILE_S * graphs            # the ledger's warmup count
    score    = tokens_per_step / (steady_s + warmup_s / AMORTIZE_STEPS)

Candidates that cannot change the traced graph on this backend (e.g.
flash block sizes on CPU, where Pallas falls back to XLA attention) tie,
and the deterministic enumeration order breaks the tie — still the same
winner twice.

Memory feasibility: when ``MXTPU_HBM_BUDGET`` is set, every candidate's
whole-ladder residency (``analysis.hlo`` liveness scan,
``ladder_peak_bytes``) is checked against it and infeasible candidates
are scored-but-never-elected (reported, no silent caps) — the search
can expand batch/bucket geometry without proposing configs that OOM
the chip.

    python -m benchmark.autotune --families bert --budget 16 \
        --cache-dir autotune_cache
    python -m benchmark.autotune --families lenet --budget 6 \
        --cache-dir autotune_cache --gate      # the CI autotune-smoke job
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python benchmark/autotune.py` direct invocation
    sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# the search space — ONE declaration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Dim:
    """One tunable dimension: ``env`` knobs overlay the trace
    (``""`` = leave unset/auto), ``geom`` dims size the probe
    batch/bucket geometry, ``struct`` dims parameterize the model
    build (remat)."""

    name: str
    kind: str                    # "env" | "geom" | "struct"
    values: tuple
    env: Optional[str] = None    # the knob, for kind == "env"
    note: str = ""


#: the declared dimensions, in deterministic enumeration order
DIMS: Dict[str, Dim] = {d.name: d for d in (
    Dim("remat", "struct", (False, True),
        note="jax.checkpoint per encoder layer — trades recompute for HBM"),
    Dim("flash_bk", "env", ("", "128", "256", "512"), env="MXTPU_FLASH_BK",
        note="flash-attention key/value block size ('' = auto)"),
    Dim("embed_grad", "env", ("0", "1"), env="MXTPU_EMBED_ONEHOT_GRAD",
        note="embedding weight grad: scatter-add (0) vs one-hot matmul (1)"),
    Dim("batch", "geom", (2, 4, 8),
        note="probe batch size / batch-bucket geometry"),
    Dim("seq", "geom", (16, 32),
        note="probe sequence length / seq-bucket geometry"),
    Dim("quantize", "struct", ("off", "int8"),
        note="serving precision: float zoo vs calibrated int8 twin "
             "(models.quantized_smoke); candidates whose quantized "
             "graphs carry MX71x errors are scored but never elected"),
)}

#: per-family dimension subsets + probe kind. Train families score the
#: full fwd+bwd+optimizer step graph (the 0.40-MFU workload); serve-only
#: families score their bucketed inference graphs.
FAMILY_SPACES: Dict[str, Dict[str, Any]] = {
    "bert": {"kind": "train",
             "dims": ("remat", "flash_bk", "embed_grad", "batch", "seq")},
    "lenet": {"kind": "train", "dims": ("batch",)},
    "bert_encoder": {"kind": "serve",
                     "dims": ("flash_bk", "batch", "seq", "quantize")},
    "transformer_encoder": {"kind": "serve",
                            "dims": ("flash_bk", "batch", "seq")},
    "nmt_encoder": {"kind": "serve",
                    "dims": ("flash_bk", "embed_grad", "batch", "seq",
                             "quantize")},
}

def candidates(family: str,
               budget: Optional[int] = None) -> List[Dict[str, Any]]:
    """Deterministic candidate list: the cartesian product of the
    family's dimensions in declared order, truncated to ``budget``.
    Truncation is reported by the caller (no silent caps)."""
    space = FAMILY_SPACES[family]
    dims = [DIMS[n] for n in space["dims"]]
    out = [dict(zip((d.name for d in dims), combo))
           for combo in itertools.product(*(d.values for d in dims))]
    return out[:budget] if budget else out


# ---------------------------------------------------------------------------
# scoring — deterministic roofline proxy over the cost table
# ---------------------------------------------------------------------------

_LAUNCH_S = 2e-6                 # per fused-kernel dispatch overhead proxy
_COMPILE_S = 30.0                # per-graph warmup compile proxy (ledger)
_AMORTIZE_STEPS = 10000.0        # steps a banked config is expected to run


def _peaks() -> Tuple[float, float, float]:
    # THE shared peak table (util.roofline_peaks): this score and
    # telemetry.goodput's predicted_mfu read one source, so a chip-kind
    # correction can never diverge them
    from incubator_mxnet_tpu.util import roofline_peaks
    return roofline_peaks()


def score(metrics: Dict[str, Any],
          measured: Optional[Dict[str, float]] = None) -> float:
    """tokens/sec under the roofline proxy — higher is better. A pure
    function of the cost-table metrics and the (fixed) peak constants,
    so candidate ranking is deterministic by construction.

    ``measured`` folds a goodput window's attribution into the score
    (the flight director's rescoring hook, TVM's learned-cost-model
    argument in miniature): the window's ``collective`` / ``input_wait``
    / ``host`` wall fractions, priced relative to its ``compute``
    fraction, re-weight the analytic terms — measured communication can
    only *raise* the analytic comm estimate (the model stays a lower
    bound), and input/host time the analytic model assumes away is added
    outright. ``None`` (the default, and every pre-existing caller) is
    the original expression bit for bit."""
    peak_flops, peak_bw, ici_bw = _peaks()
    compute_s = metrics["flops_per_step"] / peak_flops
    mem_s = metrics["hbm_bytes_per_step"] / peak_bw
    comm_s = metrics["comm_bytes_per_step"] / ici_bw
    launch_s = _LAUNCH_S * metrics["fusion_groups"]
    device_s = max(compute_s, mem_s)
    steady_s = device_s + comm_s + launch_s
    if measured:
        f_comp = max(float(measured.get("compute", 0.0)), 1e-6)
        per_compute = device_s / f_comp   # 1.0 measured fraction in secs
        comm_meas = per_compute * float(measured.get("collective", 0.0))
        input_s = per_compute * float(measured.get("input_wait", 0.0))
        host_s = per_compute * float(measured.get("host", 0.0))
        steady_s = (device_s + max(comm_s, comm_meas) + input_s + host_s
                    + launch_s)
    warmup_s = _COMPILE_S * metrics["graphs"]
    return metrics["tokens_per_step"] / (steady_s
                                         + warmup_s / _AMORTIZE_STEPS)


# ---------------------------------------------------------------------------
# candidate evaluation — trace-only, zero XLA compiles
# ---------------------------------------------------------------------------

def _train_probe(family: str, cfg: Dict[str, Any], guarded: bool = False):
    """(trainer, batch, tokens) for a train-family candidate — tiny zoo
    instance at the candidate's geometry; ``prepare()`` below builds the
    step WITHOUT dispatching, so pricing it never XLA-compiles. Probe
    trainers live for one trace (or the 3-step gate replay) — nothing to
    checkpoint. ``guarded=True`` (the --gate replay) attaches a
    StepGuard AND an LR scheduler so the one-graph contract is actually
    exercised: an unfused regression would dispatch the separate jitted
    finite check and fail the graph count."""  # mxlint: disable-file=MX401
    import jax
    import numpy as onp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fault, gluon, lr_scheduler, models, \
        parallel

    B = int(cfg.get("batch", 2))
    L = int(cfg.get("seq", 16))
    mx.random.seed(11)
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    rng = onp.random.RandomState(0)
    extra: Dict[str, Any] = {}
    if guarded:
        extra["guard"] = fault.StepGuard(policy="warn")
    if family == "bert":
        vocab, P = 1000, max(1, round(0.15 * L))
        net = models.get_bert("bert_2_128_2", vocab_size=vocab,
                              max_length=32, dropout=0.1,
                              remat=bool(cfg.get("remat", False)))
        net.initialize()
        ids = rng.randint(0, vocab, (B, L)).astype("int32")
        tt = rng.randint(0, 2, (B, L)).astype("int32")
        vl = onp.full((B,), L, "float32")
        pos = rng.randint(0, L, (B, P)).astype("int32")
        mlm_lab = rng.randint(0, vocab, (B, P)).astype("float32")
        mlm_w = onp.ones((B, P), "float32")
        nsp = rng.randint(0, 2, (B,)).astype("float32")
        batch = (ids, tt, vl, pos, mlm_lab, mlm_w, nsp)
        opt_params: Dict[str, Any] = {"learning_rate": 1e-4}
        if guarded:
            opt_params["lr_scheduler"] = lr_scheduler.CosineScheduler(
                max_update=1000, base_lr=1e-4)
        trainer = parallel.ShardedTrainer(
            net, models.bert_pretrain_loss, "adamw",
            opt_params, mesh=mesh,
            rules=models.bert_sharding_rules(), n_labels=3,
            autotune_key="bert", **extra)
        return trainer, batch, B * L
    if family == "lenet":
        net = models.LeNet()
        net.initialize()
        ce = gluon.loss.SoftmaxCrossEntropyLoss()
        x = rng.rand(B, 1, 28, 28).astype("float32")
        y = rng.randint(0, 10, (B,)).astype("float32")
        opt_params = {"learning_rate": 0.05, "momentum": 0.9}
        if guarded:
            opt_params["lr_scheduler"] = lr_scheduler.FactorScheduler(
                step=100, factor=0.9, base_lr=0.05)
        trainer = parallel.ShardedTrainer(
            net, lambda out, label: ce(out, label), "sgd",
            opt_params, mesh=mesh, autotune_key="lenet", **extra)
        return trainer, (x, y), B
    raise KeyError(f"no train probe for family {family!r}")


def evaluate(family: str, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Price one candidate: apply its env dims for exactly the trace
    scope (forced — the driver measures the candidate, not the ambient
    shell), build the probe, and read the cost table. Returns the
    metrics dict :func:`score` consumes."""
    from incubator_mxnet_tpu import autotune as _cache_mod
    from incubator_mxnet_tpu import models
    from incubator_mxnet_tpu.analysis import hlo

    env = {DIMS[k].env: str(v) for k, v in cfg.items()
           if DIMS[k].kind == "env" and str(v) != ""}
    kind = FAMILY_SPACES[family]["kind"]
    quantized = str(cfg.get("quantize", "off")) == "int8"
    quant_errors = 0
    with _cache_mod.applied({"config": {"env": env}}, force=True):
        if kind == "train":
            trainer, batch, tokens = _train_probe(family, cfg)
            trainer.prepare(*batch)
            rep = hlo.cost(trainer, sample_args=batch)
        else:
            if quantized:
                smoke = models.quantized_smoke(family,
                                               batch=cfg.get("batch"),
                                               seq=cfg.get("seq"))
            else:
                smoke = models.hlo_smoke(family, batch=cfg.get("batch"),
                                         seq=cfg.get("seq"))
            max_g = max(8, smoke["table"].num_buckets())
            rep = hlo.cost(smoke["compiled"], max_graphs=max_g)
            if quantized:
                # precision-flow gate: an int8 candidate whose graphs
                # carry MX71x errors (silent promotion, missing
                # calibration, q/dq hazards) is priced like any other
                # but marked dirty — search() never elects it
                qrep = hlo.verify(smoke["compiled"], max_graphs=max_g)
                quant_errors = sum(1 for d in qrep.errors
                                   if d.code.startswith("MX71"))
            tokens = (int(cfg.get("batch") or 2)
                      * int(cfg.get("seq") or 16))
    head = rep.head
    if head is None:
        raise RuntimeError(f"candidate {cfg} traced zero graphs for "
                           f"{family!r} (skipped: {rep.skipped})")
    return {
        "flops_per_step": rep.model_flops_per_step(),
        "bytes_per_step": rep.bytes_per_step(),
        "hbm_bytes_per_step": rep.bytes_per_step() + head.activation_bytes,
        "comm_bytes_per_step": rep.comm_bytes_per_step(),
        "fusion_groups": head.fusion_groups,
        "fusion_candidates": head.fusion_candidates,
        "graphs": len(rep.rows),
        "tokens_per_step": tokens,
        # residency (liveness scan): the worst graph's peak and the
        # whole-ladder footprint — what the memory-feasibility
        # constraint checks against MXTPU_HBM_BUDGET
        "peak_live_bytes": rep.peak_live_bytes(),
        "ladder_peak_bytes": rep.ladder_peak_bytes(),
        # MX71x error count over the quantized graphs (0 for float
        # candidates) — the precision-flow feasibility input
        "quant_errors": quant_errors,
    }


def winner_config(family: str, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The cache-entry config for one winning candidate: env knobs under
    ``env`` (what ``autotune.applied`` overlays at build time), probe
    geometry and structural choices recorded alongside for the operator."""
    env = {DIMS[k].env: str(v) for k, v in cfg.items()
           if DIMS[k].kind == "env" and str(v) != ""}
    geometry = {k: v for k, v in cfg.items() if DIMS[k].kind == "geom"}
    struct = {k: v for k, v in cfg.items() if DIMS[k].kind == "struct"}
    return {"env": env, "geometry": geometry, "struct": struct}


def search(family: str, budget: Optional[int] = None, cache=None,
           mesh_key: str = "any",
           measured: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
    """Evaluate the family's candidate list and (optionally) bank the
    winner. Deterministic: same space + budget → same winner, twice
    (``measured`` is part of that determinism key — a fixed attribution
    dict re-ranks the same rows the same way; ``None`` leaves every
    result byte-identical to the pre-rescoring search)."""
    from incubator_mxnet_tpu import autotune as _cache_mod
    from incubator_mxnet_tpu import telemetry

    space = FAMILY_SPACES[family]
    full = candidates(family)
    cand = candidates(family, budget)
    # memory-feasibility constraint: a candidate whose whole-ladder
    # residency (liveness scan, deterministic) exceeds MXTPU_HBM_BUDGET
    # is scored but NEVER elected — the search can expand geometry
    # without proposing configs that OOM the chip. Unset budget =
    # unconstrained (the pre-memory-gate behavior, bit for bit).
    from incubator_mxnet_tpu.telemetry import memory as _memory
    hbm_budget = _memory.hbm_budget()
    rows = []
    for cfg in cand:
        metrics = evaluate(family, cfg)
        mem_ok = (hbm_budget is None
                  or metrics["ladder_peak_bytes"] <= hbm_budget)
        # MX711-dirty (or any MX71x-error) int8 candidate: scored,
        # reported, never elected — same contract as the memory gate
        quant_ok = metrics.get("quant_errors", 0) == 0
        rows.append({"config": dict(cfg), "metrics": metrics,
                     "score": score(metrics, measured=measured),
                     "feasible": mem_ok and quant_ok})
    feasible_i = [i for i, r in enumerate(rows) if r["feasible"]]
    if not feasible_i:
        if hbm_budget is None:
            raise RuntimeError(
                f"autotune: every candidate of {family!r} failed the "
                "MX71x precision-flow gate — recalibrate the quantized "
                "zoo or drop the quantize dim")
        raise RuntimeError(
            f"autotune: every candidate of {family!r} exceeds the "
            f"{hbm_budget / 2**20:.1f} MiB MXTPU_HBM_BUDGET (smallest "
            f"ladder peak "
            f"{min(r['metrics']['ladder_peak_bytes'] for r in rows) / 2**20:.1f}"
            " MiB) — shrink the declared geometry dims or raise the budget")
    best_i = max(feasible_i, key=lambda i: (rows[i]["score"], -i))
    best = rows[best_i]
    result = {
        "family": family, "kind": space["kind"],
        "dims": list(space["dims"]),
        "evaluated": len(rows), "space_size": len(full),
        "truncated": len(full) - len(cand),   # no silent caps
        "infeasible": len(rows) - len(feasible_i),
        "quant_infeasible": sum(
            1 for r in rows if r["metrics"].get("quant_errors", 0)),
        "hbm_budget": hbm_budget,
        "winner": best["config"], "winner_score": best["score"],
        "winner_metrics": best["metrics"],
        "rows": rows,
        "chip": _cache_mod.chip_kind(), "mesh": mesh_key,
    }
    if measured is not None:
        result["measured"] = dict(measured)
    if cache is not None:
        meta = {"dims": list(space["dims"]), "evaluated": len(rows),
                "space_size": len(full), "driver": "benchmark.autotune"}
        if measured is not None:
            meta["measured"] = dict(measured)
        result["cache_path"] = cache.put(
            family, mesh_key, _cache_mod.chip_kind(),
            winner_config(family, best["config"]), best["score"],
            meta=meta)
    telemetry.emit("autotune.search", family=family,
                   evaluated=len(rows), space_size=len(full),
                   infeasible=result["infeasible"], hbm_budget=hbm_budget,
                   winner=best["config"], score=best["score"],
                   banked=result.get("cache_path"))
    return result


# ---------------------------------------------------------------------------
# --gate: the CI autotune-smoke contract
# ---------------------------------------------------------------------------

def gate(family: str, cache_dir: str, result: Dict[str, Any]) -> List[str]:
    """Replay the banked winner through the REAL consult path and return
    a list of failures (empty = green): the cache entry must verify, the
    fresh build must consult it (hit), the tuned steady state must add
    zero post-warmup compiles on the ledger, and the consult event must
    carry the build site (ledger attribution)."""
    from incubator_mxnet_tpu import autotune as _cache_mod
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.telemetry import compile_log

    failures: List[str] = []
    cache = _cache_mod.AutotuneCache(cache_dir)
    entry = cache.get(family, "any")
    if entry is None:
        return [f"no verified cache entry for {family!r} under "
                f"{cache_dir}"]
    prev = os.environ.get("MXTPU_AUTOTUNE_DIR")
    os.environ["MXTPU_AUTOTUNE_DIR"] = cache_dir
    try:
        kind = FAMILY_SPACES[family]["kind"]
        site = "trainer.step" if kind == "train" else "serve.compiled"
        if kind == "train":
            # guarded=True: the replay trainer carries a StepGuard + LR
            # scheduler, so "exactly one jitted graph per step" is a
            # real check — an unfused regression dispatches the separate
            # finite check and fails the count
            trainer, batch, _ = _train_probe(family, result["winner"],
                                             guarded=True)
            trainer.step(*batch)              # build + ONE warmup compile
            if trainer.autotune_entry is None:
                failures.append("trainer did not consult the cache "
                                "(autotune_entry is None)")
            compile_log.mark_warmed(site)
            for _ in range(2):
                trainer.step(*batch)
            if trainer.last_step_graphs != 1:
                failures.append(
                    f"fused step ran {trainer.last_step_graphs} graphs "
                    "per step (expected 1)")
            if not trainer._lr_fold:
                failures.append("LR schedule was not folded into the "
                                "step graph (whole-step capture broken)")
        else:
            from incubator_mxnet_tpu import models
            smoke = models.hlo_smoke(family)
            cm = smoke["compiled"]
            if cm.autotune_entry is None:
                failures.append("CompiledModel did not consult the cache "
                                "(autotune_entry is None)")
            cm.warmup()
            compile_log.mark_warmed(site)
            cm.predict(*smoke["example_args"])
        try:
            compile_log.assert_zero_post_warmup(site)
        except Exception as e:   # MXNetError with the offending records
            failures.append(f"post-warmup compile at {site}: {e}")
        consults = [e for e in telemetry.get_events("autotune.consult")
                    if e.fields.get("site") == site
                    and e.fields.get("model") == family
                    and e.fields.get("outcome") == "hit"]
        if not consults:
            failures.append(f"no autotune.consult hit event for "
                            f"site={site} model={family}")
    finally:
        if prev is None:
            os.environ.pop("MXTPU_AUTOTUNE_DIR", None)
        else:
            os.environ["MXTPU_AUTOTUNE_DIR"] = prev
    return failures


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchmark/autotune.py",
        description="device-blind config search over the model families")
    ap.add_argument("--families", default="bert",
                    help="comma-separated families, or 'all' "
                         f"(known: {sorted(FAMILY_SPACES)})")
    ap.add_argument("--budget", type=int, default=None,
                    help="max candidates per family (deterministic "
                         "truncation; default MXTPU_AUTOTUNE_BUDGET)")
    ap.add_argument("--cache-dir", default=None,
                    help="bank each family's winner into this "
                         "AutotuneCache root")
    ap.add_argument("--mesh", default="any",
                    help="mesh_shape key to bank under (default 'any' — "
                         "the consult fallback every build matches)")
    ap.add_argument("--gate", action="store_true",
                    help="after the search, replay each winner through "
                         "the real consult path and fail on a missing "
                         "cache entry, a post-warmup compile, or a "
                         "missing consult event (the CI autotune-smoke "
                         "contract)")
    ap.add_argument("--out", default=None,
                    help="write the full result JSON here")
    args = ap.parse_args(argv)

    # device-blind by design: pin cpu so the search never takes the chip,
    # which one process owns at a time
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=1").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

    if args.families == "all":
        families = sorted(FAMILY_SPACES)
    else:
        families = [f.strip() for f in args.families.split(",") if f.strip()]
        unknown = [f for f in families if f not in FAMILY_SPACES]
        if unknown:
            print(f"autotune: unknown families {unknown}; known: "
                  f"{sorted(FAMILY_SPACES)}", file=sys.stderr)
            return 2
    budget = args.budget
    if budget is None:
        budget = int(os.environ.get("MXTPU_AUTOTUNE_BUDGET", "16"))

    from incubator_mxnet_tpu import autotune as _cache_mod
    cache = (_cache_mod.AutotuneCache(args.cache_dir)
             if args.cache_dir else None)
    results, failures = {}, []
    for fam in families:
        res = search(fam, budget=budget, cache=cache, mesh_key=args.mesh)
        if res["truncated"]:
            print(f"autotune: {fam}: budget {budget} evaluated "
                  f"{res['evaluated']}/{res['space_size']} candidates "
                  f"(deterministic prefix)", file=sys.stderr)
        if res["infeasible"]:
            print(f"autotune: {fam}: {res['infeasible']}/{res['evaluated']}"
                  " candidate(s) excluded by the MXTPU_HBM_BUDGET "
                  "memory-feasibility constraint "
                  f"({res['hbm_budget']} bytes)", file=sys.stderr)
        if res["quant_infeasible"]:
            print(f"autotune: {fam}: {res['quant_infeasible']}/"
                  f"{res['evaluated']} candidate(s) excluded by the "
                  "MX71x precision-flow gate (dirty quantized graphs)",
                  file=sys.stderr)
        results[fam] = res
        if args.gate:
            if not args.cache_dir:
                failures.append(f"{fam}: --gate needs --cache-dir")
            else:
                failures.extend(f"{fam}: {f}"
                                for f in gate(fam, args.cache_dir, res))

    summary = {
        "metric": "autotune_winner_score",
        "value": {f: r["winner_score"] for f, r in results.items()},
        "unit": "proxy tokens/sec (roofline score)",
        "vs_baseline": None,
        "extra": {"winners": {f: r["winner"] for f, r in results.items()},
                  "evaluated": {f: r["evaluated"]
                                for f, r in results.items()},
                  "banked": {f: r.get("cache_path")
                             for f, r in results.items()},
                  "gate_failures": failures},
    }
    if args.out:
        tmp = f"{args.out}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"summary": summary, "results": results}, f,
                      indent=1, sort_keys=True, default=str)
            f.write("\n")
        os.replace(tmp, args.out)
    for fail in failures:
        print(f"autotune: GATE FAIL {fail}", file=sys.stderr)
    print(json.dumps(summary))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
