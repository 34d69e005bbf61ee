"""CompiledModel — a frozen, bucket-compiled inference callable.

Reference counterpart: ``CachedOp`` in inference mode (``src/imperative/
cached_op.cc``) — capture the graph once, replay it per request. The jit
equivalent adds one production hazard the reference never had: *every new
input shape is a fresh XLA compile*, seconds of latency injected into a
random unlucky request. :class:`CompiledModel` closes that hole:

- inputs quantize onto a :class:`~incubator_mxnet_tpu.serve.buckets
  .BucketTable` (powers-of-two padding on the named axes);
- :meth:`warmup` AOT-compiles **every** bucket combination up front
  (``jax.jit(...).lower(...).compile()``), so steady-state traffic never
  traces;
- a hit/miss/compile counter makes the "zero post-warmup recompiles"
  contract *assertable* — a post-warmup compile is a bug (unbucketed shape
  reaching the model), not a silent latency spike;
- input buffers are donated to the executable on accelerator backends
  (requests are one-shot buffers; parameters are not donated).

Two model sources compile identically: a live :class:`gluon.HybridBlock`
(traced through the same inference-mode pure function ``export()``
serializes) and a cold-loaded :class:`gluon.SymbolBlock` artifact (one
fixed-shape StableHLO per bucket, written by :func:`export_for_serving`).
Parameters ride as call arguments, so :meth:`refresh_params` swaps model
versions in place with **zero** recompiles.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import SingleDeviceSharding

from ..base import MXNetError
from ..context import current_context
from ..lockcheck import make_rlock
from ..ndarray import NDArray
from .. import profiler
from .buckets import BucketTable

__all__ = ["CompiledModel", "export_for_serving"]


def _as_numpy(x) -> onp.ndarray:
    if isinstance(x, NDArray):
        return x.asnumpy()
    return onp.asarray(x)


class CompiledModel:
    """Bucket-compiled inference over a Block or an exported artifact.

    ``input_axes``: one ``{axis_index: bucket_axis_name}`` dict per array
    input, mapping the dims that get padded (e.g. BERT:
    ``[{0: "batch", 1: "seq"}, {0: "batch", 1: "seq"}, {0: "batch"},
    {0: "batch"}]``). Unmapped dims keep the example signature's size.

    ``output_axes``: same shape per output; default pads every output's
    axis 0 back from the ``"batch"`` bucket (or the table's first axis).

    ``pad_values``: scalar or one scalar per input (e.g. pad
    ``valid_length`` with 0 so attention masks the fake rows).

    ``donate``: ``"auto"`` donates request buffers to XLA on non-CPU
    backends only (CPU does not support donation and would warn per call).
    """

    def __init__(self, block, table: BucketTable,
                 input_axes: Sequence[Dict[int, str]],
                 example_args: Optional[Sequence] = None,
                 output_axes: Optional[Sequence[Dict[int, str]]] = None,
                 pad_values: Any = 0, donate: Any = "auto", ctx=None,
                 autotune_key: Optional[str] = None):
        from ..gluon.block import HybridBlock, SymbolBlock
        self._table = table
        self._input_axes = [dict(a) for a in input_axes]
        self._output_axes = ([dict(a) for a in output_axes]
                             if output_axes is not None else None)
        self._ctx = ctx or current_context()
        self._lock = make_rlock("CompiledModel._lock")
        self._exe: Dict[tuple, Callable] = {}
        self.stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "compiles": 0, "warmup_compiles": 0,
            "post_warmup_compiles": 0}
        self._warmed = False
        self._block = block
        # donation *intent* ("auto"/True/False), kept apart from the
        # backend-resolved argnums so mx.analysis.hlo can reason about the
        # accelerator deployment even when staging runs on CPU
        self._donate_requested = donate
        # build-time autotune consult (MXTPU_AUTOTUNE_DIR): a banked
        # winner's env knobs overlay every bucket's trace+compile in
        # _compile — same contract as ShardedTrainer, under the serving
        # ledger site "serve.compiled"
        from .. import autotune as _autotune
        # the resolved key is kept so a derived build (e.g.
        # quantization.quantize_model's int8 twin) can inherit it and
        # keep consulting the same banked winner
        self._autotune_key = autotune_key or type(block).__name__.lower()
        self.autotune_entry = _autotune.consult(
            "serve.compiled", self._autotune_key)
        # in-graph numerics telemetry (MXTPU_NUMERICS, resolved ONCE at
        # build like the autotune consult): when enabled every bucket's
        # executable additionally returns per-site stat vectors —
        # numerics.tap()-tagged activations plus each output tensor
        # (serve.out:<i>) — computed in-graph over the padded bucket
        # tensors; predict() syncs them every cfg.every requests
        from ..telemetry import numerics as _numerics
        self._numerics_cfg = _numerics.config()
        self._num_seen = 0           # predict-call decimation counter

        if isinstance(block, SymbolBlock):
            arch = block._arch
            if not block._sigs:
                raise MXNetError("artifact has no StableHLO graphs; "
                                 "re-export with HybridBlock.export()")
            self._mode = "artifact"
            self._n_in = arch["n_inputs"]
            self._in_avals = [(tuple(s), str(d))
                              for s, d in block._sigs[0]["in_avals"]]
            self._key_impl = arch["key"]["impl"]
            self._key_data = onp.asarray(jax.random.key_data(
                jax.random.key(0, impl=self._key_impl)))
            self._param_order = list(arch["param_order"])
        elif isinstance(block, HybridBlock):
            self._mode = "block"
            if getattr(block, "_last_sig", None) is None:
                if example_args is None:
                    raise MXNetError(
                        "CompiledModel over a live block needs either a "
                        "prior hybridized forward or example_args to "
                        "establish the call signature")
                if not block._active:
                    block.hybridize()
                block(*example_args)  # warm-up: deferred init + signature
            skeleton, n_in, in_avals, ctx0 = block._last_sig
            self._skeleton, self._n_in = skeleton, n_in
            self._in_avals = [(tuple(s), str(d)) for s, d in in_avals]
            self._ctx = ctx or ctx0
            from .. import random as random_mod
            self._key_impl = random_mod._impl()
            self._key_data = onp.asarray(jax.random.key_data(
                jax.random.key(0, impl=self._key_impl)))
            self._pure, self._meta = block._make_pure_infer(
                skeleton, n_in, self._ctx)
            if self._numerics_cfg.enabled:
                self._pure = self._wrap_pure_stats(self._pure)
            if donate == "auto":
                donate = jax.default_backend() != "cpu"
            self._jit = jax.jit(
                self._pure,
                donate_argnums=(tuple(range(1, 1 + n_in)) if donate else ()))
        else:
            raise MXNetError(f"CompiledModel cannot wrap {type(block)}; "
                             "pass a HybridBlock or a SymbolBlock artifact")
        if len(self._input_axes) != self._n_in:
            raise MXNetError(
                f"input_axes has {len(self._input_axes)} entries but the "
                f"model takes {self._n_in} array inputs")
        for spec in self._input_axes:
            for name in spec.values():
                if name not in table.axes:
                    raise MXNetError(f"input_axes names bucket axis "
                                     f"{name!r} not in {table!r}")
        for spec, (shape, _d) in zip(self._input_axes, self._in_avals):
            for axis in spec:
                if axis >= len(shape):
                    raise MXNetError(
                        f"input_axes maps axis {axis} but the recorded "
                        f"input has shape {shape}")
        if onp.isscalar(pad_values) or pad_values is None:
            pad_values = [pad_values or 0] * self._n_in
        self._pad_values = list(pad_values)
        if len(self._pad_values) != self._n_in:
            raise MXNetError(
                f"pad_values has {len(self._pad_values)} entries but the "
                f"model takes {self._n_in} array inputs")
        self._primary_axis = ("batch" if "batch" in table.axes
                              else sorted(table.axes)[0])
        self._pvals = None
        self.refresh_params()
        # attribute this model's resident weight buffers on the
        # device-memory ledger (weak provider: an unloaded version
        # drops off the ledger when the registry lets go of it)
        from ..telemetry import memory as _memory
        self._mem_unregister = _memory.register_site(
            "serve.compiled", self._resident_bytes)

    def _resident_bytes(self) -> int:
        """Device bytes this compiled model pins between requests (the
        weight buffers shared by every warmed bucket) — the
        ``serve.compiled`` site of the ``telemetry.memory`` ledger."""
        with self._lock:
            pvals = self._pvals or ()
            return sum(int(getattr(p, "nbytes", 0) or 0) for p in pvals)

    # -- parameters ----------------------------------------------------
    def refresh_params(self) -> None:
        """Re-read parameter values from the wrapped block — the version
        swap path. Shapes must match the compiled graphs, so this never
        recompiles."""
        with self._lock:
            if self._mode == "artifact":
                try:
                    self._pvals = [self._block._param_arrays[n]._data
                                   for n in self._param_order]
                except KeyError as e:
                    raise MXNetError(f"artifact is missing parameter {e}; "
                                     "pass param_file to imports()") from e
            else:
                self._pvals = [p.data(self._ctx)._data
                               for p in self._block._cached_params]

    # -- bucketing ------------------------------------------------------
    def signature_for(self, assignment: Dict[str, int]
                      ) -> List[Tuple[tuple, str]]:
        """Input (shape, dtype) list for one bucket assignment."""
        sig = []
        for (shape, dtype), spec in zip(self._in_avals, self._input_axes):
            s = list(shape)
            for axis, name in spec.items():
                s[axis] = assignment[name]
            sig.append((tuple(s), dtype))
        return sig

    def _sizes_of(self, arrays: Sequence[onp.ndarray]) -> Dict[str, int]:
        sizes: Dict[str, int] = {}
        for a, spec in zip(arrays, self._input_axes):
            for axis, name in spec.items():
                if axis >= a.ndim:
                    raise MXNetError(
                        f"input has rank {a.ndim} but input_axes maps "
                        f"axis {axis}")
                sizes[name] = max(sizes.get(name, 0), a.shape[axis])
        return sizes

    # -- numerics -------------------------------------------------------
    def _wrap_pure_stats(self, base: Callable) -> Callable:
        """Wrap the pure inference function so the SAME compiled
        executable also returns the per-site numerics stats —
        ``numerics.tap()``-tagged activations collected during the
        trace plus one ``serve.out:<i>`` site per output — as a second
        (replicated, scalar-sized) result. One executable per bucket
        still; stats are in-graph reductions, never host callbacks."""
        cfg = self._numerics_cfg

        def pure_stats(key_data, *vals):
            from ..telemetry import numerics as _numerics
            with _numerics.collecting(cfg) as col:
                outs = tuple(base(key_data, *vals))
            stats = dict(zip(col.names, col.values))
            for i, o in enumerate(outs):
                site = f"serve.out:{i}"
                if cfg.wants(site):
                    stats[site] = _numerics.graph_stats(o, cfg)
            return outs, stats

        return pure_stats

    def _maybe_record_numerics(self, stats_dev) -> None:
        """Host half of serve numerics: decimated by request count
        (``cfg.every``), the stat arrays sync and fold into the rings/
        gauges/events exactly like the trainer's."""
        cfg = self._numerics_cfg
        with self._lock:
            self._num_seen += 1
            due = (self._num_seen - 1) % cfg.every == 0
            seen = self._num_seen
        if not due:
            return
        from ..telemetry import numerics as _numerics
        _numerics.record("serve.compiled", seen,
                         jax.device_get(stats_dev), cfg)

    # -- compilation ----------------------------------------------------
    def _compile(self, key: tuple, sig) -> Callable:
        from .. import autotune as _autotune
        t0 = time.perf_counter()
        # compiled for the device the parameters live on (an AOT
        # executable refuses arguments committed anywhere else): a model
        # initialized on mx.tpu(1) serves from chip 1, not from the
        # process default device
        dev = (next(iter(self._pvals[0].devices())) if self._pvals
               else self._ctx.jax_device)
        sds = functools.partial(jax.ShapeDtypeStruct,
                                sharding=SingleDeviceSharding(dev))
        avals = [sds(self._key_data.shape, self._key_data.dtype)]
        avals += [sds(s, jnp.dtype(d)) for s, d in sig]
        avals += [sds(p.shape, p.dtype) for p in self._pvals]
        from ..telemetry import compile_log
        with _autotune.applied(self.autotune_entry), \
                compile_log.at("serve.compiled"):
            # the trace reads tunable env knobs (flash block sizes,
            # embed-grad path) — the cached winner overlays exactly this
            # scope; an explicitly user-set variable still wins
            if self._mode == "artifact":
                ins = [jax.ShapeDtypeStruct(s, jnp.dtype(d)) for s, d in sig]
                ent = self._block._sig_for(ins)
                call = ent["exported"].call
                if self._numerics_cfg.enabled:
                    # baked StableHLO has no taps left; output-site
                    # stats still compute in-graph around the call
                    call = self._wrap_pure_stats(call)
                fn = jax.jit(call)
                exe = fn.lower(*avals).compile()
                info = {"out_fmt": ent["out_fmt"], "multi": ent["multi"]}
            else:
                exe = self._jit.lower(*avals).compile()
                info = {"out_fmt": self._meta["out_fmt"],
                        "multi": self._meta["multi"]}
        self._exe[key] = (exe, info)
        self.stats["compiles"] += 1
        if self._warmed:
            self.stats["post_warmup_compiles"] += 1
        else:
            self.stats["warmup_compiles"] += 1
        # process-wide recompile ledger: a post-warmup entry here is the
        # "unbucketed shape reached the model" bug, assertable anywhere
        compile_log.note("serve.compiled", sig,
                         wall_ms=(time.perf_counter() - t0) * 1e3,
                         warmup=not self._warmed)
        # bank the bucket's collective-schedule fingerprint (one extra
        # trace, no compile; off = one env read) — replicated serving
        # fleets crosscheck these the same way trainer pods do
        from ..telemetry import collective_ledger as _cledger
        if _cledger.enabled():
            try:
                fn = (jax.jit(call) if self._mode == "artifact"
                      else self._jit)
                _cledger.bank_closed("serve.compiled",
                                     jax.make_jaxpr(fn)(*avals), sig)
            except Exception:  # noqa: BLE001 — never break a compile
                pass
        return self._exe[key]

    def warmup(self, verbose: bool = False) -> Dict[str, Any]:
        """AOT-compile every bucket combination; returns a summary dict
        (bucket count, compile seconds). After warmup any further compile
        increments ``stats['post_warmup_compiles']`` — the counter the
        zero-recompile serving contract asserts on."""
        t0 = time.perf_counter()
        n = 0
        # holding the model lock across the AOT compiles is the warmup
        # CONTRACT: predict() callers block until every bucket is ready
        # instead of racing half a table
        with self._lock:  # mxlint: disable=MX803
            for assignment in self._table.assignments():
                sig = self.signature_for(assignment)
                key = tuple(sig)
                if key not in self._exe:
                    with profiler.Scope("serve.compile"):
                        self._compile(key, sig)
                    n += 1
                    if verbose:
                        print(f"serve: compiled bucket {assignment}")
            self._warmed = True
        return {"buckets": self._table.num_buckets(), "compiled": n,
                "seconds": round(time.perf_counter() - t0, 3)}

    def cache_info(self) -> Dict[str, int]:
        """Copy of the compile-cache counters plus cache size."""
        with self._lock:
            info = dict(self.stats)
            info["cached_executables"] = len(self._exe)
            info["warmed_up"] = self._warmed
        return info

    # -- inference ------------------------------------------------------
    def _pad(self, arrays: List[onp.ndarray],
             assignment: Dict[str, int]) -> List[onp.ndarray]:
        out = []
        for a, spec, pv, (shape, dtype) in zip(
                arrays, self._input_axes, self._pad_values, self._in_avals):
            target = list(a.shape)
            for axis, name in spec.items():
                target[axis] = assignment[name]
            a = a.astype(dtype, copy=False)
            if tuple(target) != a.shape:
                widths = [(0, t - s) for s, t in zip(a.shape, target)]
                a = onp.pad(a, widths, mode="constant", constant_values=pv)
            out.append(a)
        return out

    def predict(self, *args):
        """Run one padded, compiled inference call; padding is sliced back
        off every output so callers never see bucket geometry. Accepts
        NDArray / numpy / nested-list inputs; returns NDArray(s).

        The whole call is one ``serve.predict`` profiler frame with
        ``serve.pad`` / ``serve.compute`` / ``serve.unpad`` child spans,
        so ``profiler.step_report(frame="serve.predict")`` attributes
        the serving host gap the same way the trainer's ``step`` frame
        does for training."""
        with profiler.Frame("serve.predict"):
            with profiler.Scope("serve.pad"):
                arrays = [_as_numpy(a) for a in args]
                if len(arrays) != self._n_in:
                    raise MXNetError(f"expected {self._n_in} inputs, "
                                     f"got {len(arrays)}")
                sizes = self._sizes_of(arrays)
                assignment = self._table.assignment(sizes)
                sig = self.signature_for(assignment)
                key = tuple(sig)
                padded = self._pad(arrays, assignment)
            # a cold-bucket compile intentionally blocks peers: two
            # threads racing the same missing bucket must produce ONE
            # executable, not two XLA compiles
            with self._lock:  # mxlint: disable=MX803
                hit = key in self._exe
                if hit:
                    self.stats["hits"] += 1
                    exe, info = self._exe[key]
                else:
                    self.stats["misses"] += 1
                    # a cold-bucket compile is seconds of host work — give
                    # it its own segment so step_report shows "compile",
                    # not an inflated python remainder / host gap
                    with profiler.Scope("serve.compile"):
                        exe, info = self._compile(key, sig)
                pvals = self._pvals
            # a RESOURCE_EXHAUSTED out of the compiled call writes ONE
            # OOM flight bundle (live ledger + static peaks), re-raised
            from ..telemetry import memory as _memory
            with profiler.Scope("serve.compute"), \
                    _memory.oom_guard("serve.compiled"):
                outs = exe(self._key_data, *padded, *pvals)
            if self._numerics_cfg.enabled:
                outs, stats_dev = outs
                self._maybe_record_numerics(stats_dev)
            with profiler.Scope("serve.unpad"):
                result = self._unpad(list(outs), info, sizes)
            return result

    __call__ = predict

    def _unpad(self, flat: List[jax.Array], info, sizes: Dict[str, int]):
        out_axes = self._output_axes
        if out_axes is None:
            out_axes = [{0: self._primary_axis}] * len(flat)
        if len(out_axes) != len(flat):
            raise MXNetError(
                f"output_axes has {len(out_axes)} entries but the model "
                f"returned {len(flat)} outputs")
        nds = []
        for o, spec in zip(flat, out_axes):
            slicer = [slice(None)] * o.ndim
            changed = False
            for axis, name in spec.items():
                if axis < o.ndim and name in sizes \
                        and o.shape[axis] != sizes[name]:
                    slicer[axis] = slice(0, sizes[name])
                    changed = True
            nds.append(NDArray(o[tuple(slicer)] if changed else o,
                               ctx=self._ctx))
        fmt = info["out_fmt"]
        from ..gluon.block import _regroup
        result = _regroup(nds, fmt)
        return tuple(result) if info["multi"] else result[0]


def export_for_serving(block, path: str, table: BucketTable,
                       input_axes: Sequence[Dict[int, str]],
                       epoch: int = 0, platforms=None) -> Tuple[str, str]:
    """Export one StableHLO graph per bucket combination so the artifact
    can be cold-loaded (``SymbolBlock.imports``) and served with zero
    recompiles — the deploy-side half of :class:`CompiledModel`.

    ``block`` must be hybridized with one forward call recorded (the same
    contract as :meth:`HybridBlock.export`); the bucketed axes of that
    recorded signature are replaced by every bucket assignment.
    """
    if getattr(block, "_last_sig", None) is None:
        raise MXNetError("export_for_serving needs a traced graph: call "
                         "hybridize() and run one forward first")
    _, n_in, in_avals, _ = block._last_sig
    if len(input_axes) != n_in:
        raise MXNetError(f"input_axes has {len(input_axes)} entries but "
                         f"the model takes {n_in} array inputs")
    signatures = []
    for assignment in table.assignments():
        sig = []
        for (shape, dtype), spec in zip(in_avals, input_axes):
            s = list(shape)
            for axis, name in spec.items():
                s[axis] = assignment[name]
            sig.append((tuple(s), dtype))
        signatures.append(sig)
    return block.export(path, epoch=epoch, platforms=platforms,
                        signatures=signatures)
