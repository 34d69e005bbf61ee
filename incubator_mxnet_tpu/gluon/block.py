"""Block / HybridBlock — the Gluon module system.

Reference parity: ``python/mxnet/gluon/block.py`` (``Block``,
``HybridBlock._build_cache``, ``HybridBlock.export``) — SURVEY §2.8, §3.3.

TPU-native design: ``hybridize()`` ≙ ``jax.jit``. The reference's first
hybridized call traces ``hybrid_forward`` with Symbol proxies into an nnvm
graph executed by ``CachedOp`` (src/imperative/cached_op.cc). Here the first
call runs eagerly (finishing deferred parameter init); subsequent calls run a
jit-compiled pure function whose inputs are (rng key, every descendant
parameter, the data arguments) and whose outputs are (forward outputs, traced
aux-state updates). Gradients flow through the cached op as a single autograd
tape node differentiated with ``jax.vjp`` — exactly the reference's
"CachedOp::Backward over the captured graph" collapsed onto XLA.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from ..base import MXNetError, _as_list
from ..context import Context, cpu, current_context
from .. import autograd
from .. import random as random_mod
from ..ndarray import NDArray
from ..analysis.recompile import note_compile
from . import _trace
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope(threading.local):
    """Name manager: numbers block instances per type (dense0_, dense1_ …).

    Reference: ``_BlockScope`` in python/mxnet/gluon/block.py.
    """

    _current = threading.local()

    def __init__(self, block=None):
        self._block = block
        self._counter: Dict[str, int] = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _GLOBAL_SCOPE._next_prefix(hint)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def _next_prefix(self, hint):
        count = self._counter.get(hint, 0)
        self._counter[hint] = count + 1
        return f"{hint}{count}_"

    def __enter__(self):
        if self._block is not None and self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block is not None and self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


_GLOBAL_SCOPE = _BlockScope()

# True while a HybridBlock cache trace is in flight: nested hybridized
# children must run their eager path inside the parent's single trace.
_TRACING = threading.local()


def _is_tracing() -> bool:
    return getattr(_TRACING, "flag", False)


def _flatten_args(args):
    """Flatten (nested lists/tuples of) NDArrays; return (flat, fmt)."""
    flat: List[NDArray] = []

    def rec(a):
        if isinstance(a, NDArray):
            flat.append(a)
            return 0
        if isinstance(a, (list, tuple)):
            return [rec(x) for x in a]
        flat.append(a)  # non-array static leaf
        return -1

    fmt = [rec(a) for a in args]
    return flat, fmt


class _ArrSlot:
    """Placeholder for an NDArray position in a cached-arg skeleton (so the
    jit closure doesn't pin the cache-building batch's device buffers)."""

    __slots__ = ()


_ARR_SLOT = _ArrSlot()


def _strip_arrays(args):
    def rec(a):
        if isinstance(a, NDArray):
            return _ARR_SLOT
        if isinstance(a, (list, tuple)):
            return [rec(x) for x in a]
        return a

    return tuple(rec(a) for a in args)


def _static_key(flat_args):
    """Hashable digest of the non-array leaves (they are baked into the
    traced graph, so they must key the cache)."""
    out = []
    for a in flat_args:
        if isinstance(a, NDArray):
            continue
        try:
            hash(a)
            out.append(a)
        except TypeError:
            out.append(repr(a))
    return tuple(out)


def _regroup(flat, fmt):
    it = iter(flat)

    def rec(f):
        if f == 0 or f == -1:
            return next(it)
        return [rec(x) for x in f]

    return [rec(f) for f in fmt]


class Block:
    """Base class for all neural network layers and models."""

    def __init__(self, prefix: Optional[str] = None, params: Optional[ParameterDict] = None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()

    def _alias(self) -> str:
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------------
    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        """All Parameters of this block and its descendants, optionally
        filtered by regex (reference: Block.collect_params)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret._params.update(
                {k: v for k, v in self.params.items() if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix: str = "") -> Dict[str, Parameter]:
        if prefix:
            prefix += "."
        ret = {prefix + k.lstrip("_"): v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # ------------------------------------------------------------------
    def __setattr__(self, name, value):
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            if name in self.__dict__.get("_reg_params", {}):
                pass
            self.__dict__.setdefault("_reg_params", {})[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook: Callable):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook: Callable):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn: Callable) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False) -> None:
        from .. import initializer as init_mod
        self.collect_params().initialize(
            init or init_mod.Xavier(), ctx, verbose, force_reinit)

    def cast(self, dtype) -> None:
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def zero_grad(self) -> None:
        self.collect_params().zero_grad()

    def hybridize(self, active: bool = True, **kwargs) -> None:
        """No-op at Block level; HybridBlock overrides (reference parity:
        plain Blocks just cascade to children)."""
        for child in self._children.values():
            # cascading a mode flag, not re-tracing per request
            child.hybridize(active, **kwargs)  # mxlint: disable=MX501

    # ------------------------------------------------------------------
    # checkpointing (SURVEY §5.4)
    # ------------------------------------------------------------------
    def save_parameters(self, filename: str, deduplicate: bool = False) -> None:
        params = self._collect_params_with_prefix()
        from .. import ndarray as nd
        arg_dict = {}
        seen = {}
        for name, param in params.items():
            if param._data is None:
                raise RuntimeError(
                    f"Parameter '{param.name}' has not been initialized")
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = name
            arg_dict[name] = param._check_and_get(param._data, None)
        # Non-finite weights checkpoint "successfully" and poison every
        # later restore — surface it at save time (one fused jitted
        # reduction, mx.fault.guards), where the step that broke them is
        # still identifiable. Warn-only: saving a diverged model for a
        # post-mortem is legitimate.
        from ..fault.guards import all_finite
        if not all_finite([a._data for a in arg_dict.values()]):
            import warnings
            warnings.warn(
                f"save_parameters({filename!r}): parameters contain "
                "non-finite values; the saved file will restore a broken "
                "model (a fault.StepGuard on the trainer catches this at "
                "the offending step)")
        nd.save(filename, arg_dict)

    def load_parameters(self, filename: str, ctx=None, allow_missing: bool = False,
                        ignore_extra: bool = False, cast_dtype: bool = False,
                        dtype_source: str = "current") -> None:
        from .. import ndarray as nd
        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in k for k in loaded.keys()):
            # legacy prefix-based file: route through ParameterDict.load
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix,
                cast_dtype=cast_dtype, dtype_source=dtype_source)
            return
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise AssertionError(
                        f"Parameter '{name}' is missing in file '{filename}'")
        for name, data in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise AssertionError(
                        f"Parameter '{name}' loaded from file '{filename}' is "
                        "not present in this block")
                continue
            params[name]._load_init(data, ctx or current_context(),
                                    cast_dtype=cast_dtype, dtype_source=dtype_source)

    save_params = save_parameters
    load_params = load_parameters

    # ------------------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs) -> None:
        """Print a per-layer summary of output shapes and param counts."""
        rows = []
        hooks = []

        def add_hook(block):
            def hook(blk, _, out):
                o = out[0] if isinstance(out, (list, tuple)) else out
                n_param = sum(
                    int(onp.prod(p.shape)) for p in blk.params.values()
                    if p.shape and all(s > 0 for s in p.shape))
                rows.append((type(blk).__name__, blk.name,
                             tuple(getattr(o, "shape", ())), n_param))
            hooks.append(block.register_forward_hook(hook))

        self.apply(add_hook)
        try:
            self(*inputs)
        finally:
            for h in hooks:
                h.detach()
        print(f"{'Layer (type)':<30}{'Output Shape':<24}{'Param #':<12}")
        print("-" * 66)
        total = 0
        for tname, name, shape, n in rows:
            print(f"{tname + ' (' + name + ')':<30}{str(shape):<24}{n:<12}")
            total += n
        print("-" * 66)
        print(f"Total params (incl. shared): {total}")

    def __repr__(self):
        s = f"{type(self).__name__}("
        for name, child in self._children.items():
            s += f"\n  ({name}): " + repr(child).replace("\n", "\n  ")
        return s + "\n)" if self._children else s + ")"


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks_dict):
        self.id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1
        self._hooks = hooks_dict

    def detach(self):
        self._hooks.pop(self.id, None)


class HybridBlock(Block):
    """A Block whose forward is expressible as a pure function of its inputs
    and parameters — and therefore compilable (reference: hybridize() →
    CachedOp; here: → jax.jit)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags: Dict[str, Any] = {}
        self._jit_cache: Dict[Any, Callable] = {}
        self._cache_info: Dict[Any, dict] = {}
        self._warmed_up = False
        self._partition_if_dynamic = True

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, **kwargs) -> None:
        """Enable jit compilation of the forward (reference semantics:
        static_alloc/static_shape accepted; XLA buffer assignment subsumes
        both)."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape, **kwargs)
        self._clear_cached_op()
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def _clear_cached_op(self) -> None:
        self._jit_cache = {}
        self._cache_info = {}
        self._warmed_up = False
        # recompilation accounting restarts with the cache (mx.analysis)
        self.__dict__.pop("_compile_log", None)
        self.__dict__.pop("_compile_sigs", None)
        self.__dict__.pop("_recompile_warned", None)

    def infer_shape(self, *args) -> None:
        """Resolve deferred parameter shapes from input shapes. Layers with
        lazy in-channels override this (reference: generic symbolic shape
        inference; JAX has no unknown-dim inference, so it is per-layer)."""
        raise ValueError(
            f"Deferred initialization of parameters in {type(self).__name__} "
            "could not be resolved: override infer_shape() or give explicit "
            "in_units/in_channels.")

    def _get_ctx(self, flat_args) -> Context:
        for a in flat_args:
            if isinstance(a, NDArray):
                return a.context
        return current_context()

    def _fetch_params(self, ctx, args) -> Dict[str, NDArray]:
        try:
            return {name: p.data(ctx) for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_init_params(ctx, args)
            return {name: p.data(ctx) for name, p in self._reg_params.items()}

    def _deferred_init_params(self, ctx, args) -> None:
        self.infer_shape(*args)
        for p in self._reg_params.values():
            p._finish_deferred_init()

    # ------------------------------------------------------------------
    def forward(self, x, *args):
        if self._active and not _is_tracing() and isinstance(x, NDArray):
            return self._call_cached_op(x, *args)
        if isinstance(x, NDArray):
            if getattr(self, "_sg_graph", None) is not None and self._active:
                # optimize_for installed a partitioned graph: while
                # hybridized it IS the compute (running inside the cached-op
                # trace compiles it); hybridize(False) falls back to the
                # original eager forward, reference CachedOp semantics
                return self._forward_partitioned(x, *args)
            from .. import ndarray as F
            ctx = x.context
            params = self._fetch_params(ctx, (x,) + args)
            return self.hybrid_forward(F, x, *args, **params)
        # Symbol path (export / symbolic compose)
        from .. import symbol as F
        params = {name: p.var() for name, p in self._reg_params.items()}
        return self.hybrid_forward(F, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------------
    # the CachedOp: jit path
    # ------------------------------------------------------------------
    def _call_cached_op(self, *args):
        flat_args, fmt = _flatten_args(args)
        arr_args = [a for a in flat_args if isinstance(a, NDArray)]
        ctx = self._get_ctx(flat_args)

        if not self._warmed_up:
            # First call: run eagerly (finishes deferred init, discovers the
            # parameter set) — the reference's _build_cache moment.
            _TRACING.flag = True
            try:
                out = self.forward(*args)
            finally:
                _TRACING.flag = False
            self._cached_params = [
                p for _, p in sorted(self.collect_params().items())]
            self._warmed_up = True
            # export() works after THIS call already (one hybridized
            # forward, per the reference contract)
            self._last_sig = (_strip_arrays(args), len(arr_args),
                              [(tuple(a.shape), str(a._data.dtype))
                               for a in arr_args], ctx)
            return out

        params = self._cached_params
        param_vals = []
        for p in params:
            arr = p.data(ctx)
            param_vals.append(arr._data)
        training = autograd.is_training()
        key_val = random_mod.next_key(ctx)
        n_in = len(arr_args)
        # Remember the call signature so export() can re-trace an inference
        # version of this graph for the deploy artifact.
        self._last_sig = (_strip_arrays(args), n_in,
                          [(tuple(a.shape), str(a._data.dtype))
                           for a in arr_args], ctx)
        # Key must cover the arg *structure* (array count/nesting), not just
        # static leaf values — otherwise a call with a different number of
        # arrays would reuse a jit fn with a stale n_in/skeleton.
        cache_key = (training, n_in, repr(fmt), _static_key(flat_args))

        if cache_key not in self._jit_cache:
            info = {"out_fmt": None, "effects": []}
            self._cache_info[cache_key] = info
            block = self
            skeleton = _strip_arrays(args)

            def pure(key, *vals):
                ins, pvals = vals[:n_in], vals[n_in:]
                proxies = {}
                for p, v in zip(params, pvals):
                    proxies[id(p)] = NDArray(v, ctx=ctx)
                # rebuild args replacing NDArray slots with traced proxies
                it = iter(NDArray(v, ctx=ctx) for v in ins)
                rebuilt = _rebuild_args(skeleton, it)
                _TRACING.flag = True
                try:
                    with autograd.pause(train_mode=training), \
                            random_mod.trace_rng(key), \
                            _trace.TraceScope(proxies) as scope:
                        out = block.forward(*rebuilt)
                finally:
                    _TRACING.flag = False
                flat_out, out_fmt = _flatten_args(
                    out if isinstance(out, tuple) else (out,))
                info["out_fmt"] = out_fmt
                info["multi"] = isinstance(out, (tuple, list))
                info["effects"] = list(scope.effect_keys)
                prim = tuple(o._data if isinstance(o, NDArray) else o for o in flat_out)
                return prim + tuple(scope.effect_values)

            self._jit_cache[cache_key] = jax.jit(pure)

        # recompilation accounting: every distinct (static-key, input-aval)
        # signature is a fresh XLA compile — the block-level cache key alone
        # undercounts because jax.jit re-traces per shape/dtype inside one
        # entry. mx.analysis warns past a threshold (MX201).
        new_sig = note_compile(self, (cache_key, tuple(self._last_sig[2])))

        jit_fn = self._jit_cache[cache_key]
        info = self._cache_info[cache_key]

        from ..ndarray.op import dispatch_op

        def tape_fn(*vals):
            return jit_fn(key_val, *vals)

        op_args = arr_args + list(params_data(params, ctx))
        op_name = f"cached_op_{self._name}"
        if new_sig:
            # this call compiles: jax's account of it goes to the site (a
            # seen signature pays nothing for the bookkeeping)
            from ..telemetry import compile_log
            with compile_log.at("gluon.hybridize"):
                outs = dispatch_op(tape_fn, op_args, {}, ctx, name=op_name)
        else:
            outs = dispatch_op(tape_fn, op_args, {}, ctx, name=op_name)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        n_eff = len(info["effects"])
        prim = outs[: len(outs) - n_eff]
        effs = outs[len(outs) - n_eff:]
        for (p, ectx), val in zip(info["effects"], effs):
            p._deposit_aux(val._data, ectx if ectx is not None else ctx)
        flat_prim = list(prim)
        result = _regroup(flat_prim, info["out_fmt"])
        if not info["multi"]:
            return result[0]
        return tuple(result)

    # ------------------------------------------------------------------
    def _make_pure_infer(self, skeleton, n_in: int, ctx):
        """Build the inference-mode pure function over this block's cached
        graph: ``pure_infer(key_data, *inputs, *param_values) -> flat outs``
        traced with ``train_mode=False`` (dropout identity, BatchNorm on
        running stats). Returns ``(pure_infer, meta)`` — ``meta`` is filled
        with ``out_fmt``/``multi`` during tracing. Shared by
        :meth:`export` and the serving compiler
        (:class:`~incubator_mxnet_tpu.serve.CompiledModel`)."""
        impl = random_mod._impl()
        blk_params = self._cached_params
        meta: Dict[str, Any] = {}
        block = self

        def pure_infer(key_data, *vals):
            key = jax.random.wrap_key_data(key_data, impl=impl)
            ins, pvals = vals[:n_in], vals[n_in:]
            proxies = {id(p): NDArray(v, ctx=ctx)
                       for p, v in zip(blk_params, pvals)}
            it = iter(NDArray(v, ctx=ctx) for v in ins)
            rebuilt = _rebuild_args(skeleton, it)
            _TRACING.flag = True
            try:
                with autograd.pause(train_mode=False), \
                        random_mod.trace_rng(key), \
                        _trace.TraceScope(proxies):
                    out = block.forward(*rebuilt)
            finally:
                _TRACING.flag = False
            flat_out, out_fmt = _flatten_args(
                out if isinstance(out, tuple) else (out,))
            meta["out_fmt"] = out_fmt
            meta["multi"] = isinstance(out, (tuple, list))
            return tuple(o._data if isinstance(o, NDArray) else o
                         for o in flat_out)

        return pure_infer, meta

    def export(self, path: str, epoch: int = 0,
               platforms=None, signatures=None) -> Tuple[str, str]:
        """Serialize a self-contained deploy artifact (reference:
        HybridBlock.export → model-symbol.json + model-0000.params).

        TPU-native form: the inference forward is re-traced with
        ``train_mode=False`` and serialized as **StableHLO** via
        ``jax.export`` (`<path>-symbol.stablehlo`), alongside the dmlc
        ``.params`` weights and a JSON manifest that records the calling
        convention (input avals, parameter order, RNG key wire format,
        output structure). :meth:`SymbolBlock.imports` reconstructs a
        runnable block from these files WITHOUT the original Python class.

        Requires one prior hybridized call (the reference requires a forward
        before export for the same reason — shapes must be known).
        ``platforms``: optional list (e.g. ``["cpu", "tpu"]``) to make the
        artifact portable across backends; default = current backend only.

        ``signatures``: optional list of *additional-shape* input signatures
        to bake into the artifact — each entry is a list of ``(shape,
        dtype)`` pairs, one per array input. StableHLO graphs are
        fixed-shape, so a served model needs one graph per shape bucket;
        every listed signature is traced and serialized
        (``<path>-symbol.<i>.stablehlo``) and
        :meth:`SymbolBlock.forward` dispatches on the call's input shapes.
        Default: the recorded signature of the last hybridized call only.
        """
        import json

        params_file = f"{path}-{epoch:04d}.params"
        params = self._collect_params_with_prefix()
        from .. import ndarray as nd
        nd.save(params_file, {k: p._check_and_get(p._data, None)
                              for k, p in params.items() if p._data is not None})
        sym_file = f"{path}-symbol.json"
        if getattr(self, "_last_sig", None) is None:
            raise MXNetError(
                "export() needs a traced graph: call hybridize() and run one "
                "forward pass before exporting (reference behavior)")
        skeleton, n_in, in_avals, ctx = self._last_sig
        blk_params = self._cached_params
        name_by_id = {id(p): k for k, p in params.items()}
        param_order = [name_by_id[id(p)] for p in blk_params]
        impl = random_mod._impl()
        key_data_aval = jax.random.key_data(jax.random.key(0, impl=impl))

        # additional signatures ADD to the recorded one (deduped), so the
        # artifact can always replay the shape it was exported after
        sigs = [[(tuple(s), str(d)) for s, d in in_avals]]
        for sig in (signatures or []):
            norm = [(tuple(s), str(d)) for s, d in sig]
            if len(norm) != n_in:
                raise MXNetError(
                    f"export(signatures=...): each signature needs "
                    f"{n_in} (shape, dtype) input entries, got {len(norm)}")
            if norm not in sigs:
                sigs.append(norm)

        from jax import export as jax_export
        kwargs = {"platforms": tuple(platforms)} if platforms else {}
        sig_entries = []
        exported_platforms = None
        for i, sig in enumerate(sigs):
            pure_infer, meta = self._make_pure_infer(skeleton, n_in, ctx)
            args = [jax.ShapeDtypeStruct(key_data_aval.shape,
                                         key_data_aval.dtype)]
            args += [jax.ShapeDtypeStruct(s, jnp.dtype(d)) for s, d in sig]
            args += [jax.ShapeDtypeStruct(tuple(p.shape), jnp.dtype(p.dtype))
                     for p in blk_params]
            # one trace per exported artifact signature, not per request
            exported = jax_export.export(jax.jit(pure_infer), **kwargs)(*args)  # mxlint: disable=MX501
            hlo_file = (f"{path}-symbol.stablehlo" if i == 0
                        else f"{path}-symbol.{i}.stablehlo")
            with open(hlo_file, "wb") as f:
                f.write(exported.serialize())
            exported_platforms = list(exported.platforms)
            sig_entries.append({
                "in_avals": [[list(s), d] for s, d in sig],
                "stablehlo": hlo_file.rsplit("/", 1)[-1],
                "out_fmt": meta["out_fmt"],
                "multi": meta["multi"],
            })
        primary = sig_entries[0]
        arch = {
            "framework": "incubator_mxnet_tpu",
            "block": type(self).__name__,
            "name": self.name,
            "params": sorted(params.keys()),
            "param_order": param_order,
            "param_prefix_names": [p.name for p in blk_params],
            "n_inputs": n_in,
            "in_avals": primary["in_avals"],
            "key": {"shape": list(key_data_aval.shape),
                    "dtype": str(key_data_aval.dtype), "impl": impl},
            "out_fmt": primary["out_fmt"],
            "multi": primary["multi"],
            "stablehlo": primary["stablehlo"],
            "signatures": sig_entries,
            "platforms": exported_platforms,
        }
        with open(sym_file, "w") as f:
            json.dump(arch, f, indent=2)
        return sym_file, params_file

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Apply a subgraph backend, then compile (reference:
        HybridBlock.optimize_for over the subgraph property registry,
        src/operator/subgraph/). Two kinds of backend resolve here:
        block-rewrite passes (``gluon.block.register_subgraph_backend`` —
        the built-in ``"INT8"`` quantization swap), and graph-partitioning
        property backends (``mx.subgraph.register_backend`` — pattern-match
        and replace regions of the symbolically traced forward). XLA fusion
        itself needs no pass, so ``backend=None``/"XLA" is hybridize + one
        warm-up call."""
        if backend in (None, "XLA", "xla"):
            self._sg_graph = None  # revert any earlier partitioning
        else:
            from .. import subgraph as _subgraph
            if backend in _SUBGRAPH_BACKENDS:
                self._sg_graph = None  # block rewrite replaces partitioning
                _SUBGRAPH_BACKENDS[backend](self, x, *args, **kwargs)
            elif backend in _subgraph._BACKENDS:
                if kwargs:
                    raise MXNetError(
                        f"subgraph property backend {backend!r} takes no "
                        f"options; got {sorted(kwargs)}")
                self._install_partitioned_graph(backend, x, *args)
            else:
                raise MXNetError(
                    f"unknown subgraph backend {backend!r}; registered "
                    f"block passes: {sorted(_SUBGRAPH_BACKENDS)}, property "
                    f"backends: {_subgraph.list_backends()} (register with "
                    "gluon.block.register_subgraph_backend or "
                    "mx.subgraph.register_backend)")
        self.hybridize()
        return self(x, *args)

    def _install_partitioned_graph(self, backend, x, *args):
        """Trace the forward symbolically, partition it, and make the
        partitioned graph this block's compute (reference: the in-place
        CachedOp repartition done by HybridBlock.optimize_for)."""
        from .. import subgraph as _subgraph
        from .. import symbol as S
        bad = self._training_dependent_children()
        if bad:
            raise MXNetError(
                "property-backend partitioning traces the forward once in "
                "inference mode, which would bake training-time behavior "
                f"out of {bad}; blocks with training-dependent state "
                "(Dropout masks, BatchNorm running stats) are not supported "
                "here yet — use a block-rewrite backend "
                "(gluon.block.register_subgraph_backend) or plain "
                "hybridize() for this net")
        self(x, *args)  # finish deferred init so params have shapes
        data_vars = [S.Variable(f"data{i}") for i in range(1 + len(args))]
        out = self.forward(*data_vars)  # Symbol trace path
        if isinstance(out, (list, tuple)):
            out = S.Group(list(out))
        self._sg_graph = (_subgraph.partition(out, backend),
                          [v.name for v in data_vars])
        self._clear_cached_op()  # compiled pre-partition graphs are stale

    def _training_dependent_children(self) -> List[str]:
        """Names of descendant blocks whose forward depends on training
        mode or mutates running state — unsafe to freeze into a one-shot
        inference-mode symbolic trace."""
        from .nn import basic_layers as _bl
        kinds = (_bl.Dropout, _bl.BatchNorm)
        bad = []

        def walk(b):
            for child in b._children.values():
                if isinstance(child, kinds):
                    bad.append(f"{type(child).__name__}({child.name})")
                walk(child)

        walk(self)
        return bad

    def _forward_partitioned(self, x, *args):
        part, names = self._sg_graph
        ctx = x.context
        vals = dict(zip(names, (x,) + args))
        for pname, p in self.collect_params().items():
            vals[pname] = p.data(ctx)
        arg_names = part.list_arguments()
        missing = [a for a in arg_names if a not in vals]
        if missing:
            raise MXNetError(
                f"partitioned graph argument(s) {missing} not found among "
                "data inputs or parameters")
        from ..ndarray.op import dispatch_op
        from .. import symbol as S
        arrays = [vals[a] for a in arg_names]
        out = dispatch_op(S._compile_fn(part, arg_names), arrays, {}, ctx,
                          name=f"partitioned_{self._name}")
        multi = part._op == "_group"
        return list(out) if multi and isinstance(out, (list, tuple)) else out


#: subgraph-backend registry (reference: SubgraphBackendRegistry)
_SUBGRAPH_BACKENDS: Dict[str, Callable] = {}


def register_subgraph_backend(name: str, fn: Optional[Callable] = None):
    """Register a block-rewrite pass: ``fn(block, x, *args, **kwargs)``
    mutates the block tree in place before compilation. Usable as a
    decorator."""
    def _do(f):
        _SUBGRAPH_BACKENDS[name] = f
        return f
    return _do(fn) if fn is not None else _do


@register_subgraph_backend("INT8")
def _int8_backend(block, x, *args, calib_data=None, calib_mode="naive",
                  exclude_layers=(), **kwargs):
    from ..quantization import quantize_net
    quantize_net(block, calib_data=list(calib_data or [x]),
                 calib_mode=calib_mode, exclude_layers=exclude_layers)


def params_data(params, ctx):
    return [p.data(ctx) for p in params]


def _rebuild_args(args, it):
    def rec(a):
        if isinstance(a, NDArray) or isinstance(a, _ArrSlot):
            return next(it)
        if isinstance(a, (list, tuple)):
            return [rec(x) for x in a]
        return a

    return [rec(a) for a in args]


class SymbolBlock(HybridBlock):
    """A runnable Block reconstructed from an exported artifact (reference:
    gluon.SymbolBlock.imports over model-symbol.json + .params).

    TPU-native form: the compute graph is the serialized **StableHLO**
    written by :meth:`HybridBlock.export`; ``imports`` deserializes it with
    ``jax.export`` and replays it on call — the original Python Block class
    is NOT needed. Parameters load from the dmlc ``.params`` file and feed
    the compiled computation in the manifest's recorded order.
    """

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._outputs = outputs
        self._inputs = inputs
        self._exported = None
        self._sigs: List[dict] = []
        self._arch = outputs if isinstance(outputs, dict) else None
        self._param_arrays: Dict[str, NDArray] = {}

    @staticmethod
    def imports(symbol_file: str, input_names,
                param_file: Optional[str] = None, ctx=None) -> "SymbolBlock":
        import json
        import os
        with open(symbol_file) as f:
            arch = json.load(f)
        blk = SymbolBlock(arch, input_names)
        base = os.path.dirname(os.path.abspath(symbol_file))
        from jax import export as jax_export
        # multi-signature manifest (one fixed-shape StableHLO per shape
        # bucket); legacy single-graph manifests synthesize one entry
        entries = arch.get("signatures") or ([{
            "in_avals": arch["in_avals"], "stablehlo": arch.get("stablehlo"),
            "out_fmt": arch["out_fmt"], "multi": arch["multi"],
        }] if arch.get("stablehlo") else [])
        for ent in entries:
            with open(os.path.join(base, ent["stablehlo"]), "rb") as f:
                exported = jax_export.deserialize(bytearray(f.read()))
            blk._sigs.append({
                "exported": exported,
                "in_avals": [(tuple(s), str(d)) for s, d in ent["in_avals"]],
                "out_fmt": ent["out_fmt"], "multi": ent["multi"],
            })
        if blk._sigs:
            blk._exported = blk._sigs[0]["exported"]
        if param_file:
            from .. import ndarray as nd
            loaded = nd.load(param_file)
            if not isinstance(loaded, dict):
                raise MXNetError(f"{param_file}: expected a name->array dict")
            blk._param_arrays = loaded
            # surface them as real Parameters too (collect_params parity)
            for name, arr in loaded.items():
                p = blk.params.get(name, shape=arr.shape,
                                   dtype=str(arr._data.dtype))
                p._load_init(arr, ctx)
        return blk

    def signatures(self) -> List[Tuple[Tuple[tuple, str], ...]]:
        """The input (shape, dtype) signatures this artifact can run."""
        return [tuple(s["in_avals"]) for s in self._sigs]

    def _sig_for(self, ins) -> dict:
        shapes = [tuple(i.shape) for i in ins]
        dtypes = [str(i.dtype) for i in ins]
        shape_hits = [s for s in self._sigs
                      if [a[0] for a in s["in_avals"]] == shapes]
        for s in shape_hits:
            if [a[1] for a in s["in_avals"]] == dtypes:
                return s
        if shape_hits:  # shape match, dtype off — let XLA surface the cast
            return shape_hits[0]
        have = ", ".join(
            "(" + ", ".join(f"{a[0]}:{a[1]}" for a in s["in_avals"]) + ")"
            for s in self._sigs) or "<none>"
        raise MXNetError(
            f"no exported graph matches input shapes {shapes}; this "
            f"artifact was exported for: {have}. Re-export with "
            "signatures=[...] covering the needed shape buckets "
            "(serve.export_for_serving does this from a BucketTable).")

    def set_weights(self, mapping, ctx=None, allow_missing: bool = False,
                    ignore_extra: bool = False) -> int:
        """Swap parameter values in place (no recompile — shapes must
        match); returns how many parameters were updated. ``mapping`` maps
        manifest (dotted) names — or training-time prefix names, via the
        manifest's ``param_prefix_names`` — to NDArray/numpy values. This
        is the registry's version-swap path: weights from a newer
        ``fault.checkpoint`` land on a cold-loaded artifact without
        touching Python model code."""
        from .. import ndarray as nd
        arch = self._arch or {}
        order = arch.get("param_order", [])
        prefix_names = arch.get("param_prefix_names", [])
        by_prefix = dict(zip(prefix_names, order))
        known = set(order) | set(self._param_arrays)
        resolved: Dict[str, NDArray] = {}
        for name, arr in mapping.items():
            target = name if name in known else by_prefix.get(name)
            if target is None:
                if ignore_extra:
                    continue
                raise MXNetError(
                    f"set_weights: {name!r} is not a parameter of this "
                    f"artifact (known: {sorted(known)[:8]}...)")
            if not isinstance(arr, NDArray):
                arr = nd.array(onp.asarray(arr))
            old = self._param_arrays.get(target)
            if old is not None and tuple(old.shape) != tuple(arr.shape):
                raise MXNetError(
                    f"set_weights: shape mismatch for {target!r}: artifact "
                    f"has {tuple(old.shape)}, new value is "
                    f"{tuple(arr.shape)}")
            resolved[target] = arr
        if not allow_missing:
            missing = [n for n in order if n not in resolved
                       and n not in self._param_arrays]
            if missing:
                raise MXNetError(f"set_weights: missing parameters "
                                 f"{missing}; pass allow_missing=True to "
                                 "keep current values")
        for name, arr in resolved.items():
            self._param_arrays[name] = arr
            p = self.params._params.get(name)
            if p is not None:
                p._load_init(arr, ctx)
            else:
                p = self.params.get(name, shape=arr.shape,
                                    dtype=str(arr._data.dtype))
                p._load_init(arr, ctx)
        return len(resolved)

    def load_parameters(self, filename: str, ctx=None,
                        allow_missing: bool = False,
                        ignore_extra: bool = False, cast_dtype: bool = False,
                        dtype_source: str = "current") -> None:
        """Refresh this artifact's weights from a ``.params`` file (the
        generic Block implementation walks ``_reg_params``, which an
        imported artifact does not have)."""
        from .. import ndarray as nd
        loaded = nd.load(filename)
        if not isinstance(loaded, dict):
            raise MXNetError(f"{filename}: expected a name->array dict")
        self.set_weights(loaded, ctx=ctx, allow_missing=allow_missing,
                         ignore_extra=ignore_extra)

    load_params = load_parameters

    def forward(self, *inputs):
        if not self._sigs:
            raise MXNetError(
                "this SymbolBlock was imported from a manifest without a "
                "StableHLO graph; re-export with HybridBlock.export() on "
                "this framework version")
        arch = self._arch
        n_in = arch["n_inputs"]
        if len(inputs) != n_in:
            raise MXNetError(f"expected {n_in} input array(s), "
                             f"got {len(inputs)}")
        ctx = inputs[0].context if isinstance(inputs[0], NDArray) \
            else current_context()
        ins = [i._data if isinstance(i, NDArray) else jnp.asarray(i)
               for i in inputs]
        sig = self._sig_for(ins)
        try:
            pvals = [self._param_arrays[n]._data for n in arch["param_order"]]
        except KeyError as e:
            raise MXNetError(f"missing parameter {e} — pass param_file to "
                             "imports()") from e
        key = jax.random.key_data(jax.random.key(0, impl=arch["key"]["impl"]))
        key = key.astype(jnp.dtype(arch["key"]["dtype"]))
        outs = sig["exported"].call(key, *ins, *pvals)
        flat = [NDArray(o, ctx=ctx) for o in outs]
        result = _regroup(flat, sig["out_fmt"])
        # sig["multi"] is a manifest bool, not a tracer
        return tuple(result) if sig["multi"] else result[0]  # mxlint: disable=MX204

    def hybrid_forward(self, F, x, *args, **kwargs):
        return self.forward(x, *args)
