"""Optimizers (reference: ``python/mxnet/optimizer/optimizer.py`` +
``src/operator/optimizer_op.cc``).

The reference implements each update rule as a mutating operator
(``FMutateInputs``) launched per-parameter. Here each rule is a jitted pure
function ``(weight, grad, *state, lr, wd, ...) -> (new_weight, *new_state)``;
the NDArray facade swaps buffers (mutation semantics preserved). XLA's
executable cache plays the role of the reference's per-op kernel cache, and
the Trainer's fused path (gluon/trainer.py) applies all parameters in one
compiled update — the multi-tensor optimizer fusion the reference ships as
``multi_sgd_update``/LAMB multi-tensor contrib ops.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..base import Registry, MXNetError
from ..ndarray.ndarray import NDArray

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdamW", "RMSProp", "AdaGrad",
           "AdaDelta", "FTRL", "Ftrl", "Signum", "LAMB", "LARS", "DCASGD",
           "SGLD", "Adamax", "Nadam", "FTML", "Updater",
           "register", "create", "get_updater"]

_registry: Registry = Registry.get("optimizer")
register = _registry.register


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    return _registry.create(name, **kwargs)


class Optimizer:
    """Base optimizer. State is a tuple of jax arrays per parameter index."""

    def __init__(self, learning_rate=0.01, wd=0.0, rescale_grad=1.0,
                 clip_gradient=None, lr_scheduler=None, multi_precision=False,
                 param_dict=None, begin_num_update=0, **kwargs):
        self.lr = learning_rate
        self.wd = wd
        self.rescale_grad = rescale_grad
        self.clip_gradient = clip_gradient
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.multi_precision = multi_precision
        self.param_dict = param_dict or {}
        self.num_update = begin_num_update
        self.begin_num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.idx2name: Dict[int, str] = {}
        self.lr_mult: Dict[Any, float] = {}
        self.wd_mult: Dict[Any, float] = {}

    # -- bookkeeping (reference parity) -----------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("learning rate is managed by the LRScheduler")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult: Dict[Any, float]):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult: Dict[Any, float]):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        self._index_update_count.setdefault(index, self.begin_num_update)
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index) -> float:
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- per-param state ---------------------------------------------------
    def create_state(self, index, weight: NDArray) -> Tuple:
        return ()

    def create_state_multi_precision(self, index, weight: NDArray) -> Tuple:
        if self.multi_precision and weight.dtype in ("float16", "bfloat16"):
            # the states belong to the fp32 master (reference:
            # create_state(index, weight_master_copy)): the update runs
            # in fp32 and returns fp32 states, and states created in the
            # weight's 16-bit dtype would change type after one step — a
            # second trace and compile of a jitted step, and donated
            # buffers that cannot alias their outputs
            master = NDArray(weight._data.astype(jnp.float32),
                             ctx=weight.context)
            return (master._data,) + self.create_state(index, master)
        return self.create_state(index, weight)

    # -- update ------------------------------------------------------------
    def _prep_grad(self, grad):
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def step(self, weight_v, grad_v, state, lr, wd, t):
        """Pure update rule; subclasses implement."""
        raise NotImplementedError

    def update(self, index, weight: NDArray, grad: NDArray, state) -> Any:
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        w, g = weight._data, grad._data
        use_master = (self.multi_precision and len(state) > 0
                      and isinstance(state, tuple) and getattr(state[0], "dtype", None) == jnp.float32
                      and w.dtype in (jnp.float16, jnp.bfloat16))
        if use_master:
            master, rest = state[0], state[1:]
            new_master, new_rest = self.step(master, g.astype(jnp.float32), rest, lr, wd, t)
            weight._set_data(new_master.astype(w.dtype))
            return (new_master,) + tuple(new_rest)
        new_w, new_state = self.step(w, g.astype(w.dtype) if g.dtype != w.dtype else g, state, lr, wd, t)
        weight._set_data(new_w)
        return tuple(new_state)

    update_multi_precision = update

    def __repr__(self):
        return f"{type(self).__name__}(lr={self.lr})"


@register
class SGD(Optimizer):
    def __init__(self, momentum=0.0, lazy_update=False, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (jnp.zeros(weight.shape, weight._data.dtype),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        if self.momentum == 0.0:
            return w - lr * g, ()
        mom = state[0] * self.momentum - lr * g
        return w + mom, (mom,)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        return (jnp.zeros(weight.shape, weight._data.dtype),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        mom = self.momentum * state[0] + g
        return w - lr * (g + self.momentum * mom), (mom,)


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        def z():
            return jnp.zeros(weight.shape, weight._data.dtype)
        return (z(), z())

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        m = self.beta1 * state[0] + (1 - self.beta1) * g
        v = self.beta2 * state[1] + (1 - self.beta2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        return w - lr_t * m / (jnp.sqrt(v) + self.epsilon), (m, v)


@register
class AdamW(Adam):
    """Decoupled weight decay (reference: contrib ``adamw_update``)."""

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g)
        m = self.beta1 * state[0] + (1 - self.beta1) * g
        v = self.beta2 * state[1] + (1 - self.beta2) * jnp.square(g)
        mhat = m / (1 - self.beta1 ** t)
        vhat = v / (1 - self.beta2 ** t)
        return w - lr * (mhat / (jnp.sqrt(vhat) + self.epsilon) + wd * w), (m, v)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.rho, self.momentum, self.epsilon = gamma1, gamma2, epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        def z():
            return jnp.zeros(weight.shape, weight._data.dtype)
        if self.centered:
            return (z(), z(), z())  # n, g_bar, delta
        return (z(),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        if not self.centered:
            n = self.rho * state[0] + (1 - self.rho) * jnp.square(g)
            neww = w - lr * g / jnp.sqrt(n + self.epsilon)
            return neww, (n,)
        n = self.rho * state[0] + (1 - self.rho) * jnp.square(g)
        gbar = self.rho * state[1] + (1 - self.rho) * g
        delta = self.momentum * state[2] - lr * g / jnp.sqrt(n - jnp.square(gbar) + self.epsilon)
        neww = w + delta
        if self.clip_weights:
            neww = jnp.clip(neww, -self.clip_weights, self.clip_weights)
        return neww, (n, gbar, delta)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return (jnp.zeros(weight.shape, weight._data.dtype),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        hist = state[0] + jnp.square(g)
        return w - lr * g / (jnp.sqrt(hist) + self.float_stable_eps), (hist,)


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.9, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        def z():
            return jnp.zeros(weight.shape, weight._data.dtype)
        return (z(), z())

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        acc_g = self.rho * state[0] + (1 - self.rho) * jnp.square(g)
        delta = jnp.sqrt(state[1] + self.epsilon) / jnp.sqrt(acc_g + self.epsilon) * g
        acc_d = self.rho * state[1] + (1 - self.rho) * jnp.square(delta)
        return w - delta, (acc_g, acc_d)


@register
class FTRL(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        def z():
            return jnp.zeros(weight.shape, weight._data.dtype)
        return (z(), z())  # z, n

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g)
        zs, n = state
        sigma = (jnp.sqrt(n + jnp.square(g)) - jnp.sqrt(n)) / lr
        zs = zs + g - sigma * w
        n = n + jnp.square(g)
        neww = jnp.where(
            jnp.abs(zs) > self.lamda1,
            -(zs - jnp.sign(zs) * self.lamda1) / ((self.beta + jnp.sqrt(n)) / lr + wd),
            0.0,
        )
        return neww.astype(w.dtype), (zs, n)


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum, self.wd_lh = momentum, wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return ()
        return (jnp.zeros(weight.shape, weight._data.dtype),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g)
        if self.momentum == 0.0:
            return w * (1 - lr * self.wd_lh) - lr * jnp.sign(g + wd * w), ()
        mom = self.momentum * state[0] - (1 - self.momentum) * (g + wd * w)
        return w * (1 - lr * self.wd_lh) + lr * jnp.sign(mom), (mom,)


@register
class LAMB(Optimizer):
    """Layer-wise adaptive moments (reference: contrib lamb_update_phase1/2),
    the BERT-large large-batch optimizer of the north star."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-6,
                 lower_bound=None, upper_bound=None, bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        def z():
            return jnp.zeros(weight.shape, jnp.float32)
        return (z(), z())

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g).astype(jnp.float32)
        wf = w.astype(jnp.float32)
        m = self.beta1 * state[0] + (1 - self.beta1) * g
        v = self.beta2 * state[1] + (1 - self.beta2) * jnp.square(g)
        if self.bias_correction:
            mhat = m / (1 - self.beta1 ** t)
            vhat = v / (1 - self.beta2 ** t)
        else:
            mhat, vhat = m, v
        r = mhat / (jnp.sqrt(vhat) + self.epsilon) + wd * wf
        w_norm = jnp.linalg.norm(wf)
        r_norm = jnp.linalg.norm(r)
        if self.lower_bound is not None:
            w_norm = jnp.maximum(w_norm, self.lower_bound)
        if self.upper_bound is not None:
            w_norm = jnp.minimum(w_norm, self.upper_bound)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return (wf - lr * trust * r).astype(w.dtype), (m, v)


@register
class LARS(Optimizer):
    """Layer-wise adaptive rate scaling (ResNet large-batch recipes)."""

    def __init__(self, learning_rate=0.1, momentum=0.9, eta=0.001, epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum, self.eta, self.epsilon = momentum, eta, epsilon

    def create_state(self, index, weight):
        return (jnp.zeros(weight.shape, weight._data.dtype),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g)
        w_norm = jnp.linalg.norm(w)
        g_norm = jnp.linalg.norm(g)
        trust = jnp.where((w_norm > 0) & (g_norm > 0),
                          self.eta * w_norm / (g_norm + wd * w_norm + self.epsilon), 1.0)
        mom = self.momentum * state[0] + lr * trust * (g + wd * w)
        return w - mom, (mom,)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py DCASGD —
    Zheng et al.): compensates gradient staleness with a λ·g²·(w − w_prev)
    term."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum, self.lamda = momentum, lamda

    def create_state(self, index, weight):
        # reference parity: no momentum buffer at the default momentum=0.0
        if self.momentum == 0.0:
            return (weight._data,)     # (previous weight,)
        z = jnp.zeros(weight.shape, weight._data.dtype)
        return (z, weight._data)       # (momentum, previous weight)

    def step(self, w, g, state, lr, wd, t):
        # Delay compensation uses the RAW (rescaled/clipped) gradient; weight
        # decay enters the update separately (reference: dcasgd_update's
        # lamda*grad*grad*(weight - previous_weight) + wd*weight).
        g = self._prep_grad(g)
        prev = state[-1]
        comp = g + wd * w + self.lamda * jnp.square(g) * (w - prev)
        if self.momentum == 0.0:
            return w - lr * comp, (w,)
        mom = self.momentum * state[0] - lr * comp
        return w + mom, (mom, w)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py SGLD):
    SGD plus N(0, lr) gradient noise — a Bayesian sampler, not a descent
    method. Each parameter's state carries its own base key drawn from the
    global RNG (so mx.random.seed governs it and parameters decorrelate);
    the step counter folds in per update for jit purity."""

    def create_state(self, index, weight):
        from .. import random as _rng
        self._key_impl = _rng._impl()
        base = jax.random.fold_in(_rng.next_key(), index)
        # store RAW key data (plain uint32) so optimizer states stay
        # picklable/serializable like every other state array
        return (jax.random.key_data(base),)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        base = jax.random.wrap_key_data(
            state[0], impl=getattr(self, "_key_impl", None) or "threefry2x32")
        key = jax.random.fold_in(base, t)
        noise = jax.random.normal(key, w.shape, jnp.float32) * jnp.sqrt(lr)
        return w - 0.5 * lr * g + noise.astype(w.dtype), state


@register
class Adamax(Optimizer):
    """Adam with an infinity-norm second moment (reference: Adamax)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight._data.dtype)
        return (z, z)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        m = self.beta1 * state[0] + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * state[1], jnp.abs(g))
        lr_t = lr / (1.0 - self.beta1 ** t)
        return w - lr_t * m / (u + 1e-8), (m, u)


@register
class Nadam(Optimizer):
    """Adam with Nesterov momentum (reference: Nadam, Dozat 2016)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon, self.schedule_decay = epsilon, schedule_decay

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight._data.dtype)
        return (z, z, jnp.ones((), jnp.float32))   # (m, v, m_schedule)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        m_prev, v_prev, m_schedule = state
        mu_t = self.beta1 * (1 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mu_t1 = self.beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        # cumulative momentum schedule (reference: m_schedule *= mu_t)
        m_schedule = m_schedule * mu_t
        m_schedule_next = m_schedule * mu_t1
        m = self.beta1 * m_prev + (1 - self.beta1) * g
        v = self.beta2 * v_prev + (1 - self.beta2) * jnp.square(g)
        g_hat = g / (1 - m_schedule)
        m_hat = m / (1 - m_schedule_next)
        m_bar = (1 - mu_t) * g_hat + mu_t1 * m_hat
        v_hat = v / (1 - self.beta2 ** t)
        return (w - lr * m_bar / (jnp.sqrt(v_hat) + self.epsilon),
                (m, v, m_schedule))


@register
class FTML(Optimizer):
    """Follow the moving leader (reference: FTML, Zheng & Kwok 2017)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros(weight.shape, weight._data.dtype)
        return (z, z, z)               # (v, d, z)

    def step(self, w, g, state, lr, wd, t):
        g = self._prep_grad(g) + wd * w
        v_prev, d_prev, z_prev = state
        v_t = self.beta2 * v_prev + (1 - self.beta2) * jnp.square(g)
        d_t = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v_t / (1 - self.beta2 ** t)) + self.epsilon)
        sigma_t = d_t - self.beta1 * d_prev
        z_t = self.beta1 * z_prev + (1 - self.beta1) * g - sigma_t * w
        return -z_t / d_t, (v_t, d_t, z_t)


Ftrl = FTRL  # reference exposes both spellings


class Updater:
    """Stateful (index, weight, grad) applier — reference ``get_updater``
    surface used by KVStore server-side optimization."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[int, Any] = {}

    def __call__(self, index, grad: NDArray, weight: NDArray):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
        self.states[index] = self.optimizer.update(index, weight, grad, self.states[index])

    def get_states(self, dump_optimizer=False):
        import pickle

        host = {k: jax.tree_util.tree_map(lambda a: __import__("numpy").asarray(a), v)
                for k, v in self.states.items()}
        return pickle.dumps((host, self.optimizer if dump_optimizer else None))

    def set_states(self, states: bytes):
        import pickle

        host, opt = pickle.loads(states)
        self.states = {k: jax.tree_util.tree_map(jnp.asarray, v) for k, v in host.items()}
        if opt is not None:
            self.optimizer = opt


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
