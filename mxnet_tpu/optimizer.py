"""Optimizers (reference: python/mxnet/optimizer.py — registry :112, SGD with
fp16 master weights :494, LBSGD :672, Updater :1498).

All 16 registered reference optimizers are provided.  Updates are jnp
expressions over the parameter/grad/state buffers; under the Module/Trainer
fused path they are jitted together with the step.  Multi-precision mirrors
the reference: bf16/fp16 params keep an f32 master copy in the state.
"""
from __future__ import annotations

import math
import pickle
from typing import Dict, Optional

import numpy as _np
import jax
import jax.numpy as jnp

from .base import Registry
from .ndarray.ndarray import NDArray

__all__ = ["Optimizer", "SGD", "Signum", "FTML", "LBSGD", "DCASGD", "NAG", "SGLD",
           "Adam", "AdaGrad", "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam",
           "Test", "register", "create", "Updater", "get_updater"]

_REG: Registry = Registry("optimizer")


def register(klass):
    _REG.register(klass.__name__)(klass)
    return klass


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    return _REG.get(name)(**kwargs)


class Optimizer:
    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None, **kwargs):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self.multi_precision = multi_precision
        self._index_update_count: Dict[int, int] = {}
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        self.lr_mult: Dict[str, float] = {}
        self.wd_mult: Dict[str, float] = {}
        self._sym_wd_mult: Dict[str, float] = {}
        # bumped by set_lr_mult / set_wd_mult: a fused step plan bakes the
        # multipliers into its program and is rebuilt when this moves
        self._mult_epoch = 0
        if sym is not None:
            attrs = sym.attr_dict()
            for name, a in attrs.items():
                if "__lr_mult__" in a:
                    self.lr_mult[name] = float(a["__lr_mult__"])
                if "__wd_mult__" in a:
                    self.wd_mult[name] = float(a["__wd_mult__"])
                    self._sym_wd_mult[name] = float(a["__wd_mult__"])

    # -- bookkeeping --------------------------------------------------------------
    def set_learning_rate(self, lr):
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult.update(args_lr_mult)
        self._mult_epoch = getattr(self, "_mult_epoch", 0) + 1

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        # symbol-declared __wd_mult__ attrs survive a set_wd_mult call
        # (reference optimizer.py set_wd_mult re-reads sym attrs)
        self.wd_mult.update(self._sym_wd_mult)
        self.wd_mult.update(args_wd_mult)
        self._mult_epoch = getattr(self, "_mult_epoch", 0) + 1

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr_mult(self, index):
        # gluon Parameters (Trainer wires them in via param_dict) take
        # precedence, like the reference's _get_lrs
        if index in self.param_dict:
            return getattr(self.param_dict[index], "lr_mult", 1.0)
        name = self.idx2name.get(index, index if isinstance(index, str) else None)
        return self.lr_mult.get(name, 1.0)

    def _get_wd_mult(self, index):
        if index in self.param_dict:
            return getattr(self.param_dict[index], "wd_mult", 1.0)
        name = self.idx2name.get(index, index if isinstance(index, str) else None)
        return self.wd_mult.get(name, 1.0)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        return lr * self._get_lr_mult(index)

    def _get_wd(self, index):
        return self.wd * self._get_wd_mult(index)

    def _preprocess_grad_data(self, g):
        g = g * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def _preprocess_grad(self, grad):
        return self._preprocess_grad_data(grad._data)

    def _needs_master(self, weight):
        return self.multi_precision and weight.dtype in (_np.float16, jnp.bfloat16)

    # -- fused (jit-traceable) update API -----------------------------------------
    # A fused-capable optimizer also expresses its update as a pure function
    # over jnp values so the whole train step (forward + backward + every
    # parameter's update) traces into ONE donated XLA program
    # (Executor.fused_step) instead of a Python loop of per-param dispatches —
    # the reference's CreateCachedSegOpr bulking taken to the optimizer.
    fused_step_supported = False

    #: Contract for the partition-rule sharded fused step (docs/sharding.md):
    #: ``update_step`` must be ELEMENTWISE in (weight, grad, state) — no
    #: cross-element reductions like a global weight/grad norm — so running
    #: it on each device's mp SHARD equals running it on the full tensor,
    #: and optimizer state (incl. AMP f32 masters, which inherit the
    #: weight's sharding via ``zeros_like``/``astype`` at create_state time)
    #: can live sharded.  Every fused optimizer here satisfies this; a
    #: norm-based optimizer (LARS/LAMB-style) must set it False, which
    #: routes mp-sharded training back to the legacy path rather than
    #: silently computing per-shard norms.
    update_step_elementwise = True

    def fused_static_key(self):
        """Hyperparameters baked into a fused trace as constants; part of the
        compile-cache key so changing them recompiles rather than reusing a
        stale program."""
        return (type(self).__name__, float(self.rescale_grad),
                None if self.clip_gradient is None else float(self.clip_gradient))

    def fused_host_lr(self, lr, t):
        """Step-count-dependent lr correction, applied HOST-side in float64 —
        exactly as the imperative :meth:`update` computes it — before the lr
        enters the trace.  Keeps fused/legacy parity at the ulp level for
        bias-corrected optimizers (Adam); default is identity."""
        return lr

    def update_step(self, weight, grad, state, lr, wd, t=None):
        """Functional twin of :meth:`update`: ``(new_weight, new_state)`` from
        jnp values (weight/grad arrays, state pytree of arrays as laid out by
        ``create_state`` with NDArray leaves replaced by their buffers).
        ``lr``/``wd`` arrive as traced scalars with the scheduler value,
        per-param multipliers, and :meth:`fused_host_lr` correction already
        applied; ``t`` is the traced per-param update count (for optimizers
        whose math needs it in-trace).  Must be side-effect free and
        jit-traceable."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support the fused update path")

    # -- API ----------------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self._needs_master(weight):
            master = NDArray(weight._data.astype(jnp.float32))
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self._needs_master(weight):
            master, inner = state
            g32 = NDArray(grad._data.astype(jnp.float32))
            self.update(index, master, g32, inner)
            weight._data = master._data.astype(weight._data.dtype)
        else:
            self.update(index, weight, grad, state)


@register
class SGD(Optimizer):
    """SGD with momentum + lazy sparse updates (reference: optimizer.py:494)."""

    fused_step_supported = True

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def fused_static_key(self):
        return super().fused_static_key() + (float(self.momentum),)

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad) + wd * weight
        if state is None:
            return weight - lr * g, None
        mom = self.momentum * state - lr * g
        return weight + mom, mom

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        if state is None:
            weight._data = weight._data - lr * g
        else:
            mom = self.momentum * state._data - lr * g
            state._data = mom
            weight._data = weight._data + mom


@register
class Signum(Optimizer):
    """signSGD with momentum (reference: optimizer.py Signum)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad)
        if state is not None:
            m = self.momentum * state._data - (1 - self.momentum) * (g + wd * weight._data)
            state._data = m
            weight._data = (1 - lr * self.wd_lh) * weight._data + lr * jnp.sign(m)
        else:
            weight._data = (1 - lr * (wd + self.wd_lh)) * weight._data - lr * jnp.sign(g)


@register
class FTML(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z), NDArray(z))  # d, v, z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        d, v, z = state
        v_t = self.beta2 * v._data + (1 - self.beta2) * g * g
        d_t = (1 - self.beta1 ** t) / lr * (
            jnp.sqrt(v_t / (1 - self.beta2 ** t)) + self.epsilon)
        sigma = d_t - self.beta1 * d._data
        z_t = self.beta1 * z._data + (1 - self.beta1) * g - sigma * weight._data
        weight._data = -z_t / d_t
        d._data, v._data, z._data = d_t, v_t, z_t


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (reference: optimizer.py:672)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(multi_precision=multi_precision, **kwargs)
        self.momentum = momentum
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.lbmult = 1.0

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def _get_lbmult(self, nup):
        nwup = self.warmup_epochs * self.updates_per_epoch
        if nup >= nwup or nwup == 0:
            return self.batch_scale
        if self.warmup_strategy == "linear":
            return 1.0 + (self.batch_scale - 1) * nup / nwup
        if self.warmup_strategy == "power2":
            return 1.0 + (self.batch_scale - 1) * (nup * nup) / (nwup * nwup)
        if self.warmup_strategy == "sqrt":
            return 1.0 + (self.batch_scale - 1) * math.sqrt(nup / nwup)
        return 1.0

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad)
        if self.warmup_strategy == "lars":
            w_norm = float(jnp.linalg.norm(weight._data.astype(jnp.float32).reshape(-1)))
            g_norm = float(jnp.linalg.norm(g.astype(jnp.float32).reshape(-1)))
            if w_norm > 0 and g_norm > 0:
                lr = lr * (w_norm / (g_norm + wd * w_norm))
        else:
            lr = lr * self._get_lbmult(self.num_update - self.init_updates)
        mom = self.momentum * state._data - lr * (g + wd * weight._data)
        state._data = mom
        weight._data = weight._data + mom


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = NDArray(jnp.zeros_like(weight._data)) if self.momentum != 0 else None
        prev = NDArray(weight._data)
        return (mom, prev)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad)
        mom, prev = state
        comp = g + self.lamda * g * g * (weight._data - prev._data)
        if mom is not None:
            m = self.momentum * mom._data - lr * (comp + wd * weight._data)
            mom._data = m
            delta = m
        else:
            delta = -lr * (comp + wd * weight._data)
        prev._data = weight._data
        weight._data = weight._data + delta


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference: optimizer.py NAG)."""

    fused_step_supported = True

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def fused_static_key(self):
        return super().fused_static_key() + (float(self.momentum),)

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad) + wd * weight
        if state is None:
            return weight - lr * g, None
        m = self.momentum * state + g
        return weight - lr * (g + self.momentum * m), m

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        if state is None:
            weight._data = weight._data - lr * g
        else:
            m = self.momentum * state._data + g
            state._data = m
            weight._data = weight._data - lr * (g + self.momentum * m)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        from . import random as _random
        import jax

        noise = jax.random.normal(_random.next_key(), weight.shape,
                                  dtype=weight._data.dtype) * math.sqrt(lr)
        weight._data = weight._data - lr / 2 * g + noise


@register
class Adam(Optimizer):
    fused_step_supported = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z))

    def fused_static_key(self):
        return super().fused_static_key() + (
            float(self.beta1), float(self.beta2), float(self.epsilon))

    def fused_host_lr(self, lr, t):
        # same float64 host math as update(); the traced path applying a
        # pre-rounded f32 lr then matches the legacy loop at the ulp level
        return lr * math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad) + wd * weight
        m, v = state
        m2 = self.beta1 * m + (1 - self.beta1) * g
        v2 = self.beta2 * v + (1 - self.beta2) * g * g
        return weight - lr * m2 / (jnp.sqrt(v2) + self.epsilon), (m2, v2)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        lr = lr * math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        g = self._preprocess_grad(grad) + wd * weight._data
        m, v = state
        m._data = self.beta1 * m._data + (1 - self.beta1) * g
        v._data = self.beta2 * v._data + (1 - self.beta2) * g * g
        weight._data = weight._data - lr * m._data / (jnp.sqrt(v._data) + self.epsilon)


@register
class AdaGrad(Optimizer):
    fused_step_supported = True

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def fused_static_key(self):
        return super().fused_static_key() + (float(self.float_stable_eps),)

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad)
        s2 = state + g * g
        w2 = weight - lr * (g / jnp.sqrt(s2 + self.float_stable_eps)
                            + wd * weight)
        return w2, s2

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        # reference semantics (adagrad in optimizer.py): the history
        # accumulates the bare gradient; weight decay applies OUTSIDE it
        g = self._preprocess_grad(grad)
        state._data = state._data + g * g
        weight._data = weight._data - lr * (
            g / jnp.sqrt(state._data + self.float_stable_eps)
            + wd * weight._data)


@register
class RMSProp(Optimizer):
    fused_step_supported = True

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2, self.epsilon = gamma1, gamma2, epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        if self.centered:
            return (NDArray(z), NDArray(z), NDArray(z))  # n, g, delta
        return NDArray(z)

    def fused_static_key(self):
        return super().fused_static_key() + (
            float(self.gamma1), float(self.gamma2), float(self.epsilon),
            bool(self.centered),
            None if self.clip_weights is None else float(self.clip_weights))

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad) + wd * weight
        if self.centered:
            n, mg, delta = state
            n2 = (1 - self.gamma1) * g * g + self.gamma1 * n
            mg2 = (1 - self.gamma1) * g + self.gamma1 * mg
            d2 = self.gamma2 * delta - lr * g / jnp.sqrt(
                n2 - mg2 * mg2 + self.epsilon)
            w2, s2 = weight + d2, (n2, mg2, d2)
        else:
            n2 = (1 - self.gamma1) * g * g + self.gamma1 * state
            w2, s2 = weight - lr * g / jnp.sqrt(n2 + self.epsilon), n2
        if self.clip_weights:
            w2 = jnp.clip(w2, -self.clip_weights, self.clip_weights)
        return w2, s2

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        if self.centered:
            n, mg, delta = state
            n._data = (1 - self.gamma1) * g * g + self.gamma1 * n._data
            mg._data = (1 - self.gamma1) * g + self.gamma1 * mg._data
            delta._data = self.gamma2 * delta._data - lr * g / jnp.sqrt(
                n._data - mg._data * mg._data + self.epsilon)
            weight._data = weight._data + delta._data
        else:
            n = state
            n._data = (1 - self.gamma1) * g * g + self.gamma1 * n._data
            weight._data = weight._data - lr * g / jnp.sqrt(n._data + self.epsilon)
        if self.clip_weights:
            weight._data = jnp.clip(weight._data, -self.clip_weights, self.clip_weights)


@register
class AdaDelta(Optimizer):
    fused_step_supported = True

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z))

    def fused_static_key(self):
        return super().fused_static_key() + (float(self.rho), float(self.epsilon))

    def update_step(self, weight, grad, state, lr, wd, t=None):
        g = self._preprocess_grad_data(grad) + wd * weight
        acc_g, acc_delta = state
        a2 = self.rho * acc_g + (1 - self.rho) * g * g
        delta = jnp.sqrt(acc_delta + self.epsilon) / jnp.sqrt(
            a2 + self.epsilon) * g
        d2 = self.rho * acc_delta + (1 - self.rho) * delta * delta
        return weight - delta, (a2, d2)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        acc_g, acc_delta = state
        acc_g._data = self.rho * acc_g._data + (1 - self.rho) * g * g
        delta = jnp.sqrt(acc_delta._data + self.epsilon) / jnp.sqrt(acc_g._data + self.epsilon) * g
        acc_delta._data = self.rho * acc_delta._data + (1 - self.rho) * delta * delta
        weight._data = weight._data - delta


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z))  # z, n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad)
        z, n = state
        sigma = (jnp.sqrt(n._data + g * g) - jnp.sqrt(n._data)) / lr
        z._data = z._data + g - sigma * weight._data
        n._data = n._data + g * g
        weight._data = (jnp.sign(z._data) * self.lamda1 - z._data) / (
            (self.beta + jnp.sqrt(n._data)) / lr + wd) * (jnp.abs(z._data) > self.lamda1)


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        lr /= (1.0 - self.beta1 ** t)
        g = self._preprocess_grad(grad) + wd * weight._data
        m, u = state
        m._data = self.beta1 * m._data + (1 - self.beta1) * g
        u._data = jnp.maximum(self.beta2 * u._data, jnp.abs(g))
        weight._data = weight._data - lr * m._data / (u._data + 1e-8)


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        z = jnp.zeros_like(weight._data)
        return (NDArray(z), NDArray(z))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess_grad(grad) + wd * weight._data
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m, v = state
        m._data = self.beta1 * m._data + (1 - self.beta1) * g
        v._data = self.beta2 * v._data + (1 - self.beta2) * g * g
        g_prime = g / (1 - self.m_schedule)
        m_prime = m._data / (1 - m_schedule_next)
        v_prime = v._data / (1 - self.beta2 ** t)
        m_bar = (1 - momentum_t) * g_prime + momentum_t_1 * m_prime
        weight._data = weight._data - lr * m_bar / (jnp.sqrt(v_prime) + self.epsilon)


@register
class Test(Optimizer):
    """Test optimizer doing plain SGD (reference: optimizer.py Test)."""

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def update(self, index, weight, grad, state):
        weight._data = weight._data - self.rescale_grad * grad._data


ccSGD = SGD  # reference alias


# -- fused-update plumbing ---------------------------------------------------------
def _pack_state(s):
    """create_state structures (NDArray leaves, tuples, None) -> a jax pytree
    of raw device buffers, suitable as a jit argument."""
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_pack_state(x) for x in s)
    if isinstance(s, NDArray):
        return s._data
    return s


def _unpack_state_into(s, new):
    """Write a fused program's returned state pytree back into the NDArray
    leaves of the original create_state structure (in place, so Updater
    serialization and checkpoint round-trips keep working unchanged)."""
    if s is None:
        return
    if isinstance(s, (tuple, list)):
        for a, b in zip(s, new):
            _unpack_state_into(a, b)
    elif isinstance(s, NDArray):
        s._data = new


def fused_apply_update(optimizer, weight, grad, state, lr, wd, t, has_master):
    """One traced parameter update, master-weight aware (docs/amp.md).

    ``has_master`` is the STATIC per-param flag (part of every fused
    compile-cache key): when set, ``state`` is the ``(master_f32, inner)``
    pytree laid out by ``create_state_multi_precision`` — the update runs on
    the f32 master exactly like the legacy ``update_multi_precision`` loop
    (grad upcast, master stepped, low-precision weight recast from the
    master), all inside the donated fused program."""
    if not has_master:
        return optimizer.update_step(weight, grad, state, lr, wd, t)
    master, inner = state
    with jax.named_scope("amp.cast"):
        grad = grad.astype(master.dtype)
    new_master, new_inner = optimizer.update_step(master, grad, inner, lr,
                                                  wd, t)
    with jax.named_scope("amp.cast"):
        return new_master.astype(weight.dtype), (new_master, new_inner)


def uniquify_donated(trees):
    """Return ``trees`` with any REPEATED device buffer replaced by a fresh
    copy.  jax constant caching can hand identical zero-filled buffers to
    several same-shaped arrays (fresh grad/state buffers especially); donating
    such a buffer twice in one program is an XLA error.  First occurrence is
    kept (and donated), later ones are copied — a one-time cost on the first
    step only, since program outputs are always distinct."""
    seen = set()

    def fix(x):
        try:
            ptr = x.unsafe_buffer_pointer()
        except Exception:
            try:  # multi-device (replicated/sharded) array: key on the
                # first addressable shard's buffer — aliases share shards
                ptr = x.addressable_shards[0].data.unsafe_buffer_pointer()
            except Exception:
                ptr = id(x)
        if ptr in seen:
            return jnp.array(x, copy=True)
        seen.add(ptr)
        return x

    return jax.tree_util.tree_map(fix, trees)


def fused_mults(optimizer, indices) -> dict:
    """``{index: (lr_mult, wd_mult, count_delta)}``: Python floats a fused
    program bakes in as constants (part of its compile-cache key).  They
    change only through ``set_lr_mult`` / ``set_wd_mult`` (or a gluon
    Parameter's own multipliers), so a step plan computes them once.
    ``count_delta`` is a param's update count less the lead param's: 0.0,
    since a fused step runs only while the counts are uniform
    (:func:`fused_advance`)."""
    return {idx: (float(optimizer._get_lr_mult(idx)),
                  float(optimizer._get_wd_mult(idx)), 0.0)
            for idx in indices}


def fused_advance(optimizer, indices, num_steps=1):
    """The per-step remainder of a fused step's bookkeeping, in one pass:
    bump the update counts of ``indices`` exactly as the legacy per-param
    loop would (``num_steps`` times: ``_index_update_count``, ``num_update``,
    one scheduler call an inner step), and return the step's scalars
    ``(lrs, wd, ts)`` as Python floats — ``lrs`` / ``ts`` are tuples with
    one entry per inner step (the scheduler's lr after
    :meth:`fused_host_lr`, and the update count).

    Returns None, with nothing advanced, when the params carry MIXED update
    counts (a user interleaving partial legacy updates): one shared
    host-side lr correction per inner step is only exact for a uniform
    count, so such a step belongs to the per-param loop."""
    counts = optimizer._index_update_count
    begin = optimizer.begin_num_update
    t = counts.get(indices[0], begin)
    for idx in indices:
        if counts.get(idx, begin) != t:
            return None
    lrs, ts = [], []
    for _ in range(max(1, int(num_steps))):
        t += 1
        if t > optimizer.num_update:
            optimizer.num_update = t
        base = float(optimizer.lr_scheduler(optimizer.num_update)) \
            if optimizer.lr_scheduler else float(optimizer.lr)
        lrs.append(float(optimizer.fused_host_lr(base, t)))
        ts.append(float(t))
    counts.update(dict.fromkeys(indices, t))
    return tuple(lrs), float(optimizer.wd), tuple(ts)


# compiled all-params optimizer programs for the standalone update path
# (Module.update / kvstore updaters); keyed by optimizer statics + shapes so
# distinct instances with identical hyperparameters share one program
_FUSED_UPDATE_CACHE: Dict[tuple, object] = {}


def _note_compile_cache(hit: bool) -> None:
    from . import executor as _executor

    _executor._note_cache(hit)


class Updater:
    """Applies an optimizer to (index, grad, weight) triples, creating state
    lazily (reference: optimizer.py:1498 get_updater)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict = {}
        self.states_synced: Dict = {}

    def __call__(self, index, grad, weight):
        if isinstance(index, (list, tuple)):
            if not self._batch_fused(list(index), list(grad), list(weight)):
                for i, g, w in zip(index, grad, weight):
                    self._update(i, g, w)
        else:
            self._update(index, grad, weight)

    def _batch_fused(self, indices, grads, weights) -> bool:
        """Apply the whole batch of (index, grad, weight) updates as ONE jitted
        program over list pytrees (optimizer state donated) instead of a
        Python loop of per-param dispatches.  Returns False — caller falls
        back to the loop — whenever the optimizer, the buffers, or the
        environment can't take the fused path; the loop remains the semantic
        ground truth."""
        import os

        opt = self.optimizer
        if (not indices or os.environ.get("TPUMX_FUSED_STEP", "1") == "0"
                or not getattr(opt, "fused_step_supported", False)):
            return False
        from .ndarray import sparse as _sparse

        if any(isinstance(a, _sparse.BaseSparseNDArray)
               for a in list(weights) + list(grads)):
            return False
        try:  # mixed device placement (multi-device slots) stays on the loop
            devs = {tuple(sorted(d.id for d in w._data.devices()))
                    for w in weights}
            if len(devs) != 1:
                return False
        except Exception:
            return False
        scalars = fused_advance(opt, indices)
        if scalars is None:  # mixed update counts: the loop's
            return False
        lr_vec, wd, t_vec = (_np.asarray(x, _np.float32) for x in scalars)
        for i, w in zip(indices, weights):
            if i not in self.states:
                self.states[i] = opt.create_state_multi_precision(i, w)
        mults = fused_mults(opt, indices)
        w_vals = [w._data for w in weights]
        g_vals = [g._data for g in grads]
        s_vals = uniquify_donated(
            tuple(_pack_state(self.states[i]) for i in indices))
        # static per-slot master-weight flags (multi_precision low-precision
        # params carry (master_f32, state) — docs/amp.md); part of the key
        has_master = tuple(opt._needs_master(w) for w in weights)
        key = (opt.fused_static_key(), has_master,
               tuple(mults[i] for i in indices),
               tuple((v.shape, str(v.dtype)) for v in w_vals),
               tuple((v.shape, str(v.dtype)) for v in g_vals))
        _note_compile_cache(hit=key in _FUSED_UPDATE_CACHE)
        if key not in _FUSED_UPDATE_CACHE:
            mult_list = [mults[i] for i in indices]

            def fused(w_vals, g_vals, s_vals, lr, wd, t):
                new_w, new_s = [], []
                for k in range(len(w_vals)):
                    lm, wm, dt = mult_list[k]
                    w2, s2 = fused_apply_update(
                        opt, w_vals[k], g_vals[k], s_vals[k],
                        lr[0] * lm, wd * wm, t[0] + dt, has_master[k])
                    new_w.append(w2)
                    new_s.append(s2)
                return new_w, tuple(new_s)

            # donate only the state (Updater-private, never aliased); weights
            # and grads stay readable — callers legitimately hold them
            # (kvstore values, grad buffers reused by the next backward)
            _FUSED_UPDATE_CACHE[key] = jax.jit(fused, donate_argnums=(2,))
        new_w, new_s = _FUSED_UPDATE_CACHE[key](
            w_vals, g_vals, s_vals, lr_vec, wd, t_vec)
        for k, (i, w) in enumerate(zip(indices, weights)):
            w._data = new_w[k]
            _unpack_state_into(self.states[i], new_s[k])
        return True

    def _update(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad, self.states[index])

    def get_states(self, dump_optimizer=False):
        def pack(s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(pack(x) for x in s)
            if isinstance(s, NDArray):
                return s.asnumpy()
            return s

        packed = {k: pack(v) for k, v in self.states.items()}
        if dump_optimizer:
            return pickle.dumps((packed, self.optimizer))
        return pickle.dumps(packed)

    def set_states(self, states):
        data = pickle.loads(states)
        if isinstance(data, tuple) and len(data) == 2 and isinstance(data[1], Optimizer):
            data, self.optimizer = data

        def unpack(s):
            if s is None:
                return None
            if isinstance(s, tuple):
                return tuple(unpack(x) for x in s)
            if isinstance(s, _np.ndarray):
                from .ndarray import array

                return array(s)
            return s

        self.states = {k: unpack(v) for k, v in data.items()}


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
