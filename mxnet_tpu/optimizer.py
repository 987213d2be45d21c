"""Optimizers (ref: python/mxnet/optimizer.py:35,432-1197).

Same registry/Updater architecture as the reference: an ``Optimizer``
computes one parameter's update from (weight, grad, state); the ``Updater``
closure owns per-index state and is what KVStore's ``set_updater`` installs
server-side (ref: kvstore_dist_server.h updater_).

Each ``update`` calls a fused op from ops/optimizer_ops.py — one XLA program
per (optimizer, shape), the analogue of the reference's fused
``sgd_mom_update``-style kernels (ref: src/operator/optimizer_op.cc).
"""
from __future__ import annotations

import math
import pickle
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray, invoke, zeros
from .ndarray import ndarray as _nd

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdaGrad", "AdaDelta", "RMSProp",
           "Ftrl", "Adamax", "Nadam", "Signum", "SGLD", "DCASGD", "FTML",
           "LBSGD", "Updater", "get_updater", "create", "register", "Test",
           "fused_sgd_mom_flat", "fused_sgd_mom_grouped", "pack_flat",
           "unpack_flat"]

_REGISTRY: Dict[str, type] = {}


def _rsp_grad(grad):
    """If ``grad`` is row-sparse, return (gdata, rows) NDArrays for the
    lazy row-wise update ops; else None (dense path)."""
    from .ndarray import sparse as _sparse

    if isinstance(grad, _sparse.RowSparseNDArray):
        p = grad._parts()
        return (NDArray.from_raw(p["data"], grad.context),
                NDArray.from_raw(p["indices"], grad.context))
    return None


def register(klass):
    """ref: Optimizer.register."""
    _REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs) -> "Optimizer":
    if isinstance(name, Optimizer):
        return name
    try:
        return _REGISTRY[name.lower()](**kwargs)
    except KeyError:
        raise MXNetError("unknown optimizer %r" % name) from None


class Optimizer:
    """ref: python/mxnet/optimizer.py Optimizer."""

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
        self._index_update_count: Dict[int, int] = {}
        self.idx2name = dict(param_idx2name or {})
        self.lr_mult: Dict[Any, float] = {}
        self.wd_mult: Dict[Any, float] = {}
        self.multi_precision = multi_precision
        self.param_dict = param_dict or {}

    create_optimizer = staticmethod(create)

    # -- state ----------------------------------------------------------
    def create_state(self, index, weight: NDArray):
        return None

    def create_state_multi_precision(self, index, weight):
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        self.update(index, weight, grad, state)

    # -- bookkeeping ----------------------------------------------------
    def set_learning_rate(self, lr):
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            # reference defaults: no decay on bias/gamma/beta
            if n.endswith("_bias") or n.endswith("_gamma") or n.endswith("_beta"):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        self._index_update_count.setdefault(index, self.begin_num_update)
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index) -> float:
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        name = self.idx2name.get(index, index)
        lr *= self.lr_mult.get(name, self.lr_mult.get(index, 1.0))
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        name = self.idx2name.get(index, index)
        wd *= self.wd_mult.get(name, self.wd_mult.get(index, 1.0))
        return wd

    def _clip(self):
        return self.clip_gradient if self.clip_gradient is not None else -1.0


@register
class SGD(Optimizer):
    """SGD with momentum, fused update (ref: optimizer.py SGD +
    src/operator/optimizer_op.cc sgd_mom_update)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        sp = _rsp_grad(grad) if self.lazy_update else None
        if sp is not None:
            gdata, rows = sp
            if state is None:
                invoke("_sparse_sgd_update", [weight, gdata, rows],
                       {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                        "clip_gradient": self._clip()}, out=weight)
            else:
                invoke("_sparse_sgd_mom_update", [weight, gdata, rows, state],
                       {"lr": lr, "momentum": self.momentum, "wd": wd,
                        "rescale_grad": self.rescale_grad,
                        "clip_gradient": self._clip()}, out=weight)
        elif state is None:
            invoke("sgd_update", [weight, grad],
                   {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)
        else:
            invoke("sgd_mom_update", [weight, grad, state],
                   {"lr": lr, "momentum": self.momentum, "wd": wd,
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if state is None:
            invoke("sgd_update", [weight, grad],
                   {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)
        else:
            invoke("nag_mom_update", [weight, grad, state],
                   {"lr": lr, "momentum": self.momentum, "wd": wd,
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        lr *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        mean, var = state
        sp = _rsp_grad(grad) if self.lazy_update else None
        if sp is not None:
            gdata, rows = sp
            invoke("_sparse_adam_update", [weight, gdata, rows, mean, var],
                   {"lr": lr, "beta1": self.beta1, "beta2": self.beta2,
                    "epsilon": self.epsilon, "wd": wd,
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)
            return
        invoke("adam_update", [weight, grad, mean, var],
               {"lr": lr, "beta1": self.beta1, "beta2": self.beta2,
                "epsilon": self.epsilon, "wd": wd,
                "rescale_grad": self.rescale_grad,
                "clip_gradient": self._clip()}, out=weight)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        sp = _rsp_grad(grad)
        if sp is not None:
            gdata, rows = sp
            invoke("_sparse_adagrad_update", [weight, gdata, rows, state],
                   {"lr": self._get_lr(index), "epsilon": self.float_stable_eps,
                    "wd": self._get_wd(index),
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)
            return
        invoke("adagrad_update", [weight, grad, state],
               {"lr": self._get_lr(index), "epsilon": self.float_stable_eps,
                "wd": self._get_wd(index), "rescale_grad": self.rescale_grad,
                "clip_gradient": self._clip()}, out=weight)


@register
class RMSProp(Optimizer):
    """ref: optimizer.py RMSProp — centered=True uses Graves' variant."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = lambda: zeros(weight.shape, weight.context, dtype=weight.dtype)
        if self.centered:
            return (z(), z(), z())
        return z()

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        cw = self.clip_weights if self.clip_weights is not None else -1.0
        if self.centered:
            n, g, delta = state
            invoke("rmspropalex_update", [weight, grad, n, g, delta],
                   {"lr": lr, "gamma1": self.gamma1, "gamma2": self.gamma2,
                    "epsilon": self.epsilon, "wd": wd,
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip(), "clip_weights": cw},
                   out=weight)
        else:
            invoke("rmsprop_update", [weight, grad, state],
                   {"lr": lr, "gamma1": self.gamma1, "epsilon": self.epsilon,
                    "wd": wd, "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip(), "clip_weights": cw},
                   out=weight)


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.9, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        acc_g, acc_delta = state
        invoke("adadelta_update", [weight, grad, acc_g, acc_delta],
               {"rho": self.rho, "epsilon": self.epsilon,
                "wd": self._get_wd(index), "rescale_grad": self.rescale_grad,
                "clip_gradient": self._clip()}, out=weight)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        z, n = state
        invoke("ftrl_update", [weight, grad, z, n],
               {"lr": self._get_lr(index), "lamda1": self.lamda1,
                "beta": self.beta, "wd": self._get_wd(index),
                "rescale_grad": self.rescale_grad,
                "clip_gradient": self._clip()}, out=weight)


@register
class Adamax(Optimizer):
    """ref: optimizer.py Adamax (Adam with infinity norm)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        lr /= 1.0 - self.beta1 ** t
        m, u = state
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        m._assign(self.beta1 * m + (1.0 - self.beta1) * g)
        u._assign(_nd.invoke("broadcast_maximum", [self.beta2 * u, g.abs()]))
        weight._assign(weight - lr * m / u)


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        t = self._index_update_count[index]
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule *= momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m, v = state
        m._assign(self.beta1 * m + (1.0 - self.beta1) * g)
        v._assign(self.beta2 * v + (1.0 - self.beta2) * g * g)
        grad_prime = g / (1.0 - self.m_schedule)
        m_prime = m / (1.0 - m_schedule_next)
        v_prime = v / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - momentum_t) * grad_prime + momentum_t_1 * m_prime
        weight._assign(weight - lr * m_bar / (v_prime.sqrt() + self.epsilon))


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if state is None:
            invoke("signsgd_update", [weight, grad],
                   {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip()}, out=weight)
        else:
            invoke("signum_update", [weight, grad, state],
                   {"lr": lr, "momentum": self.momentum, "wd": wd,
                    "rescale_grad": self.rescale_grad,
                    "clip_gradient": self._clip(), "wd_lh": self.wd_lh},
                   out=weight)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (ref: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        from . import random as _random

        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        noise = _random.normal(0, math.sqrt(lr), weight.shape, ctx=weight.context)
        weight._assign(weight - lr / 2 * g + noise)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (ref: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous: Dict[Any, NDArray] = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = (zeros(weight.shape, weight.context, dtype=weight.dtype)
               if self.momentum != 0.0 else None)
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        mom, prev = state
        comp = g + self.lamda * g * g * (weight - prev)
        if mom is not None:
            mom._assign(self.momentum * mom - lr * comp)
            step = mom
        else:
            step = -lr * comp
        weight.copyto(prev)
        weight._assign(weight + step if mom is not None else weight + step)


@register
class FTML(Optimizer):
    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        z = lambda: zeros(weight.shape, weight.context, dtype=weight.dtype)
        return (z(), z(), z())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        d, v, z = state
        invoke("ftml_update", [weight, grad, d, v, z],
               {"lr": self._get_lr(index), "beta1": self.beta1,
                "beta2": self.beta2, "epsilon": self.epsilon,
                "wd": self._get_wd(index), "rescale_grad": self.rescale_grad,
                "clip_grad": self._clip(),
                "t": self._index_update_count[index]}, out=weight)


@register
class LBSGD(SGD):
    """Large-batch SGD: LARS layer-wise adaptive rate scaling (You et
    al. 2017) with linear lr warmup — the update rule that keeps
    TPU-pod-scale data-parallel batches (8k-32k) converging.  Beyond
    the reference's registry (which stops at plain SGD); the fused
    ``lars_sgd_mom_update`` op computes the trust ratio on device.

    Parameters
    ----------
    eta : LARS trust coefficient.
    warmup_steps : updates over which lr ramps linearly from
        ``lr * warmup_init`` to ``lr`` (0 disables warmup).
    """

    def __init__(self, momentum=0.9, eta=0.001, eps=1e-9,
                 warmup_steps=0, warmup_init=0.1, **kwargs):
        super().__init__(momentum=momentum, **kwargs)
        self.eta = float(eta)
        self.eps = float(eps)
        self.warmup_steps = int(warmup_steps)
        self.warmup_init = float(warmup_init)

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context, dtype=weight.dtype)

    def _warm_lr(self, index):
        lr = self._get_lr(index)
        t = self._index_update_count.get(index, 1)
        if self.warmup_steps and t < self.warmup_steps:
            frac = t / float(self.warmup_steps)
            lr = lr * (self.warmup_init + (1.0 - self.warmup_init) * frac)
        return lr

    def update(self, index, weight, grad, state):
        self._update_count(index)
        invoke("lars_sgd_mom_update", [weight, grad, state],
               {"lr": self._warm_lr(index), "momentum": self.momentum,
                "wd": self._get_wd(index), "eta": self.eta,
                "eps": self.eps, "rescale_grad": self.rescale_grad,
                "clip_gradient": self._clip()}, out=weight)


@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight._assign(weight + grad * self.rescale_grad)


# ---------------------------------------------------------------------------
# SGD-with-momentum as jax-level building blocks for the compiled train
# steps (parallel/dp.py FusedTrainStep, the transformer tier) — the
# per-key ``invoke`` path above stays for the Updater / kvstore
# server-side-update heritage.  ``fused_sgd_mom_flat`` is the ONE
# elementwise formula, shape-agnostic and dtype-preserving: the one-chip
# and replicated steps apply it leaf by leaf, each parameter in its own
# shape (``fused_sgd_mom_grouped``: inside one XLA program there are no
# launches to save, and on the TPU's tiled layouts a flat copy of a
# weight is a relayout, not a view); ZeRO-1 applies it to each rank's
# shard of a bucket's flat (``pack_flat`` / ``unpack_flat``, which the
# collectives need).  Per leaf == over the packed flats BITWISE (pinned
# in tests).
# ---------------------------------------------------------------------------
def pack_flat(arrays):
    """Concatenate arrays (homogeneous dtype) into one flat buffer."""
    import jax.numpy as jnp

    if len(arrays) == 1:
        return arrays[0].ravel()
    return jnp.concatenate([a.ravel() for a in arrays])


def unpack_flat(flat, ref_arrays):
    """Split a flat buffer back into ``ref_arrays``' shapes, in order."""
    out = []
    off = 0
    for ref in ref_arrays:
        sz = ref.size
        out.append(flat[off:off + sz].reshape(ref.shape))
        off += sz
    return out


def pack_flat_np(arrays):
    """Host-side (numpy) sibling of :func:`pack_flat` — the elastic
    restage path repacks checkpointed momenta on the host, before any
    device placement."""
    import numpy as np

    if len(arrays) == 1:
        return np.asarray(arrays[0]).ravel()
    return np.concatenate([np.asarray(a).ravel() for a in arrays])


def unpack_flat_np(flat, shapes):
    """Host-side :func:`unpack_flat` over explicit ``shapes`` (the
    restage path has shapes, not live ref arrays)."""
    import numpy as np

    flat = np.asarray(flat)
    out = []
    off = 0
    for shape in shapes:
        sz = 1
        for d in shape:
            sz *= int(d)
        out.append(flat[off:off + sz].reshape(tuple(shape)))
        off += sz
    return out


def fused_sgd_mom_flat(flat_w, flat_g, flat_m, lr, momentum, wd):
    """SGD-with-momentum over arrays of one shape, flat or not: the one
    elementwise formula every compiled step runs
    (``g += wd*w; m = momentum*m - lr*g; w += m``); returns
    ``(new_w, new_m)``."""
    g = flat_g + wd * flat_w
    m = momentum * flat_m - lr * g
    return flat_w + m, m


def fused_sgd_mom_grouped(keys, params, grads, moms, lr, momentum, wd):
    """:func:`fused_sgd_mom_flat` on every leaf of ``keys`` where it
    lies, in its own shape and dtype: ``params`` / ``grads`` / ``moms``
    are indexables keyed by ``keys`` (dicts keyed by name, or lists
    keyed by position — both train-step tiers use this one helper, so
    their numerics can never diverge).  Returns
    ``({key: new_param}, {key: new_mom})``."""
    new_p, new_m = {}, {}
    for k in keys:
        new_p[k], new_m[k] = fused_sgd_mom_flat(
            params[k], grads[k], moms[k], lr, momentum, wd)
    return new_p, new_m


class Updater:
    """Per-index state closure (ref: optimizer.py Updater / get_updater);
    this object is what gets pickled to the kvstore server."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}

    def __call__(self, index, grad, weight):
        from . import profiler as _profiler

        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(
                index, weight
            )
            self.states_synced[index] = True
        # one optimizer span per parameter update, aggregated per
        # optimizer class — the trace's "update" lane next to compute
        # and comms (ref: the reference stamps its fused optimizer_op
        # kernels as engine ops); record_span no-ops when stopped
        with _profiler.span(type(self.optimizer).__name__ + "::update",
                            cat="optimizer"):
            self.optimizer.update_multi_precision(index, weight, grad,
                                                  self.states[index])

    def get_states(self, dump_optimizer=False) -> bytes:
        states = {
            k: _state_to_np(v) for k, v in self.states.items()
        }
        payload = (states, self.optimizer) if dump_optimizer else states
        return pickle.dumps(payload)

    def set_states(self, states: bytes) -> None:
        data = pickle.loads(states)
        if isinstance(data, dict) and "shards" in data \
                and "num_servers" in data:
            # the DIST kvstore's gathered-server-shards wrapper
            # (KVStoreDist.get_optimizer_states_bytes): an elastic
            # resume may hand a W-rank dist checkpoint's momenta to a
            # local updater — merge the per-server key shards (keys are
            # disjoint by crc32 sharding) into one state dict
            merged = {}
            for blob in data["shards"].values():
                if not blob:
                    continue
                sub = pickle.loads(blob)
                if isinstance(sub, tuple):
                    sub, self.optimizer = sub
                merged.update(sub)
            states = merged
        elif isinstance(data, tuple):
            states, self.optimizer = data
        else:
            states = data
        self.states = {k: _state_from_np(v) for k, v in states.items()}
        self.states_synced = {k: False for k in self.states}


def _state_to_np(state):
    if state is None:
        return None
    if isinstance(state, (list, tuple)):
        return tuple(_state_to_np(s) for s in state)
    return state.asnumpy()


def _state_from_np(state):
    from .ndarray import array

    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_state_from_np(s) for s in state)
    return array(state)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
