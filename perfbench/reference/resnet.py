"""Plain reference: ResNet v1 (bottleneck) training in float32.

Straight ``jax.numpy``/``lax`` at ``highest`` matmul precision, no
kernels, nothing imported from the program.  He et al.,
arXiv:1512.03385, in the layout of the reference framework's model zoo:
the stride of a bottleneck sits on its first 1x1 convolution, the first
and last 1x1 convolutions of a unit carry a bias, BatchNorm normalises
with the batch's own (biased) variance and eps 1e-5, the loss is the
mean softmax cross entropy and the optimizer is SGD with momentum
(``m = mu*m - lr*g; p = p + m``).  Running BatchNorm statistics do not
enter the training loss and are not modelled.

``leaves(cfg)`` lists every array of the model in the order the
program's own constructor defines them, so a driver can hand the same
weights to both sides by position.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5

Leaf = Tuple[str, Tuple[int, ...], str]


def _units(cfg: Dict):
    c_in = cfg["channels"][0]
    for stage, (units, c_out) in enumerate(zip(cfg["layers"],
                                               cfg["channels"][1:])):
        for unit in range(units):
            stride = 2 if (stage > 0 and unit == 0) else 1
            yield "s%du%d" % (stage + 1, unit), c_in, c_out, stride, \
                unit == 0
            c_in = c_out


def _bn_leaves(prefix: str, c: int) -> List[Leaf]:
    return [(prefix + ".gamma", (c,), "bn_gamma"),
            (prefix + ".beta", (c,), "bn_beta"),
            (prefix + ".mean", (c,), "bn_mean"),
            (prefix + ".var", (c,), "bn_var")]


def leaves(cfg: Dict) -> List[Leaf]:
    """``(name, shape, kind)`` of every array, in definition order."""
    c0 = cfg["channels"][0]
    out: List[Leaf] = [("stem.w", (c0, cfg["image_channels"], 7, 7),
                        "conv_w")]
    out += _bn_leaves("stem.bn", c0)
    for name, c_in, c_out, _, down in _units(cfg):
        mid = c_out // 4
        out += [(name + ".c1.w", (mid, c_in, 1, 1), "conv_w"),
                (name + ".c1.b", (mid,), "conv_b")]
        out += _bn_leaves(name + ".bn1", mid)
        out += [(name + ".c2.w", (mid, mid, 3, 3), "conv_w")]
        out += _bn_leaves(name + ".bn2", mid)
        out += [(name + ".c3.w", (c_out, mid, 1, 1), "conv_w"),
                (name + ".c3.b", (c_out,), "conv_b")]
        out += _bn_leaves(name + ".bn3", c_out)
        if down:
            out += [(name + ".down.w", (c_out, c_in, 1, 1), "conv_w")]
            out += _bn_leaves(name + ".down.bn", c_out)
    out += [("fc.w", (cfg["classes"], cfg["channels"][-1]), "fc_w"),
            ("fc.b", (cfg["classes"],), "fc_b")]
    return out


TRAINABLE = ("conv_w", "conv_b", "bn_gamma", "bn_beta", "fc_w", "fc_b")


def _conv(x, w, stride, pad, q):
    if q is not None:
        x, w = q(x), q(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    xn = (x - mean) * lax.rsqrt(var + BN_EPS)
    return xn * gamma[None, :, None, None] + beta[None, :, None, None]


def _unit(p: Dict, name: str, x, stride: int, down: bool, q):
    def bn(h, which):
        return _bn(h, p[name + which + ".gamma"], p[name + which + ".beta"])

    h = _conv(x, p[name + ".c1.w"], stride, 0, q) \
        + p[name + ".c1.b"][None, :, None, None]
    h = jax.nn.relu(bn(h, ".bn1"))
    h = jax.nn.relu(bn(_conv(h, p[name + ".c2.w"], 1, 1, q), ".bn2"))
    h = _conv(h, p[name + ".c3.w"], 1, 0, q) \
        + p[name + ".c3.b"][None, :, None, None]
    h = bn(h, ".bn3")
    if down:
        x = bn(_conv(x, p[name + ".down.w"], stride, 0, q), ".down.bn")
    return jax.nn.relu(h + x)


def forward(p: Dict, x, cfg: Dict, q: Optional[Callable] = None):
    """Logits (B, classes) for images ``x`` (B, C, H, W) float32.  ``q``
    is applied to both operands of every convolution and of the
    classifier: the low-precision control passes a quantiser."""
    h = _conv(x, p["stem.w"], 2, 3, q)
    h = jax.nn.relu(_bn(h, p["stem.bn.gamma"], p["stem.bn.beta"]))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for name, _, _, stride, down in _units(cfg):
        # recompute inside a unit in the backward pass: the float32
        # activations of a whole batch would not fit beside the model
        h = jax.checkpoint(
            lambda pp, hh, name=name, stride=stride, down=down:
            _unit(pp, name, hh, stride, down, q))(p, h)
    h = jnp.mean(h, axis=(2, 3))
    hq, wq = (h, p["fc.w"]) if q is None else (q(h), q(p["fc.w"]))
    return jnp.dot(hq, wq.T, precision=HIGHEST) + p["fc.b"]


def loss_fn(p: Dict, x, y, cfg: Dict, q=None):
    logits = forward(p, x, cfg, q)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=1))


def make_step(cfg: Dict, lr: float, momentum: float, q=None):
    """One jitted SGD-momentum step over the trainable leaves."""

    def step(p, m, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y, cfg, q)
        m = {k: momentum * m[k] - lr * g[k] for k in p}
        p = {k: p[k] + m[k] for k in p}
        return p, m, loss

    return jax.jit(step, donate_argnums=(0, 1))
