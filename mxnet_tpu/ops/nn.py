"""Neural-network operators.

TPU rebuild of src/operator/nn/ + the legacy prop-based root ops
(ref: SURVEY.md §2.2 — Convolution, FullyConnected, BatchNorm, Pooling,
Activation, Dropout, SoftmaxOutput, LeakyReLU, LRN, InstanceNorm …).

Design notes (tpu-first):
  * Convolution/FullyConnected lower straight to ``lax.conv_general_dilated``
    / ``jnp.dot`` so XLA tiles them onto the MXU; there is no im2col
    (ref: src/operator/nn/im2col.h is a CPU/GPU artifact the TPU does not
    want) and no cuDNN-style algo registry (cudnn_algoreg-inl.h) — XLA
    autotunes.
  * BatchNorm keeps the reference's aux-state contract: moving_mean/var are
    *inputs that the op mutates* (registry ``mutate_aux``), so Module/Gluon
    checkpointing sees the same state layout as the reference.
  * SoftmaxOutput reproduces the reference's gradient exactly: d(data) =
    (softmax - onehot(label)) * grad_scale, independent of the incoming
    cotangent (ref: src/operator/softmax_output-inl.h backward).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..base import np_dtype
from .registry import register

# ---------------------------------------------------------------------------
# cross-device batch semantics (sync-BN / global-batch normalization)
#
# When a per-device program (shard_map over a dp mesh axis — the bucketed
# gradient-exchange path, parallel/buckets.py) traces ops under this
# context, ops whose semantics involve BATCH statistics or BATCH-size
# normalization reduce over the named axis so the math stays identical
# to the SPMD-partitioned global program: BatchNorm moments become
# global-batch moments (equal per-device batches → pmean of local
# moments IS the global moment), SoftmaxOutput's normalization='batch'/
# 'valid' divides by the GLOBAL batch / valid count.  Without this, the
# shard_map form would silently train local-batch BN — different math,
# not reduction noise.
# ---------------------------------------------------------------------------
_cross_device_axis: list = []


@contextlib.contextmanager
def cross_device_batch_stats(axis_name: str):
    """Trace-time context: batch-statistics ops reduce over ``axis_name``."""
    _cross_device_axis.append(str(axis_name))
    try:
        yield
    finally:
        _cross_device_axis.pop()


def _batch_stats_axis() -> Optional[str]:
    return _cross_device_axis[-1] if _cross_device_axis else None


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _tup(v, n, default=None):
    if v is None or v == ():
        v = (default,) * n
    if isinstance(v, int):
        v = (v,) * n
    v = tuple(int(x) for x in v)
    if len(v) < n:
        v = v + (v[-1],) * (n - len(v))
    return v


def _conv_dims(kernel) -> int:
    return len(kernel)


# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/fully_connected.cc)
# ---------------------------------------------------------------------------
@register("FullyConnected", aliases=("fully_connected",),
          input_names=("data", "weight", "bias"))
def _fully_connected(data, weight, *maybe_bias, num_hidden=0, no_bias=False,
                     flatten=True, **_):
    x = data.reshape(data.shape[0], -1) if flatten else data
    # weight: (num_hidden, input_dim) — matches reference layout
    out = jnp.dot(x, weight.T)
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0]
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: src/operator/nn/convolution.cc,
# deconvolution.cc; layout NCHW / OIHW as the reference default)
# ---------------------------------------------------------------------------
_DIMNUMS = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
            3: ("NCDHW", "OIDHW", "NCDHW")}


@register("Convolution", aliases=("convolution", "Convolution_v1"),
          input_names=("data", "weight", "bias"))
def _convolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 workspace=1024, layout=None, cudnn_tune=None, cudnn_off=False, **_):
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd, 1)
    dilate = _tup(dilate, nd, 1)
    pad = _tup(pad, nd, 0)
    out = lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=tuple((p, p) for p in pad),
        rhs_dilation=dilate,
        feature_group_count=num_group,
        dimension_numbers=_DIMNUMS[nd],
        preferred_element_type=None,
    )
    if not no_bias and maybe_bias:
        bias = maybe_bias[0].reshape((1, -1) + (1,) * nd)
        out = out + bias
    return out


@register("Deconvolution", aliases=("deconvolution",),
          input_names=("data", "weight", "bias"))
def _deconvolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                   no_bias=True, workspace=1024, layout=None, **_):
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd, 1)
    dilate = _tup(dilate, nd, 1)
    pad = _tup(pad, nd, 0)
    adj = _tup(adj, nd, 0)
    # transposed conv = lhs-dilated conv with flipped kernel.
    # weight layout is (C_in, F/g, *k) in the reference → IOHW dim numbers.
    dn_map = {1: ("NCH", "IOH", "NCH"), 2: ("NCHW", "IOHW", "NCHW"),
              3: ("NCDHW", "IODHW", "NCDHW")}
    k_eff = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    padding = tuple(
        (ke - 1 - p, ke - 1 - p + a) for ke, p, a in zip(k_eff, pad, adj)
    )
    flipped = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    out = lax.conv_general_dilated(
        data,
        flipped,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        feature_group_count=num_group,
        dimension_numbers=dn_map[nd],
    )
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0].reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc; pool_type max/avg/sum,
# pooling_convention valid|full, global_pool, count_include_pad)
# ---------------------------------------------------------------------------
@register("Pooling", aliases=("pooling", "Pooling_v1"))
def _pooling(data, kernel=(), pool_type="max", global_pool=False,
             pooling_convention="valid", stride=(), pad=(),
             count_include_pad=True, cudnn_off=False, **_):
    nd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _tup(kernel, nd, 1)
        stride = _tup(stride, nd, 1)
        pad = _tup(pad, nd, 0)

    # pooling_convention="full" (ceil) may need extra right padding
    extra = [0] * nd
    if pooling_convention == "full" and not global_pool:
        for i in range(nd):
            x = data.shape[2 + i] + 2 * pad[i] - kernel[i]
            r = x % stride[i]
            if r != 0:
                extra[i] = stride[i] - r
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = ((0, 0), (0, 0)) + tuple(
        (p, p + e) for p, e in zip(pad, extra)
    )

    if pool_type == "max":
        init = -jnp.inf
        out = lax.reduce_window(data, init, lax.max, window, strides, padding)
        return out
    if pool_type in ("avg", "sum"):
        out = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return out
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return out / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return out / jnp.maximum(counts, 1.0)
    raise ValueError("unsupported pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# Activation / LeakyReLU (ref: src/operator/activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------
@register("Activation", aliases=("activation",))
def _activation(data, act_type="relu", **_):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %r" % act_type)


@register("LeakyReLU", input_names=("data", "gamma"))
def _leaky_relu(data, *maybe_gamma, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, **_):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        gamma = maybe_gamma[0]
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        return jax.nn.gelu(data)
    if act_type == "rrelu":
        # eval-mode slope = mean of the training range; the reference samples
        # uniformly per element during training (leaky_relu.cc) — sampling
        # variant is exposed separately via Dropout-style rng if needed.
        return jnp.where(data >= 0, data, 0.5 * (lower_bound + upper_bound) * data)
    raise ValueError("unknown act_type %r" % act_type)


# ---------------------------------------------------------------------------
# softmax family (ref: src/operator/nn/softmax.cc)
# ---------------------------------------------------------------------------
@register("softmax")
def _softmax(data, axis=-1, temperature=None, **_):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, **_):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def _softmin(data, axis=-1, **_):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance", **_):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# SoftmaxOutput — softmax forward + hardwired CE gradient
# (ref: src/operator/softmax_output-inl.h; the backward ignores the incoming
# cotangent, which is what makes Module's "loss-free" training graphs work)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=256)
def _softmax_output_fn(grad_scale, ignore_label, multi_output, use_ignore,
                       preserve_shape, normalization, out_grad, smooth_alpha):
    def fwd_only(data, label):
        if multi_output:
            return jax.nn.softmax(data, axis=1)
        if preserve_shape:
            return jax.nn.softmax(data, axis=-1)
        return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)

    @jax.custom_vjp
    def f(data, label):
        return fwd_only(data, label)

    def f_fwd(data, label):
        out = fwd_only(data, label)
        return out, (out, label)

    def f_bwd(res, g):
        prob, label = res
        if multi_output:
            # prob: (N, C, ...); label may arrive flat (N, prod(...)) —
            # the reference accepts both (fcn-xs feeds (N, H*W))
            lab = label.astype(jnp.int32).reshape(
                (prob.shape[0],) + prob.shape[2:])
            onehot = jax.nn.one_hot(lab, prob.shape[1], dtype=prob.dtype)
            onehot = jnp.moveaxis(onehot, -1, 1)
            grad = prob - onehot
            if use_ignore:
                mask = (lab != int(ignore_label)).astype(prob.dtype)
                grad = grad * mask[:, None]
            valid = prob.shape[0] * int(jnp.size(prob) // (prob.shape[0] * prob.shape[1]))
        else:
            flat = prob.reshape(-1, prob.shape[-1]) if preserve_shape else prob.reshape(
                prob.shape[0], -1
            )
            lab = label.reshape(-1).astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, flat.shape[-1], dtype=prob.dtype)
            if smooth_alpha:
                k = flat.shape[-1]
                onehot = onehot * (1.0 - smooth_alpha) + smooth_alpha / (k - 1) * (1.0 - onehot)
            grad = flat - onehot
            if use_ignore:
                mask = (lab != int(ignore_label)).astype(prob.dtype)
                grad = grad * mask[:, None]
            grad = grad.reshape(prob.shape)
        scale = grad_scale
        axn = _batch_stats_axis()
        if normalization == "batch":
            batch = prob.shape[0]
            if axn is not None:
                # per-device program: normalize by the GLOBAL batch
                batch = batch * lax.psum(1, axn)
            scale = scale / batch
        elif normalization == "valid" and use_ignore:
            lab_full = label.reshape(-1).astype(jnp.int32)
            nvalid = jnp.sum(lab_full != int(ignore_label))
            if axn is not None:
                nvalid = lax.psum(nvalid, axn)
            nvalid = jnp.maximum(nvalid, 1)
            grad = grad * (1.0 / nvalid.astype(prob.dtype))
        grad = grad * scale
        return grad, jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f


@register("SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0, **_):
    f = _softmax_output_fn(float(grad_scale), float(ignore_label),
                           bool(multi_output), bool(use_ignore),
                           bool(preserve_shape), str(normalization),
                           bool(out_grad), float(smooth_alpha))
    return f(data, label)


# ---------------------------------------------------------------------------
# regression outputs (ref: src/operator/regression_output.cc) — forward is
# identity/sigmoid, backward is (pred - label)*scale via custom_vjp
# ---------------------------------------------------------------------------
def _make_regression(name, link, grad_fn):
    @functools.lru_cache(maxsize=64)
    def builder(grad_scale):
        @jax.custom_vjp
        def f(data, label):
            return link(data)

        def f_fwd(data, label):
            out = link(data)
            return out, (out, label)

        def f_bwd(res, g):
            pred, label = res
            n = label.size // label.shape[0] if label.ndim else 1
            grad = grad_fn(pred, label.reshape(pred.shape)) * (grad_scale / n)
            return grad, jnp.zeros_like(label)

        f.defvjp(f_fwd, f_bwd)
        return f

    @register(name, aliases=(_snake(name),))
    def op(data, label, grad_scale=1.0, **_):
        return builder(float(grad_scale))(data, label)

    return op


def _snake(name):
    out = []
    for i, c in enumerate(name):
        if c.isupper() and i and not name[i - 1].isupper():
            out.append("_")
        out.append(c.lower())
    return "".join(out)


_make_regression("LinearRegressionOutput", lambda x: x, lambda p, l: p - l)
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda p, l: p - l)
_make_regression(
    "MAERegressionOutput", lambda x: x, lambda p, l: jnp.sign(p - l)
)


# ---------------------------------------------------------------------------
# BatchNorm (ref: src/operator/batch_norm.cc + nn/batch_norm.cc)
# inputs: data, gamma, beta, moving_mean, moving_var (aux, mutated)
# outputs: out [, batch_mean, batch_var] + aux writebacks
# ---------------------------------------------------------------------------
@register("BatchNorm", aliases=("batch_norm", "BatchNorm_v1"),
          mutate_aux=(3, 4), train_aware=True,
          input_names=("data", "gamma", "beta", "moving_mean",
                       "moving_var"))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                _training=True, **_):
    ax = axis % data.ndim
    reduce_axes = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[i] if i == ax else 1 for i in range(data.ndim))

    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mm = lax.stop_gradient(moving_mean)
    mv = lax.stop_gradient(moving_var)

    if _training and not use_global_stats:
        # single-pass statistics: E[x] and E[x²] reduce in ONE read of
        # the activation where mean-then-E[(x-mean)²] forces a second
        # dependent pass over HBM.  BN is bandwidth- not compute-bound
        # on TPU (resnet50-bf16@32 measured: two-pass 2398 img/s,
        # one-pass 2499, BN removed 3230 — a round-5 builder's run, not
        # a driver record); fp32
        # accumulation keeps the E[x²]−E[x]² cancellation benign.
        acc_t = jnp.promote_types(data.dtype, jnp.float32)
        xf = data.astype(acc_t)
        mean32 = jnp.mean(xf, axis=reduce_axes)
        ex2 = jnp.mean(xf * xf, axis=reduce_axes)
        axn = _batch_stats_axis()
        if axn is not None:
            # sync BN: equal per-device batches make pmean of the local
            # moments the exact global-batch moments — same statistics
            # the SPMD-partitioned program computes
            mean32 = lax.pmean(mean32, axn)
            ex2 = lax.pmean(ex2, axn)
        var32 = jnp.maximum(ex2 - mean32 * mean32, 0.0)
        new_mm = mm * momentum + \
            lax.stop_gradient(mean32).astype(mm.dtype) * (1.0 - momentum)
        new_mv = mv * momentum + \
            lax.stop_gradient(var32).astype(mv.dtype) * (1.0 - momentum)
    else:
        acc_t = jnp.promote_types(data.dtype, jnp.float32)
        mean32 = mm.astype(acc_t)
        var32 = mv.astype(acc_t)
        new_mm, new_mv = mm, mv

    # fold the normalization into per-channel scale/shift vectors so the
    # big tensor is touched once (x·scale + shift), not three times
    inv32 = lax.rsqrt(var32 + eps)
    scale = g.astype(inv32.dtype) * inv32
    shift = beta.astype(inv32.dtype) - mean32 * scale
    out = data * scale.reshape(bshape).astype(data.dtype) + \
        shift.reshape(bshape).astype(data.dtype)
    if output_mean_var:
        return (out, mean32.astype(data.dtype), inv32.astype(data.dtype),
                new_mm, new_mv)
    return out, new_mm, new_mv


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **_):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    ax = axis % data.ndim
    bshape = tuple(data.shape[i] if i == ax else 1 for i in range(data.ndim))
    out = (data - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(inv, ax)
    return out


@register("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps=1e-3, **_):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LRN", aliases=("lrn",))
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **_):
    # ref: src/operator/lrn.cc — cross-channel normalisation
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = jnp.zeros_like(sq)
    for i in range(nsize):
        window = window + padded[:, i : i + data.shape[1]]
    return data / jnp.power(knorm + (alpha / nsize) * window, beta)


# ---------------------------------------------------------------------------
# Dropout (ref: src/operator/dropout.cc; rng op, identity at inference)
# ---------------------------------------------------------------------------
@register("Dropout", aliases=("dropout",), rng=True, train_aware=True)
def _dropout(key, data, p=0.5, mode="training", axes=(), _training=True, **_):
    if not _training and mode != "always":
        return data
    if p <= 0.0:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype) / keep
    return data * jax.lax.stop_gradient(mask)


# ---------------------------------------------------------------------------
# misc spatial ops
# ---------------------------------------------------------------------------
@register("UpSampling")
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=512, **_):
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        if num_args > 1 and multi_input_mode == "concat":
            outs = [out]
            for a in args[1:]:
                s = out.shape[2] // a.shape[2]
                outs.append(jnp.repeat(jnp.repeat(a, s, axis=2), s, axis=3))
            return jnp.concatenate(outs, axis=1)
        return out
    if sample_type == "bilinear":
        weight = args[1] if len(args) > 1 else None
        n, c, h, w = data.shape
        return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")
    raise ValueError("unknown sample_type %r" % sample_type)


@register("Pad", aliases=("pad",))
def _pad(data, mode="constant", pad_width=(), constant_value=0.0, **_):
    pw = tuple(
        (pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)
    )
    if mode == "constant":
        return jnp.pad(data, pw, constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(data, pw, mode="edge")
    if mode == "reflect":
        return jnp.pad(data, pw, mode="reflect")
    raise ValueError("unknown pad mode %r" % mode)


@register("BilinearSampler")
def _bilinear_sampler(data, grid, **_):
    # ref: src/operator/bilinear_sampler.cc — grid in [-1, 1]
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0

    x0 = jnp.floor(gx); x1 = x0 + 1
    y0 = jnp.floor(gy); y1 = y0 + 1
    wx1 = gx - x0; wx0 = 1.0 - wx1
    wy1 = gy - y0; wy0 = 1.0 - wy1

    def gather(yy, xx):
        yi = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xi = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        batch_idx = jnp.arange(n).reshape(n, 1, 1)
        vals = data[batch_idx, :, yi, xi]  # (n, gh, gw, c)
        inb = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1)).astype(data.dtype)
        return vals * inb[..., None]

    out = (
        gather(y0, x0) * (wy0 * wx0)[..., None]
        + gather(y0, x1) * (wy0 * wx1)[..., None]
        + gather(y1, x0) * (wy1 * wx0)[..., None]
        + gather(y1, x1) * (wy1 * wx1)[..., None]
    )
    return jnp.moveaxis(out, -1, 1)


@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0), **_):
    h, w = int(target_shape[0]), int(target_shape[1])
    if transform_type == "affine":
        n = data.shape[0]
        theta = data.reshape(n, 2, 3)
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=0).reshape(3, -1)  # (3, h*w)
        out = jnp.einsum("nij,jk->nik", theta, base)  # (n, 2, h*w)
        return out.reshape(n, 2, h, w)
    if transform_type == "warp":
        flow = data  # (n, 2, h, w) pixel offsets
        n = flow.shape[0]
        ys = jnp.arange(flow.shape[2], dtype=flow.dtype)
        xs = jnp.arange(flow.shape[3], dtype=flow.dtype)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        nx = (gx + flow[:, 0]) * 2.0 / max(flow.shape[3] - 1, 1) - 1.0
        ny = (gy + flow[:, 1]) * 2.0 / max(flow.shape[2] - 1, 1) - 1.0
        return jnp.stack([nx, ny], axis=1)
    raise ValueError("unknown transform_type %r" % transform_type)


@register("SpatialTransformer")
def _spatial_transformer(data, loc, target_shape=(0, 0),
                         transform_type="affine", sampler_type="bilinear", **_):
    from .registry import get as _get

    grid = _get("GridGenerator").fn(loc, transform_type="affine",
                                    target_shape=target_shape)
    return _get("BilinearSampler").fn(data, grid)


@register("ROIPooling")
def _roi_pooling(data, rois, pooled_size=(0, 0), spatial_scale=1.0, **_):
    # ref: src/operator/roi_pooling.cc — static-shape max pooling per ROI
    ph, pw = int(pooled_size[0]), int(pooled_size[1])
    n_rois = rois.shape[0]
    _, c, h, w = data.shape

    def one_roi(roi):
        batch = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        rh = jnp.maximum(y2 - y1 + 1.0, 1.0)
        rw = jnp.maximum(x2 - x1 + 1.0, 1.0)
        bin_h = rh / ph
        bin_w = rw / pw
        img = data[batch]

        ys = jnp.arange(h, dtype=data.dtype)
        xs = jnp.arange(w, dtype=data.dtype)

        def pool_bin(iy, ix):
            ys0 = y1 + iy * bin_h
            ys1 = y1 + (iy + 1) * bin_h
            xs0 = x1 + ix * bin_w
            xs1 = x1 + (ix + 1) * bin_w
            my = (ys >= jnp.floor(ys0)) & (ys < jnp.ceil(ys1))
            mx = (xs >= jnp.floor(xs0)) & (xs < jnp.ceil(xs1))
            mask = my[:, None] & mx[None, :]
            masked = jnp.where(mask[None], img, -jnp.inf)
            val = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(val), val, 0.0)

        iy = jnp.arange(ph)
        ix = jnp.arange(pw)
        grid = jax.vmap(lambda y: jax.vmap(lambda x: pool_bin(y, x))(ix))(iy)
        return jnp.moveaxis(grid, -1, 0)  # (c, ph, pw)

    return jax.vmap(one_roi)(rois)


@register("Crop", nondiff=False)
def _crop(*args, offset=(0, 0), h_w=(0, 0), num_args=1, center_crop=False, **_):
    data = args[0]
    # the reference's key_var_num_args creator fills num_args from the
    # argument count; callers composing Crop(*[data, shape_ref]) rely
    # on it (example/fcn-xs/symbol_fcnxs.py:158) — infer from the
    # actual inputs so the param is optional here too
    if len(args) > 1 or num_args > 1:
        th, tw = args[1].shape[2], args[1].shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    if center_crop:
        oy = (data.shape[2] - th) // 2
        ox = (data.shape[3] - tw) // 2
    else:
        oy, ox = int(offset[0]), int(offset[1])
    return data[:, :, oy : oy + th, ox : ox + tw]
