"""Plain reference: the dense decoder-only block, float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, no kernels, no
cache, no batching tricks, nothing imported from the program.  The
block is this repository's own (it is NOT GPT-NeoX, see the
configuration's ``assumed``): pre-RMSNorm with a gain and no bias, one
fused QKV projection, rotary embedding over the whole head (first half
against second half), causal multi-head attention, a tanh-GELU MLP,
residual after each half, a final RMSNorm, and the output head tied to
the embedding.  No biases anywhere.  The loss is the mean next-token
cross entropy; the optimizer is SGD with momentum
(``m = mu*m - lr*g; p = p + m``).

Gradients are accumulated row by row (one sequence at a time, each
block recomputed in the backward pass), so the float32 model, its
gradients and momenta fit on the chip beside one row's activations.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST

Leaf = Tuple[str, Tuple[int, ...], Tuple[str, float]]


def leaves(cfg: Dict) -> List[Leaf]:
    """``(name, shape, init)`` of every array, in layer order: normal
    matrices of the configuration's ``init_std`` (0.02), the two that
    write into the residual stream scaled down by sqrt(2 L), unit
    gains."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    std = cfg["init_std"]
    resid = std * (2.0 * max(n, 1)) ** -0.5
    out: List[Leaf] = [("embed", (v, d), ("normal", std))]
    for i in range(n):
        p = "blk%d." % i
        out += [(p + "attn_norm", (d,), ("const", 1.0)),
                (p + "wqkv", (d, 3 * d), ("normal", std)),
                (p + "wo", (d, d), ("normal", resid)),
                (p + "mlp_norm", (d,), ("const", 1.0)),
                (p + "w1", (d, f), ("normal", std)),
                (p + "w2", (f, d), ("normal", resid))]
    out.append(("final_norm", (d,), ("const", 1.0)))
    return out


def _mm(x, w, q):
    if q is not None:
        x, w = q(x), q(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def _rope(x, base):
    """(T, H, Dh): rotate the first half of each head against the
    second, position t by t * base**(-i / half)."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(p: Dict, pre: str, h, cfg: Dict, q):
    t, d = h.shape
    heads = cfg["num_attention_heads"]
    dh = d // heads
    a = _rmsnorm(h, p[pre + "attn_norm"], cfg["rms_norm_eps"])
    qkv = _mm(a, p[pre + "wqkv"], q)
    qh, kh, vh = (x.reshape(t, heads, dh) for x in jnp.split(qkv, 3, -1))
    qh, kh = _rope(qh, cfg["rope_base"]), _rope(kh, cfg["rope_base"])
    scores = jnp.einsum("thd,shd->hts", qh, kh, precision=HIGHEST) \
        * dh ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    o = jnp.einsum("hts,shd->thd", probs, vh, precision=HIGHEST)
    h = h + _mm(o.reshape(t, d), p[pre + "wo"], q)
    m = _rmsnorm(h, p[pre + "mlp_norm"], cfg["rms_norm_eps"])
    m = jax.nn.gelu(_mm(m, p[pre + "w1"], q), approximate=True)
    return h + _mm(m, p[pre + "w2"], q)


def forward(p: Dict, tokens, cfg: Dict, q: Optional[Callable] = None):
    """Logits (T, vocab) of ONE sequence of token ids (T,).  ``q`` is
    applied to both operands of every weight matmul: the low-precision
    control passes a quantiser."""
    h = p["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        h = jax.checkpoint(
            lambda pp, hh, pre="blk%d." % i: _block(pp, pre, hh, cfg, q)
        )(p, h)
    h = _rmsnorm(h, p["final_norm"], cfg["rms_norm_eps"])
    return _mm(h, p["embed"].T, q)


def row_loss(p: Dict, tokens, labels, cfg: Dict, q=None):
    logp = jax.nn.log_softmax(forward(p, tokens, cfg, q), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def make_step(cfg: Dict, lr: float, momentum: float, q=None):
    """One jitted SGD-momentum step over a batch (B, T): the mean of
    the rows' losses, its gradient summed row by row."""

    def step(p, m, tokens, labels):
        def one(carry, row):
            g_sum, loss_sum = carry
            loss, g = jax.value_and_grad(row_loss)(p, row[0], row[1],
                                                   cfg, q)
            return (jax.tree_util.tree_map(jnp.add, g_sum, g),
                    loss_sum + loss), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (g, loss), _ = lax.scan(one, (zero, jnp.zeros((), jnp.float32)),
                                 (tokens, labels))
        rows = tokens.shape[0]
        m = {k: momentum * m[k] - lr * g[k] / rows for k in p}
        p = {k: p[k] + m[k] for k in p}
        return p, m, loss / rows

    return jax.jit(step, donate_argnums=(0, 1))
