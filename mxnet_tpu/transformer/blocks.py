"""The parts a block can be spelled from beside the dense ones in
``model.py``: latent attention, YaRN rotary frequencies, the gated
feed-forward, the expert layer, and the hyper-connected residual.

Every function is pure, takes this layer's parameters as a dict keyed
by the leaf's short name, and names its device work with the scopes
``model.py`` documents.  Router scores, the stream maps and Sinkhorn
run in float32 whatever the compute dtype.

**The expert layer is told which experts it holds** (``held_experts``,
global ids).  It scores every token against ALL ``n_experts`` in
float32, picks ``experts_per_token`` of them by ``score + bias``,
normalises the picked scores over all of the picked ones, and computes
the part of the result that its own experts give: the assignments to
held experts are sorted by expert into one ``(k*N, D)`` buffer (its
worst case: every assignment lands here), the three products of the
gated feed-forward run as ``jax.lax.ragged_dot`` over the held experts
(on a TPU XLA lowers that to a grouped-matmul kernel that visits only
the row tiles in use, so the cost follows the rows served and not the
buffer), and the rows go back to their tokens weighted.  Nothing is
dropped and no shape depends on the routing.  What the absent experts
would add is left out; on one chip there is no exchange and nothing
stands in for one.  ``ragged_dot`` was chosen over a padded batched
einsum (sized from the worst case it would compute ``k*N`` rows for
each held expert: sixteen times the expected work at 8 of 64 experts,
4 a token) and over a hand-written Pallas kernel (XLA's own has the
same tiling and needs no second path for the CPU).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

__all__ = ["yarn_inv_freq", "yarn_softmax_mscale", "latent_qkv",
           "gated_ffn", "expert_ffn", "sinkhorn", "stream_maps",
           "hyper_residual"]


# ---------------------------------------------------------------------
# YaRN rotary scaling (the published reference's arithmetic)
# ---------------------------------------------------------------------
def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         positions: int) -> float:
    return dim * math.log(positions / (rotations * 2.0 * math.pi)) \
        / (2.0 * math.log(base))


def yarn_inv_freq(dim: int, base: float, yarn):
    """The ``dim // 2`` rotary frequencies under YaRN: the pairs that
    turn often within the original positions keep ``base**(-2i/dim)``,
    those that turn less than once are divided by ``factor``, with a
    linear ramp between ``beta_fast`` and ``beta_slow`` rotations."""
    import jax.numpy as jnp

    half = dim // 2
    extra = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    low = max(math.floor(_yarn_correction_dim(
        yarn.beta_fast, dim, base, yarn.original_positions)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        yarn.beta_slow, dim, base, yarn.original_positions)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (extra / yarn.factor) * (1.0 - mask) + extra * mask


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_mscale(yarn) -> float:
    """``m`` of the softmax scale ``d**-0.5 * m * m``; the cos/sin
    factor ``mscale(mscale) / mscale(mscale_all_dim)`` must be 1 (the
    only case the rotary here spells)."""
    if not yarn.mscale_all_dim:
        return 1.0
    if _yarn_mscale(yarn.factor, yarn.mscale) != _yarn_mscale(
            yarn.factor, yarn.mscale_all_dim):
        raise NotImplementedError(
            "rotary scaling with mscale != mscale_all_dim scales cos/sin; "
            "not spelled here")
    return _yarn_mscale(yarn.factor, yarn.mscale_all_dim)


# ---------------------------------------------------------------------
# latent attention: q through a rank-q_lora_rank bottleneck, k and v
# out of one rank-kv_lora_rank latent, one rotary key shared by heads
# ---------------------------------------------------------------------
def latent_qkv(a, lp: Dict, positions, cfg, rmsnorm, rope):
    """``a`` (B, T, D), already normed -> q, k (B, T, H, nope + rope)
    and v (B, T, H, v_head_dim).  Scope ``attn_proj`` is the caller's."""
    import jax.numpy as jnp

    b, t, _ = a.shape
    h, nope, rp, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    dt = a.dtype
    cq = rmsnorm(a @ lp["wq_a"].astype(dt), lp["q_norm"], cfg.eps)
    q = (cq @ lp["wq_b"].astype(dt)).reshape(b, t, h, nope + rp)
    kva = a @ lp["wkv_a"].astype(dt)
    ckv = rmsnorm(kva[..., :r], lp["kv_norm"], cfg.eps)
    kv = (ckv @ lp["wkv_b"].astype(dt)).reshape(b, t, h, nope + dv)
    freqs = None if cfg.rope_yarn is None else \
        yarn_inv_freq(rp, cfg.rope_base, cfg.rope_yarn)
    q_r = rope(q[..., nope:], positions, cfg.rope_base, freqs)
    k_r = rope(kva[..., r:].reshape(b, t, 1, rp), positions,
               cfg.rope_base, freqs)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rp))], axis=-1)
    return q, k, kv[..., nope:]


def latent_sm_scale(cfg) -> float:
    m = 1.0 if cfg.rope_yarn is None else yarn_softmax_mscale(cfg.rope_yarn)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# ---------------------------------------------------------------------
# feed-forwards
# ---------------------------------------------------------------------
def gated_ffn(m, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)``."""
    import jax

    dt = m.dtype
    return (jax.nn.silu(m @ w_gate.astype(dt)) * (m @ w_up.astype(dt))) \
        @ w_down.astype(dt)


def _permuted_rows():
    """``rows(x, take, back, k)``: ``x[take]`` where ``take`` lists each
    of x's rows ``k`` times over and ``back`` is where each (row, copy)
    went.  Its gradient is a gather too (``dy[back]`` summed over the
    copies), never a scatter-add."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def rows(x, take, back, k):
        return x[take]

    def fwd(x, take, back, k):
        return x[take], (back, x.shape[0])

    def bwd(k, res, dy):
        back, n = res
        dx = dy[back]
        if k > 1:
            dx = dx.reshape((n, k) + dy.shape[1:]).sum(axis=1)
        return dx, None, None

    rows.defvjp(fwd, bwd)
    return rows


def expert_ffn(m, lp: Dict, cfg) -> Tuple[object, Dict]:
    """The expert layer over ``m`` (B, T, D), already normed: the held
    routed experts' part plus the shared expert, and what it counted:
    ``counts`` (n_experts,) assignments to each expert of all,
    ``choice`` (N, k) the experts picked for each token, ``dropped``
    assignments to a held expert that were not computed (0: the buffer
    holds the worst case).  Scope ``mlp`` is the caller's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    b, t, d = m.shape
    n, k, e = b * t, cfg.experts_per_token, cfg.n_experts
    held = tuple(cfg.held_experts)
    dt = m.dtype
    x = m.reshape(n, d)
    rows = _permuted_rows()
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        bias = lax.stop_gradient(lp["router_bias"].astype(jnp.float32))
        _, choice = lax.top_k(lax.stop_gradient(scores) + bias, k)
        picked = jnp.take_along_axis(scores, choice, axis=1)
        weight = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) \
            * cfg.routed_scaling
        flat = choice.reshape(-1)
        # counted by comparison, not by scatter: one fused pass
        counts = jnp.sum(flat[:, None] == jnp.arange(e, dtype=flat.dtype),
                         axis=0, dtype=jnp.int32)
        sizes = counts[np.asarray(held)]
        # slot: position among the held experts, len(held) where absent
        slot_of = np.full((e,), len(held), np.int32)
        slot_of[list(held)] = np.arange(len(held))
        slot = jnp.asarray(slot_of)[flat]
        order = jnp.argsort(slot, stable=True)       # sorted <- flat
        back = jnp.argsort(order)                    # flat <- sorted
        here = (slot[order] < len(held))[:, None]
        served = jnp.minimum(jnp.sum(sizes), n * k)
        xs = jnp.where(here, rows(x, order // k, back, k), 0)
    with jax.named_scope("moe_expert"):
        dot = functools.partial(lax.ragged_dot, group_sizes=sizes,
                                preferred_element_type=dt)
        mid = jax.nn.silu(dot(xs, lp["we_gate"].astype(dt))) \
            * dot(xs, lp["we_up"].astype(dt))
        out = dot(mid, lp["we_down"].astype(dt))
    with jax.named_scope("moe_route"):
        # rows of no held expert are never computed: whatever the
        # kernel left there is replaced, value and gradient alike
        out = jnp.where(here, out, 0)
        per = rows(out, back, order, 1).reshape(n, k, d)
        y = jnp.sum(per.astype(jnp.float32) * weight[:, :, None], axis=1)
    with jax.named_scope("moe_shared"):
        y = y.astype(dt) + gated_ffn(x, lp["ws_gate"], lp["ws_up"],
                                     lp["ws_down"])
    return y.reshape(b, t, d), {
        "counts": counts, "choice": choice,
        "dropped": jnp.sum(sizes) - served}


# ---------------------------------------------------------------------
# hyper-connected residual streams
# ---------------------------------------------------------------------
def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: rows divided by their sum, then columns by
    theirs (each sum + ``eps``), over the last two axes."""
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def stream_maps(xs, lp: Dict, which: str, cfg):
    """The three maps of one sublayer from the streams ``xs``
    (B, T, n, D): ``H_pre`` (B, T, n), ``H_post`` (B, T, n) and the
    Sinkhorn-projected ``H_res`` (B, T, n, n), all float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t, n, d = xs.shape
    xf = xs.reshape(b, t, n * d).astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.eps)
    u = jnp.einsum("btk,kj->btj", xf,
                   lp["hc_%s_w" % which].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * inv
    alpha = lp["hc_%s_alpha" % which].astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * u[..., :n]
                         + lp["hc_%s_b_pre" % which].astype(jnp.float32))
    post = 2.0 * jax.nn.sigmoid(
        alpha[1] * u[..., n:2 * n]
        + lp["hc_%s_b_post" % which].astype(jnp.float32))
    res = jnp.clip(alpha[2] * u[..., 2 * n:].reshape(b, t, n, n)
                   + lp["hc_%s_b_res" % which].astype(jnp.float32),
                   -cfg.hc_clamp, cfg.hc_clamp)
    return pre, post, sinkhorn(jnp.exp(res), cfg.hc_sinkhorn_iters,
                               cfg.hc_eps)


def hyper_residual(xs, lp: Dict, which: str, cfg, sublayer):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] * F(sum_i H_pre[i]
    X[i])`` over the streams ``xs`` (B, T, n, D); ``sublayer`` is ``F``
    with its own norm, and may return ``(y, aux)``."""
    import jax
    import jax.numpy as jnp

    dt = xs.dtype
    with jax.named_scope("mhc"):
        pre, post, res = stream_maps(xs, lp, which, cfg)
        xf = xs.astype(jnp.float32)
        mixed = jnp.einsum("btn,btnd->btd", pre, xf).astype(dt)
    y, aux = sublayer(mixed)
    with jax.named_scope("mhc"):
        out = jnp.einsum("btij,btjd->btid", res, xf) \
            + post[..., None] * y.astype(jnp.float32)[:, :, None, :]
    return out.astype(dt), aux
