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
from typing import Callable, Dict, NamedTuple, Optional, Tuple

__all__ = ["yarn_inv_freq", "yarn_softmax_mscale", "latent_qkv",
           "latent_qkv_row", "latent_absorbed_query", "absorbed_attention",
           "absorbed_values", "gated_ffn", "expert_ffn", "sinkhorn",
           "stream_maps", "hyper_residual", "site_tally"]


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
# latent attention: q through a rank-q_lora_rank bottleneck (or, with
# q_lora_rank 0, one matrix), k and v out of one rank-kv_lora_rank
# latent, one rotary key shared by heads.  Training and prefill expand
# the latent into every head's keys and values (``latent_qkv``); decode
# caches the latent and attends in its space (``absorbed_attention``)
# ---------------------------------------------------------------------
def latent_projections(a, lp: Dict, positions, cfg, rmsnorm, rope):
    """``a`` (B, T, D), already normed -> the queries (B, T, H, nope +
    rope) with their rotary part turned, the normed latent ``c``
    (B, T, kv_lora_rank) and the one rotary key of all heads ``k_r``
    (B, T, 1, rope), turned.  ``q_lora_rank`` 0 is one ``wq`` and no
    norm on the queries' way; any other is the bottleneck ``wq_a``,
    ``q_norm``, ``wq_b``."""
    b, t, _ = a.shape
    h, nope, rp = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    dt = a.dtype
    if cfg.q_lora_rank:
        cq = rmsnorm(a @ lp["wq_a"].astype(dt), lp["q_norm"], cfg.eps)
        q = cq @ lp["wq_b"].astype(dt)
    else:
        q = a @ lp["wq"].astype(dt)
    q = q.reshape(b, t, h, nope + rp)
    kva = a @ lp["wkv_a"].astype(dt)
    ckv = rmsnorm(kva[..., :r], lp["kv_norm"], cfg.eps)
    return q, ckv, kva[..., r:].reshape(b, t, 1, rp)


def _turned(q, k_r, positions, cfg, rope):
    """The rotary parts of ``q`` and the shared key at ``positions``,
    under the configuration's frequencies (YaRN where it has them)."""
    import jax.numpy as jnp

    nope = cfg.qk_nope_head_dim
    freqs = None if cfg.rope_yarn is None else \
        yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_base, cfg.rope_yarn)
    q_r = rope(q[..., nope:], positions, cfg.rope_base, freqs)
    k_r = rope(k_r, positions, cfg.rope_base, freqs)
    return jnp.concatenate([q[..., :nope], q_r], axis=-1), k_r


def latent_qkv(a, lp: Dict, positions, cfg, rmsnorm, rope):
    """``a`` (B, T, D), already normed -> q, k (B, T, H, nope + rope)
    and v (B, T, H, v_head_dim).  Scope ``attn_proj`` is the caller's."""
    q, k, v, _ = latent_qkv_row(a, lp, positions, cfg, rmsnorm, rope)
    return q, k, v


def latent_qkv_row(a, lp: Dict, positions, cfg, rmsnorm, rope):
    """:func:`latent_qkv` and what a token CACHES: ``row`` (B, T,
    kv_lora_rank + rope) = ``[RMSNorm(c) | rope(k_r)]``, from which
    ``k`` and ``v`` of every head can be made again (and need not be:
    :func:`absorbed_attention`)."""
    import jax.numpy as jnp

    b, t, _ = a.shape
    h, nope, rp, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim, cfg.v_head_dim)
    dt = a.dtype
    q, ckv, k_r = latent_projections(a, lp, positions, cfg, rmsnorm, rope)
    kv = (ckv @ lp["wkv_b"].astype(dt)).reshape(b, t, h, nope + dv)
    q, k_r = _turned(q, k_r, positions, cfg, rope)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rp))], axis=-1)
    row = jnp.concatenate([ckv, k_r[:, :, 0]], axis=-1)
    return q, k, kv[..., nope:], row


def latent_absorbed_query(a, lp: Dict, positions, cfg, rmsnorm, rope):
    """One new token a row, ``a`` (B, 1, D) already normed -> its
    queries in the latent's space, ``[q_nope W_uk^T | rope(q_r)]``
    (B, H, kv_lora_rank + rope), and its cache ``row`` (B, 1,
    kv_lora_rank + rope).  ``W_uk`` is the keys' half of ``wkv_b`` by
    head: ``q_nope . (c W_uk) = (q_nope W_uk^T) . c``, so a cached
    token's keys are never made."""
    import jax.numpy as jnp

    h, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q, ckv, k_r = latent_projections(a, lp, positions, cfg, rmsnorm, rope)
    q, k_r = _turned(q, k_r, positions, cfg, rope)
    w_uk = lp["wkv_b"].astype(a.dtype).reshape(
        cfg.kv_lora_rank, h, nope + dv)[..., :nope]
    q_c = jnp.einsum("bhn,rhn->bhr", q[:, 0, :, :nope], w_uk)
    return (jnp.concatenate([q_c, q[:, 0, :, nope:]], axis=-1),
            jnp.concatenate([ckv, k_r[:, :, 0]], axis=-1))


def absorbed_attention(q_abs, rows, mask, cfg):
    """Attention of one new token a row over its cached latent ``rows``
    (B, W, block_tokens, kv_lora_rank + rope), the blocks of its table
    as they lie in the pool, under ``mask`` (B, W * block_tokens):
    scores ``q_abs . row`` times :func:`latent_sm_scale`, float32 from
    the product through the softmax, and the probabilities' sum of the
    latents ``u`` (B, H, kv_lora_rank), float32.  The operands stay in their
    dtype and their layout (no float32 copy of the history, no view of
    it as one run of tokens)."""
    import jax
    import jax.numpy as jnp

    b, w, bt, _ = rows.shape
    s = jnp.einsum("bhc,bwtc->bhwt", q_abs, rows,
                   preferred_element_type=jnp.float32) * latent_sm_scale(cfg)
    s = jnp.where(mask.reshape(b, 1, w, bt), s, -jnp.inf)
    p = jax.nn.softmax(s.reshape(b, -1, w * bt), axis=-1)
    # over the whole row and the rotary key's columns dropped from the
    # sum: a slice of the history first would be a copy of it
    u = jnp.einsum("bhwt,bwtc->bhc", p.reshape(s.shape).astype(rows.dtype),
                   rows, preferred_element_type=jnp.float32)
    return u[..., :cfg.kv_lora_rank]


def absorbed_values(u, lp: Dict, cfg, dtype):
    """``u`` (B, H, kv_lora_rank), float32 -> the heads' outputs (B, 1,
    H, v_head_dim) in ``dtype``: ``o_h = u_h W_uv_h``, the values' half
    of ``wkv_b`` applied after the sum instead of to every cached
    token.  In float32 (``u`` is a row a head a sequence, the product
    small): the expanded form sums float32 products of rounded values,
    and rounding ``u`` first would round once more than it does."""
    import jax.numpy as jnp
    from jax import lax

    h, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    w_uv = lp["wkv_b"].astype(u.dtype).reshape(
        cfg.kv_lora_rank, h, nope + dv)[..., nope:]
    return jnp.einsum("bhr,rhd->bhd", u, w_uv,
                      precision=lax.Precision.HIGHEST)[:, None].astype(dtype)


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


# call sites of hyper_residual traced so far, by what their operands allow
_sites = {"kernel": 0, "plain": 0}
_LEAVES = ("w", "alpha", "b_pre", "b_post", "b_res")


class _Mix(NamedTuple):
    """What a pass is specialised on beside its operands' shapes."""
    n: int
    eps: float
    clamp: float
    iters: int
    sink_eps: float
    sinkhorn: Callable      # ``blocks.sinkhorn`` as the trace found it
    tile: Optional[int]     # tokens a kernel tile; None: the plain passes
    interpret: bool = False


def site_tally(since=None):
    """How many calls of ``hyper_residual`` have been traced so far (or
    since an earlier tally), by what their operands allow: ``kernel``
    sites run the fused passes' kernels wherever the program is lowered
    for a TPU (and plain ``jax.numpy`` where it is lowered for anything
    else), ``plain`` sites run the plain formulation everywhere."""
    return {how: n - (since[how] if since else 0)
            for how, n in _sites.items()}


def _pre_map(a, u, b):
    """``H_pre`` from its share of the normed projection; the one line of
    the maps that the fused forward evaluates inside its pass, so that
    the pre-mix shares the projection's read of the streams."""
    import jax

    return jax.nn.sigmoid(a * u + b)


def _maps(u, ms, alpha, b_pre, b_post, b_res, st: _Mix):
    """The three maps from the raw projection ``u`` (..., n*n + 2n) and
    the mean square ``ms`` (..., 1) of a token's streams, float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = st.n
    u = u * lax.rsqrt(ms + st.eps)
    pre = _pre_map(alpha[0], u[..., :n], b_pre)
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[..., n:2 * n] + b_post)
    res = jnp.clip(alpha[2] * u[..., 2 * n:].reshape(u.shape[:-1] + (n, n))
                   + b_res, -st.clamp, st.clamp)
    return pre, post, st.sinkhorn(jnp.exp(res), st.iters, st.sink_eps)


def _statics(cfg, n, tile=None, interpret=False) -> _Mix:
    return _Mix(n, cfg.eps, cfg.hc_clamp, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                sinkhorn, tile, interpret)


def _leaves(lp: Dict, which: str):
    import jax.numpy as jnp

    return [lp["hc_%s_%s" % (which, k)].astype(jnp.float32)
            for k in _LEAVES]


def _projected(x, w):
    """Streams ``x`` (N, n*D) in float32, their raw projection and the
    mean square of a token's values: the plain formulation's."""
    import jax.numpy as jnp
    from jax import lax

    xf = x.astype(jnp.float32)
    return (xf, jnp.dot(xf, w, precision=lax.Precision.HIGHEST),
            jnp.mean(xf * xf, axis=-1, keepdims=True))


def stream_maps(xs, lp: Dict, which: str, cfg):
    """The three maps of one sublayer from the streams ``xs``
    (B, T, n, D): ``H_pre`` (B, T, n), ``H_post`` (B, T, n) and the
    Sinkhorn-projected ``H_res`` (B, T, n, n), all float32."""
    b, t, n, d = xs.shape
    w, *leaves = _leaves(lp, which)
    _, u, ms = _projected(xs.reshape(b * t, n * d), w)
    pre, post, res = _maps(u, ms, *leaves, _statics(cfg, n))
    return (pre.reshape(b, t, n), post.reshape(b, t, n),
            res.reshape(b, t, n, n))


# -- the plain formulation: every backend, every shape; the oracle ------
def _plain_pre(x, w, alpha, b_pre, b_post, b_res, st: _Mix):
    """Streams ``x`` (N, n*D) -> ``mixed`` (N, D), the streams again,
    ``H_post`` (N, n) and ``H_res`` (N, n, n)."""
    import jax.numpy as jnp

    xf, u, ms = _projected(x, w)
    pre, post, res = _maps(u, ms, alpha, b_pre, b_post, b_res, st)
    mixed = jnp.einsum("tn,tnd->td", pre, xf.reshape(x.shape[0], st.n, -1))
    return mixed.astype(x.dtype), x, post, res


def _plain_post(x, y, res, post, st: _Mix):
    """``out[i] = sum_j H_res[i, j] x[j] + H_post[i] y`` (N, n*D)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32).reshape(x.shape[0], st.n, -1)
    out = jnp.einsum("tij,tjd->tid", res, xf) \
        + post[..., None] * y.astype(jnp.float32)[:, None, :]
    return out.astype(x.dtype).reshape(x.shape)


# -- the fused passes: the same sums where the streams lie --------------
def _fused_tile(xs):
    """Tokens a tile of the fused passes over these streams, or None
    where they do not take them; every condition is one a compile for
    the chip, or a trace, refused."""
    import jax
    import jax.numpy as jnp

    from . import mhc_kernels

    b, t, n, d = xs.shape
    takes = (
        d % mhc_kernels.LANES == 0
        # a map entry a lane, and three bf16 parts of each side by side
        and n >= 2 and 3 * (n * n + 2 * n) <= mhc_kernels.LANES
        and xs.dtype in (jnp.bfloat16, jnp.float32)
        # Mosaic does not lower the passes' loop indices at 64 bits
        and not jax.config.jax_enable_x64
        and not getattr(jax.typeof(xs), "vma", None))
    if not takes:
        return None
    return mhc_kernels.token_tile(b * t, n * d, xs.dtype.itemsize)


def _lanes(a):
    """(N, ...) -> (N, 128) float32, entry ``k`` of a token in lane k."""
    import jax.numpy as jnp

    from .mhc_kernels import LANES

    a = a.reshape(a.shape[0], -1)
    return jnp.pad(a, ((0, 0), (0, LANES - a.shape[1])))


def _parts(a, k: int):
    """``k`` bfloat16 arrays that sum to float32 ``a`` (to 8k bits)."""
    import jax.numpy as jnp

    out = []
    for _ in range(k):
        out.append(a.astype(jnp.bfloat16))
        a = a - out[-1].astype(jnp.float32)
    return out


def _pre_forward(x, w, alpha, b_pre, b_post, b_res, *, st: _Mix):
    import jax.numpy as jnp

    from . import mhc_kernels as mk

    k = w.shape[1]
    if x.dtype == jnp.bfloat16:
        # the streams are exact in bf16; W in three bf16 parts, one MXU
        # pass over all of them, keeps float32-grade products
        w_cols = jnp.concatenate(_parts(w, 3), axis=1)
        fold = jnp.tile(jnp.eye(k, mk.LANES, dtype=jnp.float32), (3, 1))
    else:
        w_cols, fold = w, jnp.eye(k, mk.LANES, dtype=jnp.float32)
    pad = mk.LANES - w_cols.shape[1]
    u, ms, mixed = mk.pre_fwd(
        x, jnp.pad(w_cols, ((0, 0), (0, pad))),
        jnp.pad(fold, ((0, pad), (0, 0))),
        jnp.full((1, mk.LANES), alpha[0]), _lanes(b_pre[None]),
        n=st.n, eps=st.eps, pre_map=_pre_map, tile=st.tile,
        interpret=st.interpret)
    u, ms = u[:, :k], ms[:, :1]
    _, post, res = _maps(u, ms, alpha, b_pre, b_post, b_res, st)
    return mixed, post, res, u, ms


def _pre_backward(x, w, alpha, b_pre, b_post, b_res, u, ms, g_mixed, g_out,
                  g_post, g_res, *, st: _Mix):
    """``g_out`` is the cotangent of the NEW streams, as ``_fused_post``
    hands it back: ``H_res^T`` is applied here, with the other two parts
    of the streams' gradient, so that they are summed in float32."""
    import jax
    import jax.numpy as jnp

    from . import mhc_kernels as mk

    n, k = st.n, w.shape[1]
    kw = dict(n=n, tile=st.tile, interpret=st.interpret)
    d_pre = mk.pre_bwd_maps(x, g_mixed, **kw)[:, :n]
    (pre, _, res), vjp = jax.vjp(
        lambda *a: _maps(*a, st), u, ms, alpha, b_pre, b_post, b_res)
    du, dms, dalpha, db_pre, db_post, db_res = vjp((d_pre, g_post, g_res))
    if x.dtype == jnp.bfloat16:
        # dU W^T to 16 bits (hi hi + hi lo + lo hi; the sum is rounded to
        # 8), dU^T X float32-grade (X exact, dU in three parts)
        (du_hi, du_lo), (w_hi, w_lo) = _parts(du, 2), _parts(w, 2)
        du_cols = jnp.concatenate([du_hi, du_hi, du_lo], axis=1)
        wt_cols = jnp.concatenate([w_hi, w_lo, w_hi], axis=1).T
        du_rows = jnp.concatenate(_parts(du, 3), axis=1).T
    else:
        du_cols, wt_cols, du_rows = du, w.T, du.T
    pad = mk.LANES - du_cols.shape[1]
    dx, dw = mk.pre_bwd_streams(
        x, g_out, g_mixed, jnp.pad(du_cols, ((0, 0), (0, pad))),
        jnp.pad(du_rows, ((0, pad), (0, 0))),
        jnp.pad(wt_cols, ((0, pad), (0, 0))),
        _lanes(dms * (2.0 / x.shape[1])), _lanes(pre), _lanes(res), **kw)
    dw = sum(dw[g * k:(g + 1) * k] for g in range(du_rows.shape[0] // k)).T
    return dx, dw, dalpha, db_pre, db_post, db_res


def _post_forward(x, y, res, post, *, st: _Mix):
    from . import mhc_kernels as mk

    return mk.post_fwd(x, y, _lanes(res), _lanes(post), n=st.n,
                       tile=st.tile, interpret=st.interpret)


def _post_backward(x, y, post, g_out, *, st: _Mix):
    from . import mhc_kernels as mk

    n = st.n
    dy, d_res, d_post = mk.post_bwd(x, y, g_out, _lanes(post), n=n,
                                    tile=st.tile, interpret=st.interpret)
    return dy, d_res[:, :n * n].reshape(-1, n, n), d_post[:, :n]


@functools.lru_cache(maxsize=None)
def _body_jaxpr(body, operands, st: _Mix):
    import jax

    return jax.make_jaxpr(functools.partial(body, st=st), return_shape=True)(
        *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in operands))


def _body(body):
    """``body(*arrays, st=)`` under ``jax.jit``, its Python run ONCE for
    each operand signature (shapes, dtypes, ``st``) and its jaxpr
    replayed ever after.  jit alone traces a body anew whenever an
    operand's type differs in its mesh or the tracing context does (the
    first sublayer's streams against a pass's own result, the forward
    against its linearisation: up to four times a body), and tracing a
    kernel or Sinkhorn's backward costs a start-up hundreds of
    milliseconds a time; a replay costs none."""
    import jax
    from jax.extend.core import jaxpr_as_fun

    def replay(*arrays, st):
        closed, out = _body_jaxpr(
            body, tuple((a.shape, a.dtype) for a in arrays), st)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(out), jaxpr_as_fun(closed)(*arrays))

    replay.__name__ = body.__name__
    return jax.jit(replay, static_argnames=("st",))


@functools.lru_cache(maxsize=None)
def _fused():
    """The two passes as operations with a backward of their own, and
    ``jax.jit`` round each of their four bodies: all sublayers of a step
    have one operand signature, so the lowered module holds each body
    once and a call at every site (forward, forward under remat,
    backward).  Where a body is lowered for anything but a TPU its
    passes are plain ``jax.numpy`` (``mhc_kernels``)."""
    import jax

    pre_forward, pre_backward = _body(_pre_forward), _body(_pre_backward)
    post_forward, post_backward = _body(_post_forward), _body(_post_backward)

    def pre_saving(x, w, alpha, b_pre, b_post, b_res, st):
        leaves = (w, alpha, b_pre, b_post, b_res)
        mixed, post, res, u, ms = pre_forward(x, *leaves, st=st)
        return (mixed, x, post, res), (x,) + leaves + (u, ms)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
    def pre(*args):
        return pre_saving(*args)[0]

    pre.defvjp(pre_saving, lambda st, saved, cts:
               pre_backward(*saved, *cts, st=st))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def post(x, y, res, post_map, st):
        return post_forward(x, y, res, post_map, st=st)

    def post_saving(x, y, res, post_map, st):
        return post_forward(x, y, res, post_map, st=st), (x, y, post_map)

    def post_bwd(st, saved, g_out):
        # the streams' slot carries the new streams' cotangent back to
        # ``pre``'s backward untouched: see ``_pre_backward``
        return (g_out,) + post_backward(*saved, g_out, st=st)

    post.defvjp(post_saving, post_bwd)
    return pre, post


def _hyper_residual(xs, lp: Dict, which: str, cfg, sublayer, how: str):
    """``how``: ``plain`` (the plain formulation), ``dispatch`` (the two
    passes, kernels where lowered for a TPU) or ``interpret`` (the two
    passes, their kernels run by the Pallas interpreter)."""
    import jax

    b, t, n, d = xs.shape
    x = xs.reshape(b * t, n * d)
    leaves = _leaves(lp, which)
    if how == "plain":
        st = _statics(cfg, n)
        pre_pass, post_pass = _plain_pre, _plain_post
    else:
        st = _statics(cfg, n, _fused_tile(xs), interpret=how == "interpret")
        pre_pass, post_pass = _fused()
    with jax.named_scope("mhc"):
        mixed, x, post, res = pre_pass(x, *leaves, st)
    y, aux = sublayer(mixed.reshape(b, t, d))
    with jax.named_scope("mhc"):
        out = post_pass(x, y.reshape(b * t, d), res, post, st)
    return out.reshape(xs.shape), aux


def hyper_residual(xs, lp: Dict, which: str, cfg, sublayer):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] * F(sum_i H_pre[i]
    X[i])`` over the streams ``xs`` (B, T, n, D); ``sublayer`` is ``F``
    with its own norm, and may return ``(y, aux)``.

    One algorithm, two lowerings.  Where the operands allow
    (``_fused_tile``) it is two passes over the streams as they lie, one
    before and one after the sublayer, under a backward of their own:
    Pallas kernels where the program is lowered for a TPU, the same sums
    in plain ``jax.numpy`` where it is lowered for anything else
    (``mhc_kernels``).  Any other operands take the plain formulation,
    differentiated by autodiff, which is also the tests' oracle.  The
    maps between (sigmoid, clamp, Sinkhorn) are plain float32
    ``jax.numpy`` either way.  The choice is made from the lowering
    platform and the operands' shapes and dtypes, and by nothing else;
    ``site_tally()`` counts it."""
    how = "plain" if _fused_tile(xs) is None else "dispatch"
    _sites["kernel" if how == "dispatch" else "plain"] += 1
    return _hyper_residual(xs, lp, which, cfg, sublayer, how)
