"""Plain reference: the Xing4.0 block, float32, one chip's share.

Straight ``jax.numpy`` at ``highest`` matmul precision: no kernel, no
grouped product (every held expert runs over every token, under a mask), no cache,
nothing imported from the program.  It follows the equations of the
issue that added it (ISSUE 28 §1; the sandbox has no network, so the
published code was not read) and is given the same share of the model
as the program: the ``held_experts`` of ``router_width`` routed experts
and a vocabulary slice.  What the absent experts would add is left out.

Per token, ``n = hc_mult`` residual streams ``X`` (n, D):

* every sublayer ``F`` (attention, feed-forward) has its own maps:
  ``x~ = vec(X) / sqrt(mean(vec(X)^2) + eps)``, ``u = x~ W``,
  ``H_pre = sigmoid(a0 u[:n] + b_pre)``, ``H_post = 2 sigmoid(a1
  u[n:2n] + b_post)``, ``H_res = Sinkhorn(exp(clip(a2 mat(u[2n:]) +
  B_res, -30, 30)))`` (20 times: rows over their sum + hc_eps, then
  columns); ``y = F(RMSNorm(sum_i H_pre[i] X[i]))``; ``X'[i] = sum_j
  H_res[i, j] X[j] + H_post[i] y``.  Input: every stream is the
  embedding; output: the streams summed, a final RMSNorm, the head;
* latent attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``,
  ``[c_kv; k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``, ``[k_nope; v] =
  c_kv W_kvb``, rotary on ``q``'s last ``qk_rope_head_dim`` and on
  ``k_r`` (one for all heads) with YaRN frequencies, causal softmax of
  ``q.k * (nope + rope)^-0.5 * m^2``, ``m = 0.1 ln(factor) + 1``;
* the leading layer's feed-forward: ``W_down(silu(W_gate x) * W_up x)``;
* an expert layer: ``s = sigmoid(x W_g)`` over all ``router_width``
  experts, the ``num_experts_per_tok`` largest of ``s + b`` chosen,
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``,
  ``y = sum over chosen AND held of w_k E_k(x) + E_shared(x)``; after
  the update ``b += rate * sign(mean(c) - c)`` with ``c`` the step's
  assignments to each expert of all; ``b`` takes no gradient;
* the multi-token module: ``h' = W_eh [RMSNorm(h); RMSNorm(Emb(t+1))]``
  with ``h`` the trunk's summed streams before its final norm, copied
  into the streams, one expert layer, summed, its own final RMSNorm,
  the shared head, cross entropy against ``t+2``; ``loss = L_main +
  mtp_loss_weight * L_mtp``.

Departures and assumptions (each also in the configuration's
``assumed``): where the clamp and ``hc_eps`` sit; no gain on ``x~``;
scalar ``a``s; copy-in and sum-out; the repository's half-split rotary
pairing (free under seeded weights); the order ``[h; emb]`` in the
multi-token module; ``rate`` 0.001 and no auxiliary balance loss;
``mtp_loss_weight`` 0.3; SGD with momentum.

Gradients are accumulated row by row (one sequence at a time, each
block recomputed in the backward pass, attention one head at a time),
so the float32 model, its gradients and momenta fit on the chip beside
one row's activations.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST

Leaf = Tuple[str, Tuple[int, ...], Tuple[str, float]]


def layer_kinds(cfg: Dict) -> List[str]:
    dense = cfg["first_k_dense_replace"]
    return ["dense_ffn" if i < dense else "experts"
            for i in range(cfg["num_hidden_layers"])]


def frozen(cfg: Dict) -> List[str]:
    """The selection biases: state, not parameters."""
    return [n for n, _, _ in leaves(cfg) if n.endswith("router_bias")]


def leaves(cfg: Dict) -> List[Leaf]:
    """``(name, shape, init)`` of every array of the state, in the
    program's own order (``mxnet_tpu.transformer.param_shapes``; the
    driver checks).  ``cfg["init"]`` gives the standard deviations."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_attention_heads"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, e = cfg["hc_mult"], cfg["router_width"]
    g, fe = len(cfg["held_experts"]), cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * fe
    init = cfg["init"]
    std = init["std"]
    resid = std * (2.0 * cfg["num_hidden_layers"]) ** -0.5
    one = ("const", 1.0)

    def streams(p, which):
        q = "%shc_%s_" % (p, which)
        return [(q + "w", (n * d, 2 * n + n * n), ("normal", std)),
                (q + "alpha", (3,), ("const", init["hc_alpha"])),
                (q + "b_pre", (n,), ("const", 0.0)),
                (q + "b_post", (n,), ("const", 0.0)),
                (q + "b_res", (n, n), ("normal", init["hc_b_res_std"]))]

    def block(p, kind):
        out = streams(p, "attn") + [
            (p + "attn_norm", (d,), one),
            (p + "wq_a", (d, rq), ("normal", std)),
            (p + "q_norm", (rq,), one),
            (p + "wq_b", (rq, h * (nope + rp)), ("normal", std)),
            (p + "wkv_a", (d, rkv + rp), ("normal", std)),
            (p + "kv_norm", (rkv,), one),
            (p + "wkv_b", (rkv, h * (nope + dv)), ("normal", std)),
            (p + "wo", (h * dv, d), ("normal", resid))]
        out += streams(p, "mlp") + [(p + "mlp_norm", (d,), one)]
        if kind == "dense_ffn":
            f = cfg["intermediate_size"]
            return out + [(p + "w_gate", (d, f), ("normal", std)),
                          (p + "w_up", (d, f), ("normal", std)),
                          (p + "w_down", (f, d), ("normal", resid))]
        return out + [
            (p + "router", (d, e), ("normal", init["router_std"])),
            (p + "router_bias", (e,), ("normal", init["router_bias_std"])),
            (p + "we_gate", (g, d, fe), ("normal", std)),
            (p + "we_up", (g, d, fe), ("normal", std)),
            (p + "we_down", (g, fe, d), ("normal", resid)),
            (p + "ws_gate", (d, fs), ("normal", std)),
            (p + "ws_up", (d, fs), ("normal", std)),
            (p + "ws_down", (fs, d), ("normal", resid))]

    out: List[Leaf] = [("embed", (v, d), ("normal", std))]
    for i, kind in enumerate(layer_kinds(cfg)):
        out += block("blk%d." % i, kind)
    out += [("final_norm", (d,), one), ("head", (v, d), ("normal", std))]
    for j in range(cfg["num_nextn_predict_layers"]):
        p = "mtp%d." % j
        out += [(p + "hnorm", (d,), one), (p + "enorm", (d,), one),
                (p + "eh_proj", (2 * d, d), ("normal", std))]
        out += block(p, "experts") + [(p + "final_norm", (d,), one)]
    return out


# ---------------------------------------------------------------------
def _mm(x, w, q):
    if q is not None:
        x, w = q(x), q(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def yarn_frequencies(cfg: Dict):
    """The ``qk_rope_head_dim // 2`` rotary frequencies and ``m``."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    half = dim // 2
    extra = [base ** (-2.0 * i / dim) for i in range(half)]

    def correction(rotations):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freqs = []
    for i in range(half):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        mask = 1.0 - ramp
        freqs.append(extra[i] / sc["factor"] * (1.0 - mask)
                     + extra[i] * mask)
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 \
        if sc["factor"] > 1 and sc["mscale_all_dim"] else 1.0
    return jnp.asarray(freqs, jnp.float32), m


def _rope(x, freqs):
    """(T, H, R): the first half of the last axis against the second,
    position t by ``t * freqs``."""
    t = x.shape[0]
    half = x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p: Dict, pre: str, x, cfg: Dict, q):
    t = x.shape[0]
    h, nope, rp, dv = cfg["num_attention_heads"], \
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    freqs, m = yarn_frequencies(cfg)
    cq = _rmsnorm(_mm(x, p[pre + "wq_a"], q), p[pre + "q_norm"], eps)
    qh = _mm(cq, p[pre + "wq_b"], q).reshape(t, h, nope + rp)
    kva = _mm(x, p[pre + "wkv_a"], q)
    ckv = _rmsnorm(kva[:, :r], p[pre + "kv_norm"], eps)
    kv = _mm(ckv, p[pre + "wkv_b"], q).reshape(t, h, nope + dv)
    k_r = _rope(kva[:, r:].reshape(t, 1, rp), freqs)
    qh = jnp.concatenate([qh[..., :nope], _rope(qh[..., nope:], freqs)], -1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_r, (t, h, rp))], -1)
    vh = kv[..., nope:]
    scale = (nope + rp) ** -0.5 * m * m
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):
        q1, k1, v1 = qkv
        s = jnp.matmul(q1, k1.T, precision=HIGHEST) * scale
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(pr, v1, precision=HIGHEST)

    o = lax.map(one_head, (qh.transpose(1, 0, 2), kh.transpose(1, 0, 2),
                           vh.transpose(1, 0, 2)))          # (H, T, dv)
    return _mm(o.transpose(1, 0, 2).reshape(t, h * dv), p[pre + "wo"], q)


def _gated(x, gate, up, down, q):
    return _mm(jax.nn.silu(_mm(x, gate, q)) * _mm(x, up, q), down, q)


def _experts(p: Dict, pre: str, x, bias, cfg: Dict, q):
    """``(y, choice)``: the held experts' part plus the shared expert,
    and the experts chosen for each token (T, k)."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(x, p[pre + "router"], precision=HIGHEST))
    _, choice = lax.top_k(lax.stop_gradient(s) + bias, k)
    picked = jnp.take_along_axis(s, choice, axis=1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20)
    w = picked * cfg["routed_scaling_factor"]
    y = _gated(x, p[pre + "ws_gate"], p[pre + "ws_up"], p[pre + "ws_down"],
               q)
    # every held expert over every token, then the mask: a token's
    # weight for an expert it did not choose is nought
    held = jnp.asarray(cfg["held_experts"], choice.dtype)
    gates = jnp.sum(jnp.where(choice[None] == held[:, None, None], w[None],
                              0.0), axis=2)                      # (G, T)

    def each(eq, a, b_):
        if q is not None:
            a, b_ = q(a), q(b_)
        return jnp.einsum(eq, a, b_, precision=HIGHEST)

    mid = jax.nn.silu(each("td,edf->etf", x, p[pre + "we_gate"])) \
        * each("td,edf->etf", x, p[pre + "we_up"])
    out = each("etf,efd->etd", mid, p[pre + "we_down"])
    y = y + jnp.einsum("et,etd->td", gates, out, precision=HIGHEST)
    return y, choice


def _maps(p: Dict, pre: str, xs, cfg: Dict, identity_res: bool):
    t, n, d = xs.shape
    flat = xs.reshape(t, n * d)
    xt = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    u = jnp.matmul(xt, p[pre + "w"], precision=HIGHEST)
    a = p[pre + "alpha"]
    h_pre = jax.nn.sigmoid(a[0] * u[:, :n] + p[pre + "b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + p[pre + "b_post"])
    if identity_res:                     # the planted fault
        return h_pre, h_post, jnp.broadcast_to(jnp.eye(n, dtype=xs.dtype),
                                              (t, n, n))
    m = jnp.exp(jnp.clip(
        a[2] * u[:, 2 * n:].reshape(t, n, n) + p[pre + "b_res"],
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))

    def once(m, _):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg["hc_eps"])
        return m / (jnp.sum(m, axis=1, keepdims=True) + cfg["hc_eps"]), None

    m, _ = lax.scan(once, m, None, length=cfg["hc_sinkhorn_iters"])
    return h_pre, h_post, m


def _sublayer(p: Dict, pre: str, which: str, norm: str, xs, cfg, fn,
              identity_res):
    h_pre, h_post, h_res = _maps(p, "%shc_%s_" % (pre, which), xs, cfg,
                                 identity_res)
    x = _rmsnorm(jnp.einsum("tn,tnd->td", h_pre, xs, precision=HIGHEST),
                 p[pre + norm], cfg["rms_norm_eps"])
    y, extra = fn(x)
    return jnp.einsum("tij,tjd->tid", h_res, xs, precision=HIGHEST) \
        + h_post[:, :, None] * y[:, None, :], extra


def _block(p: Dict, pre: str, kind: str, xs, bias, cfg: Dict, q,
           identity_res: bool):
    xs, _ = _sublayer(p, pre, "attn", "attn_norm", xs, cfg,
                      lambda x: (_attention(p, pre, x, cfg, q), None),
                      identity_res)
    if kind == "dense_ffn":
        ffn = lambda x: (_gated(x, p[pre + "w_gate"], p[pre + "w_up"],
                                p[pre + "w_down"], q), None)
    else:
        ffn = lambda x: _experts(p, pre, x, bias, cfg, q)
    return _sublayer(p, pre, "mlp", "mlp_norm", xs, cfg, ffn, identity_res)


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def row_loss(p: Dict, biases: Dict, tokens, labels, cfg: Dict, q=None,
             identity_res: bool = False):
    """``(loss, choices)`` of ONE sequence: ``tokens`` (T,), ``labels``
    (T + num_nextn_predict_layers,); ``choices`` (L, T, k) over the
    expert layers in order, the multi-token module's last."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    t = tokens.shape[0]

    def run(pre, kind, xs):
        bias = biases.get(pre + "router_bias")
        return jax.checkpoint(
            lambda pp, bb, hh: _block(pp, pre, kind, hh, bb, cfg, q,
                                      identity_res))(p, bias, xs)

    xs = jnp.broadcast_to(p["embed"][tokens][:, None, :],
                          (t, n, cfg["hidden_size"]))
    choices = []
    for i, kind in enumerate(layer_kinds(cfg)):
        xs, choice = run("blk%d." % i, kind, xs)
        if choice is not None:
            choices.append(choice)
    h = jnp.sum(xs, axis=1)
    logits = _mm(_rmsnorm(h, p["final_norm"], eps), p["head"].T, q)
    loss = _cross_entropy(logits, labels[:t])
    for j in range(cfg["num_nextn_predict_layers"]):
        pre = "mtp%d." % j
        e = p["embed"][labels[j:j + t]]
        both = jnp.concatenate([_rmsnorm(h, p[pre + "hnorm"], eps),
                                _rmsnorm(e, p[pre + "enorm"], eps)], -1)
        xs = jnp.broadcast_to(_mm(both, p[pre + "eh_proj"], q)[:, None, :],
                              (t, n, cfg["hidden_size"]))
        xs, choice = run(pre, "experts", xs)
        choices.append(choice)
        h = jnp.sum(xs, axis=1)
        logits = _mm(_rmsnorm(h, p[pre + "final_norm"], eps),
                     p["head"].T, q)
        loss = loss + cfg["mtp_loss_weight"] * _cross_entropy(
            logits, labels[j + 1:j + 1 + t])
    return loss, jnp.stack(choices)


def make_step(cfg: Dict, lr: float, momentum: float,
              q: Optional[Callable] = None, identity_res: bool = False):
    """One jitted SGD-momentum step over a batch: ``step(p, m, b,
    tokens (B, T), labels (B, T + 1)) -> (p, m, b, loss, choices
    (B, L, T, k))``.  ``p`` and ``m`` hold the trained leaves, ``b`` the
    selection biases.  The loss is the mean of the rows' losses, its
    gradient summed row by row.  The learning rate is an argument of
    the compiled program, so a step at another rate (the planted fault
    that leaves the state unchanged) compiles nothing new."""
    compiled = _compiled_step(json.dumps(cfg, sort_keys=True), momentum, q,
                              identity_res)
    return lambda p, m, b, tokens, labels: compiled(
        p, m, b, tokens, labels, jnp.float32(lr))


@functools.lru_cache(maxsize=None)
def _compiled_step(cfg_json: str, momentum: float, q, identity_res: bool):
    cfg = json.loads(cfg_json)
    rate, width = cfg["router_bias_rate"], cfg["router_width"]
    biased = frozen(cfg)                 # in expert-layer order

    def step(p, m, b, tokens, labels, lr):
        def one(carry, row):
            g_sum, loss_sum = carry
            (loss, choice), g = jax.value_and_grad(row_loss, has_aux=True)(
                p, b, row[0], row[1], cfg, q, identity_res)
            return (jax.tree_util.tree_map(jnp.add, g_sum, g),
                    loss_sum + loss), choice

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (g, loss), choices = lax.scan(
            one, (zero, jnp.zeros((), jnp.float32)), (tokens, labels))
        rows = tokens.shape[0]
        m = {k: momentum * m[k] - lr * g[k] / rows for k in p}
        p = {k: p[k] + m[k] for k in p}
        new_b = {}
        for i, name in enumerate(biased):
            c = jnp.zeros((width,), jnp.float32).at[
                choices[:, i].reshape(-1)].add(1.0)
            new_b[name] = b[name] + rate * jnp.sign(jnp.mean(c) - c)
        return p, m, new_b, loss / rows, choices

    return jax.jit(step, donate_argnums=(0, 1))
