"""Plain reference: the sarvam-105b block, float32, one chip's share.

Straight ``jax.numpy`` at ``highest`` matmul precision: the UNabsorbed
latent attention (every head's keys and values made from the latent),
a loop over the held experts with a dense mask (every held expert over
every token; no grouped product, no sort), no cache, no batching, no
kernel, nothing imported from the program.  It follows the equations of
the issue that added it (ISSUE 38, "The layer, as this issue reads the
row"; the sandbox has no network, so the published modelling code was
not read) and is given the same share of the model as the program: the
``held_experts`` of ``router_width`` routed experts and a vocabulary
slice.  What the absent experts would add is left out.  Given every
expert (``held_experts`` = all of them) it is the uncut layer.

For a layer's input ``x`` (T, D), eps ``rms_norm_eps``:

* ``a = RMSNorm(x; g_attn)``; ``q = a W_q`` -> (T, H, nope + rope);
  ``[c_raw | k_raw] = a W_kva`` (kv_lora_rank | rope); ``c =
  RMSNorm(c_raw; g_kv)``; ``[k_nope | v] = c W_kvb`` -> (T, H, nope |
  v); rotary on ``q``'s last ``rope`` dims and on ``k_raw`` (one key for
  all heads) with YaRN frequencies, cos and sin unscaled; scores ``(q .
  k) * (nope + rope)^-0.5 * m^2``, ``m = 0.1 ln(factor) + 1``; causal
  softmax; ``x += concat(P v) W_o``.  No norm on the queries;
* ``m = RMSNorm(x; g_mlp)``; the leading ``first_k_dense_replace``
  layers: ``x += W_down(silu(W_gate m) * W_up m)``;
* an expert layer: ``s = sigmoid(m W_r)`` over all ``router_width``
  experts, the ``num_experts_per_tok`` largest of ``s + b`` chosen,
  ``w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``,
  ``x += sum over chosen AND held of w_e E_e(m) + E_shared(m)``;
* a final RMSNorm and the logits over the held rows of an untied head.

Attention runs by blocks of query rows and the model a layer at a time
(``layer``), so that the float32 cut fits the chip beside one
sequence: its float32 weights alone are 18 GB.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
# query rows a block of attention: 64 heads x 1,024 x 8,192 float32
# scores are 2.1 GB
QUERY_BLOCK = 1024

Leaf = Tuple[str, Tuple[int, ...], Tuple[str, float]]


def layer_kinds(cfg: Dict) -> List[str]:
    dense = cfg["first_k_dense_replace"]
    return ["dense_ffn" if i < dense else "experts"
            for i in range(cfg["num_hidden_layers"])]


def leaves(cfg: Dict) -> List[Leaf]:
    """``(name, shape, init)`` of every array, in the program's own
    order (``mxnet_tpu.transformer.param_shapes``; the tests check).
    ``cfg["init"]`` gives the standard deviations."""
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["num_attention_heads"]
    nope, rp, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rkv, e = cfg["kv_lora_rank"], cfg["router_width"]
    g, fe = len(cfg["held_experts"]), cfg["moe_intermediate_size"]
    fs = cfg["num_shared_experts"] * fe
    init = cfg["init"]
    std = init["std"]
    resid = std * (2.0 * cfg["num_hidden_layers"]) ** -0.5
    one = ("const", 1.0)
    out: List[Leaf] = [("embed", (v, d), ("normal", std))]
    for i, kind in enumerate(layer_kinds(cfg)):
        p = "blk%d." % i
        out += [(p + "attn_norm", (d,), one),
                (p + "wq", (d, h * (nope + rp)), ("normal", std)),
                (p + "wkv_a", (d, rkv + rp), ("normal", std)),
                (p + "kv_norm", (rkv,), one),
                (p + "wkv_b", (rkv, h * (nope + dv)), ("normal", std)),
                (p + "wo", (h * dv, d), ("normal", resid)),
                (p + "mlp_norm", (d,), one)]
        if kind == "dense_ffn":
            f = cfg["intermediate_size"]
            out += [(p + "w_gate", (d, f), ("normal", std)),
                    (p + "w_up", (d, f), ("normal", std)),
                    (p + "w_down", (f, d), ("normal", resid))]
            continue
        out += [(p + "router", (d, e), ("normal", init["router_std"])),
                (p + "router_bias", (e,),
                 ("normal", init["router_bias_std"])),
                (p + "we_gate", (g, d, fe), ("normal", std)),
                (p + "we_up", (g, d, fe), ("normal", std)),
                (p + "we_down", (g, fe, d), ("normal", resid)),
                (p + "ws_gate", (d, fs), ("normal", std)),
                (p + "ws_up", (d, fs), ("normal", std)),
                (p + "ws_down", (fs, d), ("normal", resid))]
    return out + [("final_norm", (d,), one),
                  ("head", (v, d), ("normal", std))]


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
        freqs.append(extra[i] / sc["factor"] * ramp
                     + extra[i] * (1.0 - ramp))
    if sc["mscale"] != sc["mscale_all_dim"]:
        raise NotImplementedError("cos and sin scaled: mscale differs "
                                  "from mscale_all_dim")
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


def attention(p: Dict, pre: str, x, cfg: Dict, q=None):
    """The attention sublayer's result (T, D) for the normed input
    ``x``, by blocks of ``QUERY_BLOCK`` query rows."""
    t = x.shape[0]
    h, nope, rp, dv = cfg["num_attention_heads"], \
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    freqs, m = yarn_frequencies(cfg)
    qh = _mm(x, p[pre + "wq"], q).reshape(t, h, nope + rp)
    kva = _mm(x, p[pre + "wkv_a"], q)
    c = _rmsnorm(kva[:, :r], p[pre + "kv_norm"], eps)
    kv = _mm(c, p[pre + "wkv_b"], q).reshape(t, h, nope + dv)
    k_r = _rope(kva[:, r:].reshape(t, 1, rp), freqs)
    qh = jnp.concatenate([qh[..., :nope], _rope(qh[..., nope:], freqs)], -1)
    kh = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_r, (t, h, rp))], -1)
    vh = kv[..., nope:]
    scale = (nope + rp) ** -0.5 * m * m
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, h, nope + rp)
    at = jnp.arange(t + pad).reshape(-1, block)

    def rows(args):
        q1, pos = args
        s = jnp.einsum("qhd,khd->hqk", q1, kh, precision=HIGHEST) * scale
        s = jnp.where(pos[None, :, None] >= jnp.arange(t)[None, None, :],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vh,
                          precision=HIGHEST)

    o = lax.map(rows, (qb, at)).reshape(t + pad, h * dv)[:t]
    return _mm(o, p[pre + "wo"], q)


def _gated(x, gate, up, down, q):
    return _mm(jax.nn.silu(_mm(x, gate, q)) * _mm(x, up, q), down, q)


def route(p: Dict, pre: str, x, cfg: Dict):
    """``(choice, weight)``: the experts chosen for each token (T, k)
    and what each adds of its result."""
    s = jax.nn.sigmoid(jnp.matmul(x, p[pre + "router"], precision=HIGHEST))
    _, choice = lax.top_k(s + p[pre + "router_bias"],
                          cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, choice, axis=1)
    return choice, picked / (jnp.sum(picked, axis=1, keepdims=True)
                             + 1e-20) * cfg["routed_scaling_factor"]


def experts(p: Dict, pre: str, x, cfg: Dict, q=None, shared: bool = True):
    """``(y, choice)``: the held experts' part of the expert layer's
    result for the normed input ``x``, with the shared expert's unless
    ``shared`` is False, and the experts chosen for each token."""
    choice, w = route(p, pre, x, cfg)
    held = jnp.asarray(cfg["held_experts"], choice.dtype)
    # a token's weight for an expert it did not choose is nought
    gates = jnp.sum(jnp.where(choice[None] == held[:, None, None], w[None],
                              0.0), axis=2)                      # (G, T)

    def one(y, e):
        gate, up, down, g = e
        return y + g[:, None] * _gated(x, gate, up, down, q), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (p[pre + "we_gate"], p[pre + "we_up"],
                     p[pre + "we_down"], gates))
    if shared:
        y = y + _gated(x, p[pre + "ws_gate"], p[pre + "ws_up"],
                       p[pre + "ws_down"], q)
    return y, choice


def layer(p: Dict, pre: str, kind: str, h, cfg: Dict, q=None):
    """One block over ``h`` (T, D) from its own leaves ``p`` (the
    others need not be there) -> ``(h, routed)``; ``routed`` is None
    for a dense layer, else the experts chosen for each token (T, k)
    and what the router read (T, D)."""
    eps = cfg["rms_norm_eps"]
    h = h + attention(p, pre, _rmsnorm(h, p[pre + "attn_norm"], eps), cfg,
                      q)
    m = _rmsnorm(h, p[pre + "mlp_norm"], eps)
    if kind == "dense_ffn":
        return h + _gated(m, p[pre + "w_gate"], p[pre + "w_up"],
                          p[pre + "w_down"], q), None
    y, choice = experts(p, pre, m, cfg, q)
    return h + y, {"choice": choice, "router_input": m}


def head(p: Dict, h, cfg: Dict, q=None):
    """Logits (rows, vocab) of the rows ``h`` of the last layer's
    result."""
    return _mm(_rmsnorm(h, p["final_norm"], cfg["rms_norm_eps"]),
               p["head"].T, q)


def forward(p: Dict, tokens, cfg: Dict, q: Optional[Callable] = None):
    """Logits (T, vocab) of ONE sequence of token ids (T,), the whole
    model at once (the tests' sizes).  ``q`` is applied to both operands
    of every weight matmul: the low-precision control passes a
    quantiser."""
    h = p["embed"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        h, _ = layer(p, "blk%d." % i, kind, h, cfg, q)
    return head(p, h, cfg, q)
