"""FROZEN: ``mxnet_tpu/transformer/model.py`` as it stood before the block
became data (commit 2d4b441, PR 27), with its relative imports made
absolute and nothing else changed.  ``test_transformer_blocks.py`` pins
the dense configuration through today's ``apply`` / ``lm_loss`` / train
step bit for bit against these functions.  Not a place for edits.

The original docstring follows.

Decoder-only transformer LM — the long-context workload tier.

The reference (2017 MXNet) tops out at bucketed LSTMs for sequence
work (SURVEY.md §5 "Long-context"); this is the TPU-first superset the
rebuild is required to supply: a modern decoder-only LM (RMSNorm, RoPE,
tied embedding head) whose attention is PLUGGABLE between the
single-chip fused kernel and the two sequence-parallel formulations
that already exist in ``parallel/`` but had no end-to-end workload:

  * ``flash``   — parallel/attention.py blockwise online-softmax scan
                  (single chip / no sp axis);
  * ``ring``    — parallel/ring_attention.py KV-rotation over the mesh's
                  ``sp`` axis (contexts that don't fit one chip);
  * ``ulysses`` — parallel/sequence.py all-to-all head resharding
                  (small sp relative to head count).

Selection rides ``MXNET_ATTENTION_IMPL`` (env.py) or an explicit
argument; the model body is identical either way — ring/ulysses run as
per-shard bodies inside the train step's shard_map, so positions are
derived from ``lax.axis_index("sp")`` (the ``pos_offset`` argument).

The model is a PURE param-tree function (flat ``{name: array}`` dict in
forward/layer order — exactly what ``buckets.partition`` and the ZeRO-1
sharded update consume), not a gluon Block or a Module symbol: the
forcing-function verdict on which layer carries imperative workloads is
recorded in SURVEY.md §round-14.

Rematerialization is per-block and policy-selectable
(``MXNET_REMAT_POLICY`` = ``none`` | ``block`` | ``attention``,
remat.py): ``block`` keeps only block-boundary residuals (the classic
trade for deep stacks), ``attention`` rematerializes just the attention
sub-graph (the O(T) score recompute) and keeps the cheap MLP residuals.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

from mxnet_tpu import env as _env
from mxnet_tpu.remat import checkpoint_scope, remat_policy

__all__ = [
    "TransformerConfig", "ATTENTION_IMPLS", "attention_impl",
    "make_attn_fn", "param_shapes", "init_params", "apply", "lm_loss",
    "dense_causal_attn", "gather_kv", "apply_prefill", "apply_decode",
]

ATTENTION_IMPLS = ("flash", "ring", "ulysses")


class TransformerConfig(NamedTuple):
    """Decoder-only LM dimensions + dtypes.  ``d_ff`` ``None`` means
    the conventional ``4*d_model``."""
    vocab_size: int = 256
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    d_ff: Optional[int] = None
    rope_base: float = 10000.0
    dtype: str = "float32"        # compute (activation) dtype
    param_dtype: str = "float32"  # parameter storage dtype
    eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model


def attention_impl(override: Optional[str] = None) -> str:
    """The selected attention implementation: explicit argument wins,
    else ``MXNET_ATTENTION_IMPL`` (default ``flash``).  Unknown names
    raise — a typo'd impl silently falling back would bench the wrong
    kernel."""
    impl = override if override is not None \
        else _env.get_str("MXNET_ATTENTION_IMPL")
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            "unknown attention impl %r (MXNET_ATTENTION_IMPL); pick "
            "one of %s" % (impl, "/".join(ATTENTION_IMPLS)))
    return impl


def make_attn_fn(impl: str, sp_axis: Optional[str] = None,
                 causal: bool = True):
    """Bind an attention impl to a callable ``fn(q, k, v) -> out`` over
    (B, T_local, H, Dh) activations.

    With ``sp_axis`` the returned fn is a PER-SHARD body (must run
    inside shard_map over that axis); ``flash`` is rejected there
    because local-only attention over a sequence shard is silently
    WRONG math, not a slower variant.  Without an sp axis the
    sequence-parallel impls are rejected for the symmetric reason
    (their collectives need the axis)."""
    impl = attention_impl(impl)
    if sp_axis is None:
        if impl != "flash":
            raise ValueError(
                "attention impl %r needs a sequence-parallel mesh axis; "
                "build the step over a mesh with 'sp' (or select "
                "MXNET_ATTENTION_IMPL=flash)" % impl)
        from mxnet_tpu.parallel.attention import flash_attention

        return functools.partial(flash_attention, causal=causal)
    if impl == "ring":
        from mxnet_tpu.parallel.ring_attention import ring_attention

        return functools.partial(ring_attention, axis_name=sp_axis,
                                 causal=causal)
    if impl == "ulysses":
        from mxnet_tpu.parallel.sequence import ulysses_attention

        return functools.partial(ulysses_attention, axis_name=sp_axis,
                                 causal=causal)
    raise ValueError(
        "attention impl %r cannot run sequence-sharded (sp axis %r); "
        "pick ring or ulysses" % (impl, sp_axis))


# ---------------------------------------------------------------------------
# parameters: flat dict, FORWARD (layer) order — the bucket partitioner's
# and the ZeRO-1 shard layout's input contract
# ---------------------------------------------------------------------------
def param_shapes(cfg: TransformerConfig) -> List[Tuple[str, tuple, str]]:
    """``(name, shape, dtype)`` for every trainable param in layer
    order — shapes only, no arrays: what ``scaling.grad_entries`` /
    the autotuner's leaf-granularity timing model consume to tune the
    attention-dominated comm pattern without a compile."""
    D, F, V = cfg.d_model, cfg.ff_dim, cfg.vocab_size
    dt = cfg.param_dtype
    out = [("embed", (V, D), dt)]
    for i in range(cfg.n_layers):
        p = "blk%d." % i
        out += [
            (p + "attn_norm", (D,), dt),
            (p + "wqkv", (D, 3 * D), dt),
            (p + "wo", (D, D), dt),
            (p + "mlp_norm", (D,), dt),
            (p + "w1", (D, F), dt),
            (p + "w2", (F, D), dt),
        ]
    out.append(("final_norm", (D,), dt))
    return out


def init_params(key, cfg: TransformerConfig) -> Dict:
    """Initialize the flat param dict: N(0, 0.02) matrices (wo/w2
    scaled down by sqrt(2L) — the GPT-2 residual-stream convention),
    unit norms.  Deterministic per (key, cfg)."""
    import jax
    import jax.numpy as jnp

    if cfg.d_model % cfg.n_heads:
        raise ValueError("d_model %d must divide by n_heads %d"
                         % (cfg.d_model, cfg.n_heads))
    resid_scale = (2.0 * max(cfg.n_layers, 1)) ** -0.5
    params: Dict = {}
    for idx, (name, shape, dtype) in enumerate(param_shapes(cfg)):
        sub = jax.random.fold_in(key, idx)
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, dtype)
            continue
        scale = 0.02
        if name.endswith(("wo", "w2")):
            scale *= resid_scale
        params[name] = (scale * jax.random.normal(
            sub, shape, jnp.float32)).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rmsnorm(x, gain, eps):
    import jax.numpy as jnp

    # f32 statistics (or wider, for the fp64 control methodology)
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    scale = jnp.reciprocal(jnp.sqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))
    return (xf * scale).astype(x.dtype) * gain.astype(x.dtype)


def _rope(x, positions, base):
    """Rotary position embedding over (B, T, H, Dh) with GLOBAL
    ``positions`` — (T,) shared across the batch (training / sequence
    sharding: each shard passes its own global offsets, so rotation
    angles are placement-invariant) or (B, T) per-sequence (decode:
    every slot sits at its OWN cache cursor).  The (T,) path is
    bit-for-bit the historical rotation."""
    import jax.numpy as jnp

    Dh = x.shape[-1]
    half = Dh // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    if ang.ndim == 2:                     # (T, half)
        cos = jnp.cos(ang)[None, :, None, :]  # (1, T, 1, half)
        sin = jnp.sin(ang)[None, :, None, :]
    else:                                 # (B, T, half)
        cos = jnp.cos(ang)[:, :, None, :]     # (B, T, 1, half)
        sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _gelu(x):
    import jax

    return jax.nn.gelu(x, approximate=True)


def apply(params: Dict, tokens, cfg: TransformerConfig, *,
          attn_fn, pos_offset=0, remat: Optional[str] = None):
    """Forward pass: ``tokens`` (B, T_local) int -> logits
    (B, T_local, vocab) float32 (tied embedding head).

    ``pos_offset`` is this shard's global position of token 0 (a traced
    scalar under shard_map: ``axis_index("sp") * T_local``); ``remat``
    overrides ``MXNET_REMAT_POLICY``."""
    import jax
    import jax.numpy as jnp

    policy = remat_policy(remat)
    compute = jnp.dtype(cfg.dtype)
    B, t = tokens.shape
    positions = pos_offset + jnp.arange(t)
    embed = params["embed"]
    with jax.named_scope("embed"):
        h = embed.astype(compute)[tokens]

    def attn_part(h, g, wqkv, wo):
        shape = (B, t, cfg.n_heads, cfg.head_dim)
        q, k, v = _qkv(h, g, wqkv, shape, positions, cfg)
        with jax.named_scope("attn"):
            o = attn_fn(q, k, v)
        return _attn_out(o, wo, (B, t, cfg.d_model))

    def block(h, g_attn, wqkv, wo, g_mlp, w1, w2):
        h = h + checkpoint_scope(attn_part, policy, "attention")(
            h, g_attn, wqkv, wo)
        return h + _mlp(h, g_mlp, w1, w2, cfg)

    block = checkpoint_scope(block, policy, "block")
    for i in range(cfg.n_layers):
        p = "blk%d." % i
        with jax.named_scope("layer%02d" % i):
            h = block(h, params[p + "attn_norm"], params[p + "wqkv"],
                      params[p + "wo"], params[p + "mlp_norm"],
                      params[p + "w1"], params[p + "w2"])
    return _logits(_final_norm(h, params, cfg), params, cfg,
                   "btd,vd->btv")


# The scope vocabulary of the three forwards (HLO metadata only; what a
# device trace's operations are classed by): ``embed``, ``norm``,
# ``attn_proj`` (the qkv and output matmuls, rotary), ``attn`` (the
# attention core alone), ``mlp``, ``head_loss`` (logits here, the loss
# in the train step), inside one ``layer%02d`` a layer.
def _qkv(h, g, wqkv, shape, positions, cfg):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("norm"):
        a = _rmsnorm(h, g, cfg.eps)
    with jax.named_scope("attn_proj"):
        qkv = a @ wqkv.astype(a.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = _rope(q.reshape(shape), positions, cfg.rope_base)
        k = _rope(k.reshape(shape), positions, cfg.rope_base)
        return q, k, v.reshape(shape)


def _attn_out(o, wo, shape):
    import jax

    with jax.named_scope("attn_proj"):
        return o.reshape(shape) @ wo.astype(o.dtype)


def _mlp(h, g, w1, w2, cfg):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("norm"):
        m = _rmsnorm(h, g, cfg.eps)
    with jax.named_scope("mlp"):
        return jnp.dot(_gelu(m @ w1.astype(m.dtype)), w2.astype(m.dtype))


def _final_norm(h, params, cfg):
    import jax

    with jax.named_scope("norm"):
        return _rmsnorm(h, params["final_norm"], cfg.eps)


def _logits(h, params, cfg, einsum):
    """The tied head; logits accumulate in f32 (f64 under the control
    methodology) regardless of the bf16 compute dtype."""
    import jax
    import jax.numpy as jnp

    acc = jnp.promote_types(jnp.dtype(cfg.dtype), jnp.float32)
    with jax.named_scope("head_loss"):
        return jnp.einsum(einsum, h.astype(acc),
                          params["embed"].astype(acc))


def lm_loss(logits, labels):
    """Mean next-token cross entropy over this shard's tokens: logits
    (B, T, V) f32, labels (B, T) int.  Every shard holds the same token
    count, so ``pmean`` of per-shard means over dp×sp IS the global
    mean."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("head_loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# generation forwards: prefill/decode over a PAGED KV cache
#
# The cache is a per-layer pool of fixed-size token blocks
# ``{"k<i>"|"v<i>": (num_blocks, block_tokens, H, Dh)}`` plus a
# per-sequence block table (serving/kvcache.py owns allocation; block 0
# is the GARBAGE block — every write from a padded position or an
# inactive slot is routed there, so the compiled step never branches on
# liveness).  Scatter runs BEFORE gather inside the decode step, so the
# new token attends to itself through the same cache path as its
# history — one code path, pinned by the greedy-equality tests.
# ---------------------------------------------------------------------------
def _masked_attn(q, k, v, mask):
    """Naive dense attention with an explicit boolean ``mask``
    (B, Tq, Tk): f32 scores/softmax, output cast back to q's dtype.
    This single formulation IS the generation tier's reference math —
    prefill, paged decode, and the equality tests all call it, so
    "gather == dense" reduces to "the gathered inputs are identical"."""
    import jax
    import jax.numpy as jnp

    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def dense_causal_attn(q, k, v):
    """Dense causal attention over (B, T, H, Dh) in the generation
    tier's reference formulation — pass as ``attn_fn`` to :func:`apply`
    to build the single-sequence reference the paged/continuous decode
    must match token-for-token."""
    import jax.numpy as jnp

    t = q.shape[1]
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    return _masked_attn(q, k, v,
                        jnp.broadcast_to(causal[None], (q.shape[0], t, t)))


def _scatter_tokens(pool, x, block_tables, pos, block_tokens,
                    valid=None):
    """Write per-token K or V rows ``x`` (B, T, H, Dh) into the block
    ``pool`` (N, block_tokens, H, Dh) at token positions ``pos``
    (B, T), addressed through ``block_tables`` (B, W).  Positions with
    ``valid`` False — prompt padding, inactive slots — collapse to flat
    index 0: block 0 is the garbage block, its contents never read."""
    import jax.numpy as jnp

    bt = int(block_tokens)
    blk = jnp.take_along_axis(block_tables, pos // bt, axis=1)
    flat = blk * bt + pos % bt
    if valid is not None:
        flat = jnp.where(valid, flat, 0)
    flat_pool = pool.reshape((-1,) + pool.shape[2:])
    flat_pool = flat_pool.at[flat.reshape(-1)].set(
        x.reshape((-1,) + x.shape[2:]).astype(pool.dtype))
    return flat_pool.reshape(pool.shape)


def gather_kv(pages, block_tables, layer):
    """Gather one layer's cached K/V through the block tables:
    ``(B, W)`` tables over ``(N, bt, H, Dh)`` pools -> two
    ``(B, W*bt, H, Dh)`` dense views.  This is the read path INSIDE the
    compiled decode step; the bitwise test drives it standalone."""
    k = pages["k%d" % layer][block_tables]
    v = pages["v%d" % layer][block_tables]
    b, w, bt = k.shape[:3]
    return (k.reshape((b, w * bt) + k.shape[3:]),
            v.reshape((b, w * bt) + v.shape[3:]))


def apply_prefill(params, tokens, prompt_lens, cfg: TransformerConfig,
                  *, pages, block_tables, block_tokens):
    """Prefill forward: right-padded prompts ``tokens`` (B, T) with
    real lengths ``prompt_lens`` (B,) -> (last-real-token logits
    (B, vocab) f32, new_pages).  Dense causal attention over the
    padded length (causality makes the padding rows invisible to every
    real row), with each layer's roped K and raw V scattered into the
    paged cache so decode starts from a populated history.
    ``block_tables`` is (B, T // block_tokens)."""
    import jax
    import jax.numpy as jnp

    compute = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    positions = jnp.arange(t)
    pos2 = jnp.broadcast_to(positions[None, :], (b, t))
    valid = pos2 < prompt_lens[:, None]
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    mask = jnp.broadcast_to(causal[None], (b, t, t))
    with jax.named_scope("embed"):
        h = params["embed"].astype(compute)[tokens]
    new_pages = dict(pages)
    shape = (b, t, cfg.n_heads, cfg.head_dim)
    for i in range(cfg.n_layers):
        p = "blk%d." % i
        with jax.named_scope("layer%02d" % i):
            q, k, v = _qkv(h, params[p + "attn_norm"], params[p + "wqkv"],
                           shape, positions, cfg)
            for nm, val in (("k%d" % i, k), ("v%d" % i, v)):
                new_pages[nm] = _scatter_tokens(
                    new_pages[nm], val, block_tables, pos2, block_tokens,
                    valid=valid)
            with jax.named_scope("attn"):
                o = _masked_attn(q, k, v, mask)
            h = h + _attn_out(o, params[p + "wo"], (b, t, cfg.d_model))
            h = h + _mlp(h, params[p + "mlp_norm"], params[p + "w1"],
                         params[p + "w2"], cfg)
    h = _final_norm(h, params, cfg)
    last = h[jnp.arange(b), jnp.clip(prompt_lens - 1, 0, t - 1)]
    return _logits(last, params, cfg, "bd,vd->bv"), new_pages


def apply_decode(params, tokens, positions, cfg: TransformerConfig, *,
                 pages, block_tables, block_tokens):
    """One decode tick: current tokens (B,) at cache cursors
    ``positions`` (B,) -> (next-token logits (B, vocab) f32,
    new_pages).  Per layer: rope q/k at the cursor, scatter k/v into
    the paged cache, THEN gather (B, W*bt) history through the block
    tables — the new token reads itself back through the cache — and
    attend under the inclusive length mask.  Inactive slots ride along
    with all-zero tables (every write lands in the garbage block) and
    their logits are sliced off by the engine."""
    import jax
    import jax.numpy as jnp

    compute = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    span = block_tables.shape[1] * int(block_tokens)
    pos2 = positions[:, None]
    mask = (jnp.arange(span)[None, :] <= positions[:, None])[:, None, :]
    mask = jnp.broadcast_to(mask, (b, 1, span))
    with jax.named_scope("embed"):
        h = params["embed"].astype(compute)[tokens][:, None, :]
    new_pages = dict(pages)
    shape = (b, 1, cfg.n_heads, cfg.head_dim)
    for i in range(cfg.n_layers):
        p = "blk%d." % i
        with jax.named_scope("layer%02d" % i):
            q, k, v = _qkv(h, params[p + "attn_norm"], params[p + "wqkv"],
                           shape, pos2, cfg)
            for nm, val in (("k%d" % i, k), ("v%d" % i, v)):
                new_pages[nm] = _scatter_tokens(
                    new_pages[nm], val, block_tables, pos2, block_tokens)
            kc, vc = gather_kv(new_pages, block_tables, i)
            with jax.named_scope("attn"):
                o = _masked_attn(q, kc, vc, mask)
            h = h + _attn_out(o, params[p + "wo"], (b, 1, cfg.d_model))
            h = h + _mlp(h, params[p + "mlp_norm"], params[p + "w1"],
                         params[p + "w2"], cfg)
    h = _final_norm(h, params, cfg)
    return _logits(h[:, 0], params, cfg, "bd,vd->bv"), new_pages
