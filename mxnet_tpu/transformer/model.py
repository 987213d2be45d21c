"""Decoder-only transformer LM — the long-context workload tier.

The reference (2017 MXNet) tops out at bucketed LSTMs for sequence
work (SURVEY.md §5 "Long-context"); this is the TPU-first superset the
rebuild is required to supply: a modern decoder-only LM (RMSNorm, RoPE,
tied embedding head) whose attention is PLUGGABLE between the
single-chip fused kernel and the two sequence-parallel formulations
that already exist in ``parallel/`` but had no end-to-end workload:

  * ``flash``   — parallel/attention.py online-softmax flash attention
                  (single chip / no sp axis): tiled kernels with a
                  backward of their own where the program is lowered
                  for a TPU and the shapes allow, the blockwise scan
                  everywhere else;
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

**What a block can spell** (``TransformerConfig``; the defaults are the
dense block above, and a configuration that leaves them alone traces
the same program as before they existed).  A layer is a MIXER and a
FEED-FORWARD, chosen as data:

  * mixer ``attn_kind``: ``mha`` (one fused ``wqkv``, full rotary) or
    ``latent`` (queries through a ``q_lora_rank`` bottleneck, or one
    ``wq`` where that is 0, keys and
    values out of one ``kv_lora_rank`` latent, ``qk_nope_head_dim`` +
    ``qk_rope_head_dim`` wide queries and keys over ``v_head_dim`` wide
    values, one rotary key shared by the heads, two inner RMSNorms,
    optional YaRN frequencies and softmax scale: ``rope_yarn``);
  * feed-forward per layer (``layer_kinds``): ``dense_ffn`` with
    ``ffn_act`` ``gelu_tanh`` or ``swiglu``, or ``experts`` (blocks.py:
    a float32 sigmoid router over all ``n_experts``, ``experts_per_token``
    a token by score + bias, the ``held_experts`` computed here without
    dropping an assignment, ``n_shared_experts`` shared);
  * ``hc_mult`` residual streams a token, mixed around every sublayer
    by three learned maps, the stream-to-stream one Sinkhorn-projected
    (``hc_mult`` 1 is the plain residual);
  * ``tied_head`` False gives the head a matrix of its own;
  * ``mtp_layers`` multi-token modules (training only) that share the
    embedding and the head and add ``mtp_loss_weight`` times their loss;
    ``labels`` then carries ``mtp_layers`` more columns.


:func:`apply` / :func:`loss_and_aux` spell all of these.  The
generation forwards, :func:`apply_prefill` and :func:`apply_decode`,
spell both mixers, both dense feed-forwards, the expert layer (under
the latent mixer) and either head over a paged cache whose rows the
mixer states (:func:`cache_rows`); they raise ``NotImplementedError``
for residual streams and multi-token modules, as do the ring and
ulysses attention impls for ``latent``.

Rematerialization is per-block and policy-selectable
(``MXNET_REMAT_POLICY`` = ``none`` | ``block`` | ``attention``,
remat.py): ``block`` keeps only block-boundary residuals (the classic
trade for deep stacks), ``attention`` rematerializes just the attention
sub-graph (the O(T) score recompute) and keeps the cheap MLP residuals.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

from .. import env as _env
from ..remat import checkpoint_scope, remat_policy

__all__ = [
    "TransformerConfig", "RopeYarn", "ATTENTION_IMPLS", "attention_impl",
    "make_attn_fn", "param_shapes", "init_params", "apply", "lm_loss",
    "loss_and_aux", "frozen_names",
    "dense_causal_attn", "gather_kv", "apply_prefill", "apply_decode",
    "cache_rows", "routed_shape", "routed_counts",
]

ATTENTION_IMPLS = ("flash", "ring", "ulysses")


class RopeYarn(NamedTuple):
    """YaRN rotary scaling, as a published ``rope_scaling`` gives it."""
    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


LAYER_KINDS = ("dense_ffn", "experts")


class TransformerConfig(NamedTuple):
    """Decoder-only LM dimensions + dtypes.  ``d_ff`` ``None`` means
    the conventional ``4*d_model``.  The fields after ``eps`` choose
    what a block is made of (module docstring); their defaults are the
    dense block."""
    vocab_size: int = 256
    n_layers: int = 2
    d_model: int = 64
    n_heads: int = 4
    d_ff: Optional[int] = None
    rope_base: float = 10000.0
    dtype: str = "float32"        # compute (activation) dtype
    param_dtype: str = "float32"  # parameter storage dtype
    eps: float = 1e-6
    attn_kind: str = "mha"               # | "latent"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[RopeYarn] = None
    ffn_act: str = "gelu_tanh"           # | "swiglu"
    tied_head: bool = True
    layer_kinds: Optional[Tuple[str, ...]] = None   # None: all dense_ffn
    n_experts: int = 0                   # the router's width
    experts_per_token: int = 0
    n_shared_experts: int = 0
    expert_ff: int = 0                   # one expert's width
    held_experts: Tuple[int, ...] = ()   # global ids computed here
    routed_scaling: float = 1.0
    router_bias_rate: float = 0.001
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The feed-forward kind of every layer."""
        kinds = self.layer_kinds if self.layer_kinds is not None \
            else ("dense_ffn",) * self.n_layers
        if len(kinds) != self.n_layers or \
                any(k not in LAYER_KINDS for k in kinds):
            raise ValueError("layer_kinds %r: one of %s for each of the %d "
                             "layers" % (kinds, LAYER_KINDS, self.n_layers))
        return tuple(kinds)

    @property
    def has_experts(self) -> bool:
        return "experts" in self.kinds or self.mtp_layers > 0

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
        from ..parallel.attention import flash_attention

        return functools.partial(flash_attention, causal=causal)
    if impl == "ring":
        from ..parallel.ring_attention import ring_attention

        return functools.partial(ring_attention, axis_name=sp_axis,
                                 causal=causal)
    if impl == "ulysses":
        from ..parallel.sequence import ulysses_attention

        return functools.partial(ulysses_attention, axis_name=sp_axis,
                                 causal=causal)
    raise ValueError(
        "attention impl %r cannot run sequence-sharded (sp axis %r); "
        "pick ring or ulysses" % (impl, sp_axis))


# ---------------------------------------------------------------------------
# parameters: flat dict, FORWARD (layer) order — the bucket partitioner's
# and the ZeRO-1 shard layout's input contract
# ---------------------------------------------------------------------------
def _mixer_shapes(cfg: TransformerConfig, p: str, dt: str):
    D = cfg.d_model
    if cfg.attn_kind == "mha":
        return [(p + "attn_norm", (D,), dt),
                (p + "wqkv", (D, 3 * D), dt),
                (p + "wo", (D, D), dt)]
    if cfg.attn_kind != "latent":
        raise ValueError("attn_kind %r: mha or latent" % (cfg.attn_kind,))
    H, qk = cfg.n_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    queries = [(p + "wq", (D, H * qk), dt)] if not cfg.q_lora_rank else [
        (p + "wq_a", (D, cfg.q_lora_rank), dt),
        (p + "q_norm", (cfg.q_lora_rank,), dt),
        (p + "wq_b", (cfg.q_lora_rank, H * qk), dt)]
    return [(p + "attn_norm", (D,), dt)] + queries + [
        (p + "wkv_a", (D, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt),
        (p + "kv_norm", (cfg.kv_lora_rank,), dt),
        (p + "wkv_b", (cfg.kv_lora_rank,
                       H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt),
        (p + "wo", (H * cfg.v_head_dim, D), dt)]


def _ffn_shapes(cfg: TransformerConfig, p: str, kind: str, dt: str):
    D, F = cfg.d_model, cfg.ff_dim
    out = [(p + "mlp_norm", (D,), dt)]
    if kind == "experts":
        G, Fe = len(cfg.held_experts), cfg.expert_ff
        Fs = cfg.n_shared_experts * Fe
        return out + [(p + "router", (D, cfg.n_experts), dt),
                      (p + "router_bias", (cfg.n_experts,), "float32"),
                      (p + "we_gate", (G, D, Fe), dt),
                      (p + "we_up", (G, D, Fe), dt),
                      (p + "we_down", (G, Fe, D), dt),
                      (p + "ws_gate", (D, Fs), dt),
                      (p + "ws_up", (D, Fs), dt),
                      (p + "ws_down", (Fs, D), dt)]
    if cfg.ffn_act == "gelu_tanh":
        return out + [(p + "w1", (D, F), dt), (p + "w2", (F, D), dt)]
    if cfg.ffn_act != "swiglu":
        raise ValueError("ffn_act %r: gelu_tanh or swiglu" % (cfg.ffn_act,))
    return out + [(p + "w_gate", (D, F), dt), (p + "w_up", (D, F), dt),
                  (p + "w_down", (F, D), dt)]


def _stream_shapes(cfg: TransformerConfig, p: str, which: str, dt: str):
    n = cfg.hc_mult
    if n == 1:
        return []
    q = "%shc_%s_" % (p, which)
    return [(q + "w", (n * cfg.d_model, 2 * n + n * n), dt),
            (q + "alpha", (3,), dt), (q + "b_pre", (n,), dt),
            (q + "b_post", (n,), dt), (q + "b_res", (n, n), dt)]


def _block_shapes(cfg: TransformerConfig, p: str, kind: str, dt: str):
    return (_stream_shapes(cfg, p, "attn", dt) + _mixer_shapes(cfg, p, dt)
            + _stream_shapes(cfg, p, "mlp", dt)
            + _ffn_shapes(cfg, p, kind, dt))


def param_shapes(cfg: TransformerConfig) -> List[Tuple[str, tuple, str]]:
    """``(name, shape, dtype)`` for every leaf of the state in layer
    order — shapes only, no arrays: what ``scaling.grad_entries`` /
    the autotuner's leaf-granularity timing model consume to tune the
    attention-dominated comm pattern without a compile.  Every leaf is
    trained but those :func:`frozen_names` lists."""
    D, V = cfg.d_model, cfg.vocab_size
    dt = cfg.param_dtype
    out = [("embed", (V, D), dt)]
    for i, kind in enumerate(cfg.kinds):
        out += _block_shapes(cfg, "blk%d." % i, kind, dt)
    out.append(("final_norm", (D,), dt))
    if not cfg.tied_head:
        out.append(("head", (V, D), dt))
    for j in range(cfg.mtp_layers):
        p = "mtp%d." % j
        out += [(p + "hnorm", (D,), dt), (p + "enorm", (D,), dt),
                (p + "eh_proj", (2 * D, D), dt)]
        out += _block_shapes(cfg, p, "experts", dt)
        out.append((p + "final_norm", (D,), dt))
    return out


def frozen_names(cfg: TransformerConfig) -> List[str]:
    """The leaves no gradient touches and the optimizer never sees: the
    routers' selection biases, which the train step moves by their own
    rule (``b += rate * sign(mean(count) - count)``)."""
    return [n for n, _, _ in param_shapes(cfg)
            if n.endswith("router_bias")]


_RESIDUAL_WRITERS = ("wo", "w2", "w_down", "we_down", "ws_down")


def init_params(key, cfg: TransformerConfig) -> Dict:
    """Initialize the flat param dict: N(0, 0.02) matrices (those that
    write into the residual stream scaled down by sqrt(2L) — the GPT-2
    convention), unit norms; zero selection biases; stream maps that
    start at the plain residual (``H_res`` near the identity, ``H_pre``
    1/n, ``H_post`` 1, small ``alpha``).  Deterministic per (key, cfg),
    and a leaf's values depend on its position alone."""
    import jax
    import jax.numpy as jnp

    if cfg.attn_kind == "mha" and cfg.d_model % cfg.n_heads:
        raise ValueError("d_model %d must divide by n_heads %d"
                         % (cfg.d_model, cfg.n_heads))
    resid_scale = (2.0 * max(cfg.n_layers, 1)) ** -0.5
    n = cfg.hc_mult
    params: Dict = {}
    for idx, (name, shape, dtype) in enumerate(param_shapes(cfg)):
        sub = jax.random.fold_in(key, idx)
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, dtype)
            continue
        if name.endswith(("router_bias", "b_post")):
            params[name] = jnp.zeros(shape, dtype)
            continue
        if name.endswith("alpha"):
            params[name] = jnp.full(shape, 0.01, dtype)
            continue
        if name.endswith("b_pre"):
            params[name] = jnp.full(shape, -jnp.log(n - 1.0), dtype)
            continue
        if name.endswith("b_res"):
            params[name] = (8.0 * jnp.eye(n)).astype(dtype)
            continue
        scale = 0.02
        if name.endswith(_RESIDUAL_WRITERS):
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


def _rope(x, positions, base, freqs=None):
    """Rotary position embedding over (B, T, H, Dh) with GLOBAL
    ``positions`` — (T,) shared across the batch (training / sequence
    sharding: each shard passes its own global offsets, so rotation
    angles are placement-invariant) or (B, T) per-sequence (decode:
    every slot sits at its OWN cache cursor).  The (T,) path is
    bit-for-bit the historical rotation.  ``freqs`` replaces the
    ``base**(-i/half)`` frequencies (YaRN); the pairing stays first
    half against second half."""
    import jax.numpy as jnp

    Dh = x.shape[-1]
    half = Dh // 2
    if freqs is None:
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


def _layer_params(params: Dict, prefix: str) -> Dict:
    """One layer's leaves under their short names."""
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _make_block(cfg: TransformerConfig, attn_fn, positions, policy, kind):
    """``block(h, lp) -> (h, aux)`` for one layer kind: a mixer and a
    feed-forward, each added to the residual (or, with ``hc_mult``
    streams, mixed into them); ``aux`` is what an expert layer counted
    (empty otherwise).  ``h`` is (B, T, D), or the n streams side by
    side, (B, T, n*D): between blocks they lie as ``hyper_residual``'s
    passes read them, so no other layout of them is ever written."""
    import jax

    from . import blocks as _blocks

    if cfg.attn_kind == "latent":
        sm_scale = _blocks.latent_sm_scale(cfg)

    def attn_part(h, lp):
        B, t = h.shape[:2]
        if cfg.attn_kind == "mha":
            shape = (B, t, cfg.n_heads, cfg.head_dim)
            q, k, v = _qkv(h, lp["attn_norm"], lp["wqkv"], shape,
                           positions, cfg)
            with jax.named_scope("attn"):
                o = attn_fn(q, k, v)
            return _attn_out(o, lp["wo"], (B, t, cfg.d_model))
        with jax.named_scope("norm"):
            a = _rmsnorm(h, lp["attn_norm"], cfg.eps)
        with jax.named_scope("attn_proj"):
            q, k, v = _blocks.latent_qkv(a, lp, positions, cfg, _rmsnorm,
                                         _rope)
        with jax.named_scope("attn"):
            o = attn_fn(q, k, v, sm_scale=sm_scale)
        return _attn_out(o, lp["wo"],
                         (B, t, cfg.n_heads * cfg.v_head_dim))

    def ffn_part(h, lp):
        return _ffn_part(h, lp, cfg, kind)

    attn_ck = checkpoint_scope(attn_part, policy, "attention")

    def block(h, lp):
        if cfg.hc_mult == 1:
            h = h + attn_ck(h, lp)
            y, aux = ffn_part(h, lp)
            return h + y, aux
        xs = h.reshape(h.shape[:2] + (cfg.hc_mult, cfg.d_model))
        xs, _ = _blocks.hyper_residual(
            xs, lp, "attn", cfg, lambda x: (attn_ck(x, lp), None))
        xs, aux = _blocks.hyper_residual(
            xs, lp, "mlp", cfg, lambda x: ffn_part(x, lp))
        return xs.reshape(h.shape), aux

    return checkpoint_scope(block, policy, "block")


def _to_streams(h, cfg: TransformerConfig):
    """The model's input copied into every residual stream."""
    import jax.numpy as jnp

    if cfg.hc_mult == 1:
        return h
    return jnp.tile(h, (1, 1, cfg.hc_mult))


def _from_streams(h, cfg: TransformerConfig):
    """The streams summed (float32 accumulation) into one output."""
    import jax.numpy as jnp

    if cfg.hc_mult == 1:
        return h
    streams = h.reshape(h.shape[:2] + (cfg.hc_mult, -1))
    return jnp.sum(streams.astype(jnp.float32), axis=2).astype(h.dtype)


def _trunk(params: Dict, tokens, cfg: TransformerConfig, *, attn_fn,
           pos_offset, policy):
    """Embedding and layers: the hidden state before the final norm
    (streams summed) and each expert layer's counts, in layer order."""
    import jax
    import jax.numpy as jnp

    compute = jnp.dtype(cfg.dtype)
    t = tokens.shape[1]
    positions = pos_offset + jnp.arange(t)
    embed = params["embed"]
    with jax.named_scope("embed"):
        h = _to_streams(embed.astype(compute)[tokens], cfg)
    blocks = {kind: _make_block(cfg, attn_fn, positions, policy, kind)
              for kind in set(cfg.kinds)}
    auxes = []
    for i, kind in enumerate(cfg.kinds):
        p = "blk%d." % i
        with jax.named_scope("layer%02d" % i):
            h, aux = blocks[kind](h, _layer_params(params, p))
        if aux:
            auxes.append(aux)
    with jax.named_scope("norm"):
        h = _from_streams(h, cfg)
    return h, auxes, positions


def apply(params: Dict, tokens, cfg: TransformerConfig, *,
          attn_fn, pos_offset=0, remat: Optional[str] = None):
    """Forward pass: ``tokens`` (B, T_local) int -> logits
    (B, T_local, vocab) float32.

    ``pos_offset`` is this shard's global position of token 0 (a traced
    scalar under shard_map: ``axis_index("sp") * T_local``); ``remat``
    overrides ``MXNET_REMAT_POLICY``.  Any block kind; the multi-token
    modules are :func:`loss_and_aux`'s."""
    h, _, _ = _trunk(params, tokens, cfg, attn_fn=attn_fn,
                     pos_offset=pos_offset, policy=remat_policy(remat))
    return _logits(_final_norm(h, params, cfg), params, cfg,
                   "btd,vd->btv")


def loss_and_aux(params: Dict, tokens, labels, cfg: TransformerConfig, *,
                 attn_fn, pos_offset=0, remat: Optional[str] = None):
    """The training loss and what the expert layers counted.

    ``tokens`` (B, T); ``labels`` (B, T + mtp_layers): column ``i`` is
    the token after ``tokens[:, i]``, so the first ``T`` are the
    next-token targets and multi-token module ``j`` embeds columns
    ``j..j+T`` and predicts columns ``j+1..j+1+T``.  The loss is
    ``lm_loss`` of the trunk plus ``mtp_loss_weight`` times each
    module's.  ``aux`` is empty without expert layers, else
    ``counts`` (L, n_experts) int32, ``choice`` (L, B*T, k) int32 and
    ``dropped`` (L,) int32 over the expert layers in order, the
    multi-token modules' last."""
    import jax
    import jax.numpy as jnp

    policy = remat_policy(remat)
    t = tokens.shape[1]
    if labels.shape[1] != t + cfg.mtp_layers:
        raise ValueError(
            "labels have %d columns; %d tokens and %d multi-token "
            "module(s) need %d" % (labels.shape[1], t, cfg.mtp_layers,
                                   t + cfg.mtp_layers))
    h, auxes, positions = _trunk(params, tokens, cfg, attn_fn=attn_fn,
                                 pos_offset=pos_offset, policy=policy)
    loss = lm_loss(_logits(_final_norm(h, params, cfg), params, cfg,
                           "btd,vd->btv"),
                   labels[:, :t] if cfg.mtp_layers else labels)
    if cfg.mtp_layers:
        compute = jnp.dtype(cfg.dtype)
        block = _make_block(cfg, attn_fn, positions, policy, "experts")
    for j in range(cfg.mtp_layers):
        p = "mtp%d." % j
        with jax.named_scope("mtp"):
            with jax.named_scope("embed"):
                e = params["embed"].astype(compute)[labels[:, j:j + t]]
            with jax.named_scope("norm"):
                both = jnp.concatenate(
                    [_rmsnorm(h, params[p + "hnorm"], cfg.eps),
                     _rmsnorm(e, params[p + "enorm"], cfg.eps)], -1)
            with jax.named_scope("embed"):
                x = _to_streams(
                    both @ params[p + "eh_proj"].astype(compute), cfg)
            # a layer of its own, numbered after the trunk's
            with jax.named_scope("layer%02d" % (cfg.n_layers + j)):
                x, aux = block(x, _layer_params(params, p))
            auxes.append(aux)
            with jax.named_scope("norm"):
                h = _from_streams(x, cfg)
                out = _rmsnorm(h, params[p + "final_norm"], cfg.eps)
            loss = loss + cfg.mtp_loss_weight * lm_loss(
                _logits(out, params, cfg, "btd,vd->btv"),
                labels[:, j + 1:j + 1 + t])
    # over the expert layers; no expert layer, nothing counted
    return loss, {k: jnp.stack([a[k] for a in auxes])
                  for k in (auxes[0] if auxes else ())}


# The scope vocabulary of the three forwards (HLO metadata only; what a
# device trace's operations are classed by): ``embed``, ``norm``,
# ``attn_proj`` (the qkv and output matmuls, rotary; all of the latent
# projections and their inner norms), ``attn`` (the attention core
# alone), ``mlp`` (a dense feed-forward; inside it an expert layer's
# ``moe_route``: scores, top-k, sort, gather, combine and the bias
# rule, ``moe_expert``: the grouped products, ``moe_shared``),
# ``head_loss`` (logits here, the loss in the train step), inside one
# ``layer%02d`` a layer; ``mhc`` inside a layer is the stream maps,
# Sinkhorn and mixing; ``mtp`` wraps a whole multi-token module.
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


def _ffn_part(h, lp, cfg, kind):
    """A layer's feed-forward over ``h`` with its own norm -> ``(y,
    aux)``; ``aux`` is what an expert layer counted, empty otherwise."""
    import jax

    from . import blocks as _blocks

    if kind == "dense_ffn" and cfg.ffn_act == "gelu_tanh":
        return _mlp(h, lp["mlp_norm"], lp["w1"], lp["w2"], cfg), {}
    with jax.named_scope("norm"):
        m = _rmsnorm(h, lp["mlp_norm"], cfg.eps)
    with jax.named_scope("mlp"):
        if kind == "experts":
            return _blocks.expert_ffn(m, lp, cfg)
        return _blocks.gated_ffn(m, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), {}


def _final_norm(h, params, cfg):
    import jax

    with jax.named_scope("norm"):
        return _rmsnorm(h, params["final_norm"], cfg.eps)


def _logits(h, params, cfg, einsum):
    """The head (the embedding, where tied); logits accumulate in f32 (f64 under the control
    methodology) regardless of the bf16 compute dtype."""
    import jax
    import jax.numpy as jnp

    acc = jnp.promote_types(jnp.dtype(cfg.dtype), jnp.float32)
    head = params["embed"] if cfg.tied_head else params["head"]
    with jax.named_scope("head_loss"):
        return jnp.einsum(einsum, h.astype(acc), head.astype(acc))


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
# The cache is a per-layer pool of fixed-size token blocks, a row of it
# what the layer's mixer keeps of one token (``cache_rows``): ``mha``
# keeps the roped K and the raw V of every head, ``{"k<i>"|"v<i>":
# (num_blocks, block_tokens, H, Dh)}``; ``latent`` keeps the normed
# latent and the one roped key, ``{"c<i>": (num_blocks, block_tokens,
# kv_lora_rank + qk_rope_head_dim)}``, prefills in the expanded form
# through ``flash_attention`` and decodes in the ABSORBED form (the
# keys and values of a cached token are never made:
# ``blocks.absorbed_attention``).  A per-sequence block table addresses
# it (serving/kvcache.py owns allocation; block 0 is the GARBAGE block
# — every write from a padded position or an inactive slot is routed
# there, so the compiled step never branches on liveness).  Scatter
# runs BEFORE gather inside the decode step, so the new token attends
# to itself through the same cache path as its history — one code path,
# pinned by the greedy-equality tests.
#
# A configuration with expert layers also carries ``pages["routed"]``
# (``routed_shape``): what its steps routed, added up in place on the
# device by every step and read by the host when it asks
# (``GenerationRuntime.routing_counters``), never inside a tick.
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


def _write_rows(pool, x, block_tables, pos, block_tokens):
    """One row a sequence, ``x`` (B, 1, width) at positions ``pos``
    (B, 1), written into a pool of rows ``(N, block_tokens, width)``
    WHERE IT LIES.  The chip keeps such a pool with the tokens of a
    block innermost (a width of 576 is no multiple of its 128 lanes): a
    scatter wants the rows innermost, and would copy the whole pool
    into that order and back around every write; an update of one slice
    a sequence takes the pool as it is.  An empty slot's table is all
    garbage block, so its row lands there."""
    import jax.numpy as jnp
    from jax import lax

    bt = int(block_tokens)
    blk = jnp.take_along_axis(block_tables, pos // bt, axis=1)[:, 0]
    off = (pos % bt)[:, 0]
    x = x.astype(pool.dtype)

    def write(i, pool):
        zero = jnp.zeros((), blk.dtype)
        return lax.dynamic_update_slice(
            pool, lax.dynamic_slice_in_dim(x, i, 1), (blk[i], off[i], zero))

    return lax.fori_loop(0, x.shape[0], write, pool)


def _write_blocks(pool, x, block_tables, block_tokens):
    """A prompt's rows ``x`` (B, T, width), T a multiple of the block,
    written into the pool a whole block at a time, where it lies
    (:func:`_write_rows`).  A padded prompt's rows behind its length
    land in its last block, where no query reads them before decode has
    written over them (a position is read only once the cursor has
    passed it), or, a whole block of them, in the garbage block that
    its table names there."""
    import jax.numpy as jnp
    from jax import lax

    b, t = x.shape[:2]
    bt = int(block_tokens)
    x = x.astype(pool.dtype).reshape((b * (t // bt), 1, bt) + x.shape[2:])
    blocks = block_tables.reshape(-1)

    def write(i, pool):
        zero = jnp.zeros((), blocks.dtype)
        return lax.dynamic_update_slice(pool, x[i], (blocks[i], zero, zero))

    return lax.fori_loop(0, x.shape[0], write, pool)


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


def cache_rows(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """``{pool: shape of one token's row}`` for every pool of the paged
    cache, in layer order: what ``serving.PagedKVCache`` is sized
    from."""
    if cfg.attn_kind == "latent":
        row = (cfg.kv_lora_rank + cfg.qk_rope_head_dim,)
        return {"c%d" % i: row for i in range(cfg.n_layers)}
    row = (cfg.n_heads, cfg.head_dim)
    return {"%s%d" % (kv, i): row for i in range(cfg.n_layers)
            for kv in "kv"}


def routed_shape(cfg: TransformerConfig) -> Optional[Tuple[int, int]]:
    """The shape of ``pages["routed"]`` (int32), or None without expert
    layers: a row an expert layer, in layer order, of ``n_experts``
    assignments of prompt tokens to each expert of all, ``n_experts``
    of decoded tokens, then the held experts a decode tick reached with
    at least one assignment (summed over the ticks), the decode ticks,
    and the assignments to a held expert that were not computed (0).
    Padded positions and empty slots are not counted."""
    layers = sum(k == "experts" for k in cfg.kinds)
    return (layers, 2 * cfg.n_experts + 3) if layers else None


def _routed_row(aux: Dict, counted, cfg: TransformerConfig, decode: bool):
    """One step's row of ``routed`` for one expert layer: ``aux`` from
    ``expert_ffn``, ``counted`` (N,) the tokens that are real."""
    import jax.numpy as jnp
    import numpy as np

    e = cfg.n_experts
    chosen = (aux["choice"][..., None] == jnp.arange(e)) \
        & counted[:, None, None]
    counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    zeros = jnp.zeros((e,), jnp.int32)
    if not decode:
        tail = jnp.stack([0, 0, aux["dropped"]]).astype(jnp.int32)
        return jnp.concatenate([counts, zeros, tail])
    reached = jnp.sum(counts[np.asarray(cfg.held_experts)] > 0)
    tail = jnp.stack([reached, 1, aux["dropped"]]).astype(jnp.int32)
    return jnp.concatenate([zeros, counts, tail])


def routed_counts(delta, cfg: TransformerConfig) -> Dict:
    """What a difference ``delta`` (int64, ``routed_shape``) of two
    readings of ``routed`` says was routed between them, in
    :func:`_routed_row`'s layout.

    ``assignments_total`` and ``assignments_here`` (of prompt and
    decoded tokens, to every expert and to those held here),
    ``prefill_assignments_total`` / ``_here`` and
    ``decode_assignments_total`` / ``_here``, ``dropped`` (0: nothing
    is dropped), ``decode_ticks``, ``experts_reached`` (held experts
    with at least one assignment, a layer, the mean over decode ticks),
    ``experts_reached_sum`` (the same summed over ticks and layers:
    what the ticks had to read of the experts' matrices),
    ``load_max_over_mean`` (the busiest held expert's assignments over
    the mean held expert's, the mean over layers) and ``counts``
    (layers, n_experts)."""
    import numpy as np

    e, held = cfg.n_experts, list(cfg.held_experts)
    prefill, decode, tail = delta[:, :e], delta[:, e:2 * e], delta[:, 2 * e:]
    counts = prefill + decode
    here = counts[:, held]
    ticks, reached = int(tail[0, 1]), int(tail[:, 0].sum())
    return {
        "assignments_total": int(counts.sum()),
        "assignments_here": int(here.sum()),
        "prefill_assignments_total": int(prefill.sum()),
        "prefill_assignments_here": int(prefill[:, held].sum()),
        "decode_assignments_total": int(decode.sum()),
        "decode_assignments_here": int(decode[:, held].sum()),
        "dropped": int(tail[:, 2].sum()),
        "decode_ticks": ticks,
        "experts_reached": reached / max(ticks * len(delta), 1),
        "experts_reached_sum": reached,
        "load_max_over_mean": float(np.mean(
            here.max(axis=-1) / np.maximum(here.mean(axis=-1), 1e-30))),
        "counts": counts,
    }


def _generates(cfg: TransformerConfig) -> None:
    if cfg.hc_mult != 1 or cfg.mtp_layers:
        raise NotImplementedError(
            "generation spells one residual stream and no multi-token "
            "module (hc_mult %d, mtp_layers %d)"
            % (cfg.hc_mult, cfg.mtp_layers))
    if cfg.attn_kind == "mha" and "experts" in cfg.kinds:
        raise NotImplementedError(
            "generation spells the expert layer under the latent mixer "
            "only")


def _prefill_layer(h, k_pool, v_pool, w, block_tables, pos2, valid, mask,
                   *, cfg, block_tokens):
    """One block of :func:`apply_prefill`: ``(h, k_pool, v_pool)`` in,
    the same out, the prompt's roped K and raw V scattered into the
    layer's two pools."""
    import jax

    b, t = pos2.shape
    q, k, v = _qkv(h, w["attn_norm"], w["wqkv"],
                   (b, t, cfg.n_heads, cfg.head_dim), pos2[0], cfg)
    k_pool = _scatter_tokens(k_pool, k, block_tables, pos2, block_tokens,
                             valid=valid)
    v_pool = _scatter_tokens(v_pool, v, block_tables, pos2, block_tokens,
                             valid=valid)
    with jax.named_scope("attn"):
        o = _masked_attn(q, k, v, mask)
    h = h + _attn_out(o, w["wo"], (b, t, cfg.d_model))
    h = h + _ffn_part(h, w, cfg, "dense_ffn")[0]
    return h, k_pool, v_pool


def _decode_layer(h, k_pool, v_pool, w, block_tables, pos2, mask, *, cfg,
                  block_tokens):
    """One block of :func:`apply_decode`: rope q/k at the cursor,
    scatter k/v into the layer's pools, THEN gather the history
    through the block tables and attend under the length mask."""
    import jax

    b = pos2.shape[0]
    q, k, v = _qkv(h, w["attn_norm"], w["wqkv"],
                   (b, 1, cfg.n_heads, cfg.head_dim), pos2, cfg)
    k_pool = _scatter_tokens(k_pool, k, block_tables, pos2, block_tokens)
    v_pool = _scatter_tokens(v_pool, v, block_tables, pos2, block_tokens)
    kc, vc = gather_kv({"k0": k_pool, "v0": v_pool}, block_tables, 0)
    with jax.named_scope("attn"):
        o = _masked_attn(q, kc, vc, mask)
    h = h + _attn_out(o, w["wo"], (b, 1, cfg.d_model))
    h = h + _ffn_part(h, w, cfg, "dense_ffn")[0]
    return h, k_pool, v_pool


def _latent_prefill_layer(h, pool, w, block_tables, pos2, valid, *, cfg,
                          block_tokens, kind):
    """One latent block of :func:`apply_prefill`: the prompt's cache
    rows ``[c | k_r]`` scattered into the layer's pool, attention in
    the expanded form through ``flash_attention`` (no (T, T) scores),
    then the layer's feed-forward; an expert layer also returns its row
    of ``routed``."""
    import jax

    from ..parallel.attention import flash_attention
    from . import blocks as _blocks

    b, t = pos2.shape
    with jax.named_scope("norm"):
        a = _rmsnorm(h, w["attn_norm"], cfg.eps)
    with jax.named_scope("attn_proj"):
        q, k, v, row = _blocks.latent_qkv_row(a, w, pos2[0], cfg, _rmsnorm,
                                              _rope)
    pool = _write_blocks(pool, row, block_tables, block_tokens)
    with jax.named_scope("attn"):
        o = flash_attention(q, k, v, causal=True,
                            sm_scale=_blocks.latent_sm_scale(cfg))
    h = h + _attn_out(o, w["wo"], (b, t, cfg.n_heads * cfg.v_head_dim))
    y, aux = _ffn_part(h, w, cfg, kind)
    if kind != "experts":
        return h + y, pool
    return h + y, pool, _routed_row(aux, valid.reshape(-1), cfg, False)


def _latent_decode_layer(h, pool, w, block_tables, pos2, lengths, *, cfg,
                         block_tokens, kind):
    """One latent block of :func:`apply_decode`: the cursor's cache row
    scattered into the layer's pool, THEN the history's rows read
    through the block tables and attended in the absorbed form to each
    rider's length (``paged_latent.absorbed_decode``: the paged kernel
    on a TPU, the gather of whole blocks under the mask elsewhere); then
    the layer's feed-forward."""
    import jax

    from . import blocks as _blocks
    from . import paged_latent as _paged

    b = pos2.shape[0]
    with jax.named_scope("norm"):
        a = _rmsnorm(h, w["attn_norm"], cfg.eps)
    with jax.named_scope("attn_proj"):
        q_abs, row = _blocks.latent_absorbed_query(a, w, pos2, cfg, _rmsnorm,
                                                   _rope)
    pool = _write_rows(pool, row, block_tables, pos2, block_tokens)
    with jax.named_scope("attn"):
        u = _paged.absorbed_decode(q_abs, pool, block_tables, lengths, cfg)
    with jax.named_scope("attn_proj"):
        o = _blocks.absorbed_values(u, w, cfg, h.dtype)
    h = h + _attn_out(o, w["wo"], (b, 1, cfg.n_heads * cfg.v_head_dim))
    y, aux = _ffn_part(h, w, cfg, kind)
    if kind != "experts":
        return h + y, pool
    return h + y, pool, _routed_row(aux, lengths > 0, cfg, True)


@functools.lru_cache(maxsize=None)
def _traced_once(layer, statics):
    """``layer`` under ``jax.jit``: the blocks of one kind in a
    generation forward have one operand signature, so the kind's Python
    runs once a traced step and the step's module holds it once, called
    at every such layer (XLA inlines the calls).  Unrolled, tracing and
    lowering 24 blocks anew for each of a server's 30 plan cells was
    most of its set-up."""
    import jax

    return jax.jit(layer, static_argnames=statics)


def _through_layers(dense, latent, h, params, pages, cfg, block_tokens,
                    *operands):
    """``h`` through every block (``dense`` or ``latent``, by the
    configuration's mixer), each layer's pools replaced by what its
    block returns and the expert layers' rows added to ``routed``;
    -> (h, new_pages)."""
    import jax
    import jax.numpy as jnp

    statics = {"cfg": cfg, "block_tokens": int(block_tokens)}
    if cfg.attn_kind == "latent":
        layer = _traced_once(latent, ("cfg", "block_tokens", "kind"))
    else:
        layer = _traced_once(dense, ("cfg", "block_tokens"))
    new_pages = dict(pages)
    pools, rows = list(cache_rows(cfg)), []
    per = len(pools) // cfg.n_layers
    with jax.named_scope("layers"):
        for i, kind in enumerate(cfg.kinds):
            mine = pools[i * per:(i + 1) * per]
            if cfg.attn_kind == "latent":
                statics["kind"] = kind
            h, *state = layer(
                h, *(pages[k] for k in mine),
                _layer_params(params, "blk%d." % i), *operands, **statics)
            new_pages.update(zip(mine, state))
            rows += state[per:]
    if rows:
        new_pages["routed"] = pages["routed"] + jnp.stack(rows)
    return h, new_pages


def apply_prefill(params, tokens, prompt_lens, cfg: TransformerConfig,
                  *, pages, block_tables, block_tokens):
    """Prefill forward: right-padded prompts ``tokens`` (B, T) with
    real lengths ``prompt_lens`` (B,) -> (last-real-token logits
    (B, vocab) f32, new_pages).  Dense causal attention over the
    padded length (causality makes the padding rows invisible to every
    real row), with each layer's cache rows (``cache_rows``: roped K
    and raw V, or the latent and its roped key) scattered into the
    paged cache so decode starts from a populated history.  The latent
    mixer attends through ``flash_attention``, the dense block through
    the explicit mask.  ``block_tables`` is (B, T // block_tokens)."""
    import jax
    import jax.numpy as jnp

    _generates(cfg)
    compute = jnp.dtype(cfg.dtype)
    b, t = tokens.shape
    pos2 = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    valid = pos2 < prompt_lens[:, None]
    operands = (block_tables, pos2, valid)
    if cfg.attn_kind == "mha":
        causal = jnp.tril(jnp.ones((t, t), dtype=bool))
        operands += (jnp.broadcast_to(causal[None], (b, t, t)),)
    with jax.named_scope("embed"):
        h = params["embed"].astype(compute)[tokens]
    h, new_pages = _through_layers(
        _prefill_layer, _latent_prefill_layer, h, params, pages, cfg,
        block_tokens, *operands)
    h = _final_norm(h, params, cfg)
    last = h[jnp.arange(b), jnp.clip(prompt_lens - 1, 0, t - 1)]
    return _logits(last, params, cfg, "bd,vd->bv"), new_pages


def apply_decode(params, tokens, positions, cfg: TransformerConfig, *,
                 pages, block_tables, block_tokens):
    """One decode tick: current tokens (B,) at cache cursors
    ``positions`` (B,) -> (next-token logits (B, vocab) f32,
    new_pages).  Per layer: rope q/k at the cursor, scatter the token's
    cache rows into the paged cache, THEN gather (B, W*bt) history
    through the block tables — the new token reads itself back through
    the cache — and attend under the inclusive length mask (the latent
    mixer in the absorbed form).  Inactive slots ride along
    with all-zero tables (every write lands in the garbage block) and
    their logits are sliced off by the engine."""
    import jax
    import jax.numpy as jnp

    _generates(cfg)
    compute = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    span = block_tables.shape[1] * int(block_tokens)
    pos2 = positions[:, None]
    if cfg.attn_kind == "mha":
        mask = jnp.arange(span)[None, :] <= positions[:, None]
        operands = (jnp.broadcast_to(mask[:, None, :], (b, 1, span)),)
    else:
        # the rows each rider reads: a live rider's table begins with a
        # block of its own, an empty slot's is all garbage block
        operands = (jnp.where(block_tables[:, 0] != 0, positions + 1, 0),)
    with jax.named_scope("embed"):
        h = params["embed"].astype(compute)[tokens][:, None, :]
    h, new_pages = _through_layers(
        _decode_layer, _latent_decode_layer, h, params, pages, cfg,
        block_tokens, block_tables, pos2, *operands)
    h = _final_norm(h, params, cfg)
    return _logits(h[:, 0], params, cfg, "bd,vd->bv"), new_pages
