"""Fused attention — the long-context compute primitive.

The reference (2017-era MXNet) predates attention; its long-sequence tools
were bucketing + fused cuDNN RNN (SURVEY.md §5 "Long-context").  The TPU
rebuild makes attention first-class because it is what modern long-context
workloads shard (ring attention / Ulysses in parallel/ring_attention.py and
parallel/sequence.py build on this file).

One algorithm (online-softmax flash attention), two lowerings, one entry:

  * ``flash_attention`` takes any shape on any backend.  Where the program
    is LOWERED FOR A TPU (``lax.platform_dependent``: a compile for a
    described chip counts, the process's default backend does not) and
    the operands allow (``_kernel_tile``: both sequences a multiple of a
    128-wide tile and equal when causal, head widths of 64 to 256), it
    runs as tiled kernels under one custom VJP: the forward keeps ``o``
    and the log-sum-exp beside ``q``, ``k``, ``v`` and nothing else, the
    backward recomputes the probabilities tile by tile, and key tiles
    wholly above the causal diagonal are never visited.  Everywhere else
    it is a ``lax.scan`` over key blocks, differentiable by the scan's
    native VJP (each block rematerialised by ``jax.checkpoint``, the
    carries of every step kept).  ``site_tally()`` counts the call sites
    traced each way.
  * ``pallas_flash_attention`` is the kernel lowering alone: the kernels
    jax ships (``pallas.ops.tpu.splash_attention``), differentiable.  A
    caller that asks for the kernel gets the kernel or an error: off-TPU
    it runs only with an explicit ``interpret=True``, and shapes its
    tiles do not take raise.

Layout: (batch, seq, heads, head_dim) — "BTHD" — matching the ring/Ulysses
sharding over the seq axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["attention_reference", "flash_attention", "pallas_flash_attention",
           "site_tally"]

_NEG_INF = -1e30
# the kernels' tiles, queries and keys alike: the largest that divides both
# sequences is the one a shape runs at (one choice a shape, nothing tuned
# at set-up)
_TILES = (1024, 512, 256, 128)
# call sites of flash_attention traced so far, by the lowering their
# shapes allow
_sites = {"kernel": 0, "scan": 0}


def attention_reference(q, k, v, causal=False, sm_scale=None):
    """Materialised-scores attention; the numerics oracle for every other
    implementation (O(T^2) memory — tests only)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Tq, Tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), k=Tk - Tq)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _online_block(q, k_blk, v_blk, m, l, o, mask=None, sm_scale=1.0):
    """One online-softmax accumulation step.

    q (B,Tq,H,D); k_blk/v_blk (B,Tb,H,D); m,l (B,H,Tq); o (B,Tq,H,D) f32.
    ``mask`` broadcastable to (B,H,Tq,Tb), True = attend.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - (-inf)) → exp(0); correct via l
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    return m_new, l_new, o_new


def _finalize(m, l, o, dtype):
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_size"))
def _scan_attention(q, k, v, causal=False, sm_scale=None, block_size=512):
    """Blockwise online-softmax attention via lax.scan over KV blocks: the
    lowering every backend and every shape takes.

    Memory is O(T·D + block) instead of O(T²); the scan compiles to one
    fused XLA while-loop.  Equivalent to attention_reference to fp32
    round-off (tested).  ``v`` may be of another width than ``q`` and
    ``k`` (latent attention: 192-wide keys over 128-wide values); the
    accumulator and the output take ``v``'s.
    """
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    blk = min(block_size, Tk)
    n_blocks = -(-Tk // blk)
    pad = n_blocks * blk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k_blocks = k.reshape(B, n_blocks, blk, H, D).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(B, n_blocks, blk, H, Dv).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(Tq) + (Tk - Tq)  # align causal diagonal when Tq<Tk

    # derive carries from q so their device-variance matches the scanned
    # inputs under shard_map manual axes (jax's scan-vma rule)
    zero_bhq = (q.sum(axis=3) * 0.0).transpose(0, 2, 1).astype(jnp.float32)
    m0 = zero_bhq + _NEG_INF
    l0 = zero_bhq
    o0 = (q * 0.0).astype(jnp.float32) if Dv == D else jnp.broadcast_to(
        zero_bhq.transpose(0, 2, 1)[..., None], (B, Tq, H, Dv))

    def step(carry, blk_in):
        m, l, o = carry
        k_blk, v_blk, blk_idx = blk_in
        kv_pos = blk_idx * blk + jnp.arange(blk)
        mask = kv_pos[None, :] < Tk  # padding mask (1, blk)
        if causal:
            mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        mask = mask[None, None]  # (1,1,Tq|1,blk)
        m, l, o = _online_block(q, k_blk, v_blk, m, l, o, mask=mask,
                                sm_scale=sm_scale)
        return (m, l, o), None

    (m, l, o), _ = lax.scan(
        jax.checkpoint(step), (m0, l0, o0),
        (k_blocks, v_blocks, jnp.arange(n_blocks)))
    return _finalize(m, l, o, q.dtype)


def site_tally(since=None):
    """How many calls of ``flash_attention`` have been traced so far (or
    since an earlier tally), by what their operands allow: ``kernel``
    sites run the tiled kernels wherever the program is lowered for a TPU
    (and the scan where it is lowered for anything else), ``scan`` sites
    run the scan everywhere.  A block traced once and applied at every
    layer is one site.  ``TransformerTrainStep`` takes the difference
    around its trace, for ``attn.kernel_sites`` and ``attn.scan_sites``."""
    return {how: n - (since[how] if since else 0)
            for how, n in _sites.items()}


def _kernel_tile(q, k, v, causal):
    """The tile the kernels run these operands at, or None where they do
    not take them; every condition is one a compile for the chip, or a
    trace, refused."""
    Tq, Tk = q.shape[1], k.shape[1]
    takes = (
        (Tq == Tk or not causal)
        # 64, 128, 192, 256: wider heads overflow VMEM at the largest tile
        and all(w % 64 == 0 and w <= 256 for w in (q.shape[-1], v.shape[-1]))
        and q.dtype == k.dtype == v.dtype
        and q.dtype in (jnp.bfloat16, jnp.float32)
        # Mosaic does not lower the kernels' loop indices at 64 bits (the
        # chip runs jax's default; the tests run x64, so they scan)
        and not jax.config.jax_enable_x64
        # the kernels' outputs declare no variance over the axes of a
        # shard_map whose check_vma is on
        and not any(getattr(jax.typeof(x), "vma", None) for x in (q, k, v)))
    if not takes:
        return None
    return next((t for t in _TILES if Tq % t == 0 and Tk % t == 0), None)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_size=512):
    """Online-softmax attention over (B, T, H, D) activations, never
    materialising the (T, T) scores.  Equivalent to attention_reference to
    fp32 round-off (tested).  ``v`` may be of another width than ``q`` and
    ``k`` (latent attention: 192-wide keys over 128-wide values); the
    output takes ``v``'s.

    Lowered for a TPU at shapes the tiles take (``_kernel_tile``) it is
    the kernels of ``pallas_flash_attention``; otherwise the scan over
    key blocks of ``block_size``.  The choice is made from what the
    program can observe, the lowering platform and the shapes, and by
    nothing else."""
    tile = _kernel_tile(q, k, v, causal)
    _sites["scan" if tile is None else "kernel"] += 1
    if tile is None:
        return _scan_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_size=block_size)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _dispatch(q, k, v, sm_scale, causal=causal, block_size=block_size,
                     tile=tile)


@functools.partial(jax.jit, static_argnames=("causal", "block_size", "tile"))
def _dispatch(q, k, v, sm_scale, causal, block_size, tile):
    return lax.platform_dependent(
        q, k, v, sm_scale,
        tpu=functools.partial(_kernel_attention, causal=causal,
                              block_q=tile, block_k=tile),
        default=lambda q, k, v, sm_scale: _scan_attention(
            q, k, v, causal=causal, sm_scale=sm_scale,
            block_size=block_size))


# ---------------------------------------------------------------------------
# the tiled kernels (jax's splash attention), forward and backward
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _splash_kernel(H, Tq, Tk, causal, block_q, block_k, interpret):
    """One head-batched kernel object a shape: the block-level mask tables
    (which key tiles a query tile visits) are made once, as constants."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    mask = (masks.CausalMask if causal else masks.FullMask)((Tq, Tk))
    # one backward kernel gives dK, dV and dQ from one recomputation of
    # the probabilities
    sizes = splash.BlockSizes(
        block_q=block_q, block_kv=block_k, block_kv_compute=block_k,
        block_q_dkv=block_q, block_kv_dkv=block_k,
        block_kv_dkv_compute=block_k, use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(
            masks.MultiHeadMask([mask] * H), block_sizes=sizes,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def _kernel_attention(q, k, v, sm_scale, *, causal, block_q, block_k,
                      interpret=False):
    """BTHD in, BTHD out, through kernels that take (H, T, D) a row of the
    batch and no scale: the scale goes onto ``q``, multiplied in float32
    and rounded once to ``q``'s dtype (the scores themselves stay float32
    from the product to the softmax; the scan rounds them to bf16)."""
    kernel = _splash_kernel(q.shape[2], q.shape[1], k.shape[1], bool(causal),
                            block_q, block_k, bool(interpret))
    q = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
    out = jax.vmap(kernel)(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)


def pallas_flash_attention(q, k, v, causal=False, sm_scale=None,
                           block_q=256, block_k=256, interpret=False):
    """Tiled flash attention, lowered by Mosaic on a TPU backend, with a
    backward of its own (the flash backward from ``o`` and the
    log-sum-exp; masked tiles skipped in both directions).

    There is no silent fallback: on any other backend the kernels run
    only under ``interpret=True`` (the Pallas interpreter — tests), and
    blocks that are not a multiple of 128 or do not divide the
    sequences, or a causal call with ``Tq != Tk``, raise ``ValueError``.
    ``flash_attention`` is the entry that takes any shape on any backend
    and comes here when it can."""
    Tq, Tk = q.shape[1], k.shape[1]
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    platform = jax.devices()[0].platform
    if platform != "tpu" and not interpret:
        raise RuntimeError(
            "pallas_flash_attention needs the Mosaic TPU compiler and "
            "this backend is %r; pass interpret=True to run the Pallas "
            "interpreter, or call flash_attention" % platform)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k or block_q % 128 or block_k % 128:
        raise ValueError(
            "pallas_flash_attention: blocks (%d, %d) do not divide the "
            "sequences (Tq=%d, Tk=%d) in multiples of 128"
            % (block_q, block_k, Tq, Tk))
    if causal and Tq != Tk:
        raise ValueError(
            "pallas_flash_attention: causal needs Tq == Tk, got %d and "
            "%d" % (Tq, Tk))
    return _kernel_attention(q, k, v, sm_scale, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
