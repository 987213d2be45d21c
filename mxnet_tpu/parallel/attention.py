"""Fused attention — the long-context compute primitive.

The reference (2017-era MXNet) predates attention; its long-sequence tools
were bucketing + fused cuDNN RNN (SURVEY.md §5 "Long-context").  The TPU
rebuild makes attention first-class because it is what modern long-context
workloads shard (ring attention / Ulysses in parallel/ring_attention.py and
parallel/sequence.py build on this file).

Two implementations, one contract:

  * ``flash_attention`` — blockwise online-softmax attention expressed with
    ``lax.scan`` over KV blocks.  O(T) memory, compiles to a fused XLA loop
    on any backend, differentiable via scan's native VJP (rematerialised by
    ``jax.checkpoint`` per block).
  * ``pallas_flash_attention`` — hand-tiled Pallas TPU kernel for the
    single-chip hot path (MXU-sized q/k tiles in VMEM, f32 accumulators).
    A caller that asks for the kernel gets the kernel or an error: off-TPU
    it runs only with an explicit ``interpret=True``, and shapes its
    blocks do not divide raise.

Layout: (batch, seq, heads, head_dim) — "BTHD" — matching the ring/Ulysses
sharding over the seq axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["attention_reference", "flash_attention", "pallas_flash_attention"]

_NEG_INF = -1e30


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
def flash_attention(q, k, v, causal=False, sm_scale=None, block_size=512):
    """Blockwise online-softmax attention via lax.scan over KV blocks.

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


# ---------------------------------------------------------------------------
# Pallas TPU kernel (single chip hot path)
# ---------------------------------------------------------------------------
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal, sm_scale, block_k):
    """Grid: (batch*heads, q_blocks, k_blocks).  Blocks live in VMEM;
    f32 running max / denom / accumulator in scratch."""
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (block_q, d)
    k = k_ref[0]  # (block_k, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale

    if causal:
        qb = pl.program_id(1)
        q_idx = qb * q.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_idx = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_idx >= k_idx, s, _NEG_INF)

    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:] = l_ref[:] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new

    @pl.when(kb == nk - 1)
    def _done():
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def pallas_flash_attention(q, k, v, causal=False, sm_scale=None,
                           block_q=256, block_k=256, interpret=False):
    """Tiled Pallas flash attention, lowered by Mosaic on a TPU backend.

    There is no silent fallback: on any other backend the kernel runs
    only under ``interpret=True`` (the Pallas interpreter — tests), and
    a sequence the blocks do not divide, or a causal call with
    ``Tq != Tk``, raises ``ValueError``.  ``flash_attention`` is the
    formulation that takes any shape on any backend."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    platform = jax.devices()[0].platform
    if platform != "tpu" and not interpret:
        raise RuntimeError(
            "pallas_flash_attention needs the Mosaic TPU compiler and "
            "this backend is %r; pass interpret=True to run the Pallas "
            "interpreter, or call flash_attention" % platform)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        raise ValueError(
            "pallas_flash_attention: blocks (%d, %d) do not divide the "
            "sequences (Tq=%d, Tk=%d)" % (block_q, block_k, Tq, Tk))
    if causal and Tq != Tk:
        raise ValueError(
            "pallas_flash_attention: causal needs Tq == Tk, got %d and "
            "%d" % (Tq, Tk))

    # fold batch & heads into the grid's first axis; blocks are 2-D (T, D)
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)

    grid = (B * H, Tq // block_q, Tk // block_k)
    kernel = functools.partial(_flash_kernel, causal=causal,
                               sm_scale=sm_scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=bool(interpret),
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
