"""Ring attention — context parallelism over an ICI mesh axis.

Shards the sequence across devices on a mesh axis ("sp"); each device owns
Q/K/V for its sequence slice and K/V blocks rotate around the ring with
``lax.ppermute`` while every device accumulates online-softmax partial
results for its resident Q block.  Communication rides the ICI neighbour
links (the ppermute ring) and overlaps with the per-step attention matmul —
XLA schedules the collective-permute concurrently with compute.

The reference has no counterpart (2017 code; SURVEY.md §2.3 "NOT present"
row) — this is the TPU-first superset the rebuild is required to supply for
long-context scale.  Design follows the blockwise-parallel / ring-attention
formulation (Liu et al.) on top of parallel/attention.py's online-softmax
blocks.

Causality note: with the sequence laid out contiguously (device i owns
positions [i·t, (i+1)·t)), at rotation step s device i holds the KV block
of device (i - s) mod n, so whole steps are either fully visible, fully
masked, or diagonal — the mask is computed per step from global positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .attention import _NEG_INF, _finalize, _online_block

__all__ = ["ring_attention", "ring_attention_sharded"]


def ring_attention(q, k, v, axis_name="sp", causal=False, sm_scale=None):
    """Per-shard body: q/k/v are this device's (B, T/n, H, D) slices; must
    run inside shard_map/pjit over a mesh with ``axis_name``.

    Returns this device's (B, T/n, H, D) output slice.
    """
    B, t, H, D = q.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    n = lax.psum(1, axis_name)  # axis size (lax.axis_size needs jax>=0.6)
    my_idx = lax.axis_index(axis_name)

    q_pos = my_idx * t + jnp.arange(t)  # global positions of resident Q

    m = jnp.full((B, H, t), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, t), jnp.float32)
    o = jnp.zeros((B, t, H, D), jnp.float32)

    # rotate kv i→i+1 each step; after s steps device i holds block (i-s)%n
    perm = [(i, (i + 1) % n) for i in range(n)]

    # rematerialise each step's (B,H,t,t) scores in backward instead of
    # retaining n of them — without this the unrolled ring keeps O(n·t²)
    # residuals and OOMs in exactly the long-context regime it serves
    @jax.checkpoint
    def accumulate(q, k_cur, v_cur, m, l, o, src):
        kv_pos = src * t + jnp.arange(t)
        if causal:
            mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
            mask = jnp.broadcast_to(mask, (1, 1, t, t))
        else:
            mask = None
        return _online_block(q, k_cur, v_cur, m, l, o, mask=mask,
                             sm_scale=sm_scale)

    def step(s, carry):
        m, l, o, k_cur, v_cur = carry
        src = (my_idx - s) % n
        m, l, o = accumulate(q, k_cur, v_cur, m, l, o, src)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, l, o, k_nxt, v_nxt

    carry = (m, l, o, k, v)
    # python loop: n is static (mesh axis size) → n unrolled steps whose
    # ppermute overlaps the next step's matmul in the XLA schedule
    for s in range(n):
        carry = step(s, carry)
    m, l, o, _, _ = carry
    return _finalize(m, l, o, q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           sm_scale=None):
    """Global-view convenience: q/k/v are full (B, T, H, D) arrays; returns
    the full output, computed ring-parallel over ``mesh[axis_name]``."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
