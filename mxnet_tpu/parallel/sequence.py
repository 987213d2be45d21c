"""Ulysses-style sequence parallelism — all-to-all head/sequence resharding.

DeepSpeed-Ulysses formulation: activations arrive sharded over the sequence
axis; an ``all_to_all`` reshards them over the *heads* axis so each device
runs full-sequence attention for H/n heads, then a second all_to_all
restores sequence sharding.  Two all-to-alls replace the ring's n-1
permutes — better when n is small relative to head count, and the local
attention can use the fused single-chip kernel (parallel/attention.py).

No reference counterpart (SURVEY.md §2.3 "NOT present") — TPU-first
superset.  The all_to_all lowers to an XLA AllToAll over ICI.
"""
from __future__ import annotations

import functools

import jax
from jax import lax

from .attention import flash_attention

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name="sp", causal=False, sm_scale=None,
                      attn_fn=None):
    """Per-shard body (run under shard_map): q/k/v (B, T/n, H, D) sequence
    shards; heads H must divide by the axis size.

    all_to_all #1: (B, T/n, H, D) → (B, T, H/n, D)   [gather seq, split heads]
    local attention over the full sequence for H/n heads
    all_to_all #2: (B, T, H/n, D) → (B, T/n, H, D)   [restore]
    """
    n = lax.psum(1, axis_name)  # axis size (lax.axis_size needs jax>=0.6)
    H = q.shape[2]
    assert H % n == 0, "num heads %d must divide sp axis size %d" % (H, n)
    if attn_fn is None:
        attn_fn = functools.partial(flash_attention, causal=causal,
                                    sm_scale=sm_scale)

    def seq_to_heads(x):
        # split axis 2 (heads) across devices, concat axis 1 (seq)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    q_full = seq_to_heads(q)
    k_full = seq_to_heads(k)
    v_full = seq_to_heads(v)
    out = attn_fn(q_full, k_full, v_full)
    return heads_to_seq(out)


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                              sm_scale=None):
    """Global-view convenience over full (B, T, H, D) arrays."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, sm_scale=sm_scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
