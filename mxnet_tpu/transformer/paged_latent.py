"""Decode attention of the LATENT mixer in its absorbed form, read
through the block tables where the rows lie.

``absorbed_decode`` is the one entry: one new token a rider, its
queries in the latent's space ``q_abs`` (B, H, width), over the layer's
pool of cached rows ``[c | k_r]`` (N, block_tokens, width) -> ``u`` (B,
H, rank), float32: the sum of the latents ``c`` weighted by the
softmax of ``q_abs . row x sm_scale`` over the rider's rows ``0 ..
position`` inclusive, the probabilities rounded to the rows' dtype
(``blocks.absorbed_attention``, which is also what it computes where
the kernel does not run).

Where the step is LOWERED FOR A TPU (``lax.platform_dependent``) and
the pool is one the kernel takes (``_takes``) it is one Pallas kernel,
a grid step a rider: the rider's live blocks, and no others, are copied
from the pool in HBM a chunk of ``CHUNK`` at a time, the next chunk's
copies in flight while this one's are used, and folded into an online
softmax; an empty slot (length 0) copies nothing and gives zeros.  The
chip keeps a pool whose row is no multiple of its 128 lanes with a
block's tokens innermost, so the kernel reads the pool through that
order, ``(N, width, block_tokens)``, which is the same bytes: no copy,
gather or transpose of the pool.  Everywhere else it is the gather of
whole blocks and ``blocks.absorbed_attention`` over them, the reference
formulation.  ``site_tally()`` counts the call sites traced each way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["absorbed_decode", "paged_latent_attention", "site_tally"]

LANES = 128
# blocks a chunk: what a rider's grid step copies at once, twice over
# (576 x 128 bf16 is 147 KB a block, so two chunks are 4.7 MB of VMEM);
# 4, 8 and 16 ran within a few per cent of each other (my chip runs,
# PR 39), 16 the fastest where every rider is full
CHUNK = 16
_MASKED = -1e30
# a multiple of the blocks a table holds for the kernel
_TABLE_COLS = 64
# call sites of absorbed_decode traced so far, by the lowering their
# operands allow
_sites = {"kernel": 0, "gather": 0}


def site_tally(since=None):
    """How many calls of ``absorbed_decode`` have been traced so far (or
    since an earlier tally): ``kernel`` sites run the paged kernel
    wherever the step is lowered for a TPU (and the gather where it is
    lowered for anything else), ``gather`` sites run the gather
    everywhere.  A block traced once and applied at every layer is one
    site.  ``GenerationRuntime.compile()`` takes the difference around
    the decode step's trace, for ``attn.decode_kernel_sites`` and
    ``attn.decode_gather_sites``."""
    return {how: n - (since[how] if since else 0)
            for how, n in _sites.items()}


def _takes(q_abs, pool) -> bool:
    """Whether the kernel takes these operands: a pool whose row is no
    multiple of the lanes and whose block is (the chip then keeps a
    block's tokens innermost, the order the kernel reads), queries of
    the pool's dtype, 32-bit indices (Mosaic lowers no 64-bit loop
    index; the tests run x64, so they gather)."""
    _, bt, width = pool.shape
    return (width % LANES != 0 and bt % LANES == 0
            and q_abs.dtype == pool.dtype
            and pool.dtype in (jnp.bfloat16, jnp.float32)
            and not jax.config.jax_enable_x64)


def absorbed_decode(q_abs, pool, block_tables, lengths, cfg):
    """``u`` (B, H, kv_lora_rank) float32 of one decode tick's latent
    attention: ``q_abs`` (B, H, width), the layer's ``pool`` (N,
    block_tokens, width) as the step holds it, ``block_tables`` (B, W)
    and ``lengths`` (B,), the rows each rider reads (``position + 1``, 0
    for an empty slot)."""
    takes = _takes(q_abs, pool)
    _sites["kernel" if takes else "gather"] += 1
    if not takes:
        return _gathered(q_abs, pool, block_tables, lengths, cfg=cfg)
    # tables padded with garbage blocks, never read: the plan cells of
    # one batch size share one trace
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, -block_tables.shape[1] % _TABLE_COLS)))
    return _dispatch(q_abs, pool, tables, lengths, cfg=cfg)


def _gathered(q_abs, pool, block_tables, lengths, *, cfg):
    """The reference formulation: whole blocks gathered through the
    tables, ``blocks.absorbed_attention`` under the length mask.  An
    empty slot reads its table's first row, the garbage block's (a
    softmax over no row would be NaN)."""
    from . import blocks as _blocks

    span = block_tables.shape[1] * pool.shape[1]
    mask = jnp.arange(span)[None, :] < jnp.maximum(lengths, 1)[:, None]
    return _blocks.absorbed_attention(q_abs, pool[block_tables], mask, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _dispatch(q_abs, pool, block_tables, lengths, cfg):
    """The kernel where the step is lowered for a TPU, the reference
    formulation elsewhere; under ``jax.jit``, so that the blocks of both
    kinds in a step, and the plan cells of one batch size, trace it
    once (set-up traces and lowers every plan cell on every start:
    ``lax.platform_dependent`` traced in each block of each cell added a
    fifth to the server's tracing and lowering, my CPU runs, PR 39)."""
    from . import blocks as _blocks

    return lax.platform_dependent(
        q_abs, pool, block_tables, lengths,
        tpu=functools.partial(paged_latent_attention, rank=cfg.kv_lora_rank,
                              sm_scale=_blocks.latent_sm_scale(cfg)),
        default=functools.partial(_gathered, cfg=cfg))


def paged_latent_attention(q_abs, pool, block_tables, lengths, *, rank,
                           sm_scale, chunk=CHUNK, interpret=False):
    """The kernel alone: ``u`` (B, H, rank) float32 for ``q_abs`` (B, H,
    width) over ``pool`` (N, block_tokens, width) through
    ``block_tables`` (B, W) int32 to ``lengths`` (B,) int32.

    A grid step a rider.  Its live blocks are copied a chunk at a time
    into one of two buffers while the other's chunk is folded in; the
    last chunk's step starts the next rider's first, so a rider waits
    for no copy of its own beginning.  A chunk is folded in as a whole:
    scores of all its blocks, one update of the running max and sum,
    the blocks' weighted sums (folding block by block ran at half the
    rate, my chip runs, PR 39).  Blocks past a rider's length are never
    copied; what their buffer holds is masked and weighted by 0, and is
    zeros or rows copied before, never a NaN.  ``interpret=True`` runs
    the Pallas interpreter (the tests).

    Set-up traces and lowers every plan cell of a server on every
    start, so the kernel is spelled in few operations (integer division
    as ``lax.div``, one call site a copy)."""
    b, heads, width = q_abs.shape
    _, bt, _ = pool.shape

    def kernel(lens_ref, tables_ref, q_ref, pool_ref, u_ref, buf, sems,
               m_ref, l_ref, acc_ref, state):
        def blocks_of(rider):
            return lax.div(lens_ref[rider] + bt - 1, bt)

        r = pl.program_id(0)
        length = lens_ref[r]
        chunks = lax.div(blocks_of(r) + chunk - 1, chunk)
        nxt = jnp.minimum(r + 1, b - 1)
        ahead = jnp.logical_and(r + 1 < b, lens_ref[nxt] > 0)

        @pl.when(r == 0)
        def _():
            buf[...] = jnp.zeros_like(buf)
            state[0] = 0        # the buffer rider r's first chunk takes
            state[1] = 0        # 1: the step before has started it

        def copies(rider, c, slot, act):
            # ``act`` on the copy of each of chunk ``c``'s live blocks
            # into buffer ``slot``
            live = blocks_of(rider) - c * chunk

            def one(j, _):
                act(pltpu.make_async_copy(
                    pool_ref.at[tables_ref[rider, c * chunk + j]],
                    buf.at[slot, j], sems.at[slot]))
                return 0

            lax.fori_loop(0, jnp.minimum(live, chunk), one, 0)

        def start(rider, c, slot):
            copies(rider, c, slot, lambda cp: cp.start())

        first = state[0]

        @pl.when(jnp.logical_and(chunks > 0, state[1] == 0))
        def _():
            start(r, 0, first)

        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = jnp.broadcast_to(q_ref[0], (chunk, heads, width))

        def body(c, _):
            slot = lax.rem(first + c, 2)
            more = c + 1 < chunks

            # this rider's next chunk, or after its last the next's first
            @pl.when(jnp.logical_or(more, ahead))
            def _():
                start(jnp.where(more, r, nxt), jnp.where(more, c + 1, 0),
                      1 - slot)

            copies(r, c, slot, lambda cp: cp.wait())
            fold(buf[slot], c * chunk * bt)
            return 0

        def fold(kts, at0):
            # kts (chunk, width, bt): each block's rows as columns
            s = lax.dot_general(q, kts, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
            at = at0 + bt * lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(at < length, s * sm_scale, _MASKED)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(jnp.max(s, axis=0), axis=1,
                                                keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = corr * l_ref[...] + jnp.sum(
                jnp.sum(p, axis=0), axis=1, keepdims=True)
            m_ref[...] = m_new
            sums = lax.dot_general(p.astype(kts.dtype), kts[:, :rank],
                                   (((2,), (2,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32)
            acc_ref[...] = corr[:, :1] * acc_ref[...] + jnp.sum(sums, axis=0)

        lax.fori_loop(0, chunks, body, 0)
        state[0] = lax.rem(first + chunks, 2)
        state[1] = jnp.logical_and(chunks > 0, ahead).astype(jnp.int32)
        l = l_ref[...][:, :1]
        u_ref[0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, heads, width), lambda r, *_: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, heads, rank), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, width, bt), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, LANES), jnp.float32),
            pltpu.VMEM((heads, rank), jnp.float32),
            pltpu.SMEM((2,), jnp.int32)])
    # the chip's own order of such a pool: a view, not a copy
    rows_t = jnp.swapaxes(pool, 1, 2)
    return pl.pallas_call(
        kernel, name="paged_latent_attention", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), q_abs,
      rows_t)
