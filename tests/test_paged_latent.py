"""The paged decode kernel of the latent mixer (PR 39,
``transformer/paged_latent.py``) through the Pallas interpreter on the
CPU, against the reference formulation: whole blocks gathered through
the tables and ``blocks.absorbed_attention`` under the length mask.

Ragged lengths that end mid-block and on a block's last row, the new
token's row read back at its position, empty slots whose tables are all
garbage block (zeros, never NaN), tables longer than the live span, and
spans of 1 to 64 blocks at the published row width.  Then the choice
between the kernel and the gather, and what ``site_tally`` counts."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.transformer import blocks
from mxnet_tpu.transformer import paged_latent as PL


def _cfg(rank, rope):
    return types.SimpleNamespace(kv_lora_rank=rank, qk_nope_head_dim=2 * rope,
                                 qk_rope_head_dim=rope, rope_yarn=None)


def _case(lengths, width, bt, heads=4, dtype="float32", spare=1, seed=0):
    """A pool, queries and tables for riders of ``lengths`` (0: an empty
    slot, its table all garbage block), each table ``spare`` blocks
    longer than the longest rider's span, its blocks drawn at random."""
    rng = np.random.default_rng(seed)
    spans = [-(-n // bt) for n in lengths]
    w = max(spans) + spare
    n_pool = 1 + sum(spans) + 3
    pool = rng.normal(size=(n_pool, bt, width)).astype(np.float32)
    q = (rng.normal(size=(len(lengths), heads, width)) * 0.3).astype(
        np.float32)
    tables = np.zeros((len(lengths), w), np.int32)
    free = list(rng.permutation(np.arange(1, n_pool)))
    for i, span in enumerate(spans):
        tables[i, :span] = [free.pop() for _ in range(span)]
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))


def _reference(q, pool, tables, lengths, cfg):
    span = tables.shape[1] * pool.shape[1]
    mask = jnp.arange(span)[None, :] < lengths[:, None]
    u = blocks.absorbed_attention(q, pool[tables], mask, cfg)
    # an empty slot's softmax has nothing to sum: the kernel gives zeros
    return np.where(np.asarray(lengths)[:, None, None] > 0, np.asarray(u),
                    0.0)


def _kernel(q, pool, tables, lengths, cfg, chunk=PL.CHUNK):
    with jax.enable_x64(False):
        return np.asarray(PL.paged_latent_attention(
            q, pool, tables, lengths, rank=cfg.kv_lora_rank,
            sm_scale=blocks.latent_sm_scale(cfg), chunk=chunk,
            interpret=True))


# float32: the online softmax against the whole row's, to round-off;
# bfloat16: the probabilities are rounded before the sum, unnormalised
# in the kernel and normalised in the reference
TOLERANCE = {"float32": 2e-6, "bfloat16": 4e-3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [1, 2, 16])
def test_ragged_riders_and_empty_slots_are_the_gathers(dtype, chunk):
    # mid-block, a block's last row, one row, an empty slot between,
    # the longest crossing a chunk of two; every table a block longer
    # than its rider's span, or all garbage block
    cfg = _cfg(rank=32, rope=8)
    lengths = [11, 16, 1, 0, 37, 8]
    q, pool, tables, lens = _case(lengths, width=40, bt=8, dtype=dtype)
    got = _kernel(q, pool, tables, lens, cfg, chunk=chunk)
    want = _reference(q, pool, tables, lens, cfg)
    assert got.shape == (len(lengths), 4, 32) and got.dtype == np.float32
    assert np.isfinite(got).all()
    assert not got[3].any()
    np.testing.assert_allclose(got, want, atol=TOLERANCE[dtype], rtol=0)


@pytest.mark.parametrize("spans", [(1, 33, 64), (64, 2)])
def test_spans_of_1_to_64_blocks_at_the_published_width(spans):
    # rows of 512 + 64 in blocks of 128 tokens, as sarvam-105b's cell
    cfg = _cfg(rank=512, rope=64)
    lengths = [128 * s - 5 * i for i, s in enumerate(spans)]
    q, pool, tables, lens = _case(lengths, width=576, bt=128, heads=8,
                                  dtype="bfloat16", spare=0, seed=1)
    got = _kernel(q, pool, tables, lens, cfg)
    want = _reference(q, pool, tables, lens, cfg)
    np.testing.assert_allclose(got, want, atol=TOLERANCE["bfloat16"],
                               rtol=0)


def test_the_new_token_reads_itself_back_at_its_position():
    """The row at ``position`` (length - 1) is read, the row after it is
    not: a row aligned with the queries dominates the sum where it lies
    at the position, and is ignored one row further on."""
    cfg = _cfg(rank=32, rope=8)
    lengths = [13, 13]
    q, pool, tables, lens = _case(lengths, width=40, bt=8, heads=1)
    q = q.at[:].set(1.0)
    pool = np.array(pool)
    # rider 0: the loud row at its position; rider 1: just past it
    for rider, at in ((0, 12), (1, 13)):
        blk = int(tables[rider, at // 8])
        pool[blk, at % 8] = 4.0
    got = _kernel(q, jnp.asarray(pool), tables, lens, cfg)
    np.testing.assert_allclose(got[0, 0], 4.0, atol=1e-3)
    assert np.abs(got[1, 0] - 4.0).mean() > 2.0
    np.testing.assert_allclose(
        got, _reference(q, jnp.asarray(pool), tables, lens, cfg),
        atol=TOLERANCE["float32"], rtol=0)


def test_absorbed_decode_chooses_by_the_lowering_and_the_pool():
    """Under x64 (the tests') every site gathers; at 32 bits a pool of
    blocks of 128 rows 576 wide is a kernel site, which a step lowered
    for the CPU runs as the gather (over its padded tables).  Rows that
    are a multiple of the lanes are kept in another order by the chip:
    the gather."""
    cfg = _cfg(rank=512, rope=64)
    q, pool, tables, lens = _case([130, 0], width=576, bt=128,
                                  dtype="bfloat16", heads=2)
    want = _reference(q, pool, tables, lens, cfg)[0]
    before = PL.site_tally()
    got = np.asarray(PL.absorbed_decode(q, pool, tables, lens, cfg))
    assert PL.site_tally(before) == {"kernel": 0, "gather": 1}
    # the empty slot reads the garbage block's first row: no NaN
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], want)
    with jax.enable_x64(False):
        before = PL.site_tally()
        got = np.asarray(jax.jit(lambda *a: PL.absorbed_decode(*a, cfg))(
            q, pool, tables, lens))
        assert PL.site_tally(before) == {"kernel": 1, "gather": 0}
        # over the tables padded for the kernel: the same rows summed
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got[0], want, atol=1e-6, rtol=1e-5)
        before = PL.site_tally()
        PL.absorbed_decode(q[..., :512], pool[..., :512], tables, lens,
                           _cfg(rank=448, rope=64))
        assert PL.site_tally(before) == {"kernel": 0, "gather": 1}
