"""The generation forwards over a LATENT paged cache (PR 38): a block
with latent attention without a query bottleneck, a dense SwiGLU layer
then expert layers of which a share is held, and an untied head, at a
toy size on the CPU against the plain reference
(``perfbench/reference/sarvam.py``).  Logits, not tokens: with seeded
weights the largest logit changes on rounding.

(a) prefill then decode through ``PagedKVCache`` agrees with the
reference's full forward; (b) absorbed decode attention against the
expanded form on the same rows; (c) the parts that two shares of the
experts give add up to the uncut layer, the shared expert counted once;
(d) ``latent_qkv`` without a bottleneck against the reference, with one
bit for bit what it gave before; (e) the dense block's steps are what
they were; (f) the allocator over a latent row shape; (g) greedy decode
through the paged kernel (interpreted) is the gather's; (h) the compiled
steps hand back int32 ids, the first maximum of the forwards' logits.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import profiler, serving
from mxnet_tpu.parallel.attention import attention_reference
from mxnet_tpu.serving.kvcache import CacheExhausted, PagedKVCache
from mxnet_tpu.transformer import (TransformerConfig, blocks, init_params,
                                   param_shapes)
from mxnet_tpu.transformer import model as M
from mxnet_tpu.transformer import paged_latent
from perfbench import weights
from perfbench.drivers import serve_sarvam
from perfbench.reference import sarvam as ref

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "perfbench", "toy_sarvam", "configs",
                       "sarvam_toy.json")) as f:
    TOY = json.load(f)
SEED = 3000000011


def lm_config(cfg=None, **over):
    cfg = TOY if cfg is None else cfg
    """The program's configuration as the benchmark's driver makes it."""
    drv = serve_sarvam.Driver({}, dict(cfg, **{k: v for k, v in over.items()
                                                if k in cfg}), SEED, None,
                              None)
    return drv._lm_config()._replace(**{k: v for k, v in over.items()
                                        if k not in cfg})


def test_the_toy_is_the_block_of_the_cell():
    lm = lm_config()
    assert (lm.attn_kind, lm.q_lora_rank, lm.ffn_act, lm.tied_head) \
        == ("latent", 0, "swiglu", False)
    assert lm.kinds == ("dense_ffn", "experts", "experts")
    assert (lm.n_heads, lm.qk_nope_head_dim, lm.qk_rope_head_dim,
            lm.v_head_dim, lm.kv_lora_rank) == (2, 16, 8, 16, 32)
    assert (lm.n_experts, lm.held_experts, lm.experts_per_token,
            lm.n_shared_experts) == (8, (0, 1, 2, 3), 2, 1)
    # the reference lists the leaves in the program's own order
    assert [(n, tuple(s)) for n, s, _ in ref.leaves(TOY)] \
        == [(n, tuple(s)) for n, s, _ in param_shapes(lm)]
    assert M.cache_rows(lm) == {"c0": (40,), "c1": (40,), "c2": (40,)}
    assert M.routed_shape(lm) == (2, 2 * 8 + 3)


# -- (a) prefill then decode through the paged cache -------------------
LENGTHS = (3, 8, 13)            # ragged; 8 fills a block of 8 exactly
STEPS = 7                       # every rider crosses a block edge of 4


def _served_logits(dtype, block):
    """Teacher-forced: each rider's prompt prefilled alone, then
    ``STEPS`` decode ticks of all riders together at their own cursors;
    -> the ids, the logits of every position from a prompt's last on,
    the reference's, and the cache."""
    lm = lm_config(dtype=dtype, param_dtype=dtype)
    specs = ref.leaves(TOY)
    params = weights.make_all(SEED, specs, dtype)
    p32 = weights.make_all(SEED, specs, "float32")
    rng = np.random.default_rng(5)
    ids = [rng.integers(1, TOY["vocab_size"], size=n + STEPS,
                        dtype=np.int32) for n in LENGTHS]
    span = -(-(max(LENGTHS) + STEPS) // block) * block
    kv = PagedKVCache(rows=M.cache_rows(lm), num_blocks=40,
                      block_tokens=block, dtype=dtype,
                      counters={"routed": M.routed_shape(lm)})
    got = [[] for _ in LENGTHS]
    for i, n in enumerate(LENGTHS):
        kv.alloc("s%d" % i, n)
        width = -(-n // block)
        tokens = np.zeros((1, width * block), np.int32)
        tokens[0, :n] = ids[i][:n]
        logits, kv.pages = M.apply_prefill(
            params, tokens, np.asarray([n], np.int32), lm, pages=kv.pages,
            block_tables=kv.block_table("s%d" % i, width)[None],
            block_tokens=block)
        got[i].append(np.asarray(logits[0]))
    for step in range(STEPS - 1):
        for i, n in enumerate(LENGTHS):
            kv.extend("s%d" % i, n + step + 1)
        # a fourth slot rides along empty: all-garbage table
        tables = np.stack([kv.block_table("s%d" % i, span // block)
                           for i in range(len(LENGTHS))]
                          + [np.zeros(span // block, np.int32)])
        tokens = np.asarray([ids[i][n + step]
                             for i, n in enumerate(LENGTHS)] + [0], np.int32)
        positions = np.asarray([n + step for n in LENGTHS] + [0], np.int32)
        logits, kv.pages = M.apply_decode(
            params, tokens, positions, lm, pages=kv.pages,
            block_tables=tables, block_tokens=block)
        for i in range(len(LENGTHS)):
            got[i].append(np.asarray(logits[i]))
    want = []
    with jax.default_matmul_precision("highest"):
        for i, n in enumerate(LENGTHS):
            full = np.asarray(ref.forward(p32, ids[i], TOY))
            want.append(full[n - 1:n - 1 + STEPS])
    return ids, [np.stack(g) for g in got], want, kv, lm


# float32: every logit within 1e-4.  bfloat16: 8 bits of mantissa on the
# operands of some 25 products in sequence and on the cached rows,
# against float32 throughout, at logits of mean magnitude 1.35 (init
# 0.3): the median logit within 0.04 (it reads 0.022, 1.6 %) and the
# worst within 0.8 (it reads 0.46, where an assignment flips between two
# experts whose scores lie within bfloat16 of each other)
TOLERANCE = {"float32": (1e-4, 1e-4), "bfloat16": (0.04, 0.8)}


@pytest.mark.parametrize("block", [4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_agrees_with_the_references_forward(dtype,
                                                                block):
    _, got, want, kv, lm = _served_logits(dtype, block)
    for g, w in zip(got, want):
        assert g.shape == w.shape == (STEPS, TOY["vocab_size"])
        assert g.dtype == np.float32
        median, worst = TOLERANCE[dtype]
        assert np.median(np.abs(g - w)) <= median
        np.testing.assert_allclose(g, w, atol=worst, rtol=0)
    if dtype == "float32":
        # and so the tokens, where the reference's margin allows
        for g, w in zip(got, want):
            top = np.sort(w, axis=-1)
            clear = top[:, -1] - top[:, -2] > 1e-3
            assert (g.argmax(-1) == w.argmax(-1))[clear].all()
    # what the steps routed, counted on the device: padding and the
    # empty slot are not in it
    routed = np.asarray(kv.pages["routed"])
    e, k = lm.n_experts, lm.experts_per_token
    assert routed[:, :e].sum(axis=1).tolist() == [sum(LENGTHS) * k] * 2
    assert routed[:, e:2 * e].sum(axis=1).tolist() \
        == [len(LENGTHS) * (STEPS - 1) * k] * 2
    assert routed[:, 2 * e + 1].tolist() == [STEPS - 1] * 2    # ticks
    assert routed[:, 2 * e + 2].tolist() == [0, 0]             # dropped
    assert (routed[:, 2 * e] <= len(lm.held_experts) * (STEPS - 1)).all()
    assert (routed[:, 2 * e] >= 1).all()


# -- (b) absorbed against expanded on the same rows --------------------
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 3e-2)])
def test_absorbed_decode_attention_is_the_expanded_forms(dtype, tol):
    lm = lm_config(dtype=dtype, param_dtype=dtype)
    lp = {k[len("blk1."):]: v for k, v in weights.make_all(
        SEED, ref.leaves(TOY), dtype).items() if k.startswith("blk1.")}
    t = 11
    a = jax.random.normal(jax.random.PRNGKey(2), (2, t, lm.d_model),
                          jnp.float32).astype(dtype)
    pos = jnp.arange(t)
    q, k, v, row = blocks.latent_qkv_row(a, lp, pos, lm, M._rmsnorm, M._rope)
    # the last token of each sequence, against its whole history
    want = attention_reference(q[:, -1:], k, v, causal=False,
                               sm_scale=blocks.latent_sm_scale(lm))
    q_abs, new_row = blocks.latent_absorbed_query(
        a[:, -1:], lp, jnp.full((2, 1), t - 1), lm, M._rmsnorm, M._rope)
    np.testing.assert_array_equal(np.asarray(new_row, np.float32),
                                  np.asarray(row[:, -1:], np.float32))
    assert row.shape == (2, t, lm.kv_lora_rank + lm.qk_rope_head_dim)
    # five columns of padding behind the history, masked off; the rows
    # go in as blocks of 8, as the pool holds them
    rows = jnp.pad(row, ((0, 0), (0, 5), (0, 0)), constant_values=7.0)
    mask = jnp.broadcast_to(jnp.arange(t + 5) < t, (2, t + 5))
    u = blocks.absorbed_attention(q_abs, rows.reshape(2, 2, 8, -1), mask, lm)
    got = blocks.absorbed_values(u, lp, lm, q.dtype)
    assert got.shape == want.shape == (2, 1, lm.n_heads, lm.v_head_dim)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=0)


# -- (c) the shares tie to the model -----------------------------------
def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Experts 0-3 here, 4-7 on the chip beside: the two parts, with
    the shared expert (which both compute alike) counted once, are the
    reference's layer with every expert held."""
    whole = dict(TOY, held_experts=list(range(8)))
    p = weights.make_all(SEED, ref.leaves(whole), "float32")
    pre = "blk1."
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 19, TOY["hidden_size"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, choice = ref.experts(p, pre, m[0], whole)
        shared = ref._gated(m[0], p[pre + "ws_gate"], p[pre + "ws_up"],
                            p[pre + "ws_down"], None)
        parts = []
        for share in ((0, 1, 2, 3), (4, 5, 6, 7)):
            lm = lm_config(held_experts=share)
            lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
            for name in ("we_gate", "we_up", "we_down"):
                lp[name] = lp[name][np.asarray(share)]
            y, aux = blocks.expert_ffn(m, lp, lm)
            assert int(aux["dropped"]) == 0
            np.testing.assert_array_equal(np.sort(aux["choice"], -1),
                                          np.sort(choice, -1))
            parts.append(np.asarray(y[0]))
            # a share alone is the reference given that share
            alone, _ = ref.experts(p_share(p, pre, share), pre, m[0],
                                   dict(TOY, held_experts=list(share)))
            np.testing.assert_allclose(parts[-1], alone, atol=1e-5, rtol=0)
    np.testing.assert_allclose(parts[0] + parts[1] - np.asarray(shared),
                               np.asarray(want), atol=1e-5, rtol=0)
    # every token chose experts of both shares somewhere: neither part
    # is the whole
    assert np.abs(parts[0] - np.asarray(want)).max() > 1e-3


def p_share(p, pre, share):
    out = dict(p)
    for name in ("we_gate", "we_up", "we_down"):
        out[pre + name] = p[pre + name][np.asarray(share)]
    return out


# -- (d) latent_qkv with and without the bottleneck --------------------
def test_latent_qkv_without_a_bottleneck_against_the_reference():
    lm = lm_config()
    p = weights.make_all(SEED, ref.leaves(TOY), "float32")
    pre = "blk0."
    lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
    assert "wq" in lp and "wq_a" not in lp and "q_norm" not in lp
    a = jax.random.normal(jax.random.PRNGKey(6), (1, 9, lm.d_model),
                          jnp.float32)
    q, k, v = blocks.latent_qkv(a, lp, jnp.arange(9), lm, M._rmsnorm,
                                M._rope)
    assert q.shape == k.shape == (1, 9, 2, 24) and v.shape == (1, 9, 2, 16)
    o = attention_reference(q, k, v, causal=True,
                            sm_scale=blocks.latent_sm_scale(lm))
    got = o.reshape(9, -1) @ lp["wo"]
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, pre, a[0], TOY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def _latent_qkv_before(a, lp, positions, cfg, rmsnorm, rope):
    """``blocks.latent_qkv`` as it read before PR 38, word for word."""
    b, t, _ = a.shape
    h, nope, rp, dv = (cfg.n_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    dt = a.dtype
    cq = rmsnorm(a @ lp["wq_a"].astype(dt), lp["q_norm"], cfg.eps)
    q = (cq @ lp["wq_b"].astype(dt)).reshape(b, t, h, nope + rp)
    kva = a @ lp["wkv_a"].astype(dt)
    ckv = rmsnorm(kva[..., :r], lp["kv_norm"], cfg.eps)
    kv = (ckv @ lp["wkv_b"].astype(dt)).reshape(b, t, h, nope + dv)
    freqs = None if cfg.rope_yarn is None else \
        blocks.yarn_inv_freq(rp, cfg.rope_base, cfg.rope_yarn)
    q_r = rope(q[..., nope:], positions, cfg.rope_base, freqs)
    k_r = rope(kva[..., r:].reshape(b, t, 1, rp), positions,
               cfg.rope_base, freqs)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rp))], axis=-1)
    return q, k, kv[..., nope:]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_qkv_with_a_bottleneck_is_bit_for_bit_what_it_was(dtype):
    lm = lm_config(q_lora_rank=12, dtype=dtype, param_dtype=dtype)
    params = init_params(jax.random.PRNGKey(1), lm)
    lp = M._layer_params(params, "blk0.")
    assert "wq_a" in lp and "wq" not in lp
    a = jax.random.normal(jax.random.PRNGKey(3), (2, 10, lm.d_model),
                          jnp.float32).astype(dtype)
    for fn in (lambda f: f, jax.jit):
        got = fn(lambda a, lp: blocks.latent_qkv(
            a, lp, jnp.arange(10), lm, M._rmsnorm, M._rope))(a, lp)
        want = fn(lambda a, lp: _latent_qkv_before(
            a, lp, jnp.arange(10), lm, M._rmsnorm, M._rope))(a, lp)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


# -- (e) the dense block's steps are what they were --------------------
def test_the_dense_blocks_pools_and_donation_are_unchanged():
    grt = serving.demo_generation_runtime(
        "gen_dense_38", n_layers=2, slots=2, block_tokens=8, max_prompt=8,
        max_context=16, max_new=4, prefill_batch=1)
    assert M.cache_rows(grt.cfg) == {
        "k0": (2, 16), "v0": (2, 16), "k1": (2, 16), "v1": (2, 16)}
    assert M.routed_shape(grt.cfg) is None
    assert set(grt.kv.pages) == set(grt.kv.pools) == {"k0", "v0", "k1", "v1"}
    assert grt.kv.pages["k1"].shape == (2 * 2 + 1, 8, 2, 16)
    assert grt.routing_counters() is None
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        grt.compile(warmup=True)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    assert stamped["kv.pools_donated"]["max"] == stamped["kv.pools"]["max"] \
        == 4
    # the explicit mask is no site of flash_attention
    assert "attn.kernel_sites" not in stamped
    assert "attn.scan_sites" not in stamped


def test_the_dense_blocks_prefill_and_decode_logits_are_the_forwards():
    """The dense block through the generalised ``_through_layers``: the
    same numbers as the training forward under the generation tier's
    reference attention, to float32 round-off (the token-for-token pins
    are tests/test_zz_generate_e2e.py's)."""
    cfg = TransformerConfig(vocab_size=64, n_layers=2, d_model=32,
                            n_heads=2, d_ff=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = np.arange(1, 12, dtype=np.int32)[None]
    want = np.asarray(M.apply(params, ids, cfg,
                              attn_fn=M.dense_causal_attn)[0])
    kv = PagedKVCache(rows=M.cache_rows(cfg), num_blocks=5, block_tokens=4)
    kv.alloc("s", 8)
    logits, kv.pages = M.apply_prefill(
        params, ids[:, :8], np.asarray([8], np.int32), cfg, pages=kv.pages,
        block_tables=kv.block_table("s", 2)[None], block_tokens=4)
    np.testing.assert_allclose(np.asarray(logits[0]), want[7], atol=2e-6,
                               rtol=0)
    for pos in (8, 9, 10):
        kv.extend("s", pos + 1)
        logits, kv.pages = M.apply_decode(
            params, ids[0, pos:pos + 1], np.asarray([pos], np.int32), cfg,
            pages=kv.pages, block_tables=kv.block_table("s", 3)[None],
            block_tokens=4)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=2e-6, rtol=0)


# -- (f) the allocator over a latent row shape -------------------------
def test_the_allocator_over_a_latent_row_shape():
    kv = PagedKVCache(rows={"c0": (40,), "c1": (40,)}, num_blocks=6,
                      block_tokens=4, dtype="bfloat16",
                      counters={"routed": (1, 19)})
    assert kv.pools == ("c0", "c1")
    assert set(kv.pages) == {"c0", "c1", "routed"}
    assert kv.pages["c0"].shape == (6, 4, 40)
    assert kv.pages["c0"].dtype == jnp.bfloat16
    assert kv.pages["routed"].dtype == jnp.int32
    assert kv.block_bytes() == 2 * 4 * 40 * 2
    assert len(kv.alloc("a", 5)) == 2
    assert len(kv.extend("a", 9)) == 3
    kv.alloc("b", 4)
    st = kv.stats()
    assert (st["blocks_total"], st["blocks_live"], st["blocks_free"],
            st["seqs"]) == (5, 4, 1, 2)
    with pytest.raises(CacheExhausted):
        kv.extend("b", 13)
    assert kv.block_table("a", 4).tolist()[3] == 0
    assert kv.free("a") == 3 and kv.stats()["blocks_free"] == 4
    # a step that consumed the pools: made again, zeroed, counters too
    for a in kv.pages.values():
        a.delete()
    assert kv.pools_lost()
    kv.rebuild_pools()
    assert not kv.pools_lost() and kv.pool_rebuilds == 1
    assert kv.pages["c1"].shape == (6, 4, 40)
    assert not np.asarray(kv.pages["routed"]).any()


def test_the_runtime_sizes_its_cache_from_the_model_and_counts_routing():
    lm = lm_config()
    params = weights.make_all(SEED, ref.leaves(TOY), "float32")
    rt = serving.GenerationRuntime(
        "gen_latent", params, lm, slots=2, block_tokens=8, max_prompt=16,
        max_context=32, max_new=6, prefill_batch=1)
    assert rt.kv.pools == ("c0", "c1", "c2")
    assert rt.kv.pages["c0"].shape == (2 * 4 + 1, 8, 40)
    assert rt.kv.block_bytes() == 8 * 40 * 4 * 3
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=True)
        rt.routing_counters()           # what the warm-up routed
        prompts = [list(range(1, 6)), list(range(3, 14))]
        reqs = [serving.GenRequest("gen_latent", p, 5) for p in prompts]
        for r in reqs:
            rt.engine.enqueue(r)
        while not rt.engine.idle():
            rt.engine.step()
        routed = rt.routing_counters()
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    assert stamped["kv.pools_donated"]["max"] == stamped["kv.pools"]["max"] \
        == 3
    # the prefill's attention is one site (the block is traced once);
    # under the tests' x64 it takes the scan
    assert stamped["attn.scan_sites"]["max"] \
        + stamped.get("attn.kernel_sites", {"max": 0})["max"] == 2
    for r in reqs:
        assert len(r.wait(0.1)["tokens"]) == 5
    tokens = sum(len(p) for p in prompts) + 2 * 4   # 4 decoded a request
    assert routed["assignments_total"] == tokens * 2 * 2
    assert 0 < routed["assignments_here"] < routed["assignments_total"]
    # the second request joins a tick later: its prefill follows the
    # first's
    assert routed["dropped"] == 0 and routed["decode_ticks"] == 5
    assert routed["counts"].shape == (2, 8)
    assert routed["decode_assignments_here"] \
        + routed["prefill_assignments_here"] == routed["assignments_here"]
    assert 0 < routed["experts_reached"] <= 4
    assert {"moe.assignments_total", "moe.assignments_here", "moe.dropped",
            "moe.load_max_over_mean", "moe.experts_reached"} <= set(stamped)
    # read and cleared: nothing since
    assert rt.routing_counters()["assignments_total"] == 0
    assert rt.kv.stats()["blocks_live"] == 0


def test_routed_counts_reads_the_layout_routed_row_writes():
    """A difference of two readings of ``routed`` by hand: two expert
    layers, eight experts of which 1, 2, 5 and 6 are held."""
    lm = lm_config()._replace(held_experts=(1, 2, 5, 6))
    assert M.routed_shape(lm) == (2, 19)
    delta = np.zeros((2, 19), np.int64)
    delta[0, :8] = [3, 1, 0, 0, 0, 4, 0, 0]        # prompt tokens
    delta[1, :8] = [0, 0, 2, 0, 0, 0, 6, 0]
    delta[0, 8:16] = [0, 2, 0, 0, 1, 0, 1, 0]      # decoded tokens
    delta[1, 8:16] = [1, 0, 0, 0, 0, 3, 0, 0]
    delta[:, 16] = [3, 2]       # held experts the ticks reached, summed
    delta[:, 17] = 2            # decode ticks
    delta[1, 18] = 1            # dropped
    got = M.routed_counts(delta, lm)
    assert got["prefill_assignments_total"] == 16
    assert got["prefill_assignments_here"] == 1 + 4 + 2 + 6
    assert got["decode_assignments_total"] == 8
    assert got["decode_assignments_here"] == 2 + 1 + 3
    assert got["assignments_total"] == 24 and got["assignments_here"] == 19
    assert got["dropped"] == 1 and got["decode_ticks"] == 2
    assert got["experts_reached_sum"] == 5
    assert got["experts_reached"] == 5 / (2 * 2)
    # held: layer 0 [3, 0, 4, 1], layer 1 [0, 2, 3, 6]
    assert got["load_max_over_mean"] == pytest.approx(
        (4 / 2.0 + 6 / 2.75) / 2)
    assert got["counts"].tolist() == [[3, 3, 0, 0, 1, 4, 1, 0],
                                      [1, 0, 2, 0, 0, 3, 6, 0]]
    idle = M.routed_counts(np.zeros((2, 19), np.int64), lm)
    assert idle["experts_reached"] == 0 and idle["assignments_total"] == 0


def test_routing_counters_are_read_between_the_engines_steps():
    """Another thread asks while the engine's thread ticks: every read
    comes back (the donated array is never seen consumed), and the
    reads add up to what was routed."""
    import threading

    lm = lm_config()
    params = weights.make_all(SEED, ref.leaves(TOY), "float32")
    rt = serving.GenerationRuntime(
        "gen_latent_read", params, lm, slots=2, block_tokens=8,
        max_prompt=16, max_context=32, max_new=12, prefill_batch=1)
    rt.compile(warmup=True)
    rt.routing_counters()
    prompts = [list(range(1, 6)), list(range(3, 14)), list(range(2, 9))]
    for p in prompts:
        rt.engine.enqueue(serving.GenRequest("gen_latent_read", p, 12))
    seen, failed = [], []

    def reader():
        try:
            while not done.is_set():
                seen.append(rt.routing_counters()["assignments_total"])
        except Exception as e:          # a consumed array, a torn read
            failed.append(e)

    done = threading.Event()
    asks = threading.Thread(target=reader)
    asks.start()
    try:
        while not rt.engine.idle():
            rt.engine.step()
    finally:
        done.set()
        asks.join()
    assert not failed, failed
    assert not rt.kv.in_step.locked()
    seen.append(rt.routing_counters()["assignments_total"])
    tokens = sum(len(p) for p in prompts) + 3 * 11
    assert sum(seen) == tokens * 2 * 2


# -- (g) the paged kernel in the decode step ---------------------------
def _greedy(steps, block=8):
    """Each prompt of ``LENGTHS`` prefilled alone, then ``steps`` greedy
    decode ticks of all riders together with an empty slot riding
    along; -> every rider's tokens and the ticks' logits."""
    lm = lm_config()
    params = weights.make_all(SEED, ref.leaves(TOY), "float32")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, TOY["vocab_size"], size=n, dtype=np.int32)
               for n in LENGTHS]
    span = -(-(max(LENGTHS) + steps) // block)
    kv = PagedKVCache(rows=M.cache_rows(lm), num_blocks=40,
                      block_tokens=block, counters={"routed":
                                                    M.routed_shape(lm)})
    tokens = []
    for i, p in enumerate(prompts):
        width = -(-len(p) // block)
        kv.alloc(i, len(p))
        padded = np.zeros((1, width * block), np.int32)
        padded[0, :len(p)] = p
        logits, kv.pages = M.apply_prefill(
            params, padded, np.asarray([len(p)], np.int32), lm,
            pages=kv.pages, block_tables=kv.block_table(i, width)[None],
            block_tokens=block)
        tokens.append([int(np.argmax(logits[0]))])
    ticks = []
    for step in range(steps):
        for i, p in enumerate(prompts):
            kv.extend(i, len(p) + step + 1)
        tables = np.stack([kv.block_table(i, span) for i in range(3)]
                          + [np.zeros(span, np.int32)])
        logits, kv.pages = M.apply_decode(
            params, np.asarray([t[-1] for t in tokens] + [0], np.int32),
            np.asarray([len(p) + step for p in prompts] + [0], np.int32),
            lm, pages=kv.pages, block_tables=tables, block_tokens=block)
        ticks.append(np.asarray(logits[:3]))
        for i, t in enumerate(tokens):
            t.append(int(np.argmax(logits[i])))
    return tokens, np.stack(ticks)


def test_greedy_decode_through_the_paged_kernel_is_the_gathers(monkeypatch):
    """The decode step with the kernel in place of the gather (through
    the Pallas interpreter: on the CPU the step takes the gather)
    chooses every token the gather's step chooses, over ticks that cross
    block edges, with ragged riders and an empty slot."""
    want_tokens, want = _greedy(9)          # x64: every site gathers
    calls = []

    def interpreted(q_abs, pool, block_tables, lengths, cfg):
        calls.append(lengths.shape)
        return paged_latent.paged_latent_attention(
            q_abs, pool, block_tables, lengths, rank=cfg.kv_lora_rank,
            sm_scale=blocks.latent_sm_scale(cfg), interpret=True)

    monkeypatch.setattr(paged_latent, "absorbed_decode", interpreted)
    # at 32 bits the layers are traced anew, each kind once
    with jax.enable_x64(False):
        got_tokens, got = _greedy(9)
    assert len(calls) == 2
    assert got_tokens == want_tokens
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# -- (h) the steps choose the tokens on the device ---------------------
def _runtime(block, name, slots, head=None):
    """A served runtime of the dense toy or of the latent toy; ``head``
    rewrites the head's matrix (the embedding, where tied) before it
    goes to the device."""
    if block == "dense":
        cfg = TransformerConfig(vocab_size=64, n_layers=2, d_model=32,
                                n_heads=2, d_ff=64)
        params = init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg = lm_config()
        params = weights.make_all(SEED, ref.leaves(TOY), "float32")
    if head is not None:
        name_of_head = "embed" if cfg.tied_head else "head"
        params = dict(params, **{name_of_head: head(
            np.asarray(params[name_of_head]))})
    return serving.GenerationRuntime(
        name, params, cfg, slots=slots, block_tokens=8, max_prompt=16,
        max_context=32, max_new=6, prefill_batch=slots)


@pytest.mark.parametrize("block", ["dense", "latent"])
def test_compiled_steps_hand_back_int32_ids(block):
    """Both compiled steps return ``bb`` int32 ids, the first maximum
    of the logits that the model's forward gives on the same
    arguments, and ``compile()`` stamps what the largest decode cell
    hands the host: ``bb`` times 4 bytes."""
    # four slots: a batch ladder of 1, 2, 4; three: of 1, 2, 3
    rt = _runtime(block, "gen_ids_" + block,
                  slots=4 if block == "dense" else 3)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        rt.compile(warmup=False)
    finally:
        profiler.set_state("stop")
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    bb = max(rt.batch_plan)
    assert stamped["gen.readback_bytes"]["max"] == bb * 4
    assert stamped["gen.readback_bytes"]["count"] == 1
    cfg, bt = rt.cfg, rt.block_tokens
    rng = np.random.default_rng(11)
    forwards = (
        ("prefill", M.apply_prefill, (bb, 16),
         lambda: (rng.integers(1, cfg.vocab_size, (bb, 16), np.int32),
                  rng.integers(1, 17, bb).astype(np.int32),
                  np.arange(1, 1 + 2 * bb, dtype=np.int32).reshape(bb, 2))),
        ("decode", M.apply_decode, (bb, 32),
         lambda: (rng.integers(1, cfg.vocab_size, bb).astype(np.int32),
                  rng.integers(0, 32, bb).astype(np.int32),
                  np.arange(1, 1 + 4 * bb, dtype=np.int32).reshape(bb, 4))))
    for kind, forward, key, made in forwards:
        tokens, lengths, tables = made()
        logits, _ = jax.jit(lambda p, t, n, pg, tb: forward(
            p, t, n, cfg, pages=pg, block_tables=tb, block_tokens=bt))(
                rt._params, tokens, lengths, rt.kv.pages, tables)
        logits = np.asarray(logits)
        step = (rt._prefill if kind == "prefill" else rt._decode)[key]
        ids, rt.kv.pages = step(rt._params, tokens, lengths, rt.kv.pages,
                                tables)
        ids = np.asarray(ids)
        assert ids.dtype == np.int32 and ids.shape == (bb,), kind
        top = np.sort(logits, axis=-1)
        clear = top[:, -1] - top[:, -2] > 1e-4
        assert clear.sum() >= bb - 1, kind
        assert (ids == logits.argmax(-1))[clear].all(), kind


def _halves_repeat(head):
    """The head's rows repeat after the first half: token ``v`` and
    ``v + vocab / 2`` score alike whatever the history."""
    half = head.shape[0] // 2
    return np.concatenate([head[:half], head[:half]])


def _greedy_loop(rt, prompt, n_new):
    """Greedy decode of one prompt alone through the model's own
    forwards at the shapes the engine gives a lone rider (its prompt
    and cache buckets, a batch of one), the token the first maximum of
    the logits; every choice is a tie between the two halves."""
    cfg, bt = rt.cfg, rt.block_tokens
    counters = M.routed_shape(cfg)
    kv = PagedKVCache(rows=M.cache_rows(cfg), num_blocks=8,
                      block_tokens=bt, dtype=cfg.dtype,
                      counters={"routed": counters} if counters else None)
    tb = serving.bucket_for(rt.prompt_plan, len(prompt))
    kv.alloc("s", len(prompt))
    tokens = np.zeros((1, tb), np.int32)
    tokens[0, :len(prompt)] = prompt
    logits, kv.pages = M.apply_prefill(
        rt._params, tokens, np.asarray([len(prompt)], np.int32), cfg,
        pages=kv.pages, block_tables=kv.block_table("s", tb // bt)[None],
        block_tokens=bt)
    out, pos = [], len(prompt)
    while True:
        row = np.asarray(logits[0])
        half = cfg.vocab_size // 2
        np.testing.assert_array_equal(row[:half], row[half:])
        out.append(int(np.argmax(row)))
        if len(out) == n_new:
            return out
        kv.extend("s", pos + 1)
        lb = serving.bucket_for(rt.cache_plan, pos + 1)
        logits, kv.pages = M.apply_decode(
            rt._params, np.asarray(out[-1:], np.int32),
            np.asarray([pos], np.int32), cfg, pages=kv.pages,
            block_tables=kv.block_table("s", lb // bt)[None],
            block_tokens=bt)
        pos += 1


@pytest.mark.parametrize("block", ["dense", "latent"])
def test_engine_serves_the_greedy_loops_tokens_first_maximum_on_ties(block):
    """The engine's tokens, chosen inside the compiled steps, are a
    greedy loop's over the logits of ``apply_prefill`` /
    ``apply_decode`` with numpy's rule, on a head where every choice is
    a tie: each token is the first of its pair, never the second."""
    # one slot: each request is served alone, at the loop's shapes
    rt = _runtime(block, "gen_ties_" + block, slots=1,
                  head=_halves_repeat)
    rt.compile(warmup=True)
    prompts = [[3, 9, 1, 4, 7], list(range(2, 14)), [5]]
    want = [_greedy_loop(rt, p, 6) for p in prompts]
    reqs = [serving.GenRequest(rt.name, p, 6) for p in prompts]
    for r in reqs:
        rt.engine.enqueue(r)
    while not rt.engine.idle():
        rt.engine.step()
    got = [r.wait(0.1)["tokens"] for r in reqs]
    assert got == want
    assert max(max(t) for t in got) < rt.cfg.vocab_size // 2
