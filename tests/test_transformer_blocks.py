"""What ``TransformerConfig`` can spell beside the dense block (ISSUE
28): latent attention with unequal head widths and YaRN, gated and
expert feed-forwards, hyper-connected residual streams, an untied head
and a multi-token module, against the plain reference
``perfbench/reference/xing4.py`` on seeded weights at a toy size; the
expert layer's share of a layer; routing that drops nothing; and the
dense configuration bit for bit against the block as it was
(``parent_dense_model.py``)."""
import copy
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as mx_optimizer
from mxnet_tpu.parallel.attention import (attention_reference,
                                          flash_attention)
from mxnet_tpu.transformer import (TransformerConfig, TransformerTrainStep,
                                   apply, apply_decode, apply_prefill,
                                   blocks, frozen_names, init_params,
                                   lm_loss, loss_and_aux, make_attn_fn,
                                   param_shapes)
from perfbench import weights
from perfbench.drivers.train_moe_lm import lm_config
from perfbench.reference import xing4

import parent_dense_model as parent

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 3000000019


def _toy(**over):
    with open(os.path.join(HERE, "perfbench", "toy_moe", "configs",
                           "xing4_toy.json")) as f:
        cfg = json.load(f)
    cfg.update(over)
    return cfg


def _state(cfg):
    specs = xing4.leaves(cfg)
    p = weights.make_all(SEED, specs, "float32")
    return specs, {n: p[n] for n, _, _ in specs}


def _tokens(cfg, batch=2, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    t = rng.randint(0, cfg["vocab_size"],
                    (batch, seq + 1 + cfg["num_nextn_predict_layers"]))
    return jnp.asarray(t[:, :seq], jnp.int32), jnp.asarray(t[:, 1:],
                                                           jnp.int32)


def _reference_loss_and_grads(cfg, p, tokens, labels):
    biased = xing4.frozen(cfg)
    b = {k: p[k] for k in biased}
    trained = {k: v for k, v in p.items() if k not in biased}

    def mean_loss(pp):
        with jax.default_matmul_precision("highest"):
            return sum(xing4.row_loss(pp, b, tokens[r], labels[r], cfg)[0]
                       for r in range(tokens.shape[0])) / tokens.shape[0]

    return jax.value_and_grad(mean_loss)(trained)


# ---------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------
@pytest.mark.parametrize("streams", [2, 4])
def test_loss_and_every_gradient_match_the_reference(streams):
    cfg = _toy(hc_mult=streams)
    specs, p = _state(cfg)
    lm = lm_config(cfg)
    assert [(n, tuple(s)) for n, s, _ in param_shapes(lm)] \
        == [(n, tuple(s)) for n, s, _ in specs]
    tokens, labels = _tokens(cfg)
    want, want_g = _reference_loss_and_grads(cfg, p, tokens, labels)
    frozen = frozen_names(lm)
    assert frozen == xing4.frozen(cfg) and len(frozen) == 3

    def loss(pp):
        return loss_and_aux(dict(p, **pp), tokens, labels, lm,
                            attn_fn=make_attn_fn("flash"), remat="block")

    (got, aux), got_g = jax.value_and_grad(loss, has_aux=True)(
        {k: v for k, v in p.items() if k not in frozen})
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert set(got_g) == set(want_g)
    # a leaf whose gradient is nought but for round-off (layer 0's
    # H_pre: its streams are copies and a norm follows) is held to the
    # median leaf's scale
    floor = 1e-2 * float(np.median([float(jnp.max(jnp.abs(g)))
                                    for g in want_g.values()]))
    for k in want_g:
        scale = max(float(jnp.max(jnp.abs(want_g[k]))), floor)
        assert float(jnp.max(jnp.abs(got_g[k] - want_g[k]))) / scale \
            < 2e-4, k
    assert aux["counts"].shape == (3, cfg["router_width"])
    assert int(aux["counts"].sum()) == 3 * tokens.size * 2
    assert int(aux["dropped"].sum()) == 0


def test_three_steps_match_the_reference():
    cfg = _toy()
    specs, p = _state(cfg)
    lm = lm_config(cfg)
    biased = xing4.frozen(cfg)
    step = TransformerTrainStep(lm, learning_rate=0.01, momentum=0.9,
                                attn_impl="flash", remat="block",
                                params=dict(p))
    ref = xing4.make_step(cfg, 0.01, 0.9)
    _, fresh = _state(cfg)
    rp = {k: v for k, v in fresh.items() if k not in biased}
    rm = {k: jnp.zeros_like(v) for k, v in rp.items()}
    rb = {k: fresh[k] for k in biased}
    for i in range(3):
        tokens, labels = _tokens(cfg, seed=i)
        got = step.step(mx.nd.NDArray(tokens), mx.nd.NDArray(labels))
        with jax.default_matmul_precision("highest"):
            rp, rm, rb, want, choices = ref(rp, rm, rb, tokens, labels)
        assert float(got) == pytest.approx(float(want), rel=5e-6)
    assert set(step._moms) == set(rp)
    for k in rp:
        np.testing.assert_allclose(np.asarray(step._params[k]),
                                   np.asarray(rp[k]), rtol=2e-4, atol=2e-6,
                                   err_msg=k)
    for k in biased:      # moved by the rule, by exactly +-rate a step
        np.testing.assert_allclose(np.asarray(step._params[k]),
                                   np.asarray(rb[k]), rtol=0, atol=1e-7)
        moved = np.asarray(step._params[k]) - np.asarray(fresh[k])
        assert np.all(np.abs(moved) <= 3 * 0.001 + 1e-6) and moved.any()
    counted = step.routing_counters()
    assert counted["steps"] == 3 and counted["dropped"] == 0
    assert counted["assignments_total"] == 3 * 3 * tokens.size * 2
    assert 0 < counted["assignments_here"] < counted["assignments_total"]
    assert counted["load_max_over_mean"] >= 1.0
    assert step.routing_counters() is None      # read once


# ---------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------
def _expert_layer(cfg, seed=1):
    """One expert layer's leaves at toy size with ALL of the router's
    experts present, and an input."""
    whole = _toy(held_experts=list(range(cfg["router_width"])),
                 n_routed_experts=cfg["router_width"])
    _, p = _state(whole)
    lp = {k[len("blk1."):]: v for k, v in p.items()
          if k.startswith("blk1.")}
    x = jax.random.normal(jax.random.PRNGKey(seed),
                          (2, 16, cfg["hidden_size"]), jnp.float32)
    return whole, p, lp, x


def test_the_shares_add_up_to_the_uncut_layer():
    cfg = _toy()
    whole, p, lp, x = _expert_layer(cfg)
    n, d = x.shape[0] * x.shape[1], x.shape[2]
    with jax.default_matmul_precision("highest"):
        want, _ = xing4._experts(p, "blk1.", x.reshape(n, d),
                                 p["blk1.router_bias"], whole, None)
        shared = xing4._gated(x.reshape(n, d), lp["ws_gate"], lp["ws_up"],
                              lp["ws_down"], None)
    total, served = jnp.zeros((n, d)), 0
    for share in range(4):                  # 8 experts over 4 shares
        held = (2 * share, 2 * share + 1)
        lm = lm_config(_toy(held_experts=list(held)))
        mine = dict(lp, **{k: lp[k][jnp.asarray(held)]
                           for k in ("we_gate", "we_up", "we_down")})
        y, aux = blocks.expert_ffn(x, mine, lm)
        # what every share computes alike is counted once
        total = total + y.reshape(n, d) - shared
        served += int(aux["counts"][jnp.asarray(held)].sum())
        assert int(aux["dropped"]) == 0
    assert served == n * cfg["num_experts_per_tok"]
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


def test_no_assignment_is_dropped_when_every_token_picks_one_expert():
    cfg = _toy()
    _, p, lp, x = _expert_layer(cfg)
    lm = lm_config(cfg)
    held = jnp.asarray(cfg["held_experts"])
    forced = dict(lp, **{k: lp[k][held]
                         for k in ("we_gate", "we_up", "we_down")})
    forced["router"] = jnp.zeros_like(lp["router"])
    # the bias decides: expert 2 first and 5 (not held) second, always
    forced["router_bias"] = jnp.zeros((8,)).at[2].set(2.0).at[5].set(1.0)
    y, aux = blocks.expert_ffn(x, forced, lm)
    n = x.shape[0] * x.shape[1]
    assert aux["counts"].tolist() == [0, 0, n, 0, 0, n, 0, 0]
    assert int(aux["dropped"]) == 0
    flat = x.reshape(n, -1)
    with jax.default_matmul_precision("highest"):
        # every score is sigmoid(0), so each of the two weighs 1/2 * 2
        want = xing4._gated(flat, lp["we_gate"][2], lp["we_up"][2],
                            lp["we_down"][2], None) \
            + xing4._gated(flat, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                           None)
    np.testing.assert_allclose(np.asarray(y.reshape(n, -1)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    # and the gradient reaches every token's row through the sort
    g = jax.grad(lambda xx: blocks.expert_ffn(xx, forced, lm)[0].sum())(x)
    assert bool(jnp.all(jnp.abs(g).sum(-1) > 0))


def test_sinkhorn_rows_and_columns_sum_to_one():
    """At the spread the cell's init gives the maps (unit normal before
    the exp), 20 rounds leave the columns, normalised last, at 1 within
    ``hc_eps``-sized error and the rows within 1e-4."""
    m = jnp.exp(jnp.clip(jax.random.normal(
        jax.random.PRNGKey(2), (5, 7, 4, 4), jnp.float32), -30, 30))
    out = blocks.sinkhorn(m, 20, 1e-6)
    np.testing.assert_allclose(np.asarray(out.sum(-2)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-4)
    assert bool(jnp.all(out >= 0))
    # the clamp's corners stay finite
    corner = jnp.exp(jnp.asarray([[30.0, -30.0], [-30.0, 30.0]]))
    assert bool(jnp.all(jnp.isfinite(blocks.sinkhorn(corner, 20, 1e-6))))


def test_stream_maps_are_not_the_identity_under_the_cell_s_init():
    cfg = _toy()
    _, p = _state(cfg)
    lm = lm_config(cfg)
    lp = {k[len("blk0."):]: v for k, v in p.items()
          if k.startswith("blk0.")}
    xs = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 4, 32))
    pre, post, res = blocks.stream_maps(xs, lp, "attn", lm)
    assert pre.shape == post.shape == (2, 16, 4)
    assert float(jnp.max(jnp.abs(res - jnp.eye(4)))) > 0.2
    assert float(jnp.std(res[:, :, 0, 0])) > 1e-3   # it is a function of x


def _mix_case(dtype, n, which, width=128, batch=2, seq=16):
    """Streams, a layer's stream leaves, a sublayer with a weight and an
    ``aux`` of its own, and a cotangent for the new streams."""
    cfg = TransformerConfig(vocab_size=64, n_layers=1, d_model=width,
                            n_heads=2, dtype=dtype, hc_mult=n)
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    normal = jax.random.normal
    lp = {"hc_%s_w" % which: 0.05 * normal(ks[0], (n * width,
                                                   n * n + 2 * n)),
          "hc_%s_alpha" % which: jnp.asarray([0.7, 1.1, 0.9]),
          "hc_%s_b_pre" % which: 0.3 * normal(ks[1], (n,)),
          "hc_%s_b_post" % which: 0.3 * normal(ks[2], (n,)),
          "hc_%s_b_res" % which: normal(ks[3], (n, n)),
          "gain": 1.0 + 0.1 * normal(ks[4], (width,))}
    xs = normal(ks[5], (batch, seq, n, width)).astype(dtype)
    ct = normal(ks[6], xs.shape)

    def run(how):
        def loss(xs, lp):
            def sublayer(m):
                y = jnp.tanh(m.astype(jnp.float32) * lp["gain"])
                return y.astype(m.dtype), {"sum": jnp.sum(y)}

            out, aux = blocks._hyper_residual(xs, lp, which, cfg,
                                              sublayer, how)
            seen = {}
            blocks._hyper_residual(
                xs, lp, which, cfg,
                lambda m: (seen.setdefault("mixed", m), None), how)
            return (jnp.sum(out.astype(jnp.float32) * ct)
                    + 0.01 * aux["sum"]), (out, seen["mixed"])

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(xs, lp)

    return run


@pytest.mark.parametrize("dtype,n,which,how", [
    ("float32", 4, "attn", "interpret"), ("float32", 4, "mlp", "interpret"),
    ("float32", 2, "attn", "interpret"), ("bfloat16", 4, "attn", "interpret"),
    ("bfloat16", 4, "mlp", "interpret"), ("bfloat16", 2, "mlp", "interpret"),
    ("float32", 4, "mlp", "dispatch"), ("bfloat16", 4, "attn", "dispatch")])
def test_fused_stream_passes_match_the_plain_formulation(dtype, n, which,
                                                         how):
    """``mixed``, the new streams and every gradient (streams, the
    sublayer's own weight, the five stream leaves) of the two passes
    under their own backward, against the plain formulation kept beside
    them and differentiated by autodiff: the kernels through the Pallas
    interpreter (``interpret``), and what the same passes lower to for
    the CPU (``dispatch``).  Float32 streams agree to round-off, bf16
    streams to one rounding of what is written in bf16."""
    with jax.enable_x64(False):             # as the chip runs
        run = _mix_case(dtype, n, which)
        assert blocks._fused_tile(
            jnp.zeros((2, 16, n, 128), dtype)) is not None
        (l0, (out0, mixed0)), (dx0, dlp0) = run("plain")
        (l1, (out1, mixed1)), (dx1, dlp1) = run(how)
    f32 = lambda a: np.asarray(a, np.float32)      # noqa: E731
    # one rounding: half a unit in the last of bf16's 8 bits, each side
    wide = dict(rtol=2 ** -7, atol=2 ** -7) if dtype == "bfloat16" \
        else dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(f32(mixed1), f32(mixed0), **wide)
    np.testing.assert_allclose(f32(out1), f32(out0), **wide)
    np.testing.assert_allclose(f32(dx1), f32(dx0), **wide)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    assert sorted(dlp1) == sorted(dlp0) and len(dlp0) == 6
    for name in dlp0:
        # float32 leaves either way: sums over all tokens of products
        # that the bf16 case rounds at other places
        scale = float(np.abs(f32(dlp0[name])).max())
        tol = (2e-2 if dtype == "bfloat16" else 2e-5) * scale
        np.testing.assert_allclose(f32(dlp1[name]), f32(dlp0[name]),
                                   atol=tol, err_msg=name)


def test_fused_stream_passes_take_sinkhorn_from_the_module(monkeypatch):
    """Both lowerings reach Sinkhorn as ``blocks.sinkhorn`` when they are
    traced (the benchmark's rehearsal plants its fault there), also after
    an earlier trace of the same shapes."""
    with jax.enable_x64(False):
        run = _mix_case("float32", 4, "attn")
        (_, (real, _)), _ = run("interpret")
        monkeypatch.setattr(
            blocks, "sinkhorn",
            lambda m, iters, eps: jnp.broadcast_to(
                jnp.eye(m.shape[-1]), m.shape) + 0.0 * m)
        (_, (fused, _)), _ = run("interpret")
        (_, (plain, _)), _ = run("plain")
    assert float(jnp.max(jnp.abs(fused - real))) > 0.1
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape,dtype,fits", [
    ((1, 4096, 4, 3584), "bfloat16", True),     # the cell's
    ((2, 16, 2, 128), "float32", True),
    ((2, 16, 4, 32), "float32", False),         # the toy's width
    ((1, 24, 4, 128), "float32", False),        # no tile of whole tokens
    ((2, 16, 6, 128), "bfloat16", False),       # 3 * 48 map entries
    ((2, 16, 4, 128), "float16", False)])
def test_fused_stream_passes_are_chosen_from_the_operands(shape, dtype, fits):
    xs = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    with jax.enable_x64(False):
        assert (blocks._fused_tile(xs) is not None) == fits
    assert blocks._fused_tile(xs) is None       # tier-1 traces under x64


# ---------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------
def test_flash_attention_takes_values_of_another_width():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 96, 4, 192), jnp.float32)
    k = jnp.asarray(rng.randn(2, 96, 4, 192), jnp.float32)
    v = jnp.asarray(rng.randn(2, 96, 4, 128), jnp.float32)
    scale = 192 ** -0.5 * 1.4 ** 2
    got = flash_attention(q, k, v, causal=True, sm_scale=scale,
                          block_size=32)
    want = attention_reference(q, k, v, causal=True, sm_scale=scale)
    assert got.shape == (2, 96, 4, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda vv: flash_attention(q, k, vv, causal=True).sum())(v)
    assert g.shape == v.shape


def test_yarn_frequencies_match_the_reference():
    cfg = _toy()
    lm = lm_config(cfg)
    want, m = xing4.yarn_frequencies(cfg)
    got = blocks.yarn_inv_freq(lm.qk_rope_head_dim, lm.rope_base,
                               lm.rope_yarn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert blocks.yarn_softmax_mscale(lm.rope_yarn) == pytest.approx(m)
    assert m == pytest.approx(0.1 * np.log(4.0) + 1.0)
    # the slow pairs are divided by the factor, the fast ones kept
    plain = 10000.0 ** (-np.arange(2) / 2.0)
    assert float(got[0]) == pytest.approx(plain[0])
    assert float(got[-1]) == pytest.approx(plain[-1] / 4.0)


_LATENT = dict(attn_kind="latent", q_lora_rank=8, kv_lora_rank=8,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
_EXPERTS = dict(ffn_act="swiglu", n_experts=4, experts_per_token=2,
                n_shared_experts=1, expert_ff=8, held_experts=(0, 1))


@pytest.mark.parametrize("over", [
    dict(hc_mult=2), dict(_LATENT, hc_mult=2),
    dict(_LATENT, **_EXPERTS, tied_head=False, mtp_layers=1),
    dict(_EXPERTS, layer_kinds=("experts",)),
], ids=["streams", "latent_streams", "multi_token", "mha_experts"])
def test_generation_raises_for_what_it_does_not_spell(over):
    """Since PR 38 the generation forwards spell both mixers, both
    dense feed-forwards, the expert layer under the latent mixer and
    either head (tests/test_generate_latent.py); residual streams, a
    multi-token module and an expert layer under ``mha`` still raise."""
    cfg = TransformerConfig(vocab_size=32, n_layers=1, d_model=16,
                            n_heads=2, d_ff=32, **over)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="generation spells"):
        apply_prefill(params, tokens, jnp.asarray([4]), cfg, pages={},
                      block_tables=None, block_tokens=4)
    with pytest.raises(NotImplementedError, match="generation spells"):
        apply_decode(params, tokens[:, 0], jnp.asarray([0]), cfg,
                     pages={}, block_tables=None, block_tokens=4)
    # the training forward does spell it
    assert apply(params, tokens, cfg,
                 attn_fn=make_attn_fn("flash")).shape == (1, 4, 32)


# ---------------------------------------------------------------------
# the dense configuration is the program it was
# ---------------------------------------------------------------------
DENSE = dict(vocab_size=64, n_layers=2, d_model=32, n_heads=4, d_ff=64)


@pytest.mark.parametrize("remat", ["none", "block", "attention"])
def test_dense_apply_and_loss_are_bit_for_bit_the_parent_s(remat):
    cfg = TransformerConfig(**DENSE)
    old = parent.TransformerConfig(**DENSE)
    assert param_shapes(cfg) == parent.param_shapes(old)
    key = jax.random.PRNGKey(7)
    p, q = init_params(key, cfg), parent.init_params(key, old)
    for k in q:
        assert np.array_equal(np.asarray(p[k]), np.asarray(q[k])), k
    tokens, labels = _tokens({"vocab_size": 64,
                              "num_nextn_predict_layers": 0})
    attn = make_attn_fn("flash")

    def new(pp):
        return loss_and_aux(pp, tokens, labels, cfg, attn_fn=attn,
                            remat=remat)[0]

    def was(pp):
        return parent.lm_loss(parent.apply(pp, tokens, old, attn_fn=attn,
                                           remat=remat), labels)

    assert np.array_equal(
        np.asarray(apply(p, tokens, cfg, attn_fn=attn, remat=remat)),
        np.asarray(parent.apply(q, tokens, old, attn_fn=attn, remat=remat)))
    (l1, g1), (l2, g2) = (jax.jit(jax.value_and_grad(f))(p)
                          for f in (new, was))
    assert float(l1) == float(l2)
    assert float(l1) == float(lm_loss(apply(p, tokens, cfg, attn_fn=attn,
                                            remat=remat), labels))
    for k in g2:
        assert np.array_equal(np.asarray(g1[k]), np.asarray(g2[k])), k


def test_dense_train_step_is_bit_for_bit_the_parent_s():
    """Three steps of ``TransformerTrainStep`` against the parent's
    step body (its unsharded path: loss, gradient, the leaf-by-leaf
    update) built from the frozen functions: losses and every leaf."""
    cfg = TransformerConfig(**DENSE)
    old = parent.TransformerConfig(**DENSE)
    attn = make_attn_fn("flash")
    names = [n for n, _, _ in parent.param_shapes(old)]

    def was(params_d, moms, tokens, labels):
        def pure_loss(p):
            return parent.lm_loss(parent.apply(
                p, tokens, old, attn_fn=attn, pos_offset=0, remat="block"),
                labels)

        loss, grads = jax.value_and_grad(pure_loss)(params_d)
        with jax.named_scope("optimizer"):
            new_p, new_m = mx_optimizer.fused_sgd_mom_grouped(
                names, params_d, grads, moms, 0.01, 0.9, 0.0)
        return new_p, new_m, loss

    was = jax.jit(was, donate_argnums=(0, 1))
    step = TransformerTrainStep(cfg, learning_rate=0.01, momentum=0.9,
                                attn_impl="flash", remat="block", seed=5)
    p = parent.init_params(jax.random.PRNGKey(5), old)
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    for i in range(3):
        tokens, labels = _tokens({"vocab_size": 64,
                                  "num_nextn_predict_layers": 0}, seed=i)
        got = step.step(mx.nd.NDArray(tokens), mx.nd.NDArray(labels))
        p, m, want = was(p, m, tokens, labels)
        assert float(got) == float(want)
    assert sorted(step._params) == sorted(names) == sorted(step._moms)
    for k in names:
        assert np.array_equal(np.asarray(step._params[k]),
                              np.asarray(p[k])), k
        assert np.array_equal(np.asarray(step._moms[k]),
                              np.asarray(m[k])), k
    assert step.routing_counters() is None


# ---------------------------------------------------------------------
# state without a second copy
# ---------------------------------------------------------------------
def _pointer(x):
    return x.addressable_shards[0].data.unsafe_buffer_pointer()


def test_the_step_is_built_on_the_arrays_it_is_given():
    cfg = TransformerConfig(**DENSE)
    given = init_params(jax.random.PRNGKey(1), cfg)
    given = {k: jax.device_put(v, jax.devices()[0])
             for k, v in given.items()}
    where = {k: _pointer(v) for k, v in given.items()}
    step = TransformerTrainStep(cfg, params=given)
    step._build()
    assert {k: _pointer(v) for k, v in step._params.items()} == where
    wrong = dict(given)
    wrong.pop("final_norm")
    with pytest.raises(ValueError, match="param_shapes"):
        TransformerTrainStep(cfg, params=wrong)._build()


def test_load_state_takes_device_arrays_over_without_a_copy():
    cfg = TransformerConfig(**DENSE)
    step = TransformerTrainStep(cfg, seed=0)
    step._build()
    fresh = {k: jax.device_put(v, jax.devices()[0]) for k, v in
             init_params(jax.random.PRNGKey(9), cfg).items()}
    where = {k: _pointer(v) for k, v in fresh.items()}
    step.load_state({"params": fresh})
    assert {k: _pointer(v) for k, v in step._params.items()} == where
    host = {k: np.asarray(v) for k, v in fresh.items()}
    step.load_state({"params": host})           # a checkpoint's arrays
    for k in host:
        assert np.array_equal(np.asarray(step._params[k]), host[k])


def test_state_of_an_expert_configuration_round_trips():
    cfg = _toy()
    _, p = _state(cfg)
    lm = lm_config(cfg)
    a = TransformerTrainStep(lm, params=dict(p), attn_impl="flash",
                             remat="none")
    tokens, labels = _tokens(cfg)
    a.step(mx.nd.NDArray(tokens), mx.nd.NDArray(labels))
    saved = {"params": a.params_numpy(),
             "optimizer_states": a.optimizer_states_bytes()}
    assert any(k.endswith("router_bias") for k in saved["params"])
    b = TransformerTrainStep(lm, attn_impl="flash", remat="none", seed=3)
    b.load_state(copy.deepcopy(saved))
    assert set(b._moms) == set(a._moms) \
        and not any(k.endswith("router_bias") for k in b._moms)
    la = a.step(mx.nd.NDArray(tokens), mx.nd.NDArray(labels))
    lb = b.step(mx.nd.NDArray(tokens), mx.nd.NDArray(labels))
    assert float(la) == float(lb)
    for k in a._params:
        assert np.array_equal(np.asarray(a._params[k]),
                              np.asarray(b._params[k])), k
