"""The new kernels of the training path compiled at real widths for a
v5e that is described, not attached (the ``on-chip-measurement``
guide's third rehearsal): what the chip's compiler would refuse shows
here, at no chip time.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture of this file alone, after a
test has started: only one process may hold the TPU's library, and this
worker keeps it until it exits."""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described chip is written to the persistent
    cache and cannot be read back without a chip: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _expert_cfg():
    from mxnet_tpu.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=16384, n_layers=1, d_model=3584, n_heads=32,
        dtype="bfloat16", layer_kinds=("experts",), n_experts=64,
        experts_per_token=4, n_shared_experts=1, expert_ff=1024,
        held_experts=tuple(range(8)), routed_scaling=2.0)


def test_expert_layer_compiles_to_a_grouped_kernel(one_chip, no_cache):
    """4,096 tokens through the expert layer, forward and backward, at
    the published widths: the products over the held experts become the
    chip's grouped-matmul kernel (a custom call), never a dense product
    for every expert."""
    from mxnet_tpu.transformer import blocks

    cfg = _expert_cfg()

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lp = {"router": spec((3584, 64)), "router_bias": spec((64,)),
          "we_gate": spec((8, 3584, 1024)), "we_up": spec((8, 3584, 1024)),
          "we_down": spec((8, 1024, 3584)), "ws_gate": spec((3584, 1024)),
          "ws_up": spec((3584, 1024)), "ws_down": spec((1024, 3584))}
    x = spec((1, 4096, 3584), jnp.bfloat16)

    def loss(x, lp):
        y, aux = blocks.expert_ffn(x, lp, cfg)
        return jnp.sum(y.astype(jnp.float32)), aux["counts"]

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True),
                       ).lower(x, lp).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4e9


@pytest.mark.parametrize("shape", [(4, 2048, 16, 128, 128),
                                   (1, 4096, 32, 192, 128)],
                         ids=["lm_train_s2048", "xing4_train_s4096"])
def test_flash_attention_compiles_with_narrower_values(one_chip, no_cache,
                                                       shape):
    """The gradient of ``flash_attention`` at both LM cells' shapes,
    lowered for the chip: the tiled kernels (Mosaic custom calls) and no
    loop, and none of the scan's stacked accumulators among the
    temporaries (1.30 GB and 1.86 GB before the kernels were bound)."""
    from mxnet_tpu.parallel.attention import flash_attention

    B, T, H, D, Dv = shape

    def spec(width):
        return jax.ShapeDtypeStruct((B, T, H, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       sm_scale=0.1).astype(jnp.float32))

    with jax.enable_x64(False):     # as the chip runs; under x64: the scan
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            spec(D), spec(D), spec(Dv)).compile()
    out = compiled.output_shardings
    assert len(jax.tree_util.tree_leaves(out)) == 3
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def _stream_block(layers):
    """``layers`` sublayers of four 3,584-wide streams over 4,096 tokens
    in bf16, each under ``block`` remat with leaves of its own, and the
    gradient of a loss over them: (function, arguments to lower it on)."""
    from mxnet_tpu.transformer import TransformerConfig, blocks

    n, d = 4, 3584
    cfg = TransformerConfig(vocab_size=16384, n_layers=1, d_model=d,
                            n_heads=32, dtype="bfloat16", hc_mult=n)

    def loss(xs, lps):
        for lp in lps:
            def block(xs, lp):
                return blocks.hyper_residual(
                    xs, lp, "mlp", cfg,
                    lambda m: (m * lp["gain"].astype(m.dtype), None))[0]

            xs = jax.checkpoint(block)(xs, lp)
        # of one stream: the test's own loss widens none of the four
        return jnp.sum(xs[:, :, 0].astype(jnp.float32) ** 2)

    def args(one_chip):
        def spec(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        lp = {"hc_mlp_w": spec((n * d, n * n + 2 * n)),
              "hc_mlp_alpha": spec((3,)), "hc_mlp_b_pre": spec((n,)),
              "hc_mlp_b_post": spec((n,)), "hc_mlp_b_res": spec((n, n)),
              "gain": spec((d,))}
        return spec((1, 4096, n, d), jnp.bfloat16), [lp] * layers

    return jax.jit(jax.grad(loss, argnums=(0, 1))), args


def test_stream_mixing_writes_no_float32_copy_of_the_streams(one_chip,
                                                             no_cache):
    """One ``hc_mult`` 4 sublayer at the published widths, forward and
    backward under ``block`` remat, lowered for the chip: the fused
    passes (Mosaic custom calls), and no buffer of the streams in
    float32, whichever way they are folded (234.9 MB each: the plain
    formulation wrote several a sublayer)."""
    import re

    fn, args = _stream_block(1)
    with jax.enable_x64(False):     # as the chip runs; under x64: plain
        text = fn.lower(*args(one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "bf16[4096,14336]" in text
    wide = re.findall(r"f32\[(?:1,4096,4,3584|4096,14336|1,4096,14336"
                      r"|4096,4,3584)\]", text)
    assert not wide, sorted(set(wide))


def test_stream_mixing_is_lowered_once_whatever_the_depth(one_chip,
                                                          no_cache):
    """The guard of set-up: tracing and lowering run on every start,
    outside the compile cache, and a kernel's lowering is paid at every
    copy of its body.  All sublayers have one operand signature, so the
    LOWERED module of one layer and of three hold the same bodies of the
    fused passes, called from every site: as many kernels, and as many
    functions that hold one.  A count, not a time."""
    import re

    def bodies(layers):
        fn, args = _stream_block(layers)
        with jax.enable_x64(False):
            text = fn.lower(*args(one_chip)).as_text()
        kernels = text.count("tpu_custom_call")
        holders = sum("tpu_custom_call" in body for body in
                      re.split(r"\n\s*func\.func ", text)[1:])
        calls = len(re.findall(r"call @_(?:pre|post)_(?:for|back)ward",
                               text))
        return kernels, holders, calls

    one, three = bodies(1), bodies(3)
    assert one[:2] == three[:2] and one[0] >= 5, (one, three)
    assert three[2] == 3 * one[2] > 0, (one, three)   # the sites call them


def _served_step(kind, layers, one_chip):
    """The runtime's own compiled step (``GenerationRuntime._jit_fns``,
    pools donated) of the served configuration's widths at ``layers``
    layers, with its arguments as shapes on the described chip: the
    largest plan cell of its kind."""
    import types

    from mxnet_tpu import serving
    from mxnet_tpu.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=50304, n_layers=layers, d_model=2048,
                            n_heads=16, d_ff=8192, dtype="bfloat16",
                            param_dtype="bfloat16")
    prefill, decode = serving.GenerationRuntime._jit_fns(
        types.SimpleNamespace(cfg=cfg, block_tokens=128))

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    pages = {"%s%d" % (kv, i): spec((161, 128, 16, 128), jnp.bfloat16)
             for i in range(layers) for kv in "kv"}
    if kind == "prefill":
        return prefill, (params, spec((1, 1536)), spec((1,)), pages,
                         spec((1, 12)))
    return decode, (params, spec((10,)), spec((10,)), pages,
                    spec((10, 16)))


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_served_step_writes_the_pools_in_place(one_chip, no_cache, kind):
    """The chip's compiler aliases every donated K and V pool (84.4 MB
    each at the cell's 161 blocks) to its own output and holds no copy
    of a pool's shape: undonated, every call copied each pool whole
    before it scattered a row a rider into it."""
    import re

    step, args = _served_step(kind, 2, one_chip)
    with jax.enable_x64(False):
        text = step.lower(*args).compile().as_text()
    head = next(ln for ln in text.splitlines() if ln.startswith("HloModule"))
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    assert aliased and len(re.findall(r"-alias\)", aliased.group(1))) == 4
    assert not re.findall(
        r"= bf16\[(?:161,128,16,128|20608,16,128)\]\S* copy(?:-start)?\(",
        text)


def test_served_step_lowers_one_block_whatever_the_depth(one_chip):
    """The guard of the server's set-up: every plan cell is traced and
    lowered on every start.  All layers have one operand signature, so
    the lowered decode step of two layers and of four hold the block
    once, called at every layer.  A count, not a time."""
    import re

    def lowered(layers):
        step, args = _served_step("decode", layers, one_chip)
        with jax.enable_x64(False):
            text = step.lower(*args).as_text()
        return (len(re.findall(r"func\.func ", text)),
                len(re.findall(r"call @_decode_layer", text)))

    (two_funcs, two_calls), (four_funcs, four_calls) = lowered(2), lowered(4)
    assert two_funcs == four_funcs, (two_funcs, four_funcs)
    assert (two_calls, four_calls) == (2, 4)


def _latent_served_step(kind, one_chip):
    """``_served_step`` for a block with latent attention and experts at
    sarvam-105b's published widths, a dense and an expert layer deep:
    one pool of latent rows a layer and the routing counters beside
    them."""
    import types

    from mxnet_tpu import serving
    from mxnet_tpu.transformer import (RopeYarn, TransformerConfig,
                                       param_shapes)
    from mxnet_tpu.transformer import model as M

    cfg = TransformerConfig(
        vocab_size=65536, n_layers=2, d_model=4096, n_heads=64, d_ff=16384,
        dtype="bfloat16", param_dtype="bfloat16", attn_kind="latent",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_yarn=RopeYarn(40.0, 4096, 32.0, 1.0, 1.0, 1.0),
        ffn_act="swiglu", tied_head=False,
        layer_kinds=("dense_ffn", "experts"), n_experts=128,
        experts_per_token=8, n_shared_experts=1, expert_ff=2048,
        held_experts=tuple(range(32)), routed_scaling=2.5)
    prefill, decode = serving.GenerationRuntime._jit_fns(
        types.SimpleNamespace(cfg=cfg, block_tokens=128))

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {n: spec(s, d) for n, s, d in param_shapes(cfg)}
    pages = {pool: spec((769, 128) + row, jnp.bfloat16)
             for pool, row in M.cache_rows(cfg).items()}
    pages["routed"] = spec(M.routed_shape(cfg))
    if kind == "prefill":
        return prefill, (params, spec((1, 1024)), spec((1,)), pages,
                         spec((1, 8)))
    return decode, (params, spec((48,)), spec((48,)), pages, spec((48, 16)))


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_latent_served_step_compiles_in_place_at_published_widths(
        one_chip, no_cache, kind):
    """The chip's compiler takes both steps of the latent block at the
    published widths: the two pools of latent rows (113 MB each here)
    and the routing counters are aliased to their outputs with no copy
    of a pool; the prefill attends through the tiled kernels and the
    expert layer through the grouped product; the decode holds no
    float32 copy of the gathered history."""
    import re

    step, args = _latent_served_step(kind, one_chip)
    with jax.enable_x64(False):
        text = step.lower(*args).compile().as_text()
    head = next(ln for ln in text.splitlines() if ln.startswith("HloModule"))
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    assert aliased and len(re.findall(r"-alias\)", aliased.group(1))) == 3
    assert not re.findall(
        r"= bf16\[(?:769,128,576|98432,576)\]\S* copy(?:-start)?\(", text)
    assert "ragged-dot" in text and "tpu_custom_call" in text
    if kind == "prefill":
        assert "splash" in text
    else:
        assert not re.findall(r"= f32\[48,2048,(?:576|512)\]", text)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_latent_served_step_hands_back_one_id_a_slot(one_chip, no_cache,
                                                      kind):
    """What the latent block's compiled step hands the host, at the
    published widths: one int32 id a slot, first among its results
    (the pools and the routing counters follow), and no row of the
    65,536-wide head's float32 logits (12.6 MB at 48 slots)."""
    import re

    step, args = _latent_served_step(kind, one_chip)
    with jax.enable_x64(False):
        text = step.lower(*args).compile().as_text()
    head = next(ln for ln in text.splitlines() if ln.startswith("HloModule"))
    results = re.search(r"entry_computation_layout=\{.*\)->\((.*)\)\}",
                        head).group(1)
    bb = 1 if kind == "prefill" else 48
    assert re.match(r"s32\[%d\]\{0\S*\}, " % bb, results), results[:80]
    assert "f32[%d,65536]" % bb not in results
    assert results.count("bf16[769,128,576]") == 2


def test_latent_decode_reads_the_pool_through_the_paged_kernel(one_chip,
                                                              no_cache):
    """The latent decode step at sarvam-105b's published widths attends
    through the paged kernel (``transformer/paged_latent.py``), one
    site for each block kind, and the kernel's custom call carries
    ``attn`` on its scope path (what ``decode_latent_attn_ms`` reads,
    through ``parse_hlo_scopes``).  The step holds no gather of whole
    blocks of latent rows, no transposing fusion of a gathered history
    and no copy of a pool: the kernel reads the pool (769 blocks here)
    as it lies, in the chip's own order of it."""
    import re

    from mxnet_tpu.traceview import scope_path, scopes
    from mxnet_tpu.transformer import paged_latent

    # the blocks' traces are cached by function: trace them anew here
    jax.clear_caches()
    before = paged_latent.site_tally()
    step, args = _latent_served_step("decode", one_chip)
    with jax.enable_x64(False):
        text = step.lower(*args).compile().as_text()
    assert paged_latent.site_tally(before) == {"kernel": 2, "gather": 0}
    names = scopes.parse_hlo_scopes(text)[1]
    kernels = [n for n in names if n.startswith("paged_latent_attention")]
    assert len(kernels) == 2, kernels
    for n in kernels:
        assert "attn" in scope_path(names[n]), names[n]
    assert text.count('custom_call_target="tpu_custom_call"') >= 2
    # whole blocks of rows, gathered (bf16[48,16,128,576], which the
    # gather path then wrote again in the chip's order, a transposing
    # fusion of that shape) or as the gather's flat view
    # (bf16[768,128,576]); the pool itself is 769 blocks
    assert not re.findall(r"bf16\[(?:\d+,)*(?!769,)\d+,128,576\]", text)
    assert not re.findall(
        r"= bf16\[(?:769,128,576|98432,576)\]\S* copy(?:-start)?\(", text)
