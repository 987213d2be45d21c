"""Sequence/context parallelism tests on the 8-device virtual CPU mesh:
flash attention vs reference numerics, ring attention and Ulysses all-to-all
SP vs single-device attention, including causal masking and gradients."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import (
    attention_reference,
    flash_attention,
    make_mesh,
    pallas_flash_attention,
    ring_attention_sharded,
    ulysses_attention_sharded,
)


def _qkv(B=2, T=32, H=4, D=8, seed=0, dtype="float32"):
    rng = np.random.RandomState(seed)
    shape = (B, T, H, D)
    return (jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(rng.randn(*shape), dtype),
            jnp.asarray(rng.randn(*shape), dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_cross_attention_lengths():
    q, _, _ = _qkv(T=16)
    _, k, v = _qkv(T=32, seed=1)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_padding_blocks():
    # Tk=20 not divisible by block 8 → padding path
    q, _, _ = _qkv(T=20)
    _, k, v = _qkv(T=20, seed=1)
    ref = attention_reference(q, k, v)
    out = flash_attention(q, k, v, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_grad_matches_reference():
    q, k, v = _qkv(T=16)

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_size=8) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_pallas_interpret_matches_reference():
    """Pallas kernel in interpreter mode (no TPU in CI) vs reference."""
    q, k, v = _qkv(B=1, T=256, H=2, D=8)
    ref = attention_reference(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _qkv_widths(T, D, Dv, seed=3):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, T, 2, w), "float32")
                 for w in (D, D, Dv))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("widths", [(16, 16), (24, 16)],
                         ids=["equal", "narrower_values"])
def test_kernel_path_output_and_gradients(causal, widths):
    """The lowering the TPU takes, under the Pallas interpreter: the
    output and the gradients of q, k and v against the oracle, with a
    scale that is not the default and values narrower than keys."""
    q, k, v = _qkv_widths(256, *widths)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v, causal=causal, sm_scale=0.2)
            return (out ** 2).sum(), out
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    g_ref, ref = loss(attention_reference)
    g_ker, out = loss(functools.partial(
        pallas_flash_attention, block_q=128, block_k=128, interpret=True))
    assert out.shape == ref.shape == (1, 256, 2, widths[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    for a, b in zip(g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


def test_dispatch_takes_the_scan_where_the_tiles_do_not_fit():
    """What flash_attention can observe decides, and the tally says what
    it decided: a length no tile divides, unequal lengths under causal,
    a head width the kernels do not take and a trace under x64 are scan
    sites; a shape the tiles take is a kernel site, which off the TPU
    still lowers to the scan (the same numbers as the scan called by its
    own name, and a gradient through ``jax.checkpoint``)."""
    from mxnet_tpu.parallel import attention

    def sites(fn, *args):
        before = attention.site_tally()
        return fn(*args), attention.site_tally(since=before)

    def ref(q, k, v):
        return attention_reference(q, k, v, causal=True)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    fits = _qkv_widths(256, 64, 64)
    _, new = sites(flash, *fits)            # the tests run x64
    assert new == {"kernel": 0, "scan": 1}
    with jax.enable_x64(False):             # the chip runs jax's default
        q, k, v = _qkv_widths(200, 64, 64)
        out, new = sites(flash, q, k, v)
        assert new == {"kernel": 0, "scan": 1}
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref(q, k, v)), atol=1e-5)
        q, k, v = fits
        _, new = sites(flash, q[:, :128], k, v)
        assert new == {"kernel": 0, "scan": 1}
        _, new = sites(flash, q[..., :8], k[..., :8], v)
        assert new == {"kernel": 0, "scan": 1}

        for causal in (False, True):
            fn = jax.jit(functools.partial(flash_attention, causal=causal))
            text, new = sites(lambda: fn.lower(q, k, v).compile().as_text())
            assert new == {"kernel": 1, "scan": 0}
            assert "while" in text and "tpu_custom_call" not in text
        got, new = sites(flash, q, k, v)
        assert new == {"kernel": 1, "scan": 0}
        want = attention._scan_attention(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

        def grad(fn):
            return jax.grad(lambda q, k, v: (jax.checkpoint(fn)(q, k, v)
                                             ** 2).sum(), (0, 1, 2))(q, k, v)

        g, new = sites(grad, flash)
        assert new == {"kernel": 1, "scan": 0}
        for a, b in zip(grad(ref), g):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_8dev(causal):
    mesh = make_mesh((8,), ("sp",))
    q, k, v = _qkv(B=2, T=64, H=4, D=8)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_grad():
    mesh = make_mesh((4,), ("sp",))
    q, k, v = _qkv(B=1, T=32, H=2, D=4)

    def loss_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_8dev(causal):
    mesh = make_mesh((8,), ("sp",))
    q, k, v = _qkv(B=2, T=64, H=8, D=8)  # H divisible by 8
    ref = attention_reference(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_grad():
    mesh = make_mesh((4,), ("sp",))
    q, k, v = _qkv(B=1, T=32, H=4, D=4)

    def loss_u(q, k, v):
        return (ulysses_attention_sharded(q, k, v, mesh, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_u, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_long_sequence_memory():
    """Ring attention on a long sequence (T=1024) stays blockwise — just a
    smoke test that it runs and matches on a bigger shape."""
    mesh = make_mesh((8,), ("sp",))
    q, k, v = _qkv(B=1, T=1024, H=2, D=8)
    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_pallas_raises_instead_of_falling_back():
    """A caller that asked for the kernel never gets the scan silently:
    off-TPU without interpret=True is an error, and so are shapes the
    blocks do not divide."""
    q, k, v = _qkv(B=1, T=256, H=2, D=8)
    with pytest.raises(RuntimeError, match="Mosaic"):
        pallas_flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128)
    with pytest.raises(ValueError, match="do not divide"):
        pallas_flash_attention(q[:, :200], k[:, :200], v[:, :200],
                               block_q=128, block_k=128, interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        pallas_flash_attention(q, k, v, block_q=64, block_k=64,
                               interpret=True)
    with pytest.raises(ValueError, match="causal needs"):
        pallas_flash_attention(q[:, :128], k, v, causal=True,
                               block_q=128, block_k=128, interpret=True)
