"""Sequence/context parallelism tests on the 8-device virtual CPU mesh:
flash attention vs reference numerics, ring attention and Ulysses all-to-all
SP vs single-device attention, including causal masking and gradients."""
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
    q, k, v = _qkv(B=1, T=16, H=2, D=8)
    ref = attention_reference(q, k, v, causal=True)
    out = pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


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
    q, k, v = _qkv(B=1, T=16, H=2, D=8)
    with pytest.raises(RuntimeError, match="Mosaic"):
        pallas_flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    q, k, v = _qkv(B=1, T=20, H=2, D=8)
    with pytest.raises(ValueError, match="do not divide"):
        pallas_flash_attention(q, k, v, block_q=8, block_k=8,
                               interpret=True)
    with pytest.raises(ValueError, match="causal needs"):
        pallas_flash_attention(q[:, :8], k[:, :16], v[:, :16], causal=True,
                               block_q=8, block_k=8, interpret=True)
