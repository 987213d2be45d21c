"""Weights from ``--seed``, made on the device.

A leaf is ``(name, shape, init)`` with ``init`` either ``("normal",
std)`` or ``("const", value)``.  Every value is representable in
bfloat16, so a bfloat16 program and the float32 reference start from
the same numbers.  A leaf's values depend only on the seed and the
leaf's position, so any one leaf can be made again later (the check
does, instead of keeping a second copy of the model on the chip).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

LeafInit = Tuple[str, Tuple[int, ...], Tuple[str, float]]


def root_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, index: int, shape, init, dtype):
    how, value = init
    if how == "const":
        return jnp.full(shape, value, dtype)
    sub = jax.random.fold_in(key, index)
    x = value * jax.random.normal(sub, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(dtype)


@functools.partial(jax.jit, static_argnames=("specs", "dtype"))
def _make_all(key, specs, dtype):
    return {name: _leaf(key, i, shape, init, dtype)
            for i, name, shape, init in specs}


def make_all(seed: int, specs: Sequence[LeafInit], dtype,
             only=None) -> Dict:
    """Every leaf (or those named in ``only``) in ONE jitted call, as
    ``{name: array}``.  A leaf's values depend on its position in the
    full list, whichever leaves are asked for."""
    wanted = tuple((i, n, tuple(s), tuple(init))
                   for i, (n, s, init) in enumerate(specs)
                   if only is None or n in only)
    return _make_all(root_key(seed), wanted, jnp.dtype(dtype).name)


@functools.partial(jax.jit, static_argnames=("shape", "init"))
def _change_norm(key, now, index, shape, init):
    start = _leaf(key, index, shape, init, jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(now.astype(jnp.float32) - start)))


def change_norms(seed: int, specs: Sequence[LeafInit],
                 now: Dict) -> Dict:
    """``||now[leaf] - start[leaf]||`` for every leaf in ``now``, each
    start value made again from the seed inside its own small program."""
    key = root_key(seed)
    out = {}
    for i, (name, shape, init) in enumerate(specs):
        if name in now:
            out[name] = _change_norm(key, now[name], i, tuple(shape),
                                     tuple(init))
    return out


@jax.jit
def norms(tree: Dict) -> Dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}
