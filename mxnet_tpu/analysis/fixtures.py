"""Seeded-violation fixtures proving the auditor catches each defect
class.  Every fixture is a tiny traced program carrying EXACTLY one
planted bug; the self-test (and tests/test_static_analysis.py) asserts
the matching check flags it and the clean fixture passes everything.

All fixtures trace on whatever devices exist (a 1-device CPU mesh is
enough — collective eqns appear in the jaxpr regardless of mesh size),
and the donation fixture never allocates: 100 MB exists only as a
ShapeDtypeStruct.
"""
from __future__ import annotations

from typing import Dict

__all__ = [
    "rank_dependent_traces", "undonated_lowered", "donated_lowered",
    "upcast_jaxpr", "host_sync_jaxpr", "clean_step", "UNDONATED_BYTES",
    "remat_twin_jaxprs", "noop_remat_jaxpr",
    "decode_bucket_violation", "decode_bucket_clean",
    "sparse_gradient_violation", "sparse_gradient_clean",
    "SPARSE_FIXTURE_VOCAB", "SPARSE_FIXTURE_DIM",
]

UNDONATED_BYTES = 100 * 1024 * 1024  # the planted 100MB param
SPARSE_FIXTURE_VOCAB = 512   # the planted dense-scatter table dims
SPARSE_FIXTURE_DIM = 8


def _mesh():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def _shard_map(fn, mesh, n_in):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    return shard_map(fn, mesh=mesh, in_specs=(P(),) * n_in,
                     out_specs=P(), check_vma=False)


def rank_dependent_traces() -> Dict[str, object]:
    """Two traces of 'the same' step whose gradient dict arrived in a
    different insertion order on each rank — the classic way a bucket
    plan emits a rank-dependent collective order.  Returns
    {label: jaxpr} for check_collective_uniformity, which must flag
    the schedule divergence."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    mesh = _mesh()

    def step_for(key_order):
        def local(a, b):
            grads = dict()
            grads["w_small"] = a
            grads["w_big"] = b
            out = 0.0
            for k in key_order:   # per-rank iteration order
                out = out + jnp.sum(lax.psum(grads[k], "dp"))
            return out

        return _shard_map(local, mesh, 2)

    a = jnp.ones((4,), jnp.float32)
    b = jnp.ones((128,), jnp.float32)
    return {
        "rank0": jax.make_jaxpr(step_for(("w_small", "w_big")))(a, b),
        "rank1": jax.make_jaxpr(step_for(("w_big", "w_small")))(a, b),
    }


def undonated_lowered():
    """A param-update step whose 100MB parameter buffer is a jit input
    but NOT donated: the program holds old + new params in HBM at
    once.  Lowered from abstract specs — nothing is allocated."""
    import jax
    import numpy as np

    def sgd(params, grads):
        return params - 0.05 * grads

    spec = jax.ShapeDtypeStruct((UNDONATED_BYTES // 4,), np.float32)
    return jax.jit(sgd).lower(spec, spec)  # no donate_argnums: the bug


def donated_lowered():
    """The fixed twin of :func:`undonated_lowered`: params donated for
    the in-place update, the consumed grads buffer donated as scratch."""
    import jax
    import numpy as np

    def sgd(params, grads):
        return params - 0.05 * grads

    spec = jax.ShapeDtypeStruct((UNDONATED_BYTES // 4,), np.float32)
    return jax.jit(sgd, donate_argnums=(0, 1)).lower(spec, spec)


def upcast_jaxpr():
    """A declared-bf16 matmul whose operands were silently cast to f32
    first — the MXU-throughput-halving upcast the dtype check hunts."""
    import jax
    import jax.numpy as jnp

    def fwd(x):
        y = x.astype(jnp.float32)   # the silent upcast
        return (y @ y.T).astype(jnp.bfloat16)

    x = jax.ShapeDtypeStruct((8, 8), jnp.bfloat16)
    return jax.make_jaxpr(fwd)(x)


def host_sync_jaxpr():
    """A step with a host callback buried under a scan: one host
    round-trip PER STEP of the scan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    def body(c, x):
        r = jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((), np.float32),
            x)
        return c + r, x

    def steps(xs):
        out, _ = lax.scan(body, jnp.float32(0), xs)
        return out

    return jax.make_jaxpr(steps)(jax.ShapeDtypeStruct((4,), np.float32))


def _stage_chain_grad(checkpoint_stages):
    """Gradient program over a 6-layer matmul chain, optionally with
    each 2-layer 'stage' under ``jax.checkpoint`` — the minimal
    stand-in for a conv-stage remat plan.  Without checkpoints every
    layer activation is a live backward residual; with them only the 3
    stage boundaries survive the forward sweep."""
    import jax
    import jax.numpy as jnp

    def stage(x, w1, w2):
        return jnp.tanh(jnp.tanh(x @ w1) @ w2)

    def loss(x, ws):
        for i in range(0, 6, 2):
            f = stage if not checkpoint_stages else \
                jax.checkpoint(stage)
            x = f(x, ws[i], ws[i + 1])
        return jnp.sum(x)

    def grad_fn(x, ws):
        return jax.grad(loss, argnums=1)(x, ws)

    x = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    ws = [jax.ShapeDtypeStruct((256, 256), jnp.float32)] * 6
    return jax.make_jaxpr(grad_fn)(x, ws)


def remat_twin_jaxprs():
    """(remat_jaxpr, twin_jaxpr): the SAME stage-chain gradient traced
    with per-stage ``jax.checkpoint`` and without.  The remat program
    must carry remat eqns AND a strictly lower top-level peak of live
    residual bytes — the effectiveness evidence the auditor demands of
    a real remat plan."""
    return _stage_chain_grad(True), _stage_chain_grad(False)


def noop_remat_jaxpr():
    """A program whose builder DECLARED a remat policy but whose trace
    contains no remat eqns (the policy string matched no block — the
    planted no-op): check_remat_effectiveness must flag it."""
    return _stage_chain_grad(False)


def decode_bucket_violation():
    """A generation decode history with TWO planted bugs for
    check_decode_buckets: a traced (batch=3, cache_len=48) that is no
    cell of the declared 2x2 plan (an undeclared shape compiled under
    traffic), and a compile ledger holding 6 compiles against 4 plan
    cells (steady-state recompiles).  Returns (plan, observed,
    compile_counts)."""
    plan = [(1, 16), (1, 32), (4, 16), (4, 32)]
    observed = [(1, 16), (4, 32), (3, 48)]   # the rogue shape
    counts = {"gen_decode:fx:v1:1x16": 1, "gen_decode:fx:v1:1x32": 1,
              "gen_decode:fx:v1:4x16": 3,   # recompiled under traffic
              "gen_decode:fx:v1:4x32": 1}
    return plan, observed, counts


def decode_bucket_clean():
    """The fixed twin: every observed shape is a plan cell and every
    cell compiled exactly once — zero findings."""
    plan = [(1, 16), (1, 32), (4, 16), (4, 32)]
    observed = [(1, 16), (4, 32), (4, 16)]
    counts = {"gen_decode:fx:v1:1x16": 1, "gen_decode:fx:v1:1x32": 1,
              "gen_decode:fx:v1:4x16": 1, "gen_decode:fx:v1:4x32": 1}
    return plan, observed, counts


def sparse_gradient_violation():
    """A 'sparse' embedding step built WRONG: the full (vocab, dim)
    table is a jit input, so jax's gather VJP scatter-adds the batch
    cotangents into a vocab-sized zeros — the dense gradient buffer
    check_sparse_gradients(vocab=512) must flag."""
    import jax
    import jax.numpy as jnp

    V, D = SPARSE_FIXTURE_VOCAB, SPARSE_FIXTURE_DIM

    def loss(table, ids):
        return jnp.sum(jnp.take(table, ids, axis=0) ** 2)

    def grad_fn(table, ids):
        return jax.grad(loss)(table, ids)

    return jax.make_jaxpr(grad_fn)(
        jax.ShapeDtypeStruct((V, D), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.int32))


def sparse_gradient_clean():
    """The fixed twin, shaped like recommender/model.py's sparse step:
    the jit sees only the PULLED (unique_rows<=batch, dim) block plus
    the host-computed inverse map, so the gather VJP's scatter stays in
    batch space and the (vocab, dim) table exists nowhere — zero
    findings at the same vocab."""
    import jax
    import jax.numpy as jnp

    D = SPARSE_FIXTURE_DIM

    def loss(rows_data, inverse):
        return jnp.sum(jnp.take(rows_data, inverse, axis=0) ** 2)

    def grad_fn(rows_data, inverse):
        return jax.grad(loss)(rows_data, inverse)

    return jax.make_jaxpr(grad_fn)(
        jax.ShapeDtypeStruct((32, D), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.int32))


def clean_step():
    """A well-formed bucketed train step: bf16 matmul, deterministic
    psum schedule, donated params.  Returns (fn, specs) suitable for
    ``audit_step`` — every check must pass."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    mesh = _mesh()

    def local(params, data):
        h = data.astype(jnp.bfloat16) @ params
        loss = jnp.sum(h.astype(jnp.float32))
        grads = jax.grad(
            lambda p: jnp.sum((data.astype(jnp.bfloat16) @ p)
                              .astype(jnp.float32)))(params)
        grads = lax.psum(grads, "dp")
        return params - grads.astype(params.dtype) * 0.05, loss

    fn = jax.jit(_shard_map(local, mesh, 2), donate_argnums=(0,))
    specs = (jax.ShapeDtypeStruct((16, 16), jnp.bfloat16),
             jax.ShapeDtypeStruct((8, 16), jnp.bfloat16))
    return fn, specs
