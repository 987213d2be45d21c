"""Transformer-LM workload tier (ISSUE 13): pluggable attention as
TRAINABLE kernels on the sp=2 mesh, ZeRO-1 sharded optimizer state vs
the replicated control under the fp64/lr0 methodology, fused
multi-tensor optimizer numerics, exact checkpoint/resume through the
transformer fit path, the chaos kill/resume harness on the new
workload, and the generalized (model-agnostic) autotune leaf path."""
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import env as mxenv
from mxnet_tpu.parallel.attention import attention_reference
from mxnet_tpu.parallel.mesh import current_device_count, make_mesh
from mxnet_tpu.parallel.ring_attention import ring_attention
from mxnet_tpu.parallel.sequence import ulysses_attention
from mxnet_tpu.transformer import (LMTokenIter, TransformerConfig,
                                   TransformerTrainStep, attention_impl,
                                   init_params, make_corpus, param_shapes)

_WORKER = os.path.join(os.path.dirname(__file__), "transformer_worker.py")


def _need_devices(n):
    if current_device_count() < n:
        pytest.skip("needs %d virtual devices" % n)


def _cfg(**kw):
    base = dict(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


def _iter(**kw):
    base = dict(batch_size=4, seq_len=16, vocab_size=64,
                num_sequences=32)
    base.update(kw)
    return LMTokenIter(**base)


# ---------------------------------------------------------------------------
# attention impls as TRAINABLE kernels (sp=2)
# ---------------------------------------------------------------------------
def _qkv(B=2, T=32, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.randn(B, T, H, D), "float32")
    return mk(0), mk(1), mk(2)


def _sharded(fn, mesh, axis="sp"):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis, None, None)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_attention_impls_trainable_sp2(impl):
    """forward AND grad of the sequence-parallel impls == full
    attention at ~1e-6 on the sp=2 mesh — trainable kernels, not just
    inference equivalence."""
    _need_devices(2)
    mesh = make_mesh((2,), ("sp",), jax.devices()[:2])
    q, k, v = _qkv()
    body = ring_attention if impl == "ring" else ulysses_attention
    fn = _sharded(
        lambda a, b, c: body(a, b, c, axis_name="sp", causal=True),
        mesh)

    def loss_sp(q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    np.testing.assert_allclose(np.asarray(fn(q, k, v)),
                               np.asarray(attention_reference(
                                   q, k, v, causal=True)),
                               atol=1e-6)
    g_sp = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_sp, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-5)


def test_ring_causal_edge_blocks():
    """Causal-mask edge steps on the ring: the FULLY-MASKED rotation
    step (device 0 holding device 1's future KV block) must contribute
    NOTHING to the first shard's outputs, while the diagonal block
    stays causal within the shard."""
    _need_devices(2)
    mesh = make_mesh((2,), ("sp",), jax.devices()[:2])
    q, k, v = _qkv(T=16)
    fn = _sharded(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp",
                                       causal=True), mesh)
    out = np.asarray(fn(q, k, v))
    # perturb the SECOND shard's values: positions 0..7 attend only to
    # kv 0..7 (the second-half block is fully masked for them), so
    # their outputs are bit-identical; the second half must change
    v2 = v.at[:, 8:].add(100.0)
    out2 = np.asarray(fn(q, k, v2))
    np.testing.assert_array_equal(out2[:, :8], out[:, :8])
    assert np.abs(out2[:, 8:] - out[:, 8:]).max() > 1.0
    # diagonal block: within the second shard, position 8 sees only
    # kv<=8 — perturbing kv at position 9 leaves q-position 8 alone
    v3 = v.at[:, 9].add(100.0)
    out3 = np.asarray(fn(q, k, v3))
    np.testing.assert_array_equal(out3[:, :9], out[:, :9])


def test_ulysses_heads_not_divisible_raises():
    _need_devices(2)
    mesh = make_mesh((2,), ("sp",), jax.devices()[:2])
    q, k, v = _qkv(H=3)
    fn = _sharded(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp"),
        mesh)
    with pytest.raises(AssertionError, match="divide"):
        fn(q, k, v)
    # and the train step rejects it up front, before any compile
    _need_devices(4)
    mesh4 = make_mesh((2, 2), ("dp", "sp"), jax.devices()[:4])
    step = TransformerTrainStep(_cfg(n_heads=3, d_model=33),
                                mesh=mesh4, attn_impl="ulysses")
    with pytest.raises(ValueError, match="divide"):
        step._build()


def test_flash_rejected_on_sp_mesh():
    """flash over a sequence shard is WRONG math, not a slow path —
    the selector must refuse."""
    from mxnet_tpu.transformer import make_attn_fn

    with pytest.raises(ValueError, match="sequence-sharded"):
        make_attn_fn("flash", "sp")
    with pytest.raises(ValueError, match="sequence-parallel"):
        make_attn_fn("ring", None)


# ---------------------------------------------------------------------------
# training-tier numerics
# ---------------------------------------------------------------------------
def _fit_params(mesh=None, steps=4, **step_kw):
    it = _iter()
    cfg = step_kw.pop("cfg", _cfg())
    s = TransformerTrainStep(cfg, mesh=mesh, seed=0, **step_kw)
    losses = s.fit(it, steps)
    return losses, s.params_numpy(), s


def test_sequence_parallel_matches_single_chip():
    """ring and ulysses TRAINING trajectories on the dp=2 x sp=2 mesh
    match the single-device flash run at fp tolerance — the end-to-end
    proof the two orphaned kernels now carry a real workload."""
    _need_devices(4)
    l1, p1, _ = _fit_params(mesh=None, attn_impl="flash")
    mesh = make_mesh((2, 2), ("dp", "sp"), jax.devices()[:4])
    for impl in ("ring", "ulysses"):
        ls, ps, s = _fit_params(mesh=mesh, attn_impl=impl)
        assert s.attention_impl == impl
        rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l1, ls))
        assert rel < 1e-5, "%s diverged from single-chip: %g" % (impl,
                                                                 rel)


def test_zero1_bitwise_lr0_and_fp64():
    """The fp64/lr0 control methodology applied to ZeRO-1: sharded
    optimizer state must match the replicated control BITWISE on the
    dp=2 mesh."""
    _need_devices(2)
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    # lr=0: params never move; any drift is a sharding bug
    _, p_r, _ = _fit_params(mesh=mesh, zero_stage=0, learning_rate=0.0)
    _, p_z, sz = _fit_params(mesh=mesh, zero_stage=1, learning_rate=0.0)
    assert sz.zero1
    for k in p_r:
        np.testing.assert_array_equal(p_r[k], p_z[k])
    # fp64: reduction-order noise at ~1e-16 per op — psum vs
    # reduce-scatter must produce the same sums, so params stay bitwise
    cfg64 = _cfg(dtype="float64", param_dtype="float64")
    _, p_r, _ = _fit_params(mesh=mesh, cfg=cfg64, zero_stage=0)
    _, p_z, _ = _fit_params(mesh=mesh, cfg=cfg64, zero_stage=1)
    for k in p_r:
        np.testing.assert_array_equal(p_r[k], p_z[k])


def test_zero1_bf16_and_memory():
    """bf16 ZeRO-1 trajectory within ~1e-7 of the replicated control
    (bitwise on this 2-rank mesh, in fact), and the per-rank optimizer
    state measurably ~1/dp of replicated — from the LIVE buffers."""
    _need_devices(2)
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    cfg16 = _cfg(dtype="bfloat16")
    l_r, p_r, s_r = _fit_params(mesh=mesh, cfg=cfg16, zero_stage=0)
    l_z, p_z, s_z = _fit_params(mesh=mesh, cfg=cfg16, zero_stage=1)
    rel = max(abs(a - b) / max(abs(a), 1e-9) for a, b in zip(l_r, l_z))
    assert rel <= 1e-7, "bf16 zero1 drifted: %g" % rel
    rep = s_r.optimizer_state_bytes_per_rank()
    shd = s_z.optimizer_state_bytes_per_rank()
    assert rep > 0 and shd > 0
    assert abs(shd / rep - 0.5) < 0.05, (shd, rep)


def test_fused_multi_tensor_matches_per_key_bitwise():
    """The update that runs leaf by leaf, each leaf in its own shape
    and dtype (optimizer.fused_sgd_mom_grouped, what both train steps
    call), is BITWISE the one formula over the packed flats of each
    dtype (fused_sgd_mom_flat, what ZeRO-1 runs on its shards) — the
    ROADMAP item-5 numerics pin."""
    from mxnet_tpu import optimizer as opt

    rng = np.random.RandomState(0)
    shapes = {"conv": ((64, 64, 3, 3), "float32"),
              "matrix": ((48, 96), "float32"),
              "vector": ((96,), "float32"),
              "half": ((32, 16), "bfloat16"),
              "half_vec": ((7,), "bfloat16")}

    def tree():
        return {k: jnp.asarray(rng.randn(*shape), dtype)
                for k, (shape, dtype) in shapes.items()}

    params, grads, moms = tree(), tree(), tree()
    keys = list(shapes)
    hyper = dict(lr=0.05, momentum=0.9, wd=1e-4)
    new_p, new_m = jax.jit(
        lambda p, g, m: opt.fused_sgd_mom_grouped(keys, p, g, m, **hyper)
    )(params, grads, moms)
    assert set(new_p) == set(new_m) == set(keys)
    for dtype in ("float32", "bfloat16"):
        ks = [k for k in keys if shapes[k][1] == dtype]
        flat_w, flat_m = jax.jit(
            lambda p, g, m: opt.fused_sgd_mom_flat(
                opt.pack_flat([p[k] for k in ks]),
                opt.pack_flat([g[k] for k in ks]),
                opt.pack_flat([m[k] for k in ks]), **hyper)
        )(params, grads, moms)
        refs = [params[k] for k in ks]
        for k, w, m in zip(ks, opt.unpack_flat(flat_w, refs),
                           opt.unpack_flat(flat_m, refs)):
            assert new_p[k].dtype == w.dtype == jnp.dtype(dtype)
            assert new_p[k].shape == shapes[k][0]
            np.testing.assert_array_equal(np.asarray(new_p[k]),
                                          np.asarray(w))
            np.testing.assert_array_equal(np.asarray(new_m[k]),
                                          np.asarray(m))


@pytest.mark.parametrize("n_dev", [1, 2])
def test_fused_train_step_matches_numpy_per_key_loop(n_dev):
    """Three steps of a small FusedTrainStep (one chip, and the
    replicated path behind the bucketed exchange) against a per-key
    loop written out in numpy over the eager path's gradients."""
    _need_devices(n_dev)
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.parallel.dp import FusedTrainStep

    lr, momentum, wd = 0.05, 0.9, 1e-4

    def make():
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(16))
        net.initialize(mx.init.Xavier())
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    X = nd.array(np.random.RandomState(1).rand(8, 12).astype("float32"))
    y = nd.array((np.arange(8) % 16).astype("float32"))

    net = make()
    step = FusedTrainStep(net, loss_fn, learning_rate=lr,
                          momentum=momentum, weight_decay=wd,
                          mesh=make_mesh((n_dev,), ("dp",),
                                         jax.devices()[:n_dev]))
    losses = [float(step(X, y)[0].asnumpy()) for _ in range(3)]

    twin = make()
    twin(X)                                   # settle deferred shapes
    cells = list(twin.collect_params().values())
    moms = [np.zeros(p.shape, "float32") for p in cells]
    theirs = []
    for _ in range(3):
        with autograd.record():
            loss = loss_fn(twin(X), y).mean()
        loss.backward()
        theirs.append(float(loss.asnumpy()))
        for i, p in enumerate(cells):
            w = p.data().asnumpy()
            g = p.grad().asnumpy() + np.float32(wd) * w
            moms[i] = np.float32(momentum) * moms[i] - np.float32(lr) * g
            p.set_data(nd.array(w + moms[i]))
    np.testing.assert_allclose(losses, theirs, rtol=1e-6)
    for p, q in zip(net.collect_params().values(), cells):
        np.testing.assert_allclose(p.data().asnumpy(), q.data().asnumpy(),
                                   rtol=1e-5, atol=1e-7)
    # and the momenta the step carries are the loop's
    for m, mine in zip(step._moms, moms):
        np.testing.assert_allclose(np.asarray(m), mine, rtol=1e-5,
                                   atol=1e-7)


def test_fused_train_step_zero1_matches_replicated():
    """MXNET_ZERO_STAGE threads through parallel/dp.py's conv-workload
    step too: zero1 == replicated bitwise on dp=2, with sharded
    momenta buffers."""
    _need_devices(2)
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel.dp import FusedTrainStep

    def run(stage):
        np.random.seed(0)
        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(16))
        net.initialize(mx.init.Xavier())
        mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
        step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mesh=mesh, learning_rate=0.05,
                              momentum=0.9, zero_stage=stage,
                              bucket_bytes=1024)
        X = nd.random.uniform(shape=(8, 12))
        y = nd.array((np.arange(8) % 16).astype("float32"))
        losses = [float(step(X, y)[0].asnumpy()) for _ in range(3)]
        params = [p.data().asnumpy()
                  for _, p in sorted(net.collect_params().items())]
        return losses, params, step

    l0, p0, s0 = run(0)
    l1, p1, s1 = run(1)
    assert s1.zero1 and not s0.zero1
    assert l0 == l1
    for a, b in zip(p0, p1):
        np.testing.assert_array_equal(a, b)
    assert s1.optimizer_state_bytes_per_rank() < \
        s0.optimizer_state_bytes_per_rank()


def test_remat_policies_numerics():
    """block / attention remat recompute the SAME math — trajectories
    match the no-remat run to fp round-off (XLA fuses the recompute
    differently, so bitwise is not guaranteed; ~1e-7 is)."""
    l_none, p_none, _ = _fit_params(steps=2, remat="none")
    for pol in ("block", "attention"):
        l_p, p_p, _ = _fit_params(steps=2, remat=pol)
        rel = max(abs(a - b) / max(abs(a), 1e-9)
                  for a, b in zip(l_none, l_p))
        assert rel < 1e-6, (pol, rel)
        for k in p_none:
            np.testing.assert_allclose(p_none[k], p_p[k], atol=1e-6,
                                       rtol=1e-5)
    with pytest.raises(ValueError, match="remat policy"):
        _fit_params(steps=1, remat="everything")


# ---------------------------------------------------------------------------
# checkpoint / resume / chaos
# ---------------------------------------------------------------------------
def test_fit_resume_bitwise(tmp_path):
    """Exact resume through the transformer fit path: the ZeRO-1
    sharded momenta ride the elastic manifest and the resumed run is
    BITWISE the uninterrupted control."""
    _need_devices(2)
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    ck = str(tmp_path / "ck")

    sc = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1)
    lc = sc.fit(_iter(), 6)

    sa = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1)
    sa.fit(_iter(), 3, checkpoint_every_n=3, checkpoint_dir=ck)
    # the shard carries sharded momenta through optimizer_states and
    # the manifest digests cover it
    from mxnet_tpu import checkpoint as ckpt

    payload = ckpt.load_checkpoint(ck)
    state = pickle.loads(payload["optimizer_states"])
    assert state["zero_stage"] == 1
    assert len(state["momenta"]) == state["n_buckets"]

    sb = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1)
    lb = sb.fit(_iter(), 6, resume_from=ck)
    assert lb == lc[3:]
    pc, pb = sc.params_numpy(), sb.params_numpy()
    for k in pc:
        np.testing.assert_array_equal(pc[k], pb[k])


def test_zero1_elastic_restage_across_dp(tmp_path):
    """The elastic restage acceptance (ROADMAP item 4's last gap): a
    stage-1 checkpoint written at one dp resumes at ANOTHER — 2→1 and
    1→2 — with the trajectory pinned against the uninterrupted dp=2
    control under the fp64 methodology, and per-rank momenta measured
    at ~1/dp' from the live buffers."""
    _need_devices(2)
    cfg = _cfg(dtype="float64", param_dtype="float64")
    mesh2 = make_mesh((2,), ("dp",), jax.devices()[:2])
    mesh1 = make_mesh((1,), ("dp",), jax.devices()[:1])

    sc = TransformerTrainStep(cfg, mesh=mesh2, seed=0, zero_stage=1)
    lc = sc.fit(_iter(), 6)
    pc = sc.params_numpy()

    # 2 → 1: the sharded flat momenta unpack into the replicated dict
    ck = str(tmp_path / "ck21")
    sa = TransformerTrainStep(cfg, mesh=mesh2, seed=0, zero_stage=1)
    sa.fit(_iter(), 3, checkpoint_every_n=3, checkpoint_dir=ck)
    state = pickle.loads(
        mx.checkpoint.load_checkpoint(ck)["optimizer_states"])
    assert state["zero_stage"] == 1 and state["dp"] == 2
    sb = TransformerTrainStep(cfg, mesh=mesh1, seed=0, zero_stage=1)
    lb = sb.fit(_iter(), 6, resume_from=ck)
    assert not sb.zero1  # dp=1: stage 1 degenerates to replicated
    for a, b in zip(lc[3:], lb):
        assert abs(a - b) < 1e-9, (lc[3:], lb)
    pb = sb.params_numpy()
    for k in pc:
        np.testing.assert_allclose(pc[k], pb[k], rtol=1e-10,
                                   atol=1e-12)

    # 1 → 2: the replicated dict packs back into sharded flats, and
    # the per-rank momenta really shrink to ~1/2
    ck = str(tmp_path / "ck12")
    s1 = TransformerTrainStep(cfg, mesh=mesh1, seed=0, zero_stage=1)
    s1.fit(_iter(), 3, checkpoint_every_n=3, checkpoint_dir=ck)
    s2 = TransformerTrainStep(cfg, mesh=mesh2, seed=0, zero_stage=1)
    l2 = s2.fit(_iter(), 6, resume_from=ck)
    assert s2.zero1
    for a, b in zip(lc[3:], l2):
        assert abs(a - b) < 1e-9, (lc[3:], l2)
    p2 = s2.params_numpy()
    for k in pc:
        np.testing.assert_allclose(pc[k], p2[k], rtol=1e-10,
                                   atol=1e-12)
    per_rank_sharded = s2.optimizer_state_bytes_per_rank()
    per_rank_repl = sb.optimizer_state_bytes_per_rank()
    assert abs(per_rank_sharded / per_rank_repl - 0.5) < 0.05, \
        (per_rank_sharded, per_rank_repl)


def test_resume_rejects_mismatched_bucket_plan(tmp_path):
    """Restage re-slices identical bucket layouts; a CAP change
    between runs still rejects loudly — it cannot re-bucket."""
    _need_devices(2)
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    ck = str(tmp_path / "ck")
    s = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1,
                             bucket_bytes=1024)
    s.fit(_iter(), 2, checkpoint_every_n=2, checkpoint_dir=ck)
    s2 = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1,
                              bucket_bytes=1 << 22)
    with pytest.raises(ValueError, match="bucket"):
        s2.fit(_iter(), 4, resume_from=ck)


@pytest.mark.slow
def test_chaos_kill_resume_e2e(tmp_path):
    """The existing kill/resume harness covers the transformer tier:
    chaos kills the worker mid-fit (exit 137) after a checkpoint
    landed; a fresh process resumes and finishes BITWISE equal to the
    uninterrupted control."""
    _need_devices(2)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_DUMP_DIR"] = str(tmp_path)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (flags +
                        " --xla_force_host_platform_device_count=2"
                        ).strip()
    env.pop("MXNET_CHAOS", None)

    def run(mode, ckdir, out, chaos=None, check=True):
        e = dict(env)
        if chaos:
            e["MXNET_CHAOS"] = chaos
        proc = subprocess.run(
            [sys.executable, _WORKER, mode, ckdir, out],
            env=e, capture_output=True, text=True, timeout=600)
        if check:
            assert proc.returncode == 0, proc.stdout + proc.stderr
        return proc

    ctrl = str(tmp_path / "ctrl.npz")
    run("control", str(tmp_path / "ck_ctrl"), ctrl)

    ck = str(tmp_path / "ck")
    victim = run("victim", ck, str(tmp_path / "victim.npz"),
                 chaos="kill:step=5", check=False)
    assert victim.returncode == 137, victim.stdout + victim.stderr

    res = str(tmp_path / "resume.npz")
    run("resume", ck, res)
    a, b = np.load(ctrl), np.load(res)
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# iterator + knobs + generalized autotune path
# ---------------------------------------------------------------------------
def test_lm_token_iter_contract():
    it = _iter()
    b1 = it.next()
    assert b1.data[0].shape == (4, 16)
    assert str(b1.data[0].dtype) == "int32"
    # labels are the shifted tokens (tied next-token objective)
    d = b1.data[0].asnumpy()
    l = b1.label[0].asnumpy()
    corpus = make_corpus(32, 16, 64, seed=0)
    np.testing.assert_array_equal(d, corpus[:4, :-1])
    np.testing.assert_array_equal(l, corpus[:4, 1:])
    # deterministic across fresh iterators
    it2 = _iter()
    np.testing.assert_array_equal(d, it2.next().data[0].asnumpy())
    # host-only fetch for the decode pool
    it2.reset()
    data, label, pad = it2.next_raw()
    assert isinstance(data[0], np.ndarray) and pad == 0
    np.testing.assert_array_equal(data[0], d)


def test_lm_token_iter_parts_disjoint_exhaustive():
    full = _iter(num_parts=1).data[0][1]
    seen = []
    for part in range(2):
        seen.append(_iter(num_parts=2, part_index=part).data[0][1])
    got = np.concatenate(seen)
    assert got.shape[0] == full.shape[0]
    # strided slices: every row appears exactly once
    assert {r.tobytes() for r in got} == {r.tobytes() for r in full}


def test_lm_token_iter_skip_batches_replay():
    it = _iter()
    it.next(), it.next()
    b3 = it.next().data[0].asnumpy()
    it2 = _iter()
    it2.reset()
    it2.skip_batches(2)
    np.testing.assert_array_equal(b3, it2.next().data[0].asnumpy())


def test_env_knobs(monkeypatch):
    for name in ("MXNET_ATTENTION_IMPL", "MXNET_REMAT_POLICY",
                 "MXNET_ZERO_STAGE"):
        assert mxenv.is_registered(name), name
    monkeypatch.setenv("MXNET_ATTENTION_IMPL", "ulysses")
    assert attention_impl() == "ulysses"
    monkeypatch.setenv("MXNET_ATTENTION_IMPL", "flesh")
    with pytest.raises(ValueError, match="attention impl"):
        attention_impl()
    from mxnet_tpu.parallel.dp import zero1_stage

    monkeypatch.setenv("MXNET_ZERO_STAGE", "3")
    with pytest.raises(ValueError, match="ZERO_STAGE"):
        zero1_stage()
    monkeypatch.setenv("MXNET_ZERO_STAGE", "1")
    assert zero1_stage() == 1
    from mxnet_tpu.remat import remat_policy

    monkeypatch.setenv("MXNET_REMAT_POLICY", "attention")
    assert remat_policy() == "attention"


def test_grad_entries_generalized():
    """scaling.grad_entries consumes any name->leaf mapping or an
    entry list, skips frozen params, and feeds the autotuner for the
    attention-dominated pattern (the resnet50_* names stay as
    wrappers over it)."""
    from mxnet_tpu.parallel import scaling

    # plain arrays
    params = {"a": np.zeros((4, 8), np.float32),
              "b": np.zeros((16,), np.float32)}
    ents = scaling.grad_entries(params)
    assert ents == [("a", (4, 8), "float32"), ("b", (16,), "float32")]
    assert scaling.grad_leaf_bytes(ents) == [128, 64]

    class P:
        def __init__(self, shape, grad_req="write"):
            self.shape, self.dtype = shape, "float32"
            self.grad_req = grad_req

    ents = scaling.grad_entries({"w": P((2, 2)),
                                 "frozen": P((9,), "null")})
    assert [e[0] for e in ents] == ["w"]
    # dtype override (the bf16-wire projection)
    ents = scaling.grad_entries(param_shapes(_cfg()), dtype="bfloat16")
    assert all(e[2] == "bfloat16" for e in ents)
    assert ents[0][0] == "embed"

    # the full tune path over the transformer leaves, no jax needed
    from mxnet_tpu import autotune

    leaf = scaling.grad_leaf_bytes(ents)
    tm = autotune.from_leaf_bytes(leaf, dtype="bfloat16",
                                  step_time_s=0.05,
                                  source={"kind": "transformer-test"})
    tuned = autotune.tune(tm, chips=256)
    assert 0 < tuned["score"]["eff"] <= 1.0
    assert "default_eff" in tuned["score"]


def test_autotune_plan_applies_to_transformer(tmp_path, monkeypatch):
    """A persisted tuned plan (MXNET_AUTOTUNE_PLAN) drives the
    transformer step's bucket caps — the closed loop now covers the
    attention comm pattern."""
    _need_devices(2)
    from mxnet_tpu import autotune
    from mxnet_tpu.autotune import plan as aplan
    from mxnet_tpu.parallel import scaling

    cfg = _cfg()
    ents = scaling.grad_entries(param_shapes(cfg))
    leaf = scaling.grad_leaf_bytes(ents)
    tm = autotune.from_leaf_bytes(leaf, dtype="float32",
                                  step_time_s=0.05,
                                  source={"kind": "transformer-test"})
    tuned = autotune.tune(tm, chips=256)
    path = str(tmp_path / "plan.json")
    aplan.save_plan(tuned, path)
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", path)
    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    step = TransformerTrainStep(cfg, mesh=mesh, seed=0)
    step._build()
    tuning = step.bucket_tuning()
    assert tuning is not None and tuning["plan_path"] == path
    meta = step.bucket_plan_meta()
    assert meta["workload"] == "transformer_lm"
    assert meta.get("autotune", {}).get("plan_path") == path


def test_bucket_plan_rides_flight_header():
    """The transformer step stamps its plan into the flight-recorder
    header like every other workload."""
    _need_devices(2)
    from mxnet_tpu import diagnostics as diag

    mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
    s = TransformerTrainStep(_cfg(), mesh=mesh, seed=0, zero_stage=1)
    it = _iter()
    b = it.next()
    np.asarray(s.step(b.data[0], b.label[0]))
    plan = diag.bucket_plan()
    assert plan is not None
    assert plan.get("workload") == "transformer_lm"
    assert plan.get("zero_stage") == 1


def test_param_shapes_match_init():
    cfg = _cfg()
    shapes = param_shapes(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert [n for n, _, _ in shapes] == list(params)
    for name, shape, dtype in shapes:
        assert tuple(params[name].shape) == shape
        assert str(params[name].dtype) == dtype


def test_loss_learns_bigram_structure():
    """The synthetic stream is learnable: loss drops below the uniform
    floor log(V) within a handful of steps."""
    import math

    s = TransformerTrainStep(_cfg(), seed=0, learning_rate=0.05)
    losses = s.fit(_iter(num_sequences=64, batch_size=8), 12)
    assert losses[-1] < math.log(64) - 0.2, losses


@pytest.mark.parametrize("seq_len,tiles_fit", [(128, True), (48, False)])
def test_step_stamps_how_its_attention_lowers(seq_len, tiles_fit):
    """``attn.kernel_sites`` / ``attn.scan_sites``: the calls of
    flash_attention the traced step holds (one block, traced once, is
    one site however many layers apply it), by how they lower on the
    step's devices.  On the CPU every site is a scan site, also one
    whose shapes the kernels would take on a TPU."""
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import attention

    cfg = _cfg(d_model=128, n_heads=2)          # 64-wide heads
    s = TransformerTrainStep(cfg, seed=0, attn_impl="flash", remat="block")
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, (2, seq_len + 1)).astype("int32")
    profiler.dumps(reset=True)
    profiler.set_state("run")
    try:
        with jax.enable_x64(False):             # as the chip runs
            assert (attention._kernel_tile(
                *(jnp.zeros((1, seq_len, 2, 64)),) * 3, causal=True)
                is not None) == tiles_fit
            for _ in range(2):
                s.step(mx.nd.array(tokens[:, :-1]),
                       mx.nd.array(tokens[:, 1:]))
    finally:
        profiler.set_state("stop")
    assert s._attn_sites == {"kernel": 0, "scan": 1}
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    assert stamped["attn.scan_sites"]["max"] == 1
    assert stamped["attn.scan_sites"]["count"] == 2     # every step
    assert stamped["attn.kernel_sites"]["max"] == 0


@pytest.mark.parametrize("streams,x64,sites", [
    (2, True, {"kernel": 0, "plain": 2}),       # as tier-1 traces
    (2, False, {"kernel": 2, "plain": 0}),      # as the chip runs
    (1, False, {"kernel": 0, "plain": 0})])     # the dense block
def test_step_stamps_how_its_stream_mixing_lowers(streams, x64, sites):
    """``mhc.kernel_sites`` / ``mhc.plain_sites``: the calls of
    hyper_residual the traced step holds (two a traced block), by how
    they lower on the step's
    devices: stamped every step, nought of either by the dense block.
    ``blocks.site_tally`` says what the operands allow (bf16 streams at
    a kernel's widths: ``kernel``, but never under x64); on the CPU's
    mesh every site lowers to the plain formulation."""
    from mxnet_tpu import profiler
    from mxnet_tpu.transformer import blocks

    cfg = _cfg(n_layers=1, d_model=128, n_heads=2, hc_mult=streams,
               dtype="bfloat16")
    s = TransformerTrainStep(cfg, seed=0, attn_impl="flash", remat="block")
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, (2, 17)).astype("int32")
    profiler.dumps(reset=True)
    profiler.set_state("run")
    before = blocks.site_tally()
    try:
        with jax.enable_x64(x64):
            for _ in range(2):
                s.step(mx.nd.array(tokens[:, :-1]),
                       mx.nd.array(tokens[:, 1:]))
    finally:
        profiler.set_state("stop")
    assert blocks.site_tally(since=before) == sites
    assert s._mhc_sites == {"kernel": 0, "plain": sum(sites.values())}
    stamped = profiler.summary()["counters"]["counter"]
    profiler.dumps(reset=True)
    assert stamped["mhc.plain_sites"]["max"] == sum(sites.values())
    assert stamped["mhc.plain_sites"]["count"] == 2     # every step
    assert stamped["mhc.kernel_sites"]["max"] == 0
    assert stamped["mhc.kernel_sites"]["count"] == 2
