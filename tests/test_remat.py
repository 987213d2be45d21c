"""MXNET_BACKWARD_DO_MIRROR — the remat/mirror memory knob.

Reference contract: src/executor/graph_executor.cc:249 (InitFullGraph
mirror augmentation) recomputes activation/BN class nodes in backward to
trade compute for memory; example/image-classification/README.md:370-373
documents the batch-doubling trade.  Here the knob wraps the traced
program in jax.checkpoint with a conv/matmul-saveable policy (remat.py).

Tested: env parsing; gradient equivalence with the knob on vs off on
BOTH the gluon/CachedOp path and the symbolic executor path; and that
the policy genuinely drops activation-sized residuals (the memory
mechanism, asserted via jax.ad_checkpoint.print_saved_residuals).
"""
import contextlib
import io
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, remat


@contextlib.contextmanager
def _mirror(value):
    old = os.environ.get("MXNET_BACKWARD_DO_MIRROR")
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ["MXNET_BACKWARD_DO_MIRROR"]
        else:
            os.environ["MXNET_BACKWARD_DO_MIRROR"] = old


def test_env_parsing():
    for v, expect in [("0", False), ("", False), ("false", False),
                      ("1", True), ("2", True), ("true", True)]:
        with _mirror(v):
            assert remat.mirror_enabled() is expect


def _small_conv_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1),
            gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"),
            gluon.nn.Conv2D(8, 3, padding=1),
            gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"),
            gluon.nn.GlobalAvgPool2D(),
            gluon.nn.Dense(4))
    return net


def _gluon_grads(mirror):
    mx.random.seed(7)
    np.random.seed(7)
    net = _small_conv_net()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = nd.random.uniform(shape=(2, 3, 8, 8))
    with _mirror(mirror):
        with autograd.record():
            out = net(x)
            loss = (out ** 2).mean()
        loss.backward()
    return [p.grad().asnumpy() for p in net.collect_params().values()
            if p.grad_req != "null"]


def test_gluon_cachedop_grads_match():
    g_off = _gluon_grads("0")
    g_on = _gluon_grads("1")
    assert len(g_off) == len(g_on)
    for a, b in zip(g_off, g_on):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _module_grads(mirror):
    mx.random.seed(11)
    np.random.seed(11)
    data = mx.sym.Variable("data")
    x = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1))
    x = mx.sym.BatchNorm(x, fix_gamma=False)
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=4)
    sym = mx.sym.SoftmaxOutput(x, name="softmax")
    with _mirror(mirror):
        mod = mx.mod.Module(sym, label_names=("softmax_label",))
        mod.bind(data_shapes=[("data", (2, 3, 8, 8))],
                 label_shapes=[("softmax_label", (2,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian"))
        batch = mx.io.DataBatch(
            data=[nd.array(np.random.rand(2, 3, 8, 8).astype("float32"))],
            label=[nd.array(np.array([0.0, 1.0], "float32"))])
        mod.forward(batch, is_train=True)
        mod.backward()
        return [v.asnumpy() for v in mod._exec.grad_dict.values()
                if v is not None]


def test_executor_grads_match():
    g_off = _module_grads("0")
    g_on = _module_grads("1")
    assert len(g_off) == len(g_on) and len(g_on) > 0
    for a, b in zip(g_off, g_on):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_policy_drops_activation_residuals():
    """The memory mechanism itself: under the mirror policy only
    conv/matmul outputs survive as residuals; BN/relu intermediates
    (activation-sized f32[2,8,8,8] here) are rematerialized."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.ad_checkpoint import print_saved_residuals

    def f(p, x):
        for w, g, b in p:
            x = lax.conv_general_dilated(
                x, w, (1, 1), [(1, 1), (1, 1)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            m = x.mean(axis=(0, 2, 3))
            v = ((x - m[None, :, None, None]) ** 2).mean(axis=(0, 2, 3))
            x = (x - m[None, :, None, None]) * \
                (g * lax.rsqrt(v + 1e-5))[None, :, None, None] + \
                b[None, :, None, None]
            x = jnp.maximum(x, 0)
        return (x ** 2).mean()

    p = [(jnp.ones((8, 8, 3, 3)) * 0.01, jnp.ones(8), jnp.zeros(8))
         for _ in range(3)]
    x = jnp.ones((2, 8, 8, 8))

    def n_activation_residuals(fn):
        s = io.StringIO()
        with contextlib.redirect_stdout(s):
            print_saved_residuals(fn, p, x)
        return sum(1 for ln in s.getvalue().splitlines()
                   if "[2,8,8,8]" in ln)

    with _mirror("1"):
        wrapped = remat.maybe_checkpoint(f)
        assert wrapped is not f, "mirror on must wrap"
        plain, mirrored = n_activation_residuals(f), \
            n_activation_residuals(wrapped)
    # plain keeps BN/relu intermediates; mirrored keeps ~one conv output
    # per layer (+ the input)
    assert mirrored < plain, (plain, mirrored)
    assert mirrored <= len(p) + 1, (plain, mirrored)

    with _mirror("0"):
        assert remat.maybe_checkpoint(f) is f, "mirror off must be identity"


# ---------------------------------------------------------------------------
# Conv-tier scoped remat (MXNET_REMAT_POLICY=stage / conv_block):
# blocks declaring a ``_remat_scope`` (the resnet zoo marks stages and
# residual units) are wrapped in jax.checkpoint when traced under a
# CachedOp, keeping only scope-boundary residuals live.  Pinned with an
# exact-arithmetic conv net (integer inputs, 1/4-quantized weights,
# power-of-two pooling windows): recompute reproduces the forward
# exactly, so the remat trajectory must match the no-remat control to
# fp round-off on the single-device AND bucketed-dp paths.
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _policy(value):
    old = os.environ.get("MXNET_REMAT_POLICY")
    os.environ["MXNET_REMAT_POLICY"] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ["MXNET_REMAT_POLICY"]
        else:
            os.environ["MXNET_REMAT_POLICY"] = old


def _marked_conv_net(seed=13):
    """Two stages of two conv units, markers at BOTH tiers (the same
    shape the zoo's resnets carry), weights quantized to multiples of
    1/4 so {-1,0,1} inputs keep every intermediate exact in fp32."""
    mx.random.seed(seed)
    np.random.seed(seed)

    def unit(f):
        u = gluon.nn.HybridSequential()
        u.add(gluon.nn.Conv2D(f, 3, padding=1, activation="relu"))
        u._remat_scope = "conv_block"
        return u

    def stage(f):
        s = gluon.nn.HybridSequential()
        s.add(unit(f), unit(f))
        s._remat_scope = "stage"
        return s

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(stage(4), stage(8),
                gluon.nn.GlobalAvgPool2D(),   # 8x8 window: /64, exact
                gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 3, 8, 8), "float32")))  # settle shapes
    for p in net.collect_params().values():
        p.set_data(nd.array(np.round(p.data().asnumpy() * 4.0) / 4.0))
    return net


def _conv_traj(policy, n_dp=1, steps=3, accum=None):
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    with _policy(policy):
        net = _marked_conv_net()
        mesh = make_mesh((n_dp,), ("dp",))
        step = FusedTrainStep(net, gluon.loss.L2Loss(), mesh=mesh,
                              learning_rate=0.25, momentum=0.5,
                              accum_steps=accum)
        rng = np.random.RandomState(2)
        X = nd.array(rng.randint(-1, 2, (8, 3, 8, 8)).astype("float32"))
        y = nd.array(rng.randint(-1, 2, (8, 4)).astype("float32"))
        losses = [float(step(X, y)[0].asnumpy()) for _ in range(steps)]
    params = {k.split("_", 1)[-1]: p.data().asnumpy()
              for k, p in net.collect_params().items()}
    return losses, params


def _assert_traj_equal(a, b):
    (la, pa), (lb, pb) = a, b
    np.testing.assert_allclose(la, lb, rtol=1e-6, atol=1e-7)
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


def test_conv_stage_trajectory_matches_none():
    _assert_traj_equal(_conv_traj("none"), _conv_traj("stage"))


def test_conv_block_trajectory_matches_none():
    _assert_traj_equal(_conv_traj("none"), _conv_traj("conv_block"))


def test_conv_stage_trajectory_matches_none_dp2_bucketed():
    """Same identity through the bucketed shard_map exchange."""
    from mxnet_tpu.parallel.mesh import current_device_count

    if current_device_count() < 2:
        pytest.skip("needs 2 devices")
    _assert_traj_equal(_conv_traj("none", n_dp=2),
                       _conv_traj("stage", n_dp=2))


def test_conv_stage_composes_with_grad_accum():
    """Per-stage remat + microbatch accumulation — the ISSUE 17 pair —
    still lands on the no-remat full-batch trajectory."""
    _assert_traj_equal(_conv_traj("none", accum=1),
                       _conv_traj("stage", accum=2))


def test_conv_policies_rematerialize_at_their_tier():
    """The traced step program carries one checkpoint eqn per marked
    block at the SELECTED tier: 2 stages under ``stage``, 4 units under
    ``conv_block`` — and the step's audit metadata declares the policy
    so the analysis auditor can cross-check it offline."""
    from mxnet_tpu import diagnostics as diag
    from mxnet_tpu.analysis import auditor

    for policy, expect in (("stage", 2), ("conv_block", 4)):
        diag.reset_recompile_stats()
        _conv_traj(policy, steps=1)
        fn, specs, meta = diag.recorded_steps()["FusedTrainStep.step"]
        assert meta["remat_policy"] == policy
        _findings, am = auditor.audit_step(
            fn, specs, site="test.remat.%s" % policy,
            remat_policy=policy)
        assert am["n_remat_eqns"] == expect, (policy, am)


def _stage_symbol():
    """Hand-written conv symbol with reference stage naming
    (``stageN_unitM_...``) — the executor's symbol-path segmentation
    keys on these names."""
    d = mx.sym.Variable("data")
    x = mx.sym.Activation(
        mx.sym.Convolution(d, num_filter=4, kernel=(3, 3), pad=(1, 1),
                           name="stem_conv"), act_type="relu")
    for s in (1, 2):
        x = mx.sym.Activation(
            mx.sym.Convolution(x, num_filter=4, kernel=(3, 3),
                               pad=(1, 1),
                               name="stage%d_unit1_conv1" % s),
            act_type="relu", name="stage%d_unit1_relu1" % s)
    fc = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=2,
                               name="head_fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _symbol_fit_params(policy):
    np.random.seed(5)
    mx.random.seed(5)
    X = np.random.rand(32, 3, 8, 8).astype("float32") - 0.5
    y = (np.random.rand(32) > 0.5).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=8,
                           label_name="softmax_label")
    with _policy(policy):
        mod = mx.mod.Module(_stage_symbol(),
                            label_names=("softmax_label",))
        mod.fit(it, num_epoch=3,
                optimizer_params=(("learning_rate", 0.05),))
    params, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in params.items()}


@pytest.mark.parametrize("bulk", [1, 15], ids=["per_batch", "bulk"])
def test_symbol_path_stage_trajectory_matches_none(bulk):
    """Module.fit (symbol->apply path) honors MXNET_REMAT_POLICY=stage
    via the executor's stage segmentation: the 3-epoch trained params
    must match the policy=none run (a remat segment threads its exact
    boundary values — same math, fewer residuals).  The fit mode is
    said here, not inherited from whatever an earlier test left in the
    engine: the K-step scan matches bitwise; per batch XLA:CPU rounds
    one recomputed bias gradient differently (1 ulp), so that path is
    held to the tolerance of the gluon trajectories above."""
    with mx.engine.bulk(bulk):
        p_none = _symbol_fit_params("none")
        p_stage = _symbol_fit_params("stage")
    assert set(p_none) == set(p_stage)
    for k in p_none:
        if bulk > 1:
            np.testing.assert_array_equal(p_none[k], p_stage[k],
                                          err_msg=k)
        else:
            np.testing.assert_allclose(p_none[k], p_stage[k], rtol=1e-6,
                                       atol=1e-7, err_msg=k)


def test_symbol_path_stage_rematerializes():
    """The segmentation actually fires: the traced symbol train step
    carries one checkpoint eqn per stage under ``stage`` and zero under
    ``none``."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.analysis.auditor import count_remat_eqns

    def n_eqns(policy):
        with _policy(policy):
            ex = _stage_symbol().simple_bind(
                mx.cpu(), data=(4, 3, 8, 8), softmax_label=(4,))
            step = ex._build_train_step(False)
            args = {k: v._data for k, v in ex.arg_dict.items()}
            aux = {k: v._data for k, v in ex.aux_dict.items()}
            cots = (jnp.ones((4, 2), "float32"),)
            jaxpr = jax.make_jaxpr(
                lambda a, x_, k: step(a, x_, k, cots, 1))(
                    args, aux, jax.random.PRNGKey(0))
        return count_remat_eqns(jaxpr)

    assert n_eqns("none") == 0
    assert n_eqns("stage") == 2


def test_fit_trains_with_mirror_on():
    """End to end: Module.fit converges with the knob on (the knob must
    not break the training loop — reference users flip only the env)."""
    mx.random.seed(3)
    np.random.seed(3)
    n = 64
    X = np.random.rand(n, 1, 8, 8).astype("float32")
    y = (X.mean(axis=(1, 2, 3)) > 0.5).astype("float32")
    X[y > 0.5] += 0.5
    data = mx.sym.Variable("data")
    x = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1))
    x = mx.sym.Activation(x, act_type="relu")
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=2)
    sym = mx.sym.SoftmaxOutput(x, name="softmax")
    it = mx.io.NDArrayIter(X, y, batch_size=16, label_name="softmax_label")
    with _mirror("1"):
        mod = mx.mod.Module(sym, label_names=("softmax_label",))
        metric = mx.metric.Accuracy()
        mod.fit(it, num_epoch=6, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.1),),
                eval_metric=metric, initializer=mx.init.Xavier())
    it.reset()
    metric2 = mx.metric.Accuracy()
    score = mod.score(it, metric2)
    acc = dict([score] if isinstance(score, tuple) else score).get(
        "accuracy", metric2.get()[1])
    assert acc > 0.8, acc
