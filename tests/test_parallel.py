"""Data-parallel / mesh tests on the 8-device virtual CPU mesh
(tests SURVEY.md §2.3's DP strategy; the reference tested multi-device on
CPU too — tests/python/unittest/test_model_parallel.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, sym
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.mesh import make_mesh, current_device_count
from mxnet_tpu.parallel.dp import FusedTrainStep, shard_batch, replicate


def _need_devices(n):
    if current_device_count() < n:
        pytest.skip("needs %d devices" % n)


def test_make_mesh():
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    assert mesh.axis_names == ("dp",)
    mesh2 = make_mesh((4, 2), ("dp", "mp"))
    assert mesh2.devices.shape == (4, 2)
    with pytest.raises(ValueError):
        make_mesh((64,), ("dp",))


def test_shard_and_replicate():
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    x = nd.ones((16, 4))
    shard_batch(x, mesh)
    assert "dp" in str(x._data.sharding.spec)
    w = nd.ones((4, 4))
    replicate(w, mesh)
    np.testing.assert_allclose(x.asnumpy(), 1.0)


def test_fused_train_step_dp8():
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, learning_rate=0.5, momentum=0.9)
    np.random.seed(0)
    X = np.random.rand(32, 10).astype("float32")
    y = (X @ np.arange(10) > 4.5).astype("float32")  # separable rule
    X, y = nd.array(X), nd.array(y)
    losses = []
    for _ in range(30):
        loss, logits = step(X, y)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0] * 0.5, losses
    assert logits.shape == (32, 4)


def test_fused_step_matches_single_device():
    """DP over 8 devices must give the same loss trajectory as 1 device
    (the exact-arithmetic identity style of tests/nightly/dist_sync_kvstore.py)."""
    _need_devices(8)

    def run(mesh):
        np.random.seed(3)
        mx.random.seed(3)
        net = nn.Dense(4, in_units=6)
        net.initialize(mx.init.Xavier())
        step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mesh=mesh, learning_rate=0.1, momentum=0.0)
        X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
        y = nd.array(np.random.RandomState(6).randint(0, 4, 16).astype("float32"))
        out = [float(step(X, y)[0].asnumpy()) for _ in range(5)]
        return out

    l1 = run(make_mesh((1,), ("dp",)))
    l8 = run(make_mesh((8,), ("dp",)))
    np.testing.assert_allclose(l1, l8, rtol=1e-5, atol=1e-6)


def test_fused_step_with_batchnorm_aux():
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Dense(2))
    net.initialize(mx.init.Xavier())
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh)
    X = nd.array(np.random.rand(16, 8).astype("float32"))
    y = nd.array(np.random.randint(0, 2, 16).astype("float32"))
    step(X, y)
    rm = [p for name, p in net.collect_params().items()
          if name.endswith("running_mean")][0]
    assert float(np.abs(rm.data().asnumpy()).sum()) > 0, \
        "BN running stats must update through the fused step"


def test_tensor_parallel_sharding():
    _need_devices(8)
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((4, 2), ("dp", "mp"))
    net = nn.Dense(8, in_units=6)
    net.initialize(mx.init.Xavier())

    def spec(name, shape):
        if name.endswith("weight"):
            return P("mp", None)
        return None

    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, param_spec_fn=spec)
    X = nd.array(np.random.rand(8, 6).astype("float32"))
    y = nd.array(np.random.randint(0, 8, 8).astype("float32"))
    loss, _ = step(X, y)
    assert np.isfinite(float(loss.asnumpy()))
    w = net.weight.data()._data
    assert "mp" in str(w.sharding.spec), w.sharding


def test_module_multi_context():
    """Module(context=[...]) data parallel — reference multi-device Module."""
    _need_devices(8)
    ctxs = [mx.cpu(i) for i in range(8)]
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    net = sym.Activation(data=net, act_type="relu")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(data=net, name="softmax")
    X = np.random.rand(64, 10).astype("float32")
    y = (X @ np.arange(10) > 4.5).astype("float32")  # separable
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(symbol=net, context=ctxs)
    mod.fit(it, optimizer="sgd", optimizer_params={"learning_rate": 0.5},
            num_epoch=10)
    score = mod.score(it, "acc")
    assert score[0][1] > 0.85, score


def test_dryrun_entrypoints():
    _need_devices(8)
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_fused_step_observes_set_data():
    """Parameter.set_data (checkpoint load path) must be picked up by
    the fused step's version-token fast path."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh
    import jax

    net = gluon.nn.Dense(2)
    net.initialize()
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=make_mesh((1,), ("dp",),
                                         jax.devices()[:1]),
                          learning_rate=0.0, momentum=0.0)
    x = nd.ones((2, 3))
    y = nd.zeros((2,))
    _, logits1 = step(x, y)
    # overwrite the weight via the checkpoint-load path
    net.weight.set_data(nd.zeros((2, 3)))
    net.bias.set_data(nd.zeros((2,)))
    _, logits2 = step(x, y)
    np.testing.assert_allclose(logits2.asnumpy(), 0.0, atol=1e-6)


def test_run_steps_bulk_equals_sequential():
    """K steps inside one scan program (the bulk path, ref:
    engine.set_bulk_size semantics) must match K sequential fused steps
    bit-for-bit, including the per-step RNG fold."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh
    import jax

    def build():
        net = nn.HybridSequential(prefix="bulkeq_")
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dropout(0.25),
                    nn.Dense(5))
        net.initialize(mx.init.Xavier())
        mesh = make_mesh((4,), ("dp",), jax.devices()[:4])
        return net, FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
            learning_rate=0.1)

    rng = np.random.RandomState(0)
    X = nd.array(rng.randn(8, 12).astype("float32"))
    y = nd.array(rng.randint(0, 5, 8).astype("float32"))

    net1, s1 = build()
    net1(X)  # settle deferred shapes
    saved = {k: v.data().asnumpy()
             for k, v in net1.collect_params().items()}
    mx.random.seed(11)
    seq = [float(s1(X, y)[0].asnumpy()) for _ in range(4)]

    net2, s2 = build()
    net2(X)
    for k, v in net2.collect_params().items():
        v.set_data(nd.array(saved[k]))
    mx.random.seed(11)
    scan = s2.run_steps(X, y, steps=4).asnumpy()
    np.testing.assert_allclose(seq, scan, rtol=1e-5, atol=1e-6)
    for a, b in zip(s1._param_vals, s2._param_vals):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_run_steps_stacked_batches():
    """run_steps with a leading-K batch dimension consumes one batch
    per step."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh
    import jax

    net = nn.HybridSequential(prefix="bulkst_")
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((4,), ("dp",), jax.devices()[:4])
    step = FusedTrainStep(net, gluon.loss.L2Loss(), mesh=mesh,
                          learning_rate=0.05)
    rng = np.random.RandomState(1)
    Xs = nd.array(rng.randn(3, 8, 6).astype("float32"))
    ys = nd.array(rng.randn(3, 8, 4).astype("float32"))
    losses = step.run_steps(Xs, ys)
    assert losses.shape == (3,)
    l = losses.asnumpy()
    assert np.isfinite(l).all()


# ---------------------------------------------------------------------
# PR 21: no fallback hides the device
# ---------------------------------------------------------------------
def test_dryrun_raises_with_too_few_devices():
    """Asking for more devices than the process has is an error — the
    dryrun no longer re-executes itself on a virtual CPU mesh."""
    import __graft_entry__ as ge

    with pytest.raises(RuntimeError, match="this process has"):
        ge.dryrun_multichip(current_device_count() + 1)


class _FakeJax:
    """Stands in for jax in context.py: a host backend next to an
    accelerator backend, as on a machine with chips."""

    class _Dev:
        def __init__(self, platform, i):
            self.platform, self.id = platform, i

        def __repr__(self):
            return "%s:%d" % (self.platform, self.id)

    def __init__(self, n_chips):
        self._host = [self._Dev("cpu", 0)]
        self._chips = [self._Dev("tpu", i) for i in range(n_chips)]

    def devices(self, backend=None):
        return self._host if backend == "cpu" else self._chips


def test_contexts_on_a_machine_with_both_backends(monkeypatch):
    from mxnet_tpu import context

    fake = _FakeJax(n_chips=2)
    monkeypatch.setattr(context, "_jax", lambda: fake)
    # cpu is the host, always; reference cpu(i) are views of one pool
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.cpu(3).jax_device() is mx.cpu(0).jax_device()
    # tpu(i)/gpu(i) is chip i, and a chip that is not there raises
    assert mx.tpu(1).jax_device() is fake.devices()[1]
    assert mx.gpu(0).jax_device() is fake.devices()[0]
    with pytest.raises(ValueError, match="2 device"):
        mx.tpu(2).jax_device()
    assert mx.context.num_tpus() == 2


def test_data_parallel_runner_collapses_cpu_only(monkeypatch, caplog):
    from mxnet_tpu import context
    from mxnet_tpu.parallel.dp import DataParallelRunner

    # reference scripts bind [cpu(0), cpu(1)] unconditionally: on a
    # host pool of 8 devices, cpu(0) and cpu(8) are the same device
    with caplog.at_level("WARNING"):
        runner = DataParallelRunner(None, [mx.cpu(0), mx.cpu(8)])
    assert runner.mesh.devices.size == 1
    assert "collapsing" in caplog.text
    assert DataParallelRunner(None, [mx.cpu(0), mx.cpu(1)]) \
        .mesh.devices.size == 2
    # naming one chip twice has no such reading
    fake = _FakeJax(n_chips=2)
    monkeypatch.setattr(context, "_jax", lambda: fake)
    with pytest.raises(ValueError, match="1 distinct tpu"):
        DataParallelRunner(None, [mx.tpu(0), mx.gpu(0)])
    with pytest.raises(ValueError, match="2 device"):
        DataParallelRunner(None, [mx.tpu(0), mx.tpu(5)])


def test_waitall_propagates_a_failing_barrier(monkeypatch):
    import jax

    def boom():
        raise RuntimeError("device lost")

    monkeypatch.setattr(jax, "effects_barrier", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        nd.waitall()
