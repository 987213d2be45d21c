"""FeedForward legacy API + example-script smoke tests
(models: reference tests/python/train/test_mlp.py which drives the v0
model API, and the example/ configs)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

_EX = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                   "examples"))
_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=os.path.abspath(
                os.path.join(os.path.dirname(__file__), "..")))


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=2)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    return x, y


def test_feedforward_fit_predict_score():
    x, y = _data()
    model = mx.FeedForward(_mlp(), num_epoch=8, learning_rate=0.3,
                           numpy_batch_size=64)
    model.fit(x, y)
    acc = model.score(mx.io.NDArrayIter(x, y, batch_size=64))
    assert acc > 0.9, acc
    preds = model.predict(x)
    assert preds.shape == (256, 2)
    assert ((preds.argmax(axis=1) == y).mean()) > 0.9


def test_feedforward_create_and_checkpoint(tmp_path):
    x, y = _data()
    model = mx.FeedForward.create(_mlp(), x, y, num_epoch=12,
                                  learning_rate=0.3,
                                  numpy_batch_size=64)
    prefix = str(tmp_path / "ff")
    model.save(prefix, epoch=4)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0004.params")
    loaded = mx.FeedForward.load(prefix, 4)
    preds = loaded.predict(x)
    np.testing.assert_allclose(preds, model.predict(x), rtol=1e-5)
    acc = loaded.score(mx.io.NDArrayIter(x, y, batch_size=64))
    assert acc > 0.85


def test_feedforward_predict_fresh_after_refit():
    """predict must not serve stale cached weights after another fit."""
    x, y = _data()
    model = mx.FeedForward(_mlp(), num_epoch=1, learning_rate=0.3,
                           numpy_batch_size=64)
    model.fit(x, y)
    p1 = model.predict(x)
    model.num_epoch = 8
    model.fit(x, y)
    p2 = model.predict(x)
    assert not np.allclose(p1, p2)
    assert ((p2.argmax(axis=1) == y).mean()) > 0.9


def test_feedforward_predict_batch_reshape():
    x, y = _data()
    model = mx.FeedForward(_mlp(), num_epoch=2, learning_rate=0.1)
    model.fit(x, y)
    # different prediction batch size forces predictor rebind
    p1 = model.predict(x[:100])
    p2 = model.predict(x[:64])
    np.testing.assert_allclose(p1[:64], p2, rtol=1e-5)


def _run_example(rel, *args, timeout=600):
    script = os.path.join(_EX, rel)
    out = subprocess.run([sys.executable, script, *args], env=_ENV,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout + out.stderr


@pytest.mark.slow
def test_example_train_mnist():
    log = _run_example("image_classification/train_mnist.py",
                       "--num-epochs", "2", "--batch-size", "100")
    assert "final validation accuracy" in log
    acc = float(log.rsplit("final validation accuracy:", 1)[1].split()[0])
    assert acc > 0.9  # synthetic mnist is separable


@pytest.mark.slow
def test_example_lstm_bucketing():
    log = _run_example("rnn/lstm_bucketing.py", "--num-epochs", "1",
                       "--num-hidden", "32", "--num-embed", "16")
    assert "Epoch[0]" in log or "perplexity" in log.lower()


@pytest.mark.slow
def test_example_ssd_toy():
    log = _run_example("ssd/train_ssd_toy.py", "--steps", "150")
    assert "detected" in log
