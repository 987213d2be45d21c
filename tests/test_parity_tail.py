"""Parity-tail tests: mx.text, mx.name, mx.engine, mx.rtc (Pallas),
mx.contrib.autograd, torch bridge, test_utils harness, tools
(parse_log, bandwidth)."""
import collections
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


# ------------------------------------------------------------------ text
def test_token_indexer():
    counter = collections.Counter(
        {"the": 10, "cat": 5, "sat": 5, "rare": 1})
    idx = mx.text.TokenIndexer(counter, min_freq=2,
                               reserved_tokens=["<pad>"])
    assert idx.unknown_token == "<unk>"
    assert idx.idx_to_token[0] == "<unk>"
    assert idx.idx_to_token[1] == "<pad>"
    assert idx.to_indices("the") == 2  # most frequent first
    assert "rare" not in idx.token_to_idx  # below min_freq
    assert idx.to_indices(["cat", "never-seen"])[1] == 0
    assert idx.to_tokens(2) == "the"
    assert len(idx) == 5


def test_token_indexer_most_freq_count():
    counter = collections.Counter({"a": 5, "b": 4, "c": 3, "d": 2})
    idx = mx.text.TokenIndexer(counter, most_freq_count=2)
    assert len(idx) == 3  # unk + 2


def test_glove_embedding_and_glossary(tmp_path):
    p = tmp_path / "glove.txt"
    p.write_text("hello 0.1 0.2 0.3\nworld 0.4 0.5 0.6\n")
    emb = mx.text.GloVe(pretrained_file_path=str(p))
    assert emb.vec_len == 3
    v = emb.get_vecs_by_tokens("world").asnumpy()
    np.testing.assert_allclose(v, [0.4, 0.5, 0.6], rtol=1e-6)
    # unknown → zeros
    np.testing.assert_allclose(
        emb.get_vecs_by_tokens("nope").asnumpy(), 0.0)
    # batch lookup
    m = emb.get_vecs_by_tokens(["hello", "world"]).asnumpy()
    assert m.shape == (2, 3)
    # update
    emb.update_token_vectors("hello", nd.array([1.0, 1.0, 1.0]))
    np.testing.assert_allclose(
        emb.get_vecs_by_tokens("hello").asnumpy(), 1.0)
    # glossary composes counter vocab + embedding vectors
    counter = collections.Counter({"world": 3, "unseen": 2})
    gl = mx.text.Glossary(counter, emb)
    assert gl.vec_len == 3
    np.testing.assert_allclose(
        gl.get_vecs_by_tokens("world").asnumpy(), [0.4, 0.5, 0.6],
        rtol=1e-6)


def test_fasttext_header_and_custom(tmp_path):
    p = tmp_path / "ft.vec"
    p.write_text("2 3\nab 1 2 3\ncd 4 5 6\n")
    emb = mx.text.FastText(pretrained_file_path=str(p))
    assert emb.vec_len == 3
    np.testing.assert_allclose(
        emb.get_vecs_by_tokens("cd").asnumpy(), [4, 5, 6])
    p2 = tmp_path / "custom.txt"
    p2.write_text("x,1,2\ny,3,4\n")
    emb2 = mx.text.CustomEmbedding(pretrained_file_path=str(p2),
                                   elem_delim=",")
    assert emb2.vec_len == 2
    created = mx.text.embedding.create(
        "glove", pretrained_file_path=str(tmp_path / "ft.vec"))
    assert isinstance(created, mx.text.GloVe)


def test_embedding_missing_file():
    with pytest.raises(OSError):
        mx.text.GloVe(pretrained_file_path="/nonexistent/file.txt")


def test_count_tokens_from_str():
    c = mx.text.utils.count_tokens_from_str("a b b\nc a", to_lower=True)
    assert c == collections.Counter({"a": 2, "b": 2, "c": 1})
    # regex-metacharacter delimiters must be escaped, not interpreted
    c = mx.text.utils.count_tokens_from_str("a.b c", seq_delim=".")
    assert c == collections.Counter({"a": 1, "b": 1, "c": 1})


# ---------------------------------------------------------------- naming
def test_name_prefix_scope():
    with mx.name.Prefix("net_"):
        s = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
        named = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                      num_hidden=2, name="fc")
    assert s.name == "net_fullyconnected0"  # reference name grammar
    assert named.name == "net_fc"  # Prefix applies to explicit names too
    s2 = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
    assert not s2.name.startswith("net_")


def test_name_manager_scope_resets_counters():
    """A fresh `with NameManager():` restarts auto-name counters, so
    checkpoint-deterministic rebuilds get identical parameter names."""
    mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
    with mx.name.NameManager():
        a = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
    with mx.name.NameManager():
        b = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2)
    assert a.name == b.name == "fullyconnected0"


# ---------------------------------------------------------------- engine
def test_engine_bulk_api():
    before = mx.engine.fit_bulk_size()
    prev = mx.engine.set_bulk_size(30)
    assert mx.engine.fit_bulk_size() == 30
    assert mx.engine.set_bulk_size(prev) == 30
    # handing the previous value back restores the opt-in state with
    # the number: a later Module.fit is not left in bulk mode
    assert mx.engine.fit_bulk_size() == before
    with mx.engine.bulk(5):
        x = nd.ones((4,)) + 1
    assert float(x.sum().asnumpy()) == 8.0
    assert mx.engine.fit_bulk_size() == before


# ------------------------------------------------------------------- rtc
def test_rtc_pallas_module():
    def axpy(a, x, y):
        # plain jax body is a valid "kernel" for the module API; a
        # pl.pallas_call body plugs in identically
        return a * x + y

    mod = mx.rtc.PallasModule({"axpy": axpy})
    k = mod.get_kernel("axpy")
    (out,) = k.launch([2.0, nd.ones((4,)), nd.ones((4,))])
    np.testing.assert_allclose(out.asnumpy(), 3.0)
    with pytest.raises(mx.MXNetError):
        mod.get_kernel("nope")
    with pytest.raises(mx.MXNetError):
        mx.rtc.CudaModule("__global__ void f() {}")


def test_rtc_pallas_real_kernel():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    def add(x, y):
        return pl.pallas_call(
            add_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=jax.default_backend() == "cpu",
        )(x, y)

    mod = mx.rtc.PallasModule()
    mod.add_kernel("add", add)
    (out,) = mod.get_kernel("add").launch(
        [nd.ones((8, 128)), nd.ones((8, 128))])
    np.testing.assert_allclose(out.asnumpy(), 2.0)


# ------------------------------------------------------- contrib.autograd
def test_contrib_autograd_v1():
    from mxnet_tpu.contrib import autograd as ag1

    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))

    def f(x):
        return (x * x).sum()

    g = ag1.grad(f)(x)
    np.testing.assert_allclose(g[0].asnumpy(), 2 * x.asnumpy())
    grads, loss = ag1.grad_and_loss(f)(x)
    np.testing.assert_allclose(float(loss.asnumpy()), 14.0)
    with ag1.train_section():
        assert mx.autograd.is_recording()
    assert not mx.autograd.is_recording()


# ---------------------------------------------------------- torch bridge
def test_torch_bridge():
    from mxnet_tpu import torch as mxt

    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    t = mxt.to_torch(x)
    assert tuple(t.shape) == (2, 3)
    np.testing.assert_allclose(t.numpy(), x.asnumpy())
    back = mxt.from_torch(t * 2)
    np.testing.assert_allclose(back.asnumpy(), 2 * x.asnumpy())


# ------------------------------------------------------------- test_utils
def test_check_symbolic_forward_backward():
    from mxnet_tpu import test_utils as tu

    data = mx.sym.Variable("data")
    out = data * 2 + 1
    x = np.random.rand(3, 4).astype(np.float32)
    tu.check_symbolic_forward(out, [x], [2 * x + 1])
    tu.check_symbolic_backward(out, [x], [np.ones_like(x)],
                               {"data": 2 * np.ones_like(x)})


def test_rand_sparse_ndarray():
    from mxnet_tpu import test_utils as tu

    arr, dense = tu.rand_sparse_ndarray((8, 4), "row_sparse",
                                        density=0.5)
    np.testing.assert_allclose(arr.todense().asnumpy(), dense)
    arr, dense = tu.rand_sparse_ndarray((6, 5), "csr", density=0.3)
    np.testing.assert_allclose(arr.todense().asnumpy(), dense)


# ------------------------------------------------------------------ tools
def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools")))
    import parse_log

    log = (
        "INFO:root:Epoch[0] Batch [50]\tSpeed: 5129.15 samples/sec"
        "\taccuracy=0.095294\n"
        "INFO:root:Epoch[0] Train-accuracy=0.106667\n"
        "INFO:root:Epoch[0] Time cost=1.992\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.110000\n"
        "INFO:root:Epoch[1] Batch [50]\tSpeed: 32072.67 samples/sec"
        "\taccuracy=0.630000\n"
        "INFO:root:Epoch[1] Train-accuracy=1.000000\n"
        "INFO:root:Epoch[1] Time cost=0.186\n"
        "INFO:root:Epoch[1] Validation-accuracy=1.000000\n")
    epochs = parse_log.parse(log.splitlines())
    assert epochs[0]["train"]["accuracy"] == pytest.approx(0.106667)
    assert epochs[1]["val"]["accuracy"] == 1.0
    assert epochs[0]["speed"] == [pytest.approx(5129.15)]
    assert epochs[1]["time"] == pytest.approx(0.186)


@pytest.mark.slow
def test_bandwidth_measure_local():
    tools = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "tools"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(os.path.join(
                   os.path.dirname(__file__), "..")))
    out = subprocess.run(
        [sys.executable, os.path.join(tools, "bandwidth", "measure.py"),
         "--kv-store", "local", "--num-layers", "3", "--size", "65536",
         "--iters", "3"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "GB/s" in out.stdout
