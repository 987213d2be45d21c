"""Caffe converter (VERDICT r3 item 8): prototxt + .caffemodel ->
Symbol + params, logits checked against an independent numpy forward.

No caffe exists in this environment, so the .caffemodel fixture is
fabricated with the converter's own wire-format writer
(proto_lite.build_caffemodel) — the reader is exercised on exactly the
byte layout caffe emits (packed float blobs, BlobShape dims), and the
golden logits come from a from-scratch numpy implementation of the
layer semantics, not from the framework under test.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from tools.caffe_converter.convert_model import convert, convert_symbol
from tools.caffe_converter.proto_lite import (build_caffemodel,
                                              parse_caffemodel)
from tools.caffe_converter.prototxt import parse_prototxt

LENET_PROTOTXT = """
name: "MiniLeNet"
layer {
  name: "data"
  type: "Input"
  top: "data"
  input_param { shape: { dim: 2 dim: 1 dim: 12 dim: 12 } }
}
layer {
  name: "conv1"
  type: "Convolution"
  bottom: "data"
  top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3 stride: 1 }
}
layer {
  name: "relu1"
  type: "ReLU"
  bottom: "conv1"
  top: "conv1"
}
layer {
  name: "pool1"
  type: "Pooling"
  bottom: "conv1"
  top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "ip1"
  type: "InnerProduct"
  bottom: "pool1"
  top: "ip1"
  inner_product_param { num_output: 10 }
}
layer {
  name: "prob"
  type: "Softmax"
  bottom: "ip1"
  top: "prob"
}
"""


def _numpy_forward(x, w1, b1, w2, b2):
    """Independent golden path: conv(valid) -> relu -> maxpool2x2 ->
    fc -> softmax, plain loops."""
    n, _, h, wd = x.shape
    co, ci, kh, kw = w1.shape
    oh, ow = h - kh + 1, wd - kw + 1
    conv = np.zeros((n, co, oh, ow), np.float32)
    for i in range(n):
        for o in range(co):
            for y in range(oh):
                for xx in range(ow):
                    conv[i, o, y, xx] = np.sum(
                        x[i, :, y:y + kh, xx:xx + kw] * w1[o]) + b1[o]
    conv = np.maximum(conv, 0)
    ph, pw = oh // 2, ow // 2
    pooled = np.zeros((n, co, ph, pw), np.float32)
    for y in range(ph):
        for xx in range(pw):
            pooled[:, :, y, xx] = conv[:, :, 2 * y:2 * y + 2,
                                       2 * xx:2 * xx + 2].max(axis=(2, 3))
    flat = pooled.reshape(n, -1)
    logits = flat @ w2.T + b2
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def _make_fixture(tmp_path):
    rng = np.random.RandomState(0)
    w1 = rng.randn(4, 1, 3, 3).astype(np.float32) * 0.3
    b1 = rng.randn(4).astype(np.float32) * 0.1
    w2 = rng.randn(10, 4 * 5 * 5).astype(np.float32) * 0.1
    b2 = rng.randn(10).astype(np.float32) * 0.1
    blob = build_caffemodel("MiniLeNet", [
        ("conv1", "Convolution", [(w1.shape, w1.ravel()),
                                  (b1.shape, b1)]),
        ("ip1", "InnerProduct", [(w2.shape, w2.ravel()),
                                 (b2.shape, b2)]),
    ])
    proto_path = str(tmp_path / "lenet.prototxt")
    model_path = str(tmp_path / "lenet.caffemodel")
    with open(proto_path, "w") as f:
        f.write(LENET_PROTOTXT)
    with open(model_path, "wb") as f:
        f.write(blob)
    return proto_path, model_path, (w1, b1, w2, b2)


def test_wire_roundtrip():
    w = np.arange(24, dtype=np.float32).reshape(2, 3, 2, 2)
    blob = build_caffemodel("t", [("c", "Convolution",
                                   [(w.shape, w.ravel())])])
    net = parse_caffemodel(blob)
    assert net["name"] == "t"
    assert net["layers"][0]["name"] == "c"
    got = net["layers"][0]["blobs"][0]
    assert got["shape"] == (2, 3, 2, 2)
    np.testing.assert_allclose(got["data"], w.ravel())


def test_prototxt_parser():
    net = parse_prototxt(LENET_PROTOTXT)
    assert net["name"] == "MiniLeNet"
    layers = net["layer"]
    assert [l["type"] for l in layers] == [
        "Input", "Convolution", "ReLU", "Pooling", "InnerProduct",
        "Softmax"]
    assert layers[1]["convolution_param"]["num_output"] == 4
    assert layers[3]["pooling_param"]["pool"] == "MAX"


def test_convert_logits_match_numpy_golden(tmp_path):
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    proto_path, model_path, (w1, b1, w2, b2) = _make_fixture(tmp_path)
    sym, arg_params, aux_params = convert(proto_path, model_path)
    assert set(arg_params) == {"conv1_weight", "conv1_bias",
                               "ip1_weight", "ip1_bias"}

    rng = np.random.RandomState(1)
    x = rng.randn(2, 1, 12, 12).astype(np.float32)
    golden = _numpy_forward(x, w1, b1, w2, b2)

    mod = mx.mod.Module(sym, label_names=[n for n in sym.list_arguments()
                                          if n.endswith("label")] or None)
    mod.bind(data_shapes=[("data", (2, 1, 12, 12))], for_training=False,
             label_shapes=None)
    mod.set_params(arg_params, aux_params, allow_missing=True)
    out = mod.predict(mx.io.NDArrayIter(x, None, batch_size=2)).asnumpy()
    np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_cli_checkpoint_roundtrip(tmp_path):
    import subprocess

    import mxnet_tpu as mx

    proto_path, model_path, _ = _make_fixture(tmp_path)
    prefix = str(tmp_path / "converted")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tools", "caffe_converter",
                      "convert_model.py"),
         proto_path, model_path, prefix],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    sym, arg_params, aux_params = mx.model.load_checkpoint(prefix, 0)
    assert "conv1_weight" in arg_params
    assert sym.list_arguments()  # loads back as a composable symbol
