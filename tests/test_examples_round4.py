"""Round-4 example families (VERDICT r3 item 7): nce-loss, svm_mnist,
autoencoder — run BYTE-IDENTICAL from /root/reference through the
compat/mxnet shim — plus the GAN family, whose reference implementation
is R-frontend-only (example/gan/CGAN_mnist_R), ported as
examples/gan/dcgan.py with the same two-optimizer adversarial loop.

Data shims follow the established launcher pattern (no reference file
touched): nce-loss scripts generate their own data; svm_mnist and the
autoencoder consume the sklearn-0.x fetch_mldata API (long removed, and
this environment is offline), supplied synthetically by
tests/sklearn_data_launcher.py.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REFERENCE = "/root/reference"
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
LAUNCHER = os.path.join(ROOT, "tests", "sklearn_data_launcher.py")

pytestmark = pytest.mark.skipif(
    not os.path.isdir(os.path.join(REFERENCE, "example")),
    reason="reference tree not present")


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "compat"), ROOT, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def _run(args, cwd, timeout=900, **env_extra):
    proc = subprocess.run([sys.executable] + args, cwd=cwd,
                          env=_env(**env_extra), capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    return proc.stdout + proc.stderr


@pytest.mark.slow
def test_reference_toy_nce_byte_identical():
    """example/nce-loss/toy_nce.py runs unmodified: NCE sampled-softmax
    loss (Embedding + broadcast_mul + LogisticRegressionOutput) with its
    custom NceAccuracy metric; full 20-epoch config, far above the
    ~0.17 chance level of argmax-over-6-candidates."""
    out = _run(["toy_nce.py"], cwd=os.path.join(REFERENCE, "example",
                                                "nce-loss"), timeout=1800)
    accs = [float(a) for a in
            re.findall(r"Validation-nce-accuracy=([\d.]+)", out)]
    assert accs, out[-2000:]
    assert accs[-1] > 0.4, accs


@pytest.mark.slow
def test_reference_toy_softmax_byte_identical():
    """example/nce-loss/toy_softmax.py (the full-softmax control the
    README compares NCE against) runs unmodified through Module.fit."""
    out = _run(["toy_softmax.py"], cwd=os.path.join(REFERENCE, "example",
                                                    "nce-loss"),
               timeout=2400)
    accs = [float(a) for a in
            re.findall(r"Validation-accuracy=([\d.]+)", out)]
    assert accs, out[-2000:]
    assert np.isfinite(accs[-1])


@pytest.mark.slow
def test_reference_svm_mnist_byte_identical():
    """example/svm_mnist/svm_mnist.py runs unmodified: SVMOutput (L2-SVM
    objective) + sklearn PCA pipeline + Module.fit/score."""
    out = _run([LAUNCHER, "svm_mnist.py"],
               cwd=os.path.join(REFERENCE, "example", "svm_mnist"),
               SYN_MNIST_N="60256")
    m = re.search(r"Accuracy: ([\d.]+) %", out)
    assert m, out[-2000:]
    assert float(m.group(1)) > 90.0, m.group(1)


@pytest.mark.slow
def test_reference_autoencoder_sae_byte_identical():
    """example/autoencoder/mnist_sae.py runs unmodified (its documented
    CLI shrinks iterations): layerwise pretraining + finetuning through
    the raw bind/Solver/updater path, Monitor taps, save/load of the
    args dict, and eval via extract_feature."""
    out = _run([LAUNCHER, "mnist_sae.py", "--batch-size", "64",
                "--pretrain-num-iter", "150", "--finetune-num-iter",
                "150", "--print-every", "50",
                "--num-units", "784,128,32"],
               cwd=os.path.join(REFERENCE, "example", "autoencoder"),
               SYN_MNIST_N="60256")
    tr = re.search(r"Training error: ([\d.eE+-]+)", out)
    va = re.search(r"Validation error: ([\d.eE+-]+)", out)
    assert tr and va, out[-2000:]
    assert np.isfinite(float(tr.group(1)))
    assert np.isfinite(float(va.group(1)))


def test_dcgan_adversarial_loop():
    """examples/gan/dcgan.py: two optimizers in opposition — D must
    learn to separate real/fake (loss_D falls) while G's path through
    D's parameters stays live (loss_G responds to D's improvement)."""
    sys.path.insert(0, os.path.join(ROOT, "examples", "gan"))
    try:
        import dcgan
    finally:
        sys.path.pop(0)
    G, D, hist = dcgan.train(epochs=3, batch=16, batches_per_epoch=8,
                             seed=0)
    d_losses = [h[0] for h in hist]
    g_losses = [h[1] for h in hist]
    assert all(np.isfinite(v) for v in d_losses + g_losses)
    # D improves against the fixed-speed G
    assert d_losses[-1] < d_losses[0], hist
    # the adversarial coupling is live: G's loss moves in response
    assert abs(g_losses[-1] - g_losses[0]) > 1e-3, hist
    # G's parameters actually updated by its own trainer
    assert any(float(np.abs(p.grad().asnumpy()).sum()) >= 0
               for p in G.collect_params().values()
               if p.grad_req != "null")


def _seed_mnist_idx(data_dir):
    """Uncompressed idx MNIST files (the layout GetMNIST_ubyte checks
    for in tests/python/common/get_data.py before downloading): the
    synthetic class-square set the other mnist tests use."""
    import struct

    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.RandomState(0)

    def write(img_name, lab_name, n, seed):
        r = np.random.RandomState(seed)
        labels = (np.arange(n) % 10).astype(np.uint8)
        imgs = np.zeros((n, 28, 28), np.uint8)
        for i, c in enumerate(labels):
            img = r.randint(0, 30, (28, 28))
            img[c:c + 10, c:c + 10] += 180
            imgs[i] = np.clip(img, 0, 255)
        with open(os.path.join(data_dir, lab_name), "wb") as f:
            f.write(struct.pack(">II", 2049, n) + labels.tobytes())
        with open(os.path.join(data_dir, img_name), "wb") as f:
            f.write(struct.pack(">IIII", 2051, n, 28, 28) +
                    imgs.tobytes())

    write("train-images-idx3-ubyte", "train-labels-idx1-ubyte", 2000, 1)
    write("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte", 1000, 2)


_NPCOMPAT = (
    "import numpy as _np\n"
    "for _n, _t in (('int', int), ('float', float), ('bool', bool)):\n"
    "    if not hasattr(_np, _n): setattr(_np, _n, _t)\n")


@pytest.mark.slow
def test_reference_custom_softmax_byte_identical(tmp_path):
    """example/numpy-ops/custom_softmax.py runs unmodified: the
    CustomOp/CustomOpProp protocol (forward/backward in numpy, assign
    with req) inside Module.fit.  Launcher restores the numpy<1.24
    np.int alias its backward uses; MNIST idx files pre-seeded so the
    reference's own get_data helper short-circuits."""
    _seed_mnist_idx(str(tmp_path / "data"))
    script = os.path.join(REFERENCE, "example", "numpy-ops",
                          "custom_softmax.py")
    code = (_NPCOMPAT +
            "import sys, runpy\n"
            "sys.argv = ['custom_softmax.py']\n"
            "runpy.run_path(%r, run_name='__main__')\n" % script)
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=str(tmp_path), env=_env(),
                          capture_output=True, text=True, timeout=1800)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    accs = [float(a) for a in
            re.findall(r"Validation-accuracy=([\d.]+)", out)]
    assert len(accs) == 10, out[-2000:]
    assert accs[-1] > 0.9, accs


@pytest.mark.slow
def test_reference_multi_task_byte_identical(tmp_path):
    """example/multi-task/example_multi_task.py runs unmodified: a
    two-head Group symbol with a custom Multi_Accuracy metric over a
    wrapped dual-label iterator.  It hardcodes 100 epochs; the test
    observes the first validation rounds, then stops it."""
    import time as _time

    _seed_mnist_idx(str(tmp_path / "data"))
    script = os.path.join(REFERENCE, "example", "multi-task",
                          "example_multi_task.py")
    code = (_NPCOMPAT +
            "import sys, runpy\n"
            "sys.argv = ['example_multi_task.py']\n"
            "runpy.run_path(%r, run_name='__main__')\n" % script)
    proc = subprocess.Popen([sys.executable, "-c", code],
                            cwd=str(tmp_path), env=_env(),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines = []
    hits = 0
    t0 = _time.time()
    try:
        for line in proc.stdout:
            lines.append(line)
            if "multi-accuracy" in line and "Validation" in line:
                hits += 1
                if hits >= 4:
                    break
            if _time.time() - t0 > 1500:
                break
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    out = "".join(lines)
    assert hits >= 2, out[-3000:]
    accs = [float(a) for a in
            re.findall(r"Validation-multi-accuracy[^=]*=([\d.]+)", out)]
    assert accs and all(np.isfinite(a) for a in accs), out[-2000:]
    # both heads see the same labels here, so accuracy must climb
    assert max(accs) > 0.5, accs


@pytest.mark.slow
def test_reference_profiler_matmul_byte_identical(tmp_path):
    """example/profiler/profiler_matmul.py runs unmodified: the legacy
    profiler surface (profiler_set_config(mode=...), profiler_set_state
    run/stop) around a bound executor, dumping a chrome-trace JSON.
    Launcher restores py<3.8 time.clock (removed upstream)."""
    import json

    script = os.path.join(REFERENCE, "example", "profiler",
                          "profiler_matmul.py")
    prof = str(tmp_path / "profile_matmul.json")
    code = ("import time\n"
            "if not hasattr(time, 'clock'): time.clock = time.process_time\n"
            "import sys, runpy\n"
            "sys.argv = ['profiler_matmul.py', '--profile_filename', %r,\n"
            "  '--iter_num', '8', '--begin_profiling_iter', '2',\n"
            "  '--end_profiling_iter', '6']\n"
            "runpy.run_path(%r, run_name='__main__')\n" % (prof, script))
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=str(tmp_path), env=_env(),
                          capture_output=True, text=True, timeout=1500)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-3000:]
    data = json.load(open(prof))
    events = data.get("traceEvents", data)
    names = {e.get("name") for e in events if isinstance(e, dict)}
    assert any(n and "dot" in n for n in names), sorted(names)[:20]


@pytest.mark.slow
def test_reference_numpy_softmax_byte_identical(tmp_path):
    """example/numpy-ops/numpy_softmax.py runs unmodified: the LEGACY
    NumpyOp API (pre-CustomOp; in-place numpy forward/backward) inside
    Module.fit."""
    _seed_mnist_idx(str(tmp_path / "data"))
    script = os.path.join(REFERENCE, "example", "numpy-ops",
                          "numpy_softmax.py")
    code = (_NPCOMPAT +
            "import sys, runpy\n"
            "sys.argv = ['numpy_softmax.py']\n"
            "runpy.run_path(%r, run_name='__main__')\n" % script)
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=str(tmp_path), env=_env(),
                          capture_output=True, text=True, timeout=1800)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    accs = [float(a) for a in
            re.findall(r"Validation-accuracy=([\d.]+)", out)]
    assert len(accs) == 10, out[-2000:]
    assert accs[-1] > 0.9, accs


def test_reference_weighted_logistic_regression_byte_identical(tmp_path):
    """example/numpy-ops/weighted_logistic_regression.py runs
    unmodified: parameterized CustomOpProp (constructor kwargs through
    mx.sym.Custom) + simple_bind/backward/grad_dict; the weighted
    gradient must scale positives vs negatives exactly as coded."""
    script = os.path.join(REFERENCE, "example", "numpy-ops",
                          "weighted_logistic_regression.py")
    proc = subprocess.run([sys.executable, script], cwd=str(tmp_path),
                          env=_env(), capture_output=True, text=True,
                          timeout=600)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    heads = ["Weighted Logistic Regression output:",
             "\nLogistic Regression output:",
             "Weighted Logistic Regression gradients:",
             "\nLogistic Regression gradients:"]
    pos = [out.index(h) for h in heads]
    assert pos == sorted(pos), out[-2000:]
    blocks = [out[p + len(h):(pos + [len(out)])[i + 1]]
              for i, (p, h) in enumerate(zip(pos, heads))]

    def parse(b):
        return np.array([float(v) for v in
                         re.findall(r"-?\d+\.\d+(?:e-?\d+)?", b)])

    w_out, out_, w_grad, grad = [parse(b) for b in blocks]
    # same sigmoid forward; weighted grads differ from unweighted by
    # the pos/neg scales (pos=1, neg=0.1, normalized by n=5 columns)
    np.testing.assert_allclose(w_out, out_, rtol=1e-5)
    assert np.all(np.isfinite(w_grad)) and np.all(np.isfinite(grad))
    assert not np.allclose(w_grad, grad)
