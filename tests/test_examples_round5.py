"""Round-5 example families (VERDICT r4 item 5): recommenders
matrix-factorization, cnn_text_classification, vae, fcn-xs, and the
dqn target-network slice — reference code run byte-identical from
/root/reference through the compat/mxnet shim wherever the script is
py3-clean, with synthetic data supplied by the launcher (offline box;
no reference file is touched).

* recommenders: movielens_data.py + matrix_fact.py byte-identical; the
  MF network is exec'd from demo1-MF.ipynb's own cell source; data is a
  planted low-rank MovieLens-format table.  Also exercises
  mx.notebook.callback (LiveLearningCurve, args_wrapper).
* cnn_text_classification: text_cnn.py byte-identical CLI run on
  synthetic rt-polarity files with a separable vocabulary.
* vae: VAE.py imported byte-identical; ELBO falls on synthetic binary
  digits.
* fcn-xs: symbol_fcnxs.py imported byte-identical (FCN-8s — three
  Deconvolution stages, Crop, pool4/pool3 skips); heads train with the
  trunk fixed until per-pixel CE is well under the uniform floor.
* dqn: base.py + operators.py imported byte-identical (Base executor
  wrapper, DQNOutput custom op); qnet.copy() + copy_params_to drive the
  target-network parameter-copy path on a tiny numpy MDP.
* bi-lstm-sort: lstm.bi_lstm_unroll + sort_io.BucketSentenceIter +
  lstm_sort.Perplexity byte-identical; perplexity dives under the
  uniform-vocab floor on the sort task.
* stochastic-depth: sd_mnist.py run byte-identical from a verbatim
  copy (StochasticDepthModule — a user BaseModule subclass with random
  train-time block skipping — inside SequentialModule.fit).
* warpctc: see tests/warpctc_runner.py (the toy OCR task through
  mx.sym.WarpCTC).
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REFERENCE = "/root/reference"
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

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


def _run_code(code, cwd, timeout=1500, extra_path=()):
    env = _env()
    env["PYTHONPATH"] = os.pathsep.join(
        list(extra_path) + [env["PYTHONPATH"]])
    proc = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    return proc.stdout + proc.stderr


# ------------------------------------------------------------------ MF
def _write_movielens(root):
    """MovieLens-100k-format u.data / u1.base / u1.test with a planted
    rank-4 structure, so MF can actually recover something."""
    rng = np.random.RandomState(0)
    n_user, n_item, k = 120, 80, 4
    U = rng.normal(0, 1.0, (n_user, k))
    V = rng.normal(0, 1.0, (n_item, k))
    d = os.path.join(root, "ml-100k")
    os.makedirs(d, exist_ok=True)
    open(os.path.join(root, "ml-100k.zip"), "wb").close()  # skip wget
    rows = []
    for u in range(1, n_user):
        for i in rng.choice(np.arange(1, n_item), 25, replace=False):
            score = np.clip(np.round(3 + U[u] @ V[i]), 1, 5)
            rows.append((u, i, int(score), 0))
    rng.shuffle(rows)
    cut = int(len(rows) * 0.9)

    def dump(path, rs):
        with open(path, "w") as f:
            for r in rs:
                f.write("%d\t%d\t%d\t%d\n" % r)

    dump(os.path.join(d, "u.data"), rows)
    dump(os.path.join(d, "u1.base"), rows[:cut])
    dump(os.path.join(d, "u1.test"), rows[cut:])


@pytest.mark.slow
def test_reference_recommenders_matrix_factorization(tmp_path):
    _write_movielens(str(tmp_path))
    nb = json.load(open(os.path.join(
        REFERENCE, "example", "recommenders", "demo1-MF.ipynb")))
    cell = next(("".join(c["source"]) for c in nb["cells"]
                 if "def plain_net" in "".join(c.get("source", []))))
    cell = cell.split("net1 =")[0]  # the net definition, not the viz
    code = (
        "import mxnet as mx\n"
        "import movielens_data, matrix_fact\n"
        "train, test = movielens_data.get_data_iter(batch_size=50)\n"
        "max_user, max_item = movielens_data.max_id('./ml-100k/u.data')\n"
        + cell +
        "lc = matrix_fact.train(plain_net(16), (train, test),\n"
        "                       num_epoch=20, learning_rate=0.05,\n"
        "                       ctx=[mx.cpu()])\n"
        "import json\n"
        "print('MF_EVAL_RMSE', json.dumps(lc._data['eval']['RMSE']))\n")
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "recommenders")])
    rmses = json.loads(re.search(r"MF_EVAL_RMSE (\[.*?\])", out).group(1))
    assert len(rmses) >= 20, out[-1500:]
    # planted rank-4 signal (heavily clipped/rounded, so the floor is
    # well above 0): MF must more than halve the all-zeros baseline
    # (measured trajectory: 3.40 -> 1.37)
    assert rmses[-1] < rmses[0] * 0.5, (rmses[0], rmses[-1])
    assert rmses[-1] < 1.5, rmses[-5:]


# -------------------------------------------------------- text cnn
def _write_rt_polarity(root):
    """Separable toy corpus: positive reviews use a disjoint content
    vocabulary from negative ones."""
    rng = np.random.RandomState(1)
    pos_words = ["great", "superb", "moving", "delight", "masterful",
                 "charming", "wonderful", "uplifting"]
    neg_words = ["dull", "tedious", "awful", "clumsy", "lifeless",
                 "grating", "wooden", "dreary"]
    filler = ["the", "film", "a", "movie", "it", "is", "and", "plot"]
    d = os.path.join(root, "data", "rt-polaritydata")
    os.makedirs(d, exist_ok=True)
    # text_cnn.py hardcodes a 1000-sentence dev split (x_shuffled
    # [-1000:]), so the corpus must be comfortably larger than that
    for path, words in ((os.path.join(d, "rt-polarity.pos"), pos_words),
                        (os.path.join(d, "rt-polarity.neg"), neg_words)):
        with open(path, "w", encoding="utf-8") as f:
            for _ in range(800):
                n = rng.randint(6, 12)
                toks = [str(rng.choice(filler)) for _ in range(n)]
                for _ in range(3):
                    toks[rng.randint(n)] = str(rng.choice(words))
                f.write(" ".join(toks) + "\n")


@pytest.mark.slow
def test_reference_cnn_text_classification_unmodified(tmp_path):
    _write_rt_polarity(str(tmp_path))
    script = os.path.join(REFERENCE, "example", "cnn_text_classification",
                          "text_cnn.py")
    code = (
        "import sys, runpy\n"
        "sys.argv = ['text_cnn.py', '--num-epochs', '6', '--batch-size',"
        " '32', '--num-embed', '24', '--lr', '0.001',"
        " '--disp-batches', '5']\n"
        "runpy.run_path(%r, run_name='__main__')\n" % script)
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "cnn_text_classification")])
    accs = [float(m) for m in re.findall(
        r"Validation-accuracy=([0-9.]+)", out)]
    assert len(accs) >= 6, out[-2000:]
    # disjoint vocabularies: the CNN must become near-perfect
    assert max(accs) > 0.9, (accs, out[-1500:])


# ------------------------------------------------------------- VAE
@pytest.mark.slow
def test_reference_vae_unmodified(tmp_path):
    code = (
        "import numpy as np\n"
        "import VAE as vae_mod\n"
        "rng = np.random.RandomState(0)\n"
        "protos = rng.rand(4, 64) > 0.6\n"
        "idx = rng.randint(0, 4, 600)\n"
        "x = (protos[idx] ^ (rng.rand(600, 64) < 0.05)).astype('float32')\n"
        "x = np.clip(x, 0.001, 0.999)\n"
        "m = vae_mod.VAE(n_latent=3, num_hidden_ecoder=64,\n"
        "                num_hidden_decoder=64, x_train=x[:500],\n"
        "                x_valid=None, batch_size=50,\n"
        "                learning_rate=0.01, weight_decay=0.0,\n"
        "                num_epoch=30, optimizer='adam')\n"
        "losses = m.training_loss\n"
        "print('VAE_LOSSES', losses[0], losses[-1])\n"
        "mu, logvar = vae_mod.VAE.encoder(m, x[500:])\n"
        "rec = vae_mod.VAE.decoder(m, mu)\n"
        "err = float(np.mean(np.abs(np.asarray(rec) - x[500:])))\n"
        "print('VAE_REC_ERR', err)\n")
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "vae")])
    first, last = map(float, re.search(
        r"VAE_LOSSES ([0-9.eE+-]+) ([0-9.eE+-]+)", out).groups())
    # measured trajectory (adam 0.01, 30 epochs): 44.4 -> 15.7
    assert last < first * 0.5, (first, last)
    err = float(re.search(r"VAE_REC_ERR ([0-9.eE+-]+)", out).group(1))
    # reconstruction through the 3-d latent must beat coin-flipping
    # (0.5 expected error for random binary output; measured 0.086)
    assert err < 0.2, err


# ---------------------------------------------------------- fcn-xs
@pytest.mark.slow
def test_reference_fcnxs_symbol_trains(tmp_path):
    """FCN-8s from symbol_fcnxs.py byte-identical — full VGG16 trunk,
    three Deconvolution upsampling stages, three Crop ops, pool4/pool3
    skip fusions, multi-output SoftmaxOutput — trained on synthetic
    2-class blobs with the trunk FIXED and every score/deconv head
    learning (Module fixed_param_names), mirroring the reference's own
    staged workflow where the trunk comes pretrained (fcn_xs.py
    --init-type vgg16; its README downloads the VGG16 checkpoint —
    unavailable offline, and from random init the 13-conv trunk
    either sits at the uniform point (lr<=1e-5) or NaNs (lr>=1e-3)
    under the reference's unnormalized per-pixel gradients, which is
    why its solver uses lr 1e-10 on pretrained weights).  The bar:
    per-pixel cross-entropy falls monotonically well below the
    ln(2)=0.693 uniform floor (measured 0.692 -> 0.370 over 20
    epochs), with gradients flowing through every deconv/crop/skip
    stage into the trainable heads."""
    code = """
import numpy as np
import mxnet as mx
import symbol_fcnxs

np.random.seed(0)
mx.random.seed(0)
n, size, classes = 8, 48, 2
X = np.zeros((n, 3, size, size), 'float32')
Y = np.zeros((n, size, size), 'float32')
rng = np.random.RandomState(0)
for i in range(n):
    X[i] = rng.uniform(0, 0.2, (3, size, size))
    x0, y0 = rng.randint(4, size - 20, 2)
    X[i, :, y0:y0+16, x0:x0+16] += 0.7
    Y[i, y0:y0+16, x0:x0+16] = 1
sym = symbol_fcnxs.get_fcn8s_symbol(numclass=classes, workspace_default=128)
args = sym.list_arguments()
heads = [a for a in args if a.startswith(('score', 'bigscore',
                                          'upsampling'))]
fixed = [a for a in args if a not in heads
         and a not in ('data', 'softmax_label')]
assert len(heads) >= 8, heads   # all three score stages + deconvs
mod = mx.mod.Module(sym, data_names=('data',),
                    label_names=('softmax_label',),
                    fixed_param_names=fixed)
it = mx.io.NDArrayIter(X, Y.reshape(n, -1), batch_size=4,
                       label_name='softmax_label')


def pixel_ce():
    it.reset()
    pred = mod.predict(it).asnumpy()     # (n, classes, H, W) softmax
    p_true = np.where(Y == 1, pred[:, 1], pred[:, 0])
    it.reset()
    return float(-np.log(np.clip(p_true, 1e-9, 1)).mean())


mod.fit(it, num_epoch=1, optimizer='sgd',
        optimizer_params=(('learning_rate', 1e-4), ('momentum', 0.9)),
        initializer=mx.init.Xavier())
ce0 = pixel_ce()
mod.fit(it, num_epoch=19, optimizer='sgd',
        optimizer_params=(('learning_rate', 1e-4), ('momentum', 0.9)))
ce1 = pixel_ce()
print('FCN_CE', ce0, '->', ce1)
assert np.isfinite(ce1), ce1
assert ce1 < 0.45, (ce0, ce1)  # well under the 0.693 uniform floor
assert ce1 < ce0 - 0.1, (ce0, ce1)
print('FCN_OK')
"""
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "fcn-xs")], timeout=3000)
    assert "FCN_OK" in out, out[-2000:]


# -------------------------------------------------------------- dsd
@pytest.mark.slow
def test_reference_dsd_sparse_training(tmp_path):
    """example/dsd (Dense-Sparse-Dense training): mlp.py run
    byte-identical with its SparseSGD optimizer — an mx.optimizer.SGD
    subclass that prunes via topk(ret_typ='mask') and masks
    weight/grad/momentum each update — across two pruning epochs on
    two CPU contexts (the script's hardcoded 60000/batch schedule is
    honored by seeding a 60000-sample synthetic MNIST, so the
    sparsity switches land exactly at the epoch boundaries)."""
    import struct

    d = os.path.join(str(tmp_path), "data")
    os.makedirs(d)
    rng = np.random.RandomState(5)

    def write(img_name, lab_name, n):
        labels = (np.arange(n) % 10).astype(np.uint8)
        base = rng.randint(0, 30, (10, 28, 28))
        for c in range(10):
            base[c, c:c + 10, c:c + 10] += 180
        noise = rng.randint(0, 20, (n, 28, 28))
        imgs = np.clip(base[labels] + noise, 0, 255).astype(np.uint8)
        with open(os.path.join(d, img_name), "wb") as f:
            f.write(struct.pack(">IIII", 2051, n, 28, 28) + imgs.tobytes())
        with open(os.path.join(d, lab_name), "wb") as f:
            f.write(struct.pack(">II", 2049, n) + labels.tobytes())

    write("train-images-idx3-ubyte", "train-labels-idx1-ubyte", 60000)
    write("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte", 1000)

    script = os.path.join(REFERENCE, "example", "dsd", "mlp.py")
    code = (
        "import sys, runpy\n"
        "sys.argv = ['mlp.py', '--pruning_switch_epoch', '1,2',\n"
        "            '--weight_sparsity', '30,70',\n"
        "            '--bias_sparsity', '0,0']\n"
        "runpy.run_path(%r, run_name='__main__')\n" % script)
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "dsd")], timeout=2800)
    accs = [float(m) for m in re.findall(
        r"Validation-accuracy=([0-9.]+)", out)]
    assert len(accs) == 2, out[-2000:]
    # the bright-square classes survive 70% weight pruning easily
    assert accs[-1] > 0.9, (accs, out[-1500:])


# ------------------------------------- deep-embedded-clustering
@pytest.mark.slow
def test_reference_dec_clustering(tmp_path):
    """example/deep-embedded-clustering/dec.py byte-identical: DECModel
    (whose DECLoss is a THREE-input legacy NumpyOp — the _Native
    creator path), the autoencoder example's AutoEncoderModel/Solver/
    extract_feature, sklearn KMeans seeding, and the self-training
    refresh loop.  The driver pre-trains the stacked AE briefly with
    the class's own methods and saves the checkpoint dec.py probes for
    (dec_model_pt.arg), so setup() skips its hardcoded 150k-iteration
    pretrain; clustering then runs on well-separated synthetic blobs
    and must recover them almost exactly."""
    code = """
import numpy as np
np.int = int
# sklearn removed utils.linear_assignment_ (dec.py:36 imports it);
# provide the classic scipy-backed shim process-locally
import sys, types
from scipy.optimize import linear_sum_assignment
_m = types.ModuleType('sklearn.utils.linear_assignment_')


def linear_assignment(cost):
    r, c = linear_sum_assignment(cost)
    return np.stack([r, c], axis=1)


_m.linear_assignment = linear_assignment
sys.modules['sklearn.utils.linear_assignment_'] = _m
# fetch_mldata shim (the sklearn_data_launcher pattern): dec.py's
# data.py import needs the 0.x name even though this driver feeds
# synthetic X directly
import sklearn.datasets as skd
if not hasattr(skd, 'fetch_mldata'):
    sys.path.insert(0, {TESTS_DIR!r})
    from sklearn_data_launcher import fetch_mldata
    skd.fetch_mldata = fetch_mldata
import mxnet as mx
import logging
logging.basicConfig(level=logging.INFO)
import dec
from dec import DECModel, cluster_acc
from autoencoder import AutoEncoderModel

np.random.seed(0)
mx.random.seed(0)
# 4 well-separated 784-d blobs
rng = np.random.RandomState(0)
protos = rng.uniform(0, 1, (4, 784)) * (rng.rand(4, 784) > 0.7)
X = np.zeros((1600, 784), 'float32')
y = np.zeros(1600)
for i in range(1600):
    c = i % 4
    X[i] = protos[c] + rng.normal(0, 0.05, 784)
    y[i] = c
X = np.clip(X, 0, 1).astype('float32')

# brief AE pretrain via the example's own methods, saved where
# DECModel.setup looks before launching its 150k-iteration default
ae = AutoEncoderModel(mx.cpu(), [784, 500, 500, 2000, 10],
                      pt_dropout=0.2)
ae.layerwise_pretrain(X, 256, 600, 'sgd', l_rate=0.1, decay=0.0)
ae.finetune(X, 256, 600, 'sgd', l_rate=0.1, decay=0.0)
ae.save('dec_model_pt.arg')

m = DECModel(mx.cpu(), X, 4, 1.0, 'dec_model')
acc = m.cluster(X, y, update_interval=320)
print('DEC_ACC', acc)
assert acc > 0.85, acc
print('DEC_OK')
"""
    out = _run_code(code.replace("{TESTS_DIR!r}",
                                 repr(os.path.join(ROOT, "tests"))),
                    str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "deep-embedded-clustering"),
        os.path.join(REFERENCE, "example", "autoencoder")], timeout=3000)
    assert "DEC_OK" in out, out[-3000:]


# ---------------------------------------------------------- memcost
@pytest.mark.slow
def test_reference_memcost_unmodified(tmp_path):
    """example/memcost/inception_memcost.py byte-identical: binds the
    full Inception-BN at (32,3,224,224) and prints the planned memory
    from Executor.debug_str() — backed here by XLA's compiled-program
    memory analysis.  Training allocation must dwarf the
    forward-only (grad_req='null') plan, the contrast the example
    exists to demonstrate (its Makefile's no_optimization vs
    forward_only targets; measured 1602 MB vs 235 MB)."""
    script = os.path.join(REFERENCE, "example", "memcost",
                          "inception_memcost.py")

    def run(argv_tail):
        code = ("import sys, runpy\n"
                "sys.argv = ['inception_memcost.py'%s]\n"
                "runpy.run_path(%r, run_name='__main__')\n"
                % (argv_tail, script))
        out = _run_code(code, str(tmp_path), timeout=2400)
        m = re.search(r"Total (\d+) MB allocated", out)
        assert m, out[-2000:]
        return int(m.group(1))

    train_mb = run("")
    fwd_mb = run(", 'null'")
    assert train_mb > fwd_mb * 2, (train_mb, fwd_mb)
    assert fwd_mb > 20, (train_mb, fwd_mb)


# ----------------------------------------------------- bi-lstm-sort
@pytest.mark.slow
def test_reference_bi_lstm_sort(tmp_path):
    """example/bi-lstm-sort: the reference's bidirectional LSTM
    seq2seq sorter — lstm.bi_lstm_unroll, sort_io.BucketSentenceIter
    (labels are the SORTED input sequence) and lstm_sort.Perplexity
    imported byte-identical; the driver shrinks scale only (its main
    trains hidden=300/embed=512 on a million generated lines).  The
    model must drive perplexity far below the uniform-vocab floor."""
    code = """
import numpy as np
# sort_io.py:204 divides a length with py2 `/` and feeds the float to
# np.zeros; restore the py2 tolerance process-locally (the np.int-alias
# pattern — no reference file touched)
_np_zeros = np.zeros


def _zeros_py2(shape, *a, **k):
    if isinstance(shape, float):
        shape = int(shape)
    return _np_zeros(shape, *a, **k)


np.zeros = _zeros_py2
import random
import mxnet as mx
from lstm import bi_lstm_unroll
from sort_io import BucketSentenceIter, default_build_vocab
from lstm_sort import Perplexity

random.seed(7)
np.random.seed(7)
mx.random.seed(7)
SEQ, VLOW, VHIGH = 5, 100, 120   # 20-symbol vocabulary
with open('sort.train.txt', 'w') as ftr, open('sort.valid.txt', 'w') as fv:
    for i in range(4000):
        seq = " ".join(str(random.randint(VLOW, VHIGH - 1))
                       for _ in range(SEQ))
        (fv if i % 20 == 0 else ftr).write(seq + "\\n")
vocab = default_build_vocab('sort.train.txt')
NH, NE, B = 32, 16, 50
init_states = [('l%d_init_%s' % (l, s), (B, NH))
               for l in range(2) for s in ('c', 'h')]
train = BucketSentenceIter('sort.train.txt', vocab, [SEQ], B, init_states)
val = BucketSentenceIter('sort.valid.txt', vocab, [SEQ], B, init_states)
sym = bi_lstm_unroll(SEQ, len(vocab), num_hidden=NH, num_embed=NE,
                     num_label=len(vocab))
model = mx.model.FeedForward(ctx=[mx.cpu()], symbol=sym, num_epoch=6,
                             learning_rate=0.05, momentum=0.9,
                             wd=0.00001,
                             initializer=mx.init.Normal(0.1))
perps = []


def cb(params):
    for name, value in params.eval_metric.get_name_value():
        perps.append(value)


model.fit(X=train, eval_data=val, eval_metric=mx.metric.np(Perplexity),
          eval_end_callback=cb)
print('SORT_PERPS', [round(p, 2) for p in perps])
# uniform over the 20-symbol vocab = perplexity 20; sorting is nearly
# deterministic given the multiset, so a learning model dives well
# below it
assert perps[-1] < 8.0, perps
assert perps[-1] < perps[0] * 0.6, perps
print('SORT_OK')
"""
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "bi-lstm-sort")], timeout=3000)
    assert "SORT_OK" in out, out[-2500:]


# ------------------------------------------------- stochastic-depth
@pytest.mark.slow
def test_reference_stochastic_depth_mnist(tmp_path):
    """example/stochastic-depth/sd_mnist.py run byte-identical from a
    verbatim copy of the example dir (the script writes nothing, but
    resolves its data dir relative to __file__, which is read-only
    under /root/reference — the copy is bit-for-bit).  Exercises
    StochasticDepthModule (a user-defined BaseModule subclass with
    train-time random block skipping) inside SequentialModule.fit."""
    import shutil

    sd_dir = str(tmp_path / "stochastic-depth")
    shutil.copytree(os.path.join(REFERENCE, "example", "stochastic-depth"),
                    sd_dir)
    # the script does sys.path.insert('..') + `from utils import
    # get_data`: the copied parent must carry the example-level utils
    # package (get_data.get_mnist short-circuits on existing files)
    shutil.copytree(os.path.join(REFERENCE, "example", "utils"),
                    str(tmp_path / "utils"))
    from test_examples_round4 import _seed_mnist_idx

    _seed_mnist_idx(os.path.join(sd_dir, "data"))
    code = ("import runpy\n"
            "runpy.run_path(%r, run_name='__main__')\n"
            % os.path.join(sd_dir, "sd_mnist.py"))
    out = _run_code(code, sd_dir, timeout=3000)
    accs = [float(m) for m in re.findall(
        r"Validation-accuracy=([0-9.]+)", out)]
    assert accs, out[-2500:]
    # bright-square synthetic digits: the 2-epoch sanity run must get
    # well past chance (0.1)
    assert max(accs) > 0.5, (accs, out[-1500:])


# --------------------------------------------------------- warpctc
@pytest.mark.slow
def test_reference_warpctc_toy_ctc(tmp_path):
    """plugin/warpctc's worked example (VERDICT r4 item 6): the
    reference's lstm.lstm_unroll (ends in mx.sym.WarpCTC, lstm.py:94)
    + toy_ctc's DataIter/Accuracy run byte-identical by
    tests/warpctc_runner.py; the CTC path must decode >25% of 4-digit
    sequences exactly (chance 1e-4)."""
    env = _env()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "warpctc_runner.py")],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=3500)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    assert "WARPCTC_OK" in proc.stdout


# ------------------------------------------------------------- DQN
@pytest.mark.slow
def test_reference_dqn_target_network(tmp_path):
    """The reference DQN stack (base.py Base wrapper, operators.py
    DQNOutput custom op, dqn_sym MLP-variant) on a 5-state numpy chain
    MDP: trains Q-values with a frozen target network, exercising
    Base.copy() and copy_params_to (the param-copy path VERDICT r4
    item 5 names)."""
    code = """
import numpy as np
# numpy>=1.24 removed the deprecated np.int alias operators.py:35
# uses; restore it process-locally (the SSD tests' collections.abc
# alias pattern — no reference file is touched)
np.int = int
import mxnet as mx
import sys
from collections import OrderedDict
import base as dqn_base
import operators  # registers DQNOutput
from base import Base

np.random.seed(0)
mx.random.seed(0)

n_state, n_action = 5, 2


def sym_small(action_num, name='dqn'):
    net = mx.symbol.Variable('data')
    net = mx.symbol.FullyConnected(data=net, name='fc1', num_hidden=32)
    net = mx.symbol.Activation(data=net, name='relu1', act_type='relu')
    net = mx.symbol.FullyConnected(data=net, name='fc2',
                                   num_hidden=action_num)
    net = mx.symbol.Custom(data=net, name=name, op_type='DQNOutput')
    return net


B = 32
qnet = Base(data_shapes={'data': (B, n_state),
                         'dqn_action': (B,), 'dqn_reward': (B,)},
            sym_gen=sym_small(n_action), name='QNet',
            initializer=mx.init.Xavier(), ctx=mx.cpu())
target = qnet.copy(name='TargetQNet', ctx=mx.cpu())
qnet.copy_params_to(target)
for k in qnet.params:
    assert np.allclose(qnet.params[k].asnumpy(),
                       target.params[k].asnumpy())

# chain MDP: state i, action 1 moves right (reward 1 at the end),
# action 0 resets. Optimal Q favors action 1 everywhere.
gamma = 0.9
opt = mx.optimizer.create('adam', learning_rate=0.01,
                          rescale_grad=1.0 / B)
updater = mx.optimizer.get_updater(opt)
rng = np.random.RandomState(0)
losses = []
onehot = np.eye(n_state, dtype='float32')
# value propagation travels ONE state per target sync (the frozen
# network is the Bellman iterate), so the 4-step chain needs well over
# 4 syncs; 450 iters / sync-every-25 = 18 Bellman iterations
for it in range(450):
    s = rng.randint(0, n_state, B)
    a = rng.randint(0, n_action, B)
    ns = np.where(a == 1, np.minimum(s + 1, n_state - 1), 0)
    r = ((a == 1) & (s == n_state - 2)).astype('float32')
    tq = target.forward(is_train=False,
                        data=mx.nd.array(onehot[ns]))[0].asnumpy()
    yb = r + gamma * tq.max(axis=1) * (s != n_state - 1)
    outs = qnet.forward(is_train=True, data=mx.nd.array(onehot[s]),
                        dqn_action=mx.nd.array(a.astype('float32')),
                        dqn_reward=mx.nd.array(yb.astype('float32')))
    qnet.backward()
    qnet.update(updater)
    qsel = outs[0].asnumpy()[np.arange(B), a]
    losses.append(float(np.mean((qsel - yb) ** 2)))
    if it % 25 == 24:
        qnet.copy_params_to(target)

q_all = qnet.forward(is_train=False,
                     data=mx.nd.array(np.eye(n_state, dtype='float32')))
q_all = q_all[0].asnumpy()
print('DQN_LOSS', losses[0], min(losses[-20:]))
print('DQN_Q', q_all.tolist())
# the learned policy must prefer moving right in pre-terminal states
assert (q_all[1:4, 1] > q_all[1:4, 0]).all(), q_all
assert min(losses[-20:]) < losses[0], losses[:3]
print('DQN_OK')
"""
    out = _run_code(code, str(tmp_path), extra_path=[
        os.path.join(REFERENCE, "example", "reinforcement-learning",
                     "dqn")])
    assert "DQN_OK" in out, out[-2500:]
