"""Drive the reference model-parallel LSTM library byte-identical.

BASELINE config 5 (example/model-parallel/lstm/): imports
``lstm.py`` STRAIGHT from /root/reference (no copy, no edit) through the
compat/mxnet shim and trains it with ctx_group placement over distinct
virtual devices — the PlaceDevice pass working on a real model-parallel
workload (ref: lstm.py:65-75 AttrScope ctx_group tagging,
src/executor/graph_executor.cc:406 PlaceDevice,
src/operator/cross_device_copy.cc).

The reference's driver (lstm_ptb.py) pulls its data through
example/rnn/old/bucket_io.py, which is python2-only (true-division float
into np.zeros, bucket_io.py:208) — the LIBRARY is the config's
substance, so this runner supplies the tiny py3 data iterator and keeps
every modeling/executor/training line the reference's own.

Run under: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""
import os
import sys

import numpy as np

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
REF_LSTM_DIR = "/root/reference/example/model-parallel/lstm"
sys.path.insert(0, os.path.join(ROOT, "compat"))
sys.path.insert(0, ROOT)
sys.path.insert(0, REF_LSTM_DIR)

import mxnet as mx  # the compat shim
import lstm         # BYTE-IDENTICAL reference library


class TinyBucketIter:
    """Minimal stand-in for bucket_io.BucketSentenceIter's surface as
    consumed by lstm.train_lstm: iterable of batches with ``.data``
    (seq_len, batch) int ids and ``.bucket_key``; reset()."""

    class Batch:
        def __init__(self, data, key):
            self.data = data
            self.bucket_key = key

    def __init__(self, vocab, buckets, batch_size, n_batches, seed):
        rng = np.random.RandomState(seed)
        self.batches = []
        for i in range(n_batches):
            key = buckets[i % len(buckets)]
            self.batches.append(self.Batch(
                rng.randint(1, vocab, (key, batch_size)).astype(np.float64),
                key))
        self.default_bucket_key = max(buckets)

    def __iter__(self):
        return iter(self.batches)

    def reset(self):
        pass


def main():
    batch_size = 8
    num_hidden = 32
    num_embed = 16
    num_lstm_layer = 2
    vocab = 50
    buckets = [12]

    # the reference placement plan (lstm_ptb.py:96-100) on N virtual
    # devices: embed on gpu(0), decode on the last, layers striped.
    ngpu = 2
    group2ctx = {"embed": mx.gpu(0), "decode": mx.gpu(ngpu - 1)}
    for i in range(num_lstm_layer):
        group2ctx["layer%d" % i] = mx.gpu(i * ngpu // num_lstm_layer)

    model = lstm.setup_rnn_model(
        mx.gpu(), group2ctx=group2ctx, concat_decode=False, use_loss=True,
        num_lstm_layer=num_lstm_layer,
        seq_len=buckets[0],
        num_hidden=num_hidden, num_embed=num_embed, num_label=vocab,
        batch_size=batch_size, input_size=vocab,
        initializer=mx.initializer.Uniform(0.1), dropout=0.0,
        buckets=list(buckets))

    # placement must be REAL: embed and decode params on distinct
    # jax devices of the virtual mesh
    m = model[buckets[0]]
    devs = {}
    for name, arr in m.rnn_exec.arg_dict.items():
        devs[name] = str(next(iter(arr._data.devices())))
    embed_dev = devs["embed_weight"]
    decode_dev = devs["cls_weight"]  # 'decode' ctx_group (lstm.py:68-70)
    print("embed on", embed_dev, "| decode on", decode_dev)
    if ngpu > 1:
        assert embed_dev != decode_dev, \
            "embed and decode must be placed on different devices"
    else:
        assert embed_dev == decode_dev, \
            "single-group control must land on one device"

    train = TinyBucketIter(vocab, buckets, batch_size, n_batches=6, seed=0)
    val = TinyBucketIter(vocab, buckets, batch_size, n_batches=2, seed=1)

    lstm.train_lstm(model, train, val,
                    num_round=2, update_period=1, concat_decode=False,
                    batch_size=batch_size, use_loss=True, half_life=2,
                    max_grad_norm=5.0, learning_rate=0.5, wd=0.0)
    print("MP_LSTM_OK")


if __name__ == "__main__":
    main()
