"""Decompose the ImageRecordIter->train end-to-end rate into stages.

The round-2 bench reported 186 img/s end-to-end against 1,295+ img/s of
compute (io_vs_compute 0.144) without saying WHY.  This tool measures
each stage in isolation on the current backend so the bottleneck is a
number, not a guess (ref contract this pipeline must meet:
src/io/iter_image_recordio_2.cc:138-171 OMP decode pool +
src/io/iter_prefetcher.h:47 double-buffered prefetch):

  1. decode      - native pipeline rate, no Python copy, no device
  2. deliver     - decode + the Python-side view copy/cast (io.py next())
  3. h2d_link    - host->device bandwidth, float32 and uint8 batch sizes
  4. d2h_link    - device->host (the drain path)
  5. compute     - fused train step on device-resident data (bulk path)
  6. e2e         - the full overlapped pipeline as bench.py runs it

Prints one JSON dict.  Run with no args on the default backend; on
CPU it still decomposes decode/deliver.
"""
import json
import os
import sys
import tempfile
import time

import ctypes as ct

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_rec(n=256, size=256, tmp=None):
    from mxnet_tpu import recordio

    tmp = tmp or tempfile.mkdtemp(prefix="io_diag_")
    rec_path = os.path.join(tmp, "diag.rec")
    idx_path = os.path.join(tmp, "diag.idx")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img, quality=90))
    w.close()
    return rec_path, idx_path, n


def bench_decode_native(rec_path, idx_path, batch, threads, epochs=4):
    """Stage 1: pull batches straight off the C ring buffer, touch one
    byte, release.  No numpy copy, no cast, no device."""
    from mxnet_tpu import _native

    L = _native.lib()
    mean = (ct.c_float * 3)(0, 0, 0)
    std = (ct.c_float * 3)(1, 1, 1)
    h = ct.c_void_p()
    rc = L.MXTPUImageIterCreate(
        rec_path.encode(), idx_path.encode(), batch, 3, 224, 224,
        1, 1, 1, mean, std, threads, 0, 1, 0, 1, 4, ct.byref(h))
    assert rc == 0
    data_p = ct.POINTER(ct.c_float)()
    label_p = ct.POINTER(ct.c_float)()
    pad = ct.c_int()
    seen = 0
    t0 = time.time()
    for _ in range(epochs):
        L.MXTPUImageIterReset(h)
        while True:
            rc = L.MXTPUImageIterNext(h, ct.byref(data_p), ct.byref(label_p),
                                      ct.byref(pad))
            if rc != 1:
                break
            seen += batch
    dt = time.time() - t0
    L.MXTPUImageIterFree(h)
    return seen / dt


def bench_deliver(rec_path, idx_path, batch, threads, dtype, epochs=4):
    """Stage 2: the full Python iterator surface (copy + cast), no
    device."""
    from mxnet_tpu import io

    it = io.ImageRecordIter(
        path_imgrec=rec_path, path_imgidx=idx_path,
        data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
        rand_crop=True, rand_mirror=True, preprocess_threads=threads,
        dtype=dtype)
    seen = 0
    t0 = time.time()
    for _ in range(epochs):
        it.reset()
        while True:
            try:
                b = it.next()
            except StopIteration:
                break
            seen += batch
    return seen / (time.time() - t0)


def _device_drain(x):
    return np.asarray(x).reshape(-1)[0]


def bench_link(batch, reps=12):
    """Stages 3+4: raw host<->device bandwidth at batch granularity."""
    import jax
    import jax.numpy as jnp

    out = {}
    for name, arr in [
            ("f32", np.random.rand(batch, 3, 224, 224).astype(np.float32)),
            ("u8", np.random.randint(0, 255, (batch, 3, 224, 224),
                                     dtype=np.uint8))]:
        nbytes = arr.nbytes
        d = jax.device_put(arr)  # warm
        _device_drain(d[0, 0, 0, :1])
        t0 = time.time()
        for _ in range(reps):
            d = jax.device_put(arr)
        _device_drain(d[0, 0, 0, :1])
        dt = time.time() - t0
        out["h2d_%s_MBps" % name] = round(nbytes * reps / dt / 1e6, 1)
        out["h2d_%s_batch_ms" % name] = round(dt / reps * 1e3, 2)
        # d2h: pull the whole batch back
        t0 = time.time()
        for _ in range(reps):
            host = np.asarray(d)
        dt = time.time() - t0
        out["d2h_%s_MBps" % name] = round(nbytes * reps / dt / 1e6, 1)
    return out


def bench_compute(batch, bulk_k=48, dtype=None):
    """Stage 5: fused train step on device-resident data."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((1,), ("dp",), jax.devices()[:1])
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, learning_rate=0.05, momentum=0.9,
                          dtype=dtype)
    X = nd.random.uniform(shape=(batch, 3, 224, 224))
    y = nd.array(np.random.randint(0, 1000, batch).astype("float32"))
    losses = step.run_steps(X, y, steps=bulk_k)
    _device_drain(losses.asnumpy())
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        losses = step.run_steps(X, y, steps=bulk_k)
        _device_drain(losses.asnumpy())
        best = min(best, time.time() - t0)
    return batch * bulk_k / best, step


def main():
    batch = 32
    threads = int(os.environ.get("IO_DIAG_THREADS", "8"))
    out = {"batch": batch, "threads": threads}

    rec_path, idx_path, n = make_rec()
    out["decode_native_ips"] = round(
        bench_decode_native(rec_path, idx_path, batch, threads), 1)
    out["deliver_f32_ips"] = round(
        bench_deliver(rec_path, idx_path, batch, threads, "float32"), 1)
    out["deliver_u8_ips"] = round(
        bench_deliver(rec_path, idx_path, batch, threads, "uint8"), 1)

    import jax
    out["backend"] = jax.devices()[0].device_kind
    out.update(bench_link(batch))

    compute_ips, _ = bench_compute(batch)
    out["compute_f32_ips"] = round(compute_ips, 1)

    # stage 6: bench.py's own decomposed e2e row
    import bench as bench_mod
    out["bench_io_row"] = bench_mod.bench_recordio_input(
        compute_ips=compute_ips, compute_dtype="float32", batch=batch)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
