"""Input-pipeline rearchitecture tests: sharded multi-process decode
pool + double-buffered async device prefetch (mxnet_tpu/io_pipeline.py).

The heavy lifecycle proofs (determinism, worker death, slow_decode
chaos, SIGTERM shared-memory hygiene) live in the module's own
``--self-test`` CLI and run here once as a subprocess; the in-process
tests cover the integration seams: per-iterator sharding coverage, the
device stage feeding a fused train step with donation-safe batches,
the io telemetry (queue depth gauge, decode histogram, io:* trace
lanes + overlap analysis), the compile-cache contract, and the MXL007
decode-worker lint."""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io_pipeline as iop

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _child_env():
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": ROOT + os.pathsep +
                env.get("PYTHONPATH", "")})
    env.pop("MXNET_CHAOS", None)
    return env


# ---------------------------------------------------------------------
# satellite: num_parts/part_index on every batch iterator — disjoint
# and exhaustive coverage across parts
# ---------------------------------------------------------------------
def _collect_ids(make_part, parts):
    """Label ids per part, unpadded."""
    out = []
    for p in range(parts):
        it = make_part(parts, p)
        ids = []
        while True:
            try:
                b = it.next()
            except StopIteration:
                break
            lab = b.label[0].asnumpy().reshape(-1)
            keep = len(lab) - b.pad
            ids.extend(int(v) for v in lab[:keep])
        out.append(ids)
    return out


def test_ndarray_iter_sharding_disjoint_exhaustive():
    x = np.arange(60, dtype=np.float32).reshape(30, 2)
    y = np.arange(30, dtype=np.float32)
    per_part = _collect_ids(
        lambda n, p: mx.io.NDArrayIter(x, y, batch_size=4, num_parts=n,
                                       part_index=p), 3)
    flat = [v for part in per_part for v in part]
    assert sorted(flat) == list(range(30))          # exhaustive
    assert len(flat) == len(set(flat))              # disjoint
    # strided slices, like MNISTIter
    assert per_part[1][:3] == [1, 4, 7]


def test_csv_iter_sharding(tmp_path):
    data = np.arange(24, dtype=np.float32).reshape(12, 2)
    label = np.arange(12, dtype=np.float32)
    dcsv, lcsv = str(tmp_path / "d.csv"), str(tmp_path / "l.csv")
    np.savetxt(dcsv, data, delimiter=",")
    np.savetxt(lcsv, label, delimiter=",")
    per_part = _collect_ids(
        lambda n, p: mx.io.CSVIter(data_csv=dcsv, data_shape=(2,),
                                   label_csv=lcsv, label_shape=(1,),
                                   batch_size=3, num_parts=n,
                                   part_index=p), 2)
    flat = [v for part in per_part for v in part]
    assert sorted(flat) == list(range(12)) and len(flat) == 12


def test_image_record_iter_sharding(tmp_path):
    from mxnet_tpu import recordio

    rec, idx = str(tmp_path / "a.rec"), str(tmp_path / "a.idx")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(12):
        img = rng.randint(0, 255, (32, 32, 3), dtype=np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, quality=90))
    w.close()
    per_part = _collect_ids(
        lambda n, p: mx.io.ImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 28, 28),
            batch_size=2, num_parts=n, part_index=p, dtype="uint8",
            shuffle=False), 3)
    flat = [v for part in per_part for v in part]
    assert sorted(flat) == list(range(12)) and len(flat) == 12
    assert per_part[0] == [0, 3, 6, 9]
    # no leaked temp shard files: __del__ removes the part copies
    import gc

    gc.collect()


def test_mnist_iter_next_raw_matches_next():
    it = mx.io.MNISTIter(batch_size=50, shuffle=False, num_parts=2,
                         part_index=0)
    data, label, pad = it.next_raw()
    assert data[0].shape == (50, 1, 28, 28) and pad == 0
    it2 = mx.io.MNISTIter(batch_size=50, shuffle=False, num_parts=2,
                          part_index=0)
    b = it2.next()
    np.testing.assert_array_equal(data[0], b.data[0].asnumpy())


# ---------------------------------------------------------------------
# the pool + device stage (in-process)
# ---------------------------------------------------------------------
def test_pipeline_stream_deterministic_and_complete():
    x = np.arange(64, dtype=np.float32).reshape(32, 2)
    y = np.arange(32, dtype=np.float32)
    fn = iop.make_ndarray_iter_fn(x, y, batch_size=4,
                                  last_batch_handle="discard")
    with iop.InputPipeline(fn, num_workers=2, device=False) as pipe:
        assert pipe.batch_size == 4
        assert pipe.provide_data[0].shape == (4, 2)
        e1 = []
        while True:
            try:
                b = pipe.next()
            except StopIteration:
                break
            e1.extend(int(v) for v in b.label[0].asnumpy())
        assert sorted(e1) == list(range(32))
        # worker 0 owns [0,2,4..], worker 1 [1,3,5..]; round-robin
        assert e1[:8] == [0, 2, 4, 6, 1, 3, 5, 7]
        pipe.reset()
        e2 = []
        while True:
            try:
                b = pipe.next()
            except StopIteration:
                break
            e2.extend(int(v) for v in b.label[0].asnumpy())
        assert e2 == e1
        assert pipe.cursor == 32


def test_device_prefetch_feeds_fused_step():
    """The tentpole integration: pool -> async device_put -> donated
    fused steps, with io:* spans on per-worker lanes and the overlap
    analyzer consuming the dump."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import diagnostics as diag
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, 64).astype(np.float32)
    fn = iop.make_ndarray_iter_fn(x, y, batch_size=8,
                                  last_batch_handle="discard")
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((1,), ("dp",), jax.devices()[:1])
    step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh)
    profiler.set_state("run")
    try:
        with iop.InputPipeline(fn, num_workers=2, device=True) as pipe:
            losses = None
            bd, bl = [], []
            while True:
                try:
                    b = pipe.next()
                except StopIteration:
                    break
                arr = b.data[0]._data
                assert hasattr(arr, "devices")  # device-committed
                bd.append(arr)
                bl.append(b.label[0]._data)
                if len(bd) == 4:
                    sd, sl = jnp.stack(bd), jnp.stack(bl)
                    iop.mark_disposable(sd)
                    iop.mark_disposable(sl)
                    losses = step.run_steps(sd, sl)
                    bd, bl = [], []
            assert losses is not None
            assert np.isfinite(losses.asnumpy()).all()
        events = [dict(e) for e in profiler._events]
    finally:
        profiler.set_state("stop")
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert "io:decode" in names and "io:device_put" in names \
        and "io:wait" in names
    assert any("run_steps" in n for n in names)
    # decode spans ride per-worker lanes at the reserved tid base
    lanes = {e["tid"] for e in events if e.get("name") == "io:decode"}
    assert lanes <= {iop.IO_WORKER_TID_BASE, iop.IO_WORKER_TID_BASE + 1}
    assert len(lanes) >= 1
    # overlap analyzer consumes the span families
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import merge_traces as mt
    finally:
        sys.path.pop(0)
    rep = mt.analyze_io_overlap({0: {"traceEvents": events}})
    assert rep and rep[0]["n_io_spans"] > 0 and rep[0]["n_step_spans"] > 0
    assert 0.0 <= rep[0]["prefetch_overlap_frac"] <= 1.0
    # metrics registry fed: queue depth gauge + decode-time histogram
    assert diag.metrics.gauge("mxnet_io_queue_depth").value is not None
    h = diag.metrics.histogram("mxnet_io_decode_seconds")
    assert h.count > 0


def test_donate_safe_put_disposable_handoff():
    """A pipeline-owned (disposable) array donates as-is; a caller-owned
    one still gets the defensive copy."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from mxnet_tpu.parallel.dp import _donate_safe_put

    dev = jax.devices()[0]
    sh = SingleDeviceSharding(dev)
    a = jax.device_put(np.ones((4, 4), np.float32), dev)
    iop.mark_disposable(a)
    assert _donate_safe_put(jax, a, sh) is a
    # the mark is one-shot: a second donate of the same array copies
    assert _donate_safe_put(jax, a, sh) is not a
    b = jax.device_put(np.ones((4, 4), np.float32), dev)
    placed = _donate_safe_put(jax, b, sh)
    assert placed is not b


def test_skip_batches_matches_consumed_stream():
    """skip_batches(n) lands the stream at exactly the position n
    next() calls would (the exact-resume fast path)."""
    x = np.arange(64, dtype=np.float32).reshape(32, 2)
    y = np.arange(32, dtype=np.float32)
    fn = iop.make_ndarray_iter_fn(x, y, batch_size=4,
                                  last_batch_handle="discard")
    with iop.InputPipeline(fn, num_workers=2, device=False) as p1:
        seq = []
        while True:
            try:
                seq.append([int(v) for v in p1.next().label[0].asnumpy()])
            except StopIteration:
                break
    with iop.InputPipeline(fn, num_workers=2, device=False) as p2:
        p2.skip_batches(3)
        assert p2.cursor == 12
        nxt = [int(v) for v in p2.next().label[0].asnumpy()]
        assert nxt == seq[3]


def test_self_test_cli():
    """The tier-1 wiring for the pool's lifecycle proofs: start/stop/
    drain, determinism, worker death, slow_decode chaos, device stage,
    SIGTERM shared-memory hygiene."""
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.io_pipeline", "--self-test"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT,
        timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["self_test_ok"], payload


# ---------------------------------------------------------------------
# satellite: the persistent compile cache is placed from OUTSIDE
# (JAX_COMPILATION_CACHE_DIR), else a fixed in-checkout directory
# ---------------------------------------------------------------------
_CC_CHILD = (
    "import json, os\n"
    "import jax\n"
    "dir_updates = []\n"
    "_orig = jax.config.update\n"
    "def _spy(name, val):\n"
    "    if name == 'jax_compilation_cache_dir':\n"
    "        dir_updates.append(val)\n"
    "    return _orig(name, val)\n"
    "jax.config.update = _spy\n"
    "from mxnet_tpu import compile_cache as cc\n"
    "default_before = cc.entry_count(cc.default_dir())\n"
    "d = cc.enable()\n"
    "assert cc.enable() == d and cc.enabled_dir() == d\n"
    "if os.environ.get('T_COMPILE'):\n"
    "    import jax.numpy as jnp\n"
    "    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()\n"
    "print('CC ' + json.dumps({'dir': d, 'dir_updates': dir_updates,\n"
    "    'jax_dir': jax.config.jax_compilation_cache_dir,\n"
    "    'entries': cc.entry_count(),\n"
    "    'default_grew': cc.entry_count(cc.default_dir())\n"
    "                    - default_before}))\n")


def _cc_children(*specs):
    """Run one child per ``(env_extra, cwd)`` spec, concurrently."""
    procs = []
    for env_extra, cwd in specs:
        env = _child_env()
        # the suite itself runs with the cache disabled (conftest);
        # these children exercise the enabled contract
        for k in ("JAX_ENABLE_COMPILATION_CACHE",
                  "JAX_COMPILATION_CACHE_DIR"):
            env.pop(k, None)
        env.update(env_extra)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CC_CHILD], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env, cwd=cwd))
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=240)
        assert p.returncode == 0, stdout + stderr
        line = [ln for ln in stdout.splitlines() if ln.startswith("CC ")]
        outs.append(json.loads(line[-1][3:]))
    return outs


def test_compile_cache_placed_from_outside_or_one_fixed_path(tmp_path):
    d = str(tmp_path / "cc")
    placed, a, b = _cc_children(
        ({"JAX_COMPILATION_CACHE_DIR": d, "T_COMPILE": "1"}, ROOT),
        ({}, ROOT), ({}, str(tmp_path)))
    # JAX_COMPILATION_CACHE_DIR set: entries land there and nowhere
    # else, and the code never configures a directory itself
    assert placed["dir"] == d and placed["jax_dir"] == d
    assert placed["dir_updates"] == []
    assert placed["entries"] >= 1
    assert placed["default_grew"] == 0
    assert any(f.endswith("-cache") for f in os.listdir(d))
    # unset: every process of this checkout — whatever its cwd — lands
    # on the same in-checkout directory
    want = os.path.join(ROOT, ".jax_cache")
    assert a["dir"] == b["dir"] == want
    assert a["jax_dir"] == want and a["dir_updates"] == [want]


def test_compile_cache_disabled_by_env_and_bad_dir_raises(tmp_path,
                                                          monkeypatch):
    import jax

    from mxnet_tpu import compile_cache

    # conftest disables the cache through JAX's own switch
    assert not jax.config.jax_enable_compilation_cache
    assert compile_cache.enable() is None
    assert compile_cache.enabled_dir() is None
    # a cache that cannot be enabled is an error, not a log line
    blocker = tmp_path / "file"
    blocker.write_text("x")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "default_dir",
                        lambda: str(blocker / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        with pytest.raises(OSError):
            compile_cache.enable()
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
    assert compile_cache.enabled_dir() is None
    assert jax.config.jax_compilation_cache_dir is None


# ---------------------------------------------------------------------
# satellite: MXL007 — jax/device calls inside decode-worker functions
# ---------------------------------------------------------------------
def test_mxlint_mxl007_flags_worker_jax():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import mxlint
    finally:
        sys.path.pop(0)
    src = (
        "import jax\n"
        "def _decode_worker_main(q):\n"
        "    x = q.get()\n"
        "    jax.device_put(x)\n"
        "def host_side(x):\n"
        "    return jax.device_put(x)\n"
        "def my_factory(num_parts=1, part_index=0):\n"
        "    return jax.numpy.zeros(())\n"
        "def boot():\n"
        "    return InputPipeline(iter_fn=my_factory)\n"
    )
    registered, import_ok = mxlint.registered_env_names()
    linter = mxlint.ModuleLinter("<t>.py", src, registered, import_ok,
                                 is_env_py=False)
    found = [(f["code"], f["scope"]) for f in linter.run()]
    assert ("MXL007", "_decode_worker_main") in found
    assert ("MXL007", "my_factory") in found  # iter_fn= callee flagged
    # jax on the HOST side (device stage, bench loops) stays legal
    assert not any(s == "host_side" for c, s in found if c == "MXL007")


def test_mxlint_repo_has_no_mxl007():
    """The shipped decode worker itself honors the host-only contract."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import mxlint
    finally:
        sys.path.pop(0)
    registered, import_ok = mxlint.registered_env_names()
    findings = mxlint.lint_paths(
        [os.path.join(ROOT, "mxnet_tpu", "io_pipeline.py")],
        registered, import_ok)
    assert not [f for f in findings if f["code"] == "MXL007"], findings


# ---------------------------------------------------------------------
# new env knobs are registered (mxlint MXL001 would also catch reads)
# ---------------------------------------------------------------------
def test_io_env_knobs_registered():
    from mxnet_tpu import env

    for name in ("MXNET_IO_WORKERS", "MXNET_IO_PREFETCH_DEPTH",
                 "MXNET_IO_POOL_SLOTS", "MXNET_IO_START_METHOD"):
        assert env.is_registered(name), name
    # the compile cache is placed by JAX's own variable, not a knob
    assert not env.is_registered("MXNET_COMPILE_CACHE_DIR")


# ---------------------------------------------------------------------
# elastic heartbeat coverage (ISSUE 15 satellite): the parent's decode
# wait beacons liveness — a supervised run starved behind slow decode
# workers must not be SIGKILLed as "hung"
# ---------------------------------------------------------------------
def test_io_wait_touches_heartbeat(tmp_path, monkeypatch):
    from mxnet_tpu import chaos as chaos_mod
    from mxnet_tpu import diagnostics as diag

    hb_dir = str(tmp_path / "hb")
    monkeypatch.setenv("MXNET_ELASTIC_HEARTBEAT_DIR", hb_dir)
    # a seeded straggler: every batch from worker 0 arrives ~0.6s late,
    # so the parent's fetch loop spins its Empty branch
    monkeypatch.setenv("MXNET_CHAOS",
                       "slow_decode:worker=0,ms=600,count=100")
    chaos_mod.reset()
    monkeypatch.setattr(diag, "_hb_last", 0.0)
    monkeypatch.setattr(diag, "_hb_path", None)
    x = np.arange(32, dtype=np.float32).reshape(16, 2)
    y = np.arange(16, dtype=np.float32)
    fn = iop.make_ndarray_iter_fn(x, y, batch_size=4,
                                  last_batch_handle="discard")
    pool = iop.ShardedDecodePool(fn, num_workers=1)
    try:
        b = pool.next()
        assert b is not None
        assert os.path.exists(os.path.join(hb_dir, "hb_rank0")), \
            os.listdir(hb_dir) if os.path.isdir(hb_dir) else "no hb"
    finally:
        pool.close()
        chaos_mod.reset()


# ---------------------------------------------------------------------
# one process per chip: workers are born with JAX_PLATFORMS=cpu
# ---------------------------------------------------------------------
def test_host_only_children_pins_the_environment_workers_inherit(
        monkeypatch):
    """A spawned worker imports jax (with the package) before its body
    runs, so the pin must be in the environment it inherits — and must
    not outlive the start in the parent."""
    import multiprocessing as mp

    from mxnet_tpu.context import host_only_children

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with host_only_children():
        with mp.get_context("spawn").Pool(1) as pool:
            seen = pool.apply(os.getenv, ("JAX_PLATFORMS",))
    assert seen == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with host_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ
