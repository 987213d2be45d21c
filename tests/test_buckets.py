"""Bucketed backward-overlapped gradient all-reduce (parallel/buckets.py
+ the FusedTrainStep/bulk/kvstore threading; ISSUE 4 tentpole).

Covers: the reverse-layer-order partitioner contract, numerical
equality of the bucketed reduction against the monolithic psum (and the
ppermute ring variant), >1 gradient reduction in the compiled HLO (no
round-5 combined monolith), sync-BN global-batch semantics, the
kvstore('tpu') fused fast path, the multi-context bulk fit, and the
overlap.py --self-test entry point.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import buckets
from mxnet_tpu.parallel.dp import FusedTrainStep
from mxnet_tpu.parallel.mesh import make_mesh, current_device_count
from mxnet_tpu.parallel.scaling import reduction_accounting

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _need_devices(n):
    if current_device_count() < n:
        pytest.skip("needs %d devices" % n)


# ---------------------------------------------------------------------
# partitioner unit tests
# ---------------------------------------------------------------------
def test_partition_reverse_layer_order_and_cap():
    entries = [("w%d" % i, (256,), "float32") for i in range(10)]  # 1 KB each
    plan = buckets.partition(entries, cap_bytes=3 * 1024)
    # reverse layer order: first bucket holds the LAST layers
    assert plan[0].keys == ("w9", "w8", "w7")
    # every grad exactly once
    seen = [k for b in plan for k in b.keys]
    assert sorted(seen) == sorted(e[0] for e in entries)
    assert len(seen) == len(set(seen))
    # size cap respected
    assert all(b.nbytes <= 3 * 1024 for b in plan)
    # deterministic
    assert buckets.partition(entries, cap_bytes=3 * 1024) == plan


def test_partition_oversize_grad_gets_own_bucket():
    entries = [("small", (4,), "float32"),
               ("huge", (10000,), "float32"),
               ("tail", (4,), "float32")]
    plan = buckets.partition(entries, cap_bytes=1024)
    assert ("huge",) in [b.keys for b in plan]
    seen = [k for b in plan for k in b.keys]
    assert sorted(seen) == ["huge", "small", "tail"]


def test_partition_never_mixes_dtypes():
    entries = [("a", (8,), "float32"), ("b", (8,), "bfloat16"),
               ("c", (8,), "bfloat16")]
    plan = buckets.partition(entries, cap_bytes=1 << 20)
    for b in plan:
        assert len({b.dtype}) == 1
    assert [b.keys for b in plan] == [("c", "b"), ("a",)]


def test_partition_first_last_cap_asymmetry():
    """The autotuner's knobs: bucket 0 capped separately (small first
    bucket -> comm starts while backward has barely run) and trailing
    buckets folded up to the last cap (tail reductions can't overlap
    anything anyway)."""
    entries = [("w%d" % i, (256,), "float32") for i in range(10)]  # 1 KB
    plan = buckets.partition(entries, cap_bytes=3 * 1024,
                             first_cap_bytes=1024,
                             last_cap_bytes=6 * 1024)
    assert plan[0].keys == ("w9",)  # first cap 1 KB
    # middle bucket(s) at the 3 KB cap, tail folded to <= 6 KB
    assert plan[1].keys == ("w8", "w7", "w6")
    assert plan[-1].nbytes <= 6 * 1024
    seen = [k for b in plan for k in b.keys]
    assert sorted(seen) == sorted(e[0] for e in entries)
    assert len(seen) == len(set(seen))
    # tail folding never merges into bucket 0
    assert plan[0].keys == ("w9",)
    # symmetric call unchanged by the new kwargs' defaults
    assert buckets.partition(entries, cap_bytes=3 * 1024) == \
        buckets.partition(entries, 3 * 1024)


def test_partition_last_cap_never_mixes_dtypes():
    entries = [("a", (512,), "float32"), ("b", (512,), "float32"),
               ("c", (512,), "bfloat16"), ("d", (512,), "bfloat16")]
    plan = buckets.partition(entries, cap_bytes=1024,
                             last_cap_bytes=1 << 20)
    for b in plan:
        assert len({b.dtype}) == 1
    # folds stay within one dtype: no bucket ever spans the boundary
    keys = [b.keys for b in plan]
    assert all(set(k) <= {"a", "b"} or set(k) <= {"c", "d"}
               for k in keys)


def test_bucket_cap_env_knob(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "123456")
    assert buckets.bucket_cap_bytes() == 123456
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "0")
    assert buckets.bucket_cap_bytes() == 0
    monkeypatch.delenv("MXNET_KVSTORE_BUCKET_BYTES")
    assert buckets.bucket_cap_bytes() == buckets.DEFAULT_BUCKET_BYTES


# ---------------------------------------------------------------------
# reduction equality (shard_map, CPU mesh)
# ---------------------------------------------------------------------
def _reduce_on_mesh(grads_np, plan, impl="psum", mean=False,
                    local_n=None):
    """Run bucketed_reduce under shard_map on the 8-device mesh; device
    d contributes ``value * (d+1)`` per key (leading device axis
    sharded over dp)."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((8,), ("dp",))
    args = {k: np.stack([v * (d + 1) for d in range(8)])
            for k, v in grads_np.items()}

    def local(args):
        stripped = {k: v.reshape(v.shape[1:]) for k, v in args.items()}
        return buckets.bucketed_reduce(stripped, plan, "dp", n=8,
                                       mean=mean, impl=impl,
                                       local_n=local_n)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P("dp"),), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)(args)


def test_bucketed_reduce_matches_monolithic_psum():
    _need_devices(8)
    rng = np.random.RandomState(0)
    grads = {i: rng.randn(*shape).astype("float32")
             for i, shape in enumerate([(33,), (8, 9), (120,), (5, 5, 5)])}
    entries = [(i, g.shape, g.dtype) for i, g in grads.items()]
    many = buckets.partition(entries, cap_bytes=512)
    one = buckets.partition(entries, cap_bytes=1 << 40)
    assert len(many) > 1 and len(one) == 1

    out_many = _reduce_on_mesh(grads, many)
    out_one = _reduce_on_mesh(grads, one)
    expect = {k: v * sum(range(1, 9)) for k, v in grads.items()}
    for k in grads:
        np.testing.assert_allclose(np.asarray(out_many[k]),
                                   np.asarray(out_one[k]), rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(out_many[k]), expect[k],
                                   rtol=1e-5)


def test_ring_impl_matches_psum():
    _need_devices(8)
    rng = np.random.RandomState(1)
    grads = {i: rng.randn(*shape).astype("float32")
             for i, shape in enumerate([(67,), (4, 11)])}
    entries = [(i, g.shape, g.dtype) for i, g in grads.items()]
    plan = buckets.partition(entries, cap_bytes=256)
    out_psum = _reduce_on_mesh(grads, plan, impl="psum")
    out_ring = _reduce_on_mesh(grads, plan, impl="ring")
    for k in grads:
        np.testing.assert_allclose(np.asarray(out_ring[k]),
                                   np.asarray(out_psum[k]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("local_n", [2, 4, 8])
def test_hierarchical_impl_matches_psum(local_n):
    """Two-tier reduction (intra-host psum, inter-host ppermute ring):
    the 8-device mesh split as H=8/local_n virtual hosts x local_n
    devices must produce the flat psum's sums; local_n=8 is the
    single-host degenerate case (pure intra psum)."""
    _need_devices(8)
    rng = np.random.RandomState(7)
    grads = {i: rng.randn(*shape).astype("float32")
             for i, shape in enumerate([(67,), (4, 11), (33,)])}
    entries = [(i, g.shape, g.dtype) for i, g in grads.items()]
    plan = buckets.partition(entries, cap_bytes=256)
    out_psum = _reduce_on_mesh(grads, plan, impl="psum")
    out_hier = _reduce_on_mesh(grads, plan, impl="hierarchical",
                               local_n=local_n)
    for k in grads:
        np.testing.assert_allclose(np.asarray(out_hier[k]),
                                   np.asarray(out_psum[k]),
                                   rtol=1e-5, atol=1e-5)


def test_hierarchical_without_local_n_falls_back_to_psum():
    """An unqualified topology (no local_n) must not break: the
    hierarchical impl silently reduces with the flat psum."""
    _need_devices(8)
    rng = np.random.RandomState(8)
    grads = {0: rng.randn(16).astype("float32")}
    plan = buckets.partition([(0, (16,), "float32")], cap_bytes=1 << 20)
    out = _reduce_on_mesh(grads, plan, impl="hierarchical", local_n=None)
    expect = grads[0] * sum(range(1, 9))
    np.testing.assert_allclose(np.asarray(out[0]), expect, rtol=1e-5)


def test_host_local_count_topologies():
    """host_local_count keys the hierarchical grouping off the mesh's
    process layout: contiguous equal blocks qualify, everything else
    (single device, ragged, interleaved) falls back."""
    class _Dev:
        def __init__(self, p):
            self.process_index = p

    class _Mesh:
        def __init__(self, procs):
            self.devices = np.array([_Dev(p) for p in procs],
                                    dtype=object)

    assert buckets.host_local_count(_Mesh([0, 0, 1, 1])) == 2
    assert buckets.host_local_count(_Mesh([0, 0, 0, 0])) == 4
    assert buckets.host_local_count(_Mesh([0, 0, 0, 1])) is None  # ragged
    assert buckets.host_local_count(_Mesh([0, 1, 0, 1])) is None  # interleaved
    assert buckets.host_local_count(_Mesh([0])) is None
    # the real single-host CPU mesh: every device is process 0
    mesh = make_mesh((8,), ("dp",))
    assert buckets.host_local_count(mesh) == 8


# ---------------------------------------------------------------------
# FusedTrainStep: bucketed path equality + HLO accounting
# ---------------------------------------------------------------------
def _bn_step(mesh, bucket_bytes, seed=3):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix="bkt%d_" % (bucket_bytes or 0))
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"))
        net.add(nn.BatchNorm())
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, learning_rate=0.1, momentum=0.9,
                          bucket_bytes=bucket_bytes)


def _traj(step, X, y, k=5):
    return [float(step(X, y)[0].asnumpy()) for _ in range(k)]


def test_fused_step_bucketed_equals_monolithic_psum():
    """The acceptance identity: bucketed reduction trajectories equal
    the monolithic-psum path (single bucket = one combined reduction of
    the same concatenated payload — identical per-element arithmetic)."""
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    t_bucketed = _traj(_bn_step(mesh, bucket_bytes=4096), X, y)
    t_mono = _traj(_bn_step(mesh, bucket_bytes=1 << 40), X, y)
    np.testing.assert_allclose(t_bucketed, t_mono, rtol=1e-7, atol=1e-7)


def test_fused_step_bucketed_matches_spmd_and_single_device():
    """Sync-BN check: the bucketed shard_map path keeps GLOBAL-batch
    BatchNorm statistics, so dp8 matches both the SPMD-partitioned
    program and the single-device run to fp tolerance."""
    _need_devices(8)
    mesh8 = make_mesh((8,), ("dp",))
    mesh1 = make_mesh((1,), ("dp",))
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    t_bucketed = _traj(_bn_step(mesh8, bucket_bytes=4096), X, y)
    t_spmd = _traj(_bn_step(mesh8, bucket_bytes=0), X, y)
    t_one = _traj(_bn_step(mesh1, bucket_bytes=None), X, y)
    np.testing.assert_allclose(t_bucketed, t_spmd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(t_bucketed, t_one, rtol=1e-4, atol=1e-5)


def test_fused_step_hlo_has_multiple_gradient_reductions():
    """Round-5's failure mode was ONE combined 44.77 MB sync all-reduce;
    the bucketed program must compile to >1 reduction op."""
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    step = _bn_step(mesh, bucket_bytes=4096)
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    assert step.run_steps(X, y, steps=1).shape == (1,)
    assert step.bucketed
    plan = step.bucket_accounting()
    assert plan is not None and len(plan) > 1
    text = step.lower_only(X, y).compile().as_text()
    rows = [r for r in reduction_accounting(text)
            if r["op"].startswith("all-reduce")]
    assert len(rows) > 1, rows
    # every bucket payload appears as a reduction of exactly its size
    red_bytes = sorted(r["bytes"] for r in rows)
    for b in plan:
        assert b["bytes"] in red_bytes, (plan, rows)


def test_fused_step_run_steps_bucketed_equals_monolithic():
    """The K-step scan path (run_steps) rides the same bucketed step."""
    _need_devices(8)
    mesh = make_mesh((8,), ("dp",))
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    l_b = _bn_step(mesh, bucket_bytes=4096).run_steps(X, y, steps=4)
    l_m = _bn_step(mesh, bucket_bytes=1 << 40).run_steps(X, y, steps=4)
    np.testing.assert_allclose(l_b.asnumpy(), l_m.asnumpy(),
                               rtol=1e-7, atol=1e-7)


# ---------------------------------------------------------------------
# autotuned plans: numerics regression (ISSUE 12 satellite) — a tuned
# plan is a different SCHEDULE of the same arithmetic, so trajectories
# must match the monolithic-psum path at fp tolerance on the dp=2 mesh
# ---------------------------------------------------------------------
def _autotune_plan_file(tmp_path, **caps):
    plan = {"format": "mxnet-tpu-autotune-plan", "version": 1,
            "cap_bytes": caps.get("cap_bytes", 2048),
            "first_cap_bytes": caps.get("first_cap_bytes"),
            "last_cap_bytes": caps.get("last_cap_bytes"),
            "fingerprint": None}
    path = str(tmp_path / "plan.json")
    with open(path, "w") as f:
        json.dump(plan, f)
    return path


_AT_PREFIX = [0]


def _bn_step2(mesh, bucket_bytes, seed=3):
    """Same net family as _bn_step but prefix-isolated per build so the
    autotuned steps never share parameter cells."""
    np.random.seed(seed)
    mx.random.seed(seed)
    _AT_PREFIX[0] += 1
    net = nn.HybridSequential(prefix="at%d_" % _AT_PREFIX[0])
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"))
        net.add(nn.BatchNorm())
        net.add(nn.Dense(32, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mesh=mesh, learning_rate=0.1, momentum=0.9,
                          bucket_bytes=bucket_bytes)


def test_fused_step_autotuned_plan_equals_monolithic(tmp_path,
                                                     monkeypatch):
    """An autotuned plan (caps != 4 MiB, asymmetric first/last) on the
    CPU dp=2 mesh reproduces the monolithic-psum trajectory at ~1e-7,
    and the tuning provenance lands in the step's plan stamp."""
    _need_devices(2)
    from mxnet_tpu import diagnostics

    mesh = make_mesh((2,), ("dp",))
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    path = _autotune_plan_file(tmp_path, cap_bytes=2048,
                               first_cap_bytes=1024,
                               last_cap_bytes=8192)
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", path)
    step_tuned = _bn_step2(mesh, None)  # bucket_bytes=None -> tuned
    t_tuned = _traj(step_tuned, X, y)
    assert step_tuned.bucketed
    tuning = step_tuned.bucket_tuning()
    assert tuning is not None and tuning["plan_path"] == path
    assert tuning["cap_bytes"] == 2048
    # every bucket honors the tuned caps (first bucket the small one)
    acct = step_tuned.bucket_accounting()
    assert acct[0]["bytes"] <= 1024
    assert all(b["bytes"] <= 8192 for b in acct)
    # the flight-recorder header stamp carries the tuning provenance
    stamped = diagnostics.bucket_plan()
    assert stamped and stamped.get("autotune", {}).get("plan_path") == path

    monkeypatch.delenv("MXNET_AUTOTUNE_PLAN")
    t_mono = _traj(_bn_step2(mesh, 1 << 40), X, y)
    np.testing.assert_allclose(t_tuned, t_mono, rtol=1e-7, atol=1e-7)


def test_fused_step_degenerate_one_bucket_plan_equals_monolithic(
        tmp_path, monkeypatch):
    """The degenerate tuned plan (one huge cap -> 1 bucket) is exactly
    the monolithic concat-psum: trajectories must agree at ~1e-7."""
    _need_devices(2)
    mesh = make_mesh((2,), ("dp",))
    X = nd.array(np.random.RandomState(5).rand(16, 6).astype("float32"))
    y = nd.array(np.random.RandomState(6).randint(0, 4, 16)
                 .astype("float32"))
    path = _autotune_plan_file(tmp_path, cap_bytes=1 << 40)
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", path)
    step = _bn_step2(mesh, None)
    t_one = _traj(step, X, y)
    assert step.bucketed and len(step.bucket_accounting()) == 1
    monkeypatch.delenv("MXNET_AUTOTUNE_PLAN")
    t_mono = _traj(_bn_step2(mesh, 1 << 40), X, y)
    np.testing.assert_allclose(t_one, t_mono, rtol=1e-7, atol=1e-7)


# ---------------------------------------------------------------------
# kvstore('tpu') fused fast path
# ---------------------------------------------------------------------
def test_kvstore_tpu_bucketed_push_matches_local():
    from mxnet_tpu.kvstore import KVStoreTPU

    kv = mx.kv.create("tpu")
    assert isinstance(kv, KVStoreTPU)
    keys = ["a", "b", "c"]
    rng = np.random.RandomState(2)
    vals = [[nd.array(rng.randn(32, 8).astype("float32"))
             for _ in range(4)] for _ in keys]
    kv.init(keys, [v[0] for v in vals])
    kv.push(keys, vals)
    outs = [nd.zeros((32, 8)) for _ in keys]
    kv.pull(keys, outs)

    kvl = mx.kv.create("local")
    kvl.init(keys, [v[0] for v in vals])
    kvl.push(keys, vals)
    outsl = [nd.zeros((32, 8)) for _ in keys]
    kvl.pull(keys, outsl)
    for o, ol in zip(outs, outsl):
        # stacked-sum vs sequential adds: fp reduction order differs
        np.testing.assert_allclose(o.asnumpy(), ol.asnumpy(),
                                   rtol=1e-4, atol=1e-6)


def test_kvstore_tpu_push_stamps_bucket_telemetry(tmp_path):
    from mxnet_tpu import profiler

    kv = mx.kv.create("tpu")
    keys = list("abcd")
    vals = [[nd.ones((64, 64)) for _ in range(2)] for _ in keys]
    kv.init(keys, [v[0] for v in vals])
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, profile_all=True)
    profiler.set_state("run")
    kv.push(keys, vals)
    profiler.set_state("stop")
    trace = profiler.dump()
    with open(fname) as f:
        text = f.read()
    assert "KVStore::AllReduceBucket" in text
    assert "kvstore:bucket_allreduce_bytes" in text


# ---------------------------------------------------------------------
# overlap.py --self-test (tier-1 CI for the async-pair parser)
# ---------------------------------------------------------------------
def test_overlap_self_test_module():
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.parallel.overlap",
         "--self-test"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env=dict(os.environ))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["self_test_ok"] is True
    assert rec["parsed"]["n_async_pairs"] == 2
    assert rec["parsed"]["overlap_measured"] == 1.0


def test_schedulable_bound_respects_dependencies():
    """The dataflow bound must refuse credit for compute that DEPENDS on
    the reduction result."""
    from mxnet_tpu.parallel.overlap import schedulable_overlap_from_text

    hlo = """
HloModule t

%add.0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[64,64], g: f32[1000000]) -> f32[64,64] {
  %x = f32[64,64] parameter(0)
  %g = f32[1000000] parameter(1)
  %ar = f32[1000000] all-reduce(%g), to_apply=%add.0
  %w = f32[64,64] bitcast(f32[1000000] %ar)
  %dep = f32[64,64] dot(%w, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %out = f32[64,64] add(%dep, %dep)
}
"""
    out = schedulable_overlap_from_text(hlo, achieved_flops=1e9)
    assert out["n_reduction_ops"] == 1
    # the only dot is a descendant of the all-reduce: nothing hidable
    assert out["overlap_schedulable"] == 0.0

    hlo_free = hlo.replace("dot(%w, %x)", "dot(%x, %x)")
    out2 = schedulable_overlap_from_text(hlo_free, achieved_flops=1e6)
    assert out2["overlap_schedulable"] == 1.0


# ---------------------------------------------------------------------
# multi-context Module.fit rides the bucketed bulk scan
# ---------------------------------------------------------------------
def _fit_module(nctx, with_bn=False):
    from mxnet_tpu import engine, io as mio, sym

    np.random.seed(0)
    mx.random.seed(0)
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    net = sym.Activation(data=net, act_type="relu")
    if with_bn:
        net = sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(data=net, name="softmax")
    X = np.random.RandomState(1).rand(64, 10).astype("float32")
    y = (X @ np.arange(10) > 4.5).astype("float32")
    it = mio.NDArrayIter(X, y, batch_size=16)
    ctxs = [mx.cpu(i) for i in range(nctx)] if nctx > 1 else mx.cpu()
    mod = mx.mod.Module(symbol=net, context=ctxs)
    with engine.bulk(4):
        mod.fit(it, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05}, num_epoch=4)
    return mod


@pytest.mark.parametrize("with_bn", [False, True])
def test_bulk_fit_multi_context_bucketed(with_bn):
    _need_devices(8)
    mod1 = _fit_module(1, with_bn)
    mod8 = _fit_module(8, with_bn)
    bl = mod8._bulk_loop
    assert bl is not None and bl.available(), \
        bl._reason if bl else "no bulk loop"
    assert bl._bucketed, "8-ctx bulk must ride the bucketed shard_map"
    w1 = mod1._exec.arg_dict["fc1_weight"].asnumpy()
    w8 = mod8._exec.arg_dict["fc1_weight"].asnumpy()
    np.testing.assert_allclose(w1, w8, rtol=1e-5, atol=1e-6)
