"""Distributed kvstore tests: spawn a real local PS cluster
(scheduler + servers + workers as processes) and assert exact
arithmetic identities — the reference's testing strategy for dist
kvstore (tests/nightly/dist_sync_kvstore.py run via
`tools/launch.py -n 4` with the local launcher, test_all.sh:55)."""
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import launch  # noqa: E402  (tools/launch.py)

_WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")


def _run_cluster(kind, num_workers, num_servers, extra_env=None):
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = {
        # workers only need CPU; keep jax off any accelerator in children
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.abspath(repo) + os.pathsep +
        os.environ.get("PYTHONPATH", ""),
    }
    env.update(extra_env or {})
    codes = launch.launch_local(
        num_workers, num_servers,
        [sys.executable, _WORKER, kind], env=env)
    assert codes == [0] * num_workers, "worker failures: %s" % codes


@pytest.mark.parametrize("workers,servers", [(2, 1), (3, 2)])
def test_dist_sync(workers, servers):
    _run_cluster("dist_sync", workers, servers)


def test_dist_async():
    _run_cluster("dist_async", 2, 1)


def test_dist_profiler_rank_dumps(tmp_path):
    """MXNET_PROFILER_AUTOSTART=1 makes every worker self-start tracing
    and dump profile_rank{K}.json (pid=rank) at exit — the inputs
    tools/merge_traces.py stitches into one timeline."""
    import json

    _run_cluster("dist_async", 2, 1, extra_env={
        "MXNET_PROFILER_AUTOSTART": "1",
        "MXNET_PROFILER_FILENAME": str(tmp_path / "profile.json")})
    for rank in range(2):
        path = tmp_path / ("profile_rank%d.json" % rank)
        assert path.exists(), "rank %d wrote no trace" % rank
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        assert events and all(e["pid"] == rank for e in events)
        # the worker's push/pull left comms spans in its trace
        assert any(e.get("cat") == "comms" and e.get("ph") == "X"
                   for e in events)


def test_flight_recorder_desync(tmp_path):
    """One worker of two intentionally skips its last push; both dump
    flight recorders at exit and `merge_traces.py --health` must name
    the lagging rank and the exact collective seq it never completed
    (the observability contract for a hung/desynced fleet)."""
    import json
    import subprocess

    base = tmp_path / "flightrecorder.json"
    _run_cluster("flight", 2, 1, extra_env={
        "MXNET_FLIGHT_RECORDER_DUMP": "1",
        "MXNET_FLIGHT_RECORDER_FILE": str(base)})
    dumps = []
    for rank in range(2):
        path = tmp_path / ("flightrecorder_rank%d.json" % rank)
        assert path.exists(), "rank %d wrote no flight recorder" % rank
        with open(path) as f:
            payload = json.load(f)
        assert payload["header"]["rank"] == rank
        dumps.append(str(path))
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "merge_traces.py")
    out = tmp_path / "health.json"
    res = subprocess.run(
        [sys.executable, tool, "--health", "-o", str(out)] + dumps,
        capture_output=True, text=True)
    # exit code 2 == desync detected
    assert res.returncode == 2, (res.returncode, res.stdout, res.stderr)
    # rank 0 pushed 4 times (seqs 0..3), rank 1 skipped the last: the
    # report names rank 1, stalled at seq 3, and the key it carried
    assert "rank 1 never completed seq 3" in res.stdout, res.stdout
    assert "keys a" in res.stdout, res.stdout
    with open(out) as f:
        report = json.load(f)
    (lag,) = report["desync"]["laggards"]
    assert lag["rank"] == 1 and lag["stalled_at_seq"] == 3
    assert report["desync"]["max_completed_seq"] == 3
    assert report["desync"]["ranks"]["0"]["last_seq_completed"] == 3
    assert report["desync"]["ranks"]["1"]["last_seq_completed"] == 2


def test_dist_compression_wire_bytes_and_numerics():
    """ISSUE 12 acceptance: a 2-worker cluster where compressed pushes
    show the 16x bytes-on-wire reduction in
    mxnet_kvstore_bytes_total{op=push} at numerics EXACTLY equal to the
    uncompressed path (representable-gradient + power-of-two error-
    feedback controls — the fp64/lr0 methodology applied to the wire
    format; all assertions live in dist_worker.run_compression_wire)."""
    _run_cluster("compression", 2, 1)


def test_dist_compression_env_toggle():
    """MXNET_GRADIENT_COMPRESSION turns on worker-side encode at
    create: the same 2-worker exactness suite must pass with the
    threshold coming from the env registry instead of an API call."""
    _run_cluster("compression_env", 2, 1, extra_env={
        "MXNET_GRADIENT_COMPRESSION": "2bit",
        "MXNET_GRADIENT_COMPRESSION_THRESHOLD": "0.5"})


def test_dist_sparse_wire_bytes_and_compression():
    """ISSUE 19 acceptance: on a 2-worker/2-server cluster (crc32
    spreads the emb:sN shard keys across both servers), row-sparse
    pull/push wire bytes are ∝ UNIQUE ROWS with exact formulas
    (U*(row_bytes+8) uncompressed, U*8 + ceil(U*dim/4) compressed) in
    mxnet_kvstore_bytes_total{op=row_sparse_pull|row_sparse_push}, and
    sparse 2-bit compression with per-row error feedback round-trips
    BITWISE against the uncompressed control (all assertions live in
    dist_worker.run_sparse_wire)."""
    _run_cluster("sparse_wire", 2, 2)


def test_dist_sparse_chaos_drop_pull():
    """ISSUE 19 chaos kind: rank 1's second row_sparse_pull response is
    dropped (drop_sparse_pull:rank=1,nth=2); the retry path must absorb
    it with every pulled value bitwise identical to the fault-free
    schedule (assertions in dist_worker.run_sparse_chaos)."""
    _run_cluster("sparse_chaos", 2, 1, extra_env={
        "MXNET_CHAOS": "drop_sparse_pull:rank=1,nth=2"})  # mxlint: disable=MXL002


def test_local_set_gradient_compression_raises():
    """Satellite bugfix: the local store used to SILENTLY store the
    params and never compress anything.  Every in-process spelling now
    raises loudly (only dist stores put bytes on a wire), matching the
    dist-path behavior; invalid params are rejected for all kinds."""
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError

    for kind in ("local", "device", "tpu"):
        kv = mx.kv.create(kind)
        with pytest.raises(MXNetError, match="dist"):
            kv.set_gradient_compression({"type": "2bit",
                                         "threshold": 0.5})
    # invalid params are rejected BEFORE the kind check, every kind
    with pytest.raises(ValueError):
        mx.kv.create("local").set_gradient_compression({"type": "1bit"})
    with pytest.raises(ValueError):
        mx.kv.create("local").set_gradient_compression(
            {"type": "2bit", "threshold": -1.0})
    # the launcher-less dist fallback (single process, no wire)
    # validates + warns instead: launcher scripts stay runnable
    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == 1
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    with pytest.raises(ValueError):
        kv.set_gradient_compression({"type": "1bit"})


def test_compression_wire_nbytes_accounting():
    """The deterministic wire accounting the push counter uses:
    ceil(n/4) bytes for a compressed dense push."""
    from mxnet_tpu.gradient_compression import GradientCompression

    assert GradientCompression.wire_nbytes(4096) == 1024
    assert GradientCompression.wire_nbytes(5) == 2
    gc = GradientCompression(type="2bit", threshold=0.5)
    codes, shape = gc.compress("k", np.zeros(4096, np.float32))
    assert len(codes) == GradientCompression.wire_nbytes(4096)


def test_gradient_compression_unit():
    from mxnet_tpu.gradient_compression import GradientCompression

    gc = GradientCompression(type="2bit", threshold=0.5)
    g = np.array([0.7, -0.9, 0.2, -0.1, 0.0, 1.5], np.float32)
    codes, shape = gc.compress("k", g)
    out = gc.decompress(codes, shape)
    np.testing.assert_allclose(out, [0.5, -0.5, 0, 0, 0, 0.5])
    # error feedback: residuals accumulate until they cross threshold
    codes, _ = gc.compress("k", g)
    out2 = gc.decompress(codes, shape)
    # second push of same grad: 0.2+0.2=0.4 still below, 0.7+0.2=0.9 ≥ .5
    np.testing.assert_allclose(out2, [0.5, -0.5, 0, 0, 0, 0.5])
    # packing matches 4-per-byte
    assert len(codes) == (6 + 3) // 4
    with pytest.raises(ValueError):
        GradientCompression(type="1bit")
    with pytest.raises(ValueError):
        GradientCompression(threshold=-1.0)


def test_single_process_dist_fallback():
    """dist_sync without DMLC env degrades to the local store."""
    import mxnet_tpu as mx

    for var in ("DMLC_ROLE", "DMLC_PS_ROOT_URI"):
        assert var not in os.environ
    kv = mx.kv.create("dist_sync")
    assert kv.rank == 0 and kv.num_workers == 1
    from mxnet_tpu import nd

    kv.init("k", nd.zeros((2,)))
    kv.push("k", nd.ones((2,)))
    out = nd.zeros((2,))
    kv.pull("k", out=out)
    np.testing.assert_allclose(out.asnumpy(), 1.0)


def test_server_command_channel_controller():
    """SendCommandToServers -> server controller (the MXKVStoreRunServer
    contract): a generic (head, body) command reaches the registered
    controller callback and is acked."""
    import socket as _socket

    from mxnet_tpu import _ps
    from mxnet_tpu.kvstore_server import KVStoreServer

    got = []
    srv = KVStoreServer.__new__(KVStoreServer)
    srv.controller = lambda head, body: got.append((head, body))
    a, b = _socket.socketpair()
    try:
        _ps.send_msg(a, {"op": "command", "head": 7, "body": "sync=0"})
        msg = _ps.recv_msg(b)
        assert srv._dispatch(b, msg) in (None, False)
        reply = _ps.recv_msg(a)
        assert reply == {"ok": True}
        assert got == [(7, "sync=0")]
    finally:
        a.close()
        b.close()
