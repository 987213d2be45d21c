"""mxnet_tpu.diagnostics — flight recorder, recompile tracking,
step-metrics registry, and the merge_traces --health analysis (fast
tier-1).

Covers the observability acceptance contract: ring-buffer wraparound,
watchdog suspect-marking + dump, on-demand/exit/signal dump paths,
desync identification from per-rank dumps (rank + exact seq/bucket),
>=2-compile detection with the recompilation-storm warning when input
shapes churn, and Prometheus text-exposition validity.
"""
import json
import logging
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import diagnostics as diag
from mxnet_tpu import nd

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import merge_traces  # noqa: E402


# ---------------------------------------------------------------------
# flight recorder core
# ---------------------------------------------------------------------
def test_ring_wraparound_keeps_latest():
    fr = diag.FlightRecorder(capacity=8)
    for i in range(20):
        seq = fr.start("push", keys=["k%d" % i], nbytes=4 * i,
                       dtype="float32")
        assert seq == i  # seqs are monotonic and dense
        fr.complete(seq)
    header, entries = fr.snapshot()
    assert len(entries) == 8
    assert header["dropped"] == 12
    assert header["next_seq"] == 20
    assert [e["seq"] for e in entries] == list(range(12, 20))
    assert all(e["state"] == "completed" for e in entries)
    assert all(e["complete_ts"] >= e["enqueue_ts"] for e in entries)


def test_record_collective_states():
    fr = diag.FlightRecorder(capacity=4)
    # completed
    s = fr.start("allreduce", keys=[0, 1], bucket=2, nbytes=1024,
                 dtype="bfloat16")
    fr.complete(s)
    _, entries = fr.snapshot()
    assert entries[0]["keys"] == ["0", "1"]
    assert entries[0]["bucket"] == 2
    assert entries[0]["dtype"] == "bfloat16"
    # in-flight entry stays in-flight until completed
    fr.start("push", keys=["w"])
    assert len(fr.in_flight()) == 1
    assert fr.last_completed_seq() == 0


def test_record_collective_error_state():
    fr = diag.FlightRecorder(capacity=4)
    old, diag.recorder = diag.recorder, fr
    try:
        with pytest.raises(RuntimeError):
            with diag.record_collective("push", keys=["a"]):
                raise RuntimeError("boom")
    finally:
        diag.recorder = old
    _, entries = fr.snapshot()
    assert entries[0]["state"] == "error"
    assert entries[0]["complete_ts"] is not None


def test_disabled_recorder_is_noop():
    fr = diag.FlightRecorder(capacity=0)
    assert not fr.enabled
    assert fr.start("push", keys=["a"]) is None
    assert fr.dump() is None


def test_watchdog_marks_suspect_and_dumps(tmp_path):
    fr = diag.FlightRecorder(capacity=8)
    fr.start("bucket_reduce", keys=["w7"], bucket=7, nbytes=1 << 20,
             dtype="float32")
    path = str(tmp_path / "wd.json")
    fr.dump_path = lambda base=None: path
    import time as _time

    _time.sleep(0.02)
    n = fr.check_timeouts(0.01)
    assert n == 1
    with open(path) as f:
        payload = json.load(f)
    assert payload["header"]["reason"] == "watchdog_timeout"
    (entry,) = payload["entries"]
    assert entry["state"] == "suspect" and entry["bucket"] == 7
    # suspects persist; a second check does not re-dump (no new suspect)
    os.unlink(path)
    assert fr.check_timeouts(0.01) == 1
    assert not os.path.exists(path)


def test_dump_on_demand_rank_suffix(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    # unset MXNET_DUMP_DIR (conftest defaults it): this test pins the
    # env-less behavior — relative dumps land in the CWD
    monkeypatch.delenv("MXNET_DUMP_DIR", raising=False)
    fr = diag.FlightRecorder(capacity=4)
    s = fr.start("push", keys=["a"], nbytes=16, dtype="float32")
    fr.complete(s)
    fname = fr.dump()
    assert fname == "flightrecorder_rank0.json"
    with open(fname) as f:
        payload = json.load(f)
    assert payload["header"]["flight_recorder"] is True
    assert payload["header"]["rank"] == 0
    assert merge_traces.is_flight_payload(payload)


def test_dump_env_boolean_spellings_agree(monkeypatch):
    """MXNET_FLIGHT_RECORDER_DUMP regression: boolean spellings (any
    case) request a dump WITHOUT hijacking the output path, and the
    atexit leg + dump_path share one parse so they never disagree."""
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_FILE", "/tmp/cfg.json")
    for spelling in ("1", "true", "TRUE", "yes", "on"):
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DUMP", spelling)
        want, override = diag._dump_env()
        assert want and override is None, spelling
        assert diag.recorder.dump_path() == "/tmp/cfg_rank0.json", spelling
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DUMP", "/tmp/flag.json")
    assert diag._dump_env() == (True, "/tmp/flag.json")
    assert diag.recorder.dump_path() == "/tmp/flag_rank0.json"
    for spelling in ("0", "false", "FALSE", "no", "off"):
        monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DUMP", spelling)
        assert diag._dump_env() == (False, None), spelling


def test_sigusr1_chains_app_handler(tmp_path):
    """The dump handler must not silently eat a SIGUSR1 handler the
    application installed first — it dumps, then chains."""
    import signal as _signal
    import time as _time

    fired = []
    prev_usr1 = _signal.signal(_signal.SIGUSR1,
                               lambda s, f: fired.append(s))
    prev_term = _signal.getsignal(_signal.SIGTERM)
    try:
        fr = diag.FlightRecorder(capacity=4)
        s = fr.start("push", keys=["a"])
        fr.complete(s)
        path = str(tmp_path / "usr1.json")
        fr.dump_path = lambda base=None: path
        assert fr.install_signal_handlers()
        os.kill(os.getpid(), _signal.SIGUSR1)
        for _ in range(100):
            if fired and os.path.exists(path):
                break
            _time.sleep(0.01)
        assert os.path.exists(path)  # the dump happened
        assert fired == [_signal.SIGUSR1]  # ...and the app handler ran
    finally:
        _signal.signal(_signal.SIGUSR1, prev_usr1)
        _signal.signal(_signal.SIGTERM, prev_term)


def test_bucket_plan_header_stamp():
    fr = diag.FlightRecorder(capacity=4)
    fr.set_bucket_plan({"n_buckets": 3, "total_bytes": 300,
                        "cap_bytes": 100})
    header, _ = fr.snapshot()
    assert header["bucket_plan"]["n_buckets"] == 3


def test_bucket_plan_owned_clear():
    """A monolithic step builder clearing the plan only erases its OWN
    stale stamp — a different live bucketed step's plan survives."""
    fr = diag.FlightRecorder(capacity=4)
    fr.set_bucket_plan({"n_buckets": 2}, owner=111)  # live bucketed step
    fr.set_bucket_plan(None, owner=222)  # someone else's monolithic build
    assert fr.bucket_plan() == {"n_buckets": 2}
    fr.set_bucket_plan(None, owner=111)  # the owner rebuilds monolithic
    assert fr.bucket_plan() is None
    fr.set_bucket_plan({"n_buckets": 5}, owner=111)
    fr.set_bucket_plan(None)  # unowned clear stays unconditional
    assert fr.bucket_plan() is None


# ---------------------------------------------------------------------
# kvstore integration: every push/pull leaves a flight entry
# ---------------------------------------------------------------------
def test_kvstore_flight_entries():
    before = diag.recorder.n_recorded()
    kv = mx.kv.create("local")
    kv.init("a", nd.zeros((4,)))
    kv.push("a", nd.ones((4,)))
    out = nd.zeros((4,))
    kv.pull("a", out=out)
    _, entries = diag.recorder.snapshot()
    new = [e for e in entries if e["seq"] >= before]
    ops = [e["op"] for e in new]
    assert ops == ["push", "pull"], ops
    assert all(e["state"] == "completed" for e in new)
    assert new[0]["keys"] == ["a"]
    assert new[0]["bytes"] == 4 * np.dtype(out.dtype).itemsize
    np.testing.assert_allclose(out.asnumpy(), 1.0)


def test_kvstore_tpu_bucket_entries():
    """The kvstore('tpu') fused multi-key push records one entry per
    bucket reduction on top of the push itself."""
    before = diag.recorder.n_recorded()
    kv = mx.kv.create("tpu")
    keys = ["x0", "x1", "x2"]
    for k in keys:
        kv.init(k, nd.zeros((8,)))
    vals = [[nd.ones((8,)), nd.ones((8,)) * 2] for _ in keys]
    kv.push(keys, vals)
    _, entries = diag.recorder.snapshot()
    new = [e for e in entries if e["seq"] >= before]
    ops = [e["op"] for e in new]
    assert "push" in ops
    assert any(o == "bucket_reduce" for o in ops), ops
    bucket_entries = [e for e in new if e["op"] == "bucket_reduce"]
    assert all(e["bucket"] is not None for e in bucket_entries)
    out = nd.zeros((8,))
    kv.pull("x1", out=out)
    np.testing.assert_allclose(out.asnumpy(), 3.0)


def test_bucket_bytes_counter_independent_of_flight():
    """stamp_profiler feeds mxnet_kvstore_bytes_total{op=bucket_reduce}
    even with the profiler stopped AND the flight recorder disabled —
    the same metrics-independence contract the kvstore verb fast paths
    honor."""
    from mxnet_tpu.parallel import buckets

    plan = [buckets.Bucket(("w0", "w1"), 256, "float32"),
            buckets.Bucket(("w2",), 128, "float32")]
    ctr = diag.metrics.counter("mxnet_kvstore_bytes_total",
                               labels={"op": "bucket_reduce"})
    before = ctr.value
    disabled, diag.recorder = diag.recorder, diag.FlightRecorder(capacity=0)
    try:
        assert not diag.flight_enabled()
        buckets.stamp_profiler(plan)
    finally:
        diag.recorder = disabled
    assert ctr.value == before + 384


# ---------------------------------------------------------------------
# recompile tracking (acceptance: shape churn -> >=2 compiles + warning)
# ---------------------------------------------------------------------
def test_recompile_tracking_shape_churn(caplog):
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.dp import FusedTrainStep

    diag.reset_recompile_stats()
    net = nn.Dense(4)
    net.initialize()
    step = FusedTrainStep(net, gloss.SoftmaxCrossEntropyLoss())
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.diagnostics"):
        step(nd.random.uniform(shape=(8, 6)), nd.zeros((8,)))
        # deliberate input-shape change between steps
        step(nd.random.uniform(shape=(12, 6)), nd.zeros((12,)))
    stats = diag.recompile_stats()
    assert stats["FusedTrainStep.step"]["count"] >= 2, stats
    assert stats["FusedTrainStep.step"]["total_ms"] > 0
    # the once-per-run recompilation-storm warning fired, naming the
    # offending avals
    storm = [r for r in caplog.records if "RECOMPILATION STORM" in
             r.getMessage()]
    assert storm, caplog.text
    assert "FusedTrainStep.step" in storm[0].getMessage()
    assert "12, 6" in storm[0].getMessage()  # the churned aval
    # warning is once-per-run: a third shape does not re-warn
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.diagnostics"):
        step(nd.random.uniform(shape=(16, 6)), nd.zeros((16,)))
    assert diag.recompile_stats()["FusedTrainStep.step"]["count"] >= 3
    assert not [r for r in caplog.records
                if "RECOMPILATION STORM" in r.getMessage()]
    # stable shapes do not count as compiles
    n = diag.recompile_stats()["FusedTrainStep.step"]["count"]
    step(nd.random.uniform(shape=(16, 6)), nd.zeros((16,)))
    assert diag.recompile_stats()["FusedTrainStep.step"]["count"] == n


def test_instrument_jit_delegates_attributes():
    import jax

    fn = diag.instrument_jit("selftest.delegate", jax.jit(lambda x: x * 2))
    out = fn(3.0)
    assert float(out) == 6.0
    # .lower passes through to the wrapped jit (dp.lower_only contract)
    lowered = fn.lower(jax.ShapeDtypeStruct((2,), "float32"))
    assert lowered is not None


def test_instrument_jit_fallback_signature_detection():
    """Without _cache_size introspection the first-seen aval-signature
    fallback detects compiles — a repeated shape is NOT re-counted, a
    new shape is."""
    fn = diag.instrument_jit("selftest.fallback", lambda x: x)
    a = np.zeros((4, 4), np.float32)
    fn(a)
    fn(a)  # same signature: no new "compile"
    fn(np.zeros((8, 4), np.float32))
    assert diag.recompile_stats()["selftest.fallback"]["count"] == 2


# ---------------------------------------------------------------------
# metrics registry + prom exposition
# ---------------------------------------------------------------------
def test_metrics_registry_prom_valid():
    reg = diag.MetricsRegistry()
    reg.gauge("t_loss", help="loss").set(0.25)
    reg.counter("t_samples_total", help="samples").inc(128)
    reg.counter("t_kv_bytes_total", labels={"op": "push"}).inc(4096)
    h = reg.histogram("t_step_seconds", help="step time")
    for v in (0.002, 0.004, 0.03, 0.3, 2.0, 100.0):
        h.observe(v)
    text = reg.to_prom()
    problems = diag.validate_prom_text(text)
    assert problems == [], (problems, text)
    # independent structural checks on the exposition format
    assert "# TYPE t_loss gauge" in text
    assert "# TYPE t_step_seconds histogram" in text
    assert 't_kv_bytes_total{op="push"} 4096' in text
    assert 't_step_seconds_bucket{le="+Inf"} 6' in text
    assert "t_step_seconds_count 6" in text
    # every non-comment line is name{labels} value
    line_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
        r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
        r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
        r" (NaN|[+-]?Inf|[+-]?[0-9.eE+-]+)$")
    for line in text.splitlines():
        if not line.startswith("#"):
            assert line_re.match(line), line


def test_metrics_histogram_percentile():
    h = diag.Histogram("t_pct")
    for _ in range(99):
        h.observe(0.004)
    h.observe(5.0)
    assert h.percentile(0.5) == 0.005  # bucket upper bound containing p50
    assert h.percentile(0.99) >= 0.004
    assert h.count == 100


def test_metrics_histogram_quantile_interpolates():
    """quantile() is the interpolated (prometheus histogram_quantile)
    variant percentile()'s coarse upper bound keeps its old contract
    next to: values land INSIDE the containing bucket."""
    h = diag.Histogram("t_q")
    for _ in range(99):
        h.observe(0.004)
    h.observe(5.0)
    q50 = h.quantile(0.5)
    assert 0.0025 < q50 < 0.005  # inside (0.0025, 0.005], not the bound
    assert h.quantile(0.99) <= 0.005
    # +Inf bucket clamps to the top finite bound instead of inventing
    h2 = diag.Histogram("t_q2", buckets=(1.0, 2.0))
    h2.observe(50.0)
    assert h2.quantile(0.99) == 2.0
    assert diag.Histogram("t_q3").quantile(0.5) is None


def test_to_prom_derives_p50_p99_gauges():
    """The serving-SLO satellite: every histogram exports derived
    ``_p50``/``_p99`` gauge families (typed, labeled, grouped) and the
    whole exposition still validates."""
    reg = diag.MetricsRegistry()
    h = reg.histogram("t_lat_seconds", help="latency",
                      labels={"model": "m1"})
    for v in (0.004, 0.009, 0.02, 0.02, 3.0):
        h.observe(v)
    reg.histogram("t_lat_seconds", labels={"model": "m2"}).observe(0.5)
    text = reg.to_prom()
    assert not diag.validate_prom_text(text), \
        diag.validate_prom_text(text)
    assert "# TYPE t_lat_seconds_p50 gauge" in text
    assert "# TYPE t_lat_seconds_p99 gauge" in text
    assert 't_lat_seconds_p50{model="m1"}' in text
    assert 't_lat_seconds_p50{model="m2"}' in text
    assert 't_lat_seconds_p99{model="m1"}' in text
    # families stay grouped: both p50 samples precede the p99 header
    assert text.index('t_lat_seconds_p50{model="m2"}') < \
        text.index("# TYPE t_lat_seconds_p99")
    # an empty histogram derives nothing (no NaN gauges)
    reg2 = diag.MetricsRegistry()
    reg2.histogram("t_empty_seconds")
    assert "_p50" not in reg2.to_prom()


def test_metrics_dump_json_and_flush(tmp_path):
    reg = diag.MetricsRegistry()
    reg.gauge("t_flush_gauge").set(7)
    js = reg.dump_json()
    assert js["metrics"]["t_flush_gauge"]["value"] == 7.0
    assert "rank" in js
    path = str(tmp_path / "metrics.prom")
    out = reg.flush(path=path)
    assert out == path
    with open(path) as f:
        text = f.read()
    assert diag.validate_prom_text(text) == []
    assert "t_flush_gauge 7" in text


def test_validate_prom_rejects_garbage():
    assert diag.validate_prom_text("not a metric line at all!\n")
    bad_hist = ("# TYPE h histogram\n"
                'h_bucket{le="+Inf"} 3\n'
                "h_sum 1.0\n"
                "h_count 5\n")
    assert any("+Inf" in p for p in diag.validate_prom_text(bad_hist))


def test_counter_monotonic():
    c = diag.Counter("t_mono")
    c.inc(5)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 5.0


# ---------------------------------------------------------------------
# fit() feeds the registry; Speedometer zero-interval fix
# ---------------------------------------------------------------------
def test_fit_feeds_step_metrics():
    from mxnet_tpu import sym

    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, name="fc", num_hidden=4)
    net = sym.SoftmaxOutput(data=net, name="softmax")
    X = np.random.uniform(size=(32, 8)).astype(np.float32)
    y = np.random.randint(0, 4, size=(32,)).astype(np.float32)
    train = mx.io.NDArrayIter(X, y, batch_size=8)
    hist = diag.metrics.histogram("mxnet_step_time_seconds")
    samples = diag.metrics.counter("mxnet_samples_total")
    n0, s0 = hist.count, samples.value
    mod = mx.mod.Module(symbol=net, context=mx.cpu())
    mod.fit(train, optimizer="sgd", num_epoch=1)
    assert hist.count >= n0 + 4  # one observation per batch
    assert samples.value >= s0 + 32
    assert diag.metrics.gauge("mxnet_samples_per_second").value is not None
    g = diag.metrics.gauge("mxnet_train_metric",
                           labels={"metric": "accuracy"})
    assert g.value is not None


def test_speedometer_zero_interval(monkeypatch, caplog):
    """callback.py regression: `frequent` batches inside one clock tick
    must not ZeroDivisionError — the registry's samples/s stands in."""
    from mxnet_tpu import callback as cb

    diag.metrics.gauge("mxnet_samples_per_second").set(123.0)
    frozen = 1000.0
    monkeypatch.setattr(cb.time, "time", lambda: frozen)
    sp = cb.Speedometer(batch_size=32, frequent=1, auto_reset=False)
    param = cb.BatchEndParam(epoch=0, nbatch=1, eval_metric=None,
                             locals=None)
    sp(param)  # arms tic at the frozen clock
    with caplog.at_level(logging.INFO):
        sp(cb.BatchEndParam(epoch=0, nbatch=2, eval_metric=None,
                            locals=None))  # elapsed == 0.0
    assert "123.00 samples/sec" in caplog.text
    assert diag.metrics.gauge(
        "mxnet_speedometer_samples_per_second").value == 123.0


# ---------------------------------------------------------------------
# --health over real recorder dumps: the simulated bucket-reduction hang
# ---------------------------------------------------------------------
def _dump_as_rank(fr, path, rank, monkeypatch):
    monkeypatch.setenv("DMLC_WORKER_ID", str(rank))
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    try:
        assert fr.dump(path=str(path))
    finally:
        monkeypatch.delenv("DMLC_WORKER_ID")
        monkeypatch.delenv("DMLC_NUM_WORKER")


def test_health_identifies_bucket_stall(tmp_path, monkeypatch):
    """Simulated hang: one worker of two stalls before its final bucket
    reduction — --health must name the stalled rank and the exact
    seq/bucket it never completed (acceptance criterion)."""
    plan = {"n_buckets": 4, "total_bytes": 4096, "cap_bytes": 1024}
    paths = []
    for rank in (0, 1):
        fr = diag.FlightRecorder(capacity=16)
        fr.set_bucket_plan(plan)
        for step in range(3):
            for b in range(4):
                if rank == 1 and step == 2 and b == 3:
                    # rank 1 enters its final bucket reduction and
                    # never comes back
                    fr.start("bucket_reduce", keys=["w%d" % b], bucket=b,
                             nbytes=1024, dtype="float32")
                    break
                s = fr.start("bucket_reduce", keys=["w%d" % b], bucket=b,
                             nbytes=1024, dtype="float32")
                fr.complete(s)
        p = tmp_path / ("flightrecorder_rank%d.json" % rank)
        _dump_as_rank(fr, p, rank, monkeypatch)
        paths.append(str(p))
    flight, traces = merge_traces.load_health_inputs(paths)
    assert set(flight) == {0, 1} and traces == {}
    report = merge_traces.health_report(flight, traces)
    desync = report["desync"]
    assert desync["detected"]
    assert desync["max_completed_seq"] == 11  # rank 0 completed 12
    (lag,) = desync["laggards"]
    assert lag["rank"] == 1
    assert lag["stalled_at_seq"] == 11
    assert lag["collective"]["bucket"] == 3
    assert lag["collective"]["keys"] == ["w3"]
    assert not report["bucket_plans"]["mismatch"]
    text = "\n".join(merge_traces.format_health(report))
    assert "rank 1 never completed seq 11" in text
    assert "bucket 3" in text


def test_health_bucket_plan_mismatch(tmp_path, monkeypatch):
    paths = []
    for rank, nb in ((0, 4), (1, 5)):
        fr = diag.FlightRecorder(capacity=8)
        fr.set_bucket_plan({"n_buckets": nb, "total_bytes": 4096,
                            "cap_bytes": 1024})
        s = fr.start("bucket_reduce", keys=["w"], bucket=0, nbytes=64,
                     dtype="float32")
        fr.complete(s)
        p = tmp_path / ("flightrecorder_rank%d.json" % rank)
        _dump_as_rank(fr, p, rank, monkeypatch)
        paths.append(str(p))
    flight, _ = merge_traces.load_health_inputs(paths)
    report = merge_traces.health_report(flight, {})
    assert report["bucket_plans"]["mismatch"]
    text = "\n".join(merge_traces.format_health(report))
    assert "BUCKET PLAN MISMATCH" in text


def test_health_straggler_flags(tmp_path):
    """A rank whose p50 step time is far above the fleet median gets the
    straggler flag; heavy per-rank tail gets the intermittent flag."""

    def trace(rank, durs):
        return {"traceEvents": [
            {"name": "step", "cat": "operator", "ph": "X", "ts": float(i),
             "dur": float(d), "pid": rank, "tid": 0}
            for i, d in enumerate(durs)]}

    traces = {0: trace(0, [100.0] * 20),
              1: trace(1, [101.0] * 20),
              2: trace(2, [400.0] * 19 + [5000.0])}
    report = merge_traces.health_report({}, traces)
    st = report["stragglers"]
    assert st["step_span"] == "step"
    assert st["slowest_rank"] == 2
    assert st["per_rank"][2]["straggler"]
    assert not st["per_rank"][0]["straggler"]
    assert 2 in st["flagged_ranks"]
    text = "\n".join(merge_traces.format_health(report))
    assert "STRAGGLER" in text and "slowest rank: 2" in text


# ---------------------------------------------------------------------
# CLI self-test (ring wraparound + signal dump + prom rendering) — the
# tier-1 wiring the issue asks for, mirroring overlap --self-test
# ---------------------------------------------------------------------
def test_cli_self_test():
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.diagnostics", "--self-test"],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.strip().splitlines()[-1])
    assert payload["self_test_ok"] is True
    assert payload["checks"]["ring_keeps_latest"]
    assert payload["checks"]["signal_dump"]
    assert payload["checks"]["prom_valid"]
    assert payload["checks"]["watchdog_dumped"]


def test_shutdown_path_shared(tmp_path):
    """A rank that dies mid-run emits BOTH artifacts through one
    shutdown path: the profiler trace and the flight recorder."""
    script = r"""
import os
import mxnet_tpu as mx
from mxnet_tpu import nd
mx.profiler.set_config(filename=os.environ["T_TRACE"])
mx.profiler.set_state("run")
kv = mx.kv.create("local")
kv.init("a", nd.zeros((2,)))
kv.push("a", nd.ones((2,)))
# a collective that never completes (simulated death mid-collective)
from mxnet_tpu import diagnostics
diagnostics.record_start("allreduce", keys=["stuck"], nbytes=8,
                         dtype="float32")
raise SystemExit(0)  # atexit runs; neither dump was explicit
"""
    trace = tmp_path / "trace.json"
    res = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 T_TRACE=str(trace),
                 MXNET_DUMP_DIR=str(tmp_path),  # relative dumps -> here
                 PYTHONPATH=os.path.abspath(
                     os.path.join(os.path.dirname(__file__), "..")) +
                 os.pathsep + os.environ.get("PYTHONPATH", "")))
    assert res.returncode == 0, res.stderr
    assert trace.exists(), "profiler trace not dumped at exit"
    fr = tmp_path / "flightrecorder_rank0.json"
    assert fr.exists(), "flight recorder not dumped at exit"
    with open(fr) as f:
        payload = json.load(f)
    states = [e["state"] for e in payload["entries"]]
    assert "in_flight" in states  # the stuck collective is the evidence
