"""Serving-tier tests: the batching model server's robustness layer.

The training side proved it survives preemption and desync (PR 7);
these tests prove the INFERENCE side degrades correctly when overload
and partial failure are the steady state: bounded queues shed excess
load with accounting, deadlines expire work before dispatch instead of
batching it, the circuit breaker fast-fails a broken model, drain
completes every admitted request, and the chaos-injected overload e2e
holds admitted p99 under the deadline while 2x-capacity traffic is
shed."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import chaos
from mxnet_tpu import checkpoint as ckpt
from mxnet_tpu import diagnostics as diag
from mxnet_tpu import serving

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SERVE_WORKER = os.path.join(os.path.dirname(__file__),
                             "serve_worker.py")


def _child_env(extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("MXNET_CHAOS", None)
    env.pop("MXNET_SERVE_QUEUE_MAX", None)
    env.update(extra or {})
    return env


# ---------------------------------------------------------------------
# CLI self-test (the satellite: tier-1 covers queue admission, deadline
# expiry, breaker trip/reset, drain ordering)
# ---------------------------------------------------------------------
def test_serving_self_test():
    res = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.serving", "--self-test"],
        capture_output=True, text=True, env=_child_env(), cwd=ROOT,
        timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(res.stdout.splitlines()[-1])
    assert payload["self_test_ok"], payload


# ---------------------------------------------------------------------
# runtime: buckets, AOT compile, padding, checkpoint loading
# ---------------------------------------------------------------------
def test_plan_batch_buckets():
    assert serving.plan_batch_buckets(32) == (1, 2, 4, 8, 16, 32)
    assert serving.plan_batch_buckets(6) == (1, 2, 4, 6)
    assert serving.plan_batch_buckets(1) == (1,)
    # explicit ladders are deduped/sorted and always include the cap
    assert serving.plan_batch_buckets(16, [4, 8, 4]) == (4, 8, 16)


def test_runtime_padding_matches_unpadded():
    rt = serving.demo_runtime(max_batch=8)
    rt.compile(warmup=True)
    assert rt.compiled
    x = np.random.RandomState(0).randn(3, 16).astype("float32")
    cls3, logits3 = rt.execute(x)
    assert cls3.shape == (3,) and logits3.shape == (3, 4)
    cls1, logits1 = rt.execute(x[:1])
    assert int(cls1[0]) == int(cls3[0])
    np.testing.assert_allclose(np.float64(logits1[0]),
                               np.float64(logits3[0]), rtol=1e-6)


def test_runtime_bf16_compute_dtype():
    rt = serving.demo_runtime(max_batch=2)
    # params were cast once at load
    assert str(rt._params["w1"].dtype) == "bfloat16"
    rt32 = serving.demo_runtime(max_batch=2, compute_dtype=None)
    assert str(rt32._params["w1"].dtype) == "float32"


def test_runtime_from_checkpoint(tmp_path):
    d = str(tmp_path / "ckpt")
    params = {"w1": np.random.RandomState(1).randn(16, 32)
              .astype("float32"),
              "b1": np.zeros(32, dtype="float32"),
              "w2": np.random.RandomState(2).randn(32, 4)
              .astype("float32"),
              "b2": np.zeros(4, dtype="float32")}
    ckpt.save_checkpoint(d, 7, params=params)

    def apply_fn(p, aux, x):
        import jax.numpy as jnp

        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    rt = serving.ModelRuntime.from_checkpoint(
        "ck", d, apply_fn, sample_shape=(16,), max_batch=4)
    rt.compile(warmup=True)
    out = rt.execute(np.ones((2, 16), dtype="float32"))
    assert out.shape == (2, 4)
    assert "step7" in rt.source or "step 7" in rt.source or \
        "00000007" in rt.source or "7" in rt.source


def test_runtime_from_checkpoint_names_missing_ranks(tmp_path):
    """Server startup must explain WHY a model won't load: the exact
    ranks whose shards are missing (the checkpoint satellite)."""
    d = str(tmp_path / "ckpt2")
    ckpt.CheckpointManager(d, rank=0, num_ranks=2).save(
        5, params={"w": np.ones(3, dtype="float32")}, blocking=True)
    with pytest.raises(FileNotFoundError) as ei:
        serving.ModelRuntime.from_checkpoint(
            "ck", d, lambda p, a, x: x, sample_shape=(3,),
            num_ranks=2, rank=1)
    msg = str(ei.value)
    assert "rank(s) [1]" in msg and "of 2" in msg, msg


# ---------------------------------------------------------------------
# server robustness: shed accounting, expiry, breaker metrics
# ---------------------------------------------------------------------
class _GatedRuntime:
    """Executor gated on an event — deterministic queue pressure."""

    def __init__(self, name="gated", max_batch=2):
        self.name = name
        self.sample_shape = (2,)
        self.max_batch = max_batch
        self.plan = serving.plan_batch_buckets(max_batch)
        self.compiled = True
        self.gate = threading.Event()
        self.executed = 0

    def bucket_for(self, n):
        for b in self.plan:
            if n <= b:
                return b
        raise ValueError(n)

    def execute(self, batch):
        self.gate.wait(10.0)
        self.executed += int(np.asarray(batch).shape[0])
        return np.asarray(batch).sum(axis=-1)


def _counter_value(name, **labels):
    c = diag.metrics.counter(name, labels=labels or None)
    return c.value


def test_queue_full_shed_is_counted():
    rt = _GatedRuntime()
    srv = serving.ModelServer(queue_max=2, max_batch=2,
                              batch_deadline_ms=1,
                              default_deadline_ms=10_000)
    srv.add_model(rt)
    before = _counter_value("mxnet_serve_rejected_total",
                           reason="queue_full")
    x = np.ones((1, 2), dtype="float32")
    admitted, shed = [], 0
    for _ in range(7):
        try:
            admitted.append(srv.submit("gated", x))
        except serving.Rejected as e:
            assert e.reason == "queue_full"
            assert e.retry_after_s is not None and e.retry_after_s > 0
            shed += 1
    assert shed >= 3  # 7 offers vs <=2 riding + 2 queued
    rt.gate.set()
    for r in admitted:
        r.wait(10.0)
    after = _counter_value("mxnet_serve_rejected_total",
                          reason="queue_full")
    assert after - before == shed


def test_unknown_model_and_bad_input_shed():
    srv = serving.ModelServer(queue_max=2, max_batch=2)
    with pytest.raises(serving.Rejected) as ei:
        srv.submit("nope", np.ones((1, 2), dtype="float32"))
    assert ei.value.reason == "unknown_model"
    rt = _GatedRuntime("shapes")
    rt.gate.set()
    srv.add_model(rt)
    with pytest.raises(serving.Rejected) as ei:
        srv.submit("shapes", np.ones((1, 5), dtype="float32"))
    assert ei.value.reason == "bad_input"
    with pytest.raises(serving.Rejected) as ei:
        srv.submit("shapes", np.ones((9, 2), dtype="float32"))
    assert ei.value.reason == "too_large"


def test_expired_request_never_dispatched():
    rt = _GatedRuntime()
    srv = serving.ModelServer(queue_max=8, max_batch=2,
                              batch_deadline_ms=1,
                              default_deadline_ms=10_000)
    srv.add_model(rt)
    x = np.ones((1, 2), dtype="float32")
    blocker = srv.submit("gated", x)
    time.sleep(0.05)  # the batcher takes the blocker, wedges on gate
    victim = srv.submit("gated", x, deadline_ms=30)
    time.sleep(0.08)  # victim expires while QUEUED
    rt.gate.set()
    blocker.wait(10.0)
    with pytest.raises(serving.DeadlineExceeded):
        victim.wait(5.0)
    # the expired sample was never executed
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline and rt.executed < 1:
        time.sleep(0.01)
    assert rt.executed == 1


def test_breaker_trip_flushes_queue_and_resets():
    class _Flaky(_GatedRuntime):
        def __init__(self):
            super().__init__("flaky2", max_batch=2)
            self.gate.set()
            self.fail = True

        def execute(self, batch):
            if self.fail:
                raise serving.ExecutorFailure("boom")
            return super().execute(batch)

    rt = _Flaky()
    srv = serving.ModelServer(queue_max=8, max_batch=2,
                              batch_deadline_ms=1,
                              default_deadline_ms=10_000,
                              breaker_n=2, breaker_reset_s=0.15)
    srv.add_model(rt)
    x = np.ones((1, 2), dtype="float32")
    for _ in range(2):
        r = srv.submit("flaky2", x)
        with pytest.raises(serving.ExecutorFailure):
            r.wait(10.0)
    deadline = time.monotonic() + 5.0
    while srv._get("flaky2").breaker.state() == "closed" and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    assert srv._get("flaky2").breaker.state() in ("open", "half_open")
    with pytest.raises(serving.Rejected) as ei:
        srv.submit("flaky2", x)
    assert ei.value.reason == "breaker_open"
    assert ei.value.retry_after_s is not None
    # half-open probe after the reset window closes it again
    time.sleep(0.2)
    rt.fail = False
    probe = srv.submit("flaky2", x)
    probe.wait(10.0)
    assert srv._get("flaky2").breaker.state() == "closed"


def test_breaker_lost_probe_does_not_wedge():
    """A half-open probe that is shed at offer (or expires in the
    queue) must not leave the breaker fast-failing forever: an
    explicit abort releases the reservation, and the reservation
    itself times out after reset_s."""
    br = serving.CircuitBreaker(1, 0.05)
    assert br.on_failure() and br.state() == "open"
    time.sleep(0.06)
    assert br.admit() is True          # the probe reservation
    assert br.admit() is False         # concurrent submits fast-fail
    br.abort_probe()                   # probe was shed at offer
    assert br.admit() is True          # next submit may probe NOW
    time.sleep(0.06)                   # probe expired in queue instead
    assert br.admit() is True          # reservation timed out too
    br.on_success()
    assert br.state() == "closed"


def test_probes_ready_vs_live():
    rt = _GatedRuntime("probe2")
    rt.gate.set()
    srv = serving.ModelServer(queue_max=4, max_batch=2,
                              batch_deadline_ms=1)
    srv.add_model(rt)
    rep = srv.ready()
    assert rep["ready"] and srv.live()
    srv.drain(timeout_s=5.0)
    assert not srv.ready()["ready"]
    assert not srv.live()


# ---------------------------------------------------------------------
# e2e: chaos-slowed executors at 2x capacity — bounded p99 for admitted
# traffic, excess shed WITH accounting; drain-under-load loses nothing
# ---------------------------------------------------------------------
def _overloaded_server(monkeypatch, slow_ms=5, queue_max=32,
                       deadline_ms=2000):
    monkeypatch.setenv(
        "MXNET_CHAOS",
        "slow_request:model=demo,ms=%d,count=1000000" % slow_ms)
    chaos.reset()
    rt = serving.demo_runtime(max_batch=8)
    srv = serving.ModelServer(max_batch=8, queue_max=queue_max,
                              batch_deadline_ms=2,
                              default_deadline_ms=deadline_ms)
    srv.add_model(rt)
    return srv


def test_e2e_overload_bounded_p99_and_shed(monkeypatch):
    deadline_ms = 2000
    srv = _overloaded_server(monkeypatch, deadline_ms=deadline_ms)
    try:
        # calibrate capacity at a gentle rate, then offer ~2x
        calib = serving.run_load(srv, "demo", qps=100, duration_s=0.5)
        assert calib["ok"] > 0 and calib["hung"] == 0
        cap_qps = 8 / 0.007  # 8-sample buckets, ~(5+2)ms per batch
        before = _counter_value("mxnet_serve_rejected_total",
                               reason="queue_full")
        st = serving.run_load(srv, "demo", qps=2 * cap_qps,
                              duration_s=2.0)
        # accounting closes: every offered request is admitted or shed
        assert st["admitted"] + st["shed_total"] == st["offered"]
        assert st["hung"] == 0 and st["errors"] == 0
        # excess traffic WAS shed, and the shed counter accounts for it
        assert st["shed"].get("queue_full", 0) > 0
        after = _counter_value("mxnet_serve_rejected_total",
                              reason="queue_full")
        assert after - before >= st["shed"]["queue_full"]
        # admitted requests kept a bounded p99 under the deadline
        assert st["ok"] > 0
        assert st["p99_ms"] < deadline_ms, st
        assert chaos.injected_total("slow_request") > 0
    finally:
        chaos.reset()


def test_e2e_drain_under_load_loses_nothing(monkeypatch):
    srv = _overloaded_server(monkeypatch, slow_ms=5, queue_max=64,
                             deadline_ms=30_000)
    try:
        load = serving.BackgroundLoad(srv, "demo", qps=400,
                                      duration_s=3.0).start()
        time.sleep(0.6)  # mid-load: queue is non-empty
        rep = srv.drain(timeout_s=15.0)
        st = load.join(30.0)
        assert st is not None
        # drain completed every admitted in-flight request
        assert rep["drained"] and rep["left"] == 0, rep
        assert st["hung"] == 0, st
        assert st["ok"] == st["admitted"], st
        # offers arriving after the drain began were shed as draining
        assert st["shed"].get("draining", 0) > 0, st
    finally:
        chaos.reset()


def test_e2e_fail_execute_chaos_trips_breaker(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS",
                       "fail_execute:model=demo,count=1000000")
    chaos.reset()
    try:
        rt = serving.demo_runtime(max_batch=4)
        srv = serving.ModelServer(max_batch=4, queue_max=16,
                                  batch_deadline_ms=1,
                                  default_deadline_ms=5_000,
                                  breaker_n=3, breaker_reset_s=30.0)
        srv.add_model(rt)
        x = np.zeros((1, 16), dtype="float32")
        for _ in range(3):
            r = srv.submit("demo", x)
            with pytest.raises(serving.ExecutorFailure):
                r.wait(10.0)
        deadline = time.monotonic() + 5.0
        while srv._get("demo").breaker.state() == "closed" and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv._get("demo").breaker.state() == "open"
        with pytest.raises(serving.Rejected) as ei:
            srv.submit("demo", x)
        assert ei.value.reason == "breaker_open"
        assert chaos.injected_total("fail_execute") >= 3
    finally:
        chaos.reset()


# ---------------------------------------------------------------------
# the SLO ramp (serving.qps_at_slo) and the tool that drives it
# ---------------------------------------------------------------------
def test_qps_at_slo_reports_the_last_rate_that_held():
    rt = serving.demo_runtime(max_batch=8)
    srv = serving.ModelServer(max_batch=8, queue_max=128,
                              batch_deadline_ms=2,
                              default_deadline_ms=5_000)
    srv.add_model(rt)
    try:
        # a generous SLO: both rates of the ramp (20, 40) hold, and the
        # answer is the last one's achieved rate
        out = serving.qps_at_slo(srv, rt.name, slo_p99_ms=5_000,
                                 start_qps=20, max_qps=50, window_s=0.4)
        assert [st["offered_qps"] for st in out["ramp"]] == [20.0, 40.0]
        assert all(st["met_slo"] for st in out["ramp"]), out
        assert out["qps_at_slo"] == out["ramp"][-1]["achieved_qps"] > 0
        assert out["p99_ms_at_slo"] == out["ramp"][-1]["p99_ms"]
        # an SLO nothing can meet: the ramp stops at its first rate and
        # no rate is reported as held
        none = serving.qps_at_slo(srv, rt.name, slo_p99_ms=0.0,
                                  start_qps=20, max_qps=50, window_s=0.4)
        assert [st["met_slo"] for st in none["ramp"]] == [False], none
        assert none["qps_at_slo"] == 0.0
        assert none["p99_ms_at_slo"] is None
    finally:
        srv.drain()


@pytest.mark.parametrize("mode, keys", [
    (["--qps", "50", "--duration", "0.5"],
     ("offered", "admitted", "ok", "shed", "shed_total", "p50_ms",
      "p99_ms", "achieved_qps")),
    (["--slo-p99-ms", "0.0"],
     ("slo_p99_ms", "qps_at_slo", "p99_ms_at_slo", "ramp")),
], ids=["fixed_rate", "slo_ramp"])
def test_serve_loadgen_tool_prints_its_accounting(mode, keys):
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_loadgen.py")]
        + mode, capture_output=True, text=True, env=_child_env(),
        cwd=ROOT, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout[res.stdout.index("{"):])
    for key in keys:
        assert key in out, (key, out)
    if "ramp" in out:
        assert all("met_slo" in st for st in out["ramp"]), out
    else:
        assert out["admitted"] + out["shed_total"] == out["offered"]
        assert out["hung"] == 0 and out["ok"] > 0, out


# ---------------------------------------------------------------------
# SIGTERM drain: subprocess exits 83 with zero admitted requests lost
# ---------------------------------------------------------------------
def test_sigterm_drain_exits_83_and_completes_admitted(tmp_path):
    report = str(tmp_path / "drain_report.json")
    env = _child_env({
        "MXNET_CHAOS": "slow_request:model=demo,ms=5,count=1000000",
    })
    proc = subprocess.Popen(
        [sys.executable, _SERVE_WORKER, report],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=ROOT)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, line
        time.sleep(0.8)  # let it admit a stream of requests
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == diag.EXIT_PREEMPTED, (rc, proc.stderr.read())
    with open(report) as f:
        rep = json.load(f)
    assert rep["drain"]["drained"] and rep["drain"]["left"] == 0, rep
    assert rep["admitted"] > 0
    # every admitted request completed before exit; none hung or lost
    assert rep["done"] == rep["admitted"], rep
    assert rep["ok"] == rep["admitted"], rep


# ---------------------------------------------------------------------
# HTTP front-end: status mapping is the shed contract made visible
# ---------------------------------------------------------------------
def test_http_roundtrip_and_probe_status():
    rt = serving.demo_runtime(max_batch=4)
    srv = serving.ModelServer(max_batch=4, queue_max=8,
                              batch_deadline_ms=1)
    srv.add_model(rt)
    fe = serving.HttpFrontend(srv, port=0)
    host, port = fe.start()
    base = "http://%s:%d" % (host, port)
    try:
        assert urllib.request.urlopen(base + "/healthz").status == 200
        assert urllib.request.urlopen(base + "/readyz").status == 200
        prom = urllib.request.urlopen(base + "/metrics").read().decode()
        assert not diag.validate_prom_text(prom)
        req = urllib.request.Request(
            base + "/v1/models/demo:predict",
            data=json.dumps({"instances": [[0.5] * 16]}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req)
        body = json.loads(resp.read())
        assert resp.status == 200 and len(body["predictions"][0]) == 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/ghost:predict",
                data=b'{"instances": [[1.0]]}'))
        assert ei.value.code == 404
        # valid JSON that is not an object must be a clean 400, not a
        # dropped connection
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/demo:predict", data=b'[1, 2, 3]'))
        assert ei.value.code == 400
        srv.drain(timeout_s=5.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/demo:predict",
                data=json.dumps({"instances": [[0.5] * 16]}).encode()))
        assert ei.value.code == 503  # draining
    finally:
        fe.stop()


# ---------------------------------------------------------------------
# serving metrics surface quantile gauges (the diagnostics satellite,
# observed end-to-end through real traffic)
# ---------------------------------------------------------------------
def test_serving_latency_quantiles_in_prom():
    rt = serving.demo_runtime(max_batch=4)
    srv = serving.ModelServer(max_batch=4, queue_max=8,
                              batch_deadline_ms=1)
    srv.add_model(rt)
    x = np.zeros((2, 16), dtype="float32")
    for _ in range(5):
        srv.predict("demo", x)
    text = diag.metrics.to_prom()
    assert not diag.validate_prom_text(text)
    assert "mxnet_serve_latency_seconds_p50" in text
    assert "mxnet_serve_latency_seconds_p99" in text
    # outcome counters carry the serving VERSION label (the reload
    # tentpole: a scraper can split error rates per model version)
    assert ('mxnet_serve_requests_total{model="demo",outcome="ok",'
            'version="v1"}') in text


# ---------------------------------------------------------------------
# live reload: hot swap, canary rollback, fail-closed (the tentpole)
# ---------------------------------------------------------------------
def _drive_until_terminal(srv, model, x, timeout_s=30.0):
    """Keep traffic flowing until the reload decision lands; returns
    (terminal_state, n_ok, n_failed) — the zero-drop accounting."""
    n_ok = n_failed = 0
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            srv.predict(model, x)
            n_ok += 1
        except Exception:
            n_failed += 1
        st = srv.reload_status(model)
        if st["state"] in ("promoted", "rolled_back", "failed"):
            return st, n_ok, n_failed
    return srv.reload_status(model), n_ok, n_failed


def test_reload_hot_swap_promotes_with_zero_drop(tmp_path):
    """A new version loads from a digest-verified checkpoint, warms in
    the background, canaries, promotes — and every request submitted
    during the swap is answered (zero admitted dropped)."""
    d = str(tmp_path / "v2ckpt")
    ckpt.save_checkpoint(d, 3, params=serving.demo_params(seed=9))
    rt = serving.demo_runtime(max_batch=4, seed=0)
    srv = serving.ModelServer(max_batch=4, queue_max=64,
                              batch_deadline_ms=1, canary_pct=50,
                              canary_min_n=4)
    srv.add_model(rt)
    x = np.random.RandomState(0).randn(1, 16).astype("float32")
    before = srv.predict("demo", x)[1]
    srv.reload("demo", d)
    st, n_ok, n_failed = _drive_until_terminal(srv, "demo", x)
    assert st["state"] == "promoted", st
    assert n_failed == 0 and n_ok > 0, (n_ok, n_failed)
    assert st["canary_stats"]["errors"] == 0
    # the server now answers from the NEW weights
    v2 = serving.demo_runtime(max_batch=4, seed=9)
    v2.compile(warmup=False)
    want = np.float64(np.asarray(v2.execute(x)[1]))
    got = np.float64(np.asarray(srv.predict("demo", x)[1]))
    assert np.allclose(got, want), "post-swap output is not v2's"
    assert not np.allclose(got, np.float64(np.asarray(before)))
    assert srv.stats()["demo"]["version"] == 2
    # reloads are counted by terminal outcome
    assert diag.metrics.counter(
        "mxnet_serve_reloads_total",
        labels={"model": "demo", "outcome": "promoted"}).value >= 1
    srv.drain(timeout_s=5.0)


def test_reload_bad_version_rolls_back_e2e(tmp_path, monkeypatch):
    """Acceptance e2e: chaos 'bad_version' makes every canary batch of
    the new version fail — the server auto-rolls-back with ZERO
    admitted requests dropped (failed canary batches re-execute on the
    stable version) and mxnet_serve_rollbacks_total increments."""
    d = str(tmp_path / "v2ckpt")
    ckpt.save_checkpoint(d, 3, params=serving.demo_params(seed=9))
    rt = serving.demo_runtime(max_batch=4, seed=0)
    srv = serving.ModelServer(max_batch=4, queue_max=64,
                              batch_deadline_ms=1, canary_pct=50,
                              canary_min_n=4)
    srv.add_model(rt)
    x = np.random.RandomState(1).randn(1, 16).astype("float32")
    stable_out = np.float64(np.asarray(srv.predict("demo", x)[1]))
    rb_before = diag.metrics.counter(
        "mxnet_serve_rollbacks_total", labels={"model": "demo"}).value
    monkeypatch.setenv("MXNET_CHAOS",
                       "bad_version:model=demo,count=100000")
    chaos.reset()
    try:
        srv.reload("demo", d)
        st, n_ok, n_failed = _drive_until_terminal(srv, "demo", x)
        injected = chaos.injected_total("bad_version")
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
    assert st["state"] == "rolled_back", st
    assert injected > 0, "the bad_version fault never fired"
    # zero admitted dropped: every request during the canary answered OK
    assert n_failed == 0 and n_ok > 0, (n_ok, n_failed)
    assert st["canary_stats"]["errors"] >= 4
    assert diag.metrics.counter(
        "mxnet_serve_rollbacks_total",
        labels={"model": "demo"}).value == rb_before + 1
    # stable version keeps serving, bit-identical to before the canary
    after = np.float64(np.asarray(srv.predict("demo", x)[1]))
    assert np.allclose(after, stable_out)
    assert srv.stats()["demo"]["version"] == 1
    assert srv.stats()["demo"]["canary_version"] is None
    srv.drain(timeout_s=5.0)


def test_reload_corrupt_checkpoint_fails_closed(tmp_path):
    """Integrity meets serving: a reload pointed at a corrupt
    checkpoint FAILS (naming the shard) and the stable version keeps
    serving untouched — the bad bytes never reach traffic."""
    d = str(tmp_path / "badckpt")
    ckpt.save_checkpoint(d, 3, params=serving.demo_params(seed=9))
    with open(ckpt.shard_path(d, 3, 0), "r+b") as f:
        f.seek(50)
        f.write(b"\x00\x01\x02\x03")
    rt = serving.demo_runtime(max_batch=4, seed=0)
    srv = serving.ModelServer(max_batch=4, queue_max=16,
                              batch_deadline_ms=1)
    srv.add_model(rt)
    x = np.zeros((1, 16), dtype="float32")
    st = srv.reload("demo", d, wait_s=30.0)
    assert st["state"] == "failed", st
    assert "rank0.ckpt" in str(st.get("error", "")), st
    assert srv.predict("demo", x)[0].shape == (1,)
    assert srv.stats()["demo"]["version"] == 1
    # a second reload attempt is allowed after a failed one
    assert srv.reload_status("demo")["state"] == "failed"
    srv.drain(timeout_s=5.0)


def test_reload_in_progress_rejected(tmp_path):
    d = str(tmp_path / "v2ckpt")
    ckpt.save_checkpoint(d, 3, params=serving.demo_params(seed=9))
    rt = serving.demo_runtime(max_batch=4, seed=0)
    srv = serving.ModelServer(max_batch=4, queue_max=16,
                              batch_deadline_ms=1, canary_pct=50,
                              canary_min_n=4)
    srv.add_model(rt)
    srv.reload("demo", d)  # no traffic -> sits in loading/canary
    with pytest.raises(serving.Rejected) as ei:
        srv.reload("demo", d)
    assert ei.value.reason == "reload_in_progress"
    # finish it so drain is clean
    x = np.zeros((1, 16), dtype="float32")
    st, _, _ = _drive_until_terminal(srv, "demo", x)
    assert st["state"] == "promoted"
    srv.drain(timeout_s=5.0)


def test_http_reload_route(tmp_path):
    """POST /v1/models/<name>:reload kicks the zero-downtime reload;
    the stats route exposes the reload state machine."""
    d = str(tmp_path / "v2ckpt")
    ckpt.save_checkpoint(d, 3, params=serving.demo_params(seed=9))
    rt = serving.demo_runtime(max_batch=4, seed=0)
    srv = serving.ModelServer(max_batch=4, queue_max=16,
                              batch_deadline_ms=1, canary_pct=0)
    srv.add_model(rt)
    fe = serving.HttpFrontend(srv, port=0)
    host, port = fe.start()
    base = "http://%s:%d" % (host, port)
    try:
        req = urllib.request.Request(
            base + "/v1/models/demo:reload",
            data=json.dumps({"directory": d, "wait_s": 30}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req)
        body = json.loads(resp.read())
        # canary_pct=0: promoted as soon as compiled+warm (no traffic
        # needed), waited to terminal -> 200
        assert resp.status == 200, body
        assert body["reload"]["state"] == "promoted", body
        stats = json.loads(urllib.request.urlopen(
            base + "/stats").read())
        assert stats["demo"]["version"] == 2
        assert stats["demo"]["reload"]["state"] == "promoted"
        # bad body -> 400; unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/demo:reload", data=b'{}'))
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/models/ghost:reload",
                data=json.dumps({"directory": d}).encode()))
        assert ei.value.code == 404
    finally:
        srv.drain(timeout_s=5.0)
        fe.stop()


# ---------------------------------------------------------------------
# elastic heartbeat coverage (ISSUE 15 satellite): the batcher loop
# beacons liveness, so a supervised server idling between requests is
# never falsely SIGKILLed by MXNET_ELASTIC_HEARTBEAT_TIMEOUT_S
# ---------------------------------------------------------------------
def test_batcher_loop_touches_heartbeat(tmp_path, monkeypatch):
    hb_dir = str(tmp_path / "hb")
    monkeypatch.setenv("MXNET_ELASTIC_HEARTBEAT_DIR", hb_dir)
    # reset the rate limiter so the beacon fires for THIS dir
    monkeypatch.setattr(diag, "_hb_last", 0.0)
    monkeypatch.setattr(diag, "_hb_path", None)
    rt = serving.demo_runtime(max_batch=2)
    srv = serving.ModelServer(max_batch=2, queue_max=4)
    try:
        srv.add_model(rt)
        deadline = time.monotonic() + 5.0
        path = os.path.join(hb_dir, "hb_rank0")
        while time.monotonic() < deadline and not os.path.exists(path):
            time.sleep(0.05)  # no traffic at all — idling must beacon
        assert os.path.exists(path), os.listdir(hb_dir) \
            if os.path.isdir(hb_dir) else "no hb dir"
    finally:
        srv.drain(timeout_s=5.0)
