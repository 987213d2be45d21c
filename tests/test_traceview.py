"""Measured device timeline (ISSUE 16): traceview capture ->
attribution -> autotune feedback.

* fixture-trace golden attribution + the committed self-test CLI
  (``python -m mxnet_tpu.traceview --self-test`` is tier-1 here);
* a LIVE dp=2 CPU-mesh ``FusedTrainStep`` capture cross-checked
  against the stamped bucket plan (scope-exact bucket map via the
  ``mxbkt<k>`` named scopes in the xplane sidecar);
* ``from_trace()`` -> ``tune()`` roundtrip pinning the acceptance
  criterion: a tuned plan built from a real captured trace carries
  ``assumptions.bandwidth_source == "trace"`` and measured per-bucket
  occupancy in its score block;
* cross-rank phase-skew health naming the slow rank, with
  chaos-injected stalls labeled instead of misattributed;
* mxlint MXL009 (direct ``jax.profiler`` use outside traceview/) and
  ``MXNET_TRACE_*`` env-registry drift.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXTURE = os.path.join(ROOT, "mxnet_tpu", "traceview",
                       "fixture_trace.json")


def _import_tool(name):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------
# fixture golden attribution + the committed self-test CLI
# ---------------------------------------------------------------------
def test_fixture_golden_attribution():
    from mxnet_tpu.traceview import parse

    with open(FIXTURE) as f:
        fx = json.load(f)
    s = parse.attribute(fx["trace"], plan_meta=fx["plan_meta"],
                        workload="fixture")
    g = fx["golden"]
    assert s["format"] == parse.SUMMARY_FORMAT
    assert s["steps"]["n"] == g["n_steps"]
    assert s["plan_match"] is True
    for phase, want in g["phases_mean_s"].items():
        got = s["phases"][phase]["mean_s"]
        assert got == pytest.approx(want, rel=1e-6), phase
    assert s["overlap"]["overlap_frac"] == \
        pytest.approx(g["overlap_frac"], rel=1e-6)
    assert s["overlap"]["source"] == "trace"
    assert [b["bucket"] for b in s["buckets"]] == \
        [b["bucket"] for b in g["buckets"]]
    for got, want in zip(s["buckets"], g["buckets"]):
        assert got["occupancy"] == pytest.approx(want["occupancy"],
                                                 rel=1e-6)
        assert got["measured_GBps"] == \
            pytest.approx(want["measured_GBps"], rel=1e-6)


def test_traceview_self_test_cli():
    """The committed offline check the CI wires in: parser +
    attribution over the fixture and the synthetic CPU lanes."""
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.traceview", "--self-test"],
        cwd=ROOT, env=dict(os.environ), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "traceview self-test OK" in proc.stdout, proc.stdout


# ---------------------------------------------------------------------
# live capture on the dp=2 CPU mesh (shared across the tests below)
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def live_capture(tmp_path_factory):
    """Arm the env-gated tracer, run a bucketed FusedTrainStep on a
    dp=2 CPU mesh, return (summary, summary_path).  A small bucket cap
    forces a multi-bucket plan so the scope-exact bucket map actually
    has something to prove."""
    import jax

    from mxnet_tpu import traceview
    from mxnet_tpu.parallel.dp import FusedTrainStep
    from mxnet_tpu.parallel.mesh import make_mesh

    trace_dir = str(tmp_path_factory.mktemp("traceview_live"))
    knobs = {"MXNET_TRACE_DIR": trace_dir, "MXNET_TRACE_STEPS": "2",
             "MXNET_KVSTORE_BUCKET_BYTES": "1024"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    traceview.reset()
    try:
        mesh = make_mesh((2,), ("dp",), jax.devices()[:2])
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(10))
        net.initialize(mx.init.Xavier())
        step = FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mesh=mesh)
        X = mx.nd.array(np.random.RandomState(0)
                        .uniform(size=(8, 16)).astype("float32"))
        y = mx.nd.array((np.arange(8) % 10).astype("float32"))
        for _ in range(4):        # 1 warmup + 2 windows + margin
            step(X, y)
        summary = traceview.last_summary()
        path = traceview.last_summary_path()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        traceview.reset()
    assert summary is not None, "armed tracer produced no summary"
    return summary, path


def test_live_capture_matches_bucket_plan(live_capture):
    """Bucket-plan cross-check: the capture's collective attribution
    must name exactly the stamped plan's buckets, via the mxbkt scope
    metadata (not the issue-order guess) — BN-stat psums or the loss
    pmean must never masquerade as gradient buckets."""
    summary, path = live_capture
    assert summary["format"] == "mxnet-tpu-traceview-summary"
    assert summary["bucket_map"] == "scope", summary["bucket_map"]
    assert summary["plan_match"] is True
    plan = summary["bucket_plan"]
    assert plan and plan["n_buckets"] >= 2, plan
    assert [b["bucket"] for b in summary["buckets"]] == \
        list(range(plan["n_buckets"]))
    assert summary["steps"]["n"] == 2
    for b in summary["buckets"]:
        assert b["device_s_per_step"] > 0.0, b
        assert 0.0 <= b["occupancy"] <= 1.0, b
        assert b["injected_stall"] is False, b
    # phase breakdown present and sane on the serial CPU executor
    for phase in ("h2d", "forward", "backward", "bucket_reduce",
                  "optimizer", "d2h"):
        assert phase in summary["phases"], summary["phases"].keys()
    assert summary["phases"]["bucket_reduce"]["mean_s"] > 0.0
    assert summary["overlap"]["source"] == "trace"
    assert 0.0 <= summary["overlap"]["overlap_frac"] <= 1.0
    # the summary landed on disk next to the trace
    assert path and os.path.exists(path)
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["bucket_map"] == "scope"
    assert on_disk["capture"]["warmup_skipped"] == 1
    assert on_disk["capture"]["trace_path"]


def test_live_capture_feeds_phase_metrics(live_capture):
    from mxnet_tpu import diagnostics as diag

    prom = diag.metrics.to_prom()
    assert "mxnet_step_phase_seconds" in prom
    assert 'phase="bucket_reduce"' in prom


def test_from_trace_tune_roundtrip(live_capture, tmp_path):
    """Acceptance pin: a tuned plan produced from a REAL captured
    trace records bandwidth_source="trace" and carries the measured
    per-bucket occupancy in its score block."""
    from mxnet_tpu.autotune import search, timing

    summary, path = live_capture
    model = timing.from_trace(summary, path=path)
    assert model.step_time_s and model.step_time_s > 0
    assert model.measured_GBps and model.measured_GBps > 0
    assert model.source["kind"] == "trace"
    plan = search.tune(model, chips=8)
    assert plan["assumptions"]["bandwidth_source"] == "trace"
    measured = plan["score"]["measured"]
    assert measured["source"] == "trace"
    assert 0.0 <= measured["overlap_frac"] <= 1.0
    occ = measured["bucket_occupancy"]
    assert len(occ) == summary["bucket_plan"]["n_buckets"]
    assert all(r["occupancy"] is not None for r in occ), occ
    # the tuned-plan JSON round-trips with the provenance intact
    out = tmp_path / "tuned_plan.json"
    out.write_text(json.dumps(plan, indent=1))
    back = json.loads(out.read_text())
    assert back["assumptions"]["bandwidth_source"] == "trace"
    assert back["score"]["measured"]["bucket_occupancy"] == occ
    # the content-sniffing loader accepts the on-disk summary too
    model2 = timing.load_any(path)
    assert model2.source["kind"] == "trace"


# ---------------------------------------------------------------------
# cross-rank phase-skew health (tools/merge_traces --health)
# ---------------------------------------------------------------------
def _tv_summary(rank, slow=1.0, injected=0):
    return {
        "format": "mxnet-tpu-traceview-summary", "version": 1,
        "rank": rank, "workload": "FusedTrainStep",
        "steps": {"n": 3, "mean_s": 0.01},
        "phases": {"backward": {"mean_s": 0.004},
                   "bucket_reduce": {"mean_s": 0.001 * slow}},
        "buckets": [{"bucket": b,
                     "device_s_per_step": 0.0002 * (slow if b == 5
                                                    else 1.0)}
                    for b in range(6)],
        "injected": {"events": injected,
                     "kinds": ["delay_collective"] if injected else []},
    }


def test_phase_skew_names_slow_rank():
    mt = _import_tool("merge_traces")
    tvs = {r: _tv_summary(r, slow=2.1 if r == 2 else 1.0)
           for r in range(3)}
    skew = mt.analyze_phase_skew(tvs)
    assert skew["detected"] is True
    assert {(f["kind"], f.get("bucket"), f["rank"])
            for f in skew["findings"]} >= {("bucket", 5, 2)}
    assert all(f["rank"] == 2 and not f["injected"]
               for f in skew["findings"])
    text = "\n".join(mt.format_health(
        mt.health_report({}, {}, traceviews=tvs)))
    assert "rank 2 spends 2.1x fleet-median in bucket 5 reduce" in text


def test_injected_stall_never_flips_health_verdict():
    """Satellite (a): the chaos tag is the deterministic signal — the
    same 2.1x skew reads INJECTED STALL, not straggler, and the
    verdict stays green with zero timing heuristics involved."""
    mt = _import_tool("merge_traces")
    tvs = {r: _tv_summary(r, slow=2.1 if r == 2 else 1.0,
                          injected=3 if r == 2 else 0)
           for r in range(3)}
    skew = mt.analyze_phase_skew(tvs)
    assert skew["findings"] and skew["detected"] is False
    assert skew["injected_ranks"] == [2]
    text = "\n".join(mt.format_health(
        mt.health_report({}, {}, traceviews=tvs)))
    assert "INJECTED STALL (chaos): rank 2" in text
    assert "not a hardware straggler" in text


def test_chaos_injection_tags_flight_entry_and_summary(monkeypatch):
    """delay_collective -> flight entry injected=true -> traceview
    summary injected block + per-bucket injected_stall."""
    from mxnet_tpu import chaos
    from mxnet_tpu import diagnostics as diag
    from mxnet_tpu.traceview import parse

    monkeypatch.setenv("MXNET_CHAOS", "delay_collective:op=push,ms=1")
    chaos.reset()
    try:
        seq = diag.record_start("push", keys=["w0"], bucket=1,
                                nbytes=64, dtype="float32")
        diag.record_complete(seq)
        _hdr, entries = diag.recorder.snapshot()
        tagged = [e for e in entries if e.get("injected")]
        assert tagged and tagged[-1]["injected_kind"] == \
            "delay_collective", entries[-3:]
        assert chaos.injected_total("delay_collective") == 1
    finally:
        chaos.reset()
    # the tag rides attribution into the summary + bucket rows
    with open(FIXTURE) as f:
        fx = json.load(f)
    s = parse.attribute(
        fx["trace"], plan_meta=fx["plan_meta"],
        flight_entries=[{"op": "bucket_reduce", "seq": 0, "bucket": 0},
                        {"op": "bucket_reduce", "seq": 1, "bucket": 1,
                         "injected": True,
                         "injected_kind": "delay_collective"}])
    assert s["injected"] == {"events": 1, "kinds": ["delay_collective"]}
    assert s["buckets"][1]["injected_stall"] is True
    assert s["buckets"][0]["injected_stall"] is False


# ---------------------------------------------------------------------
# mxlint MXL009: jax.profiler is traceview's monopoly
# ---------------------------------------------------------------------
def test_mxl009_flags_direct_profiler_use():
    mxlint = _import_tool("mxlint")
    src = ("import jax\n"
           "def capture():\n"
           "    jax.profiler.start_trace('/tmp/t')\n"
           "    with jax.profiler.TraceAnnotation('step'):\n"
           "        pass\n"
           "    with jax.profiler.StepTraceAnnotation('s', step_num=1):\n"
           "        pass\n"
           "    jax.profiler.stop_trace()\n")
    registered, import_ok = mxlint.registered_env_names()

    def codes(*path):
        return [f["code"] for f in mxlint.ModuleLinter(
            os.path.join(ROOT, "mxnet_tpu", *path), src,
            registered, import_ok, is_env_py=False).run()]

    # capture.py ALONE is exempt: not the rest of traceview/
    assert codes("rogue.py").count("MXL009") == 4
    assert codes("traceview", "scopes.py").count("MXL009") == 4
    assert "MXL009" not in codes("traceview", "capture.py")


def test_mxlint_repo_has_no_mxl009():
    mxlint = _import_tool("mxlint")
    registered, import_ok = mxlint.registered_env_names()
    findings = mxlint.lint_paths([os.path.join(ROOT, "mxnet_tpu")],
                                 registered, import_ok)
    assert not [f for f in findings if f["code"] == "MXL009"], \
        [f for f in findings if f["code"] == "MXL009"]


# ---------------------------------------------------------------------
# env-registry + docs drift for the capture knobs
# ---------------------------------------------------------------------
def test_trace_knobs_registered_and_documented():
    from mxnet_tpu import env

    reg = env.registered()
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    for name in ("MXNET_TRACE_DIR", "MXNET_TRACE_STEPS"):
        assert name in reg, name
        assert reg[name].doc and len(reg[name].doc) > 10, name
        assert name in readme, "%s missing from README" % name
        assert name in env.describe()
