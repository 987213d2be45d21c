"""Self-tuning collectives (mxnet_tpu/autotune/ — ISSUE 12 tentpole).

Covers: the CLI --self-test (tier-1 wiring), timing-model extraction
from flight dumps and merge_traces --bucket-timings exports, the cap
sweep's tuned-vs-default guarantee on the recorded resnet50-shaped
payload, plan persistence + env resolution precedence, and the
plan_with_tuning hook the FusedTrainStep build consumes.
"""
import json
import os
import subprocess
import sys

import pytest

from mxnet_tpu import autotune
from mxnet_tpu.parallel import buckets

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
MIB = 1024 * 1024


# ---------------------------------------------------------------------
# tier-1 CI: the subsystem's own self-test
# ---------------------------------------------------------------------
def test_autotune_self_test_cli():
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.autotune", "--self-test"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env=dict(os.environ))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "autotune self-test OK" in proc.stdout


# ---------------------------------------------------------------------
# timing-model extraction
# ---------------------------------------------------------------------
def _flight_payload(with_plan=True, with_wire=True):
    entries = []
    for s, nbytes in enumerate((4 * MIB, 2 * MIB, 1 * MIB)):
        entries.append({"seq": s, "op": "bucket_reduce", "bucket": s,
                        "bytes": nbytes, "dtype": "float32",
                        "enqueue_ts": 10.0 + s,
                        "complete_ts": 10.0 + s + 1e-6,
                        "state": "completed",
                        "args": {"in_graph": True}})
    if with_wire:
        entries.append({"seq": 3, "op": "push", "bucket": None,
                        "bytes": 2 * MIB, "dtype": "float32",
                        "enqueue_ts": 20.0, "complete_ts": 20.002,
                        "state": "completed"})
    header = {"flight_recorder": True, "rank": 0, "num_workers": 2}
    if with_plan:
        header["bucket_plan"] = {
            "n_buckets": 3, "total_bytes": 7 * MIB,
            "cap_bytes": 4 * MIB,
            "buckets": [
                {"bucket": 0, "n_grads": 2, "bytes": 4 * MIB,
                 "dtype": "float32"},
                {"bucket": 1, "n_grads": 1, "bytes": 2 * MIB,
                 "dtype": "float32"},
                {"bucket": 2, "n_grads": 3, "bytes": 1 * MIB,
                 "dtype": "float32"}]}
    return {"header": header, "entries": entries}


def test_from_flight_dump_plan_and_bandwidth():
    tm = autotune.from_flight_dump(_flight_payload())
    assert tm.granularity == "bucket"
    assert [b for b, _ in tm.units] == [4 * MIB, 2 * MIB, 1 * MIB]
    assert tm.recorded_cap_bytes == 4 * MIB
    # 2 MiB in 2 ms ~ 1.05 GB/s from the REAL push duration; the
    # in-graph issue stamps (1 us) must not poison the estimate
    assert tm.measured_GBps == pytest.approx(1.048576, rel=1e-3)


def test_from_flight_dump_entries_fallback_and_no_wire():
    tm = autotune.from_flight_dump(_flight_payload(with_plan=False,
                                                   with_wire=False))
    assert [b for b, _ in tm.units] == [4 * MIB, 2 * MIB, 1 * MIB]
    assert tm.measured_GBps is None


def test_from_flight_dump_empty_raises():
    with pytest.raises(ValueError, match="no bucket plan"):
        autotune.from_flight_dump({"header": {}, "entries": []})


def test_load_any_sniffs_all_three_formats(tmp_path):
    flight = tmp_path / "flightrecorder_rank0.json"
    flight.write_text(json.dumps(_flight_payload()))
    tm = autotune.load_any(str(flight), step_time_s=0.01)
    assert tm.source["kind"] == "flight" and tm.step_time_s == 0.01

    summary = tmp_path / "traceview_summary_rank0.json"
    summary.write_text(json.dumps({
        "format": "mxnet-tpu-traceview-summary",
        "buckets": [{"bucket": i, "bytes": MIB} for i in range(4)],
        "steps": {"mean_s": 0.02, "n": 3}}))
    tm = autotune.load_any(str(summary))
    assert tm.source["kind"] == "trace" and tm.step_time_s == 0.02

    bt = tmp_path / "bucket_timings.json"
    bt.write_text(json.dumps({"format": "bucket-timings", "version": 1,
                              "ranks": {"0": {
                                  "bucket_plan": None,
                                  "timings": [{
                                      "seq": 0, "op": "bucket_reduce",
                                      "bucket": 0, "bytes": MIB,
                                      "dtype": "float32",
                                      "duration_s": None,
                                      "in_graph": True}]}}}))
    tm = autotune.load_any(str(bt), step_time_s=0.01)
    assert tm.source["kind"] == "bucket-timings" and tm.n_units == 1

    other = tmp_path / "other.json"
    other.write_text("{}")
    with pytest.raises(ValueError):
        autotune.load_any(str(other))


def test_bucket_timings_tool_roundtrip(tmp_path):
    """merge_traces --bucket-timings output feeds the autotuner (the
    satellite's offline pipeline, end to end as subprocesses)."""
    dump = tmp_path / "flightrecorder_rank0.json"
    dump.write_text(json.dumps(_flight_payload()))
    tool = os.path.join(ROOT, "tools", "merge_traces.py")
    out = tmp_path / "bt.json"
    proc = subprocess.run(
        [sys.executable, tool, "--bucket-timings", "-o", str(out),
         str(dump)], capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.autotune", "--tune", str(out),
         "--step-time", "0.0138", "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env=dict(os.environ))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    plan = json.loads(proc.stdout.splitlines()[0])
    assert plan["format"] == "mxnet-tpu-autotune-plan"
    assert plan["score"]["beats_default"] in (True, False)
    assert plan["assumptions"]["step_time_s"] == 0.0138


def test_tune_requires_step_time():
    tm = autotune.from_flight_dump(_flight_payload())
    with pytest.raises(ValueError, match="step time"):
        autotune.tune(tm)


# ---------------------------------------------------------------------
# the search: tuned >= default, resnet50-shaped acceptance
# ---------------------------------------------------------------------
def test_tuned_beats_default_on_resnet50_shaped_payload():
    """The ISSUE acceptance shape: ~100 MB fp32 payload at a bench-like
    step time — the tuned plan's modeled eff@256 must be >= the 4 MiB
    default's under the same stated model."""
    # resnet50-ish leaf profile: many small BN/bias leaves + a few
    # multi-MiB conv/fc leaves, layer order
    leaves = ([256, 1024, 4096] * 20
              + [1 * MIB, 2 * MIB, 4 * MIB // 2] * 20
              + [8 * MIB, 2 * MIB])
    tm = autotune.from_leaf_bytes(leaves, dtype="float32",
                                  step_time_s=32.0 / 1295.0)
    tuned = autotune.tune(tm, chips=256)
    assert tuned["score"]["beats_default"]
    assert tuned["score"]["eff"] >= tuned["score"]["default_eff"]
    # payload conserved through the repartition
    assert sum(tuned["bucket_bytes"]) == sum(leaves)
    # the plan file's fingerprint matches the model
    assert tuned["fingerprint"]["total_bytes"] == sum(leaves)


def test_projection_rides_autotune_model_kwargs():
    """scaling.simulate_bucketed_overlap defaults reproduce r6; the
    autotuner's kwargs change the answer in the documented direction."""
    from mxnet_tpu.parallel.scaling import simulate_bucketed_overlap

    bb = [4 * MIB] * 10
    base = simulate_bucketed_overlap(bb, 0.02, 256)
    assert base["coll_latency_s"] == 0.0 and base["readiness"] == "uniform"
    lat = simulate_bucketed_overlap(bb, 0.02, 256, coll_latency_s=1e-4)
    assert lat["t_comm_total_s"] > base["t_comm_total_s"]
    assert lat["exposed_s"] >= base["exposed_s"]
    # byte-weighted readiness: a tiny first bucket issues earlier than
    # uniform readiness would allow
    skew = [1024] + [8 * MIB] * 4
    u = simulate_bucketed_overlap(skew, 0.02, 256, readiness="uniform")
    b = simulate_bucketed_overlap(skew, 0.02, 256, readiness="bytes")
    assert b["exposed_s"] <= u["exposed_s"]


# ---------------------------------------------------------------------
# plan persistence + resolution precedence
# ---------------------------------------------------------------------
def _mini_plan(tmp_path, name="plan.json", **over):
    tm = autotune.TimingModel([(2 * MIB, "float32")] * 4, "bucket",
                              step_time_s=0.01)
    plan = autotune.tune(tm, chips=8)
    plan.update(over)
    path = str(tmp_path / name)
    autotune.save_plan(plan, path)
    return plan, path


def test_explicit_plan_env_beats_dir(tmp_path, monkeypatch):
    plan_a, path_a = _mini_plan(tmp_path, "a.json")
    d = tmp_path / "plans"
    d.mkdir()
    plan_b, path_b = _mini_plan(d, "b.json")
    monkeypatch.setenv("MXNET_AUTOTUNE_DIR", str(d))
    caps, src = autotune.resolve_caps(
        total_bytes=plan_b["fingerprint"]["total_bytes"])
    assert src == path_b
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", path_a)
    caps, src = autotune.resolve_caps(total_bytes=12345)
    assert src == path_a  # explicit wins, fingerprint notwithstanding


def test_explicit_plan_env_invalid_raises(monkeypatch, tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{\"format\": \"nope\"}")
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", str(bad))
    with pytest.raises(ValueError):
        autotune.resolve_caps(total_bytes=1)
    missing = tmp_path / "missing.json"
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", str(missing))
    with pytest.raises(OSError):
        autotune.resolve_caps(total_bytes=1)


def test_dir_skips_non_plans_and_matches_fingerprint(tmp_path,
                                                     monkeypatch):
    d = tmp_path / "plans"
    d.mkdir()
    (d / "junk.json").write_text("not json at all")
    (d / "other.json").write_text(json.dumps({"unrelated": True}))
    plan, path = _mini_plan(d, "real.json")
    monkeypatch.setenv("MXNET_AUTOTUNE_DIR", str(d))
    caps, src = autotune.resolve_caps(
        total_bytes=plan["fingerprint"]["total_bytes"])
    assert src == path and caps["cap_bytes"] == plan["cap_bytes"]
    caps, src = autotune.resolve_caps(total_bytes=1)
    assert caps is None and src is None


def test_plan_version_from_the_future_rejected(tmp_path):
    _plan, path = _mini_plan(tmp_path)
    with open(path) as f:
        payload = json.load(f)
    payload["version"] = 99
    with open(path, "w") as f:
        json.dump(payload, f)
    with pytest.raises(ValueError, match="newer"):
        autotune.load_plan(path)


def test_plan_with_tuning_applies_and_stamps(tmp_path, monkeypatch):
    """The hook dp.py consumes: tuned caps drive the partitioner and
    the tuning meta rides plan_meta into the artifact stamps."""
    entries = [("w%d" % i, (256,), "float32") for i in range(32)]  # 1 KiB
    plan, no_tuning = buckets.plan_with_tuning(entries)
    assert no_tuning is None
    tuned, path = _mini_plan(tmp_path, "t.json", cap_bytes=4096,
                             first_cap_bytes=1024,
                             last_cap_bytes=8192)
    monkeypatch.setenv("MXNET_AUTOTUNE_PLAN", path)
    plan, tuning = buckets.plan_with_tuning(entries)
    assert tuning is not None and tuning["plan_path"] == path
    assert plan[0].nbytes <= 1024
    seen = [k for b in plan for k in b.keys]
    assert sorted(seen) == sorted(e[0] for e in entries)
    meta = buckets.plan_meta(plan, tuning["cap_bytes"], tuning=tuning)
    assert meta["autotune"]["plan_path"] == path
    assert meta["cap_bytes"] == 4096
    # an explicit cap bypasses tuning entirely
    plan2, tuning2 = buckets.plan_with_tuning(entries, 2048)
    assert tuning2 is None
